"""Wall-clock instrumentation: profiler spans with optional second counters,
and the EMA the Flor adaptive-checkpointing controller smooths with."""
from __future__ import annotations

import time
from contextlib import contextmanager

import jax


@contextmanager
def span(name: str, counters: dict | None = None, key: str | None = None,
         **meta):
    """A named host span on the profiler's clock: while a ``jax.profiler``
    trace runs it lands in the same ``.xplane.pb`` as the device planes;
    otherwise it costs an inactive TraceMe check. ``meta`` rides on the
    event (``None`` values are left out). When ``counters`` is given, the
    span's elapsed ``perf_counter`` seconds are added into
    ``counters[key]``."""
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(
                name, **{k: v for k, v in meta.items() if v is not None}):
            yield
    finally:
        if counters is not None:
            counters[key] = counters.get(key, 0.0) \
                + time.perf_counter() - t0


class EMA:
    """Exponential moving average with bias correction (Flor uses EMAs of
    materialization/compute times so early noisy samples wash out)."""

    def __init__(self, beta: float = 0.7):
        self.beta = beta
        self._v = 0.0
        self._n = 0

    def update(self, x: float) -> float:
        self._v = self.beta * self._v + (1.0 - self.beta) * float(x)
        self._n += 1
        return self.value

    @property
    def value(self) -> float:
        if self._n == 0:
            return 0.0
        return self._v / (1.0 - self.beta ** self._n)

    @property
    def count(self) -> int:
        return self._n
