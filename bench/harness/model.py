"""The system under test, built from a configuration file and a traffic mix:
the program's model config (from the configuration's family module,
``bench/families/<ref>.py``), the weights (made here, from the seed), and
the jitted train step the window drives.

Weights are the benchmark's own: one jitted call draws every leaf from the
seed, so the plain reference can draw the same ones without taking anything
the program made. The step is the program's: ``repro.train.step`` for full
training, or, for a mix that freezes the lower layers, the program's model
loss and AdamW applied to the trainable subtree (the program has no frozen-
parameter step of its own).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# optimizer settings of the program's build_train_step defaults; the plain
# reference states the same numbers on its own side
PEAK_LR, WARMUP, TOTAL_STEPS, GRAD_CLIP, WEIGHT_DECAY = 3e-4, 100, 10000, 1.0, 0.1
B1, B2, ADAM_EPS = 0.9, 0.95, 1e-8
STEP_NAME = "record_train_step"     # the jitted step's module in the trace


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, 64-bit seeds included."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 32)),
                              seed >> 32)


def param_shapes(cfg):
    from repro.models import build_model
    return build_model(cfg).param_shapes()


def make_init(shapes, fan_in):
    """``init(key) -> params``: norms 1, the embedding N(0, 0.02), every
    projection N(0, 1/fan_in(path, shape)), in the leaves' stored dtype."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def init(key):
        leaves = []
        for i, (path, sd) in enumerate(flat):
            p = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, i)
            core = sd.shape[1:] if "layers" in p else sd.shape
            if len(core) == 1:
                leaf = jnp.ones(sd.shape, sd.dtype)
            else:
                std = 0.02 if "embed" in p else fan_in(p, sd.shape) ** -0.5
                leaf = (std * jax.random.normal(k, sd.shape, jnp.float32)
                        ).astype(sd.dtype)
            leaves.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, leaves)
    return init


class System:
    """Weights, state and the jitted step of one cell.

    The checkpointed state is ``{"frozen": ..., "train": TrainState}``:
    frozen leaves are the same buffers from step to step, so the record
    pass finds them unchanged, as a frozen backbone is."""

    def __init__(self, family, config: dict, traffic: dict):
        from repro.models import build_model
        from repro.train.optimizer import (AdamWState, adamw,
                                           clip_by_global_norm)
        from repro.train.schedule import warmup_cosine
        from repro.train.state import TrainState
        self.cfg = cfg = family.program_config(config)
        self.trainable = trainable = traffic.get("trainable", "all")
        init_params = make_init(param_shapes(cfg), family.fan_in)
        model = build_model(cfg)
        sched = warmup_cosine(PEAK_LR, WARMUP, TOTAL_STEPS)
        opt_init, opt_update = adamw(sched, b1=B1, b2=B2, eps=ADAM_EPS,
                                     weight_decay=WEIGHT_DECAY,
                                     moment_dtype=cfg.moment_dtype)

        def init_state(key):
            frozen, train = family.split_trainable(init_params(key),
                                                   trainable)
            opt = opt_init(train)
            rng = jax.random.key_data(jax.random.fold_in(key, 1))
            return {"frozen": frozen,
                    "train": TrainState(train, opt.mu, opt.nu,
                                        jnp.zeros((), jnp.int32), rng)}

        def record_train_step(frozen, st, batch):
            def loss_fn(tp):
                return model.loss(family.merge_trainable(frozen, tp), batch)
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(st.params)
            grads, gnorm = clip_by_global_norm(grads, GRAD_CLIP)
            new_p, opt = opt_update(grads, AdamWState(st.mu, st.nu),
                                    st.params, st.step)
            return (TrainState(new_p, opt.mu, opt.nu, st.step + 1, st.rng),
                    {"loss": loss, "grad_norm": gnorm})

        self.init_state = jax.jit(init_state)
        self._jit_step = jax.jit(record_train_step)
        if trainable == "all":
            # full training is the program's own step, under the same name
            from repro.train.step import build_train_step
            _, program_step = build_train_step(
                cfg, peak_lr=PEAK_LR, warmup=WARMUP, total_steps=TOTAL_STEPS,
                grad_clip=GRAD_CLIP, weight_decay=WEIGHT_DECAY)

            def record_train_step(frozen, st, batch):   # noqa: F811
                return program_step(st, batch)
            self._jit_step = jax.jit(record_train_step)
        self.state_shapes = jax.eval_shape(self.init_state,
                                           jax.random.PRNGKey(0))

    def step(self, state, batch):
        """One training step of the window's call: ``(state, metrics)``."""
        st, m = self._jit_step(state["frozen"], state["train"], batch)
        return {"frozen": state["frozen"], "train": st}, m

    def lower_step(self, state_shapes, batch_shapes):
        return self._jit_step.lower(state_shapes["frozen"],
                                    state_shapes["train"], batch_shapes)
