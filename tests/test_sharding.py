"""Sharding resolver properties + serve/train step mesh lowering on a small
local mesh (8 fake devices, subprocess so the main process keeps 1 device)."""
import subprocess
import sys
import textwrap

import jax
import pytest

from proptest import given, st

from jax.sharding import PartitionSpec as P


def _mesh(shape=(2, 2), axes=("data", "model")):
    # an ABSTRACT mesh: the resolver only needs axis names/sizes
    return jax.sharding.AbstractMesh(shape, axes)


from repro.parallel.sharding import physical_spec  # noqa: E402


def test_divisibility_fallback():
    mesh = _mesh((2, 16), ("data", "model"))
    # kv_heads=8 does not divide 16 -> replicate that dim
    spec = physical_spec(("embed", "kv_heads", None), (64, 8, 64), mesh)
    assert spec == P(("data",), None, None) or spec == P("data", None, None)
    # heads=32 divides -> sharded
    spec = physical_spec(("embed", "heads", None), (64, 32, 64), mesh)
    assert spec[1] == "model"


def test_no_axis_reuse():
    mesh = _mesh((2, 2), ("data", "model"))
    spec = physical_spec(("heads", "mlp"), (4, 4), mesh)
    used = [s for s in spec if s is not None]
    flat = []
    for s in used:
        flat.extend(s if isinstance(s, tuple) else [s])
    assert len(flat) == len(set(flat))


def test_cache_seq_spreads_over_all_axes():
    mesh = _mesh((2, 16, 16), ("pod", "data", "model"))
    spec = physical_spec((None, "cache_seq", None, None),
                         (1, 4096, 8, 128), mesh)
    assert spec[1] == ("pod", "data", "model")


def test_batch_of_one_replicates():
    """long_500k decode: B=1 can't use 'data', so the cache sequence dim
    grabs BOTH free axes — all 256 chips still participate."""
    mesh = _mesh((16, 16), ("data", "model"))
    spec = physical_spec(("batch", "cache_seq", None), (1, 4096, 16), mesh)
    assert spec[0] is None
    assert spec[1] == ("data", "model")


@given(dims=st.lists(st.sampled_from([1, 2, 3, 8, 16, 17, 64, 4096]),
                     min_size=1, max_size=4),
       names=st.lists(st.sampled_from(["batch", "embed", "heads", "mlp",
                                       "cache_seq", "vocab", None]),
                      min_size=1, max_size=4))
def test_physical_spec_always_valid(dims, names):
    """Any (logical, shape) combination resolves to a spec that (a) divides
    every dim it shards and (b) never reuses a mesh axis."""
    n = min(len(dims), len(names))
    dims, names = dims[:n], names[:n]
    mesh = _mesh((2, 4, 4), ("pod", "data", "model"))
    sizes = dict(zip(("pod", "data", "model"), (2, 4, 4)))
    spec = physical_spec(names, dims, mesh)
    used = []
    for dim, entry in zip(dims, tuple(spec)):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        used.extend(axes)
        total = 1
        for a in axes:
            total *= sizes[a]
        assert dim % total == 0, (dims, names, spec)
    assert len(used) == len(set(used)), spec


def test_constrain_is_noop_without_mesh():
    import jax.numpy as jnp
    from repro.parallel import constrain
    x = jnp.ones((4, 4))
    assert constrain(x, ("batch", None)) is x


@pytest.mark.slow
def test_small_mesh_train_and_decode_lowering():
    """8 fake devices in a subprocess: florbench train_step + decode_step
    lower+compile with the same sharding machinery the dry-run uses."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import repro.configs as C
        from repro.launch.mesh import make_mesh
        from repro.launch.specs import (batch_shardings, cache_shardings,
                                        param_shardings, state_shardings)
        from repro.configs.base import ShapeSpec
        from repro.models import build_model
        from repro.parallel import use_mesh
        from repro.serve.step import build_decode_step
        from repro.train.step import build_train_step
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = C.get_smoke("granite-3-2b")
        mesh = make_mesh((2, 4), ("data", "model"))
        model = build_model(cfg)
        shape = ShapeSpec("t", "train", 64, 4)
        with mesh, use_mesh(mesh):
            init_state, train_step = build_train_step(cfg)
            st_shapes = jax.eval_shape(init_state, jax.random.PRNGKey(0))
            st_sh = state_shardings(cfg, mesh, st_shapes)
            b_sh, b_specs = batch_shardings(model, shape, mesh)
            rep = NamedSharding(mesh, P())
            c = jax.jit(train_step, in_shardings=(st_sh, b_sh),
                        out_shardings=(st_sh, rep)).lower(
                            st_shapes, b_specs).compile()
            assert c.cost_analysis() is not None
            dshape = ShapeSpec("d", "decode", 256, 8)
            p_sh, p_shapes = param_shardings(model, mesh, dtype=cfg.dtype)
            c_sh, c_specs = cache_shardings(model, dshape, mesh)
            b_sh, b_specs = batch_shardings(model, dshape, mesh)
            step = build_decode_step(cfg)
            c2 = jax.jit(step, in_shardings=(p_sh, c_sh, b_sh["tokens"], rep),
                         out_shardings=(rep, rep, c_sh)).lower(
                p_shapes, c_specs, b_specs["tokens"], b_specs["pos"]).compile()
        print("LOWERED_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    assert "LOWERED_OK" in out.stdout, out.stderr[-2000:]


@pytest.mark.slow
def test_divisibility_fallback_on_real_8dev_mesh():
    """Forced 8-device CPU mesh: physical_spec's divisibility fallback and
    respec's resharding rules hold on REAL devices — device_put under the
    resolved spec round-trips the exact bytes, and a recorded (2, 4) spec
    re-resolves on a (8,) mesh by dropping the absent axis."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.parallel.sharding import physical_spec, respec, spec_entries

        devs = jax.devices()
        assert len(devs) == 8, devs
        mesh = Mesh(np.array(devs).reshape(2, 4), ("data", "model"))

        # kv_heads=6 does not divide model=4 -> replicated dim; embed -> data
        spec = physical_spec(("embed", "kv_heads"), (16, 6), mesh)
        assert tuple(spec) == ("data", None), spec
        x = jnp.arange(16 * 6, dtype=jnp.float32).reshape(16, 6)
        xs = jax.device_put(x, NamedSharding(mesh, spec))
        assert np.array_equal(np.asarray(jax.device_get(xs)), np.asarray(x))

        # heads=8 divides model=4 -> sharded on real devices
        spec2 = physical_spec(("embed", "heads"), (16, 8), mesh)
        assert tuple(spec2) == ("data", "model"), spec2
        y = jax.device_put(jnp.full((16, 8), 1.5),
                           NamedSharding(mesh, spec2))
        assert len({d.id for d in y.devices()}) == 8

        # respec: recorded ("data","model") entries re-resolve on a 1-axis
        # replay mesh — "model" is absent so that dim replicates, and a
        # non-dividing dim falls back to its longest dividing prefix
        m8 = Mesh(np.array(devs).reshape(8), ("data",))
        r = respec(spec_entries(spec2), (16, 8), m8)
        assert tuple(r) == ("data", None), r
        r2 = respec(spec_entries(P(("data", "model"))), (12,), m8)
        assert tuple(r2) == (None,), r2   # 12 % 8 != 0 -> replicate
        print("FALLBACK_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    assert "FALLBACK_OK" in out.stdout, out.stderr[-2000:]


def test_serve_param_shardings_drop_fsdp():
    """serve_replicate_fsdp: serve-path params lose the 'embed' FSDP dim
    (weights-stationary decode) while train params keep it."""
    import repro.configs as C
    from repro.launch.specs import param_shardings
    from repro.models import build_model
    mesh = _mesh((4, 4), ("data", "model"))
    cfg = C.get_smoke("mixtral-8x7b").replace(d_model=64)
    model = build_model(cfg)
    train_sh, _ = param_shardings(model, mesh, dtype=cfg.dtype, serve=False)
    serve_sh, _ = param_shardings(model, mesh, dtype=cfg.dtype, serve=True)

    def uses_data(sh):
        found = []
        for s in jax.tree_util.tree_leaves(
                sh, is_leaf=lambda x: hasattr(x, "spec")):
            for e in s.spec:
                axes = e if isinstance(e, tuple) else (e,)
                if "data" in axes:
                    found.append(s)
        return found

    assert uses_data(train_sh)          # FSDP present in training layout
    assert not uses_data(serve_sh)      # fully weights-stationary at serve


def test_serve_param_shardings_respect_opt_out():
    import repro.configs as C
    from repro.launch.specs import param_shardings
    from repro.models import build_model
    mesh = _mesh((4, 4), ("data", "model"))
    cfg = C.get_smoke("mixtral-8x7b").replace(d_model=64,
                                              serve_replicate_fsdp=False)
    model = build_model(cfg)
    serve_sh, _ = param_shardings(model, mesh, dtype=cfg.dtype, serve=True)
    specs = [s.spec for s in jax.tree_util.tree_leaves(
        serve_sh, is_leaf=lambda x: hasattr(x, "spec"))]
    assert any(any(("data" in (e if isinstance(e, tuple) else (e,)))
                   for e in sp if e) for sp in specs)
