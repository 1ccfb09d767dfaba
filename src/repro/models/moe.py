"""Mixture-of-Experts: dropless dispatch over the share of experts a model
holds, with explicit expert parallelism.

A layer holds the routed experts ``[expert_offset, expert_offset + held)``
of ``num_experts`` (``MoEConfig``); the router keeps all ``num_experts``
outputs and its ``top_k``, and the layer computes the part of the result
that its own experts give. Nothing is dropped: every (token, choice) pair
routed to a held expert is computed.

Dispatch: the pairs of each held expert are laid out in one group of rows,
each group starting on a row tile and padded to its end with zero rows
(``_layout``); the expert products run over those rows as grouped matrix
products (``kernels.ops.expert_matmul``: Pallas ``expert_gmm`` and
``expert_tgmm`` on TPU), so compute follows the routed rows. Moving rows in
and out of the layout is a gather both ways, forward and backward
(``_dispatch``, ``_combine``); there is no scatter of activations.

Router: ``softmax`` (top-k of the probabilities, renormalised) or
``sigmoid`` (DeepSeek-V3): experts are selected on the sigmoid score plus
an optional per-expert ``router_bias`` that takes no gradient, and the gate
is the selected scores, normalised; either gate is then multiplied by
``routed_scaling``.

With a mesh the layer runs INSIDE a shard_map so dispatch stays local:

  * tokens are sharded over ("pod","data") and replicated over "model";
  * EP mode (held % model_axis == 0): each model shard owns held/ms experts
    and psums its partial combine over "model". No all-to-all:
    replicated-dispatch EP.
  * TP mode (small expert counts, e.g. Mixtral's 8 on a 16-way axis): every
    shard holds all experts but only d_ff/ms of each; partial outputs psum.

Counters (the step's metrics): ``moe_rows``, the pairs routed to held
experts, and ``moe_load_max``, the largest held expert's rows over the held
mean.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ops import expert_matmul
from repro.models.layers import activation, dense_spec, is_gated
from repro.models.params import ParamSpec
from repro.parallel import current_mesh

MAX_ROW_TILE = 256


def moe_spec(cfg):
    mo = cfg.moe
    d, E, f = cfg.d_model, mo.num_experts, mo.d_ff_expert
    H = mo.held()
    spec = {
        "router": dense_spec((d, E), ("embed", None)),
        "experts": {
            "wi": dense_spec((H, d, f), ("expert", "embed", "mlp"), fan_in=d),
            "wo": dense_spec((H, f, d), ("expert", "mlp", "embed"), fan_in=f),
        },
    }
    if mo.selection_bias:
        spec["router_bias"] = ParamSpec((E,), (None,), init="zeros")
    if is_gated(cfg.ffn_activation):
        spec["experts"]["wg"] = dense_spec((H, d, f), ("expert", "embed", "mlp"),
                                           fan_in=d)
    if mo.num_shared_experts:
        fs = f * mo.num_shared_experts
        spec["shared"] = {
            "wi": dense_spec((d, fs), ("embed", "mlp")),
            "wo": dense_spec((fs, d), ("mlp", "embed"), fan_in=fs),
        }
        if is_gated(cfg.ffn_activation):
            spec["shared"]["wg"] = dense_spec((d, fs), ("embed", "mlp"))
    return spec


def _route(cfg, router_w, x_flat, bias=None):
    """Router logits (float32) -> (gates [T,k], expert ids [T,k], aux)."""
    mo = cfg.moe
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32),
                        router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if mo.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        pick = scores if bias is None else \
            scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
        _, ids = jax.lax.top_k(pick, mo.top_k)
        w = jnp.take_along_axis(scores, ids, axis=-1)
        probs = scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        w, ids = jax.lax.top_k(probs, mo.top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9) * mo.routed_scaling
    # load-balance aux loss (Switch): E * sum_e f_e * P_e
    E = logits.shape[-1]
    hot = jax.nn.one_hot(ids[:, 0], E, dtype=jnp.float32)
    aux = E * jnp.sum(hot.mean(0) * probs.mean(0))
    return w, ids, aux


def _row_tile(pairs: int, groups: int) -> int:
    """Rows per tile: the expected group size rounded up to a power of two,
    in [16, MAX_ROW_TILE]."""
    per = max(1, -(-pairs // groups))
    return int(min(MAX_ROW_TILE, max(16, 1 << (per - 1).bit_length())))


def _layout(local, G: int, tm: int):
    """Tile-aligned rows of the pairs whose local expert id is in [0, G).

    Returns (row_of_pair [P] (``M`` for a pair of no held expert),
    tile_group [M // tm], num_tiles, rows per expert [G], M). A pair's row
    keeps the pairs' order within its expert's group."""
    P = local.shape[0]
    held = (local >= 0) & (local < G)
    safe = jnp.clip(local, 0, G - 1)
    hot = (held[:, None] & (safe[:, None] == jnp.arange(G)[None, :])
           ).astype(jnp.int32)
    csum = jnp.cumsum(hot, axis=0)
    rows = csum[-1]
    rank = jnp.take_along_axis(csum, safe[:, None], axis=1)[:, 0] - 1
    tiles = (rows + tm - 1) // tm
    start = (jnp.cumsum(tiles) - tiles) * tm
    M = (-(-P // tm) + G) * tm                  # every pair, plus padding
    row = jnp.where(held, start[safe] + rank, M)
    tile_group = jnp.repeat(jnp.arange(G, dtype=jnp.int32), tiles,
                            total_repeat_length=M // tm)
    return row, tile_group, tiles.sum(), rows, M


def _gather_sum(x, idx, w=None):
    """float32 ``sum_j w[:, j] * x[idx[:, j]]`` (w omitted: 1), with zero
    rows where ``idx`` is out of range: one gather per column, which XLA
    fuses with the sum, so no [T, k, d] array is made."""
    out = 0.0
    for j in range(idx.shape[1]):
        rows = jnp.take(x, idx[:, j], axis=0, mode="fill",
                        fill_value=0).astype(jnp.float32)
        out = out + (rows if w is None else w[:, j:j + 1] * rows)
    return out


@jax.custom_vjp
def _dispatch(x, tok_of_row, row):
    """Token rows into the layout: ``x[tok_of_row]``, zero rows where it is
    out of range. ``row`` [T, k] is each pair's row (out of range for a
    pair of no held expert), so the backward gathers too."""
    return jnp.take(x, tok_of_row, axis=0, mode="fill", fill_value=0)


def _dispatch_fwd(x, tok_of_row, row):
    return _dispatch(x, tok_of_row, row), row


def _dispatch_bwd(row, g):
    return _gather_sum(g, row).astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(y, w, row, pair_of_row):
    """``out[t] = sum_j w[t, j] * y[row[t, j]]`` in float32: each token's
    gated rows of the layout. ``pair_of_row`` [M] names each row's pair
    ``t * k + j`` (out of range for padding rows)."""
    return _gather_sum(y, row, w)


def _combine_fwd(y, w, row, pair_of_row):
    return _combine(y, w, row, pair_of_row), (y, w, row, pair_of_row)


def _combine_bwd(res, g):
    y, w, row, pair_of_row = res
    k = row.shape[1]
    gate = jnp.take(w.reshape(-1), pair_of_row, mode="fill", fill_value=0)
    dy = jnp.take(g.astype(y.dtype), pair_of_row // k, axis=0, mode="fill",
                  fill_value=0) * gate.astype(y.dtype)[:, None]
    dw = jnp.stack([jnp.sum(jnp.take(y, row[:, j], axis=0, mode="fill",
                                     fill_value=0).astype(jnp.float32) * g,
                            axis=-1) for j in range(k)], axis=1)
    return dy, dw.astype(w.dtype), None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _expert_ffn(cfg, pe, x_pad, tile_group, num_tiles, tm):
    """Rows of the layout through their experts' (possibly ff-sliced) MLP."""
    act = activation(cfg.ffn_activation)
    dt = x_pad.dtype

    def mm(a, w):
        return expert_matmul(a, w.astype(dt), tile_group, num_tiles, tm)
    h = mm(x_pad, pe["wi"])
    if "wg" in pe:
        h = act(mm(x_pad, pe["wg"])) * h
    else:
        h = act(h)
    return mm(h, pe["wo"])


def _moe_local(cfg, p, x_flat, e_offset: int, e_local: int):
    """Dispatch/compute/combine over local experts [e_offset,
    e_offset+e_local). Returns (partial_out [T,d], aux, rows per local
    expert [e_local])."""
    T = x_flat.shape[0]
    k = cfg.moe.top_k
    w, ids, aux = _route(cfg, p["router"], x_flat, p.get("router_bias"))
    P = T * k
    tm = _row_tile(P, e_local)
    row, tile_group, num_tiles, rows, M = _layout(
        ids.reshape(-1) - e_offset, e_local, tm)
    pair_of_row = jnp.full((M,), P, jnp.int32).at[row].set(
        jnp.arange(P, dtype=jnp.int32), mode="drop")
    row = row.reshape(T, k)
    x_pad = _dispatch(x_flat, pair_of_row // k, row)
    y_pad = _expert_ffn(cfg, p["experts"], x_pad, tile_group, num_tiles, tm)
    out = _combine(y_pad, w, row, pair_of_row)
    return out.astype(x_flat.dtype), aux, rows


def moe_apply(cfg, p, x):
    """x [B,S,d] -> (y [B,S,d], metrics dict). Shared experts added outside
    the shard_map (plain GSPMD tensor-parallel MLP)."""
    from jax.sharding import PartitionSpec as P

    mo = cfg.moe
    B, S, d = x.shape
    held = mo.held()
    mesh = current_mesh()
    x_flat = x.reshape(B * S, d)
    route = {k: p[k] for k in ("router", "router_bias") if k in p}

    if mesh is not None and "model" in mesh.shape:
        from repro.parallel.sharding import physical_spec

        ms = mesh.shape["model"]
        dp = cfg.dense_layout == "dp"
        # divisibility-aware token sharding (decode with B*S==1 replicates)
        tok_spec = physical_spec(("batch_dp3" if dp else "batch", None),
                                 (B * S, d), mesh)
        tok_axes = ()
        if tok_spec and tok_spec[0] is not None:
            tok_axes = (tok_spec[0] if isinstance(tok_spec[0], tuple)
                        else (tok_spec[0],))
        ep = held % ms == 0
        e_local = held // ms if ep else held
        if ep:
            expert_specs = jax.tree_util.tree_map(
                lambda _: P("model", None, None), p["experts"])
        else:
            expert_specs = jax.tree_util.tree_map(
                lambda _: P(None, None, "model"), p["experts"])
            # wo is [E, f, d]: slice f (dim 1), not d
            expert_specs["wo"] = P(None, "model", None)
        route_specs = jax.tree_util.tree_map(
            lambda a: P(*([None] * a.ndim)), route)
        in_specs = (tok_spec, route_specs, expert_specs)
        out_specs = (tok_spec, P(), P())

        model_in_tok = dp and tok_axes and "model" in tok_axes

        def shard_fn(xl, route_l, experts_l):
            idx = jax.lax.axis_index("model")
            off = mo.expert_offset + (idx * e_local if ep else 0)
            pl = {**route_l, "experts": experts_l}
            if model_in_tok:
                # dp layout: tokens are sharded over "model" too — gather
                # them for dispatch, reduce-scatter the combined outputs
                xg = jax.lax.all_gather(xl, "model", axis=0, tiled=True)
                out, aux, rows = _moe_local(cfg, pl, xg, off, e_local)
                out = jax.lax.psum_scatter(out, "model", scatter_dimension=0,
                                           tiled=True)
            else:
                out, aux, rows = _moe_local(cfg, pl, xl, off, e_local)
                out = jax.lax.psum(out, "model")
            if ep:
                rows = jax.lax.all_gather(rows, "model", axis=0, tiled=True)
            # metrics differ across token shards: average the loss term and
            # add up the rows so the replicated out_specs is semantically true
            mean_axes = tuple(a for a in tok_axes if a != "model") or None
            if mean_axes:
                aux = jax.lax.pmean(aux, mean_axes)
                rows = jax.lax.psum(rows, mean_axes)
            return out, aux, rows

        y_flat, aux, rows = jax.shard_map(
            shard_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False)(x_flat, route, p["experts"])
    else:
        y_flat, aux, rows = _moe_local(cfg, p, x_flat, mo.expert_offset, held)

    y = y_flat.reshape(B, S, d)
    if "shared" in p:
        from repro.models.layers import mlp_apply
        y = y + mlp_apply(cfg, p["shared"], x)
    rows = rows.astype(jnp.float32)
    metrics = {"moe_aux": aux, "moe_rows": rows.sum(),
               "moe_load_max": rows.max() * held
               / jnp.maximum(rows.sum(), 1.0)}
    return y, metrics
