"""How the benchmark builds the program's MLA and mixture-of-experts decoder
(the DeepSeek-V3 layout, ``model_type`` deepseek_v3) from a configuration
file: the interface of ``bench/families/dense_lm.py``'s docstring.

The file gives the published keys, with ``n_routed_experts`` the experts
this chip holds, ``n_routed_experts_in_layer`` the router's width and
``held_expert_offset`` the first held expert. The program builds the
``moe`` family with ``mla``: leading dense layers (``first_k_dense_replace``),
then layers whose router scores all experts with a sigmoid, selects the
top ``num_experts_per_tok`` on the score plus a per-expert bias, and gates
the held experts' outputs by the selected scores, normalised and scaled by
``routed_scaling_factor``, beside ``n_shared_experts`` shared experts.

``trainable`` may be ``{"experts_per_layer": n}`` (Expert-Specialised
Fine-Tuning): the first ``n`` held experts of each MoE layer train, and
everything else (attention, the shared experts, the router and its bias,
the dense layers, the norms, the embedding and the head) is frozen.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def dims(c: dict) -> dict:
    return {
        "d_model": c["hidden_size"],
        "num_layers": c["num_hidden_layers"],
        "dense_layers": c["first_k_dense_replace"],
        "num_heads": c["num_attention_heads"],
        "q_lora_rank": c["q_lora_rank"],
        "kv_lora_rank": c["kv_lora_rank"],
        "qk_nope_head_dim": c["qk_nope_head_dim"],
        "qk_rope_head_dim": c["qk_rope_head_dim"],
        "v_head_dim": c["v_head_dim"],
        "d_ff": c["intermediate_size"],
        "d_ff_expert": c["moe_intermediate_size"],
        "experts": c["n_routed_experts_in_layer"],
        "held": c["n_routed_experts"],
        "offset": c["held_expert_offset"],
        "top_k": c["num_experts_per_tok"],
        "shared": c["n_shared_experts"],
        "routed_scaling": float(c["routed_scaling_factor"]),
        "vocab_size": c["vocab_size"],
        "rope_theta": float(c["rope_theta"]),
        "norm_eps": float(c["rms_norm_eps"]),
    }


# what the program's layer computes; a file that asks for anything else is
# refused rather than run as something it is not
_REQUIRED = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
             "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
             "moe_layer_freq": 1, "hidden_act": "silu",
             "tie_word_embeddings": False, "attention_bias": False}


def program_config(c: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig
    wrong = {k: c.get(k) for k, v in _REQUIRED.items() if c.get(k) != v}
    if wrong or c["num_key_value_heads"] != c["num_attention_heads"]:
        raise SystemExit(f"bench: {c['name']}: the program's MLA-MoE layer "
                         f"does not compute {wrong or 'grouped kv heads'}")
    d = dims(c)
    return ModelConfig(
        name=c["name"], family="moe", num_layers=d["num_layers"],
        d_model=d["d_model"], num_heads=d["num_heads"],
        num_kv_heads=d["num_heads"], d_ff=d["d_ff"],
        vocab_size=d["vocab_size"], ffn_activation="swiglu",
        rope_theta=d["rope_theta"], norm_eps=d["norm_eps"],
        tie_embeddings=False,
        moe=MoEConfig(num_experts=d["experts"], top_k=d["top_k"],
                      d_ff_expert=d["d_ff_expert"],
                      num_shared_experts=d["shared"],
                      first_dense_layers=d["dense_layers"], router="sigmoid",
                      selection_bias=True,
                      routed_scaling=d["routed_scaling"],
                      expert_offset=d["offset"], experts_held=d["held"]),
        mla=MLAConfig(q_lora_rank=d["q_lora_rank"],
                      kv_lora_rank=d["kv_lora_rank"],
                      qk_nope_head_dim=d["qk_nope_head_dim"],
                      qk_rope_head_dim=d["qk_rope_head_dim"],
                      v_head_dim=d["v_head_dim"]))


def fan_in(path: str, shape: tuple) -> int:
    s = shape[1:] if "layers" in path else shape     # drop the stacked axis
    if "['experts']" in path:
        return s[1]                       # [E, d, f] -> d; wo [E, f, d] -> f
    if path.endswith(("['w_q']", "['w_uq']", "['w_uk']", "['w_uv']")):
        return s[0]                       # [in, heads, head_dim]
    return math.prod(s[:-1])


# ------------------------------------------------------------ trainable --
def _trained_experts(trainable):
    return None if trainable == "all" else int(trainable["experts_per_layer"])


def split_trainable(params, trainable):
    """(frozen, trainable) halves: with ``"all"`` everything trains; with
    ``{"experts_per_layer": n}`` the first ``n`` held experts of each MoE
    layer train (their leaves cut along the expert axis) and the rest is
    frozen."""
    n = _trained_experts(trainable)
    if n is None:
        return {}, params
    experts = params["layers"]["moe"]["experts"]
    moe = dict(params["layers"]["moe"],
               experts=jax.tree_util.tree_map(lambda x: x[:, n:], experts))
    frozen = dict(params, layers=dict(params["layers"], moe=moe))
    train = {"experts": jax.tree_util.tree_map(lambda x: x[:, :n], experts)}
    return frozen, train


def merge_trainable(frozen, train):
    if not frozen:
        return train
    moe = frozen["layers"]["moe"]
    experts = jax.tree_util.tree_map(
        lambda t, f: jnp.concatenate([t, f], axis=1), train["experts"],
        moe["experts"])
    return dict(frozen, layers=dict(frozen["layers"],
                                    moe=dict(moe, experts=experts)))


# ---------------------------------------------------------------- FLOPs --
def _attn_proj(d: dict) -> float:
    """Forward FLOPs per token of one layer's attention projections."""
    dm, h = d["d_model"], d["num_heads"]
    qk = d["qk_nope_head_dim"] + d["qk_rope_head_dim"]
    r, q = d["kv_lora_rank"], d["q_lora_rank"]
    q_proj = dm * h * qk if q is None else dm * q + q * h * qk
    kv = dm * (r + d["qk_rope_head_dim"]) + r * h * (d["qk_nope_head_dim"]
                                                     + d["v_head_dim"])
    return 2.0 * (q_proj + kv + h * d["v_head_dim"] * dm)


def _attn_core(d: dict, seq: int) -> float:
    """Forward FLOPs per token of causal attention (QK^T and PV) at
    ``(seq + 1) / 2`` keys on average."""
    qk = d["qk_nope_head_dim"] + d["qk_rope_head_dim"]
    return 2.0 * d["num_heads"] * (qk + d["v_head_dim"]) * (seq + 1) / 2


def _expert(d: dict) -> float:
    """Forward FLOPs of one routed row through one expert (SwiGLU)."""
    return 2.0 * 3 * d["d_model"] * d["d_ff_expert"]


def _rows_per_token(d: dict) -> float:
    """Routed rows a token sends to the held experts, expected under
    uniform routing: top_k x held / experts."""
    return d["top_k"] * d["held"] / d["experts"]


def expert_flops(d: dict, batch: int, seq: int, trainable) -> float:
    """FLOPs one training step's expert products require (the grouped
    products of the held experts; the shared experts are not among them):
    the forward of every MoE layer at the expected routed rows; input
    gradients in every MoE layer above the lowest one, and in the lowest
    only the down projection's, on the trained experts' rows; weight
    gradients of the trained experts only. ``"all"``: twice the forward
    for the backward, everywhere."""
    n = _trained_experts(trainable)
    tokens = batch * seq
    moe_layers = d["num_layers"] - d["dense_layers"]
    fwd = moe_layers * _rows_per_token(d) * _expert(d) * tokens
    if n is None:
        return 3.0 * fwd
    trained_rows = d["top_k"] * n / d["experts"] * tokens
    down = 2.0 * d["d_model"] * d["d_ff_expert"]
    input_grads = (moe_layers - 1) / moe_layers * fwd + trained_rows * down
    weight_grads = moe_layers * trained_rows * _expert(d)
    return fwd + input_grads + weight_grads


def step_flops(d: dict, batch: int, seq: int, trainable) -> float:
    """Model FLOPs one training step requires: the forward of every layer
    and the logits, and the backward only where gradients are needed.
    Routed experts count at the expected rows (``_rows_per_token``);
    recomputation under remat is not counted.

    ``"all"``: backward = twice the forward, everywhere. With
    ``{"experts_per_layer": n}`` the backward runs through the logits
    (input gradient only, the head is frozen) and every MoE layer above
    the lowest (input gradients of every product, both operands of the
    attention core), and in the lowest only through its trained experts
    (``expert_flops``); no weight gradient but the trained experts'."""
    n = _trained_experts(trainable)
    tokens = batch * seq
    preds = batch * (seq - 1)
    moe_layers = d["num_layers"] - d["dense_layers"]
    proj, core = _attn_proj(d), _attn_core(d, seq)
    router = 2.0 * d["d_model"] * d["experts"]
    shared = d["shared"] * _expert(d)
    dense_mlp = 2.0 * 3 * d["d_model"] * d["d_ff"]
    logits = 2.0 * d["d_model"] * d["vocab_size"] * preds
    moe_other = (proj + core + router + shared) * tokens
    fwd = (d["dense_layers"] * (proj + core + dense_mlp) * tokens
           + moe_layers * moe_other + logits
           + moe_layers * _rows_per_token(d) * _expert(d) * tokens)
    if n is None:
        return 3.0 * fwd
    upper = (moe_layers - 1) * (moe_other + core * tokens)
    experts = expert_flops(d, batch, seq, trainable) \
        - moe_layers * _rows_per_token(d) * _expert(d) * tokens
    return fwd + logits + upper + experts
