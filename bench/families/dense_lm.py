"""How the benchmark builds the program's dense decoder from a configuration
file: its sizes, the program's ``ModelConfig``, the initializer's fan-in,
the frozen and trained halves of a fine-tune, and the FLOPs of a step.

A configuration names its model family by its ``reference`` key, ``<ref>``.
The harness then takes two modules by path, and nothing else that depends
on the model: ``bench/families/<ref>.py`` (this interface) and the plain
reference ``bench/references/<ref>.py``. A new family is these two files.

A family module gives:

* ``dims(config) -> dict``: the sizes the reference and ``step_flops``
  read;
* ``program_config(config)``: the program's ``ModelConfig``, with which the
  program builds the model the window trains;
* ``fan_in(path, shape) -> int``: a weight leaf's fan-in, by its
  ``jax.tree_util.keystr`` path and stored shape; the benchmark's
  initializer draws it from N(0, 1/fan_in);
* ``split_trainable(params, trainable) -> (frozen, train)`` and
  ``merge_trainable(frozen, train) -> params``, where ``trainable`` is the
  traffic mix's ``trainable`` value: ``"all"`` (``frozen`` is ``{}``) or a
  form of the family's own;
* ``step_flops(dims, batch, seq, trainable) -> float``: the model FLOPs one
  training step requires, from shapes alone;
* ``TRAFFIC_KEYS`` (optional): keys of a traffic mix that this family reads
  beyond those of the benchmark's generator.

The plain reference imports nothing of the program and gives
``first_steps(dims, opt, frozen, train, batches, block_rows,
precision="highest") -> {"losses", "grad_norms", "delta_norms"}``, with
``precision="fp8"`` as the control.

This family: the program's ``dense`` decoder with a tied embedding, from a
file with GPT-2's ``n_*`` keys or the Llama-style ``*_size`` keys.
``trainable`` may be ``{"top_layers": n}``: the embedding and the lower
layers freeze, the top ``n`` layers and the final norm train.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def dims(c: dict) -> dict:
    """Sizes of a dense decoder under either naming a configuration file
    uses (GPT-2's ``n_*`` keys or the Llama-style ``*_size`` keys)."""
    d = c.get("hidden_size", c.get("n_embd"))
    heads = c.get("num_attention_heads", c.get("n_head"))
    ff = c.get("intermediate_size", c.get("n_inner")) or 4 * d
    act = c.get("hidden_act", c.get("activation_function"))
    return {
        "d_model": d,
        "num_layers": c.get("num_hidden_layers", c.get("n_layer")),
        "num_heads": heads,
        "num_kv_heads": c.get("num_key_value_heads", heads),
        "head_dim": c.get("head_dim") or d // heads,
        "d_ff": ff,
        "vocab_size": c["vocab_size"],
        "gated": act in ("silu", "swiglu"),
        "rope_theta": float(c.get("rope_theta", 10000.0)),
        "norm_eps": float(c.get("rms_norm_eps", c.get("layer_norm_epsilon",
                                                      1e-5))),
        "tie": bool(c.get("tie_word_embeddings", True)),
    }


def program_config(c: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig
    d = dims(c)
    if not d["tie"]:
        raise SystemExit("bench: only tied-embedding dense decoders so far")
    return ModelConfig(
        name=c["name"], family="dense", num_layers=d["num_layers"],
        d_model=d["d_model"], num_heads=d["num_heads"],
        num_kv_heads=d["num_kv_heads"], d_ff=d["d_ff"],
        vocab_size=d["vocab_size"], head_dim=d["head_dim"],
        ffn_activation="swiglu" if d["gated"] else "gelu",
        rope_theta=d["rope_theta"], norm_eps=d["norm_eps"],
        tie_embeddings=True)


def fan_in(path: str, shape: tuple) -> int:
    s = shape[1:] if "layers" in path else shape     # drop the stacked axis
    if path.endswith(("['wq']", "['wk']", "['wv']")):
        return s[0]                                  # [d, heads, head_dim]
    return math.prod(s[:-1])


# ------------------------------------------------------------ trainable --
def _top_layers(trainable):
    return None if trainable == "all" else int(trainable["top_layers"])


def split_trainable(params, trainable):
    """(frozen, trainable) halves of a dense decoder's params: with
    ``"all"`` everything trains; with ``{"top_layers": n}`` the embedding
    and the lower layers freeze and the top ``n`` layers and the final norm
    train."""
    top_layers = _top_layers(trainable)
    if top_layers is None:
        return {}, params
    cut = lambda x: x[:-top_layers]                  # noqa: E731
    top = lambda x: x[-top_layers:]                  # noqa: E731
    frozen = {"embed": params["embed"],
              "layers": jax.tree_util.tree_map(cut, params["layers"])}
    train = {"ln_f": params["ln_f"],
             "layers": jax.tree_util.tree_map(top, params["layers"])}
    return frozen, train


def merge_trainable(frozen, train):
    if not frozen:
        return train
    cat = lambda a, b: jnp.concatenate([a, b])       # noqa: E731
    return {"embed": frozen["embed"], "ln_f": train["ln_f"],
            "layers": jax.tree_util.tree_map(cat, frozen["layers"],
                                             train["layers"])}


# ---------------------------------------------------------------- FLOPs --
def _layer_matmul_flops(d: dict) -> float:
    """Forward matmul FLOPs of one layer per token (projections + MLP)."""
    dm, h, kv, hd, ff = (d["d_model"], d["num_heads"], d["num_kv_heads"],
                         d["head_dim"], d["d_ff"])
    proj = dm * h * hd + 2 * dm * kv * hd + h * hd * dm
    mlp = dm * ff * (3 if d["gated"] else 2)
    return 2.0 * (proj + mlp)


def step_flops(d: dict, batch: int, seq: int, trainable) -> float:
    """Model FLOPs one training step requires: the forward over every layer
    and the logits, and the backward only where gradients are needed.
    Causal attention counts the keys each query attends, ``(seq + 1) / 2``
    on average; recomputation under remat is not counted.

    ``"all"`` is full training (backward = twice the forward, everywhere).
    With ``{"top_layers": n}`` only the top ``n`` layers and the final norm
    train: the backward runs through the logits (input gradient only, the
    tied embedding is frozen) and those layers, and the lowest of them needs
    no gradient for its input projections' input."""
    top_layers = _top_layers(trainable)
    L, h, hd, V = d["num_layers"], d["num_heads"], d["head_dim"], d["vocab_size"]
    tokens = batch * seq
    preds = batch * (seq - 1)                  # positions with a next token
    layer = _layer_matmul_flops(d) * tokens
    attn = 4.0 * h * hd * (seq + 1) / 2 * tokens      # QK^T and PV
    logits = 2.0 * d["d_model"] * V * preds
    fwd = L * (layer + attn) + logits
    if top_layers is None:
        return 3.0 * fwd
    qkv_input_grad = 2.0 * d["d_model"] * (h + 2 * d["num_kv_heads"]) * hd \
        * tokens
    bwd = logits + top_layers * 2.0 * (layer + attn) - qkv_input_grad
    return fwd + bwd
