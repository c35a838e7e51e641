"""Qwen2 for the benchmark: its sizes, its weights, and the program's view.

``make_weights`` draws a whole model from a seed in one jitted call, on the
device, in the types it is served in, laid out as the program's parameter
tree.  The plain reference (``reference/qwen2.py``) reads the same tree;
the weights are the benchmark's, never the program's.

Init (the configuration's ``assumed``): every matrix and bias normal with
the published ``initializer_range``; norm scales 1 + N(0, 0.1²), so a
path that dropped a scale would show; embeddings tied.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import flops as F

NORM_SPREAD = 0.1


def dims(conf: dict) -> F.Dims:
    """The widths ``flops`` counts with, from a Qwen2 ``config.json``."""
    h = conf["num_attention_heads"]
    return F.Dims(layers=conf["num_hidden_layers"],
                  d_model=conf["hidden_size"], n_heads=h,
                  n_kv_heads=conf["num_key_value_heads"],
                  head_dim=conf["hidden_size"] // h,
                  d_ff=conf["intermediate_size"],
                  vocab=conf["vocab_size"])


def train_step_flops(conf: dict, tokens: int, seq_len: int,
                     dp: int = 1) -> float:
    """Operations one training step requires, the FFN at 1/dp."""
    return F.train_step_flops(dims(conf), tokens, seq_len, dp)


def tiny(conf: dict) -> dict:
    """``conf`` with every width shrunk, at a size a CPU test can hold."""
    c = dict(conf, hidden_size=64, intermediate_size=256,
             num_attention_heads=4, num_key_value_heads=2,
             num_hidden_layers=2, vocab_size=512,
             initializer_range=0.2)   # so the layers, not the tied
                                      # embedding's echo, set the logits
    c["program"] = {"pattern_nb": 4}
    return c


def dtype(conf: dict):
    """The type the weights are served in."""
    return jnp.dtype(conf["torch_dtype"])


def shapes(conf: dict) -> dict:
    """name → (shape, type) of every weight, in the program's layout:
    layers stacked on a leading axis, norm scales in float32."""
    d = dims(conf)
    L, m, H, KH, hd, f = (d.layers, d.d_model, d.n_heads, d.n_kv_heads,
                          d.head_dim, d.d_ff)
    w, s = dtype(conf), jnp.float32
    return {
        "embed.tok": ((d.vocab, m), w),
        "attn.wq": ((L, m, H, hd), w), "attn.wk": ((L, m, KH, hd), w),
        "attn.wv": ((L, m, KH, hd), w), "attn.wo": ((L, H, hd, m), w),
        "attn.bq": ((L, H, hd), w), "attn.bk": ((L, KH, hd), w),
        "attn.bv": ((L, KH, hd), w),
        "ffn.w_up": ((L, m, f), w), "ffn.w_gate": ((L, m, f), w),
        "ffn.w_down": ((L, f, m), w),
        "norm1.scale": ((L, m), s), "norm2.scale": ((L, m), s),
        "final_norm.scale": ((m,), s),
    }


def nest(flat: dict) -> dict:
    """Flat ``name → array`` to the program's parameter tree."""
    layer: dict = {}
    for name, arr in flat.items():
        group, leaf = name.split(".")
        if group not in ("embed", "final_norm"):
            layer.setdefault(group, {})[leaf] = arr
    return {"embed": {"tok": flat["embed.tok"]}, "stacks": [layer],
            "final_norm": {"scale": flat["final_norm.scale"]}}


def make_weights(conf: dict, key) -> dict:
    """All weights from ``key``, in one jitted call (program layout)."""
    spec = shapes(conf)
    std = conf["initializer_range"]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(spec))
        flat = {}
        for k, (name, (shape, dt)) in zip(keys, sorted(spec.items())):
            z = jax.random.normal(k, shape, jnp.float32)
            if name.endswith("scale"):
                flat[name] = (1.0 + NORM_SPREAD * z).astype(dt)
            else:
                flat[name] = (std * z).astype(dt)
        return nest(flat)

    return make(key)


def program_config(conf: dict, program: dict):
    """The program's ``ModelConfig`` for this configuration.  ``program``
    holds the settings the program needs beyond the published ones (its
    pattern blocking), from the configuration file."""
    from repro.models.transformer import ModelConfig
    d = dims(conf)
    if conf.get("hidden_act") != "silu" or not conf["tie_word_embeddings"]:
        raise ValueError("this adapter covers the published Qwen2 block: "
                         "silu gate, tied embeddings")
    return ModelConfig(
        name=conf["name"], family="dense", n_layers=d.layers,
        d_model=d.d_model, n_heads=d.n_heads, n_kv_heads=d.n_kv_heads,
        head_dim=d.head_dim, d_ff=d.d_ff, vocab=d.vocab, qkv_bias=True,
        rope_theta=float(conf["rope_theta"]), tie_embeddings=True,
        norm_eps=float(conf["rms_norm_eps"]), dtype=conf["torch_dtype"],
        pattern_nb=int(program["pattern_nb"]))


def plan_blocks(cfg) -> tuple:
    """(nb, block): the FFN's hidden units in ``pattern_nb`` blocks."""
    return cfg.pattern_nb, cfg.d_ff // cfg.pattern_nb


def check_layout(cfg, weights) -> None:
    """The weights must be exactly the program's parameter tree: same
    leaves, shapes and types as ``init_lm`` declares."""
    from repro.models.transformer import init_lm
    want = jax.eval_shape(lambda: init_lm(cfg)[0])
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       weights)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"benchmark weights do not match the program's "
                         f"parameter tree:\n{want}\n{got}")
