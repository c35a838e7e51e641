"""Operations and bytes the benchmarked work requires, from shapes alone.

Counts are what the algorithm needs, not what a program executes:
recomputation (remat) and padding are not counted, so a share of a peak
computed from them can only fall when work is wasted.  A matmul of
[m, k] @ [k, n] is 2·m·k·n operations.  Element-wise work (norms,
softmax, activations) is left out: it is under 1% of a step here.

``Dims`` holds the widths of a dense GQA decoder (Qwen2 and its kin).
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    """The widths the counts need (a dense, gated-FFN GQA decoder)."""

    layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    param_bytes: int = 2          # bf16 weights and activations


def attn_proj_flops(d: Dims) -> float:
    """Q, K, V and O projections, per token per layer."""
    q = d.n_heads * d.head_dim
    kv = d.n_kv_heads * d.head_dim
    return 2.0 * d.d_model * (q + 2 * kv) + 2.0 * q * d.d_model


def attn_core_flops(d: Dims, keys: float) -> float:
    """Scores and weighted values of one query over ``keys`` keys."""
    return 4.0 * d.n_heads * d.head_dim * keys


def ffn_flops(d: Dims, dp: int = 1) -> float:
    """Up, gate and down projections over the kept 1/dp of the hidden
    units, per token per layer (forward)."""
    return 6.0 * d.d_model * d.d_ff / dp


def head_flops(d: Dims) -> float:
    """The unembedding over the whole vocabulary, per token."""
    return 2.0 * d.d_model * d.vocab


def causal_keys(seq: int) -> float:
    """Mean number of keys a causal query attends to in a ``seq`` row."""
    return (seq + 1) / 2.0


def train_step_flops(d: Dims, tokens: int, seq: int, dp: int = 1) -> float:
    """Forward and backward of one training step (backward = 2× forward),
    the FFN at 1/dp, everything else dense, remat not counted."""
    per_layer = (attn_proj_flops(d) + attn_core_flops(d, causal_keys(seq))
                 + ffn_flops(d, dp))
    return 3.0 * tokens * (d.layers * per_layer + head_flops(d))


def train_ffn_kernel_work(d: Dims, tokens: int, dp: int) -> tuple:
    """(operations, bytes) of the kept FFN blocks in one training step:
    the three forward matmuls and their six backward ones, every layer.
    Bytes: each operand read once and each result written once."""
    kept = d.d_ff // dp
    b = d.param_bytes
    ops = 3.0 * tokens * d.layers * ffn_flops(d, dp)
    x, h, w = tokens * d.d_model * b, tokens * kept * b, d.d_model * kept * b
    # each of the 9 matmuls (3 forward; a data and a weight gradient for
    # each in the backward) reads an activation, a kept weight or gradient
    # block and a hidden slice, and writes one of them: x + h + w apiece
    return ops, float(d.layers * 9 * (x + h + w))
