"""Model building blocks (pure-pytree params, logical-axis annotated).

Everything is plain JAX: params are nested dicts of arrays; each init_*
returns ``(params, axes)`` where ``axes`` mirrors params with logical-axis
tuples (see parallel/sharding.py).  No flax dependency.

Approximate Random Dropout integration: FFN/MoE/SSM blocks accept a pattern
(a ``core.plan.BoundPlan``, or the legacy ``PatternArgs`` shim; dp and bias
static) and compute only the kept 1/dp of the hidden dimension, dispatching
the pattern math through the family/backend registries in ``core.plan``
(DESIGN.md §8).  The default "slice" backend uses *strided block slices* —
TP-friendly (each model shard slices locally, no gather) and shape-static
per (dp, bias) executable bucket (DESIGN.md §2).  Every backend — pallas
included, via the custom-VJP kernels in kernels/autodiff.py — is
differentiable, so the same blocks serve training and serving unchanged.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Literal, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plan as plan_mod
from repro.core.plan import BoundPlan, _slice_blocks
from repro.kernels import attention as attn_kernel
from repro.parallel.sharding import constrain, current_mesh

Init = jax.nn.initializers


# --------------------------------------------------------------------------
# Pattern plumbing
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PatternArgs:
    """DEPRECATED shim over ``repro.core.plan.BoundPlan``.

    The canonical pattern object is ``BoundPlan`` (constructed through a
    ``DropoutPlan``); every layer below accepts either and normalizes via
    ``plan.as_bound``.  This dataclass keeps the historical field names
    alive for legacy call sites and forwards all semantics — including
    validation: an unregistered ``impl``/``kind``, a ``bias >= dp`` or a
    block count not divisible by ``dp`` raise ``ValueError`` at
    construction (previously a typo like ``impl="palas"`` silently fell
    through to the slice path).

    ``dp`` — period (1 = no dropout); ``bias`` — base block offset;
    ``kind`` — pattern family name ("rdp" | "tdp" | ...); ``nb`` — number
    of pattern blocks in the dropped dim; ``impl`` — execution backend
    ("slice" | "gather" | "pallas").
    """
    dp: int = 1
    bias: int = 0
    kind: str = "rdp"
    nb: int = 128
    impl: Literal["slice", "gather", "pallas"] = "slice"

    def __post_init__(self):
        self.bound  # constructing the BoundPlan runs all validation

    @property
    def bound(self) -> BoundPlan:
        """The canonical BoundPlan this shim forwards to."""
        return BoundPlan(family=self.kind, dp=self.dp, bias=self.bias,
                         nb=self.nb, backend=self.impl)

    @property
    def active(self) -> bool:
        return self.dp > 1

    def layer_bias(self, layer: int) -> int:
        """Fold the layer index into the bias for cross-layer diversity
        (forwards to the plan's default "layer_offset" bias policy)."""
        return self.bound.layer_bias(layer)


NO_PATTERN = PatternArgs()


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def init_rmsnorm(dim: int):
    return {"scale": jnp.ones((dim,), jnp.float32)}, {"scale": ("embed",)}


def rms_norm(params, x, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return (x * params["scale"]).astype(dt)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_cache(positions: jax.Array, dim: int, theta: float = 1e4):
    """positions: [...]; returns cos/sin of shape [..., dim/2]."""
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array):
    """x: [B, S, H, D]; cos/sin: [B, S, D/2] (broadcast over heads)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c, s = cos[..., None, :], sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1).astype(x.dtype)


# --------------------------------------------------------------------------
# Attention (GQA, causal, optional sliding window) — blockwise online softmax
# --------------------------------------------------------------------------

def init_attention(d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   qkv_bias: bool = False, dtype=jnp.bfloat16):
    k = 1.0 / math.sqrt(d_model)
    def w(shape):  # deterministic-zero init placeholder; real init at model level
        return (k, shape)
    params = {
        "wq": jnp.zeros((d_model, n_heads, head_dim), dtype),
        "wk": jnp.zeros((d_model, n_kv, head_dim), dtype),
        "wv": jnp.zeros((d_model, n_kv, head_dim), dtype),
        "wo": jnp.zeros((n_heads, head_dim, d_model), dtype),
    }
    axes = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if qkv_bias:
        params |= {"bq": jnp.zeros((n_heads, head_dim), dtype),
                   "bk": jnp.zeros((n_kv, head_dim), dtype),
                   "bv": jnp.zeros((n_kv, head_dim), dtype)}
        axes |= {"bq": ("heads", "head_dim"), "bk": ("kv_heads", "head_dim"),
                 "bv": ("kv_heads", "head_dim")}
    return params, axes


def blockwise_attention(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None, chunk: int = 1024,
                        q_offset: int = 0) -> jax.Array:
    """Attention, GQA: q [B, Sq, H, D]; k/v [B, Sk, KH, D] with H = G·KH.

    ``q_offset``: absolute position of q[0] (for decode/prefill
    continuation).  Lowered for a TPU, whole-sequence causal self-attention
    over unpartitioned bf16 operands with 128-aligned heads and length runs
    the causal flash kernel (``kernels/attention.py``); everything else —
    windows, offsets, D ≠ Dv, Sq ≠ Sk, a mesh of more than one device, and
    every other platform — runs ``scan_attention``.
    """
    if not _kernel_applies(q, k, v, causal=causal, window=window,
                           q_offset=q_offset):
        return scan_attention(q, k, v, causal=causal, window=window,
                              chunk=chunk, q_offset=q_offset)
    return jax.lax.platform_dependent(
        q, k, v,
        tpu=lambda q, k, v: attn_kernel.causal_attention(q, k, v, False),
        default=functools.partial(scan_attention, causal=causal,
                                  chunk=chunk))


def _kernel_applies(q, k, v, *, causal, window, q_offset) -> bool:
    """The shapes and partitioning the flash kernel serves (the platform
    is decided at lowering, by ``platform_dependent``)."""
    _, sq, _, d = q.shape
    sk = k.shape[1]
    mesh = current_mesh()
    return (causal and window is None and isinstance(q_offset, int)
            and q_offset == 0 and sq == sk and sq % attn_kernel.LANE == 0
            and v.shape[-1] == d and d % attn_kernel.LANE == 0
            and q.dtype == k.dtype == v.dtype == jnp.bfloat16
            and (mesh is None or mesh.size == 1))


def scan_attention(q, k, v, *, causal: bool = True,
                   window: Optional[int] = None, chunk: int = 1024,
                   q_offset: int = 0) -> jax.Array:
    """Flash-style attention in XLA: scan over key chunks with online
    softmax.  Arguments as ``blockwise_attention``.  Never materializes
    [Sq, Sk]; peak score block is [B, KH, G, Sq, chunk].
    """
    B, Sq, H, D = q.shape
    _, Sk, KH, _ = k.shape
    Dv = v.shape[-1]
    G = H // KH
    qg = q.reshape(B, Sq, KH, G, D).transpose(0, 2, 3, 1, 4)  # [B,KH,G,Sq,D]
    kc = k.transpose(0, 2, 1, 3)                               # [B,KH,Sk,D]
    vc = v.transpose(0, 2, 1, 3)
    scale = 1.0 / math.sqrt(D)
    chunk = min(chunk, Sk)
    n_chunks = math.ceil(Sk / chunk)
    pad = n_chunks * chunk - Sk
    if pad:
        kc = jnp.pad(kc, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vc = jnp.pad(vc, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kc = kc.reshape(B, KH, n_chunks, chunk, D).transpose(2, 0, 1, 3, 4)
    vc = vc.reshape(B, KH, n_chunks, chunk, Dv).transpose(2, 0, 1, 3, 4)

    q_pos = q_offset + jnp.arange(Sq)

    def step(carry, inp):
        m, l, acc = carry
        kb, vb, cidx = inp
        k_pos = cidx * chunk + jnp.arange(chunk)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                       kb.astype(jnp.float32)) * scale
        mask = k_pos[None, :] <= (q_pos[:, None] if causal
                                  else jnp.full_like(q_pos, Sk)[:, None])
        if window is not None:
            mask &= k_pos[None, :] > (q_pos[:, None] - window)
        mask &= (k_pos < Sk)[None, :]
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(-1))
        # guard fully-masked rows (m_new = -inf)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KH, G, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KH, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KH, G, Sq, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (kc, vc, jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, Dv).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *,
                     window: Optional[int] = None) -> jax.Array:
    """Single-step attention over a (possibly longer-than-filled) KV cache.

    q: [B, 1, H, D]; caches: [B, S, KH, D] / [B, S, KH, Dv]; cache_len: []
    current length (the new token is at cache_len - 1 after insertion).
    ``valid_mask`` semantics: positions [0, cache_len) are attendable.
    """
    B, _, H, D = q.shape
    _, S, KH, _ = k_cache.shape
    Dv = v_cache.shape[-1]
    G = H // KH
    qg = q.reshape(B, KH, G, D)
    s = jnp.einsum("bhgd,bshd->bhgs", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) / math.sqrt(D)
    pos = jnp.arange(S)
    mask = pos < cache_len
    if window is not None:
        mask &= pos > (cache_len - 1 - window)
    s = jnp.where(mask[None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, Dv).astype(q.dtype)


def attention_block(params, x, *, n_heads: int, n_kv: int, head_dim: int,
                    rope_theta: float = 1e4, causal: bool = True,
                    window: Optional[int] = None, chunk: int = 1024,
                    positions: Optional[jax.Array] = None,
                    pat=NO_PATTERN, layer: int = 0) -> jax.Array:
    """Full attention sub-layer on [B, S, d_model] (training/prefill path).

    Approximate dropout applies at KV-group granularity for families
    declaring ``attn_head_granular`` (head_rdp): one dropped unit is one KV
    head together with its G = n_heads/n_kv query-head group, so GQA
    grouping stays contiguous and the kept heads run as compact blocks
    through the unchanged blockwise attention (``nb`` must equal ``n_kv``
    — ``_attn_pat`` in models/transformer.py enforces this).  Kept-head
    output is scaled by dp (inverted dropout); a dropped head's output —
    including its wo contribution — is exactly zero in the mask oracle.
    """
    B, S, _ = x.shape
    bp = plan_mod.as_bound(pat).for_layer(layer)
    dp = bp.dp if (bp.active and
                   plan_mod.get_family(bp.family).attn_head_granular) else 1
    wq, wk, wv, wo = params["wq"], params["wk"], params["wv"], params["wo"]
    bq, bk, bv = params.get("bq"), params.get("bk"), params.get("bv")
    if dp > 1:
        b = bp.bias
        assert n_kv % dp == 0 and bp.nb == n_kv, (n_kv, dp, bp.nb)
        wq = _slice_blocks(wq, 1, n_kv, dp, b)   # blk = G query heads
        wk = _slice_blocks(wk, 1, n_kv, dp, b)
        wv = _slice_blocks(wv, 1, n_kv, dp, b)
        wo = _slice_blocks(wo, 0, n_kv, dp, b)
        if bq is not None:
            bq = _slice_blocks(bq, 0, n_kv, dp, b)
            bk = _slice_blocks(bk, 0, n_kv, dp, b)
            bv = _slice_blocks(bv, 0, n_kv, dp, b)
        n_heads //= dp
        n_kv //= dp
    q = jnp.einsum("bsd,dhk->bshk", x, wq)
    k = jnp.einsum("bsd,dhk->bshk", x, wk)
    v = jnp.einsum("bsd,dhk->bshk", x, wv)
    if bq is not None:
        q, k, v = q + bq, k + bk, v + bv
    if positions is None:
        positions = jnp.arange(S)[None, :].repeat(B, 0)
    cos, sin = rope_cache(positions, head_dim, rope_theta)
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    q = constrain(q, ("batch", "q_seq", "heads", "head_dim"))
    # project from the seq-sharded x LOCALLY, then gather the (much
    # narrower) kv activations — not the d_model-wide input.  The first
    # constraint pins the projection output seq-sharded (zero comm), the
    # second forces the gather on k/v (kv_heads·head_dim wide, e.g. 5×
    # narrower than d_model for qwen2.5).
    k = constrain(k, ("batch", "q_seq", "kv_heads", "head_dim"))
    v = constrain(v, ("batch", "q_seq", "kv_heads", "head_dim"))
    k = constrain(k, ("batch", "kv_seq", "kv_heads", "head_dim"))
    v = constrain(v, ("batch", "kv_seq", "kv_heads", "head_dim"))
    o = blockwise_attention(q, k, v, causal=causal, window=window, chunk=chunk)
    out = jnp.einsum("bshk,hkd->bsd", o, wo)
    if dp > 1:
        out = out * dp  # inverted-dropout scale on kept heads
    # head-sharded partial sums reduce-scatter straight into the seq-sharded
    # residual stream under SP (vs all-reduce to replicated)
    return constrain(out, ("batch", "res_seq", "embed"))


# --------------------------------------------------------------------------
# Dense FFN with Approximate Random Dropout
# --------------------------------------------------------------------------

def init_ffn(d_model: int, d_ff: int, gated: bool = True, dtype=jnp.bfloat16):
    params = {"w_up": jnp.zeros((d_model, d_ff), dtype),
              "w_down": jnp.zeros((d_ff, d_model), dtype)}
    axes = {"w_up": ("embed", "ffn"), "w_down": ("ffn", "embed")}
    if gated:
        params["w_gate"] = jnp.zeros((d_model, d_ff), dtype)
        axes["w_gate"] = ("embed", "ffn")
    return params, axes


def ffn_block(params, x, pat=NO_PATTERN, *, layer: int = 0,
              act: Callable = jax.nn.silu) -> jax.Array:
    """(Gated) FFN computing only the kept 1/dp of the hidden dim.

    ``pat``: a ``BoundPlan`` (or the legacy ``PatternArgs`` shim).  The
    actual pattern math is dispatched through the family registry
    (core.plan.FAMILIES): rdp slices w_up/w_gate columns and w_down rows,
    tdp masks diagonal synapse tiles of the up projection, col_rdp slices
    input columns — each on its plan-selected backend (slice/gather/pallas).
    """
    bp = plan_mod.as_bound(pat).for_layer(layer)
    w_up, w_down = params["w_up"], params["w_down"]
    w_gate = params.get("w_gate")
    # inactive patterns (dp=1) dispatch through the identity family — one
    # dense-FFN body lives in the registry instead of being duplicated here
    fam = plan_mod.get_family(bp.family if bp.active else "identity")
    # named_scope lands in HLO op_name metadata, letting hlo_profile
    # attribute the pattern-compacted matmuls (1/dp FLOP gauging at
    # warm_start) without guessing from shapes
    with jax.named_scope("ffn_pattern"):
        out = fam.apply_ffn(x, w_up, w_down, w_gate, dp=bp.dp, bias=bp.bias,
                            nb=bp.nb, backend=bp.backend, act=act)
    return constrain(out, ("batch", "res_seq", "embed"))


# --------------------------------------------------------------------------
# Mixture of Experts (capacity-based scatter dispatch, EP-shardable)
# --------------------------------------------------------------------------

def init_moe(d_model: int, d_ff: int, n_experts: int, n_shared: int = 0,
             dtype=jnp.bfloat16):
    params = {
        "router": jnp.zeros((d_model, n_experts), jnp.float32),
        "w_up": jnp.zeros((n_experts, d_model, d_ff), dtype),
        "w_gate": jnp.zeros((n_experts, d_model, d_ff), dtype),
        "w_down": jnp.zeros((n_experts, d_ff, d_model), dtype),
    }
    axes = {
        "router": ("embed", "experts"),
        "w_up": ("experts", "embed", "moe_ffn"),
        "w_gate": ("experts", "embed", "moe_ffn"),
        "w_down": ("experts", "moe_ffn", "embed"),
    }
    if n_shared:
        p, a = init_ffn(d_model, n_shared * d_ff, gated=True, dtype=dtype)
        params["shared"], axes["shared"] = p, a
    return params, axes


def moe_block(params, x, *, top_k: int, capacity_factor: float = 1.25,
              pat=NO_PATTERN, layer: int = 0,
              act: Callable = jax.nn.silu):
    """Top-k routed MoE with static per-expert capacity.

    Dispatch via scatter-add into [E, C, d] buffers (no [T,E,C] one-hot);
    under `ep_full` rules the buffers shard over experts and XLA inserts the
    all-to-all.  Approximate dropout composes two ways (DESIGN.md §4, §11):
    families declaring ``moe_hidden_slice`` (rdp) compact *within* experts
    (hidden dim, same dp every expert); families declaring
    ``expert_granular`` (expert_drop) slice the expert axis itself — router
    columns and w_up/w_gate/w_down expert slices of dropped experts are
    removed before routing, so dropped experts are never dispatched.  The
    router softmax then renormalizes over kept experts (== the
    mask-logits-to--inf oracle), so no inverted-dropout scale applies.
    Other families run experts dense.  Returns (y, aux_loss).
    """
    B, S, d = x.shape
    E = params["router"].shape[-1]
    bp = plan_mod.as_bound(pat).for_layer(layer)
    fam = plan_mod.get_family(bp.family)
    router = params["router"]
    w_up, w_gate, w_down = params["w_up"], params["w_gate"], params["w_down"]
    expert_pat = (bp.active and fam.expert_granular
                  and E % bp.dp == 0 and top_k <= E // bp.dp)
    if expert_pat:
        eb = bp.bias
        router = _slice_blocks(router, 1, E, bp.dp, eb)
        w_up = _slice_blocks(w_up, 0, E, bp.dp, eb)
        w_gate = _slice_blocks(w_gate, 0, E, bp.dp, eb)
        w_down = _slice_blocks(w_down, 0, E, bp.dp, eb)
        E //= bp.dp
    T = B * S
    C = int(math.ceil(T * top_k / E * capacity_factor))
    C = max(8, -(-C // 8) * 8)  # round up to 8 for sublane alignment

    xt = x.reshape(T, d)
    logits = (xt.astype(jnp.float32) @ router)
    probs = jax.nn.softmax(logits, -1)
    gate_vals, topk_idx = jax.lax.top_k(probs, top_k)        # [T, k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # position of token t's k-th assignment within its expert's buffer —
    # computed one k-slot at a time so the transient is [T, E], not [T·k, E]
    counts = jnp.zeros((E,), jnp.int32)
    pos_cols = []
    for kk in range(top_k):
        oh = jax.nn.one_hot(topk_idx[:, kk], E, dtype=jnp.int32)  # [T, E]
        pos_k = ((jnp.cumsum(oh, 0) - 1 + counts[None, :]) * oh).sum(-1)
        pos_cols.append(pos_k)
        counts = counts + oh.sum(0)
    pos_in_e = jnp.stack(pos_cols, -1)                        # [T, k]
    keep = pos_in_e < C

    e_flat = topk_idx.reshape(-1)
    p_flat = jnp.where(keep, pos_in_e, C).reshape(-1)         # overflow → slot C
    # scatter tokens into capacity buffers (slot C is a waste bucket)
    buf = jnp.zeros((E, C + 1, d), x.dtype)
    tok_rep = jnp.repeat(xt, top_k, 0)
    buf = buf.at[e_flat, p_flat].add(tok_rep)
    buf = constrain(buf[:, :C], ("experts", None, "embed"))

    # per-expert FFN (batched over experts; within-expert approx dropout)
    dp = bp.dp if (bp.active and fam.moe_hidden_slice) else 1
    if dp > 1:
        b = bp.bias
        w_up = _slice_blocks(w_up, 2, bp.nb, dp, b)
        w_gate = _slice_blocks(w_gate, 2, bp.nb, dp, b)
        w_down = _slice_blocks(w_down, 1, bp.nb, dp, b)
    h = jnp.einsum("ecd,edf->ecf", buf, w_up)
    h = act(h) * jnp.einsum("ecd,edf->ecf", buf, w_gate)
    if dp > 1:
        h = h * dp
    out = jnp.einsum("ecf,efd->ecd", h, w_down)
    out = constrain(out, ("experts", None, "embed"))
    out = jnp.pad(out, ((0, 0), (0, 1), (0, 0)))              # waste bucket

    # combine
    y = (out[e_flat, p_flat].reshape(T, top_k, d)
         * gate_vals[..., None].astype(x.dtype)
         * keep[..., None]).sum(1)

    # load-balancing aux loss (Switch-style)
    me = probs.mean(0)
    fe = jnp.bincount(e_flat, length=E).astype(jnp.float32) / (T * top_k)
    aux = E * jnp.vdot(me, fe)

    y = y.reshape(B, S, d)
    if "shared" in params:
        # expert_drop targets routed experts; shared experts always run
        sp = NO_PATTERN if fam.expert_granular else pat
        y = y + ffn_block(params["shared"], x, sp, layer=layer, act=act)
    return constrain(y, ("batch", "res_seq", "embed")), aux


def moe_block_ep(params, x, *, top_k: int, n_experts: int,
                 capacity_factor: float = 1.25,
                 pat=NO_PATTERN, layer: int = 0,
                 act: Callable = jax.nn.silu):
    """Expert-parallel MoE: shard_map + all_to_all dispatch (the optimized
    beyond-baseline path, EXPERIMENTS.md §Perf).

    The scatter-dispatch ``moe_block`` builds [E, C, d] buffers that XLA's
    SPMD partitioner can only realize by replicate-and-all-reduce (measured
    ~85 TB/device/step on deepseek-v3).  Here each device packs its OWN
    tokens into per-expert send buffers and a single all_to_all moves them
    to the expert shards — wire bytes drop to ~tokens·k·cf·d per device.

    Requires: experts shard over mesh axes (from the ambient rules) with
    E % n_ep == 0, batch divisible by the batch axes, seq by 'model'.
    Falls back to ``moe_block`` otherwise (single-device tests).
    """
    from repro.parallel.sharding import current_mesh, current_rules
    from jax.sharding import PartitionSpec as PSpec

    mesh, rules = current_mesh(), current_rules()
    E = n_experts
    # fallback captures the ORIGINAL params/pat — moe_block applies its own
    # expert/hidden slicing, so nothing is sliced twice
    fallback = functools.partial(
        moe_block, params, x, top_k=top_k, capacity_factor=capacity_factor,
        pat=pat, layer=layer, act=act)
    if mesh is None or rules is None:
        return fallback()

    # expert dropout: slice the expert axis up front so dropped experts are
    # never dispatched — smaller buffers, fewer all_to_all bytes, and the EP
    # divisibility below is computed over the KEPT expert count
    bp = plan_mod.as_bound(pat).for_layer(layer)
    fam = plan_mod.get_family(bp.family)
    router = params["router"]
    w_up_p, w_gate_p = params["w_up"], params["w_gate"]
    w_down_p = params["w_down"]
    if (bp.active and fam.expert_granular
            and E % bp.dp == 0 and top_k <= E // bp.dp):
        eb = bp.bias
        router = _slice_blocks(router, 1, E, bp.dp, eb)
        w_up_p = _slice_blocks(w_up_p, 0, E, bp.dp, eb)
        w_gate_p = _slice_blocks(w_gate_p, 0, E, bp.dp, eb)
        w_down_p = _slice_blocks(w_down_p, 0, E, bp.dp, eb)
        E //= bp.dp

    spec = rules.lookup("experts", is_param=True)
    ep_axes = tuple(a for a in ((spec,) if isinstance(spec, str) else
                                (spec or ())) if a in mesh.axis_names)
    # shrink the EP axis set until the expert count divides it (e.g. 128
    # experts on a 256-way ('data','model') rule -> EP over 'model' only)
    while ep_axes and E % int(np.prod([mesh.shape[a] for a in ep_axes])):
        ep_axes = ep_axes[1:]
    n_ep = int(np.prod([mesh.shape[a] for a in ep_axes])) if ep_axes else 1
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_b = int(np.prod([mesh.shape[a] for a in batch_axes]))
    n_s = mesh.shape.get("model", 1)
    B, S, d = x.shape
    if (n_ep <= 1 or E % n_ep or B % n_b or S % n_s):
        return fallback()

    t_loc = (B // n_b) * (S // n_s)
    C_src = int(math.ceil(t_loc * top_k / E * capacity_factor))
    C_src = max(8, -(-C_src // 8) * 8)
    E_loc = E // n_ep

    # within-expert approximate dropout (same dp for every expert)
    dp = bp.dp if (bp.active and fam.moe_hidden_slice) else 1
    b_pat = bp.bias if dp > 1 else 0

    def mapped(xl, router, w_up, w_gate, w_down):
        # xl: [B/nb, S/ns, d] — this device's tokens
        xt = xl.reshape(-1, d)                               # [t_loc, d]
        logits = xt.astype(jnp.float32) @ router             # [t_loc, E]
        probs = jax.nn.softmax(logits, -1)
        gate_vals, topk_idx = jax.lax.top_k(probs, top_k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

        # local slot assignment per expert (cumsum per k-slot)
        counts = jnp.zeros((E,), jnp.int32)
        pos_cols = []
        for kk in range(top_k):
            oh = jax.nn.one_hot(topk_idx[:, kk], E, dtype=jnp.int32)
            pos_k = ((jnp.cumsum(oh, 0) - 1 + counts[None, :]) * oh).sum(-1)
            pos_cols.append(pos_k)
            counts = counts + oh.sum(0)
        pos_in_e = jnp.stack(pos_cols, -1)                   # [t_loc, k]
        keep = pos_in_e < C_src
        e_flat = topk_idx.reshape(-1)
        p_flat = jnp.where(keep, pos_in_e, C_src).reshape(-1)

        buf = jnp.zeros((E, C_src + 1, d), xl.dtype)
        buf = buf.at[e_flat, p_flat].add(jnp.repeat(xt, top_k, 0))
        buf = buf[:, :C_src]                                 # [E, C_src, d]

        # dispatch: experts -> their shards; sources concat on capacity
        recv = jax.lax.all_to_all(buf, ep_axes, split_axis=0,
                                  concat_axis=1, tiled=True)
        # recv: [E_loc, n_ep*C_src, d]

        wu, wg, wd = w_up, w_gate, w_down                    # [E_loc, d, f]
        if dp > 1:
            wu = _slice_blocks(wu, 2, bp.nb, dp, b_pat)
            wg = _slice_blocks(wg, 2, bp.nb, dp, b_pat)
            wd = _slice_blocks(wd, 1, bp.nb, dp, b_pat)
        h = jnp.einsum("ecd,edf->ecf", recv, wu)
        h = act(h) * jnp.einsum("ecd,edf->ecf", recv, wg)
        if dp > 1:
            h = h * dp
        out = jnp.einsum("ecf,efd->ecd", h, wd)              # [E_loc, ., d]

        # combine: back to the source devices
        back = jax.lax.all_to_all(out, ep_axes, split_axis=1,
                                  concat_axis=0, tiled=True)
        back = jnp.pad(back, ((0, 0), (0, 1), (0, 0)))       # waste bucket
        y = (back[e_flat, p_flat].reshape(-1, top_k, d)
             * gate_vals[..., None].astype(xl.dtype)
             * keep[..., None]).sum(1)                       # [t_loc, d]

        # load-balance aux over GLOBAL stats: pmean the per-shard me/fe
        # first, dot after (mean-of-dots != dot-of-means)
        all_axes = tuple(mesh.axis_names)
        me = jax.lax.pmean(probs.mean(0), all_axes)
        fe = jax.lax.pmean(
            jnp.bincount(e_flat, length=E).astype(jnp.float32) /
            (xt.shape[0] * top_k), all_axes)
        aux = E * jnp.vdot(me, fe)
        return y.reshape(xl.shape), aux

    xspec = PSpec(batch_axes if len(batch_axes) > 1 else
                  (batch_axes[0] if batch_axes else None),
                  "model" if n_s > 1 else None, None)
    ep_spec = PSpec(ep_axes if len(ep_axes) > 1 else ep_axes[0])
    y, aux = jax.shard_map(
        mapped, mesh=mesh,
        in_specs=(xspec, PSpec(), ep_spec, ep_spec, ep_spec),
        out_specs=(xspec, PSpec()),
        check_vma=False,
    )(x, router, w_up_p, w_gate_p, w_down_p)

    if "shared" in params:
        # expert_drop targets routed experts; shared experts always run
        sp = NO_PATTERN if fam.expert_granular else pat
        y = y + ffn_block(params["shared"], x, sp, layer=layer, act=act)
    return constrain(y, ("batch", "res_seq", "embed")), aux


# --------------------------------------------------------------------------
# Mamba2 (SSD — state-space duality, chunked)
# --------------------------------------------------------------------------

def init_mamba2(d_model: int, d_state: int, headdim: int = 64,
                expand: int = 2, d_conv: int = 4, dtype=jnp.bfloat16):
    d_inner = expand * d_model
    n_heads = d_inner // headdim
    # in_proj → [z (d_inner), x (d_inner), B (d_state), C (d_state), dt (n_heads)]
    d_in_proj = 2 * d_inner + 2 * d_state + n_heads
    params = {
        "in_proj": jnp.zeros((d_model, d_in_proj), dtype),
        "conv_w": jnp.zeros((d_conv, d_inner + 2 * d_state), dtype),
        "conv_b": jnp.zeros((d_inner + 2 * d_state,), dtype),
        "A_log": jnp.zeros((n_heads,), jnp.float32),
        "D": jnp.zeros((n_heads,), jnp.float32),
        "dt_bias": jnp.zeros((n_heads,), jnp.float32),
        "norm_scale": jnp.ones((d_inner,), jnp.float32),
        "out_proj": jnp.zeros((d_inner, d_model), dtype),
    }
    axes = {
        "in_proj": ("embed", "inner"), "conv_w": (None, "inner"),
        "conv_b": ("inner",), "A_log": (None,), "D": (None,),
        "dt_bias": (None,), "norm_scale": ("inner",),
        "out_proj": ("inner", "embed"),
    }
    return params, axes


def _segsum(x):
    """Stable segment-sum: out[..., i, j] = sum_{k in (j, i]} x[..., k]."""
    T = x.shape[-1]
    c = jnp.cumsum(x, -1)
    d = c[..., :, None] - c[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool), 0)
    return jnp.where(mask, d, -jnp.inf)


def mamba2_block(params, x, *, d_state: int, headdim: int = 64,
                 expand: int = 2, d_conv: int = 4, chunk: int = 256,
                 pat=NO_PATTERN, layer: int = 0):
    """SSD mixer on [B, L, d_model] (training/prefill path).

    Approximate dropout participates at two granularities, selected by the
    plan family's capability flags (DESIGN.md §4, §11):

    * ``head_granular`` (rdp, head_rdp) — whole SSD heads: kept heads are
      computed, dropped heads contribute zero; in/out projections, conv,
      A/D/dt and norm_scale all slice by head-block.
    * ``ssm_state_granular`` (ssm_row) — rows of the recurrent *state*:
      the d_state channels of B and C.  The SSD recurrence is elementwise
      in the state index, so keeping 1/dp of the B/C columns (in_proj and
      conv) computes exactly the masked recurrence at 1/dp the state FLOPs.
      Only the SSD output is ×dp-scaled — the D·x skip never touches the
      state and stays unscaled.
    """
    B, L, _ = x.shape
    d_inner = expand * x.shape[-1]
    n_heads = d_inner // headdim

    # --- projections (RDP over heads: slice head-blocks of in/out proj) ---
    bp = plan_mod.as_bound(pat).for_layer(layer)
    fam = plan_mod.get_family(bp.family)
    dp = bp.dp if (bp.active and fam.head_granular) else 1
    state_dp = bp.dp if (bp.active and dp == 1
                         and fam.ssm_state_granular
                         and d_state % bp.dp == 0) else 1
    in_proj, out_proj = params["in_proj"], params["out_proj"]
    conv_w, conv_b = params["conv_w"], params["conv_b"]
    A_log, D, dt_bias = params["A_log"], params["D"], params["dt_bias"]
    nh = n_heads
    if state_dp > 1:
        # row dropout over the state dim: slice the B and C column ranges
        # of in_proj (z | x | B | C | dt layout) and the matching conv
        # channels ((x, B, C) layout); everything head-shaped stays dense
        b = bp.bias % state_dp
        kept_n = jnp.arange(d_state // state_dp) * state_dp + b
        zx = in_proj[:, :2 * d_inner]
        bc_lo = 2 * d_inner
        bcol = _slice_blocks(in_proj[:, bc_lo:bc_lo + d_state],
                             1, d_state, state_dp, b)
        ccol = _slice_blocks(in_proj[:, bc_lo + d_state:bc_lo + 2 * d_state],
                             1, d_state, state_dp, b)
        dtc = in_proj[:, bc_lo + 2 * d_state:]
        in_proj = jnp.concatenate([zx, bcol, ccol, dtc], 1)
        conv_keep = jnp.concatenate(
            [jnp.arange(d_inner), d_inner + kept_n,
             d_inner + d_state + kept_n])
        conv_w, conv_b = conv_w[:, conv_keep], conv_b[conv_keep]
        d_state //= state_dp
    if dp > 1:
        b = bp.bias
        assert n_heads % dp == 0, (n_heads, dp)
        keep = (jnp.arange(n_heads // dp) * dp + b) % n_heads
        # split in_proj columns: z | x | B | C | dt
        zc = _slice_blocks(in_proj[:, :d_inner], 1, n_heads, dp, b)
        xc = _slice_blocks(in_proj[:, d_inner:2 * d_inner], 1, n_heads, dp, b)
        bc = in_proj[:, 2 * d_inner:2 * d_inner + 2 * d_state]
        dtc = jnp.take(in_proj[:, 2 * d_inner + 2 * d_state:], keep, 1)
        in_proj = jnp.concatenate([zc, xc, bc, dtc], 1)
        conv_keep = jnp.concatenate(
            [(keep[:, None] * headdim + jnp.arange(headdim)).reshape(-1),
             d_inner + jnp.arange(2 * d_state)])
        conv_w, conv_b = conv_w[:, conv_keep], conv_b[conv_keep]
        A_log, D, dt_bias = A_log[keep], D[keep], dt_bias[keep]
        out_proj = _slice_blocks(out_proj, 0, n_heads, dp, b)
        norm_scale = _slice_blocks(params["norm_scale"], 0, n_heads, dp, b)
        d_inner //= dp
        nh = n_heads // dp
    else:
        norm_scale = params["norm_scale"]

    proj = x @ in_proj
    z, xs, Bc, Cc, dt = jnp.split(
        proj, [d_inner, 2 * d_inner, 2 * d_inner + d_state,
               2 * d_inner + 2 * d_state], -1)

    # causal depthwise conv over (x, B, C)
    xbc = jnp.concatenate([xs, Bc, Cc], -1)
    xbc = jax.nn.silu(_causal_conv1d(xbc, conv_w, conv_b, d_conv))
    xs, Bc, Cc = jnp.split(xbc, [d_inner, d_inner + d_state], -1)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)    # [B, L, H]
    A = -jnp.exp(A_log)                                       # [H]
    xh = xs.reshape(B, L, nh, headdim)
    y = _ssd_chunked(xh, dt, A, Bc, Cc, chunk)                # [B, L, H, P]
    if state_dp > 1:
        # inverted-dropout scale on the state sum only: the D·x skip below
        # bypasses the recurrence and must stay unscaled
        y = y * state_dp
    y = y + D[None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(B, L, d_inner)
    if dp > 1:
        y = y * dp  # inverted-dropout scale on kept heads
    # gated RMSNorm (Mamba2)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True) + 1e-6)
    y = (y * norm_scale).astype(x.dtype)
    out = y @ out_proj
    return constrain(out, ("batch", "res_seq", "embed"))


def _causal_conv1d(x, w, b, d_conv: int):
    """Depthwise causal conv: x [B, L, C], w [K, C]."""
    xp = jnp.pad(x, ((0, 0), (d_conv - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(d_conv):
        out = out + xp[:, i:i + x.shape[1]].astype(jnp.float32) * w[i].astype(jnp.float32)
    return (out + b.astype(jnp.float32)).astype(x.dtype)


def _ssd_chunked(x, dt, A, Bc, Cc, chunk: int, return_state: bool = False):
    """Chunked SSD (Mamba-2 Alg. minimal_ssd): x [B,L,H,P], dt [B,L,H],
    A [H], B/C [B,L,N] (single group).  Returns [B,L,H,P] float32
    (+ final state [B,H,P,N] when return_state — the prefill→decode
    handoff)."""
    Bsz, L0, H, P = x.shape
    N = Bc.shape[-1]
    Q = min(chunk, L0)
    pad = (-L0) % Q
    if pad:
        # dt=0 padding is exact: decay=1 and zero state contribution
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bc = jnp.pad(Bc, ((0, 0), (0, pad), (0, 0)))
        Cc = jnp.pad(Cc, ((0, 0), (0, pad), (0, 0)))
    L = L0 + pad
    nc = L // Q
    xf = x.astype(jnp.float32).reshape(Bsz, nc, Q, H, P)
    dtc = dt.reshape(Bsz, nc, Q, H)
    Bf = Bc.astype(jnp.float32).reshape(Bsz, nc, Q, N)
    Cf = Cc.astype(jnp.float32).reshape(Bsz, nc, Q, N)
    dA = dtc * A[None, None, None, :]                         # [B,nc,Q,H]
    dAc = jnp.cumsum(dA, 2)

    # 1. intra-chunk (diagonal blocks)
    Ldec = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))         # [B,nc,H,Q,Q]
    scores = jnp.einsum("bcqn,bckn->bcqk", Cf, Bf)            # [B,nc,Q,Q]
    y_diag = _ssd_diag(scores, Ldec, dtc, xf)

    # 2. chunk states
    decay_states = jnp.exp(dAc[:, :, -1:, :] - dAc)           # [B,nc,Q,H]
    states = jnp.einsum("bcqn,bcqh,bcqhp->bchpn",
                        Bf, decay_states * dtc, xf)           # [B,nc,H,P,N]

    # 3. inter-chunk recurrence over chunk boundary states
    dA_sum = dA.sum(2)                                        # [B,nc,H]

    def scan_fn(h, inp):
        st, da = inp
        h_new = h * jnp.exp(da)[..., None, None] + st
        return h_new, h  # emit state BEFORE this chunk

    h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    h_final, prev_states = jax.lax.scan(
        scan_fn, h0,
        (states.transpose(1, 0, 2, 3, 4), dA_sum.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)        # [B,nc,H,P,N]

    # 4. state → output contribution
    state_decay = jnp.exp(dAc)                                # [B,nc,Q,H]
    y_off = jnp.einsum("bcqn,bchpn,bcqh->bcqhp", Cf, prev_states, state_decay)
    y = (y_diag + y_off).reshape(Bsz, L, H, P)[:, :L0]
    return (y, h_final) if return_state else y


def _ssd_diag(scores, Ldec, dtc, xf):
    """y_diag[b,c,q,h,p] = Σ_k scores[b,c,q,k]·Ldec[b,c,h,q,k]·dt[b,c,k,h]·x[b,c,k,h,p]."""
    w = scores[:, :, None] * Ldec                             # [B,nc,H,Q,Q]
    wx = w * dtc.transpose(0, 1, 3, 2)[:, :, :, None, :]      # dt over k
    return jnp.einsum("bchqk,bckhp->bcqhp", wx, xf)


# --------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V2/V3)
# --------------------------------------------------------------------------

def init_mla(d_model: int, n_heads: int, q_lora: int, kv_lora: int,
             qk_nope: int, qk_rope: int, v_dim: int, dtype=jnp.bfloat16):
    params = {
        "wq_a": jnp.zeros((d_model, q_lora), dtype),
        "q_norm": jnp.ones((q_lora,), jnp.float32),
        "wq_b": jnp.zeros((q_lora, n_heads, qk_nope + qk_rope), dtype),
        "wkv_a": jnp.zeros((d_model, kv_lora + qk_rope), dtype),
        "kv_norm": jnp.ones((kv_lora,), jnp.float32),
        "wkv_b": jnp.zeros((kv_lora, n_heads, qk_nope + v_dim), dtype),
        "wo": jnp.zeros((n_heads, v_dim, d_model), dtype),
    }
    axes = {
        "wq_a": ("embed", None), "q_norm": (None,),
        "wq_b": (None, "heads", "head_dim"),
        "wkv_a": ("embed", None), "kv_norm": (None,),
        "wkv_b": (None, "heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    return params, axes


def mla_project_qkv(params, x, positions, *, n_heads, qk_nope, qk_rope,
                    v_dim, rope_theta=1e4):
    """Shared q/k/v construction for MLA (train & prefill paths).

    Returns q, k [B,S,H,qk_nope+qk_rope], v [B,S,H,v_dim], plus the
    decode-cache payloads (c_kv normed, k_rope roped)."""
    q = rms_norm({"scale": params["q_norm"]}, x @ params["wq_a"])
    q = jnp.einsum("bsl,lhk->bshk", q, params["wq_b"])
    q_nope, q_rope = q[..., :qk_nope], q[..., qk_nope:]
    kv_a = x @ params["wkv_a"]
    c_kv, k_rope = kv_a[..., :-qk_rope], kv_a[..., -qk_rope:]
    c_kv = rms_norm({"scale": params["kv_norm"]}, c_kv)
    kv = jnp.einsum("bsl,lhk->bshk", c_kv, params["wkv_b"])
    k_nope, v = kv[..., :qk_nope], kv[..., qk_nope:]
    cos, sin = rope_cache(positions, qk_rope, rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope[..., None, :], cos, sin)       # 1 shared head
    k_rope_b = jnp.broadcast_to(k_rope, k_nope.shape[:-1] + (qk_rope,))
    q_full = jnp.concatenate([q_nope, q_rope], -1)
    k_full = jnp.concatenate([k_nope, k_rope_b], -1)
    return q_full, k_full, v, c_kv, k_rope[..., 0, :]


def mla_block(params, x, *, n_heads, qk_nope, qk_rope, v_dim,
              rope_theta=1e4, chunk: int = 1024):
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :].repeat(B, 0)
    q, k, v, _, _ = mla_project_qkv(params, x, positions, n_heads=n_heads,
                                    qk_nope=qk_nope, qk_rope=qk_rope,
                                    v_dim=v_dim, rope_theta=rope_theta)
    q = constrain(q, ("batch", "q_seq", "heads", "head_dim"))
    k = constrain(k, ("batch", "kv_seq", "heads", "head_dim"))
    o = blockwise_attention(q, k, v, causal=True, chunk=chunk)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"])
    return constrain(out, ("batch", "res_seq", "embed"))


# --------------------------------------------------------------------------
# Embedding / unembedding
# --------------------------------------------------------------------------

def init_embed(vocab: int, d_model: int, tie: bool, dtype=jnp.bfloat16):
    params = {"tok": jnp.zeros((vocab, d_model), dtype)}
    axes = {"tok": ("vocab", "embed")}
    if not tie:
        params["unembed"] = jnp.zeros((d_model, vocab), dtype)
        axes["unembed"] = ("embed", "vocab")
    return params, axes


def embed_tokens(params, tokens):
    out = jnp.take(params["tok"], tokens, axis=0)
    return constrain(out, ("batch", "res_seq", "embed"))


def unembed(params, x, scale: float = 1.0):
    w = params.get("unembed")
    if w is None:
        w = params["tok"].T
    logits = (x @ w).astype(jnp.float32) * scale
    return constrain(logits, ("batch", "res_seq", "vocab"))


# --------------------------------------------------------------------------
# LSTM (paper §IV-C) — 2-layer, dropout between layers
# --------------------------------------------------------------------------

def init_lstm_cell(d_in: int, d_hid: int, dtype=jnp.float32):
    params = {"wx": jnp.zeros((d_in, 4 * d_hid), dtype),
              "wh": jnp.zeros((d_hid, 4 * d_hid), dtype),
              "b": jnp.zeros((4 * d_hid,), dtype)}
    axes = {"wx": ("embed", "ffn"), "wh": ("ffn", "ffn"), "b": ("ffn",)}
    return params, axes


def lstm_layer(params, x, h0=None, c0=None):
    """x: [B, T, d_in] → outputs [B, T, d_hid]."""
    B, T, _ = x.shape
    H = params["wh"].shape[0]
    h0 = jnp.zeros((B, H), x.dtype) if h0 is None else h0
    c0 = jnp.zeros((B, H), x.dtype) if c0 is None else c0
    xw = x @ params["wx"] + params["b"]

    def step(carry, xt):
        h, c = carry
        gates = xt + h @ params["wh"]
        i, f, g, o = jnp.split(gates, 4, -1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    (_, _), hs = jax.lax.scan(step, (h0, c0), xw.transpose(1, 0, 2))
    return hs.transpose(1, 0, 2)


# --------------------------------------------------------------------------
# Weight materialization (shape/axes trees → real random init)
# --------------------------------------------------------------------------

def materialize(key: jax.Array, abstract_params) -> dict:
    """Name-aware init: embeddings N(0,1)·0.02; matmuls fan-in normal;
    norms ones; biases/zeros-by-name zeros; mamba A_log/dt specialized."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_params)
    keys = jax.random.split(key, len(flat))
    leaves = []
    for (path, leaf), k in zip(flat, keys):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        shape, dtype = leaf.shape, leaf.dtype
        if name in ("scale", "q_norm", "kv_norm", "norm_scale"):
            leaves.append(jnp.ones(shape, dtype))
        elif name == "A_log":
            n = int(math.prod(shape))
            leaves.append(jnp.log(jnp.linspace(1.0, 16.0, n)
                                  .reshape(shape)).astype(dtype))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape) *
                         (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
            leaves.append((dt + jnp.log(-jnp.expm1(-dt))).astype(dtype))
        elif name == "D":
            leaves.append(jnp.ones(shape, dtype))
        elif name.startswith("b") or name == "conv_b" or not shape:
            leaves.append(jnp.zeros(shape, dtype))
        elif name == "tok":
            leaves.append((jax.random.normal(k, shape) * 0.02).astype(dtype))
        else:
            # fan-in by name, robust to the stacked leading layer dim
            # (negative indices see the same dims stacked or not):
            if name == "wo":                      # [..., H, hd, d]
                fan_in = math.prod(shape[-3:-1])
            elif name in ("wq", "wk", "wv",       # [..., d, H, hd]
                          "wq_b", "wkv_b"):       # [..., lora, H, hd]
                fan_in = shape[-3]
            elif len(shape) >= 2:                 # [..., fan_in, fan_out]
                fan_in = shape[-2]
            else:
                fan_in = shape[0]
            std = 1.0 / math.sqrt(max(fan_in, 1))
            leaves.append((jax.random.normal(k, shape) * std).astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)
