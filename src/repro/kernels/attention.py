"""Causal flash attention for training: one Pallas kernel forward, two
backward, behind a ``jax.custom_vjp``.

GQA is folded into the rows: one grid instance owns one KV head and a
block of ``bq`` query positions of its ``G`` query heads, so ``[G·bq, D]``
rows run against one ``[bk, D]`` K/V block.  Blocks above the diagonal are
never computed (``pl.when``) and never fetched (the K/V or Q ``index_map``
clamps to the last block needed); only blocks that straddle the diagonal
are masked.

Numerics (at least as exact as the XLA chunk scan,
``models/layers.scan_attention``, step by step):

* ``s = dot(q, k)`` on bf16 operands with float32 accumulation, scaled by
  ``1/sqrt(D)`` in float32 (q is never pre-scaled in bf16);
* the running max, sum and output accumulator stay in float32; the output
  is normalised in float32 and cast once;
* the forward's ``P·V`` takes ``P`` rounded to bf16: on a v5e the scan's
  float32-operand dot gives the same bits (PERF.md §6);
* the forward keeps its float32 output and logsumexp, so the backward forms
  ``D = rowsum(dO ∘ O)`` from the float32 output;
* ``dS`` enters the dQ and dK matmuls as bf16, as in the scan; ``P``
  enters dV as bf16 hi and lo parts (two MXU passes), where the scan
  rounds both P and dO/l to bf16; all accumulate in float32.  dK and dV
  sum over every q block and all G heads of their KV head in float32 VMEM
  scratch and are cast once; dQ sums over k blocks in float32.

Each ``pallas_call`` is named (``attn_causal_fwd``, ``attn_causal_dkv``,
``attn_causal_dq``), so a device trace finds the kernels by instruction
name.  ``models/layers.blockwise_attention`` decides when to use them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
# Block sizes (query positions, key positions) of each kernel, the fastest
# of a sweep on one TPU v5e at S 4096, 12/2 heads of 128 (PERF.md §6); a
# block is gcd(S, size), so any S that is a multiple of 128 tiles exactly.
FWD_BLOCKS = (512, 1024)
DKV_BLOCKS = (512, 512)
DQ_BLOCKS = (512, 1024)
# Scoped VMEM for the [G·bq, bk] float32 score tiles (a v5e core has 128 MiB)
VMEM_LIMIT = 100 * 2 ** 20

_NT = (((1,), (1,)), ((), ()))      # a @ b.T


def _last_k(i, bq: int, bk: int):
    """Last key block that query block ``i`` sees."""
    return ((i + 1) * bq - 1) // bk


def _first_q(j, bq: int, bk: int):
    """First query block that sees key block ``j``."""
    return (j * bk) // bq


def _straddles(i, j, bq: int, bk: int):
    """Block (i, j) holds a key after some query of it: mask needed."""
    return (j + 1) * bk - 1 > i * bq


def _visible(rows: int, cols: int, q0, k0, bq: int, *, keys_in_rows: bool):
    """Causal visibility of a score tile whose query axis is ``G`` blocks
    of ``bq`` positions starting at ``q0`` and whose key axis starts at
    ``k0``."""
    qa, ka = (1, 0) if keys_in_rows else (0, 1)
    qpos = q0 + lax.broadcasted_iota(jnp.int32, (rows, cols), qa) % bq
    kpos = k0 + lax.broadcasted_iota(jnp.int32, (rows, cols), ka)
    return kpos <= qpos


def _p_dot(p, x, *, exact: bool):
    """``p @ x`` for float32 probabilities and bf16 ``x``, accumulated in
    float32: ``p`` rounded to bf16, or, when ``exact``, split into bf16
    hi and lo parts that take two MXU passes."""
    hi = p.astype(x.dtype)
    out = jnp.dot(hi, x, preferred_element_type=jnp.float32)
    if exact:
        lo = (p - hi.astype(jnp.float32)).astype(x.dtype)
        out = out + jnp.dot(lo, x, preferred_element_type=jnp.float32)
    return out


def _rows(ref):
    """A ``[G, bq]`` block of per-row statistics as one ``[1, G·bq]`` row."""
    g = ref.shape[0]
    return jnp.concatenate([ref[h:h + 1, :] for h in range(g)], axis=1)


def _cols(ref):
    """A ``[G, bq]`` block of per-row statistics as one ``[G·bq, 1]``
    column."""
    g, bq = ref.shape
    return jnp.concatenate([ref[h, :].reshape(bq, 1) for h in range(g)],
                           axis=0)


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                scale: float, bq: int, bk: int):
    i, j = pl.program_id(2), pl.program_id(3)
    g, _, d = q_ref.shape
    n = g * bq
    last = _last_k(i, bq, bk)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, -jnp.inf, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def step(masked: bool):
        q = q_ref[...].reshape(n, d)
        s = lax.dot_general(q, k_ref[...], _NT,
                            preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_visible(n, bk, i * bq, j * bk, bq,
                                   keys_in_rows=False), s, -jnp.inf)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_sc[...] = alpha * l_sc[...] + p.sum(axis=1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + _p_dot(p, v_ref[...],
                                                    exact=False)
        m_sc[...] = m_new

    straddles = _straddles(i, j, bq, bk)
    pl.when((j <= last) & jnp.logical_not(straddles))(lambda: step(False))
    pl.when((j <= last) & straddles)(lambda: step(True))

    @pl.when(j == last)
    def _finish():
        l = l_sc[...]
        o_ref[...] = (acc_sc[...] / l).reshape(g, bq, d)
        lse = m_sc[...] + jnp.log(l)
        for h in range(g):
            lse_ref[h:h + 1, :] = lse[h * bq:(h + 1) * bq, :].reshape(1, bq)


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def _fwd(qg, kt, vt, *, blocks, interpret: bool):
    """qg [B, KH, G, S, D], kt/vt [B, KH, S, D] → float32 output
    [B, KH, G, S, D] and logsumexp [B, KH, G, S]."""
    b, kh, g, s, d = qg.shape
    bq, bk = (math.gcd(s, x) for x in blocks)
    kern = functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(d), bq=bq,
                             bk=bk)

    def kv_map(b_, h, i, j):
        return (b_, h, jnp.minimum(j, _last_k(i, bq, bk)), 0)

    return pl.pallas_call(
        kern,
        name="attn_causal_fwd",
        grid=(b, kh, s // bq, s // bk),
        in_specs=[
            pl.BlockSpec((None, None, g, bq, d),
                         lambda b_, h, i, j: (b_, h, 0, i, 0)),
            pl.BlockSpec((None, None, bk, d), kv_map),
            pl.BlockSpec((None, None, bk, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, None, g, bq, d),
                         lambda b_, h, i, j: (b_, h, 0, i, 0)),
            pl.BlockSpec((None, None, g, bq),
                         lambda b_, h, i, j: (b_, h, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, kh, g, s, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, kh, g, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((g * bq, 1), jnp.float32),
                        pltpu.VMEM((g * bq, 1), jnp.float32),
                        pltpu.VMEM((g * bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(qg, kt, vt)


# --------------------------------------------------------------------------
# Backward: dK and dV (key block stationary, query blocks streamed)
# --------------------------------------------------------------------------

def _dkv_kernel(q_ref, do_ref, lse_ref, di_ref, k_ref, v_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, scale: float, bq: int, bk: int):
    j, i = pl.program_id(2), pl.program_id(3)
    g, _, d = q_ref.shape
    n = g * bq

    @pl.when(i == 0)
    def _init():
        dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    def step(masked: bool):
        q = q_ref[...].reshape(n, d)
        do = do_ref[...].reshape(n, d)
        # scores with keys in rows: the per-query statistics are rows
        st = lax.dot_general(k_ref[...], q, _NT,
                             preferred_element_type=jnp.float32) * scale
        if masked:
            st = jnp.where(_visible(bk, n, i * bq, j * bk, bq,
                                    keys_in_rows=True), st, -jnp.inf)
        pt = jnp.exp(st - _rows(lse_ref))
        dv_sc[...] += _p_dot(pt, do, exact=True)
        dpt = lax.dot_general(v_ref[...], do, _NT,
                              preferred_element_type=jnp.float32)
        dst = (pt * (dpt - _rows(di_ref)) * scale).astype(q.dtype)
        dk_sc[...] += jnp.dot(dst, q, preferred_element_type=jnp.float32)

    first = _first_q(j, bq, bk)
    straddles = _straddles(i, j, bq, bk)
    pl.when((i >= first) & jnp.logical_not(straddles))(lambda: step(False))
    pl.when((i >= first) & straddles)(lambda: step(True))

    @pl.when(i == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def _dkv(qg, kt, vt, dog, lse, di, *, blocks, interpret: bool):
    b, kh, g, s, d = qg.shape
    bq, bk = (math.gcd(s, x) for x in blocks)
    kern = functools.partial(_dkv_kernel, scale=1.0 / math.sqrt(d), bq=bq,
                             bk=bk)

    def q_map(b_, h, j, i):
        return (b_, h, 0, jnp.maximum(i, _first_q(j, bq, bk)), 0)

    def stat_map(b_, h, j, i):
        return (b_, h, 0, jnp.maximum(i, _first_q(j, bq, bk)))

    def kv_map(b_, h, j, i):
        return (b_, h, j, 0)

    kv_spec = pl.BlockSpec((None, None, bk, d), kv_map)
    return pl.pallas_call(
        kern,
        name="attn_causal_dkv",
        grid=(b, kh, s // bk, s // bq),
        in_specs=[
            pl.BlockSpec((None, None, g, bq, d), q_map),
            pl.BlockSpec((None, None, g, bq, d), q_map),
            pl.BlockSpec((None, None, g, bq), stat_map),
            pl.BlockSpec((None, None, g, bq), stat_map),
            kv_spec, kv_spec,
        ],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                   jax.ShapeDtypeStruct(vt.shape, vt.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(qg, dog, lse, di, kt, vt)


# --------------------------------------------------------------------------
# Backward: dQ (query block stationary, key blocks streamed)
# --------------------------------------------------------------------------

def _dq_kernel(q_ref, do_ref, lse_ref, di_ref, k_ref, v_ref, dq_ref,
               dq_sc, *, scale: float, bq: int, bk: int):
    i, j = pl.program_id(2), pl.program_id(3)
    g, _, d = q_ref.shape
    n = g * bq
    last = _last_k(i, bq, bk)

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros(dq_sc.shape, jnp.float32)

    def step(masked: bool):
        q = q_ref[...].reshape(n, d)
        do = do_ref[...].reshape(n, d)
        k = k_ref[...]
        s = lax.dot_general(q, k, _NT,
                            preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_visible(n, bk, i * bq, j * bk, bq,
                                   keys_in_rows=False), s, -jnp.inf)
        p = jnp.exp(s - _cols(lse_ref))
        dp = lax.dot_general(do, v_ref[...], _NT,
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - _cols(di_ref)) * scale).astype(k.dtype)
        dq_sc[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    straddles = _straddles(i, j, bq, bk)
    pl.when((j <= last) & jnp.logical_not(straddles))(lambda: step(False))
    pl.when((j <= last) & straddles)(lambda: step(True))

    @pl.when(j == last)
    def _finish():
        dq_ref[...] = dq_sc[...].reshape(g, bq, d).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def _dq(qg, kt, vt, dog, lse, di, *, blocks, interpret: bool):
    b, kh, g, s, d = qg.shape
    bq, bk = (math.gcd(s, x) for x in blocks)
    kern = functools.partial(_dq_kernel, scale=1.0 / math.sqrt(d), bq=bq,
                             bk=bk)

    def kv_map(b_, h, i, j):
        return (b_, h, jnp.minimum(j, _last_k(i, bq, bk)), 0)

    q_spec = pl.BlockSpec((None, None, g, bq, d),
                          lambda b_, h, i, j: (b_, h, 0, i, 0))
    stat_spec = pl.BlockSpec((None, None, g, bq),
                             lambda b_, h, i, j: (b_, h, 0, i))
    kv_spec = pl.BlockSpec((None, None, bk, d), kv_map)
    return pl.pallas_call(
        kern,
        name="attn_causal_dq",
        grid=(b, kh, s // bq, s // bk),
        in_specs=[q_spec, q_spec, stat_spec, stat_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, qg.dtype),
        scratch_shapes=[pltpu.VMEM((g * bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(qg, dog, lse, di, kt, vt)


# --------------------------------------------------------------------------
# Differentiable op on the model's [B, S, heads, D] layout
# --------------------------------------------------------------------------

def _to_kernel_layout(q, k, v):
    b, s, h, d = q.shape
    kh = k.shape[2]
    qg = q.reshape(b, s, kh, h // kh, d).transpose(0, 2, 3, 1, 4)
    return qg, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)


def _from_group_layout(xg):
    b, kh, g, s, d = xg.shape
    return xg.transpose(0, 3, 1, 2, 4).reshape(b, s, kh * g, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def causal_attention(q, k, v, interpret: bool):
    """Causal GQA attention.  q [B, S, H, D]; k, v [B, S, KH, D] with
    KH | H; S and D multiples of 128; bf16.  Returns [B, S, H, D] in q's
    type.  ``interpret`` runs the kernels in Pallas interpret mode."""
    return _causal_fwd(q, k, v, interpret)[0]


def _causal_fwd(q, k, v, interpret):
    qg, kt, vt = _to_kernel_layout(q, k, v)
    o, lse = _fwd(qg, kt, vt, blocks=FWD_BLOCKS, interpret=interpret)
    out = _from_group_layout(o.astype(q.dtype))
    return out, (qg, kt, vt, o, lse)


def _causal_bwd(interpret, res, g):
    qg, kt, vt, o, lse = res
    b, kh, gq, s, d = qg.shape
    dog = g.reshape(b, s, kh, gq, d).transpose(0, 2, 3, 1, 4)
    # D = rowsum(dO ∘ O) from the float32 output
    di = jnp.sum(dog.astype(jnp.float32) * o, axis=-1)
    dk, dv = _dkv(qg, kt, vt, dog, lse, di, blocks=DKV_BLOCKS,
                  interpret=interpret)
    dq = _dq(qg, kt, vt, dog, lse, di, blocks=DQ_BLOCKS, interpret=interpret)
    return (_from_group_layout(dq), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))


causal_attention.defvjp(_causal_fwd, _causal_bwd)
