"""Pallas TPU kernels for Row-based Dropout Pattern (RDP) compact matmuls.

Two variants (DESIGN.md §6):

* ``rdp_matmul_cols`` — up-projection.  ``C[M, N/dp] = A[M, K] @ W[:, kept]``.
  The W BlockSpec's ``index_map`` enumerates only *kept* column-blocks
  ``(b + j·dp) mod nb``, so dropped blocks are never DMA'd from HBM — that is
  the TPU translation of the paper's "prevent GPU from fetching those dropped
  data into shared memory" (Fig. 3a step 2).

* ``rdp_matmul_rows`` — down-projection.  ``C[M, N] = Ac[M, K/dp] @ W[kept, :]``
  where ``Ac`` is the already-compact hidden activation; kept *row*-blocks of
  W are read strided.

Both accumulate in an f32 VMEM scratch over the contraction grid dimension and
fold the inverted-dropout scale (×dp) into the epilogue.  The bias ``b`` is a
scalar-prefetch operand → one compiled kernel per ``dp`` (pattern bucketing),
no recompile across biases.

Block sizes default to (128, 128, 512): the pattern-dim block is pinned to the
128-lane group granularity (a kept group is one lane-aligned block); the
contraction block is larger to amortize the MXU pipeline.

Each ``pallas_call`` is named after its function (``name=``): the HLO
instruction, and so a device trace, names the kernel by it
(``rdp_matmul_cols.40``) whatever the call site is, which is how a trace
reduction finds the pattern kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128


def _fit_block(dim: int, pref: int, quantum: int = 8) -> int:
    """Largest divisor of ``dim`` that is <= pref, preferring multiples of
    ``quantum`` (sublane alignment).  Falls back to any divisor."""
    pref = min(pref, dim)
    if dim % pref == 0:
        return pref
    for b in range(pref - pref % quantum, 0, -quantum):
        if b and dim % b == 0:
            return b
    for b in range(pref, 0, -1):
        if dim % b == 0:
            return b
    return dim


def _acc_kernel(scale: float, contraction_axis: int,
                dims=((1,), (0,)), prefetch: bool = True):
    """Shared accumulate-over-k kernel body (fwd and bwd kernels).

    Contracts ``dims`` of (lhs, rhs) per ``lax.dot_general`` convention —
    ``((1,), (0,))`` is a plain matmul, ``((1,), (1,))`` is ``lhs @ rhsᵀ``,
    ``((0,), (0,))`` is ``lhsᵀ @ rhs``.  Zero-inits the f32 VMEM scratch at
    the first contraction step and writes the scaled epilogue at the last.
    ``prefetch`` prepends the scalar-prefetch bias ref that
    PrefetchScalarGridSpec kernels receive (bias-free kernels run a plain
    grid).
    """

    def body(l_ref, r_ref, o_ref, acc_ref):
        k = pl.program_id(contraction_axis)

        @pl.when(k == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            l_ref[...], r_ref[...], (dims, ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(k == pl.num_programs(contraction_axis) - 1)
        def _fin():
            o_ref[...] = (acc_ref[...] * scale).astype(o_ref.dtype)

    if not prefetch:
        return body

    def kernel(b_ref, l_ref, r_ref, o_ref, acc_ref):
        body(l_ref, r_ref, o_ref, acc_ref)

    return kernel


@functools.partial(jax.jit, static_argnames=(
    "dp", "block", "bm", "bk", "scale", "interpret"))
def rdp_matmul_cols(a: jax.Array, w: jax.Array, b: jax.Array, *, dp: int,
                    block: int = LANE, bm: int = 128, bk: int = 512,
                    scale: bool = True, interpret: bool = False) -> jax.Array:
    """C[M, N/dp] = (A @ W[:, kept_blocks]) · dp.   kept = (b + j·dp) % nb.

    a: [M, K], w: [K, N], b: int32 scalar bias.  Requires dp | (N/block),
    bm | M, bk | K.  dtypes: f32 or bf16 (f32 accumulation).
    """
    m, kdim = a.shape
    k2, n = w.shape
    assert kdim == k2, (a.shape, w.shape)
    nb = n // block
    assert n % block == 0 and nb % dp == 0, (n, block, dp)
    nc = n // dp                      # compact output width
    bm = _fit_block(m, bm)
    bk = _fit_block(kdim, bk)
    assert m % bm == 0 and kdim % bk == 0, (m, bm, kdim, bk)

    grid = (m // bm, nc // block, kdim // bk)
    kern = _acc_kernel(float(dp) if (scale and dp > 1) else 1.0,
                       contraction_axis=2)

    return pl.pallas_call(
        kern,
        name="rdp_matmul_cols",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k, bias: (i, k)),
                # only KEPT column-blocks of W are ever DMA'd:
                pl.BlockSpec((bk, block),
                             lambda i, j, k, bias: (k, (bias[0] + j * dp) % nb)),
            ],
            out_specs=pl.BlockSpec((bm, block), lambda i, j, k, bias: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, block), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, nc), a.dtype),
        interpret=interpret,
    )(jnp.asarray(b, jnp.int32).reshape(1), a, w)


@functools.partial(jax.jit, static_argnames=(
    "dp", "block", "bm", "bn", "scale", "interpret"))
def rdp_matmul_rows(a_compact: jax.Array, w: jax.Array, b: jax.Array, *,
                    dp: int, block: int = LANE, bm: int = 128, bn: int = 512,
                    scale: bool = False, interpret: bool = False) -> jax.Array:
    """C[M, N] = Ac[M, K/dp] @ W[kept_row_blocks, :] (· dp if scale).

    a_compact: [M, K/dp] kept-neuron activations; w: [K, N] full weight.
    Requires dp | (K/block), block | (K/dp) contraction blocking.
    """
    m, kc = a_compact.shape
    kdim, n = w.shape
    assert kc * dp == kdim, (a_compact.shape, w.shape, dp)
    nb = kdim // block
    assert kdim % block == 0 and nb % dp == 0, (kdim, block, dp)
    assert kc % block == 0, (kc, block)
    bm = _fit_block(m, bm)
    bn = _fit_block(n, bn)
    assert m % bm == 0 and n % bn == 0, (m, bm, n, bn)

    grid = (m // bm, n // bn, kc // block)
    kern = _acc_kernel(float(dp) if (scale and dp > 1) else 1.0,
                       contraction_axis=2)

    return pl.pallas_call(
        kern,
        name="rdp_matmul_rows",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, block), lambda i, j, k, bias: (i, k)),
                # strided kept ROW-blocks of W:
                pl.BlockSpec((block, bn),
                             lambda i, j, k, bias: ((bias[0] + k * dp) % nb, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, bias: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), a_compact.dtype),
        interpret=interpret,
    )(jnp.asarray(b, jnp.int32).reshape(1), a_compact, w)
