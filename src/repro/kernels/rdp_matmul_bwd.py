"""Backward Pallas kernels for the compact RDP matmuls (dgrad + wgrad).

The paper applies the sampled dropout pattern to the backward matmuls too
(Fig. 3 step 4: dgrad/wgrad reuse the same kept set), which is where the
training-time speedup actually comes from — the forward FFN is only a third
of a training step's matmul FLOPs.  These kernels give each forward kernel
in ``rdp_matmul.py`` its two adjoints:

* ``rdp_cols_dgrad``  — ``dA[M, K]  = dC[M, N/dp] @ W[:, kept]ᵀ``:
  the cotangent of the compact up-projection, contracting over the compact
  hidden dim.  Only *kept* column-blocks of W are DMA'd, mirroring the
  forward's BlockSpec index_map.
* ``rdp_cols_wgrad``  — ``dWc[K, N/dp] = Aᵀ @ dC``: the *compact* weight
  grad.  It is bias-independent (the bias only decides where the compact
  blocks scatter back, see ``kernels/autodiff.py``); dropped blocks of the
  full ``dW`` are identically zero.
* ``rdp_rows_dgrad``  — ``dAc[M, K/dp] = dC[M, N] @ W[kept, :]ᵀ``: adjoint
  of the compact down-projection; kept *row*-blocks of W read strided.
* ``rdp_rows_wgrad``  — ``dWc[K/dp, N] = Acᵀ @ dC``: compact row-block
  weight grad, scattered into the kept rows of the full ``dW`` by the
  caller.

All four accumulate in f32 VMEM scratch over the contraction grid dim and
share the forward kernels' contracts: the bias is a scalar-prefetch operand
(one compiled kernel per ``dp`` bucket, no recompile across biases), block
sizes are fitted with ``_fit_block``, and the compact/pattern dim is pinned
to lane-aligned blocks.  ``interpret=True`` runs them on CPU.  Like the
forward kernels, each ``pallas_call`` carries its function's name.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .rdp_matmul import LANE, _acc_kernel, _fit_block


@functools.partial(jax.jit, static_argnames=(
    "dp", "block", "bm", "bk", "scale", "interpret"))
def rdp_cols_dgrad(dc: jax.Array, w: jax.Array, b: jax.Array, *, dp: int,
                   block: int = LANE, bm: int = 128, bk: int = 512,
                   scale: bool = True, interpret: bool = False) -> jax.Array:
    """dA[M, K] = dC[M, N/dp] @ W[:, kept]ᵀ (· dp if the forward scaled).

    Adjoint of ``rdp_matmul_cols`` w.r.t. the dense activation.  dc: the
    compact cotangent [M, N/dp]; w: the full weight [K, N]; b: int32 bias.
    Kept column-blocks ``(b + j·dp) % nb`` are the only W blocks DMA'd.
    """
    m, nc = dc.shape
    kdim, n = w.shape
    assert nc * dp == n, (dc.shape, w.shape, dp)
    nb = n // block
    assert n % block == 0 and nb % dp == 0, (n, block, dp)
    assert nc % block == 0, (nc, block)
    bm = _fit_block(m, bm)
    bk = _fit_block(kdim, bk)
    assert m % bm == 0 and kdim % bk == 0, (m, bm, kdim, bk)

    grid = (m // bm, kdim // bk, nc // block)
    kern = _acc_kernel(float(dp) if (scale and dp > 1) else 1.0,
                       contraction_axis=2, dims=((1,), (1,)))

    return pl.pallas_call(
        kern,
        name="rdp_cols_dgrad",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, block), lambda i, k, j, bias: (i, j)),
                # contract against the same KEPT column-blocks the forward
                # multiplied by — dropped blocks never enter the adjoint:
                pl.BlockSpec((bk, block),
                             lambda i, k, j, bias: (k, (bias[0] + j * dp) % nb)),
            ],
            out_specs=pl.BlockSpec((bm, bk), lambda i, k, j, bias: (i, k)),
            scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, kdim), dc.dtype),
        interpret=interpret,
    )(jnp.asarray(b, jnp.int32).reshape(1), dc, w)


@functools.partial(jax.jit, static_argnames=(
    "dp", "block", "bm", "bk", "scale", "interpret"))
def rdp_cols_wgrad(a: jax.Array, dc: jax.Array, *, dp: int,
                   block: int = LANE, bm: int = 512, bk: int = 128,
                   scale: bool = True, interpret: bool = False) -> jax.Array:
    """dWc[K, N/dp] = Aᵀ[K, M] @ dC[M, N/dp] (· dp if the forward scaled).

    The *compact* weight grad of ``rdp_matmul_cols`` — grads for the kept
    column-blocks only.  Bias-free: which full-layout blocks these columns
    correspond to is resolved by the caller's scatter (autodiff.py), and
    dropped-block grads are identically zero by construction.
    """
    m, kdim = a.shape
    m2, nc = dc.shape
    assert m == m2, (a.shape, dc.shape)
    assert nc % block == 0, (nc, block)
    bm = _fit_block(m, bm)
    bk = _fit_block(kdim, bk)
    assert m % bm == 0 and kdim % bk == 0, (m, bm, kdim, bk)

    grid = (kdim // bk, nc // block, m // bm)
    kern = _acc_kernel(float(dp) if (scale and dp > 1) else 1.0,
                       contraction_axis=2, dims=((0,), (0,)), prefetch=False)

    return pl.pallas_call(
        kern,
        name="rdp_cols_wgrad",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda k, j, i: (i, k)),
            pl.BlockSpec((bm, block), lambda k, j, i: (i, j)),
        ],
        out_specs=pl.BlockSpec((bk, block), lambda k, j, i: (k, j)),
        scratch_shapes=[pltpu.VMEM((bk, block), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((kdim, nc), dc.dtype),
        interpret=interpret,
    )(a, dc)


@functools.partial(jax.jit, static_argnames=(
    "dp", "block", "bm", "bn", "scale", "interpret"))
def rdp_rows_dgrad(dc: jax.Array, w: jax.Array, b: jax.Array, *, dp: int,
                   block: int = LANE, bm: int = 128, bn: int = 512,
                   scale: bool = False, interpret: bool = False) -> jax.Array:
    """dAc[M, K/dp] = dC[M, N] @ W[kept_rows, :]ᵀ (· dp if the forward scaled).

    Adjoint of ``rdp_matmul_rows`` w.r.t. the compact activation; kept
    row-blocks of W are read strided, exactly the forward's working set.
    """
    m, n = dc.shape
    kdim, n2 = w.shape
    assert n == n2, (dc.shape, w.shape)
    nb = kdim // block
    assert kdim % block == 0 and nb % dp == 0, (kdim, block, dp)
    kc = kdim // dp
    assert kc % block == 0, (kc, block)
    bm = _fit_block(m, bm)
    bn = _fit_block(n, bn)
    assert m % bm == 0 and n % bn == 0, (m, bm, n, bn)

    grid = (m // bm, kc // block, n // bn)
    kern = _acc_kernel(float(dp) if (scale and dp > 1) else 1.0,
                       contraction_axis=2, dims=((1,), (1,)))

    return pl.pallas_call(
        kern,
        name="rdp_rows_dgrad",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bn), lambda i, k, j, bias: (i, j)),
                # strided kept ROW-blocks of W, transposed in-register:
                pl.BlockSpec((block, bn),
                             lambda i, k, j, bias: ((bias[0] + k * dp) % nb, j)),
            ],
            out_specs=pl.BlockSpec((bm, block), lambda i, k, j, bias: (i, k)),
            scratch_shapes=[pltpu.VMEM((bm, block), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, kc), dc.dtype),
        interpret=interpret,
    )(jnp.asarray(b, jnp.int32).reshape(1), dc, w)


@functools.partial(jax.jit, static_argnames=(
    "dp", "block", "bm", "bn", "scale", "interpret"))
def rdp_rows_wgrad(a_compact: jax.Array, dc: jax.Array, *, dp: int,
                   block: int = LANE, bm: int = 512, bn: int = 512,
                   scale: bool = False, interpret: bool = False) -> jax.Array:
    """dWc[K/dp, N] = Acᵀ @ dC (· dp if the forward scaled).

    The compact row-block weight grad of ``rdp_matmul_rows``: one grad row
    per *kept* neuron.  The caller scatters these into the kept rows of the
    full ``dW`` (dropped rows stay exactly zero).
    """
    m, kc = a_compact.shape
    m2, n = dc.shape
    assert m == m2, (a_compact.shape, dc.shape)
    assert kc % block == 0, (kc, block)
    bm = _fit_block(m, bm)
    bn = _fit_block(n, bn)
    assert m % bm == 0 and n % bn == 0, (m, bm, n, bn)

    grid = (kc // block, n // bn, m // bm)
    kern = _acc_kernel(float(dp) if (scale and dp > 1) else 1.0,
                       contraction_axis=2, dims=((0,), (0,)), prefetch=False)

    return pl.pallas_call(
        kern,
        name="rdp_rows_wgrad",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, block), lambda k, j, i: (i, k)),
            pl.BlockSpec((bm, bn), lambda k, j, i: (i, j)),
        ],
        out_specs=pl.BlockSpec((block, bn), lambda k, j, i: (k, j)),
        scratch_shapes=[pltpu.VMEM((block, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((kc, n), dc.dtype),
        interpret=interpret,
    )(a_compact, dc)
