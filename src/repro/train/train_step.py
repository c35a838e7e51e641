"""Distributed train step: microbatched grad accumulation + optimizer update.

``make_train_step`` returns a pure function
    (params, opt_state, batch, lr) -> (params, opt_state, metrics)
suitable for jit with shardings.  The dropout pattern (dp, bias) is baked in
statically — the trainer keeps one compiled executable per pattern bucket
(DESIGN.md §2) and dispatches per step.

The pattern applies to BOTH passes: ``jax.value_and_grad`` differentiates
through the pattern FFNs, and every backend keeps the backward matmuls
compact — "slice"/"gather" because XLA transposes the strided slice/gather,
"pallas" through the dropout-aware dgrad/wgrad kernels registered via
``jax.custom_vjp`` (kernels/autodiff.py, DESIGN.md §9).  That is the
paper's Fig. 3 step 4: dgrad/wgrad skip dropped blocks too, so a step runs
at ~1/dp of the dense FFN FLOPs end-to-end.

Gradient accumulation: the global batch is split into ``microbatches``
chunks scanned sequentially; grads are averaged in fp32.  Optional TernGrad
compression (parallel/compression.py) is applied to the accumulated grads
before the optimizer (the all-reduce over 'pod'/'data' then moves ternary
values — the compression the paper cites as compatible).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import NO_PATTERN
from repro.models.transformer import ModelConfig, lm_loss
from repro.optim.optimizers import clip_by_global_norm
from repro.parallel.compression import terngrad_compress_decompress


def _split_micro(batch, m: int):
    def sp(x):
        return x.reshape((m, x.shape[0] // m) + x.shape[1:])
    return jax.tree.map(sp, batch)


def make_train_step(cfg: ModelConfig, optimizer, *, microbatches: int = 1,
                    pat=NO_PATTERN, clip_norm: float = 1.0,
                    compress_grads: bool = False, acc_shardings=None):
    """``acc_shardings``: optional pytree of NamedShardings for the f32
    grad-accumulation buffers (normally the ZeRO-1 optimizer shardings —
    ``DistributedTrainer`` wires its ``zero1_opt_sharding`` layout in here).
    Without it XLA may keep the scan-carried grads replicated and all-gather
    every per-micro partial grad (measured: +0.4 TB/device on deepseek).
    The same constraint is applied to the single-microbatch grads, so the
    backward's partial sums reduce straight into ZeRO-1 shards there too."""
    def loss_fn(params, mb):
        loss, metrics = lm_loss(cfg, params, mb, pat)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def _constrain_acc(g):
        if acc_shardings is None:
            return g
        return jax.tree.map(jax.lax.with_sharding_constraint, g,
                            acc_shardings)

    def train_step(params, opt_state, batch, lr):
        if microbatches > 1:
            micro = _split_micro(batch, microbatches)

            def acc_body(carry, mb):
                gacc, lacc = carry
                (loss, _), grads = grad_fn(params, mb)
                # pin the PER-MICRO grads to the accumulator sharding too,
                # so partial-sum grads reduce into shards instead of being
                # materialized replicated each micro (embed-grad fix)
                grads = _constrain_acc(
                    jax.tree.map(lambda g: g.astype(jnp.float32), grads))
                gacc = jax.tree.map(
                    lambda a, g: a + g / microbatches, gacc, grads)
                return (_constrain_acc(gacc), lacc + loss / microbatches), None

            g0 = _constrain_acc(jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params))
            (grads, loss), _ = jax.lax.scan(acc_body, (g0, 0.0), micro)
        else:
            (loss, _), grads = grad_fn(params, batch)
            grads = _constrain_acc(
                jax.tree.map(lambda g: g.astype(jnp.float32), grads))

        if compress_grads:
            grads = terngrad_compress_decompress(grads)
        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, clip_norm)
            params, opt_state = optimizer.update(params, grads, opt_state,
                                                 lr)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    return train_step
