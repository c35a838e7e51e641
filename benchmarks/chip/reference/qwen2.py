"""The plain reference for Qwen2 with row-based dropout patterns.

Straight ``jax.numpy`` in float32 at ``precision="highest"``, imported by
nothing of the program and taking nothing it made: the weights come from
the benchmark's own generator (``models/qwen2.py``), the math below follows
the published Qwen2 block (pre-norm RMSNorm, GQA with q/k/v biases and
rotate-half RoPE, SiLU-gated FFN, tied embeddings).

The dropout pattern is the configuration's: hidden units of the FFN are
split into ``nb`` blocks; under a draw (dp, b) block j is kept iff
j mod dp == b, kept units are scaled by dp after the gate, and the same
draw holds at every layer of a step.  ``keep`` vectors carry it.

``low=True`` is the control: every matmul runs in fp8 with float32
accumulation (e4m3 operands, e5m2 gradients, per-tensor absmax scaling),
the step below the bfloat16 the configuration states.

Departures from a textbook pass, none of which changes the mathematics:
layers run as a ``lax.scan`` with rematerialisation, and attention and the
loss's unembedding run over blocks of rows, so that the reference fits one
chip at the benchmarked sizes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8_FWD = jnp.float8_e4m3fn       # operands, as fp8 training recipes use
F8_BWD = jnp.float8_e5m2         # gradients
DECAYED = ("tok", "wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down")


def fake_quant(x, dtype):
    """``x`` rounded to ``dtype`` under per-tensor absmax scaling, back in
    float32.  A product of two such values is exact in float32, so a
    float32 matmul of them is an fp8 matmul with float32 accumulation."""
    top = float(jnp.finfo(dtype).max)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_fp8(spec, a, b):
    return jnp.einsum(spec, fake_quant(a, F8_FWD), fake_quant(b, F8_FWD),
                      precision=HI)


def _mm_fp8_fwd(spec, a, b):
    aq, bq = fake_quant(a, F8_FWD), fake_quant(b, F8_FWD)
    return jnp.einsum(spec, aq, bq, precision=HI), (aq, bq)


def _mm_fp8_bwd(spec, res, ct):
    aq, bq = res
    _, vjp = jax.vjp(lambda a, b: jnp.einsum(spec, a, b, precision=HI),
                     aq, bq)
    return vjp(fake_quant(ct, F8_BWD))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def mm(spec: str, a, b, low: bool = False):
    """``einsum(spec, a, b)`` in float32, or, for the control, in fp8 with
    float32 accumulation (e4m3 operands, e5m2 gradients)."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if low:
        return _mm_fp8(spec, a, b)
    return jnp.einsum(spec, a, b, precision=HI)


def rms_norm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def rope(x, pos, theta):
    """Rotate-half RoPE on [B, S, H, D] at integer positions ``pos`` [S]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention_rows(q, k, v, q0: int, low: bool):
    """Causal attention of the queries at positions q0.. over all keys."""
    D = q.shape[-1]
    s = mm("bqhd,bkhd->bhqk", q, k, low) / math.sqrt(D)
    causal = (q0 + jnp.arange(q.shape[1]))[:, None] >= \
        jnp.arange(k.shape[1])[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    return mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, low)


def attention(q, k, v, low, rows: int = 1024):
    """Causal GQA attention.  q [B,S,H,D], k/v [B,S,KH,D] -> [B,S,H,D].
    Queries go in blocks of ``rows``, each rematerialised, so the scores
    of one block at a time are alive."""
    G = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    S = q.shape[1]
    if S <= rows:
        return _attention_rows(q, k, v, 0, low)
    block = jax.checkpoint(_attention_rows, static_argnums=(3, 4))
    return jnp.concatenate([block(q[:, i:i + rows], k, v, i, low)
                            for i in range(0, S, rows)], axis=1)


def layer(conf, lp, x, pos, keep, low):
    """One decoder layer.  x [B,S,d] f32; keep [S, d_ff] FFN multipliers."""
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    a = lp["attn"]
    h = rms_norm(x, lp["norm1"]["scale"], eps)
    q = mm("bsd,dhk->bshk", h, a["wq"], low) + a["bq"].astype(jnp.float32)
    k = mm("bsd,dhk->bshk", h, a["wk"], low) + a["bk"].astype(jnp.float32)
    v = mm("bsd,dhk->bshk", h, a["wv"], low) + a["bv"].astype(jnp.float32)
    o = attention(rope(q, pos, theta), rope(k, pos, theta), v, low)
    x = x + mm("bshk,hkd->bsd", o, a["wo"], low)
    f = lp["ffn"]
    h = rms_norm(x, lp["norm2"]["scale"], eps)
    g = jax.nn.silu(mm("bsd,df->bsf", h, f["w_up"], low)) \
        * mm("bsd,df->bsf", h, f["w_gate"], low)
    return x + mm("bsf,fd->bsd", g * keep[None], f["w_down"], low)


def keep_vector(d_ff: int, nb: int, dp: int, bias: int):
    """FFN multipliers of one draw: dp on kept units, 0 on dropped ones."""
    if dp <= 1:
        return jnp.ones((d_ff,), jnp.float32)
    block = jnp.arange(d_ff) // (d_ff // nb)
    return jnp.where(block % dp == bias, float(dp), 0.0)


def pattern(conf, dp: int, bias: int, seq_len: int):
    """``keep`` for ``clipped_grad`` under the draw (dp, bias): the FFN
    multipliers of every position of a ``seq_len`` row, [seq_len, d_ff]."""
    keep = keep_vector(conf["intermediate_size"],
                       conf["program"]["pattern_nb"], dp, bias)
    return jnp.broadcast_to(keep, (seq_len, keep.shape[0]))


def trunk(conf, w, tokens, keep, low=False):
    """Final-normed hidden states [B,S,d] of ``tokens`` [B,S]; ``keep``
    [S, d_ff] gives each position's FFN multipliers."""
    x = w["embed"]["tok"].astype(jnp.float32)[tokens]
    pos = jnp.arange(tokens.shape[1])

    def body(x, lp):
        return layer(conf, lp, x, pos, keep, low), None

    (stack,) = w["stacks"]
    x, _ = jax.lax.scan(jax.checkpoint(body), x, stack)
    return rms_norm(x, w["final_norm"]["scale"], conf["rms_norm_eps"])


def logits(w, x, low=False):
    """Tied unembedding of hidden states [..., d] -> [..., vocab]."""
    return mm("...d,vd->...v", x, w["embed"]["tok"], low)


def lm_loss(conf, w, tokens, labels, keep, low=False, rows: int = 512):
    """Mean next-token cross entropy, the unembedding over row blocks."""
    x = trunk(conf, w, tokens, keep, low)
    d = x.shape[-1]
    rows = min(rows, labels.size)
    xs = x.reshape(-1, rows, d)
    ys = labels.reshape(-1, rows)

    @jax.checkpoint
    def block_nll(xb, yb):
        lg = logits(w, xb, low)
        lse = jax.nn.logsumexp(lg, -1)
        return jnp.sum(lse - jnp.take_along_axis(lg, yb[:, None], -1)[:, 0])

    def body(acc, xy):
        return acc + block_nll(*xy), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ys))
    return total / labels.size


# --------------------------------------------------------------------------
# training: loss, clipped gradient, AdamW
# --------------------------------------------------------------------------

def cosine_lr(job: dict, step: int) -> float:
    """The job's learning rate at ``step``: linear warm-up, then cosine to
    ``min_lr_frac`` of the base over ``total_steps``."""
    base, warm = job["lr"], job["warmup_steps"]
    if step < warm:
        return base * step / max(warm, 1)
    prog = min(max((step - warm) / max(job["total_steps"] - warm, 1), 0.0),
               1.0)
    lo = job["min_lr_frac"]
    return base * (lo + (1 - lo) * 0.5 * (1 + math.cos(math.pi * prog)))


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def clipped_grad(conf, job, w, batch, keep, low=False):
    """(loss, gradient, the factor that clips it to the job's global norm)
    at ``w``, the gradient taken in float32; the clipped gradient is
    gradient × factor."""
    loss, g = jax.value_and_grad(
        lambda w: lm_loss(conf, w, batch["tokens"], batch["labels"], keep,
                          low))(jax.tree.map(lambda x: x.astype(jnp.float32),
                                             w))
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    return loss, g, jnp.minimum(1.0, job["clip_norm"] / jnp.maximum(norm,
                                                                    1e-9))


def adamw(job, w, g, scale, m, v, count: int, lr: float):
    """One AdamW update with the gradient ``g × scale``, in float32; each
    parameter is kept in its own type (the configuration's), so it is
    rounded to it after the update, as a model kept in that type is."""
    b1, b2, eps, wd = job["b1"], job["b2"], job["eps"], job["weight_decay"]
    bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count

    def upd(path, p, gl, ml, vl):
        dt, p = p.dtype, p.astype(jnp.float32)
        gl = gl * scale
        ml = b1 * ml + (1 - b1) * gl
        vl = b2 * vl + (1 - b2) * gl * gl
        u = (ml / bc1) / (jnp.sqrt(vl / bc2) + eps)
        if _leaf_name(path) in DECAYED:
            u = u + wd * p
        return (p - lr * u).astype(dt), ml, vl

    out = jax.tree_util.tree_map_with_path(upd, w, g, m, v)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)
