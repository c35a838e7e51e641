#!/usr/bin/env python
"""Attention parity probe: the causal flash kernel against the XLA scan.

    python tools/attn_parity.py [--seq 4096] [--seed 0]

Builds one ``attention_block`` at Qwen2-1.5B's attention widths (hidden
1536, 12 query and 2 KV heads of 128, q/k/v biases, RoPE theta 1e6), B 1,
bf16, every weight and bias N(0, 0.02²) (the published
``initializer_range``), the input N(0, 1) as a normed hidden state is.  It
takes the gradient for one fixed random bf16 cotangent of the output, with
the attention core run three ways:

* ``reference``: the scan (``layers.scan_attention``) on the same values in
  float32, every matmul at ``precision="highest"``;
* ``scan``: the scan in bf16, as the program ran before the kernel;
* ``kernel``: the kernel (``kernels/attention.causal_attention``; in
  interpret mode where JAX runs on the CPU).

For the output and the gradients of wq, wk, wv and wo it prints, for the
scan and the kernel, the relative error against the reference (norm of
the difference over the reference's norm) and the share of elements whose
sign differs from the reference's among those under the median reference
magnitude, and each ratio kernel/scan.  The last line is JSON with the
numbers and ``within``: whether the kernel is at most ``1.1×`` the scan on
every row and both measures.  On the CPU the scan's matmuls are exact
float32, so only a TPU run compares the two at the chip's precision.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.kernels import attention as attn_kernel  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models import layers as L  # noqa: E402

D_MODEL, HEADS, KV_HEADS, HEAD_DIM, THETA, STD = 1536, 12, 2, 128, 1e6, 0.02
ROWS = ("out", "wq", "wk", "wv", "wo")
WITHIN = 1.1


def _kernel_core(q, k, v, **_):
    return attn_kernel.causal_attention(q, k, v, ops._interpret())


def _scan_core(q, k, v, *, causal=True, window=None, chunk=1024, **_):
    return L.scan_attention(q, k, v, causal=causal, window=window,
                            chunk=chunk)


@contextlib.contextmanager
def _core(fn):
    """Run ``attention_block`` with ``fn`` as its attention core."""
    saved = L.blockwise_attention
    L.blockwise_attention = fn
    try:
        yield
    finally:
        L.blockwise_attention = saved


def make_inputs(seq: int, seed: int):
    """Weights, biases, input and output cotangent, bf16, from ``seed``."""
    shapes = {"wq": (D_MODEL, HEADS, HEAD_DIM),
              "wk": (D_MODEL, KV_HEADS, HEAD_DIM),
              "wv": (D_MODEL, KV_HEADS, HEAD_DIM),
              "wo": (HEADS, HEAD_DIM, D_MODEL),
              "bq": (HEADS, HEAD_DIM), "bk": (KV_HEADS, HEAD_DIM),
              "bv": (KV_HEADS, HEAD_DIM),
              "x": (1, seq, D_MODEL), "ct": (1, seq, D_MODEL)}
    rng = np.random.default_rng(seed)
    vals = {n: jnp.asarray(rng.standard_normal(shape, np.float32)
                           * (1.0 if n in ("x", "ct") else STD),
                           jnp.bfloat16) for n, shape in shapes.items()}
    x, ct = vals.pop("x"), vals.pop("ct")
    return vals, x, ct


def run(core, params, x, ct, dtype, precision=None):
    """Output and weight gradients of one attention block under ``core``."""
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    x, ct = x.astype(dtype), ct.astype(dtype)

    def block(p, x):
        return L.attention_block(p, x, n_heads=HEADS, n_kv=KV_HEADS,
                                 head_dim=HEAD_DIM, rope_theta=THETA)

    @jax.jit
    def fwd_bwd(p, x, ct):
        out, vjp = jax.vjp(block, p, x)
        return out, vjp(ct)[0]

    with _core(core), jax.default_matmul_precision(precision):
        out, grads = fwd_bwd(params, x, ct)
    got = {"out": out}
    got.update({n: grads[n] for n in ROWS[1:]})
    return {n: np.asarray(a.astype(jnp.float32)) for n, a in got.items()}


def rel_err(a, ref) -> float:
    return float(np.linalg.norm(a - ref) / np.linalg.norm(ref))


def sign_share(a, ref) -> float:
    """Share of sign disagreements among elements under the median
    reference magnitude."""
    mag = np.abs(ref)
    small = mag < np.median(mag)
    wrong = small & (np.sign(a) != np.sign(ref))
    return float(wrong.sum() / max(small.sum(), 1))


def compare(seq: int, seed: int) -> dict:
    params, x, ct = make_inputs(seq, seed)
    ref = run(_scan_core, params, x, ct, jnp.float32, "highest")
    scan = run(_scan_core, params, x, ct, jnp.bfloat16)
    kern = run(_kernel_core, params, x, ct, jnp.bfloat16)
    table = {}
    for n in ROWS:
        row = {"rel_scan": rel_err(scan[n], ref[n]),
               "rel_kernel": rel_err(kern[n], ref[n]),
               "sign_scan": sign_share(scan[n], ref[n]),
               "sign_kernel": sign_share(kern[n], ref[n])}
        row["rel_ratio"] = row["rel_kernel"] / max(row["rel_scan"], 1e-30)
        row["sign_ratio"] = row["sign_kernel"] / max(row["sign_scan"], 1e-30)
        table[n] = row
    within = all(r["rel_ratio"] <= WITHIN and r["sign_ratio"] <= WITHIN
                 for r in table.values())
    return {"seq": seq, "seed": seed, "device": jax.devices()[0].device_kind,
            "rows": table, "within": within}


def print_table(res: dict) -> None:
    print(f"attention parity, S={res['seq']} seed={res['seed']} on "
          f"{res['device']} (against the float32 'highest' scan)")
    print(f"{'':4} {'rel scan':>11} {'rel kernel':>11} {'ratio':>7} "
          f"{'sign scan':>10} {'sign kernel':>11} {'ratio':>7}")
    for n, r in res["rows"].items():
        print(f"{n:4} {r['rel_scan']:11.4e} {r['rel_kernel']:11.4e} "
              f"{r['rel_ratio']:7.3f} {r['sign_scan']:10.4%} "
              f"{r['sign_kernel']:11.4%} {r['sign_ratio']:7.3f}")
    print(json.dumps(res))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    print_table(compare(args.seq, args.seed))
    return 0


if __name__ == "__main__":
    sys.exit(main())
