"""Compile the main path's Pallas kernels at Qwen2-1.5B widths for a
described TPU v5e — no chip needed.  Interpret mode (every other kernel
test) never sees what Mosaic refuses: misaligned blocks, VMEM overruns.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file.  Keep these tests in this one file for the same reason.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import attention, ops
from repro.kernels.autodiff import rdp_matmul_cols_vjp, rdp_matmul_rows_vjp
from repro.kernels.fused_ffn import fused_ffn_gated_vjp
from repro.kernels.rdp_matmul import rdp_matmul_cols, rdp_matmul_rows

CFG = get_config("qwen2_1_5b")
M, K, N = 4096, CFG.d_model, CFG.d_ff     # tokens per train step, widths
ATTN_KERNELS = {"attn_causal_fwd", "attn_causal_dkv", "attn_causal_dq"}
BLOCK = N // CFG.pattern_nb
DPS = (2, 7)


@pytest.fixture(scope="module")
def topo():
    # libtpu logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but
    cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, sharding, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args) -> str:
    """Compile for the described chip; the HLO text of the result."""
    return jax.jit(fn).lower(*args).compile().as_text()


def test_widths_are_lane_aligned():
    assert N % CFG.pattern_nb == 0 and BLOCK % 128 == 0, BLOCK


@pytest.mark.parametrize("dp", DPS)
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_rdp_matmul_cols_compiles(one_chip, no_persistent_cache, dp, grad):
    """Up-projection [M, K] @ W[K, N][:, kept] — the fwd kernel, or the
    custom-VJP op under value_and_grad (fwd + compact dgrad/wgrad)."""
    a, w = _sds((M, K), one_chip), _sds((K, N), one_chip)
    b = _sds((), one_chip, jnp.int32)
    if grad:
        def fn(a, w, b):
            return jax.value_and_grad(lambda a, w: rdp_matmul_cols_vjp(
                a, w, b, dp, BLOCK, True, False).astype(jnp.float32).sum(),
                argnums=(0, 1))(a, w)
    else:
        def fn(a, w, b):
            return rdp_matmul_cols(a, w, b, dp=dp, block=BLOCK,
                                   interpret=False)
    hlo = _compile(fn, a, w, b)
    assert hlo.count("tpu_custom_call") >= (3 if grad else 1)


@pytest.mark.parametrize("dp", DPS)
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_rdp_matmul_rows_compiles(one_chip, no_persistent_cache, dp, grad):
    """Down-projection Ac[M, N/dp] @ W[N, K][kept, :]."""
    ac, w = _sds((M, N // dp), one_chip), _sds((N, K), one_chip)
    b = _sds((), one_chip, jnp.int32)
    if grad:
        def fn(ac, w, b):
            return jax.value_and_grad(lambda ac, w: rdp_matmul_rows_vjp(
                ac, w, b, dp, BLOCK, False, False).astype(jnp.float32).sum(),
                argnums=(0, 1))(ac, w)
    else:
        def fn(ac, w, b):
            return rdp_matmul_rows(ac, w, b, dp=dp, block=BLOCK,
                                   interpret=False)
    hlo = _compile(fn, ac, w, b)
    assert hlo.count("tpu_custom_call") >= (3 if grad else 1)


@pytest.mark.parametrize("dp", DPS)
def test_fused_ffn_gated_grad_compiles(one_chip, no_persistent_cache, dp):
    """The fused gated FFN under value_and_grad: the fused forward kernel
    plus the compact backward that rematerializes the hidden (8 kernels)."""
    x = _sds((M, K), one_chip)
    wu, wg, wd = (_sds((K, N), one_chip), _sds((K, N), one_chip),
                  _sds((N, K), one_chip))
    b = _sds((), one_chip, jnp.int32)

    def fn(x, wu, wg, wd, b):
        def loss(x, wu, wg, wd):
            y = fused_ffn_gated_vjp(x, wu, wg, wd, b, dp, BLOCK, jax.nn.silu,
                                    False)
            return y.astype(jnp.float32).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(x, wu, wg, wd)

    hlo = _compile(fn, x, wu, wg, wd, b)
    assert hlo.count("tpu_custom_call") >= 9


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_attention_kernel_compiles(one_chip, no_persistent_cache, grad):
    """Causal flash attention at the training cell's shape (S 4096, 12
    query and 2 KV heads of 128): the forward kernel, or all three under
    ``vjp`` — Mosaic's block and VMEM checks, which interpret mode skips."""
    S, H, KH, D = M, CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
    q, k, v = (_sds((1, S, h, D), one_chip) for h in (H, KH, KH))

    def fn(q, k, v):
        if not grad:
            return attention.causal_attention(q, k, v, False)
        out, vjp = jax.vjp(lambda q, k, v: attention.causal_attention(
            q, k, v, False), q, k, v)
        return vjp(out)

    hlo = _compile(fn, q, k, v)
    names = set(re.findall(r"%(attn_causal_\w+?)(?:\.\d+)? = ", hlo))
    assert names == (ATTN_KERNELS if grad else {"attn_causal_fwd"}), names


@pytest.mark.parametrize("entry", ["rdp_up", "rdp_down", "fused_ffn"])
def test_misaligned_block_raises_clear_error(one_chip, no_persistent_cache,
                                             monkeypatch, entry):
    """70-wide blocks (Qwen2-1.5B's d_ff over 128 pattern blocks) stop
    with an error naming the block and d_ff before Mosaic sees them."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    block, dp = 70, 2
    x, b = _sds((M, K), one_chip), _sds((), one_chip, jnp.int32)
    w_up, w_down = _sds((K, N), one_chip), _sds((N, K), one_chip)
    h = _sds((M, N // dp), one_chip)
    calls = {
        "rdp_up": lambda: jax.jit(lambda x, w, b: ops.rdp_up(
            x, w, b, dp=dp, block=block)).lower(x, w_up, b),
        "rdp_down": lambda: jax.jit(lambda h, w, b: ops.rdp_down(
            h, w, b, dp=dp, block=block)).lower(h, w_down, b),
        "fused_ffn": lambda: jax.jit(lambda x, wu, wd, b: ops.fused_ffn(
            x, wu, wd, b, dp=dp, block=block)).lower(x, w_up, w_down, b),
    }
    with pytest.raises(ValueError, match=rf"block of {block} over d_ff={N}"):
        calls[entry]()


def test_step_names_are_stable(one_chip, no_persistent_cache, monkeypatch):
    """What a device trace reads the training step by: its executable is
    ``jit_step``, each compact kernel's instruction carries a name of
    ``PATTERN_KERNELS`` (the explicit ``pallas_call`` names), the causal
    attention kernels carry their own names, none of them a pattern
    kernel's, under the ``attn`` scope, and the five named scopes reach the
    ``op_name`` metadata, forward and backward.  One rdp bucket of a small
    model with lane-wide pattern blocks and 128-wide heads."""
    import dataclasses
    from pathlib import Path

    from repro.configs import get_smoke
    from repro.core.plan import DropoutPlan
    from repro.data.pipeline import SyntheticLMData
    from repro.models import init_lm, materialize
    from repro.optim.optimizers import AdamW
    from repro.train.distributed import DistributedTrainer, TrainerConfig
    monkeypatch.syspath_prepend(str(Path(__file__).resolve().parents[1]
                                    / "benchmarks" / "chip"))
    from kernels import PATTERN_KERNELS, base_name
    from trace_layers import SCOPES, scope_of

    cfg = dataclasses.replace(get_smoke("qwen2_1_5b"), d_ff=512,
                              pattern_nb=4, head_dim=128, dtype="bfloat16")
    params = materialize(jax.random.PRNGKey(0), init_lm(cfg)[0])
    plan = DropoutPlan(family="rdp", dist=(0.5, 0.5), nb=cfg.pattern_nb,
                       block=cfg.d_ff // cfg.pattern_nb, backend="pallas")
    tr = DistributedTrainer(cfg, AdamW(), params, plan=plan,
                            tcfg=TrainerConfig(steps=1))
    batch = jax.tree.map(jnp.asarray, SyntheticLMData(
        vocab=cfg.vocab, seq_len=128, global_batch=1).batch(0))
    fn = tr._step_fn(2, 1, batch)
    assert fn.lower(tr.state, batch, jnp.float32(0)).as_text().startswith(
        "module @jit_step")

    monkeypatch.setattr(ops, "_interpret", lambda: False)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    hlo = jax.jit(fn.__wrapped__).lower(
        jax.tree.map(sds, tr.state), jax.tree.map(sds, batch),
        sds(jnp.float32(0))).compile().as_text()
    assert hlo.startswith("HloModule jit_step")
    calls = re.findall(r"%(\S+) = ([^\n]*)custom_call_target="
                       r"\"tpu_custom_call\"([^\n]*)", hlo)
    kernels = {base_name(m) for m, _, _ in calls}
    pattern = {"rdp_matmul_cols", "rdp_matmul_rows", "rdp_cols_dgrad",
               "rdp_cols_wgrad", "rdp_rows_dgrad", "rdp_rows_wgrad"}
    assert kernels == pattern | ATTN_KERNELS
    assert pattern <= PATTERN_KERNELS
    assert not ATTN_KERNELS & PATTERN_KERNELS
    for m, a, b in calls:
        if base_name(m) in ATTN_KERNELS:
            op = re.search(r'op_name="([^"]*)"', a + b)
            assert op and scope_of(op.group(1)) == "attn", (m, op)
    paths = re.findall(r'op_name="([^"]*)"', hlo)
    assert {scope_of(p) for p in paths} == set(SCOPES) | {""}
    # the backward pass keeps the scopes too
    backward = {scope_of(p) for p in paths if "transpose(" in p}
    assert {"embed", "attn", "ffn", "head"} <= backward
