"""Operation and byte counts against hand arithmetic at the Qwen2 smoke
shapes (d 64, 4 query and 2 key/value heads of 16, d_ff 256, vocab 512,
2 layers) and at the published widths."""
import pytest

import flops as F
import run as R

SMOKE = F.Dims(layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
               d_ff=256, vocab=512)
FULL = F.Dims(layers=8, d_model=1536, n_heads=12, n_kv_heads=2,
              head_dim=128, d_ff=8960, vocab=151936)


def test_pieces_by_hand():
    # q 64x64, k and v 64x32 each, o 64x64: 2*(4096+2048+2048+4096)
    assert F.attn_proj_flops(SMOKE) == 2 * (64 * 64 + 2 * 64 * 32 + 64 * 64)
    assert F.attn_core_flops(SMOKE, 10) == 4 * 64 * 10
    assert F.ffn_flops(SMOKE) == 6 * 64 * 256
    assert F.ffn_flops(SMOKE, 4) == 6 * 64 * 64
    assert F.head_flops(SMOKE) == 2 * 64 * 512
    assert F.causal_keys(4) == 2.5


def test_train_step_by_hand():
    tokens = seq = 32
    per_layer = 24576 + 4 * 64 * 16.5 + 98304 / 2
    want = 3 * tokens * (2 * per_layer + 65536)
    assert F.train_step_flops(SMOKE, tokens, seq, dp=2) == pytest.approx(want)


def test_published_width_step():
    # 1.62e13 dense and 1.21e13 at dp 2 for 8 layers x 4096 tokens
    assert F.train_step_flops(FULL, 4096, 4096, 1) == pytest.approx(
        1.6172e13, rel=1e-3)
    assert F.train_step_flops(FULL, 4096, 4096, 2) == pytest.approx(
        1.2114e13, rel=1e-3)


def test_kernel_work_by_hand():
    ops, nbytes = F.train_ffn_kernel_work(SMOKE, 32, 4)
    assert ops == 3 * 32 * 2 * 6 * 64 * 64
    x, h, w = 32 * 64 * 2, 32 * 64 * 2, 64 * 64 * 2
    assert nbytes == 2 * 9 * (x + h + w)


@pytest.mark.parametrize("dp", [1, 2, 5, 7])
def test_adapter_counts_the_step_as_flops_does(dp):
    # train.mfu_pct reads the adapter's count; it must be flops.py's
    conf = R.load_json(R.BENCH / "configs" / "qwen2-1.5b-train.json")
    model = R.load_module(R.BENCH / "models" / f"{conf['model_type']}.py")
    assert model.train_step_flops(conf, 4096, 4096, dp) == \
        F.train_step_flops(model.dims(conf), 4096, 4096, dp)
