"""The reference a cell is judged against is the one its configuration's
``model_type`` names, and its dropout draw is the one the driver built
before the draw moved into the reference."""
import types

import jax.numpy as jnp
import numpy as np
import pytest

import run as R
import tiny

CELLS = ["train-rdp-p0.5", "train-dense"]


def _shifted(ref, nats: float):
    """A stand-in for ``ref`` whose every loss reads ``nats`` higher."""
    def clipped_grad(*a, **k):
        loss, g, scale = ref.clipped_grad(*a, **k)
        return loss + nats, g, scale
    return types.SimpleNamespace(pattern=ref.pattern, adamw=ref.adamw,
                                 cosine_lr=ref.cosine_lr,
                                 clipped_grad=clipped_grad)


@pytest.mark.parametrize("nats", [0.0, 0.05])
@pytest.mark.parametrize("name", CELLS)
def test_cell_is_judged_by_the_reference_it_is_given(name, nats):
    stand_in = _shifted(tiny.cell(name).reference, nats)
    res = R.run_cell(tiny.cell(name, reference=stand_in), tiny.SEED, 0.5,
                     False, tiny.CPU)
    assert res["correct"] == (nats == 0.0), res["compared"]


@pytest.mark.parametrize("name", CELLS)
def test_pattern_is_the_broadcast_keep_vector(name):
    bench = R.load_json(R.ROOT / "BENCHMARK.json")
    cell = R.Cell(bench, name)
    conf, seq, ref = cell.conf, cell.mix["seq_len"], cell.reference
    cfg = cell.model.program_config(conf, conf["program"])
    buckets = cell.driver.make_plan(cell.model, cfg, cell.mix).buckets()
    assert buckets
    for dp, b in buckets:
        keep = ref.keep_vector(conf["intermediate_size"],
                               conf["program"]["pattern_nb"], dp, b)
        want = np.asarray(jnp.broadcast_to(keep, (seq, keep.shape[0])))
        got = np.asarray(ref.pattern(conf, dp, b, seq))
        assert (got.shape, got.dtype) == (want.shape, want.dtype)
        assert got.tobytes() == want.tobytes(), (dp, b)
