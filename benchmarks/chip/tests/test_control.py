"""The control and the planted faults, at a size a CPU test can hold.

A sound tiny run of each cell is correct; the reference put in the
program's place one precision lower (fp8) reads above the limits on at
least one number; and each fault the cells can have, planted under the
timed path while the rest of the run goes on as usual, turns ``correct``
false.  The chip-size readings these mirror are in PERF.md.
"""
import pytest

import run as R
import tiny


def _run(name, control=False):
    cell = tiny.cell(name)
    return R.run_cell(cell, tiny.SEED, 0.5, False, tiny.CPU,
                      control=control)


def _over(readings: dict, limits: dict) -> list:
    return [k for k, v in readings.items() if v > limits[k]]


@pytest.mark.parametrize("name", ["train-rdp-p0.5", "train-dense"])
def test_sound_run_passes_and_control_fails(name):
    res = _run(name, control=True)
    assert res["correct"], res["compared"]
    assert _over(res["control"], tiny.LIMITS), res["control"]


def test_state_left_unchanged_fails(monkeypatch):
    import repro.train.distributed as D
    make = D.make_train_step

    def frozen(*a, **k):
        step = make(*a, **k)

        def run(params, opt, batch, lr):
            _, _, metrics = step(params, opt, batch, lr)
            return params, opt, metrics
        return run

    monkeypatch.setattr(D, "make_train_step", frozen)
    res = _run("train-rdp-p0.5")
    assert not res["correct"]
    assert res["compared"]["update_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_fails(monkeypatch):
    import repro.train.train_step as TS
    loss = TS.lm_loss

    def half(cfg, params, batch, pat):
        n = batch["tokens"].shape[-1] // 2
        return loss(cfg, params, {k: v[..., :n] for k, v in batch.items()},
                    pat)

    monkeypatch.setattr(TS, "lm_loss", half)
    res = _run("train-rdp-p0.5")
    assert not res["correct"], res["compared"]
