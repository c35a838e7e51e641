"""Cells at a size a CPU test can hold: each configuration shrunk by its
adapter's ``tiny``, and short mixes."""
import copy
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def conf(name: str) -> dict:
    import run as R
    c = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return R.load_module(BENCH / "models" / f"{c['model_type']}.py").tiny(c)


def mix(name: str) -> dict:
    m = copy.deepcopy(json.loads(
        (BENCH / "traffic" / f"{name}.json").read_text()))
    m["seq_len"] = 64
    m["trace"] = {"lead_s": 0.0, "seconds": 0.5}
    return m


# Limits for the tiny cells, set as the real ones are, from their own
# readings on the CPU: sound runs read loss 0.007-0.008, grad 0.004-0.009
# and update 0.018-0.026; the fp8 control loss 0.062 and grad 0.083; half
# of the rows loss 0.24.
LIMITS = {"loss_gap": 0.02, "grad_gap": 0.03, "update_gap": 0.1}


def cell(name: str, **over):
    """A ``run.Cell`` for cell ``name`` at tiny size."""
    import run as R
    bench = R.load_json(R.ROOT / "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}[name]
    return R.Cell(bench, name, conf=conf(entry["config"]),
                  mix=mix(entry["traffic"]),
                  limits={k: {"limit": v} for k, v in LIMITS.items()},
                  **over)


CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
SEED = 2**31 + 11
