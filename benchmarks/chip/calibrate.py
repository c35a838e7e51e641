"""Readings for the limits of a cell's numbers, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...] [--control]

Runs the cell once per seed in one process, as ``run.py`` would, and
prints one JSON line per seed with the numbers compared; with
``--control`` also the control's readings (the reference one precision
lower, in the program's place) and, for training, those of the reference
fed half of each step's rows.  The benchmark's own runs never do this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run as R  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    cell = R.Cell(R.load_json(R.ROOT / "BENCHMARK.json"), args.workload)
    R.configure_jax()
    device = R.device_info(cell.entry["chips"])
    for seed in args.seeds:
        res = R.run_cell(cell, seed, args.seconds, False, device,
                         control=args.control)
        keep = {k: res[k] for k in ("correct", "attempted", "failed",
                                     "metrics", "compiles_in_window",
                                     "notes", "compared") if k in res}
        for k in ("control", "fault_half_batch"):
            if k in res:
                keep[k] = res[k]
        print(json.dumps(dict(keep, seed=seed)), flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
