"""The command's contract where no chip is: it exits nonzero and prints no
result, also in a directory holding only the benchmark's files."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
ARGS = ["--workload", "train-rdp-p0.5", "--seed", str(2**31 + 5),
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", *ARGS], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if "metrics" in json.loads(line):
                return False
        except ValueError:
            pass
    return True


def test_without_a_chip_exits_nonzero():
    p = _run(ROOT)
    assert p.returncode != 0, p.stdout
    assert _no_result(p.stdout)
    assert "no accelerator" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0, p.stdout
    assert _no_result(p.stdout)


# what run.py's and drivers/train.py's docstrings ask of an adapter and
# of a training reference
ADAPTER = ("make_weights", "program_config", "check_layout", "plan_blocks",
           "train_step_flops", "dims", "tiny")
TRAIN_REFERENCE = ("pattern", "clipped_grad", "adamw", "cosine_lr")


def test_every_name_resolves_to_files():
    import run as R
    bench = R.load_json(ROOT / "BENCHMARK.json")
    for w in bench["workloads"]:
        cell = R.Cell(bench, w["name"])
        assert cell.end_to_end and cell.per_layer
        for m in cell.per_layer:
            assert (BENCH / "metrics" / f"{m['name']}.py").exists()
        assert all(callable(getattr(cell.model, f, None)) for f in ADAPTER)
        if cell.mix["kind"] == "train":
            assert all(callable(getattr(cell.reference, f, None))
                       for f in TRAIN_REFERENCE)
    for c in bench["configs"]:
        assert (ROOT / c["file"]).exists()
        kind = R.load_json(ROOT / c["file"])["model_type"]
        assert (BENCH / "models" / f"{kind}.py").exists()
        assert (BENCH / "reference" / f"{kind}.py").exists()
