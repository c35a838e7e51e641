"""Run one benchmark cell once, on the chip, and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration file, its mix ``traffic/<traffic>.json``, the model adapter
``models/<model_type>.py`` and the plain reference
``reference/<model_type>.py`` (``model_type`` from the configuration), the
driver ``drivers/<kind>.py`` the mix names, the limits
``limits/<cell>.json``, and, with ``--trace 1``, one reader
``metrics/<metric>.py`` per per-layer metric.  Nothing here knows a cell
or a model by its name.

A model adapter provides:

* ``make_weights(conf, key)`` — every weight from the key, in one jitted
  call, in the program's parameter tree and the configuration's types;
* ``program_config(conf, program)`` — the program's ``ModelConfig``;
* ``check_layout(cfg, weights)`` — raises unless the weights are exactly
  the program's parameter tree;
* ``plan_blocks(cfg)`` — ``(nb, block)``: the pattern blocks of the
  dropped dimension and the units in each, for the program's plan;
* ``train_step_flops(conf, tokens, seq_len, dp)`` — operations one
  training step requires under a draw of period ``dp`` (``train.mfu_pct``);
* ``dims(conf)`` — ``flops.Dims`` for the compact FFN kernels' counts
  (only cells that report ``train.ffn_roofline``);
* ``tiny(conf)`` — the configuration at a size a CPU test can hold.

What a training reference provides is in ``drivers/train.py``.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``, then
``compared``); the numbers compared with their limits are also the last
lines of stderr.  Without an accelerator, or with fewer chips than the
cell asks for, it exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
CACHE = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
NO_CHIP = 3


def load_module(path: Path):
    """Import a benchmark file by path (names may hold dots and dashes)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """A cell's files, resolved from ``BENCHMARK.json`` by name."""

    def __init__(self, bench: dict, name: str, **given):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; "
                             f"known: {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        confs = {c["name"]: c for c in bench["configs"]}
        # ``given`` replaces files (the tests run cells at small sizes,
        # or against a stand-in reference)
        self.conf = given.get("conf") or load_json(
            ROOT / confs[self.entry["config"]]["file"])
        self.mix = given.get("mix") or load_json(
            BENCH / "traffic" / f"{self.entry['traffic']}.json")
        self.limits = given.get("limits") or load_json(
            BENCH / "limits" / f"{name}.json")
        kind = self.conf["model_type"]
        self.model = given.get("model") or load_module(
            BENCH / "models" / f"{kind}.py")
        self.reference = given.get("reference") or load_module(
            BENCH / "reference" / f"{kind}.py")
        self.driver = load_module(BENCH / "drivers"
                                  / f"{self.mix['kind']}.py")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])
                          and m["moves"] in reported]


class Context:
    """What a driver gets: the cell's data, the run's arguments, and the
    hooks that time, trace and count the window."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 control: bool = False):
        self.cell, self.seed, self.seconds, self.trace = (
            cell, seed, seconds, trace)
        # calibration only: also read the control and the planted faults
        self.control = control
        self.conf, self.mix = cell.conf, cell.mix
        self.model, self.reference = cell.model, cell.reference
        self.counters: dict = {}
        self.t_window = None
        self.memory_peak = self.memory_limit = None
        self._compiles = 0
        self._in_window = False
        self._tracing = None
        tr = self.mix.get("trace", {})
        self.trace_lead = float(tr.get("lead_s", 0.0))
        self.trace_len = float(tr.get("seconds", seconds))
        self.trace_window = None

    # -- compile counting -------------------------------------------------
    def on_event(self, name: str, *_a, **_k) -> None:
        if self._in_window and name.endswith("backend_compile_duration"):
            self._compiles += 1

    def window_open(self) -> None:
        """Set-up ends here: everything after is the measured window."""
        self.t_window = time.perf_counter()
        self._in_window = True

    def window_closed(self) -> None:
        self._in_window = False
        self.stop_trace()

    @property
    def compiles_in_window(self) -> int:
        return self._compiles

    # -- tracing ----------------------------------------------------------
    @contextlib.contextmanager
    def maybe_traced(self, elapsed: float):
        """Around one call into the program: starts the profiler once the
        window is ``lead_s`` old, stops it ``seconds`` later."""
        import jax
        if (self.trace and self._tracing is None
                and self.trace_window is None and elapsed >= self.trace_lead):
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            # device operations and the benchmark's annotations; no
            # Python function tracing, which would slow the window
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            ann = jax.profiler.TraceAnnotation("bench.traced")
            ann.__enter__()
            self._tracing = (ann, time.perf_counter())
        try:
            yield
        finally:
            if (self._tracing is not None and time.perf_counter()
                    - self._tracing[1] >= self.trace_len):
                self.stop_trace()

    def stop_trace(self) -> None:
        if self._tracing is None:
            return
        import jax
        ann, t0 = self._tracing
        ann.__exit__(None, None, None)
        self.trace_window = time.perf_counter() - t0
        jax.profiler.stop_trace()
        self._tracing = None

    # -- memory -----------------------------------------------------------
    def read_memory(self) -> None:
        """Peak device memory of the fullest chip, before the reference."""
        import jax
        peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
                 for d in jax.local_devices() if d.memory_stats()]
        self.memory_peak = max(peaks) if peaks else None
        limits = [d.memory_stats().get("bytes_limit", 0)
                  for d in jax.local_devices() if d.memory_stats()]
        self.memory_limit = min(limits) if limits else None


def device_info(need: int) -> dict:
    """The accelerator as JAX reports it; exits when there is none."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < need:
        print(f"no accelerator for this cell: JAX reports {len(devs)} "
              f"{devs[0].platform} device(s), the cell needs {need} "
              f"chip(s)", file=sys.stderr)
        sys.exit(NO_CHIP)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def configure_jax() -> None:
    """The compile cache at a fixed path in the checkout, every program
    cached (small ones too), before the program sets anything."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def per_layer_metrics(cell: Cell, ctx: Context, events, device: dict) -> dict:
    """Each per-layer metric's reader, by name; a reader that finds
    nothing returns None and its metric is left out."""
    import peaks as P
    rctx = {"events": events, "counters": ctx.counters, "conf": cell.conf,
            "mix": cell.mix, "model": cell.model,
            "peaks": P.peaks(device["kind"]),
            "trace_window_s": ctx.trace_window}
    out = {}
    for m in cell.per_layer:
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(rctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def judge(cell: Cell, compared: dict) -> tuple:
    """(correct, [(name, value, limit)]): each number against its limit."""
    rows, ok = [], True
    for name, value in compared.items():
        limit = cell.limits[name]["limit"]
        rows.append((name, value, limit))
        ok = ok and value == value and value <= limit
    return ok, rows


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             device: dict, control: bool = False) -> dict:
    """Drive the cell once; the result line as a dict (with ``control``,
    also the control's and the faults' readings, for calibration)."""
    import jax
    ctx = Context(cell, seed, seconds, trace, control)
    jax.monitoring.register_event_duration_secs_listener(ctx.on_event)
    sys.path.insert(0, str(ROOT / "src"))
    out = cell.driver.run(ctx)
    setup_s = ctx.t_window - T_START
    correct, rows = judge(cell, out["compared"])
    correct = correct and out["failed"] == 0
    device = dict(device, memory_peak_bytes=ctx.memory_peak)
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"]}
    if trace:
        import trace_reduce as TR
        if os.environ.get("BENCH_KEEP_TRACE"):
            print(json.dumps(TR.inventory(str(TRACE_DIR))), file=sys.stderr)
        events = TR.extract(str(TRACE_DIR))
        if os.environ.get("BENCH_KEEP_TRACE"):
            TR.save(events, os.environ["BENCH_KEEP_TRACE"])
        busy, window = TR.busy_s(events)
        device.update(busy_s=busy, window_s=window)
        result["metrics"] = per_layer_metrics(cell, ctx, events, device)
        result["breakdown"] = TR.breakdown(events)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        vals = dict(out["e2e"], setup_s=setup_s)
        result["metrics"] = {k: {"value": vals[k], "unit": units[k]}
                             for k in units}
    result["device"] = device
    result["compiles_in_window"] = ctx.compiles_in_window
    result["notes"] = dict(out.get("notes", {}),
                           memory_limit_bytes=ctx.memory_limit)
    for key in ("control", "fault_half_batch"):
        if key in out:
            result[key] = out[key]
    result["compared"] = {n: {"value": v, "limit": lim}
                          for n, v, lim in rows}
    for n, v, lim in rows:
        print(f"{n} {v!r} limit {lim!r}", file=sys.stderr)
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = Cell(bench, args.workload)
    configure_jax()
    device = device_info(cell.entry["chips"])
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    sys.path.insert(0, str(BENCH))
    main()
