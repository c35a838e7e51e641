"""Unified observability layer: registry/tracer/watchdog/drift units, the
registry-generic no-recompile sweep, the tracer's spans in a profiler
trace, and the CPU smoke acceptance — a short instrumented train run must
emit a Perfetto-loadable trace, FFN FLOPs at 1/dp of dense in each compiled
bucket, zero recompile violations after warm_start, and an in-distribution
drift verdict for the plan's own draws."""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.plan import BACKENDS, FAMILIES, DropoutPlan, get_family
from repro.obs import (DriftMonitor, MetricsRegistry, Observability,
                       RecompileWatchdog, SpanTracer, bucket_labels)
from repro.obs.recompile import RecompileViolation

from tools.validate_obs import validate_metrics, validate_trace


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

class TickClock:
    def __init__(self):
        self.t = 0.0

    def now(self):
        self.t += 1.0
        return self.t


def test_registry_counters_gauges_histograms_label_keyed():
    reg = MetricsRegistry()
    c1 = reg.counter("tokens_total", bucket_labels(2, 1))
    c2 = reg.counter("tokens_total", bucket_labels(2, 0))
    assert c1 is not c2
    assert c1 is reg.counter("tokens_total", {"bias": 1, "dp": 2})
    c1.inc(5)
    assert c1.value == 5
    with pytest.raises(ValueError):
        c1.inc(-1)
    reg.gauge("queue_depth").set(7)
    assert reg.gauge("queue_depth").value == 7.0
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("tokens_total", bucket_labels(2, 1))


def test_registry_exporters_valid_and_deterministic(tmp_path):
    reg = MetricsRegistry(clock=TickClock())
    assert reg.now() == 1.0
    reg.counter("a_total", bucket_labels(2, 0, family="rdp",
                                         backend="slice")).inc(3)
    reg.gauge("b_gauge").set(1.5)
    h = reg.histogram("c_seconds", bucket_labels(4, 1))
    for v in (0.1, 0.2, 0.3):
        h.record(v)
    jsonl = reg.to_jsonl()
    assert jsonl == reg.to_jsonl()           # deterministic
    path = tmp_path / "metrics.jsonl"
    path.write_text(jsonl)
    assert validate_metrics(str(path), "tools/obs_metrics.schema.json") == 3
    prom = reg.to_prometheus()
    assert '# TYPE a_total counter' in prom
    assert 'a_total{backend="slice",bias="0",dp="2",family="rdp"} 3.0' in prom
    assert "c_seconds_count" in prom and 'quantile="0.95"' in prom


def test_histogram_reservoir_exact_below_cap_bounded_above():
    # below the cap: summary identical to the exact computation over the
    # raw values (the pre-reservoir behavior)
    exact = MetricsRegistry().histogram("h", cap=1000)
    rng = np.random.default_rng(0)
    vals = rng.exponential(1.0, 500)
    for v in vals:
        exact.record(v)
    s = exact.summary()
    assert not exact.sampled
    assert s["count"] == 500
    np.testing.assert_allclose(s["mean"], vals.mean(), rtol=1e-12)
    np.testing.assert_allclose(s["p50"], np.percentile(vals, 50), rtol=1e-12)
    np.testing.assert_allclose(s["max"], vals.max(), rtol=0)

    # above the cap: memory stays bounded, count/mean/max stay exact,
    # percentiles stay within sampling error of the true distribution
    cap = 512
    res = MetricsRegistry().histogram("r", cap=cap)
    vals = rng.exponential(1.0, 20_000)
    for v in vals:
        res.record(v)
    assert res.sampled and len(res._values) == cap
    s = res.summary()
    assert s["count"] == 20_000
    np.testing.assert_allclose(s["mean"], vals.mean(), rtol=1e-9)
    np.testing.assert_allclose(s["max"], vals.max(), rtol=0)
    assert abs(s["p50"] - np.percentile(vals, 50)) < 0.2


def test_serve_histogram_is_registry_histogram_with_cap():
    from repro.serve.metrics import Histogram
    h = Histogram("ttft", cap=4)
    for v in range(10):
        h.record(float(v))
    assert h.count == 10 and len(h._values) == 4
    assert h.summary()["max"] == 9.0


# --------------------------------------------------------------------------
# tracer
# --------------------------------------------------------------------------

def test_tracer_disabled_is_shared_noop():
    """A disabled tracer records no JSONL event; its spans are bare
    profiler annotations, which record nothing outside a profiler
    session."""
    t = SpanTracer(enabled=False)
    with t.span("x", a=1) as span:
        pass
    assert isinstance(span, jax.profiler.TraceAnnotation)
    t.instant("i")
    t.counter("c", v=1)
    assert t.events() == []
    assert t.write("/nonexistent/never_written") is None


def _profiled_host_events(trace_dir, prefix: str) -> list:
    """(name, start ns, duration ns) of the host events in the newest
    profiler trace under ``trace_dir`` whose names start with ``prefix``."""
    import glob
    import os
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.duration_ns) for e in line.events
                    if e.name.startswith(prefix)]
    return sorted(out, key=lambda e: e[1])


@pytest.mark.parametrize("enabled", [True, False], ids=["jsonl", "off"])
def test_span_is_a_host_event_of_the_profiler_trace(tmp_path, enabled):
    """Every span, recorded to JSONL or not, is a host event of the same
    name in a ``jax.profiler`` trace, on the clock of the device's
    operations; the span's args stay out of the event's name, and the
    JSONL it writes still validates."""
    t = SpanTracer(path=str(tmp_path / "trace.jsonl"), enabled=enabled)
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        with t.span("train.outer", step=7):
            with t.span("train.inner", dp=2, bias=1):
                jax.block_until_ready(jnp.ones(4) + 1)
    finally:
        jax.profiler.stop_trace()
    (outer, t0, dur), (inner, t1, dur1) = _profiled_host_events(
        tmp_path / "prof", "train.")
    assert (outer, inner) == ("train.outer", "train.inner")
    assert t0 <= t1 and t1 + dur1 <= t0 + dur
    if enabled:
        assert t.write() is not None
        assert validate_trace(str(tmp_path / "trace.jsonl")) == 2
        assert [e["name"] for e in t.events()] == ["train.inner",
                                                   "train.outer"]
    else:
        assert t.events() == [] and t.write() is None


def test_tracer_trace_is_perfetto_loadable_and_schema_valid(tmp_path):
    clock = TickClock()
    t = SpanTracer(clock=clock, pid=1, tid=2)
    with t.span("step", dp=2, bias=1):
        pass
    t.instant("marker", step=3)
    t.counter("loss", value=1.5)
    path = tmp_path / "trace.jsonl"
    t.write(str(path))
    assert validate_trace(str(path)) == 3
    # the unclosed-array form still parses as standard JSON once closed —
    # exactly what chrome://tracing / Perfetto do on load
    evs = json.loads(path.read_text().rstrip().rstrip(",") + "]")
    assert [e["ph"] for e in evs] == ["X", "i", "C"]
    span = evs[0]
    assert span["name"] == "step" and span["args"] == {"dp": 2, "bias": 1}
    assert span["dur"] == 1e6     # TickClock: 1 s between enter and exit
    assert span["pid"] == 1 and span["tid"] == 2


def test_validate_trace_rejects_malformed(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text('[\n{"name": "x", "ph": "X", "ts": 0}\n')
    with pytest.raises(ValueError, match="pid"):
        validate_trace(str(p))
    p.write_text("not a trace\n")
    with pytest.raises(ValueError, match="expected the trace"):
        validate_trace(str(p))


# --------------------------------------------------------------------------
# recompile watchdog
# --------------------------------------------------------------------------

def test_watchdog_expected_universe_and_freeze():
    reg = MetricsRegistry()
    wd = RecompileWatchdog(registry=reg, name="t")
    wd.expect([(1, 0), (2, 0), (2, 1)])
    for k in [(1, 0), (2, 0), (2, 1)]:
        assert wd.record_compile(k)
    assert wd.violation_count == 0 and not wd.report()["missing"]
    with pytest.warns(RuntimeWarning, match="outside the declared"):
        assert not wd.record_compile((4, 0))
    wd.freeze()
    with pytest.warns(RuntimeWarning, match="after freeze"):
        wd.record_compile((1, 0))
    assert wd.violation_count == 2
    assert reg.counter("recompile_violations_total",
                       {"watchdog": "t"}).value == 2
    with pytest.raises(RecompileViolation):
        wd.assert_clean()


def test_watchdog_flags_duplicate_compiles():
    wd = RecompileWatchdog().expect([(2, 0)])
    assert wd.record_compile((2, 0))
    with pytest.warns(RuntimeWarning, match="duplicate"):
        assert not wd.record_compile((2, 0))


def test_watchdog_key_projection():
    wd = RecompileWatchdog(project=lambda k: k[1])
    wd.expect([(2, 0)])
    assert wd.record_compile(("decode", (2, 0)))
    assert wd.record_compile(("prefill_full", (2, 0), 16))
    with pytest.warns(RuntimeWarning):
        assert not wd.record_compile(("decode", (4, 0)))


def test_watchdog_watch_jit_detects_cache_growth():
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones((4,)))
    wd = RecompileWatchdog().watch_jit(f, "double")
    f(jnp.ones((4,)))                      # same shape: cache hit
    assert wd.check_jit() == []
    with pytest.warns(RuntimeWarning, match="cache grew"):
        f(jnp.ones((8,)))                  # new shape: recompile
        assert len(wd.check_jit()) == 1
    with pytest.raises(TypeError, match="not a jax.jit"):
        RecompileWatchdog().watch_jit(lambda x: x, "plain")


# --------------------------------------------------------------------------
# drift monitor
# --------------------------------------------------------------------------

def _plan(dist=(0.5, 0.5)):
    return DropoutPlan(family="rdp", dist=dist, nb=8, block=4)


def test_drift_plan_own_draws_in_distribution():
    plan = _plan((0.25, 0.25, 0.0, 0.5))
    mon = DriftMonitor(plan, registry=MetricsRegistry())
    for step in range(4000):
        mon.observe_bound(plan.sample(step))
    rep = mon.report()
    assert rep["verdict"] == "in-distribution", rep
    assert rep["samples"] == 4000 and not rep["unexpected_buckets"]
    assert rep["kl_divergence"] < 0.01


def test_drift_detects_skew_and_offplan_buckets():
    plan = _plan()
    mon = DriftMonitor(plan)
    for _ in range(1000):
        mon.observe(1, 0)                  # all mass on dp=1: 2x the target
    rep = mon.report()
    assert rep["verdict"] == "drift"
    assert rep["worst_bucket"] == (1, 0)
    assert rep["chi_square"] > 100

    mon2 = DriftMonitor(plan)
    for step in range(200):
        mon2.observe_bound(plan.sample(step))
    mon2.observe(8, 3)                     # a bucket the plan cannot produce
    assert mon2.report()["verdict"] == "drift"
    assert "(8, 3)" in mon2.report()["unexpected_buckets"]


def test_drift_insufficient_samples():
    mon = DriftMonitor(_plan())
    mon.observe(1, 0)
    assert mon.report()["verdict"] == "insufficient-samples"
    assert not mon.in_distribution()


# --------------------------------------------------------------------------
# registry-generic no-recompile sweep: every family x differentiable backend
# --------------------------------------------------------------------------

def _differentiable_pairs():
    return [(n, be) for n in sorted(FAMILIES) if n != "identity"
            and FAMILIES[n].differentiable
            for be in FAMILIES[n].backends
            if BACKENDS[be].differentiable]


# gather/pallas trace the bias operand (one executable per dp); slice bakes
# the bias into static slicing (one executable per (dp, bias) bucket — the
# trainer's pattern-bucketing contract)
_TRACED_BIAS = {"gather", "pallas"}


@pytest.mark.parametrize("family,backend", _differentiable_pairs())
def test_no_recompiles_across_biases_every_family_backend(family, backend):
    fam = get_family(family)
    nb, dp = 8, 4
    ks = jax.random.split(jax.random.PRNGKey(hash(family) % 97), 4)
    x = jax.random.normal(ks[0], (16, 64))
    w_up = jax.random.normal(ks[1], (64, 256))
    w_down = jax.random.normal(ks[2], (256, 64))
    w_gate = jax.random.normal(ks[3], (64, 256))
    traced = backend in _TRACED_BIAS
    static = ("dp",) if traced else ("dp", "bias")
    f = jax.jit(functools.partial(fam.apply_ffn, backend=backend, nb=nb,
                                  act=jax.nn.silu), static_argnames=static)

    def run(bias):
        b = jnp.int32(bias) if traced else bias
        return f(x, w_up, w_down, w_gate, dp=dp, bias=b).block_until_ready()

    run(0)
    if traced:
        # bias is a traced operand: zero recompiles across all biases
        wd = RecompileWatchdog().watch_jit(f, f"{family}/{backend}")
        for bias in range(1, dp):
            run(bias)
        wd.assert_clean()
    else:
        # static bias: exactly one executable per bucket, stable on repeat
        for bias in range(1, dp):
            run(bias)
        wd = RecompileWatchdog().watch_jit(f, f"{family}/{backend}")
        for bias in range(dp):
            run(bias)
        wd.assert_clean()
        assert f._cache_size() == dp


# --------------------------------------------------------------------------
# serve telemetry rebase: schema bitwise-stable, registry-backed
# --------------------------------------------------------------------------

def test_telemetry_snapshot_schema_unchanged():
    from repro.serve.metrics import Telemetry
    tel = Telemetry()
    tel.requests_rejected += 2               # the scheduler's += API
    tel.decode_steps += 1
    tel.prompt_tokens += 32
    tel.ttft.record(0.5)
    tel.record_decode_tokens(2, 1, 10)
    tel.record_decode_tokens(1, 0, 5)
    snap = tel.snapshot(duration_s=2.0)
    assert set(snap) == {
        "ttft", "ttft_member", "tpot", "queue_delay", "queue_delay_member",
        "tokens_generated", "prompt_tokens", "prompt_tokens_members",
        "prefill_shared_ratio", "requests_completed", "requests_rejected",
        "requests_shed", "members_completed", "decode_steps",
        "prefill_chunks", "mean_ffn_flop_fraction", "bucket_tokens",
        "kv_pages", "cow_forks", "cow_copies", "compile_cache_hits",
        "router", "duration_s", "throughput_tok_s", "throughput_req_s"}
    assert set(snap["ttft"]) == {"count", "mean", "p50", "p90", "p95", "max"}
    assert snap["requests_rejected"] == 2
    assert snap["tokens_generated"] == 15
    assert snap["bucket_tokens"] == {"dp=2,b=1": 10, "dp=1,b=0": 5}
    assert snap["mean_ffn_flop_fraction"] == pytest.approx(10 / 15)
    # registry-backed: the same numbers export as prometheus text
    assert "serve_requests_rejected_total 2.0" in tel.registry.to_prometheus()


# --------------------------------------------------------------------------
# hlo_profile: scoped attribution + CLI
# --------------------------------------------------------------------------

def _scoped_hlo():
    def f(x, w):
        with jax.named_scope("ffn_pattern"):
            y = x @ w
        return y @ w.T

    return (jax.jit(f)
            .lower(jnp.ones((8, 16)), jnp.ones((16, 4))).compile().as_text())


def test_scoped_dot_flops_isolates_named_scope():
    from repro.launch.hlo_profile import attribute, scoped_dot_flops
    hlo = _scoped_hlo()
    total = sum(v for (k, _, _), v in attribute(hlo).items() if k == "dot")
    scoped = scoped_dot_flops(hlo, "ffn_pattern")
    assert scoped == 2 * 8 * 4 * 16          # only the in-scope matmul
    assert total == scoped + 2 * 8 * 16 * 4  # plus the out-of-scope one


def test_hlo_profile_cli_smoke(tmp_path, capsys):
    from repro.launch.hlo_profile import main
    p = tmp_path / "m.hlo"
    p.write_text(_scoped_hlo())
    assert main([str(p), "--kind", "dot"]) == 0
    out = capsys.readouterr().out
    assert "FLOP" in out and "ffn_pattern" in out
    assert main([str(p), "--kind", "dot", "--scope", "ffn_pattern"]) == 0
    assert len(capsys.readouterr().out.strip().splitlines()) == 1
    with pytest.raises(SystemExit) as e:
        main([str(tmp_path / "missing.hlo")])
    assert e.value.code == 2


# --------------------------------------------------------------------------
# bench provenance
# --------------------------------------------------------------------------

def test_bench_record_carries_provenance():
    from benchmarks.common import bench_record
    rec = bench_record("kernel", config={"dp": 2}, rows=[])
    prov = rec["provenance"]
    assert set(prov) == {"git_sha", "jax_version", "device_kind",
                         "device_count", "timestamp"}
    assert prov["jax_version"] == jax.__version__
    assert prov["device_count"] >= 1
    assert "T" in prov["timestamp"]          # ISO 8601
    assert rec["bench"] == "kernel" and rec["config"] == {"dp": 2}


# --------------------------------------------------------------------------
# CPU smoke acceptance: instrumented trainer end to end
# --------------------------------------------------------------------------

def _compiled_ffn_flops(tr, batch, dp: int) -> float:
    """Dot FLOPs under ``ffn_pattern`` in the compiled HLO of the trainer's
    ``(dp, 0)`` bucket."""
    from repro.launch.hlo_profile import scoped_dot_flops
    from repro.parallel.sharding import set_mesh_and_rules
    with set_mesh_and_rules(tr.mesh, tr.rules):
        return scoped_dot_flops(
            tr._step_fn(dp, 0, batch).lower(tr.state, batch, jnp.float32(0))
            .compile().as_text(), "ffn_pattern")


def test_instrumented_train_smoke_acceptance(tmp_path):
    """A short CPU train run with tracing on must satisfy all four
    acceptance properties of the observability layer at once."""
    import dataclasses
    from repro.configs import get_smoke
    from repro.data.pipeline import SyntheticLMData
    from repro.models import init_lm, materialize
    from repro.optim.optimizers import AdamW
    from repro.train.distributed import DistributedTrainer, TrainerConfig

    cfg = dataclasses.replace(get_smoke("qwen2_1_5b"), dtype="float32")
    params = materialize(jax.random.PRNGKey(0), init_lm(cfg)[0])
    plan = DropoutPlan(family="rdp", dist=(0.5, 0.5), nb=cfg.pattern_nb,
                       block=cfg.d_ff // cfg.pattern_nb)
    trace_path = str(tmp_path / "trace.jsonl")
    obs = Observability.create(trace_path=trace_path, plan=plan)
    data = SyntheticLMData(vocab=cfg.vocab, seq_len=32, global_batch=8)
    tr = DistributedTrainer(
        cfg, AdamW(), params, plan=plan, obs=obs,
        tcfg=TrainerConfig(steps=30, log_every=1000))

    tr.warm_start(data.batch)
    # (c) zero recompile-watchdog violations after warm_start ...
    assert obs.watchdog.violation_count == 0
    rep = obs.watchdog.report()
    assert rep["frozen"] and not rep["missing"]

    tr.run(data.batch)
    obs.watchdog.assert_clean()              # ... and through the run

    # (b) FFN FLOPs = 1/dp of dense, in the HLO of each compiled bucket
    batch = jax.tree.map(jnp.asarray, data.batch(0))
    flops = {dp: _compiled_ffn_flops(tr, batch, dp) for dp in (1, 2)}
    assert flops[1] > 0
    assert flops[2] / flops[1] == pytest.approx(0.5, abs=0.02)

    # (d) drift verdict for the plan's own draws
    drift = obs.drift.report(min_samples=30)
    assert drift["verdict"] == "in-distribution", drift

    # (a) the trace is schema-valid and Perfetto-loadable
    assert obs.tracer.write() == trace_path
    n = validate_trace(trace_path)
    evs = json.loads(open(trace_path).read().rstrip().rstrip(",") + "]")
    assert len(evs) == n
    names = {e["name"] for e in evs}
    assert {"train.compile", "train.data", "train.dispatch",
            "train.sync"} <= names
    steps = [e for e in evs if e["name"] == "train.dispatch"]
    assert len(steps) == 30
    assert all(e["args"]["dp"] in (1, 2) for e in steps)

    # per-bucket step-time histograms were recorded
    hists = [m for m in obs.registry.metrics()
             if m.name == "train_step_time_s"]
    assert sum(m.count for m in hists) == 30


@pytest.fixture(scope="module")
def smoke_launch_trainer():
    """The trainer of CI's Observability smoke (``launch.train --arch
    qwen2-1.5b --smoke --batch 4 --seq 32 --dropout 0.5 --dp-max 4``),
    one batch, and its dense bucket's FFN FLOPs."""
    from repro.configs import get_smoke
    from repro.core.plan import build_plan
    from repro.data.pipeline import SyntheticLMData
    from repro.models import init_lm, materialize
    from repro.optim.optimizers import AdamW
    from repro.train.distributed import DistributedTrainer, TrainerConfig

    cfg = get_smoke("qwen2_1_5b")
    params = materialize(jax.random.PRNGKey(0), init_lm(cfg)[0])
    plan = build_plan("rdp", 0.5, nb=cfg.pattern_nb, dp_max=4,
                      block=cfg.d_ff // cfg.pattern_nb)
    tr = DistributedTrainer(cfg, AdamW(), params, plan=plan,
                            tcfg=TrainerConfig(steps=1))
    batch = jax.tree.map(jnp.asarray, SyntheticLMData(
        vocab=cfg.vocab, seq_len=32, global_batch=4).batch(0))
    return tr, batch, _compiled_ffn_flops(tr, batch, 1)


@pytest.mark.parametrize("dp", [2, 4])
def test_compiled_ffn_flops_are_one_over_dp_in_every_bucket(
        smoke_launch_trainer, dp):
    """Each patterned bias-0 bucket of the smoke plan (dp 2 and 4; 3 does
    not divide its 8 blocks) compiles to 1/dp of the dense bucket's FFN
    dot FLOPs."""
    tr, batch, dense = smoke_launch_trainer
    assert sorted(d for d, b in tr.plan0.buckets() if b == 0) == [1, 2, 4]
    assert dense > 0
    assert _compiled_ffn_flops(tr, batch, dp) / dense == pytest.approx(
        1 / dp, abs=0.02)


PHASES = ["train.sample", "train.data", "train.lr", "train.dispatch",
          "train.sync", "train.record"]


def test_trainer_emits_each_phase_span_once_per_step_in_order(tmp_path):
    """``DistributedTrainer.run`` wraps each step in the six phase spans,
    once each and in order, all inside one ``train.run`` span per call."""
    from repro.configs import get_smoke
    from repro.core.plan import identity_plan
    from repro.data.pipeline import SyntheticLMData
    from repro.models import init_lm, materialize
    from repro.optim.optimizers import AdamW
    from repro.train.distributed import DistributedTrainer, TrainerConfig

    cfg = get_smoke("qwen2_1_5b")
    params = materialize(jax.random.PRNGKey(0), init_lm(cfg)[0])
    obs = Observability.create(trace_path=str(tmp_path / "trace.jsonl"))
    data = SyntheticLMData(vocab=cfg.vocab, seq_len=16, global_batch=2)
    tr = DistributedTrainer(cfg, AdamW(), params, obs=obs,
                            plan=identity_plan(nb=cfg.pattern_nb),
                            tcfg=TrainerConfig(steps=3, log_every=1000))
    tr.run(data.batch)
    evs = sorted(obs.tracer.events(), key=lambda e: (e["ts"], -e["dur"]))
    spans = [e for e in evs if e["ph"] == "X"]
    assert spans[0]["name"] == "train.run"
    run_end = spans[0]["ts"] + spans[0]["dur"]
    assert [e["name"] for e in spans[1:]] == PHASES * 3
    assert all(e["ts"] + e["dur"] <= run_end for e in spans)
    # phases do not overlap: each ends before the next begins
    for a, b in zip(spans[1:], spans[2:]):
        assert a["ts"] + a["dur"] <= b["ts"]
    assert [e["args"]["step"] for e in spans[1:]
            if e["name"] == "train.sample"] == [0, 1, 2]
