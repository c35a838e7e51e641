"""The program's spans and scopes in a trace: the scope of an ``op_name``,
device time by scope, idle time by the innermost span, and the six readers
built on them; the older readers on the older recorded slice.
``recorded_train_spans.json.gz`` is a slice of a one-chip training trace
that carries the spans and scopes."""
from pathlib import Path

import pytest

import peaks as P
import run as R
import trace_layers as TL
import trace_reduce as TR

DEV = "/device:TPU:0"
HERE = Path(__file__).resolve().parent


def op(name, t0, dur, scope=""):
    return {"kind": "op", "plane": DEV, "name": name, "t0": t0, "dur": dur,
            "long": "", "target": "", "scope": scope}


def span(name, t0, dur):
    return {"kind": "span", "plane": "/host:CPU", "name": name, "t0": t0,
            "dur": dur, "long": "", "target": "", "scope": ""}


EVENTS = [
    {"kind": "host", "plane": "/host:CPU", "name": TR.WINDOW, "t0": 0,
     "dur": 1000, "long": "", "target": "", "scope": ""},
    span("train.run", 100, 800),                 # [100, 900]
    span("train.lr", 200, 100),                  # [200, 300]
    span("train.dispatch", 300, 50),             # [300, 350]
    span("train.sync", 350, 250),                # [350, 600]
    op("fusion.1", 0, 150, "attn"),              # [0, 150]
    op("fusion.2", 320, 180, "ffn"),             # [320, 500]
    op("while.1", 550, 250),                     # [550, 800], unscoped
    op("fusion.3", 600, 100, "attn"),            # inside the while
]
# device idle: [150, 320], [500, 550], [800, 1000]


@pytest.mark.parametrize("path, scope", [
    ("jit(step)/transpose(jvp())/while/body/closed_call/attn/mul", "attn"),
    ("jit(step)/jvp(embed)/jit(_take)/gather", "embed"),
    ("jit(step)/transpose(jvp(head))/log_softmax", "head"),
    ("jit(step)/optimizer/add", "optimizer"),
    ("jit(step)/jvp()/while/body/closed_call/ffn/ffn_pattern/"
     "jvp(jit(rdp_matmul_cols))/rdp_matmul_cols/pallas_call", "ffn"),
    ("jit(step)/jvp()/while/body/closed_call/ffn_pattern/dot_general", ""),
    ("state.params['stacks'][0]['attn']['wq']", ""),
    ("jit(step)/transpose(jvp())/while/body/dynamic_update_slice", ""),
    ("", ""),
])
def test_scope_of_an_op_name(path, scope):
    assert TL.scope_of(path) == scope


def test_op_names_come_from_the_hlo_in_the_trace(tmp_path):
    """A profiler trace keeps each executable's HLO in its metadata plane;
    its instructions' ``op_name`` carry the named scopes."""
    import glob

    import jax
    import jax.numpy as jnp

    @jax.jit
    def scoped(x):
        with jax.named_scope("attn"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("head"):
            return jnp.sum(jnp.exp(y))

    x = jnp.ones((32, 32))
    jax.block_until_ready(scoped(x))
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(scoped(x))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = TL.hlo_op_names(path)
    (module,) = [m for m in names if m.startswith("jit_scoped(")]
    scopes = {TL.scope_of(op) for op in names[module].values()}
    assert {"attn", "head"} <= scopes
    assert TL.hlo_op_names(path, modules=set()) == {}


def test_events_add_the_programs_spans_from_run_pys_trace(tmp_path,
                                                          monkeypatch):
    """A reader's events are those ``run.py`` reduced, copied, with the
    program's spans added from the trace still in ``run.py``'s
    ``TRACE_DIR``, read once for all readers; with no trace there, the
    events are left as they were."""
    import jax
    import jax.numpy as jnp
    from repro.obs import SpanTracer

    monkeypatch.setattr(R, "TRACE_DIR", tmp_path)
    tracer = SpanTracer(enabled=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(TR.WINDOW):
            with tracer.span("train.sync"):
                jax.block_until_ready(jnp.ones(4) + 1)
    finally:
        jax.profiler.stop_trace()
    given = TR.extract(str(tmp_path))
    before = [dict(e) for e in given]
    ev = TL.events({"events": given})
    assert given == before
    (sync,) = [e for e in ev if e["kind"] == "span"]
    t0, t1 = TR.window(ev)
    assert sync["name"] == "train.sync"
    assert t0 <= sync["t0"] and sync["t0"] + sync["dur"] <= t1
    assert TL.events({"events": given}) is ev
    monkeypatch.setattr(R, "TRACE_DIR", tmp_path / "removed")
    assert TL.events({"events": given}) is given


def test_scopes_are_put_by_the_executable_around_each_op():
    ev = [{"kind": "module", "plane": DEV, "name": "jit_step(1)", "t0": 0,
           "dur": 100},
          {"kind": "module", "plane": DEV, "name": "jit_step(2)", "t0": 200,
           "dur": 100},
          op("fusion.1", 10, 5), op("fusion.1", 210, 5), op("fusion.1", 150, 5),
          op("fusion.2", 220, 5)]
    TL._put_scopes(ev, {
        "jit_step(1)": {"fusion.1": "jit(step)/jvp()/attn/mul"},
        "jit_step(2)": {"fusion.1": "jit(step)/transpose(jvp(head))/exp",
                        "fusion.2": "jit(step)/add"}})
    assert [e["scope"] for e in ev if e["kind"] == "op"] == [
        "attn", "head", "", ""]


def test_scope_seconds_are_self_times_in_the_window():
    # the while's self time excludes fusion.3, which is attn's
    assert TL.scope_seconds(EVENTS, "attn") == pytest.approx(250e-9)
    assert TL.scope_seconds(EVENTS, "ffn") == pytest.approx(180e-9)
    assert TL.scope_seconds(EVENTS, "head") == 0.0
    assert TL.scope_seconds(EVENTS, "") == pytest.approx(150e-9)


def test_innermost_span_wins():
    pieces = TL.innermost([e for e in EVENTS if e["kind"] == "span"])
    assert pieces == [(100, 200, "train.run"), (200, 300, "train.lr"),
                      (300, 350, "train.dispatch"),
                      (350, 600, "train.sync"), (600, 900, "train.run")]


def test_idle_by_span_puts_uncovered_time_outside():
    idle = TL.idle_by_span(EVENTS)
    # [150, 320]: run 50, lr 100, dispatch 20; [500, 550]: sync 50;
    # [800, 1000]: run 100, then no span: outside 100
    assert idle == pytest.approx({
        "train.run": 150e-9, "train.lr": 100e-9, "train.dispatch": 20e-9,
        "train.sync": 50e-9, TL.OUTSIDE: 100e-9})
    busy, window = TR.busy_s(EVENTS)
    assert sum(idle.values()) == pytest.approx(window - busy)


def test_idle_by_span_without_spans_is_all_outside():
    ev = [e for e in EVENTS if e["kind"] != "span"]
    assert TL.idle_by_span(ev) == pytest.approx({TL.OUTSIDE: 420e-9})


NEW = ("train.attn_ms", "train.ffn_ms", "train.head_ms",
       "train.optimizer_ms", "train.idle_lr_ms", "train.idle_readback_ms")
OLD = ("train.step_gap_ms_p50", "train.mfu_pct", "train.ffn_roofline",
       "train.device_idle_pct")


def reader(name):
    return R.load_module(R.BENCH / "metrics" / f"{name}.py").read


def test_new_readers_on_synthetic_events():
    ctx = {"events": EVENTS,
           "counters": {"steps": [(2, 0, 0.3, True), (5, 1, 0.3, True),
                                  (7, 2, 0.3, False)]}}
    got = {n: reader(n)(ctx) for n in NEW}
    # ms per traced step, two traced steps
    assert got == pytest.approx({
        "train.attn_ms": 250e-6 / 2, "train.ffn_ms": 180e-6 / 2,
        "train.head_ms": None, "train.optimizer_ms": None,
        "train.idle_lr_ms": 100e-6 / 2,
        "train.idle_readback_ms": 50e-6 / 2})


def _context(events, steps):
    bench = R.load_json(R.ROOT / "BENCHMARK.json")
    cell = R.Cell(bench, "train-rdp-p0.5")
    return {"events": events, "conf": cell.conf, "mix": cell.mix,
            "model": cell.model, "peaks": P.peaks("TPU v5 lite"),
            "counters": {"steps": steps, "window_s": 0.838,
                         "tokens_per_step": 4096},
            "trace_window_s": 0.838}


def test_older_readers_read_the_older_slice_as_before():
    """The readers the benchmark had read the same numbers on the slice
    recorded before the program wrote spans and scopes (the values the
    parent commit's readers give), and the new ones find nothing there."""
    ev = TR.load(str(HERE / "recorded_train.json.gz"))
    ctx = _context(ev, [(2, 0, 0.41, True), (5, 1, 0.41, True)])
    got = {n: reader(n)(ctx) for n in OLD + NEW}
    assert got == pytest.approx({
        "train.step_gap_ms_p50": 10.628418,
        "train.mfu_pct": 13.200510217617484,
        "train.ffn_roofline": 10.910193889416949,
        "train.device_idle_pct": 2.5390055132637923,
        **{n: None for n in NEW}})


def test_recorded_slice_with_spans_and_scopes():
    """Three steps of a one-chip v5e trace of `train-rdp-p0.5` (8 layers)
    whose program writes the spans and scopes: the six new readers read
    it, the five scopes hold 94% of the busy time, and the idle time,
    split by span, is the window's."""
    ev = TR.load(str(HERE / "recorded_train_spans.json.gz"))
    ctx = _context(ev, [(2, 0, 0.33, True)] * 3)
    got = {n: reader(n)(ctx) for n in NEW}
    assert got == pytest.approx({
        "train.attn_ms": 179.119369, "train.ffn_ms": 72.195073,
        "train.head_ms": 49.721610, "train.optimizer_ms": 21.681211,
        "train.idle_lr_ms": 7.010043,
        "train.idle_readback_ms": 1.752789})
    busy, window = TR.busy_s(ev)
    scoped = sum(TL.scope_seconds(ev, s) for s in TL.SCOPES)
    assert scoped / busy > 0.93
    idle = TL.idle_by_span(ev)
    assert sum(idle.values()) == pytest.approx(window - busy)
    assert max(idle, key=idle.get) == "train.lr"
    assert {e["name"] for e in ev if e["kind"] == "span"} == {
        "train.run", "train.sample", "train.data", "train.lr",
        "train.dispatch", "train.sync", "train.record"}
