"""Training cells: the program's ``DistributedTrainer`` driven from a seed.

Set-up builds one trainer from the benchmark's weights and drives it,
through ``DistributedTrainer.run`` and the mix's data feed, through the
three steps the reference follows; then once through every bucket of the
plan, so that nothing compiles later.  The buckets of the three checked
steps are drawn from the run seed (``check_buckets``), so that over seeds
the comparison reaches every ``(dp, bias)`` the window runs.  The window keeps calling
``run`` one step at a time until ``seconds`` have passed.  After it, the
trainer is freed and the plain reference repeats the first three steps
from the same weights, rows and pattern draws.

Numbers compared (limits in ``limits/<cell>.json``):

* ``loss_gap``   — widest |loss - reference loss| over the three steps, nats;
* ``grad_gap``   — worst leaf's gap between the norms of the first step's
  clipped gradient (the program's, read back from its AdamW first moment
  after one step) and the reference's, over the larger of that leaf's
  reference norm and the median leaf's;
* ``update_gap`` — the same for each leaf's change over the three steps.

A leaf is each layer's slice of a stacked parameter.  Leaves whose
reference gradient is under a thousandth of the median leaf's are left
out: they move under Adam by rounding alone.

The reference is the cell's (``ctx.reference``, found by the
configuration's ``model_type``).  A training reference provides, all in
float32 on the benchmark's own weights:

* ``pattern(conf, dp, bias, seq_len)`` — what ``clipped_grad`` takes as
  the draw ``(dp, bias)``, the row's positions on the leading axis (the
  half-batch fault keeps its first half);
* ``clipped_grad(conf, job, w, batch, keep, low)`` — (loss, gradient, the
  factor that clips it to the job's global norm); ``low=True`` is the
  control, the same step one precision below the configuration's;
* ``adamw(job, w, g, scale, m, v, count, lr)`` — one update, each
  parameter rounded to its own type;
* ``cosine_lr(job, step)`` — the job's learning rate.

A new training cell appends its name to the ``workloads`` of
``train_tokens_per_s`` and of each per-layer metric it reports in
``BENCHMARK.json``; no other entry changes.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import gen

CHECK_STEPS = 3
CHECK_SALT = 0xC4EC     # keys the checked steps' draw apart from the rows'
NEGLIGIBLE_GRAD = 1e-3


class _Pinned:
    """A view of a plan whose every draw is one bucket (the checked steps
    and the warm-up)."""

    def __init__(self, plan, dp: int, bias: int):
        self._plan, self._bound = plan, plan.bind(dp, bias)

    def sample(self, step=None, **_):
        return self._bound

    def __getattr__(self, name):
        return getattr(self._plan, name)


def _norm_list(tree) -> list:
    """Traceable: each leaf's float32 norm, per layer for stacked leaves."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = x.astype(jnp.float32)
        per_layer = _stacked(path)
        axes = tuple(range(1, x.ndim)) if per_layer else None
        out.append(jnp.sqrt(jnp.sum(jnp.square(x), axis=axes)))
    return out


def _stacked(path) -> bool:
    return any(getattr(k, "key", None) == "stacks" for k in path)


def _name(path) -> str:
    return ".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def named_norms(tree, values) -> dict:
    """name → norm from ``_norm_list`` values, one entry per layer slice."""
    res = {}
    for (path, _), v in zip(jax.tree_util.tree_flatten_with_path(tree)[0],
                            values):
        v = np.asarray(v, np.float64)
        if _stacked(path):
            for i, x in enumerate(v):
                res[f"{_name(path)}[{i}]"] = float(x)
        else:
            res[_name(path)] = float(v)
    return res


def leaf_norms(tree) -> dict:
    """name → float32 norm of each leaf (each layer's slice of a stack)."""
    return named_norms(tree, jax.jit(_norm_list)(tree))


def diff_norms(a, b) -> dict:
    """``leaf_norms`` of a - b, both cast to float32."""
    return leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


def worst_leaf_gap(got: dict, want: dict, grad_ref: dict) -> tuple:
    """(worst relative gap of norms, its leaf), over leaves the reference's
    gradient moves."""
    med_g = float(np.median(list(grad_ref.values())))
    med = float(np.median(list(want.values())))
    worst, where = 0.0, ""
    for name, w in want.items():
        if grad_ref[name] < NEGLIGIBLE_GRAD * med_g:
            continue
        gap = abs(got[name] - w) / max(w, med, 1e-30)
        if gap > worst:
            worst, where = gap, name
    return worst, where


def check_buckets(plan, seed: int) -> list:
    """The ``(dp, bias)`` of the checked steps, drawn from the run seed:
    the plan's patterned periods (all its periods if it has none) in the
    seed's order, repeated as needed, each at a bias of the seed's
    choosing.  With three periods, every seed checks each of them."""
    biases = {}
    for dp, b in plan.buckets():
        biases.setdefault(dp, []).append(b)
    dps = sorted(dp for dp in biases if dp > 1) or sorted(biases)
    g = gen.rng(seed, CHECK_SALT)
    order = [dps[i] for i in g.permutation(len(dps))]
    picks = [order[i % len(order)] for i in range(CHECK_STEPS)]
    return [(dp, int(g.choice(biases[dp]))) for dp in picks]


def step_stats(dts: list) -> dict:
    """Host-clock step times of the window: median, longest, where the
    longest fell, and the seconds spent beyond 1.5 medians (stalls)."""
    ms = np.asarray(dts, np.float64) * 1e3
    p50 = float(np.median(ms))
    return {"p50_ms": p50, "max_ms": float(ms.max()),
            "argmax": int(ms.argmax()),
            "stall_s": float(np.sum(np.maximum(ms - 1.5 * p50, 0)) / 1e3)}


def _rows(mix, seed, vocab):
    def feed(step):
        return gen.train_batch(seed, step, mix["batch"], mix["seq_len"],
                               vocab)
    return feed


def make_plan(model, cfg, mix):
    """The mix's dropout plan over the adapter's blocking (the identity
    plan where the mix has none)."""
    from repro.core.plan import build_plan, identity_plan
    nb, block = model.plan_blocks(cfg)
    p = mix.get("plan")
    if not p:
        return identity_plan(nb=nb, block=block)
    return build_plan(p["family"], p["rate"], nb=nb, dp_max=p["dp_max"],
                      block=block, backend=p["backend"], seed=p["seed"])


def build(ctx):
    """The trainer, its data feed and the plan, from the cell's files."""
    from repro.optim.optimizers import AdamW
    from repro.train.distributed import DistributedTrainer, TrainerConfig
    conf, mix, model = ctx.conf, ctx.mix, ctx.model
    cfg = model.program_config(conf, conf["program"])
    job = mix["job"]
    plan = make_plan(model, cfg, mix)
    weights = model.make_weights(conf, jax.random.key(gen.key32(ctx.seed)))
    model.check_layout(cfg, weights)
    tcfg = TrainerConfig(steps=job["total_steps"], base_lr=job["lr"],
                         warmup=job["warmup_steps"],
                         clip_norm=job["clip_norm"], log_every=1 << 30)
    opt = AdamW(b1=job["b1"], b2=job["b2"], eps=job["eps"],
                weight_decay=job["weight_decay"])
    trainer = DistributedTrainer(cfg, opt, weights, plan=plan, tcfg=tcfg)
    return trainer, _rows(mix, ctx.seed, cfg.vocab)


def _annotated(feed):
    def f(step):
        with jax.profiler.TraceAnnotation("bench.data"):
            return feed(step)
    return f


def _step(trainer, feed, k: int) -> None:
    with jax.profiler.TraceAnnotation("bench.step"):
        trainer.start_step = k
        trainer.run(feed, until=k + 1)


def run(ctx) -> dict:
    """One training run: set-up, window, reference check."""
    trainer, rows = build(ctx)
    feed = _annotated(rows)
    job = ctx.mix["job"]
    plan0 = trainer.plan0
    # the steps the reference follows, through the window's own call, at
    # buckets drawn from the seed
    for k, (dp, b) in enumerate(check_buckets(plan0, ctx.seed)):
        trainer.plan = _Pinned(plan0, dp, b)
        _step(trainer, feed, k)
        if k == 0:
            grad_prog = {
                name: v / (1 - job["b1"])
                for name, v in leaf_norms(trainer.state.opt["mu"]).items()}
    p0 = ctx.model.make_weights(ctx.conf,
                                jax.random.key(gen.key32(ctx.seed)))
    upd_prog = diff_norms(trainer.state.params, p0)
    del p0
    check_hist = [dict(h) for h in trainer.history[:CHECK_STEPS]]
    # every bucket once more with the state as the window sees it (the
    # first steps' state differs in placement), so nothing compiles later
    k = CHECK_STEPS
    for dp, b in plan0.buckets():
        trainer.plan = _Pinned(plan0, dp, b)
        _step(trainer, feed, k)
        k += 1
    trainer.plan = plan0
    jax.block_until_ready(trainer.state)

    tokens_per_step = ctx.mix["batch"] * ctx.mix["seq_len"]
    ctx.window_open()
    t0 = time.perf_counter()
    first = len(trainer.history)
    traced = []
    while True:
        with ctx.maybe_traced(time.perf_counter() - t0):
            traced.append(ctx._tracing is not None)
            _step(trainer, feed, k)
        k += 1
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    t1 = time.perf_counter()
    ctx.window_closed()
    steps = trainer.history[first:]
    losses = [h["loss"] for h in steps]
    failed = sum(not np.isfinite(x) for x in losses)
    ctx.counters.update({
        "steps": [(h["dp"], h["bias"], h["dt"], tr)
                  for h, tr in zip(steps, traced)],
        "tokens_per_step": tokens_per_step,
        "window_s": t1 - t0})
    timing = step_stats([h["dt"] for h in steps])
    ctx.read_memory()
    del trainer, steps
    gc.collect()

    buckets = [(h["dp"], h["bias"]) for h in check_hist]
    ref_read = reference(ctx, buckets)
    prog = {"loss": [h["loss"] for h in check_hist], "grad": grad_prog,
            "update": upd_prog}
    compared, where = compare(prog, ref_read)
    out = {
        "attempted": len(losses), "failed": failed,
        "e2e": {"train_tokens_per_s": len(losses) * tokens_per_step
                / (t1 - t0)},
        "compared": compared,
        "notes": dict(where, losses=prog["loss"],
                      ref_losses=ref_read["loss"], checked=buckets,
                      steps=timing),
    }
    if ctx.control:
        # the reference in the program's place, one precision lower, and
        # the reference fed half of each step's rows: readings for limits
        out["control"] = compare(reference(ctx, buckets, low=True),
                                 ref_read)[0]
        out["fault_half_batch"] = compare(
            reference(ctx, buckets, half=True), ref_read)[0]
    return out


def compare(got: dict, want: dict) -> tuple:
    """The numbers compared, and the leaves that set them."""
    loss_gap = max(abs(a - b) for a, b in zip(got["loss"], want["loss"]))
    grad_gap, grad_leaf = worst_leaf_gap(got["grad"], want["grad"],
                                         want["grad"])
    upd_gap, upd_leaf = worst_leaf_gap(got["update"], want["update"],
                                       want["grad"])
    return ({"loss_gap": loss_gap, "grad_gap": grad_gap,
             "update_gap": upd_gap},
            {"grad_leaf": grad_leaf, "update_leaf": upd_leaf})


def reference(ctx, buckets, low: bool = False, half: bool = False) -> dict:
    """The reference's first steps from the run's weights and rows:
    losses, the first clipped gradient's leaf norms, and each leaf's
    change over the steps.  ``low`` is the reference's control, one
    precision lower; ``half`` keeps only the first half of each step's
    rows (a fault)."""
    conf, mix, ref = ctx.conf, ctx.mix, ctx.reference
    job = mix["job"]
    rows = _rows(mix, ctx.seed, conf["vocab_size"])

    def start():
        return ctx.model.make_weights(conf,
                                      jax.random.key(gen.key32(ctx.seed)))

    w = start()
    m = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), w)
    v = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), w)

    def step(w, m, v, batch, keep, count, lr):
        loss, g, scale = ref.clipped_grad(conf, job, w, batch, keep, low)
        norms = [n * scale for n in _norm_list(g)]
        w, m, v = ref.adamw(job, w, g, scale, m, v, count, lr)
        return loss, norms, w, m, v

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    losses, grad = [], None
    for k, (dp, b) in enumerate(buckets):
        keep = ref.pattern(conf, dp, b, mix["seq_len"])
        batch = rows(k)
        if half:
            n = mix["seq_len"] // 2
            batch = {k_: v_[:, :n] for k_, v_ in batch.items()}
            keep = keep[:n]
        batch = jax.tree.map(jnp.asarray, batch)
        loss, g_norms, w, m, v = step(
            w, m, v, batch, keep, jnp.float32(k + 1),
            jnp.float32(ref.cosine_lr(job, k)))
        losses.append(float(loss))
        if k == 0:
            grad = named_norms(w, g_norms)
    del m, v
    w0 = start()
    upd = diff_norms(w, w0)
    return {"loss": losses, "grad": grad, "update": upd}
