"""Mesh-aware distributed trainer: DropoutPlan bucketing × sharding profiles.

This module composes the pieces that previously existed side by side but
were never wired together (`parallel/sharding.py` profiles, `launch/mesh.py`
meshes, the `acc_shardings` hook of `train_step.py`, the elastic checkpoint
path) into ONE training path:

  * ``TrainState`` — the (params, opt, step) pytree the trainer owns, with a
    logical-axes twin (``state_logical_axes``) so every leaf has an explicit
    sharding derived from the active ``ShardingRules`` profile.
  * ``DistributedTrainer`` — per (dp, bias) pattern bucket, jits the train
    step with explicit in/out shardings: params from the profile, ZeRO-1
    optimizer state via ``zero1_opt_sharding``, f32 grad-accumulation
    buffers wired into the ``acc_shardings`` hook, batch inputs sharded
    over the batch mesh axes.  Steps trace under an ambient
    ``set_mesh_and_rules`` context so compact-FFN activations are
    ``constrain``-ed with the pattern-aware ``ffn_kept`` logical axis.
  * Plan × mesh validation — ``DropoutPlan.validate_mesh`` runs at
    construction: every bucket's kept FFN dim (d_ff/dp) must divide the
    mesh axes its rule names, or a ``MeshDivisibilityError`` explains the
    fix (instead of the silent replication fallback in ``_pspec_for``).
  * Elastic checkpoints — the sharded ``TrainState`` saves through
    ``checkpoint.py`` (unsharded storage) and restores with the CURRENT
    mesh's shardings, so a job restarted on a different topology just
    re-shards on load.

The single-host ``Trainer`` (train/loop.py) is a thin wrapper over this
class on ``single_device_mesh()`` — one code path from 1 device to a pod.

Host-side behaviours (pattern bucketing, checkpoint/restart, straggler
watchdog) are documented in train/loop.py and DESIGN.md §2/§5/§10.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as PSpec

from repro.core import plan as plan_mod
from repro.core.online_search import OnlineSearch, OnlineSearchConfig
from repro.core.plan import (BucketSupersetViolation, DropoutPlan,
                             identity_plan)
from repro.launch.mesh import single_device_mesh
from repro.obs import Observability, bucket_labels
from repro.models.transformer import (ModelConfig, batch_logical_axes,
                                      init_lm)
from repro.optim.optimizers import cosine_schedule
from repro.parallel.sharding import (PROFILES, ShardingRules,
                                     logical_sharding, param_shardings,
                                     set_mesh_and_rules, zero1_opt_sharding)
from repro.train import checkpoint as ckpt_lib
from repro.train.train_step import make_train_step


# --------------------------------------------------------------------------
# TrainState — the pytree the trainer owns
# --------------------------------------------------------------------------

@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("params", "opt", "step", "extras"),
                   meta_fields=())
@dataclasses.dataclass
class TrainState:
    """Training state pytree: model params + optimizer state + step counter.

    Registered as a pytree (all fields are data), so it jits, donates,
    shards and checkpoints as one object.  Use
    ``state_logical_axes``/``state_shardings`` for its sharding twin.

    ``extras`` holds auxiliary host-managed state that must ride through
    the jitted step untouched (identity pass-through) and survive elastic
    checkpoints — today the online-search logits/EMAs (DESIGN.md §14).  An
    empty dict contributes zero pytree leaves, so states and checkpoints
    written without extras stay layout-compatible.
    """

    params: object
    opt: object
    step: object
    extras: dict = dataclasses.field(default_factory=dict)


def state_logical_axes(params, params_axes, abstract_opt) -> TrainState:
    """Logical-axes twin of a TrainState.

    Params use their model-declared axes (``init_lm``'s second return).
    Optimizer leaves that mirror a parameter (Adam moments, momenta —
    matched by tree path suffix and shape) inherit that parameter's axes;
    structural leaves (step counts) get no axes.  ZeRO-1 'data' sharding is
    layered on top at the sharding level, not here — logical axes describe
    the tensor, the profile + ``zero1_opt_sharding`` decide placement.
    """
    flat_p, treedef = jax.tree_util.tree_flatten_with_path(params)
    ax_leaves = treedef.flatten_up_to(params_axes)
    by_path = {tuple(path): (leaf.shape, ax)
               for (path, leaf), ax in zip(flat_p, ax_leaves)}

    def pick(path, leaf):
        hit = by_path.get(tuple(path[1:]))
        if hit is not None and hit[0] == leaf.shape:
            return hit[1]
        return (None,) * getattr(leaf, "ndim", 0)

    opt_axes = jax.tree_util.tree_map_with_path(pick, abstract_opt)
    return TrainState(params=params_axes, opt=opt_axes, step=())


def state_shardings(params, params_axes, abstract_opt, mesh,
                    rules: ShardingRules, extras=None) -> TrainState:
    """NamedSharding twin of a TrainState under one mesh + profile.

    Params follow the profile's param rules; optimizer tensors additionally
    get ZeRO-1 'data'-axis partitioning on their first free divisible dim
    (``zero1_opt_sharding`` — classic optimizer-state sharding); the step
    counter and every ``extras`` leaf (tiny host-managed arrays) are
    replicated.
    """
    state_ax = state_logical_axes(params, params_axes, abstract_opt)
    p_sh = param_shardings(params, params_axes, mesh, rules)

    def opt_sh(leaf, ax):
        base = logical_sharding(leaf.shape, ax, mesh, rules, is_param=True)
        return zero1_opt_sharding(base, leaf.shape)

    o_sh = jax.tree.map(opt_sh, abstract_opt, state_ax.opt)
    repl = NamedSharding(mesh, PSpec())
    return TrainState(params=p_sh, opt=o_sh, step=repl,
                      extras=jax.tree.map(lambda _: repl, extras or {}))


# --------------------------------------------------------------------------
# Host-side loop config + watchdog (moved here from loop.py; loop re-exports)
# --------------------------------------------------------------------------

@dataclasses.dataclass
class StragglerWatchdog:
    """Flags steps slower than mean + tolerance·std of an EMA estimate."""
    ema: float = 0.0
    var: float = 0.0
    beta: float = 0.9
    tolerance: float = 4.0
    warmup: int = 5
    seen: int = 0
    flagged: int = 0

    def observe(self, dt: float) -> bool:
        self.seen += 1
        if self.seen <= self.warmup:
            self.ema = dt if self.seen == 1 else \
                self.beta * self.ema + (1 - self.beta) * dt
            return False
        mean = self.ema
        self.ema = self.beta * self.ema + (1 - self.beta) * dt
        dev = abs(dt - mean)
        self.var = self.beta * self.var + (1 - self.beta) * dev * dev
        slow = dt > mean + self.tolerance * max(self.var ** 0.5, 1e-4)
        if slow:
            self.flagged += 1
        return slow


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    base_lr: float = 3e-4
    warmup: int = 10
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    clip_norm: float = 1.0
    microbatches: int = 1
    compress_grads: bool = False
    log_every: int = 10


# --------------------------------------------------------------------------
# Plan × mesh composition
# --------------------------------------------------------------------------

def plan_dims(plan: DropoutPlan, cfg: ModelConfig) -> dict:
    """The logical axes a plan's family compacts, mapped to their FULL
    sizes — the ``dims`` argument of ``DropoutPlan.validate_mesh``.

    Family-aware: every family compacts the FFN hidden dim (``ffn_kept``);
    ``attn_head_granular`` families additionally shrink the query/KV head
    axes, ``expert_granular`` the expert axis, ``ssm_state_granular`` /
    ``head_granular`` the SSM inner dim.  Dims the model does not have
    (d_ff=0 for pure-SSM configs, n_experts=0 for dense) are omitted.
    """
    fam = plan_mod.get_family(plan.family)
    dims: dict = {}
    if cfg.d_ff:
        dims["ffn_kept"] = cfg.d_ff
    if fam.attn_head_granular and cfg.n_heads and not cfg.mla:
        dims["heads"] = cfg.n_heads
        dims["kv_heads"] = cfg.n_kv_heads
    if fam.expert_granular and getattr(cfg, "n_experts", 0):
        dims["experts"] = cfg.n_experts
    # head-granular SSD shrinks the d_inner-sized out_proj/norm axes;
    # ssm_row shrinks only the (unsharded, d_state-sized) B/C channels, so
    # it adds no extra mesh constraint
    if fam.head_granular and getattr(cfg, "ssm_state", 0):
        dims["inner"] = cfg.d_inner
    return dims


# --------------------------------------------------------------------------
# The trainer
# --------------------------------------------------------------------------

class DistributedTrainer:
    """Mesh-aware trainer: pattern-bucketed executables × sharding profile.

    ``profile`` is a ``PROFILES`` key (or a ShardingRules instance);
    ``mesh`` defaults to a 1x1 mesh on the first device.  Construction
    validates that the plan composes with the mesh
    (``DropoutPlan.validate_mesh``) and shards params + ZeRO-1 optimizer state onto it; ``run`` then dispatches one
    explicitly-sharded jitted executable per sampled (dp, bias) bucket
    under the ambient mesh/rules context.
    """

    def __init__(self, cfg: ModelConfig, optimizer, params, *,
                 mesh=None, profile: str | ShardingRules = "tp",
                 plan: Optional[DropoutPlan] = None,
                 tcfg: Optional[TrainerConfig] = None,
                 params_axes=None, obs: Optional[Observability] = None,
                 online_search: OnlineSearchConfig | OnlineSearch
                 | None = None):
        self.cfg = cfg
        self.optimizer = optimizer
        self.mesh = mesh if mesh is not None else single_device_mesh()
        if isinstance(profile, ShardingRules):
            self.profile, self.rules = "custom", profile
        else:
            if profile not in PROFILES:
                raise ValueError(f"unknown sharding profile {profile!r}; "
                                 f"available: {sorted(PROFILES)}")
            self.profile, self.rules = profile, PROFILES[profile]
        # DropoutPlan is the canonical configuration; nb is pinned to the
        # model's pattern blocking.
        if plan is not None:
            self.plan = plan.with_nb(cfg.pattern_nb)
        else:
            self.plan = identity_plan(nb=cfg.pattern_nb)
        # training needs grads through the pattern matmuls — reject an
        # inference-only backend here rather than deep inside jax.grad
        # ("slice"/"gather" differentiate via XLA autodiff, "pallas" via
        # the custom-VJP compact kernels in kernels/autodiff.py)
        if not plan_mod.BACKENDS[self.plan.backend].differentiable:
            raise ValueError(
                f"pattern backend {self.plan.backend!r} is not "
                f"differentiable and cannot be used for training")
        # every bucket's kept dim must divide the mesh axes its rule names
        # — fail at construction, not silently mid-partitioning.  Which
        # dims a plan compacts depends on its family's granularity flags.
        self.plan.validate_mesh(self.mesh, self.rules,
                                dims=plan_dims(self.plan, cfg))
        # NOTE: default must be constructed per instance — a dataclass
        # default in the signature would be one shared mutable config
        self.tcfg = tcfg if tcfg is not None else TrainerConfig()

        # observability: pass a preconfigured bundle (e.g. with tracing on)
        # or get the always-on default (registry + watchdog, no trace file)
        self.obs = obs if obs is not None \
            else Observability.create(plan=self.plan)

        # ---- online search (DESIGN.md §14) --------------------------------
        # ``plan0`` declares the frozen bucket superset: warm_start
        # precompiles it, the watchdog freezes it, and every resync's
        # ``with_dist`` view may only reweight within it.
        self.plan0 = self.plan
        self._superset = frozenset(self.plan0.buckets())
        if isinstance(online_search, OnlineSearch):
            self.online_search: Optional[OnlineSearch] = online_search
        elif online_search is not None:
            self.online_search = OnlineSearch(
                self.plan0, n_layers=max(1, cfg.n_layers),
                cfg=online_search, registry=self.obs.registry)
        else:
            self.online_search = None
        extras = {}
        if self.online_search is not None:
            extras = {"search": jax.tree.map(
                jnp.asarray, self.online_search.state_arrays())}

        # ---- shard the state onto the mesh --------------------------------
        if params_axes is None:
            params_axes = init_lm(cfg)[1]
        abstract_opt = jax.eval_shape(optimizer.init, params)
        self.state_sh = state_shardings(params, params_axes, abstract_opt,
                                        self.mesh, self.rules,
                                        extras=extras)
        params = jax.device_put(params, self.state_sh.params)
        # init the opt state directly into its ZeRO-1 sharding (never
        # materializes replicated moments)
        opt_state = jax.jit(optimizer.init,
                            out_shardings=self.state_sh.opt)(params)
        self.state = TrainState(params=params, opt=opt_state,
                                step=jnp.zeros((), jnp.int32),
                                extras=extras)
        # f32 grad-accumulation buffers share the ZeRO-1 layout (the
        # acc_shardings hook of make_train_step); one device has nothing
        # to shard them over, and its step then binds no mesh's devices
        self._acc_sh = None if self.mesh.size == 1 else jax.tree.map(
            lambda sh, p: zero1_opt_sharding(sh, p.shape),
            self.state_sh.params, params)

        self.lr_fn = cosine_schedule(self.tcfg.base_lr, self.tcfg.warmup,
                                     self.tcfg.steps)
        self._buckets: dict[tuple, Callable] = {}
        self._batch_sh = None
        self.obs.watchdog.expect(self.plan0.buckets())
        self.watchdog = StragglerWatchdog()
        self.async_ckpt = ckpt_lib.AsyncCheckpointer()
        self.start_step = 0
        self.history: list[dict] = []

    # ---- compat views ------------------------------------------------------
    @property
    def params(self):
        """The current (sharded) model parameters."""
        return self.state.params

    @property
    def opt_state(self):
        """The current (ZeRO-1-sharded) optimizer state."""
        return self.state.opt

    # ---- pattern bucketing -------------------------------------------------
    def _batch_shardings(self, batch):
        if self._batch_sh is None:
            axes = batch_logical_axes(self.cfg, batch)
            self._batch_sh = jax.tree.map(
                lambda x, ax: logical_sharding(x.shape, ax, self.mesh,
                                               self.rules, is_param=False),
                batch, axes)
        return self._batch_sh

    def _step_fn(self, dp: int, bias: int, batch) -> Callable:
        key = (dp, bias)
        if key not in self._buckets:
            self.obs.watchdog.record_compile(key)
            self.obs.registry.counter("train_compiles_total",
                                      bucket_labels(dp, bias)).inc()
            pat = self.plan.bind(dp, bias) if dp > 1 else plan_mod.IDENTITY
            base = make_train_step(
                self.cfg, self.optimizer,
                microbatches=self.tcfg.microbatches, pat=pat,
                clip_norm=self.tcfg.clip_norm,
                compress_grads=self.tcfg.compress_grads,
                acc_shardings=self._acc_sh)

            def step(state, b, lr):
                p, o, metrics = base(state.params, state.opt, b, lr)
                # extras are host-managed: identity pass-through keeps the
                # search state inside the donated/checkpointed pytree
                return TrainState(params=p, opt=o, step=state.step + 1,
                                  extras=state.extras), metrics

            repl = NamedSharding(self.mesh, PSpec())
            self._buckets[key] = jax.jit(
                step,
                in_shardings=(self.state_sh, self._batch_shardings(batch),
                              repl),
                out_shardings=(self.state_sh, repl),
                donate_argnums=(0,))
        return self._buckets[key]

    def warm_start(self, batch_fn: Callable[[int], dict]):
        """Pre-compile every ``plan.buckets()`` executable.

        Training then never stalls on a mid-run compile; afterwards the
        compile cache holds exactly ``len(plan.buckets())`` executables
        (the acceptance invariant — bias is static per bucket) and the
        watchdog is frozen: any further compile is a violation.  Nothing
        executes: ``lower().compile()`` fills the same cache the steps hit,
        and needs no second copy of the state on the device.
        """
        batch = jax.tree.map(jnp.asarray, batch_fn(0))
        tracer = self.obs.tracer
        with set_mesh_and_rules(self.mesh, self.rules):
            for dp, b in self.plan0.buckets():
                fn = self._step_fn(dp, b, batch)
                with tracer.span("train.compile", dp=dp, bias=b):
                    fn.lower(self.state, batch, jnp.float32(0.0)).compile()
        self.obs.watchdog.freeze()

    # ---- fault tolerance ---------------------------------------------------
    def maybe_resume(self):
        """Restore the newest checkpoint (if any) with the CURRENT mesh's
        shardings — the elastic path: storage is unsharded, so a restart on
        a different topology just re-shards on load."""
        if not self.tcfg.ckpt_dir:
            return
        try:
            step, restored = ckpt_lib.restore_latest(
                self.tcfg.ckpt_dir, self.state, self.state_sh)
        except AssertionError as e:
            raise ValueError(
                f"checkpoint in {self.tcfg.ckpt_dir!r} does not match the "
                f"TrainState layout (params/opt/step) — it was likely "
                f"written by the pre-mesh-aware Trainer as a "
                f"{{'params', 'opt'}} tree.  Load it manually with "
                f"train.checkpoint.restore(dir, step, "
                f"{{'params': ..., 'opt': ...}}) and re-save through the "
                f"current trainer") from e
        if restored is not None:
            self.state = restored
            self.start_step = step + 1
            if self.online_search is not None:
                ext = getattr(self.state, "extras", None) or {}
                if "search" in ext:
                    # restore logits + EMAs, then re-derive the dispatch
                    # distribution so the resumed run draws the same
                    # buckets as an uninterrupted one from this step
                    self.online_search.load_state(
                        jax.tree.map(np.asarray, ext["search"]))
                    self._set_plan(self.plan0.with_dist(
                        self.online_search.current_dist()))

    def _maybe_checkpoint(self, step: int, force: bool = False):
        if not self.tcfg.ckpt_dir:
            return
        if force or (step + 1) % self.tcfg.ckpt_every == 0:
            self.async_ckpt.save_async(self.tcfg.ckpt_dir, step, self.state)

    # ---- online search -----------------------------------------------------
    def _set_plan(self, plan: DropoutPlan) -> None:
        """Swap in a re-distributed plan view and retarget the drift
        monitor's expectations (its observation window resets with the
        target).  The bucket universe is unchanged by construction."""
        self.plan = plan
        if self.obs.drift is not None:
            self.obs.drift.retarget(plan)

    def _search_hook(self, step: int, rec: dict, tracer) -> None:
        """Post-step online-search protocol: fold the loss into the EMAs,
        resync at window boundaries, and mirror the controller state into
        ``TrainState.extras`` so the next checkpoint carries it."""
        ctl = self.online_search
        ctl.observe(step, rec["loss"], rec["dp"], rec["bias"])
        if ctl.should_resync(step):
            drift_rep = None
            if self.obs.drift is not None:
                drift_rep = self.obs.drift.report(
                    min_samples=min(50, ctl.cfg.resync_every))
            with tracer.span("train.search_resync", step=step):
                new_plan = ctl.resync(step)
            if drift_rep is not None:
                ctl.resync_log[-1]["drift_verdict"] = drift_rep["verdict"]
            self._set_plan(new_plan)
        self.state.extras["search"] = jax.tree.map(
            jnp.asarray, ctl.state_arrays())

    # ---- the loop ----------------------------------------------------------
    def run(self, batch_fn: Callable[[int], dict],
            until: Optional[int] = None) -> list[dict]:
        """Train until ``until`` (default tcfg.steps); returns history.

        Each step runs under the ``train.*`` phase spans, in this order:
        ``sample``, ``data``, ``lr``, ``dispatch`` (the jitted call, with a
        new bucket's first compile), ``sync`` (waiting for the loss and
        reading it back) and ``record``; ``train.run`` covers the call."""
        until = until or self.tcfg.steps
        tracer, reg = self.obs.tracer, self.obs.registry
        with tracer.span("train.run", start=self.start_step, until=until):
            self.maybe_resume()
            with set_mesh_and_rules(self.mesh, self.rules):
                for step in range(self.start_step, until):
                    self._run_step(step, batch_fn, tracer, reg)
            self.async_ckpt.wait()
            if self.tcfg.ckpt_dir:
                ckpt_lib.save(self.tcfg.ckpt_dir, until - 1, self.state)
        return self.history

    def _run_step(self, step: int, batch_fn, tracer, reg) -> None:
        with tracer.span("train.sample", step=step):
            bound = self.plan.sample(step)
            if (bound.dp, bound.bias) not in self._superset:
                # defense in depth: with_dist already forbids support
                # escapes, so an off-superset draw means state
                # corruption — raise rather than compile on the hot path
                raise BucketSupersetViolation(
                    f"sampled bucket (dp={bound.dp}, bias={bound.bias})"
                    f" outside the frozen superset "
                    f"{sorted(self._superset)}")
            if self.obs.drift is not None:
                self.obs.drift.observe_bound(bound)
        with tracer.span("train.data", step=step):
            batch = jax.tree.map(jnp.asarray, batch_fn(step))
        t0 = time.perf_counter()
        with tracer.span("train.lr", step=step):
            lr = jnp.float32(self.lr_fn(step))
        with tracer.span("train.dispatch", dp=bound.dp, bias=bound.bias):
            fn = self._step_fn(bound.dp, bound.bias, batch)
            self.state, metrics = fn(self.state, batch, lr)
        with tracer.span("train.sync", step=step):
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            loss = float(metrics["loss"])
        with tracer.span("train.record", step=step):
            slow = self.watchdog.observe(dt)
            blabels = bucket_labels(bound.dp, bound.bias)
            reg.histogram("train_step_time_s", blabels).record(dt)
            reg.counter("train_steps_total", blabels).inc()
            if slow:
                reg.counter("train_stragglers_total", blabels).inc()
                tracer.instant("straggler", step=step, dt=dt)
            rec = {"step": step, "loss": loss, "dp": bound.dp,
                   "bias": bound.bias, "dt": dt, "straggler": slow}
            self.history.append(rec)
            if self.online_search is not None:
                self._search_hook(step, rec, tracer)
            if step % self.tcfg.log_every == 0:
                print(f"step {step}: loss={rec['loss']:.4f} "
                      f"dp={bound.dp} dt={dt*1e3:.0f}ms"
                      + (" [STRAGGLER]" if slow else ""), flush=True)
            self._maybe_checkpoint(step)
