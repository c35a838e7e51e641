"""End-to-end training launcher.

    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen2-1.5b --smoke --steps 50 --dropout 0.5 --pattern rdp

``--smoke`` runs the reduced config (the CPU path, with
``JAX_PLATFORMS=cpu``); without it the full config runs at its published
widths, and ``--layers N`` cuts its depth to fit one chip.  The launcher
wires together: config → pattern-distribution search (Alg. 1) → data
pipeline → DistributedTrainer (pattern bucketing × sharding profile,
checkpoints, watchdog).
``--backend pallas`` trains through the compact-DMA Pallas kernels
(custom-VJP backward, DESIGN.md §9).  ``--profile`` picks the
``parallel.sharding.PROFILES`` entry and ``--mesh-shape DxM`` (or
``PxDxM``) the mesh — e.g. with 8 forced host devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \\
        --dropout 0.5 --profile tp --mesh-shape 2x4
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from pathlib import Path

import jax

from repro.configs import get_spec, normalize
from repro.core.online_search import OnlineSearchConfig
from repro.core.plan import FAMILIES, build_plan, identity_plan
from repro.data.pipeline import SyntheticLMData
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import mesh_from_spec, single_device_mesh
from repro.models import init_lm, materialize
from repro.obs import Observability
from repro.optim.optimizers import AdamW
from repro.parallel.sharding import PROFILES
from repro.train.distributed import DistributedTrainer
from repro.train.loop import TrainerConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the config to its first N layers (every "
                         "width unchanged) so the model fits the device")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="target rate p for Approximate Random Dropout")
    ap.add_argument("--dp-max", type=int, default=8,
                    help="largest pattern period searched for K — restrict "
                         "when a sharded mesh rejects large-dp buckets "
                         "(see DropoutPlan.validate_mesh)")
    ap.add_argument("--pattern", default="rdp",
                    choices=sorted(f for f in FAMILIES if f != "identity"),
                    help="pattern family from the registry (core.plan."
                         "FAMILIES) — e.g. rdp/tdp, head_rdp, ssm_row, "
                         "expert_drop")
    ap.add_argument("--backend", choices=["slice", "gather", "pallas"],
                    default="slice",
                    help="pattern execution backend (pallas = compact "
                         "kernels, fwd + custom-VJP bwd)")
    ap.add_argument("--profile", choices=sorted(PROFILES), default="tp",
                    help="sharding profile (parallel.sharding.PROFILES)")
    ap.add_argument("--mesh-shape", default=None,
                    help="mesh as DxM or PxDxM (e.g. 2x4); default: a "
                         "1x1 mesh on the first device")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSONL of the "
                         "trainer's train.* phase spans here")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the final metrics-registry snapshot "
                         "(JSONL; use .prom suffix for Prometheus text)")
    ap.add_argument("--warm-start", action="store_true",
                    help="precompile every plan bucket before step 0 "
                         "and freeze the recompile watchdog")
    ap.add_argument("--online-search", action="store_true",
                    help="re-run Alg. 1 during training (core.online_search"
                         ".OnlineSearch): per-layer K distributions drift "
                         "toward cheaper patterns while the loss EMA "
                         "permits, reweighting within the frozen bucket "
                         "superset (DESIGN.md §14)")
    ap.add_argument("--resync-every", type=int, default=50,
                    help="steps between online-search warm restarts")
    args = ap.parse_args(argv)
    use_compile_cache()

    spec = get_spec(normalize(args.arch))
    cfg = spec.smoke if args.smoke else spec.config
    if args.layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    params = materialize(jax.random.PRNGKey(args.seed), init_lm(cfg)[0])

    if args.dropout > 0:
        # dp must divide the pattern-block count (the Trainer re-pins nb to
        # the model's cfg.pattern_nb; _attn/_ssm/_moe_pat re-pin per site).
        # block only feeds the equivalence oracle — pure-SSM archs have
        # d_ff == 0, so clamp to 1 there.
        plan = build_plan(args.pattern, args.dropout, nb=cfg.pattern_nb,
                          dp_max=args.dp_max,
                          block=max(1, cfg.d_ff // cfg.pattern_nb),
                          backend=args.backend, seed=args.seed)
    else:
        plan = identity_plan()

    data = SyntheticLMData(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
        seed=args.seed, n_codebooks=cfg.n_codebooks,
        vision_tokens=cfg.vision_tokens, vision_dim=cfg.vision_dim)

    tcfg = TrainerConfig(steps=args.steps, base_lr=args.lr,
                         microbatches=args.microbatches,
                         ckpt_dir=args.ckpt_dir,
                         compress_grads=args.compress_grads)
    mesh = (mesh_from_spec(args.mesh_shape) if args.mesh_shape
            else single_device_mesh())
    obs = Observability.create(trace_path=args.trace, plan=plan)
    osearch = None
    if args.online_search:
        if args.dropout <= 0:
            ap.error("--online-search needs --dropout > 0 (a searched plan)")
        osearch = OnlineSearchConfig(resync_every=args.resync_every,
                                     seed=args.seed)
    trainer = DistributedTrainer(cfg, AdamW(), params, mesh=mesh,
                                 profile=args.profile, plan=plan, tcfg=tcfg,
                                 obs=obs, online_search=osearch)
    print(f"mesh {dict(mesh.shape)} profile {args.profile} "
          f"buckets {trainer.plan.buckets()}", flush=True)
    if args.warm_start:
        trainer.warm_start(data.batch)
    history = trainer.run(data.batch)
    print(f"final loss: {history[-1]['loss']:.4f} "
          f"(from {history[0]['loss']:.4f}); "
          f"stragglers flagged: {trainer.watchdog.flagged}")
    if obs.drift is not None:
        drift = obs.drift.report(min_samples=min(50, args.steps))
        print(f"pattern drift: {drift['verdict']} "
              f"(max dev {drift['max_abs_deviation']:.4f} over "
              f"{drift['samples']} draws)")
    if trainer.online_search is not None:
        ctl = trainer.online_search
        print(f"online search: {ctl.resyncs} resyncs, "
              f"rate {plan.expected_rate():.3f} -> "
              f"{trainer.plan.expected_rate():.3f}, "
              f"E[1/dp] {trainer.plan.expected_flop_fraction():.3f}")
        for rec in ctl.resync_log:
            print(f"  resync@{rec['step']}: ema={rec['ema_loss']:.4f} "
                  f"rate={rec['expected_rate']:.3f} "
                  f"drift={rec.get('drift_verdict', 'n/a')}")
    if obs.watchdog.violation_count:
        print(f"RECOMPILE VIOLATIONS: {obs.watchdog.violation_count}")
    if args.trace:
        print(f"trace -> {obs.tracer.write()}")
    if args.metrics_out:
        text = (obs.registry.to_prometheus()
                if args.metrics_out.endswith(".prom")
                else obs.registry.to_jsonl())
        Path(args.metrics_out).write_text(text)
        print(f"metrics -> {args.metrics_out}")
    if args.out:
        Path(args.out).write_text(json.dumps(history))
    return trainer


if __name__ == "__main__":
    main()
