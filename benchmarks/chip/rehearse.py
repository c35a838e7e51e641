"""Compile a cell's executables for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 benchmarks/chip/rehearse.py \\
        train [--config qwen2-1.5b-train] --layers 8 [--dp 1 2]

Prints, per executable, what ``memory_analysis`` gives (arguments,
temporaries, outputs, aliased bytes) and the count of Pallas kernels
(``tpu_custom_call``).  The configuration ``configs/<config>.json`` and
its adapter ``models/<model_type>.py`` are found by name.  This sizes the
training depth before any chip time is spent; it measures no time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
GIB = 1 << 30


def described_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # the Pallas wrappers pick interpret mode on a CPU backend; the
    # described chip needs the Mosaic kernels
    from repro.kernels import ops
    ops._interpret = lambda: False
    return topo.devices[0]


def report(name: str, compiled) -> dict:
    m = compiled.memory_analysis()
    row = {"exe": name,
           "args_gib": m.argument_size_in_bytes / GIB,
           "temp_gib": m.temp_size_in_bytes / GIB,
           "out_gib": m.output_size_in_bytes / GIB,
           "alias_gib": m.alias_size_in_bytes / GIB,
           "kernels": compiled.as_text().count("tpu_custom_call")}
    row["total_gib"] = (row["args_gib"] + row["temp_gib"] + row["out_gib"]
                        - row["alias_gib"])
    print(json.dumps(row), flush=True)
    return row


def train(args, dev) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.core.plan import build_plan
    from repro.models.transformer import batch_logical_axes, init_lm
    from repro.optim.optimizers import AdamW
    from repro.parallel.sharding import (PROFILES, logical_sharding,
                                         set_mesh_and_rules,
                                         zero1_opt_sharding)
    from repro.train.distributed import TrainState, state_shardings
    from repro.train.train_step import make_train_step
    sys.path.insert(0, str(BENCH))
    import run as R
    conf = R.load_json(BENCH / "configs" / f"{args.config}.json")
    conf = dict(conf, num_hidden_layers=args.layers)
    model = R.load_module(BENCH / "models" / f"{conf['model_type']}.py")
    cfg = model.program_config(conf, conf["program"])
    mesh = Mesh([[dev]], ("data", "model"))
    rules = PROFILES["tp"]
    params, axes = init_lm(cfg)
    opt = AdamW()
    abs_opt = jax.eval_shape(opt.init, params)
    sh = state_shardings(params, axes, abs_opt, mesh, rules)
    acc = jax.tree.map(lambda s, p: zero1_opt_sharding(s, p.shape),
                       sh.params, params)
    seq = args.seq
    batch = {"tokens": jax.ShapeDtypeStruct((1, seq), jnp.int32),
             "labels": jax.ShapeDtypeStruct((1, seq), jnp.int32)}
    bsh = jax.tree.map(lambda x, ax: logical_sharding(
        x.shape, ax, mesh, rules, is_param=False), batch,
        batch_logical_axes(cfg, batch))
    repl = NamedSharding(mesh, PartitionSpec())
    state = TrainState(params=params, opt=abs_opt,
                       step=jax.ShapeDtypeStruct((), jnp.int32))
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        state, TrainState(params=sh.params, opt=sh.opt, step=repl))
    batch = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        batch, bsh)
    nb, block = model.plan_blocks(cfg)
    plan = build_plan("rdp", 0.5, nb=nb, dp_max=8, block=block,
                      backend="pallas", seed=0)
    from repro.core import plan as plan_mod
    for dp in args.dp:
        pat = plan.bind(dp, dp - 1) if dp > 1 else plan_mod.IDENTITY
        base = make_train_step(cfg, opt, pat=pat, clip_norm=1.0,
                               acc_shardings=acc)

        def step(state, b, lr, _base=base):
            p, o, met = _base(state.params, state.opt, b, lr)
            return TrainState(params=p, opt=o, step=state.step + 1), met

        fn = jax.jit(step, in_shardings=(
            TrainState(params=sh.params, opt=sh.opt, step=repl), bsh, repl),
            out_shardings=(TrainState(params=sh.params, opt=sh.opt,
                                      step=repl), repl),
            donate_argnums=(0,))
        lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=repl)
        with set_mesh_and_rules(mesh, rules):
            report(f"train L={args.layers} seq={seq} dp={dp}",
                   fn.lower(state, batch, lr).compile())


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    t = sub.add_parser("train")
    t.add_argument("--config", default="qwen2-1.5b-train")
    t.add_argument("--layers", type=int, default=8)
    t.add_argument("--seq", type=int, default=4096)
    t.add_argument("--dp", type=int, nargs="+", default=[1, 2])
    args = ap.parse_args()
    dev = described_chip()
    train(args, dev)


if __name__ == "__main__":
    main()
