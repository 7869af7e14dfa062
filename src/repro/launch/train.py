"""Production training launcher.

Single host (this container):
  PYTHONPATH=src python -m repro.launch.train --arch granite-3-8b \
      --batch 8 --seq 256 --steps 100 --set n_layers=4 --set d_model=256

Multi-host pods: the same entry point runs under one process per host with
jax.distributed (see launch/pod_launch.sh); device mesh axes come from
--mesh. Checkpoints are elastic — a run stopped on one mesh resumes on
another (train/checkpoint.py resharding).
"""

from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp

from repro.configs import apply_overrides, get_config
from repro.data.pipeline import make_dataset, shard_batch
from repro.dist import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.model import get_model
from repro.optim import adamw
from repro.train.fault_tolerance import StepMonitor, resilient_train
from repro.train.loop import make_train_step


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="repro-100m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default="auto",
                    help='"auto", "DxM" (e.g. 4x2), or "PxDxM"')
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--data", default="synthetic", choices=["synthetic",
                                                            "bytes"])
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--distributed", action="store_true",
                    help="initialize jax.distributed from env (multi-host)")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (repeatable)")
    return ap.parse_args(argv)


def build_mesh(spec: str):
    n = len(jax.devices())
    if spec == "auto":
        model = 1
        while model * 2 <= n and n % (model * 2) == 0 and model < 8:
            model *= 2
        return make_mesh((n // model, model), ("data", "model"))
    dims = tuple(int(x) for x in spec.split("x"))
    axes = {2: ("data", "model"), 3: ("pod", "data", "model")}[len(dims)]
    return make_mesh(dims, axes)


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    if args.distributed:
        jax.distributed.initialize()
    cfg = get_config(args.arch)
    overrides = dict(kv.split("=", 1) for kv in args.set)
    if overrides:
        cfg = apply_overrides(cfg, overrides)
    mesh = build_mesh(args.mesh)
    api = get_model(cfg)
    print(f"arch={cfg.name} devices={len(jax.devices())} "
          f"mesh={dict(mesh.shape)}")

    with shd.activate(mesh):
        params = api.init(jax.random.PRNGKey(0))
        pspec = shd.param_specs(params, mesh)
        params = jax.tree_util.tree_map(jax.device_put, params, pspec)
        ocfg = adamw.AdamWConfig(lr=args.lr,
                                 int8_moments=cfg.int8_optimizer)
        opt = adamw.init(params, ocfg)
        step_jit = jax.jit(make_train_step(api, ocfg,
                                           total_steps=args.steps,
                                           warmup=max(args.steps // 20, 5),
                                           grad_specs=pspec))

        def step_fn(p, o, batch, s):
            return step_jit(p, o, shard_batch(batch, mesh), s)

        ds = make_dataset(cfg, batch=args.batch, seq=args.seq, seed=0,
                          source=args.data)
        monitor = StepMonitor()
        params, opt, history, restarts = resilient_train(
            train_step=step_fn, params=params, opt_state=opt, dataset=ds,
            ckpt_dir=args.ckpt, total_steps=args.steps,
            save_every=args.save_every, monitor=monitor)
    for s, l in history:
        print(f"step {s:5d}  loss {l:.4f}")
    print(f"done: restarts={restarts} stragglers={len(monitor.events)}")
    return params


if __name__ == "__main__":
    main()
