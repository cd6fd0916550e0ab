"""Distributed training launcher.

Builds the production mesh (or a host mesh for CPU smoke), attaches the
FSDP+TP shardings from sharding/specs.py, and runs the training loop on
synthetic LM data.  On this CPU host use ``--host-mesh`` (optionally under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --smoke --steps 50 --batch 8 --seq 256
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.data.synthetic import lm_batches, needle_batches
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.model import build_model
from repro.sharding import ctx as shctx
from repro.sharding import specs as sh
from repro.training import checkpoint as ckpt
from repro.training import loop as train_loop
from repro.training import optimizer as opt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--data", choices=("lm", "needle"), default="lm")
    ap.add_argument("--host-mesh", default=None,
                    help="DATAxMODEL, e.g. 4x2 (CPU host devices)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--save", default=None, help="checkpoint path")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    cfg = dataclasses.replace(cfg, remat=not args.smoke)
    model = build_model(cfg)

    if args.host_mesh:
        d, m = (int(x) for x in args.host_mesh.split("x"))
        mesh = make_host_mesh(model=m, data=d)
    elif jax.device_count() >= 256:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    else:
        mesh = None   # single device

    ocfg = opt.OptimizerConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                               total_steps=args.steps)
    key = jax.random.PRNGKey(0)
    gen = (lm_batches(key, cfg.vocab, args.batch, args.seq) if args.data == "lm"
           else needle_batches(key, cfg.vocab, args.batch, args.seq | 1))

    if mesh is None:
        state, _ = train_loop.train(model, gen, ocfg=ocfg, steps=args.steps)
    else:
        shctx.set_policy(mesh, tuple(a for a in ("pod", "data")
                                     if a in mesh.axis_names))
        with mesh:
            state = train_loop.init_state(model, key)
            pspec = sh.param_specs(cfg, state.params, mesh)
            st_sh = sh.to_shardings(mesh, train_loop.TrainState(
                params=pspec, opt=opt.OptState(step=P(), mu=pspec, nu=pspec)))
            state = jax.device_put(state, st_sh)
            step_fn = jax.jit(train_loop.make_train_step(model, ocfg),
                              in_shardings=(st_sh, None), donate_argnums=0)
            for i in range(args.steps):
                batch = next(gen)
                state, metrics = step_fn(state, batch)
                if i % 10 == 0:
                    print(f"step {i:5d} loss {float(metrics['loss']):.4f} "
                          f"gnorm {float(metrics['grad_norm']):.3f}")
        shctx.clear_policy()

    if args.save:
        ckpt.save(args.save, state.params, {"arch": cfg.name,
                                            "steps": args.steps})
        print("saved", args.save)


if __name__ == "__main__":
    main()
