"""LM training driver: mesh + sharded train_step + checkpoint/restart.

CPU-scale entry point exercising the full production path (sharding rules,
set_mesh constraints, checkpoint manager, token stream):

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --reduced --steps 20 --mesh 2x2 --ckpt-dir /tmp/ck --ckpt-every 10

On a fleet the same file runs under one process per host with
jax.distributed.initialize(); nothing else changes (the mesh constructor
sees all addressable devices).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.ckpt import CheckpointManager, restore_train_state
from repro.ckpt.checkpoint import latest_checkpoint
from repro.configs import get_config
from repro.data.tokens import TokenStream
from repro.models.model import init_params
from repro.sharding.rules import batch_spec, param_specs, tp_size
from repro.training.train_step import TrainState, make_train_step, train_state_init


def make_mesh(spec: str):
    from repro.launch.mesh import _mesh

    dims = tuple(int(t) for t in spec.split("x"))
    names = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    return _mesh(dims, names)


def state_shardings(state, mesh):
    pspecs = param_specs(state.params, mesh)
    sspecs = TrainState(
        params=pspecs,
        opt=type(state.opt)(step=P(), mu=pspecs, nu=pspecs),
        step=P(),
    )
    return jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs,
                        is_leaf=lambda x: isinstance(x, P))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--reduced", action="store_true", help="CPU-size config")
    ap.add_argument("--override", action="append", default=[],
                    help="config field override, e.g. --override n_layers=12")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    cfg = get_config(args.arch)
    over = {}
    for kv in args.override:
        k, v = kv.split("=", 1)
        over[k] = type(getattr(cfg, k))(v) if not isinstance(getattr(cfg, k), bool) else v == "True"
    if args.reduced:
        cfg = cfg.reduced(**over)
    elif over:
        from dataclasses import replace

        cfg = replace(cfg, **over)
    mesh = make_mesh(args.mesh)
    tp = tp_size(mesh)

    params = init_params(jax.random.key(0), cfg, tp)
    state = train_state_init(params)
    ssh = state_shardings(state, mesh)
    state = jax.device_put(state, ssh)
    bsh = NamedSharding(mesh, batch_spec(mesh, args.batch))

    stream = TokenStream(cfg.vocab, args.batch, args.seq, seed=17)
    start_step = 0
    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, keep=3, install_sigterm=True)
        if args.resume:
            path = latest_checkpoint(args.ckpt_dir)
            if path:
                state, manifest = restore_train_state(path, state, ssh)
                stream.load_state_dict(manifest["extras"]["stream"])
                start_step = int(manifest["step"])
                print(f"[train] resumed from {path} at step {start_step}")

    step_fn = jax.jit(
        make_train_step(cfg, tp=tp, lr=args.lr, grad_accum=args.grad_accum),
        in_shardings=(ssh, bsh, bsh),
        out_shardings=(ssh, NamedSharding(mesh, P())),
        donate_argnums=(0,),
    )

    if mgr:
        # preemption-safe: SIGTERM triggers a final checkpoint
        snap = {"state": state, "step": start_step}
        mgr.register_state_provider(
            lambda: (snap["step"], snap["state"], {"stream": stream.state_dict()})
        )

    with jax.set_mesh(mesh):
        t_last = time.time()
        for i in range(start_step, start_step + args.steps):
            tok, lab = stream.next()
            state, metrics = step_fn(state, jnp.asarray(tok), jnp.asarray(lab))
            if mgr:
                snap = {"state": state, "step": i + 1}
            if (i + 1) % 10 == 0 or i == start_step:
                loss = float(metrics["loss"])
                dt = time.time() - t_last
                t_last = time.time()
                print(f"[train] step {i+1} loss {loss:.4f} ({dt:.2f}s)")
            if mgr and (i + 1) % args.ckpt_every == 0:
                mgr.save(i + 1, state, {"stream": stream.state_dict()})
    if mgr:
        mgr.save(start_step + args.steps, state,
                 {"stream": stream.state_dict()}, block=True)
        mgr.close()
    print("[train] done; final loss", float(metrics["loss"]))
    return state


if __name__ == "__main__":
    main()
