"""End-to-end training launcher.

Two drivers:
  * `--model simgnn` (default): trains the paper's SimGNN on the synthetic
    AIDS-like pair stream — the (b) end-to-end example required by the
    assignment (100M-class model for a few hundred steps works on CPU).
  * `--model <arch-id>`: trains an assigned LM architecture (reduced config
    on CPU with --reduced; full config on a real fleet with --mesh).

Both paths share train/loop.py: checkpoint/restart, straggler monitoring,
failure retry. `--simulate-failure N` kills the process at step N to
exercise the restart path (tests do this in-process).
"""

from __future__ import annotations

import argparse
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np


def train_simgnn(args):
    from repro.configs.simgnn_aids import CONFIG as scfg
    from repro.core.engine import ScoringEngine
    from repro.core.simgnn import init_simgnn_params
    from repro.data.graphs import pair_stream
    from repro.distributed.sharding import (force_host_device_count,
                                            tile_runtime)
    from repro.train.optimizer import adamw_init
    from repro.train.step import build_simgnn_train_step
    from repro.train import loop

    runtime = None
    if args.devices > 1:
        # Data-parallel packed training (DESIGN.md §16): the engine shards
        # each batch's tile axis over a 1-D mesh and psums the chunk-scan
        # loss/grads. On CPU-only hosts the mesh is simulated (the opt-in
        # host-platform XLA flag, a no-op on real accelerators) so the
        # flag is exercisable anywhere. Must run before first backend use.
        force_host_device_count(args.devices)
        runtime = tile_runtime(args.devices)
    params = init_simgnn_params(jax.random.PRNGKey(args.seed), scfg)
    opt_state = adamw_init(params)
    # The engine dispatches the forward AND backward passes (DESIGN.md §11):
    # it measures each batch and picks the packed-sparse / packed-dense /
    # reference executor; the step itself contains no path selection.
    engine = ScoringEngine(params, scfg, runtime=runtime)
    step_fn = build_simgnn_train_step(engine, peak_lr=args.lr)
    stream = pair_stream(args.seed, args.batch, max_nodes=scfg.max_nodes)
    batches = {}

    def batch_fn(step):            # deterministic per step for restartability
        while step not in batches:
            batches[len(batches)] = next(stream)
        return batches[step]

    def on_metrics(step, rec):
        print(f"step {step:5d} loss {rec['loss']:.5f} "
              f"gnorm {rec['grad_norm']:.3f} {rec['sec_per_step']*1e3:.0f}ms")
        if args.simulate_failure and step == args.simulate_failure:
            print("[train] simulated failure!")
            os._exit(42)

    def on_resume(step, skipped):
        # Land the verified-restore outcome on the engine's counters so
        # `engine.health()` reports the resume story next to the breakers
        # (DESIGN.md §13): how many corrupt checkpoints the walk-back
        # skipped, and whether a resume happened at all.
        if step is not None:
            engine.counters["ckpt_resumes"] += 1
        engine.counters["ckpt_walkback_skipped"] += len(skipped)

    params, opt_state, hist = loop.run(
        step_fn, params, opt_state, batch_fn, n_steps=args.steps,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        resume=args.resume, on_metrics=on_metrics, on_resume=on_resume)
    if engine.counters.get("train_skipped_steps"):
        print(f"[train] skipped {engine.counters['train_skipped_steps']} "
              "non-finite steps")
    if engine.counters.get("ckpt_walkback_skipped"):
        print(f"[train] resume walked back past "
              f"{engine.counters['ckpt_walkback_skipped']} corrupt "
              "checkpoint(s)")
    print(f"[train] final loss {hist[-1]['loss']:.5f}")
    return hist


def train_lm(args):
    from repro.configs import get_config, reduced_config
    from repro.data.tokens import batch_for_step
    from repro.distributed.sharding import make_runtime
    from repro.models.init import init_params
    from repro.train.optimizer import adamw_init
    from repro.train.step import build_train_step
    from repro.train import loop
    from repro.launch.mesh import make_production_mesh

    cfg = reduced_config(args.model) if args.reduced else get_config(args.model)
    mesh = None
    if args.mesh != "none":
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    rt = make_runtime(mesh)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    opt_state = adamw_init(params, cfg.opt_state_dtype)
    step_fn = jax.jit(build_train_step(cfg, rt, peak_lr=args.lr,
                                       compress_grads=args.compress_grads),
                      donate_argnums=(0, 1))

    def batch_fn(step):
        b = batch_for_step(cfg, step, global_batch=args.batch,
                           seq_len=args.seq_len)
        return {k: jnp.asarray(v) for k, v in b.items()}

    def on_metrics(step, rec):
        print(f"step {step:5d} loss {rec['loss']:.4f} "
              f"gnorm {rec['grad_norm']:.2f} lr {rec['lr']:.2e} "
              f"{rec['sec_per_step']*1e3:.0f}ms")

    params, opt_state, hist = loop.run(
        step_fn, params, opt_state, batch_fn, n_steps=args.steps,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        resume=args.resume, on_metrics=on_metrics)
    print(f"[train] final loss {hist[-1]['loss']:.4f}")
    return hist


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="simgnn")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    # "auto" restores the latest complete checkpoint in --ckpt-dir and
    # replays the deterministic data stream from there (DESIGN.md §6/§12);
    # "none" always starts from step 0 (fresh run into a reused directory).
    ap.add_argument("--resume", default="auto", choices=["auto", "none"])
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default="none", choices=["none", "single", "multi"])
    ap.add_argument("--compress-grads", action="store_true")
    # simgnn only: shard packed training over N mesh devices (§16). CPU
    # hosts simulate the mesh, so --devices 8 works on a laptop.
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--simulate-failure", type=int, default=0)
    args = ap.parse_args(argv)
    from repro.launch import compile_cache

    compile_cache.enable()
    if args.model == "simgnn":
        return train_simgnn(args)
    return train_lm(args)


if __name__ == "__main__":
    main()
