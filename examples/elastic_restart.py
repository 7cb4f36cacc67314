"""Elastic restart demo: train, checkpoint, crash, resume — then restore the
same checkpoint under a *different* mesh layout (the fleet-resize path).

    PYTHONPATH=src python examples/elastic_restart.py
"""

import os
import shutil

import jax
import jax.numpy as jnp

from repro.ckpt import manager as ckpt
from repro.configs import reduced_config
from repro.distributed.sharding import Runtime
from repro.data.tokens import batch_for_step
from repro.launch import compile_cache
from repro.models.init import init_params
from repro.train import loop
from repro.train.optimizer import adamw_init
from repro.train.step import build_train_step

CKPT = "/tmp/repro_elastic_demo"


def main():
    compile_cache.enable()
    shutil.rmtree(CKPT, ignore_errors=True)
    cfg = reduced_config("gemma2-9b")
    rt = Runtime(mesh=None)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = adamw_init(params)
    step = jax.jit(build_train_step(cfg, rt, peak_lr=3e-3))

    def batch_fn(s):
        b = batch_for_step(cfg, s, global_batch=8, seq_len=64)
        return {k: jnp.asarray(v) for k, v in b.items()}

    print("== phase 1: train 6 steps, checkpoint every 3")
    loop.run(step, params, opt, batch_fn, n_steps=6, ckpt_dir=CKPT,
             ckpt_every=3, resume=None, log_every=2)

    print("== phase 2: 'crash' and resume (auto picks up step 6)")
    p2, o2, hist = loop.run(step, params, opt, batch_fn, n_steps=10,
                            ckpt_dir=CKPT, ckpt_every=3, resume="auto",
                            log_every=2)
    print(f"resumed and reached step {int(o2.step)}")

    print("== phase 3: elastic restore (same ckpt, new device layout)")
    last = ckpt.latest_step(CKPT)
    # On a resized fleet this would pass the new mesh's NamedShardings;
    # off-mesh the restore just re-materializes on the local device.
    p3, o3 = ckpt.restore(CKPT, last, (p2, o2))
    diff = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
               for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(p3)))
    print(f"restored step {last}; max param diff after round trip: {diff:.1e}")


if __name__ == "__main__":
    main()
