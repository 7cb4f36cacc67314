"""Quickstart: score graph pairs with SimGNN on the SPA-GCN stack.

Runs on CPU in seconds:
    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp

from repro.configs.simgnn_aids import CONFIG as CFG
from repro.core.simgnn import init_simgnn_params, pair_score, simgnn_loss
from repro.data.graphs import pair_stream
from repro.kernels.ops import simgnn_pair_score_kernel
from repro.launch import compile_cache


def main():
    compile_cache.enable()
    params = init_simgnn_params(jax.random.PRNGKey(0), CFG)
    batch = next(pair_stream(seed=0, batch=8))
    args = [jnp.asarray(batch[k]) for k in
            ("adj1", "feats1", "mask1", "adj2", "feats2", "mask2")]

    scores = jax.jit(pair_score)(params, *args)
    print("similarity scores (jnp path):   ",
          [f"{s:.4f}" for s in scores.tolist()])

    scores_k = simgnn_pair_score_kernel(params, *args)
    print("similarity scores (Pallas path):",
          [f"{s:.4f}" for s in scores_k.tolist()])
    print("GED targets:                    ",
          [f"{t:.4f}" for t in batch["target"].tolist()])

    dense_keys = ("adj1", "feats1", "mask1", "adj2", "feats2", "mask2",
                  "target")
    loss = simgnn_loss(params, {k: jnp.asarray(batch[k]) for k in dense_keys})
    print(f"untrained MSE vs exp(-nGED) targets: {float(loss):.4f}")
    print("run `python -m repro.launch.train --model simgnn` to train it.")


if __name__ == "__main__":
    main()
