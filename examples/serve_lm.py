"""Serve a (reduced) LM: prefill + KV-cache greedy decode, the serving path
the decode_32k / long_500k dry-run cells exercise at production shapes.

    PYTHONPATH=src python examples/serve_lm.py --arch gemma2-9b --new 12
"""

import argparse

import jax
import jax.numpy as jnp

from repro.configs import reduced_config
from repro.distributed.sharding import Runtime
from repro.launch import compile_cache
from repro.models import lm
from repro.models.init import init_params


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-9b")
    ap.add_argument("--new", type=int, default=12)
    args = ap.parse_args()

    cfg = reduced_config(args.arch)
    rt = Runtime(mesh=None)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size)
    last, caches, pos = jax.jit(
        lambda p, t: lm.prefill(p, cfg, rt, t, cache_len=8 + args.new)
    )(params, prompt)
    decode = jax.jit(lambda p, t, c, s: lm.decode_step(p, cfg, rt, t, c, s))
    toks = [jnp.argmax(last, -1)]
    for _ in range(args.new - 1):
        logits, caches, pos = decode(params, toks[-1][:, None], caches, pos)
        toks.append(jnp.argmax(logits, -1))
    out = jnp.stack(toks, 1)
    print(f"arch={args.arch} (reduced) prompt={prompt.tolist()}")
    print(f"greedy continuation: {out.tolist()}")


if __name__ == "__main__":
    main()
