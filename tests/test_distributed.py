"""Distribution substrate: sharding rules, compression, dry-run smoke.

The dry-run smoke runs in a subprocess with 8 host devices (2x2 / 2x2x2
meshes) so the main test process keeps its single-device view.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis", reason="hypothesis not installed "
                    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.distributed.compression import (compression_error_bound,
                                           int8_roundtrip)
from repro.distributed.sharding import param_spec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_param_spec_rules():
    assert param_spec("embed/table", 2) == P("model", "data")
    assert param_spec("groups/0/attn/wq", 2) == P("data", "model")
    # stacked scan-group leading axis is replicated
    assert param_spec("groups/0/attn/wq", 3) == P(None, "data", "model")
    assert param_spec("groups/0/moe/w_in", 4) == P(None, None, "data", "model")
    assert param_spec("groups/0/mamba/out_proj", 3) == P(None, "model", "data")
    assert param_spec("groups/0/ln1/scale", 2) == P(None, None)
    assert param_spec("groups/0/rwkv/wr", 3) == P(None, "data", "model")
    assert param_spec("something/unknown", 1) == P(None)


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_int8_compression_error_bound(seed):
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.standard_normal((32, 16)).astype(np.float32)
                    * rng.uniform(1e-3, 1e3))
    out = int8_roundtrip(g)
    bound = compression_error_bound(g)
    assert float(jnp.max(jnp.abs(out - g))) <= bound * 1.001


def test_int8_compression_preserves_direction():
    g = jnp.asarray(np.random.default_rng(0).standard_normal((64, 64)),
                    jnp.float32)
    out = int8_roundtrip(g)
    cos = float(jnp.sum(g * out)
                / (jnp.linalg.norm(g) * jnp.linalg.norm(out)))
    assert cos > 0.999


@pytest.mark.slow
def test_dryrun_reduced_single_and_multi_mesh(tmp_path):
    """Full dry-run machinery end-to-end on an 8-device host: one train cell
    and one decode cell, on both the 2x2 single and 2x2x2 multi-pod mesh."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    for arch, shape in [("qwen1.5-4b", "train_4k"),
                        ("gemma2-9b", "decode_32k")]:
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
             "--shape", shape, "--mesh", "both", "--reduced",
             "--out", str(tmp_path)],
            env=env, capture_output=True, text=True, timeout=900)
        assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    import json
    rec = json.load(open(tmp_path / "qwen1.5-4b__train_4k__multi.json"))
    assert rec["mesh_axes"] == ["pod", "data", "model"]
    assert rec["hlo_flops"] > 0
    assert rec["collectives"]["bytes_wire"] > 0


def test_constrain_divisibility_guard():
    """constrain() drops axes that don't divide the dim (long_500k batch=1)."""
    from repro.distributed.sharding import Runtime, constrain
    rt = Runtime(mesh=None)
    x = jnp.ones((1, 8, 4))
    # off-mesh: pure no-op
    assert constrain(rt, x, "dp", None, None) is x


@pytest.mark.slow
def test_gpipe_pipeline_parallelism(tmp_path):
    """GPipe over a 4-stage mesh == sequential model (fwd + grad), in a
    4-device subprocess."""
    script = r'''
import jax, jax.numpy as jnp
from repro.distributed.pipeline import gpipe, stack_stage_params
mesh = jax.make_mesh((4,), ("stage",),
                     axis_types=(jax.sharding.AxisType.Auto,))
d = 16
def stage_fn(p, x):
    return x + jnp.tanh(x @ p["w1"]) @ p["w2"]
stages = [{"w1": jax.random.normal(jax.random.PRNGKey(i), (d, 32)) * 0.3,
           "w2": jax.random.normal(jax.random.PRNGKey(100 + i), (32, d)) * 0.3}
          for i in range(4)]
stacked = stack_stage_params(stages)
x = jax.random.normal(jax.random.PRNGKey(7), (8, d))
seq = x
for p in stages:
    seq = stage_fn(p, seq)
piped = gpipe(stage_fn, mesh, n_microbatches=4)
y = jax.jit(piped)(stacked, x)
assert float(jnp.max(jnp.abs(y - seq))) < 1e-5
g = jax.grad(lambda ps, xx: jnp.sum(piped(ps, xx) ** 2))(stacked, x)
assert all(bool(jnp.all(jnp.isfinite(v))) for v in jax.tree.leaves(g))
print("PIPE_OK")
'''
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and "PIPE_OK" in r.stdout, r.stderr[-2000:]
