"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode).

Tolerance policy follows /opt/skills/resources/kernel_taxonomy.md Part E:
fp32 sweeps at 1e-5-class atol, bf16 at 2x measured bf16-vs-fp32 ref error.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.gcn import normalized_adjacency
from repro.core.simgnn import SimGNNConfig, init_simgnn_params
from repro.data.graphs import pair_stream
from repro.kernels import ops, ref
from repro.kernels.flash_attn import flash_attention
from repro.kernels.fused_gcn import fused_gcn_att
from repro.kernels.wkv6 import wkv6


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-9))


# --------------------------------------------------------------- fused_gcn

@pytest.mark.parametrize("n_nodes,block_graphs", [(8, 2), (16, 4), (32, 8),
                                                  (64, 4)])
def test_fused_gcn_shapes(n_nodes, block_graphs):
    cfg = SimGNNConfig(max_nodes=n_nodes)
    params = init_simgnn_params(jax.random.PRNGKey(0), cfg)
    if n_nodes >= 64:
        batch = next(pair_stream(1, 8, max_nodes=n_nodes))
        adj, feats, mask = (jnp.asarray(batch["adj1"]),
                            jnp.asarray(batch["feats1"]),
                            jnp.asarray(batch["mask1"]))
    else:                        # synthesize graphs that fit the bucket
        key = jax.random.PRNGKey(1)
        adj = (jax.random.uniform(key, (8, n_nodes, n_nodes)) > 0.5).astype(jnp.float32)
        adj = jnp.triu(adj, 1)
        adj = adj + adj.transpose(0, 2, 1)
        mask = jnp.ones((8, n_nodes))
        feats = jax.random.normal(key, (8, n_nodes, cfg.n_node_labels))
    a_norm = normalized_adjacency(adj, mask)
    out_k = fused_gcn_att(a_norm, feats, mask, params["gcn"],
                          params["att"]["w"], block_graphs=block_graphs,
                          interpret=True)
    out_r = ref.fused_gcn_att_ref(a_norm, feats, mask, params["gcn"],
                                  params["att"]["w"])
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("backend,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None)])
def test_should_interpret_only_on_cpu(monkeypatch, backend, interpret):
    """Interpret mode is the CPU's alone: TPU compiles with Mosaic, and any
    other backend raises rather than silently interpreting the kernels."""
    from repro.kernels.common import should_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match=backend):
            should_interpret()
    else:
        assert should_interpret() is interpret


def test_fused_gcn_bf16():
    cfg = SimGNNConfig()
    params = init_simgnn_params(jax.random.PRNGKey(0), cfg)
    batch = next(pair_stream(2, 8))
    to16 = lambda t: jax.tree.map(lambda x: x.astype(jnp.bfloat16), t)
    a_norm = normalized_adjacency(jnp.asarray(batch["adj1"]),
                                  jnp.asarray(batch["mask1"]))
    out_k = fused_gcn_att(a_norm.astype(jnp.bfloat16),
                          jnp.asarray(batch["feats1"], jnp.bfloat16),
                          jnp.asarray(batch["mask1"]),
                          to16(params["gcn"]), to16(params["att"]["w"]),
                          block_graphs=4, interpret=True)
    out_r = ref.fused_gcn_att_ref(a_norm, jnp.asarray(batch["feats1"]),
                                  jnp.asarray(batch["mask1"]),
                                  params["gcn"], params["att"]["w"])
    assert _rel(out_k.astype(jnp.float32), out_r) < 0.05


def test_full_simgnn_kernel_path_matches_core():
    from repro.core.simgnn import pair_score
    cfg = SimGNNConfig()
    params = init_simgnn_params(jax.random.PRNGKey(0), cfg)
    b = next(pair_stream(3, 12))
    args = [jnp.asarray(b[k]) for k in
            ("adj1", "feats1", "mask1", "adj2", "feats2", "mask2")]
    s_kernel = ops.simgnn_pair_score_kernel(params, *args, interpret=True)
    s_core = pair_score(params, *args)
    np.testing.assert_allclose(np.asarray(s_kernel), np.asarray(s_core),
                               rtol=1e-5, atol=1e-6)


# -------------------------------------------------------------- simgnn_head

@pytest.mark.parametrize("b,f,k", [(8, 16, 4), (128, 32, 16), (32, 64, 8)])
def test_simgnn_head_sweep(b, f, k):
    key = jax.random.PRNGKey(b + f)
    ntn = {"w": jax.random.normal(key, (k, f, f)) / f,
           "v": jax.random.normal(key, (k, 2 * f)) / f,
           "b": jnp.zeros((k,))}
    fcn = [{"w": jax.random.normal(key, (k, 4)) * 0.3, "b": jnp.zeros((4,))},
           {"w": jax.random.normal(key, (4, 1)) * 0.3, "b": jnp.zeros((1,))}]
    h1 = jax.random.normal(jax.random.PRNGKey(1), (b, f))
    h2 = jax.random.normal(jax.random.PRNGKey(2), (b, f))
    out_k = ops.pair_scores_fused({"ntn": ntn, "fcn": fcn}, h1, h2,
                                  block_pairs=min(8, b), interpret=True)
    out_r = ref.simgnn_head_ref(h1, h2, ntn, fcn)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-6)


# -------------------------------------------------------------- flash_attn

@pytest.mark.parametrize("causal,window,softcap", [
    (True, None, None), (False, None, None), (True, 64, None),
    (True, None, 30.0), (True, 32, 50.0)])
def test_flash_attention_masks(causal, window, softcap):
    b, t, h, kv, d = 2, 128, 4, 2, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, t, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, t, kv, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, t, kv, d))
    out_k = flash_attention(q, k, v, causal=causal, window=window,
                            softcap=softcap, block_q=32, block_kv=32,
                            interpret=True)
    out_r = ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                    softcap=softcap)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("t,s,h,kv,d", [(64, 64, 8, 8, 64), (128, 128, 8, 1, 16),
                                        (256, 256, 4, 4, 128)])
def test_flash_attention_shapes(t, s, h, kv, d):
    b = 2
    q = jax.random.normal(jax.random.PRNGKey(3), (b, t, h, d))
    k = jax.random.normal(jax.random.PRNGKey(4), (b, s, kv, d))
    v = jax.random.normal(jax.random.PRNGKey(5), (b, s, kv, d))
    out_k = flash_attention(q, k, v, causal=True, block_q=64, block_kv=64,
                            interpret=True)
    out_r = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_bf16():
    b, t, h, kv, d = 2, 128, 4, 2, 64
    mk = lambda i, kvh: jax.random.normal(jax.random.PRNGKey(i),
                                          (b, t, kvh, d)).astype(jnp.bfloat16)
    q, k, v = mk(0, h), mk(1, kv), mk(2, kv)
    out_k = flash_attention(q, k, v, causal=True, block_q=64, block_kv=64,
                            interpret=True)
    out_r = ref.flash_attention_ref(q, k, v, causal=True)
    assert _rel(out_k.astype(jnp.float32), out_r.astype(jnp.float32)) < 0.03


# -------------------------------------------------------------------- wkv6

@pytest.mark.parametrize("t,h,kd,vd,bt", [(64, 2, 16, 16, 32), (128, 4, 64, 64, 64),
                                          (32, 1, 8, 8, 32)])
def test_wkv6_sweep(t, h, kd, vd, bt):
    b = 2
    key = jax.random.PRNGKey(7)
    r = jax.random.normal(key, (b, t, h, kd)) * 0.5
    k = jax.random.normal(jax.random.PRNGKey(8), (b, t, h, kd)) * 0.5
    v = jax.random.normal(jax.random.PRNGKey(9), (b, t, h, vd)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(10), (b, t, h, kd)))
    u = jax.random.normal(jax.random.PRNGKey(11), (h, kd)) * 0.1
    out_k = wkv6(r, k, v, w, u, block_t=bt, interpret=True)
    out_r = ref.wkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-4, atol=1e-5)


def test_wkv6_matches_model_scan():
    """Kernel recurrence == the model's XLA-path scan (rwkv6.wkv_scan)."""
    from repro.models.rwkv6 import wkv_scan
    b, t, h, kd = 2, 64, 2, 16
    r = jax.random.normal(jax.random.PRNGKey(0), (b, t, h, kd)) * 0.5
    k = jax.random.normal(jax.random.PRNGKey(1), (b, t, h, kd)) * 0.5
    v = jax.random.normal(jax.random.PRNGKey(2), (b, t, h, kd)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(3), (b, t, h, kd)))
    u = jax.random.normal(jax.random.PRNGKey(4), (h, kd)) * 0.1
    out_scan, _ = wkv_scan(r, k, v, w, u)
    out_kernel = wkv6(r, k, v, w, u, block_t=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(out_scan),
                               rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------- moe_experts

@pytest.mark.parametrize("e,c,d,f,bc", [(4, 128, 64, 32, 64),
                                        (8, 256, 128, 64, 128),
                                        (2, 128, 256, 512, 128)])
def test_moe_expert_kernel_sweep(e, c, d, f, bc):
    from repro.kernels.moe_experts import moe_expert_ffn, moe_expert_ffn_ref
    x = jax.random.normal(jax.random.PRNGKey(0), (e, c, d))
    wi = jax.random.normal(jax.random.PRNGKey(1), (e, d, 2 * f)) * 0.05
    wo = jax.random.normal(jax.random.PRNGKey(2), (e, f, d)) * 0.05
    yk = moe_expert_ffn(x, wi, wo, block_c=bc, interpret=True)
    yr = moe_expert_ffn_ref(x, wi, wo)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), rtol=1e-5,
                               atol=1e-6)


def test_moe_kernel_path_equals_xla_path():
    from repro.configs import reduced_config
    from repro.models.moe import moe_ffn
    cfg = reduced_config("granite-moe-3b-a800m")
    key = jax.random.PRNGKey(0)
    d, e, f2 = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    p = {"router": jax.random.normal(key, (d, e)) * 0.1,
         "w_in": jax.random.normal(jax.random.PRNGKey(1), (e, d, 2 * f2)) * 0.05,
         "w_out": jax.random.normal(jax.random.PRNGKey(2), (e, f2, d)) * 0.05}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 33, d))
    y_xla, _ = moe_ffn(p, x, cfg)
    y_k, _ = moe_ffn(p, x, cfg.with_(moe_use_kernel=True))
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_xla), atol=1e-6)


# -------------------------------------------------------------- mamba_scan

@pytest.mark.parametrize("bsz,t,din,n,bt,bd", [(2, 64, 32, 4, 32, 16),
                                               (1, 128, 64, 16, 64, 64),
                                               (2, 32, 16, 8, 32, 16)])
def test_mamba_scan_kernel_sweep(bsz, t, din, n, bt, bd):
    from repro.kernels.mamba_scan import (mamba_selective_scan,
                                          mamba_selective_scan_ref)
    k = jax.random.PRNGKey(0)
    dt = jax.nn.softplus(jax.random.normal(k, (bsz, t, din))) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(1), (bsz, t, din))
    b = jax.random.normal(jax.random.PRNGKey(2), (bsz, t, n)) * 0.5
    c = jax.random.normal(jax.random.PRNGKey(3), (bsz, t, n)) * 0.5
    a = -jnp.exp(jax.random.normal(jax.random.PRNGKey(4), (din, n)) * 0.3)
    d = jnp.ones((din,))
    yk = mamba_selective_scan(dt, x, b, c, a, d, block_t=bt, block_d=bd,
                              interpret=True)
    yr = mamba_selective_scan_ref(dt, x, b, c, a, d)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), rtol=1e-4,
                               atol=1e-5)


def test_mamba_scan_kernel_matches_model_block():
    """Kernel == the exact recurrence inside models/mamba.py (no conv/gate)."""
    from repro.kernels.mamba_scan import (mamba_selective_scan,
                                          mamba_selective_scan_ref)
    bsz, t, din, n = 2, 48, 24, 4
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(9),
                                           (bsz, t, din))) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(10), (bsz, t, din))
    b = jax.random.normal(jax.random.PRNGKey(11), (bsz, t, n)) * 0.5
    c = jax.random.normal(jax.random.PRNGKey(12), (bsz, t, n)) * 0.5
    a = -jnp.exp(jax.random.normal(jax.random.PRNGKey(13), (din, n)) * 0.3)
    d = jnp.zeros((din,))
    # sequential reference computed step by step in numpy
    h = np.zeros((bsz, din, n), np.float64)
    ys = np.zeros((bsz, t, din), np.float64)
    dtn, xn, bn, cn = map(np.asarray, (dt, x, b, c))
    an = np.asarray(a)
    for tt in range(t):
        a_bar = np.exp(dtn[:, tt][..., None] * an)
        h = a_bar * h + (dtn[:, tt] * xn[:, tt])[..., None] * bn[:, tt][:, None, :]
        ys[:, tt] = (h * cn[:, tt][:, None, :]).sum(-1)
    yk = mamba_selective_scan(dt, x, b, c, a, d, block_t=16, block_d=24,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(yk), ys, rtol=1e-4, atol=1e-5)
