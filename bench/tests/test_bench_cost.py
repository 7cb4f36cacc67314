"""The useful-work model, counted by hand for one pair of each
configuration."""

import json
import os

import _paths  # noqa: F401
import numpy as np
import pytest

from harness import cost

CONFIGS = os.path.join(_paths.BENCH, "configs")


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def _graph(n, edges, label=0):
    adj = np.zeros((n, n), np.float32)
    for a, b in edges:
        adj[a, b] = adj[b, a] = 1.0
    return {"adj": adj, "labels": np.full(n, label, np.int32)}


PATH3 = _graph(3, [(0, 1), (1, 2)])
TRIANGLE = _graph(3, [(0, 1), (1, 2), (0, 2)])
EDGE2 = _graph(2, [(0, 1)])

# Per graph, layer by layer (dims L-128-64-32, nnz = 2E + n self loops):
#   2 n Fi Fo transform + 2 nnz Fo aggregation, then attention
#   n F (mean) + 2 F^2 (context) + 2 n F (scores) + 2 n F (pooling);
# per pair, the NTN 2 K F^2 + 2 K F + 2 K 2F = 35,840 and the FCN
# 2 (16*8 + 8*4 + 4*1) = 328.
HEAD = 35_840 + 328


@pytest.mark.parametrize("config,g1,g2,flops,nbytes", [
    # AIDS, 29 labels. path3: 24,064 + 50,048 + 12,736 + 2,528 = 89,376;
    # edge2: 15,872 + 33,280 + 8,448 + 2,368 = 59,968.
    ("simgnn_aids", PATH3, EDGE2, 89_376 + 59_968 + HEAD,
     (4 * 3 + 16 * 2) + (4 * 2 + 16 * 1) + 4),
    # IMDB, 1 label. triangle: 3,072 + 50,304 + 12,864 + 2,528 = 68,768;
    # edge2: 1,536 + 33,280 + 8,448 + 2,368 = 45,632.
    ("simgnn_imdb", TRIANGLE, EDGE2, 68_768 + 45_632 + HEAD,
     (4 * 3 + 16 * 3) + (4 * 2 + 16 * 1) + 4),
])
def test_pair_cost_by_hand(config, g1, g2, flops, nbytes):
    got_flops, got_bytes = cost.pair_cost(_config(config), g1, g2)
    assert got_flops == flops
    assert got_bytes == nbytes


def test_head_and_weights():
    cfg = _config("simgnn_aids")
    assert cost.head_flops(cfg) == HEAD
    # GCN 29*128+128 + 128*64+64 + 64*32+32, att 32*32, NTN 16*32*32 +
    # 16*64 + 16, FCN 16*8+8 + 8*4+4 + 4*1+1: 32,801 float32 weights.
    assert cost.param_bytes(cfg) == 4 * 32_801
