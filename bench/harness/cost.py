"""Useful work of SimGNN, from each graph's real node and edge counts.

"Useful" is the published model's layers on unpadded graphs, whatever
kernel computes them: a GCN layer is a dense F_in x F_out transform of the
real nodes plus an aggregation over the real edges (both directions) and
one self loop per node; the first layer counts as a dense
n_labels -> gcn_dims[0] transform; then attention pooling, the NTN's K
bilinear slices and linear term, and the FCN. Padding, one-hot gathers,
normalization and extra MXU passes for float32 precision do not count.
A multiply-add is two operations.

Bytes are the least a device must move for the work: each graph's labels
(int32 per node) and edge list (two int32 per directed edge), the weights
once per call, and one float32 score per pair.
"""

from __future__ import annotations

import numpy as np


def _dims(cfg: dict) -> tuple:
    return (cfg["n_node_labels"],) + tuple(cfg["gcn_dims"])


def embed_flops(cfg: dict, n: int, e: int) -> float:
    """GCN stack plus attention pooling of one graph of `n` nodes and `e`
    undirected edges."""
    d = _dims(cfg)
    nnz = 2 * e + n
    flops = 0.0
    for fi, fo in zip(d[:-1], d[1:]):
        flops += 2.0 * n * fi * fo + 2.0 * nnz * fo
    f = d[-1]
    flops += n * f + 2.0 * f * f + 2.0 * n * f + 2.0 * n * f
    return flops


def head_flops(cfg: dict) -> float:
    """NTN and FCN of one pair of graph embeddings."""
    f, k = cfg["gcn_dims"][-1], cfg["ntn_k"]
    flops = 2.0 * k * f * f + 2.0 * k * f + 2.0 * k * 2 * f
    fcn = (k,) + tuple(cfg["fcn_dims"]) + (1,)
    flops += sum(2.0 * a * b for a, b in zip(fcn[:-1], fcn[1:]))
    return flops


def param_bytes(cfg: dict) -> float:
    d = _dims(cfg)
    f, k = d[-1], cfg["ntn_k"]
    fcn = (k,) + tuple(cfg["fcn_dims"]) + (1,)
    n = sum(a * b + b for a, b in zip(d[:-1], d[1:]))
    n += f * f + k * f * f + k * 2 * f + k
    n += sum(a * b + b for a, b in zip(fcn[:-1], fcn[1:]))
    return 4.0 * n


def graph_size(g: dict) -> tuple[int, int]:
    adj = g["adj"]
    return int(adj.shape[0]), int(np.count_nonzero(np.triu(adj, 1)))


def graph_bytes(n: int, e: int) -> float:
    return 4.0 * n + 8.0 * 2 * e


def pair_cost(cfg: dict, g1: dict, g2: dict) -> tuple[float, float]:
    """(operations, bytes) of scoring one pair, weights excluded."""
    (n1, e1), (n2, e2) = graph_size(g1), graph_size(g2)
    flops = embed_flops(cfg, n1, e1) + embed_flops(cfg, n2, e2) \
        + head_flops(cfg)
    return flops, graph_bytes(n1, e1) + graph_bytes(n2, e2) + 4.0
