"""Padded batching, size-bucketing and node-packing for many small graphs.

This is the TPU-native replacement for SPA-GCN's dynamic zero-skipping
(DESIGN.md §2): instead of skipping zero MACs at runtime, we remove the
dominant *structural* zero populations up front:

  * pad zeros  — graphs are padded to the smallest bucket (8/16/32/64 nodes)
                 that fits them instead of a global max, so a 10-node AIDS
                 graph costs 16^2 adjacency work, not 64^2;
  * packing    — `pack_pairs` goes further (DESIGN.md §8): multiple
                 variable-size graphs share one fixed `[node_budget]` tile
                 (first-fit-decreasing), with per-node segment IDs marking
                 graph membership, so a 17-node graph costs ~17 rows instead
                 of a 32-row bucket;
  * adjacency zeros — aggregation can run from the edge list
                 (`edge_aggregate`) touching only real edges, the analogue of
                 the paper streaming only non-zero A' entries to the FPGA;
                 `pack_pairs(with_edges=True)` emits the packed-CSR tile
                 form of the same non-zeros (`PackedEdges`, DESIGN.md §9)
                 that the packed-sparse megakernel aggregates from.

Buckets/tiles also give XLA a small, fixed set of shapes to compile (one
executable per bucket), mirroring the paper's per-layer customization.

Every host<->device copy here goes through `to_device`/`to_host`: one
`engine.to_device`/`engine.to_host` profiler span per call site (carrying
its byte count), and the bytes added to the `stats` dict the caller passes
(`h2d_bytes`, `d2h_bytes`), which is how `ScoringEngine` counts them. The
one exception is the degree table's fill (`_inv_sqrt_degree`), counted by
`degree_rsqrt_misses` instead.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.profile import span

Array = jax.Array

DEFAULT_BUCKETS = (8, 16, 32, 64)


class GraphBatch(NamedTuple):
    """A batch of padded graphs. All arrays are device-ready."""
    feats: Array          # [B, N, F]  one-hot node labels (or embeddings)
    adj: Array            # [B, N, N]  raw 0/1 adjacency (no self loops)
    mask: Array           # [B, N]     1.0 for real nodes
    n_nodes: Array        # [B]        int32
    labels: Array | None = None   # [B, N] int32 node labels (pad slots 0) —
                                  # the compact form of one-hot `feats`; lets
                                  # kernels gather W1 rows instead of
                                  # multiplying [N, n_labels] one-hots.

    @property
    def max_nodes(self) -> int:
        return self.adj.shape[-1]


class EdgeBatch(NamedTuple):
    """Edge-list view of the same batch (for edge-level aggregation)."""
    senders: Array        # [B, E] int32, padded with 0
    receivers: Array      # [B, E] int32
    weights: Array        # [B, E] normalized A' entries (0 for pad edges)
    edge_mask: Array      # [B, E]

    @property
    def edge_budget(self) -> int:
        """The *realized* per-graph edge budget E — after any auto-grow in
        `to_edge_batch` — so callers can carry it into the next batch of a
        stream instead of re-deriving (and re-warning) every call."""
        return self.senders.shape[-1]


def _add_stat(stats: dict | None, key: str, n: int) -> None:
    if stats is not None:
        stats[key] = stats.get(key, 0) + n


def to_device(arrays: Sequence[np.ndarray],
              stats: dict | None = None) -> list:
    """Copy host arrays to the device inside one `engine.to_device` span;
    their bytes add to `stats["h2d_bytes"]`."""
    nbytes = sum(int(a.nbytes) for a in arrays)
    with span("to_device", bytes=nbytes):
        out = [jnp.asarray(a) for a in arrays]
    _add_stat(stats, "h2d_bytes", nbytes)
    return out


def to_host(arrays: Sequence, stats: dict | None = None) -> list:
    """Copy arrays to host numpy inside one `engine.to_host` span; the
    device arrays' bytes add to `stats["d2h_bytes"]`. Waits for the device
    values first, in an `engine.wait` span, so that the copy span times the
    transfer and not the device work still producing them. Host arrays pass
    through uncounted."""
    on_device = [a for a in arrays if isinstance(a, jax.Array)]
    with span("wait"):
        jax.block_until_ready(on_device)
    nbytes = sum(int(a.nbytes) for a in on_device)
    with span("to_host", bytes=nbytes):
        out = [np.asarray(a) for a in arrays]
    _add_stat(stats, "d2h_bytes", nbytes)
    return out


def pad_graphs(graphs: Sequence[dict], n_labels: int, max_nodes: int, *,
               stats: dict | None = None) -> GraphBatch:
    """graphs: list of {"adj": np [n,n], "labels": np [n] int}. Pads to
    max_nodes. `stats` receives the copy's `h2d_bytes`."""
    b = len(graphs)
    feats = np.zeros((b, max_nodes, n_labels), np.float32)
    adj = np.zeros((b, max_nodes, max_nodes), np.float32)
    mask = np.zeros((b, max_nodes), np.float32)
    n_nodes = np.zeros((b,), np.int32)
    labels = np.zeros((b, max_nodes), np.int32)
    for i, g in enumerate(graphs):
        n = g["adj"].shape[0]
        if n > max_nodes:
            raise ValueError(f"graph with {n} nodes exceeds bucket {max_nodes}")
        adj[i, :n, :n] = g["adj"]
        feats[i, np.arange(n), g["labels"]] = 1.0
        mask[i, :n] = 1.0
        n_nodes[i] = n
        labels[i, :n] = g["labels"]
    return GraphBatch(*to_device((feats, adj, mask, n_nodes, labels), stats))


def bucket_for(n: int, buckets: Sequence[int] = DEFAULT_BUCKETS, *,
               allow_oversize: bool = False) -> int:
    for b in buckets:
        if n <= b:
            return b
    if allow_oversize:
        # Oversized queries get a power-of-two bucket of their own instead of
        # killing the call; doubling bounds the executable count at
        # O(log max_n) while capping pad waste at 2x.
        b = buckets[-1]
        while b < n:
            b *= 2
        return b
    raise ValueError(f"graph with {n} nodes exceeds largest bucket {buckets[-1]}")


def bucket_pairs(pairs: Sequence[tuple], n_labels: int,
                 buckets: Sequence[int] = DEFAULT_BUCKETS, *,
                 allow_oversize: bool = False, stats: dict | None = None):
    """Group graph *pairs* by the bucket of the larger graph.

    Returns {bucket_size: (GraphBatch_lhs, GraphBatch_rhs, indices)} where
    `indices` restores the original pair order. One compiled executable per
    bucket (the 'customize per workload' principle, paper Table 2). With
    `allow_oversize`, graphs beyond the largest bucket fall into power-of-two
    overflow buckets instead of raising. `stats` receives the copies'
    `h2d_bytes`.
    """
    groups: dict[int, list] = {}
    for idx, (g1, g2) in enumerate(pairs):
        b = bucket_for(max(g1["adj"].shape[0], g2["adj"].shape[0]), buckets,
                       allow_oversize=allow_oversize)
        groups.setdefault(b, []).append((idx, g1, g2))
    out = {}
    for b, items in sorted(groups.items()):
        idxs = np.asarray([i for i, _, _ in items], np.int32)
        lhs = pad_graphs([g for _, g, _ in items], n_labels, b, stats=stats)
        rhs = pad_graphs([g for _, _, g in items], n_labels, b, stats=stats)
        out[b] = (lhs, rhs, idxs)
    return out


# --------------------------------------------------------- pair packing (§8)

class PackedPairBatch(NamedTuple):
    """Graph *pairs* packed into fixed node-budget tiles (DESIGN.md §8).

    Tile t holds up to P pairs; pair slot p of tile t owns one contiguous
    node range in the lhs tile (its G1) and one in the rhs tile (its G2).
    Adjacency is block-diagonal by construction — no pair's edges cross
    another's range — so in-kernel masked normalization factors per graph.
    `seg*` maps every node slot to its pair slot (pad slots: segment 0 with
    mask 0, contributing exact zeros to every segment reduction).
    """
    adj1: Array           # [T, NB, NB] block-diagonal raw adjacency (lhs);
                          # host numpy when packed `with_edges=True`
                          # (the sparse kernels never read it)
    labels1: Array        # [T, NB] int32 node labels (pad 0)
    mask1: Array          # [T, NB] 1.0 for real nodes
    seg1: Array           # [T, NB] int32 pair-slot id in [0, P)
    adj2: Array           # [T, NB, NB] (rhs)
    labels2: Array        # [T, NB]
    mask2: Array          # [T, NB]
    seg2: Array           # [T, NB]
    pair_mask: Array      # [T, P] 1.0 for real pair slots
    pair_index: Array     # [T, P] int32 original pair position (0 where pad)
    edges: "PackedEdges | None" = None   # tile-local A' edge lists (§9) —
                                         # present when packed with
                                         # `with_edges=True`; the packed-sparse
                                         # megakernel's input view.

    @property
    def node_budget(self) -> int:
        return self.adj1.shape[-1]

    @property
    def slots_per_tile(self) -> int:
        return self.pair_mask.shape[-1]

    def kernel_operands(self, sparse: bool) -> tuple:
        """The tile-leading operands of the packed megakernels, in the
        order `sparse_pair_score`/`packed_pair_score` (and their grad twins
        in `kernels/grad.py`) take them: per side the packed-CSR edge planes
        (sparse, from `edges`) or the dense adjacency, then labels, mask and
        segment ids; `pair_mask` last. The one place this order is written."""
        if sparse:
            e1, e2 = self.edges.edges1, self.edges.edges2
            o1, o2 = self.edges.overflow1, self.edges.overflow2
            return (e1.senders, e1.weights,
                    o1.senders, o1.receivers, o1.weights,
                    self.labels1, self.mask1, self.seg1,
                    e2.senders, e2.weights,
                    o2.senders, o2.receivers, o2.weights,
                    self.labels2, self.mask2, self.seg2,
                    self.pair_mask)
        return (self.adj1, self.labels1, self.mask1, self.seg1,
                self.adj2, self.labels2, self.mask2, self.seg2,
                self.pair_mask)


class PackedEdges(NamedTuple):
    """Packed-CSR view of a packed tile batch's *normalized* adjacency
    (DESIGN.md §9): per tile and side, the non-zeros of A' as
    (sender, receiver, weight) triples padded to a shared power-of-two
    `edge_budget`, laid out in D = edge_budget / node_budget neighbor
    *planes* (ELLPACK column-major): slot `s` of a tile holds the
    (s // NB)-th in-edge of node `s % NB`, so summing a node's neighbors
    is the sum of D contiguous [NB, F] planes — fully vectorizable, no
    scatter (receivers are stored explicitly too, so the arrays are also a
    valid plain edge list for `edge_aggregate`). Nodes with in-degree > D
    spill their excess edges to a small COO `overflow` list — LW-GCN's
    compressed-row format plus Accel-GCN's degree-aware split: the regular
    rows ride the vector path, the heavy tail a tiny one-hot contraction.
    Block-diagonality is inherited from the node packing — no edge crosses
    graphs, so segment reductions stay exact. Pad slots carry exact-zero
    weight/mask (neutral in aggregation). The lists are built on the host
    (`packed_pair_edges`) and are bitwise the non-zeros of
    `gcn.normalized_adjacency` on the default backend; they are the only
    adjacency the packed-sparse path copies to the device.
    """
    edges1: EdgeBatch     # lhs CSR rows, arrays [T, NB*D]
    edges2: EdgeBatch     # rhs CSR rows
    overflow1: EdgeBatch  # lhs COO spill (in-degree > D), arrays [T, E_ov]
    overflow2: EdgeBatch  # rhs COO spill

    @property
    def edge_budget(self) -> int:
        return self.edges1.senders.shape[-1]

    @property
    def overflow_budget(self) -> int:
        return self.overflow1.senders.shape[-1]


def _first_fit(sizes: Sequence[tuple[int, int]], node_budget: int, cap: int):
    """First-fit-decreasing placement of pairs of node counts `sizes` into
    tiles of `node_budget` nodes per side and at most `cap` pairs.

    Pairs go in stable order of decreasing n1 + n2, each into the first
    tile (in creation order) where both sides and a slot still fit. Only
    *open* tiles are scanned: a tile is closed, for good, once it is full
    or its free lhs (rhs) budget is below the smallest lhs (rhs) size still
    to place (suffix minima over the order), since no later pair can enter
    it. Closing skips only tiles that would fail the check, so the result
    is the plain FFD scan's.

    Returns per pair its tile and slot, per side the pairs' node offsets in
    their tile, the tiles' pair counts, and the number of tile checks made.
    """
    n = len(sizes)
    order = sorted(range(n), key=lambda i: -(sizes[i][0] + sizes[i][1]))
    # low1[k] / low2[k]: smallest lhs / rhs size among order[k:].
    low1 = [node_budget + 1] * (n + 1)
    low2 = [node_budget + 1] * (n + 1)
    for k in range(n - 1, -1, -1):
        n1, n2 = sizes[order[k]]
        low1[k] = n1 if n1 < low1[k + 1] else low1[k + 1]
        low2[k] = n2 if n2 < low2[k + 1] else low2[k + 1]
    free1: list[int] = []
    free2: list[int] = []
    counts: list[int] = []
    tile_of, slot = [0] * n, [0] * n
    off1, off2 = [0] * n, [0] * n
    open_tiles: list[int] = []          # in creation order
    probes = 0
    m1 = m2 = 0
    for k, i in enumerate(order):
        n1, n2 = sizes[i]
        if low1[k] != m1 or low2[k] != m2:   # smallest sizes left grew
            m1, m2 = low1[k], low2[k]
            open_tiles = [t for t in open_tiles
                          if free1[t] >= m1 and free2[t] >= m2]
        for j, t in enumerate(open_tiles):
            if free1[t] >= n1 and free2[t] >= n2:
                probes += j + 1
                break
        else:
            probes += len(open_tiles)
            j, t = len(open_tiles), len(counts)
            free1.append(node_budget)
            free2.append(node_budget)
            counts.append(0)
            open_tiles.append(t)
        f1, f2, c = free1[t], free2[t], counts[t]
        tile_of[i], slot[i] = t, c
        off1[i], off2[i] = node_budget - f1, node_budget - f2
        free1[t], free2[t], counts[t] = f1 - n1, f2 - n2, c + 1
        if c + 1 == cap or f1 - n1 < low1[k + 1] or f2 - n2 < low2[k + 1]:
            del open_tiles[j]
    return tile_of, slot, (off1, off2), counts, probes


def pack_pairs(pairs: Sequence[tuple], node_budget: int = 64, *,
               slots_per_tile: int | None = None,
               with_edges: bool = False, edge_budget: int | None = None,
               overflow_budget: int = 8):
    """First-fit-decreasing packing of graph pairs into `[T, node_budget]`
    tiles. Returns (PackedPairBatch, stats).

    Both sides of a pair land in the *same* tile at the same pair slot (the
    packed NTN stage scores tile-aligned slot pairs), so a pair is placed in
    the first tile where its G1 fits the remaining lhs budget AND its G2 the
    rhs budget. Decreasing order by total pair size keeps FFD occupancy high
    (~0.9 on AIDS-like streams vs ~0.55 for max-side bucketing).

    With `with_edges=True` the result additionally carries `edges`: the
    tile-local padded edge list of the normalized adjacency (`PackedEdges`,
    DESIGN.md §9) that the packed-sparse megakernel aggregates from, built
    on the host from the tile arrays just filled, as `packed_pair_edges`
    builds it, at a quantized `edge_budget`
    (node_budget rows x a small neighbor-budget ladder, auto-grown to fit;
    `kernels.ops.packed_edge_budget` is the sizing policy) and an
    `overflow_budget` floor for the COO spill — callers that stream many
    batches pass the previous batch's realized `stats["overflow_budget"]`
    back in so the compiled [T, E_ov] shape stays put. Only the edge
    lists go to the device: `adj1`/`adj2` stay host numpy arrays, and
    nothing is read back. stats then gains the nnz / adjacency density per
    side, counted on the host, and `degree_rsqrt_misses`.

    stats: occupancy / pad-fraction per side plus tile shape, the tile
    checks the first-fit made (`ffd_probes`, see `_first_fit`), and the
    bytes the packing copied to the device (`h2d_bytes`) and back
    (`d2h_bytes`).
    """
    sizes = [(g1["adj"].shape[0], g2["adj"].shape[0]) for g1, g2 in pairs]
    for n1, n2 in sizes:
        if max(n1, n2) > node_budget:
            raise ValueError(
                f"graph with {max(n1, n2)} nodes exceeds node_budget "
                f"{node_budget}; route oversized pairs to the padded fallback")
    cap = slots_per_tile if slots_per_tile else len(pairs) or 1
    tile_of, slot, offsets, counts, probes = _first_fit(sizes, node_budget,
                                                        cap)

    n_tiles = len(counts) or 1
    if slots_per_tile is None:
        most = max(counts, default=1)
        slots_per_tile = max(8, -(-most // 8) * 8)    # sublane-aligned P
    adj = [np.zeros((n_tiles, node_budget, node_budget), np.float32)
           for _ in range(2)]
    labels = [np.zeros((n_tiles, node_budget), np.int32) for _ in range(2)]
    mask = [np.zeros((n_tiles, node_budget), np.float32) for _ in range(2)]
    seg = [np.zeros((n_tiles, node_budget), np.int32) for _ in range(2)]
    pair_mask = np.zeros((n_tiles, slots_per_tile), np.float32)
    pair_index = np.zeros((n_tiles, slots_per_tile), np.int32)
    tile_np = np.asarray(tile_of, np.int64)
    slot_np = np.asarray(slot, np.int64)
    pair_mask[tile_np, slot_np] = 1.0
    pair_index[tile_np, slot_np] = np.arange(len(pairs))
    for side in range(2):
        graphs = [pair[side] for pair in pairs]
        n = np.asarray([s[side] for s in sizes], np.int64)
        # Flat node targets, graph by graph in input order: graph i's nodes
        # go to tile_of[i] * NB + its offset in the tile, onwards.
        start = tile_np * node_budget + np.asarray(offsets[side], np.int64)
        flat = (np.repeat(start - (np.cumsum(n) - n), n)
                + np.arange(int(n.sum())))
        if graphs:
            labels[side].reshape(-1)[flat] = np.concatenate(
                [g["labels"] for g in graphs])
        mask[side].reshape(-1)[flat] = 1.0
        seg[side].reshape(-1)[flat] = np.repeat(slot_np, n)
        a = adj[side]
        for g, t, o, k in zip(graphs, tile_of, offsets[side], n.tolist()):
            a[t, o:o + k, o:o + k] = g["adj"]

    real = [sum(s[0] for s in sizes), sum(s[1] for s in sizes)]
    cells = max(n_tiles * node_budget, 1)
    stats = {
        "n_pairs": len(pairs), "n_tiles": n_tiles,
        "node_budget": node_budget, "slots_per_tile": slots_per_tile,
        "occupancy_lhs": real[0] / cells, "occupancy_rhs": real[1] / cells,
        "pad_fraction_lhs": 1.0 - real[0] / cells,
        "pad_fraction_rhs": 1.0 - real[1] / cells,
        "mean_pairs_per_tile": len(pairs) / n_tiles,
        "ffd_probes": probes,
        "h2d_bytes": 0, "d2h_bytes": 0,
    }
    if not with_edges:
        packed = PackedPairBatch(*to_device(
            (adj[0], labels[0], mask[0], seg[0],
             adj[1], labels[1], mask[1], seg[1], pair_mask, pair_index),
            stats))
        return packed, stats
    # The packed-sparse kernels read the edge lists, never the dense
    # adjacency: it stays on the host, where the edge lists are built.
    l1, m1, s1, l2, m2, s2, pm, pi = to_device(
        (labels[0], mask[0], seg[0], labels[1], mask[1], seg[1],
         pair_mask, pair_index), stats)
    with span("edges"):
        edges, nnz = _packed_pair_edges(((adj[0], mask[0]), (adj[1], mask[1])),
                                        edge_budget, overflow_budget, stats)
    packed = PackedPairBatch(adj[0], l1, m1, s1, adj[1], l2, m2, s2, pm, pi,
                             edges)
    adj_cells = n_tiles * node_budget * node_budget
    stats.update(
        edge_budget=edges.edge_budget,
        overflow_budget=edges.overflow_budget,
        nnz_lhs=nnz[0], nnz_rhs=nnz[1],
        density_lhs=nnz[0] / adj_cells, density_rhs=nnz[1] / adj_cells,
        edge_occupancy=(nnz[0] + nnz[1])
        / max(2 * n_tiles * edges.edge_budget, 1))
    return packed, stats


def packed_pair_edges(packed: PackedPairBatch,
                      edge_budget: int | None = None,
                      overflow_budget: int = 8, *,
                      stats: dict | None = None) -> PackedEdges:
    """Extract per-tile packed-CSR A' edge lists from a packed tile batch
    (DESIGN.md §9).

    The packed adjacency is block-diagonal and the masked normalization
    factors per graph, so each tile's A' non-zeros ARE the union of its
    graphs' A' non-zeros. They are found on the host, with no device round
    trip: one flat scan per side of the (A + I) structure on real nodes,
    the weights as `gcn.normalized_adjacency` forms them (`_a_prime`).
    The (receiver-sorted) list is then laid out in
    D = edge_budget/node_budget ELLPACK neighbor planes (plane d, slot n =
    node n's d-th in-edge); edges beyond a node's D slots spill to the COO
    overflow list. Budgets are powers of two and auto-grow to fit
    (`edge_budget=None` sizes D to the realized max in-degree, leaving the
    overflow empty). Both sides share one budget.

    `pack_pairs(with_edges=True)` builds the same lists from its host tile
    arrays; this entry point serves batches packed without edges, whose
    adjacency and masks are read back once if they are on the device.
    Runs in one `engine.edges` span; `stats` receives the bytes of any such
    read-back (`d2h_bytes`), of the edge lists copied out (`h2d_bytes`) and
    the `degree_rsqrt_misses` of `_inv_sqrt_degree`.
    """
    with span("edges"):
        sides = [packed.adj1, packed.mask1, packed.adj2, packed.mask2]
        if any(isinstance(a, jax.Array) for a in sides):
            sides = to_host(sides, stats)
        return _packed_pair_edges((sides[:2], sides[2:]), edge_budget,
                                  overflow_budget, stats)[0]


#: float32 degree (as its uint32 bits) -> its `gcn.inv_sqrt_degree` entry,
#: as the default backend computes it. 0/1 graphs of a node budget NB have
#: at most NB + 1 distinct degrees, so after warm-up every lookup hits and
#: the host builds A' with no device call at all.
_INV_SQRT_DEGREE: dict[int, np.float32] = {}
#: the fixed length misses are padded to: one executable, warmed once.
_DEGREE_CHUNK = 128
#: entries kept before the table starts over (weighted graphs).
_DEGREE_TABLE_CAP = 1 << 16


def _inv_sqrt_degree(deg: np.ndarray, stats: dict | None) -> np.ndarray:
    """`gcn.inv_sqrt_degree(deg)` for a 1-D host float32 `deg`, bitwise as the
    default backend computes it (its rsqrt need not be correctly rounded,
    so numpy's would not do): each distinct degree is looked up in
    `_INV_SQRT_DEGREE`, and the misses are evaluated on the device in
    `_DEGREE_CHUNK`-long padded batches, their number added to
    `stats["degree_rsqrt_misses"]`. These table fills are not counted as
    transfer bytes: 512 bytes each way per 128 new degrees, once."""
    from repro.core.gcn import inv_sqrt_degree  # late import, no cycle

    keys, inverse = np.unique(deg.view(np.uint32), return_inverse=True)
    keys = keys.tolist()
    known = [_INV_SQRT_DEGREE.get(k) for k in keys]
    missing = [k for k, v in zip(keys, known) if v is None]
    new: dict[int, np.float32] = {}
    for i in range(0, len(missing), _DEGREE_CHUNK):
        part = np.asarray(missing[i:i + _DEGREE_CHUNK], np.uint32)
        padded = np.ones(_DEGREE_CHUNK, np.float32)
        padded[:part.size] = part.view(np.float32)
        inv = np.asarray(inv_sqrt_degree(jnp.asarray(padded)))[:part.size]
        new.update(zip(part.tolist(), inv))
    if new:
        if len(_INV_SQRT_DEGREE) + len(new) > _DEGREE_TABLE_CAP:
            _INV_SQRT_DEGREE.clear()
        _INV_SQRT_DEGREE.update(new)
    _add_stat(stats, "degree_rsqrt_misses", len(missing))
    table = np.asarray([new[k] if v is None else v
                        for k, v in zip(keys, known)], np.float32)
    return table[inverse.reshape(-1)]


def _a_prime(adj: np.ndarray, mask: np.ndarray, stats: dict | None):
    """The non-zeros of A' = `gcn.normalized_adjacency(adj, mask)` for host
    tile arrays `adj` [T, NB, NB] and `mask` [T, NB], as (tiles, rows, cols,
    weights) in row-major order, so sorted by (tile, receiver). Each step is
    the device expression's, in its order and precision: a_tilde =
    (adj + I) * (mask_r * mask_c), degree = its row sum, weight =
    a_tilde * inv_r * inv_c in float32. The degree is summed in float64 and
    rounded once; that is every float32 order's sum whenever the row sums
    exactly, as 0/1 and small dyadic weights do."""
    t, nb, _ = adj.shape
    diag = np.arange(nb)
    nz = adj != 0
    nz[:, diag, diag] = True            # A + I: every diagonal is a candidate
    flat = np.flatnonzero(nz)
    tiles, cell = np.divmod(flat, nb * nb)
    rows, cols = np.divmod(cell, nb)
    a_tilde = ((adj.reshape(-1)[flat] + (rows == cols))
               * (mask[tiles, rows] * mask[tiles, cols]))
    recv = tiles * nb + rows
    deg = np.bincount(recv, weights=a_tilde, minlength=t * nb)
    inv = _inv_sqrt_degree(deg.astype(np.float32), stats)
    w = a_tilde * inv[recv] * inv[tiles * nb + cols]
    keep = w != 0                       # exact zeros of A' are not edges
    return tiles[keep], rows[keep], cols[keep], w[keep]


def _packed_pair_edges(sides, edge_budget, overflow_budget, stats):
    """`packed_pair_edges` on host `((adj1, mask1), (adj2, mask2))`; returns
    the device `PackedEdges` and each side's non-zero count."""
    nb = sides[0][0].shape[-1]
    if edge_budget is not None and edge_budget % nb:
        raise ValueError(f"edge_budget {edge_budget} must be a multiple of "
                         f"node_budget {nb} (CSR rows)")
    d_budget = (edge_budget // nb) if edge_budget else 1
    # Fully vectorized extraction (no per-tile Python loop — the host pack
    # sits on the training hot path since DESIGN.md §11): edges arrive
    # sorted by (tile, receiver), so the in-row rank is a searchsorted
    # subtraction.
    found = []
    for adj, mask in sides:
        t = adj.shape[0]
        tiles, rows, cols, w = _a_prime(adj, mask, stats)
        key = tiles * nb + rows
        rank = np.arange(key.size) - np.searchsorted(key, key, side="left")
        max_rank = int(rank.max()) + 1 if key.size else 0
        found.append((t, tiles, rows, cols, w, rank, max_rank))

    d = max(d_budget, 1)
    if edge_budget is None:
        d = next_pow2(max(s[6] for s in found), floor=2)
    ov_need = 0
    for t, tiles, rows, cols, w, rank, _ in found:
        spill = rank >= d
        if spill.any():
            ov_need = max(ov_need, int(np.bincount(tiles[spill]).max()))
    e_ov = next_pow2(ov_need, floor=max(8, overflow_budget))

    # Narrow index planes (DESIGN.md §16 satellite): within-tile node
    # indices fit int16 whenever the node budget does, halving the four
    # index planes' host->device bytes; the kernels' gathers and compares
    # promote against int32 iotas/offsets, so scores are bit-identical
    # (pinned by the int16 row of the sharded parity matrix).
    idx_dtype = np.int16 if nb < 2 ** 15 else np.int32
    planes = []
    for t, tiles, rows, cols, w, rank, _ in found:
        cs = np.zeros((t, nb * d), idx_dtype)
        cr = np.tile(np.tile(np.arange(nb, dtype=idx_dtype), d), (t, 1))
        cw = np.zeros((t, nb * d), np.float32)
        cm = np.zeros((t, nb * d), np.float32)
        os_ = np.zeros((t, e_ov), idx_dtype)
        or_ = np.zeros((t, e_ov), idx_dtype)
        ow = np.zeros((t, e_ov), np.float32)
        om = np.zeros((t, e_ov), np.float32)
        fit = rank < d
        # Plane-major (ELLPACK) flat slot: tile * NB·D + rank * NB + row.
        slot = tiles[fit] * (nb * d) + rank[fit] * nb + rows[fit]
        cs.reshape(-1)[slot] = cols[fit]
        cw.reshape(-1)[slot] = w[fit]
        cm.reshape(-1)[slot] = 1.0
        if (~fit).any():
            t_ov = tiles[~fit]            # sorted: position within tile is
            pos = (np.arange(t_ov.size)   # offset from the tile's first
                   - np.searchsorted(t_ov, t_ov, side="left"))
            oslot = t_ov * e_ov + pos
            os_.reshape(-1)[oslot] = cols[~fit]
            or_.reshape(-1)[oslot] = rows[~fit]
            ow.reshape(-1)[oslot] = w[~fit]
            om.reshape(-1)[oslot] = 1.0
        planes += [cs, cr, cw, cm, os_, or_, ow, om]
    a = to_device(planes, stats)
    edges = PackedEdges(EdgeBatch(*a[0:4]), EdgeBatch(*a[8:12]),
                        EdgeBatch(*a[4:8]), EdgeBatch(*a[12:16]))
    return edges, (found[0][4].size, found[1][4].size)


def unpack_pair_scores(scores_tp, packed: PackedPairBatch,
                       n_pairs: int, *, stats: dict | None = None
                       ) -> np.ndarray:
    """Scatter kernel output [T, P] back to original pair order (host-side).
    `stats` receives the bytes read back (`d2h_bytes`)."""
    s, pair_mask, pair_index = to_host(
        (scores_tp, packed.pair_mask, packed.pair_index), stats)
    s = s.astype(np.float32, copy=False)
    live = pair_mask > 0
    out = np.zeros(n_pairs, np.float32)
    out[pair_index[live]] = s[live]
    return out


def next_pow2(n: int, floor: int = 8) -> int:
    """Smallest power of two >= max(n, floor) — the shape-quantization helper
    shared by overflow buckets, tile counts and edge budgets (a small, fixed
    executable set under varying workloads). Always a true power of two,
    even when `floor` itself is not."""
    target = max(n, floor)
    p = 1
    while p < target:
        p *= 2
    return p


#: (requested, grown) budget pairs already warned about — a stream that
#: outruns its `max_edges` on every batch re-derives the same grown budget
#: each call; warning once per distinct growth (per process) keeps the log
#: readable while `EdgeBatch.edge_budget` gives callers the realized value
#: to feed back in (at which point growth — and the warning — stop).
_GROW_WARNED: set[tuple[int, int]] = set()


def reset_grow_warnings() -> None:
    """Clear the warn-once registry so the next budget growth warns again.

    The registry is process-global (one warning per distinct growth per
    process), which is right for servers but wrong for test isolation and
    for long-lived processes that deliberately re-tune budgets — both were
    reaching in and mutating `_GROW_WARNED` directly. This is the supported
    hook."""
    _GROW_WARNED.clear()


def to_edge_batch(batch: GraphBatch, max_edges: int) -> EdgeBatch:
    """Extract the normalized-adjacency non-zeros as a padded edge list.

    Includes self loops (A+I) with symmetric normalization weights — i.e. the
    exact non-zero structure of A' that the paper streams to the FPGA.
    Host-side (numpy); small graphs make this negligible (paper §3.2.2).

    If any graph's non-zero count exceeds `max_edges`, the whole batch's edge
    budget auto-grows to the next power of two that fits instead of killing
    the stream — the same degrade-to-padding policy as the power-of-two
    overflow buckets of `bucket_for`. The warning fires ONCE per distinct
    (requested, grown) pair per process, not per batch; the realized budget
    is surfaced as `EdgeBatch.edge_budget` (and in `pack_pairs` stats) so
    stream callers reuse it on the next batch. Pad edge slots carry
    sender/receiver 0 and exact-zero weight/mask, so they are neutral in
    every aggregation.
    """
    from repro.core.gcn import normalized_adjacency  # late import, no cycle

    a_norm = np.asarray(normalized_adjacency(batch.adj, batch.mask))
    bsz, n, _ = a_norm.shape
    nonzeros = [np.nonzero(a_norm[i]) for i in range(bsz)]
    peak = max((len(r) for r, _ in nonzeros), default=0)
    if peak > max_edges:
        grown = next_pow2(peak, floor=max(8, max_edges))
        if (max_edges, grown) not in _GROW_WARNED:
            _GROW_WARNED.add((max_edges, grown))
            import warnings
            warnings.warn(
                f"{peak} non-zeros exceed max_edges={max_edges}; growing the "
                f"edge budget to {grown} (power-of-two) instead of raising "
                "(warned once per stream: reuse EdgeBatch.edge_budget to "
                "stop re-growing)",
                RuntimeWarning, stacklevel=2)
        max_edges = grown
    senders = np.zeros((bsz, max_edges), np.int32)
    receivers = np.zeros((bsz, max_edges), np.int32)
    weights = np.zeros((bsz, max_edges), np.float32)
    emask = np.zeros((bsz, max_edges), np.float32)
    for i, (r, c) in enumerate(nonzeros):
        e = len(r)
        receivers[i, :e], senders[i, :e] = r, c
        weights[i, :e] = a_norm[i, r, c]
        emask[i, :e] = 1.0
    return EdgeBatch(jnp.asarray(senders), jnp.asarray(receivers),
                     jnp.asarray(weights), jnp.asarray(emask))


def edge_aggregate(edges: EdgeBatch, hw: Array) -> Array:
    """Aggregation step from the edge list: out[b, r] += w * hw[b, s].

    Touches only real edges (plus pad slots that contribute exact zeros) —
    the paper's 'read only the non-zero A' elements' (§3.2.2), expressed as a
    batched gather + segment-sum so XLA lowers it to vectorized dynamic ops.
    hw: [B, N, F] (the H·W product) -> [B, N, F].
    """
    gathered = jnp.take_along_axis(hw, edges.senders[..., None], axis=1)   # [B, E, F]
    msgs = gathered * (edges.weights * edges.edge_mask)[..., None]
    n = hw.shape[1]
    seg = jax.vmap(lambda m, r: jax.ops.segment_sum(m, r, num_segments=n))
    return seg(msgs, edges.receivers)
