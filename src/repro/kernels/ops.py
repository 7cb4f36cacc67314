"""Jit'd public wrappers around the Pallas kernels.

These are the entry points the rest of the framework uses: they handle
padding to block multiples, parameter plumbing from the core/ model param
trees, and the interpret-mode fallback (DESIGN.md §2 — kernels compile with
Mosaic on TPU, run emulated elsewhere).

For SimGNN pair scoring there are four kernel paths (path selection lives in
`core.engine.ScoringEngine`, DESIGN.md §9):

  * `pair_score_sparse` — the edge-centric packed-sparse megakernel
    (DESIGN.md §9): packed tiles aggregated from the A' non-zero edge list
    (in-kernel segment sum) instead of the dense adjacency matmul; the
    engine's choice for sparse (AIDS-like) streams.
  * `pair_score_packed` — the packed-pair megakernel (DESIGN.md §8): many
    variable-size pairs share fixed node-budget tiles (segment IDs), the
    first layer gathers W1 rows from int32 labels instead of multiplying
    one-hots; the engine's choice for dense-adjacency streams.
  * `pair_score_megakernel` — ONE pallas_call per bucket-padded pair batch
    (DESIGN.md §7); the dense-feats path, kept for non-one-hot inputs and
    as the bucketed fallback for oversized pairs.
  * `simgnn_pair_score_kernel` — the two-kernel composition (fused GCN+Att,
    then fused NTN+FCN head) kept as building blocks for embedding-only /
    head-only callers and as the benchmark comparison point.

Both packed paths share one executor: `_packed_kernel` runs either kernel
on `core.batching.PackedPairBatch.kernel_operands` (which owns the operand
order), on one device under `pair_score_packed`/`pair_score_sparse` and per
shard under `build_pair_score_sharded` (DESIGN.md §16); `pad_tiles` is the
one tile-axis pad.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.flash_attn import flash_attention
from repro.kernels.fused_gcn import fused_gcn_att
from repro.kernels.fused_pair import fused_pair_score
from repro.kernels.packed_pair import packed_pair_score
from repro.kernels.retrieval import (blocked_topm, blocked_topm_ntn,
                                     collapse_query_ntn,
                                     retrieval_block_cols)
from repro.kernels.simgnn_head import simgnn_head
from repro.kernels.sparse_pair import sparse_pair_score
from repro.kernels.wkv6 import wkv6

__all__ = ["flash_attention", "wkv6", "graph_embeddings_fused",
           "pair_scores_fused", "simgnn_pair_score_kernel",
           "pair_score_megakernel", "megakernel_block_pairs",
           "pair_score_packed", "packed_node_budget", "packed_tile_block",
           "pair_score_sparse", "packed_edge_budget", "sparse_tile_block",
           "blocked_topm", "blocked_topm_ntn", "collapse_query_ntn",
           "retrieval_block_cols", "pad_tiles", "sharded_tile_block",
           "sharded_tile_plan", "build_pair_score_sharded"]


def _pad_batch(x: jax.Array, multiple: int) -> tuple[jax.Array, int]:
    b = x.shape[0]
    pad = (-b) % multiple
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], 0)
    return x, b


def graph_embeddings_fused(params, adj_norm, feats, mask, *,
                           block_graphs: int = 8,
                           interpret: bool | None = None) -> jax.Array:
    """SimGNN stages 1-2 via the fused Pallas kernel. Pads B to a block
    multiple (pad graphs have all-zero masks -> zero embeddings)."""
    adj_norm, b = _pad_batch(adj_norm, block_graphs)
    feats, _ = _pad_batch(feats, block_graphs)
    mask, _ = _pad_batch(mask, block_graphs)
    out = fused_gcn_att(adj_norm, feats, mask, params["gcn"],
                        params["att"]["w"], block_graphs=block_graphs,
                        interpret=interpret)
    return out[:b]


def pair_scores_fused(params, hg1, hg2, *, block_pairs: int = 128,
                      interpret: bool | None = None) -> jax.Array:
    """SimGNN stages 3-4 via the fused head kernel."""
    hg1, b = _pad_batch(hg1, block_pairs)
    hg2, _ = _pad_batch(hg2, block_pairs)
    out = simgnn_head(hg1, hg2, params["ntn"], params["fcn"],
                      block_pairs=block_pairs, interpret=interpret)
    return out[:b]


def simgnn_pair_score_kernel(params, adj1, feats1, mask1, adj2, feats2, mask2,
                             *, block_graphs: int = 8,
                             interpret: bool | None = None) -> jax.Array:
    """Full SimGNN pipeline on the two-kernel path: both graphs share one
    fused GCN+Att invocation (batch 2B), then the fused NTN+FCN head — the
    graph embeddings round-trip through HBM between the two launches (the
    megakernel below removes that). Expects *raw* adjacency; normalization
    happens here (parity with core.simgnn)."""
    from repro.core.gcn import normalized_adjacency

    adj = jnp.concatenate([adj1, adj2], axis=0)
    feats = jnp.concatenate([feats1, feats2], axis=0)
    mask = jnp.concatenate([mask1, mask2], axis=0)
    a_norm = normalized_adjacency(adj, mask)
    hg = graph_embeddings_fused(params, a_norm, feats, mask,
                                block_graphs=block_graphs, interpret=interpret)
    hg1, hg2 = jnp.split(hg, 2, axis=0)
    bp = max(8, min(128, hg1.shape[0]))
    return pair_scores_fused(params, hg1, hg2, block_pairs=bp,
                             interpret=interpret)


def megakernel_block_pairs(n_nodes: int) -> int:
    """Pairs-per-program policy for the megakernel, by graph bucket size.

    Sized so one program's working set (two graphs' adjacency + every layer
    activation at the widest feature dim) stays a small fraction of the
    ~16 MB VMEM: 64 pairs at N=8 down to 8 pairs at N=64."""
    return max(8, min(64, 512 // max(n_nodes, 1)))


def pair_score_megakernel(params, adj1, feats1, mask1, adj2, feats2, mask2,
                          *, block_pairs: int | None = None,
                          interpret: bool | None = None) -> jax.Array:
    """Full SimGNN pipeline in ONE pallas_call (DESIGN.md §7): raw adjacency
    in, [B] scores out; normalization, the whole GCN stack, Att pooling, NTN
    and FCN never leave VMEM. Pads B to a block multiple (pad pairs have
    all-zero masks; their scores are sliced off)."""
    if block_pairs is None:
        block_pairs = megakernel_block_pairs(adj1.shape[-1])
    b = adj1.shape[0]
    # Never pad beyond one block: a batch smaller than block_pairs shrinks
    # the block to B rounded up to the 8-sublane tile instead.
    block_pairs = min(block_pairs, max(8, -(-b // 8) * 8))
    padded = [_pad_batch(x, block_pairs)[0]
              for x in (adj1, feats1, mask1, adj2, feats2, mask2)]
    out = fused_pair_score(*padded, params["gcn"], params["att"]["w"],
                           params["ntn"], params["fcn"],
                           block_pairs=block_pairs, interpret=interpret)
    return out[:b]


def packed_node_budget(max_nodes: int) -> int:
    """Node budget for packed tiles: at least one whole graph must fit, and a
    64-node floor keeps the tile's last dims near the 128-lane MXU tile while
    a single tile's working set (two sides' adjacency + A' + widest-layer
    activations, ~200 KB fp32 at NB=64) stays a small fraction of the ~16 MB
    VMEM even at `packed_tile_block` tiles per program."""
    return max(64, -(-max_nodes // 8) * 8)


def packed_tile_block(node_budget: int) -> int:
    """Tiles-per-program policy for the packed megakernel: scale down with
    the node budget so a program's working set (two sides' adjacency + A' +
    widest activations, ~130 KB fp32 per NB=64 tile) stays ~2 MB — a small
    fraction of the ~16 MB VMEM (16 tiles at NB=64, 8 at NB=128)."""
    return max(1, min(16, 1024 // max(node_budget, 1)))


def _tile_pad_plan(t: int, tile_block: int,
                   quantize_tiles: bool) -> tuple[int, int]:
    """Shared tile-count padding policy for the packed megakernels: returns
    (target_tiles, tile_block) with target a tile_block multiple >= t.

    `quantize_tiles` rounds T up to the next power of two so a serving loop
    with varying batch sizes compiles O(log T) executables instead of one
    per tile count (the 'small, fixed set of shapes' principle)."""
    target = _pow2_ceil(t) if quantize_tiles else t
    tile_block = min(tile_block, target)
    # Pad-tile waste is real kernel work: halve tile_block until the rounding
    # waste is <= t/8 (always true once tile_block divides target).
    while (tile_block > 1
           and (-(-target // tile_block) * tile_block - target) * 8 > target):
        tile_block //= 2
    # target is a tile_block multiple >= t, so padding to `target` lands on it.
    return -(-target // tile_block) * tile_block, tile_block


def pad_tiles(operands, multiple: int) -> tuple:
    """Zero-pad every operand's leading tile axis to a `multiple` multiple
    (pad tiles carry all-zero masks, so `pair_mask` zeroes their slots)."""
    return tuple(_pad_batch(x, multiple)[0] for x in operands)


def _packed_kernel(params, operands, *, sparse: bool, tile_block: int,
                   interpret: bool | None) -> jax.Array:
    """Run the packed-sparse (`sparse`) or packed-dense megakernel on
    `PackedPairBatch.kernel_operands` already padded to a tile_block
    multiple: the one call site of both kernels, on one device or per shard
    (`build_pair_score_sharded`)."""
    kernel = sparse_pair_score if sparse else packed_pair_score
    return kernel(*operands, params["gcn"], params["att"]["w"],
                  params["ntn"], params["fcn"],
                  tile_block=tile_block, interpret=interpret)


def _pair_score_tiles(params, packed, *, sparse: bool,
                      tile_block: int | None, quantize_tiles: bool,
                      interpret: bool | None) -> jax.Array:
    """The single-device packed executor body: pick the tile block, plan
    the tile padding (`_tile_pad_plan`), pad every operand, run the kernel
    and slice the live [T, P] tiles back out."""
    if tile_block is None:
        tile_block = (sparse_tile_block if sparse
                      else packed_tile_block)(packed.node_budget)
    t = packed.mask1.shape[0]
    target, tile_block = _tile_pad_plan(t, tile_block, quantize_tiles)
    operands = pad_tiles(packed.kernel_operands(sparse), target)
    return _packed_kernel(params, operands, sparse=sparse,
                          tile_block=tile_block, interpret=interpret)[:t]


def pair_score_packed(params, packed, *, tile_block: int | None = None,
                      quantize_tiles: bool = False,
                      interpret: bool | None = None) -> jax.Array:
    """Score a `core.batching.PackedPairBatch` in ONE pallas_call
    (DESIGN.md §8): [T, P] pair-slot scores, zero at pad slots. Pads T to a
    tile_block multiple (pad tiles carry all-zero masks; `pair_mask` zeroes
    their slots). Use `core.batching.unpack_pair_scores` to restore the
    original pair order. See `_tile_pad_plan` for `quantize_tiles`."""
    return _pair_score_tiles(params, packed, sparse=False,
                             tile_block=tile_block,
                             quantize_tiles=quantize_tiles,
                             interpret=interpret)


def sparse_tile_block(node_budget: int) -> int:
    """Tiles-per-program policy for the packed-sparse megakernel. The
    tile's inputs are small (edge lists are ~3·E words, ~3 KB at E=256, vs
    16 KB+16 KB of fp32 adjacency at NB=64), but the in-kernel one-hot
    gathers dominate the working set: the v5e compile measures ~2.7 MB of
    scoped VMEM per tile at NB=64, so 8 tiles per program fit the kernel's
    32 MiB limit (`sparse_pair.SPARSE_VMEM_LIMIT`), scaling down with the
    node budget."""
    return max(1, min(8, 512 // max(node_budget, 1)))


def packed_edge_budget(node_budget: int, avg_degree: float | None = None) -> int:
    """Packed-CSR edge budget per tile side: node_budget receiver rows times
    a per-node neighbor budget D from a small quantized ladder (4/6/8/12/16
    ... — O(log) distinct compiled shapes, like the power-of-two tile
    counts) sized to cover ~p75 of the in-degree distribution (self loop
    included) — D=4 at AIDS-like degree ~2.1, so NB·D = 256 slots vs the
    4096-entry dense block at NB=64. The tail beyond D spills to the small
    COO overflow list (degree-aware split), so a modest D never loses
    edges; `packed_pair_edges` also auto-grows if a whole stream outruns
    the budget. Half-way degrees round UP (floor(d + 0.5), not Python's
    banker's round(): round(2.5) == 2 made degree 2.5 share D=4 with the
    1.5–2.4 band while 3.5 rounded up — an inconsistent ladder step)."""
    d = 2.5 if avg_degree is None else avg_degree
    need = math.floor(d + 0.5) + 2         # ~p75 of molecule-like streams;
    for per_node in (4, 6, 8, 12, 16, 24, 32, 48, 64):   # tail -> overflow
        if per_node >= need:
            return node_budget * per_node
    return node_budget * node_budget       # degenerate: fully dense rows


def pair_score_sparse(params, packed, *, tile_block: int | None = None,
                      quantize_tiles: bool = False,
                      interpret: bool | None = None) -> jax.Array:
    """Score a `core.batching.PackedPairBatch` through the edge-centric
    packed-sparse megakernel (DESIGN.md §9): aggregation runs from the
    tile-local A' edge list (in-kernel segment sum) instead of the dense
    block-diagonal adjacency matmul. Same [T, P] output contract, tile
    padding and `quantize_tiles` policy as `pair_score_packed`.

    Expects `packed.edges` (pack with `with_edges=True`); when absent, the
    edge lists are extracted here at the default `packed_edge_budget`."""
    from repro.core.batching import packed_pair_edges

    if packed.edges is None:
        packed = packed._replace(edges=packed_pair_edges(
            packed, packed_edge_budget(packed.node_budget)))
    return _pair_score_tiles(params, packed, sparse=True,
                             tile_block=tile_block,
                             quantize_tiles=quantize_tiles,
                             interpret=interpret)


# ---------------------------------------------------------------------------
# Device-sharded packed scoring (DESIGN.md §16): the [T, ...] tile axis is
# the data-parallel unit — shard it over a 1-D `tile` mesh, run the SAME
# packed megakernel per device on its tile span, gather scores host-side.
# Params ride in replicated (P()); the kernel body is unchanged, so per-tile
# results are bitwise products of the same program as the unsharded call.
#
# All sharded shape policy is pure powers of two (tile_block, padded tile
# count, device count), so every device count's per-device span is a whole
# number of identical tile_block programs, and the per-tile results stay
# bitwise-reproducible across device counts: the kernels are
# tile_block-invariant (each tile's reductions are within-tile; pinned by
# tests/test_sharded.py), so balance-shrinking tile_block to spread few
# tiles over many devices changes only the launch grid, never the scores.


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def _pow2_ceil(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def sharded_tile_block(node_budget: int, *, sparse: bool = False) -> int:
    """Tiles-per-program ceiling for the sharded executor: the
    single-device VMEM policy rounded down to a power of two (see block
    comment above)."""
    tb = (sparse_tile_block if sparse else packed_tile_block)(node_budget)
    return _pow2_floor(tb)


def sharded_tile_plan(t: int, node_budget: int, n_devices: int, *,
                      sparse: bool = False) -> tuple[int, int]:
    """(padded tile count, tile_block) for a sharded call over `t` live
    tiles: T pads to a power-of-two >= t with at least one program per
    device, and tile_block shrinks below the VMEM policy when the mesh has
    more parallelism than tiles — the tile -> device balance assignment
    (20 tiles on 8 devices run as 5 devices x one 4-tile program, not one
    device x a 32-tile program plus 7 idle)."""
    tb = sharded_tile_block(node_budget, sparse=sparse)
    target = _pow2_ceil(max(t, 1))
    tb = min(tb, max(1, target // int(n_devices)))
    return max(target, int(n_devices) * tb), tb


def build_pair_score_sharded(mesh: Mesh, node_budget: int, *, sparse: bool,
                             tile_block: int | None = None,
                             interpret: bool | None = None):
    """Returns (fn, tile_block): `fn(params, operands)` scores
    `PackedPairBatch.kernel_operands(sparse)` with the tile axis sharded
    over the mesh's `tile` axis, each shard running `_packed_kernel`.
    Operands must be padded to the `sharded_tile_plan` target; the output
    is the full padded [T, P] score block (caller slices). `tile_block`
    defaults to the VMEM policy ceiling; callers pass the
    `sharded_tile_plan` block to balance few tiles over many devices.

    check_vma=False: pallas_call carries no replication rule, and every
    output element is tile-local anyway."""
    from repro.distributed.sharding import TILE_AXIS

    if tile_block is None:
        tile_block = sharded_tile_block(node_budget, sparse=sparse)
    local = functools.partial(_packed_kernel, sparse=sparse,
                              tile_block=tile_block, interpret=interpret)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(P(), P(TILE_AXIS)),
                       out_specs=P(TILE_AXIS), check_vma=False)
    return jax.jit(fn), tile_block
