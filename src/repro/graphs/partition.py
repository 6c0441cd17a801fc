"""Node partitioners + halo expansion for graph micro-batching, plus the
degree-bucketed aggregation layout.

``sequential`` is the paper's §6/§7.3 behaviour: GPipe splits the node-index
tensor *by position*, so chunk boundaries cut edges arbitrarily. ``greedy``
is a lightweight edge-cut-aware partitioner (METIS stand-in). ``halo``
expands a chunk with its k-hop neighborhood so message passing stays exact —
the "intelligent graph batching" the paper calls for in §8.

``degree_bucketed_layout`` re-tiles the padded ``(n, max_deg)`` neighbor
matrix into geometric degree buckets (widths 8/16/32/…/max_deg): each row
moves to the narrowest bucket its live slot count fits, so aggregation work
scales with the degree *distribution* instead of the single worst-case
degree — on power-law graphs the padded layout spends almost all its slots
on padding for a handful of hubs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.data import BucketedGraphBatch, DegreeBucket, GraphBatch


def sequential_partition(num_nodes: int, chunks: int) -> list[np.ndarray]:
    """Index-sequential split — exactly what torchgpipe does to a tensor."""
    return [np.asarray(p) for p in np.array_split(np.arange(num_nodes), chunks)]


def random_partition(num_nodes: int, chunks: int, *, seed: int = 0) -> list[np.ndarray]:
    """Uniformly random node split — the locality-free baseline the greedy
    partitioner is compared against."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(num_nodes)
    return [np.sort(p) for p in np.array_split(perm, chunks)]


def _adjacency_sets(g: GraphBatch) -> list[set[int]]:
    nbr = np.asarray(g.neighbors)
    msk = np.asarray(g.mask)
    out: list[set[int]] = []
    for i in range(nbr.shape[0]):
        s = set(int(j) for j, m in zip(nbr[i], msk[i]) if m and j != i)
        out.append(s)
    return out


def greedy_partition(g: GraphBatch, chunks: int, *, seed: int = 0) -> list[np.ndarray]:
    """Greedy BFS-grown balanced partitions (edge-cut-aware METIS stand-in).

    Grows each part from a random seed by BFS, preferring frontier nodes, so
    intra-part connectivity is much higher than an index split."""
    n = g.num_nodes
    adj = _adjacency_sets(g)
    rng = np.random.default_rng(seed)
    target = [len(p) for p in np.array_split(np.arange(n), chunks)]
    unassigned = set(range(n))
    parts: list[list[int]] = []
    order = rng.permutation(n)
    cursor = 0
    for c in range(chunks):
        part: list[int] = []
        frontier: list[int] = []
        while len(part) < target[c] and unassigned:
            if not frontier:
                # pick a fresh unassigned seed
                while cursor < n and order[cursor] not in unassigned:
                    cursor += 1
                if cursor >= n:
                    frontier = [next(iter(unassigned))]
                else:
                    frontier = [int(order[cursor])]
            node = frontier.pop()
            if node not in unassigned:
                continue
            unassigned.discard(node)
            part.append(node)
            frontier.extend(j for j in adj[node] if j in unassigned)
        parts.append(part)
    # dump any stragglers into the last part
    parts[-1].extend(unassigned)
    return [np.sort(np.array(p, dtype=np.int64)) for p in parts]


def pad_partition(
    nodes: np.ndarray, core: np.ndarray, n_pad: int
) -> tuple[np.ndarray, np.ndarray]:
    """Pad a chunk's (nodes, core_mask) spec to ``n_pad`` entries by repeating
    node 0 with core_mask False — the padded duplicates lose their edges in
    ``subgraph()``'s remap and their loss mask is off, so they are inert.
    Uniform chunk sizes let one jitted step (or one stacked scan) serve every
    chunk."""
    extra = n_pad - len(nodes)
    if extra < 0:
        raise ValueError(f"chunk of {len(nodes)} nodes exceeds pad target {n_pad}")
    if extra == 0:
        return nodes, core
    nodes = np.concatenate([nodes, np.zeros(extra, dtype=nodes.dtype)])
    core = np.concatenate([core, np.zeros(extra, dtype=bool)])
    return nodes, core


def expand_halo(g: GraphBatch, core: np.ndarray, hops: int) -> tuple[np.ndarray, np.ndarray]:
    """Return (nodes, core_mask): ``core`` plus its ``hops``-hop neighborhood.

    ``core_mask[i]`` is True iff nodes[i] is a core node (loss/update target).
    With hops == model receptive depth, aggregation on the halo'd sub-graph is
    exact for every core node."""
    nbr = np.asarray(g.neighbors)
    msk = np.asarray(g.mask)
    current = np.zeros(g.num_nodes, dtype=bool)
    current[core] = True
    reach = current.copy()
    for _ in range(hops):
        sel = np.flatnonzero(reach)
        hop = nbr[sel][msk[sel]]
        nxt = reach.copy()
        nxt[hop] = True
        reach = nxt
    nodes = np.flatnonzero(reach)
    core_mask = current[nodes]
    return nodes, core_mask


def ego_subgraph(
    g: GraphBatch, seeds: np.ndarray, hops: int
) -> tuple[GraphBatch, np.ndarray]:
    """The ``hops``-hop ego-subgraph around ``seeds`` plus the seeds' local
    row indices — the serving frontend's extraction step.

    With ``hops`` >= the model's receptive depth the halo is lossless:
    every message a seed aggregates exists in the sub-graph, so its
    prediction equals the full-graph one (bit-identically on the padded
    backend — ``subgraph`` preserves each kept node's neighbor column order
    and trailing pad columns contribute exact zeros)."""
    from repro.graphs.data import subgraph

    seeds = np.asarray(seeds)
    nodes, _ = expand_halo(g, seeds, hops)
    sub = subgraph(g, nodes)
    # expand_halo returns nodes as flatnonzero output — sorted ascending —
    # so the seeds' local rows come from a binary search
    rows = np.searchsorted(nodes, seeds)
    return sub, rows


def degree_bucket_widths(max_deg: int, *, base: int = 8) -> tuple[int, ...]:
    """Geometric bucket-width ladder ``(base, 2·base, …, max_deg)``.

    ``max_deg`` is the padded layout's slot width (self-loop included) and is
    always the last rung, so every row fits somewhere.
    """
    if max_deg <= 0:
        raise ValueError(f"max_deg must be positive, got {max_deg}")
    widths: list[int] = []
    w = base
    while w < max_deg:
        widths.append(w)
        w *= 2
    widths.append(max_deg)
    return tuple(widths)


def degree_bucketed_layout(
    g: GraphBatch,
    widths: tuple[int, ...] | None = None,
    *,
    row_capacities: tuple[int, ...] | None = None,
    block: int = 8,
) -> BucketedGraphBatch:
    """Permute rows into degree buckets; carry the permutation + inverse.

    Each row's live slots are first compacted leftward (``subgraph()`` can
    leave holes in ``mask``), then the row is assigned to the narrowest
    bucket whose width covers its slot count (slot-less padding rows land in
    bucket 0 as inert all-masked rows). Each bucket is padded to a row
    capacity — a multiple of ``block`` by default, or the caller's
    ``row_capacities`` when several chunks must share one set of bucket
    shapes (one jitted program for all chunks). The permutation lives in
    ``row_node`` (bucket row -> original row) and its inverse in
    ``gather_rows`` (original row -> bucket-concat row).

    Host-side (numpy) by design, like ``subgraph``: layout construction is a
    per-plan preprocessing step, never part of the jitted hot path.
    """
    nbr = np.asarray(g.neighbors)
    msk = np.asarray(g.mask)
    nrm = np.asarray(g.norm)
    n, max_deg = nbr.shape
    if widths is None:
        widths = degree_bucket_widths(max_deg)
    if widths[-1] < max_deg:
        raise ValueError(f"last bucket width {widths[-1]} < layout width {max_deg}")
    if row_capacities is not None and len(row_capacities) != len(widths):
        raise ValueError("row_capacities must match widths")

    # compact live slots leftward: stable argsort of ~mask keeps live-slot
    # order (slot 0's self-loop stays first) while closing subgraph() holes.
    # Within-row slot order only affects float summation order, which the
    # oracle-tolerance equivalence tests already absorb.
    order = np.argsort(~msk, axis=1, kind="stable")
    nbr = np.take_along_axis(nbr, order, axis=1)
    nrm = np.take_along_axis(nrm, order, axis=1)
    msk = np.take_along_axis(msk, order, axis=1)
    slots = msk.sum(axis=1)  # live slots per row (self-loop included)

    # narrowest bucket whose width >= slots; slot-less rows -> bucket 0
    bucket_of = np.searchsorted(np.asarray(widths), slots)

    buckets: list[DegreeBucket] = []
    gather = np.zeros(n, dtype=np.int32)
    offset = 0
    for b, wb in enumerate(widths):
        rows = np.flatnonzero(bucket_of == b)
        if row_capacities is not None:
            cap = int(row_capacities[b])
        else:
            cap = -(-len(rows) // block) * block if len(rows) else 0
        if cap < len(rows):
            raise ValueError(f"bucket {b}: capacity {cap} < {len(rows)} rows")
        b_nbr = np.zeros((cap, wb), dtype=np.int32)
        b_nrm = np.zeros((cap, wb), dtype=nrm.dtype)
        b_msk = np.zeros((cap, wb), dtype=bool)
        b_row = np.zeros(cap, dtype=np.int32)
        b_nbr[: len(rows)] = nbr[rows, :wb]
        b_nrm[: len(rows)] = nrm[rows, :wb]
        b_msk[: len(rows)] = msk[rows, :wb]
        b_row[: len(rows)] = rows
        gather[rows] = offset + np.arange(len(rows), dtype=np.int32)
        buckets.append(
            DegreeBucket(
                neighbors=jnp.asarray(b_nbr),
                norm=jnp.asarray(b_nrm, dtype=g.norm.dtype),
                mask=jnp.asarray(b_msk),
                row_node=jnp.asarray(b_row),
            )
        )
        offset += cap
    return BucketedGraphBatch(
        base=g, buckets=tuple(buckets), gather_rows=jnp.asarray(gather)
    )


def bucketize_stacked(
    g: GraphBatch, *, widths: tuple[int, ...] | None = None, block: int = 8
) -> BucketedGraphBatch:
    """Bucketize a chunk-stacked graph (leading ``chunks`` axis on every leaf).

    All chunks share one set of bucket row capacities (the per-bucket max
    over chunks, rounded up to ``block``), so the per-chunk layouts stack
    into uniform-shape arrays and one jitted stage program serves every
    chunk — the same uniformity contract ``MicroBatchPlan.stacked()`` keeps
    for the padded layout.
    """
    msk = np.asarray(g.mask)  # (chunks, n_pad, max_deg)
    chunks, _, max_deg = msk.shape
    if widths is None:
        widths = degree_bucket_widths(max_deg)
    slots = msk.sum(axis=2)  # (chunks, n_pad)
    bucket_of = np.searchsorted(np.asarray(widths), slots)
    caps = []
    for b in range(len(widths)):
        most = int((bucket_of == b).sum(axis=1).max())
        caps.append(-(-most // block) * block if most else 0)
    caps = tuple(caps)

    per_chunk = [
        degree_bucketed_layout(
            jax.tree_util.tree_map(lambda a, c=c: a[c], g),
            widths,
            row_capacities=caps,
            block=block,
        )
        for c in range(chunks)
    ]
    stacked_buckets = tuple(
        DegreeBucket(
            neighbors=jnp.stack([pc.buckets[b].neighbors for pc in per_chunk]),
            norm=jnp.stack([pc.buckets[b].norm for pc in per_chunk]),
            mask=jnp.stack([pc.buckets[b].mask for pc in per_chunk]),
            row_node=jnp.stack([pc.buckets[b].row_node for pc in per_chunk]),
        )
        for b in range(len(widths))
    )
    gather = jnp.stack([pc.gather_rows for pc in per_chunk])
    return BucketedGraphBatch(base=g, buckets=stacked_buckets, gather_rows=gather)


def layout_slots(graph) -> int:
    """Neighbor slots the aggregation layout materializes per chunk:
    ``n_pad · max_deg`` for the padded layout, ``Σ rows_b · width_b`` for a
    degree-bucketed wrapper."""
    if isinstance(graph, BucketedGraphBatch):
        return int(sum(b.rows * b.width for b in graph.buckets))
    return int(graph.neighbors.shape[-2] * graph.neighbors.shape[-1])


def edge_cut_fraction(g: GraphBatch, parts: list[np.ndarray]) -> float:
    """Fraction of (directed, non-self) edge slots crossing part boundaries —
    the information the paper's sequential split throws away."""
    owner = np.empty(g.num_nodes, dtype=np.int64)
    for pid, p in enumerate(parts):
        owner[p] = pid
    nbr = np.asarray(g.neighbors)
    msk = np.asarray(g.mask).copy()
    msk[:, 0] = False  # ignore self-loops
    src_owner = np.broadcast_to(owner[:, None], nbr.shape)
    cut = (owner[nbr] != src_owner) & msk
    total = msk.sum()
    return float(cut.sum()) / float(max(total, 1))


def streamed_plan(ds, chunks: int, *, max_degree: int | None = None):
    """Micro-batch plan over a ``repro.graphs.datasets.StreamedPowerlaw``:
    ``chunks`` contiguous node ranges, each materialized independently via
    ``ds.chunk_batch`` so the full graph never exists in memory — the
    streamed analogue of ``make_plan(..., strategy="sequential")`` (same
    lossy boundary semantics, same all-core masks, same plan container the
    pipeline engines consume).

    ``edge_cut`` is computed from the generator's own drop counts (edges
    generated with exactly one endpoint inside a chunk), since there is no
    whole graph to diff against.
    """
    import time

    from repro.core.microbatch import MicroBatch, MicroBatchPlan

    t0 = time.perf_counter()
    batches, kept, dropped = [], 0, 0
    for lo, hi in ds.chunk_ranges(chunks):
        g = ds.chunk_batch(lo, hi, max_degree=max_degree)
        _, d = ds.chunk_edges(lo, hi)
        kept += int(g.num_edges) // 2
        dropped += d
        batches.append(MicroBatch(graph=g, core_mask=jnp.ones(g.num_nodes, dtype=bool)))
    return MicroBatchPlan(
        strategy="streamed",
        chunks=chunks,
        batches=batches,
        rebuild_seconds=time.perf_counter() - t0,
        edge_cut=float(dropped) / float(max(kept + dropped, 1)),
    )
