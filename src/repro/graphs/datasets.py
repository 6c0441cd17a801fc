"""Synthetic, stat-matched citation-network datasets.

The container is offline, so Cora/CiteSeer/PubMed are generated to match the
paper's §5 statistics exactly (nodes / undirected edges / feature dim /
classes) with a planted-partition (SBM-style) topology and TF-IDF-like
class-correlated sparse features, so the node-classification task is
actually learnable and the paper's qualitative claims can be validated.

Splits follow the standard semi-supervised protocol of Kipf & Welling /
Veličković et al.: 20 train nodes per class, 500 val, 1000 test.
"""

from __future__ import annotations

import dataclasses
import functools
import zlib

import numpy as np
import jax
import jax.numpy as jnp

from repro.graphs.data import GraphBatch, build_graph_batch

# name: (num_nodes, num_undirected_edges, num_features, num_classes)
DATASETS: dict[str, tuple[int, int, int, int]] = {
    "cora": (2708, 5429, 1433, 7),
    "citeseer": (3312, 4732, 3703, 6),
    "pubmed": (19717, 44338, 500, 3),
    # small stand-ins for the paper's "too big for this study" §5 datasets,
    # used by the scaling example only
    "reddit-mini": (8192, 131072, 300, 50),
    "karate": (34, 78, 34, 2),
}

# Power-law (Zipf) degree graphs: max_deg ≫ median_deg, so the padded
# (n, max_deg) layout's cost is dominated by a handful of hub rows. These
# are the fixtures for the degree-bucketed sparse path (fig3 sparse rows,
# the CI sparse gate, and the engine backend-equivalence tests).
# name: (num_nodes, num_features, num_classes, zipf_a, deg_cap)
SKEWED_DATASETS: dict[str, tuple[int, int, int, float, int]] = {
    "skewed-powerlaw": (8192, 64, 16, 1.7, 1024),
    # test-sized twin: same shape of degree distribution, tractable in tier-1
    "skewed-mini": (256, 16, 4, 1.7, 96),
}


def _powerlaw_edges(
    rng: np.random.Generator,
    labels: np.ndarray,
    *,
    zipf_a: float,
    deg_cap: int,
    p_intra: float,
) -> np.ndarray:
    """Undirected edges with Zipf-distributed target degrees.

    Each node draws a target degree from Zipf(a) (capped), then connects to
    that many partners — within-class with probability ``p_intra`` so the
    classification task stays aggregation-dependent, like the planted
    citation graphs. The realized degree distribution keeps the heavy tail:
    a few hub nodes collect both their own draws and everyone else's.
    """
    n = labels.shape[0]
    by_class = [np.flatnonzero(labels == c) for c in range(labels.max() + 1)]
    target = np.minimum(rng.zipf(zipf_a, size=n), min(deg_cap, n - 1))
    edges: set[tuple[int, int]] = set()
    for i in range(n):
        want = int(target[i])
        intra = rng.random(want) < p_intra
        members = by_class[labels[i]]
        for k in range(want):
            j = int(members[rng.integers(0, len(members))]) if intra[k] else int(rng.integers(0, n))
            if i == j:
                continue
            edges.add((min(i, j), max(i, j)))
    return np.array(sorted(edges), dtype=np.int64)


def _planted_edges(rng: np.random.Generator, labels: np.ndarray, m: int, p_intra: float) -> np.ndarray:
    """Sample ~m unique undirected edges, p_intra of them within-class."""
    n = labels.shape[0]
    by_class = [np.flatnonzero(labels == c) for c in range(labels.max() + 1)]
    edges: set[tuple[int, int]] = set()
    # sample in batches until we hit m unique edges
    while len(edges) < m:
        want = m - len(edges)
        intra = rng.random(want) < p_intra
        a = rng.integers(0, n, size=want)
        b = np.empty(want, dtype=np.int64)
        for k in range(want):
            if intra[k]:
                members = by_class[labels[a[k]]]
                b[k] = members[rng.integers(0, len(members))]
            else:
                b[k] = rng.integers(0, n)
        for x, y in zip(a, b):
            if x == y:
                continue
            e = (int(min(x, y)), int(max(x, y)))
            edges.add(e)
    out = np.array(sorted(edges), dtype=np.int64)[:m]
    return out


def _tfidf_features(
    rng: np.random.Generator,
    labels: np.ndarray,
    num_features: int,
    *,
    words_per_doc: int = 24,
    on_topic_frac: float = 0.17,
) -> np.ndarray:
    """Sparse bag-of-words-ish features with per-class topic vocabularies.

    ``on_topic_frac`` is deliberately weak: the per-node feature signal alone
    should NOT solve the task, so the model has to aggregate neighborhoods —
    which is what makes the paper's Fig-4 accuracy collapse (edges lost under
    sequential micro-batching) observable.
    """
    n = labels.shape[0]
    c = labels.max() + 1
    feats = np.zeros((n, num_features), dtype=np.float32)
    # each class owns a random slice of ~num_features/(2c) topic words
    topic_size = max(4, num_features // (2 * c))
    topics = [rng.choice(num_features, size=topic_size, replace=False) for _ in range(c)]
    for i in range(n):
        k_topic = max(1, int(round(words_per_doc * on_topic_frac)))
        on_topic = topics[labels[i]][rng.integers(0, topic_size, size=k_topic)]
        off_topic = rng.integers(0, num_features, size=words_per_doc - k_topic)
        idx = np.concatenate([on_topic, off_topic])
        vals = rng.random(idx.shape[0]).astype(np.float32) + 0.5
        feats[i, idx] = vals
    # row-normalize as PyG does for citation BoW features
    row = feats.sum(axis=1, keepdims=True)
    row[row == 0] = 1.0
    return feats / row


def _standard_split(
    rng: np.random.Generator, labels: np.ndarray, *, per_class: int = 20, n_val: int = 500, n_test: int = 1000
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    n = labels.shape[0]
    c = labels.max() + 1
    train = np.zeros(n, dtype=bool)
    for cls in range(c):
        members = np.flatnonzero(labels == cls)
        # tiny graphs (karate): keep ≥2/3 of each class out of train so the
        # val/test splits are non-empty
        take = min(per_class, max(1, len(members) // 3))
        train[rng.choice(members, size=take, replace=False)] = True
    rest = np.flatnonzero(~train)
    rest = rng.permutation(rest)
    n_val = min(n_val, max(0, len(rest) - 1))
    n_test = min(n_test, max(0, len(rest) - n_val))
    val = np.zeros(n, dtype=bool)
    test = np.zeros(n, dtype=bool)
    val[rest[:n_val]] = True
    test[rest[n_val : n_val + n_test]] = True
    return train, val, test


def load_dataset(
    name: str,
    *,
    seed: int = 0,
    max_degree: int | None = None,
    p_intra: float = 0.9,
) -> GraphBatch:
    """Generate the stat-matched synthetic dataset ``name`` deterministically."""
    if name not in DATASETS and name not in SKEWED_DATASETS:
        raise KeyError(
            f"unknown dataset {name!r}; have {sorted(DATASETS) + sorted(SKEWED_DATASETS)}"
        )
    # crc32, not hash(): str hashing is salted per process (PYTHONHASHSEED),
    # which silently made "deterministic" datasets differ between runs
    name_key = zlib.crc32(name.encode()) & 0xFFFF
    rng = np.random.default_rng(np.random.SeedSequence([name_key, seed]))
    if name in SKEWED_DATASETS:
        n, d, c, zipf_a, deg_cap = SKEWED_DATASETS[name]
        labels = rng.integers(0, c, size=n).astype(np.int64)
        edges = _powerlaw_edges(rng, labels, zipf_a=zipf_a, deg_cap=deg_cap, p_intra=p_intra)
    else:
        n, m, d, c = DATASETS[name]
        labels = rng.integers(0, c, size=n).astype(np.int64)
        edges = _planted_edges(rng, labels, m, p_intra)
    feats = _tfidf_features(rng, labels, d)
    train, val, test = _standard_split(rng, labels)
    return build_graph_batch(
        feats,
        edges,
        labels,
        c,
        train_mask=train,
        val_mask=val,
        test_mask=test,
        max_degree=max_degree,
    )


# ------------------------------------------------ streamed power-law graphs --
#
# The registries above generate the WHOLE graph in one rng stream, so every
# node's data depends on every draw before it — fine at 20k nodes, hopeless at
# a million (and it forces the full (n, d) feature matrix into memory at
# once). The streamed generator below is random-access by fixed-size BLOCK:
# each block of ``block_size`` nodes owns an independent rng seeded
# ``[name_key, seed, block_index]`` and draws, in a fixed order, its labels,
# its nodes' out-edges, its features, and its split coins. Any node range
# ``[lo, hi)`` can therefore be materialized by generating only the blocks it
# overlaps — the chunk a pipeline micro-batch needs, never the full graph —
# and the result is invariant to HOW the graph is chunked (property-tested in
# tests/test_streamed.py: a chunk's edge set equals the restriction of any
# containing chunk's edge set).
#
# Blocks double as the planted communities: a node's intra-class partners are
# drawn from its own block (global partners are uniform over all n nodes), so
# edge generation never needs another block's labels.

# name: (num_nodes, num_features, num_classes, zipf_a, deg_cap)
STREAMED_DATASETS: dict[str, tuple[int, int, int, float, int]] = {
    "powerlaw-64k": (65_536, 64, 16, 1.7, 48),
    "powerlaw-256k": (262_144, 64, 16, 1.7, 48),
    "powerlaw-1m": (1_048_576, 64, 16, 1.7, 48),
}

# third SeedSequence word for the stream shared across blocks (class topic
# vocabularies); block streams use the block index, which starts at 0, so the
# salt must sit outside the block-index range
_TOPIC_SALT = 0x7F000001


def _padded_rows_from_edges(
    n: int, edges: np.ndarray, max_degree: int | None
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Vectorized twin of ``build_graph_batch``'s padded-layout construction
    (which walks Python adjacency sets — fine at 20k nodes, minutes at 1M).

    Same contract bit for bit: undirected ``edges`` (m, 2) -> (neighbors,
    mask, norm) with the self-loop in slot 0, neighbors sorted ascending,
    truncation keeping the lowest-index neighbors, and GCN norm computed
    from the UNtruncated degree. Like the reference, repeated edges count
    once and self-loop edges are dropped.
    """
    edges = np.asarray(edges).reshape(-1, 2)
    edges = np.unique(np.sort(edges[edges[:, 0] != edges[:, 1]], axis=1), axis=0)
    if len(edges):
        directed = np.concatenate([edges, edges[:, ::-1]])
        order = np.lexsort((directed[:, 1], directed[:, 0]))
        src, dst = directed[order, 0], directed[order, 1]
    else:
        src = dst = np.zeros(0, dtype=np.int64)
    deg_full = np.bincount(src, minlength=n)
    true_max = int(deg_full.max(initial=0))
    width = 1 + (true_max if max_degree is None else min(max_degree, true_max))

    # rank of each directed edge within its source's sorted run; keep the
    # first width-1 (== build_graph_batch's "drop highest-index" truncation)
    starts = np.concatenate([[0], np.cumsum(deg_full)[:-1]])
    rank = np.arange(len(src)) - starts[src]
    keep = rank < width - 1

    neighbors = np.zeros((n, width), dtype=np.int32)
    mask = np.zeros((n, width), dtype=bool)
    neighbors[:, 0] = np.arange(n)
    mask[:, 0] = True
    neighbors[src[keep], 1 + rank[keep]] = dst[keep]
    mask[src[keep], 1 + rank[keep]] = True

    inv_sqrt = 1.0 / np.sqrt(deg_full + 1.0)  # self-looped, untruncated
    norm = inv_sqrt[:, None] * inv_sqrt[neighbors] * mask
    return neighbors, mask, norm.astype(np.float32)


@dataclasses.dataclass(frozen=True)
class StreamedPowerlaw:
    """A power-law graph generated lazily, one node block at a time.

    Never holds the full graph: ``chunk_batch(lo, hi)`` materializes exactly
    the blocks overlapping ``[lo, hi)`` and returns a host-built
    ``GraphBatch`` of that node range with boundary-crossing edges dropped
    (the paper's sequential-lossy micro-batching, applied at generation
    time). Chunk contents are independent of the chunking because every
    block draws from its own ``[name_key, seed, block]`` rng.
    """

    name: str
    num_nodes: int
    num_features: int
    num_classes: int
    zipf_a: float
    deg_cap: int
    seed: int = 0
    block_size: int = 4096
    p_intra: float = 0.9

    @property
    def num_blocks(self) -> int:
        """Generator blocks covering the node axis (last may be short)."""
        return -(-self.num_nodes // self.block_size)

    @property
    def _name_key(self) -> int:
        return zlib.crc32(self.name.encode()) & 0xFFFF

    def _block_rng(self, block: int) -> np.random.Generator:
        return np.random.default_rng(
            np.random.SeedSequence([self._name_key, self.seed, block])
        )

    @functools.cached_property
    def _topics(self) -> np.ndarray:
        """Per-class topic vocabularies, shared by every block (seeded off a
        dedicated stream so block generation stays random-access)."""
        rng = np.random.default_rng(
            np.random.SeedSequence([self._name_key, self.seed, _TOPIC_SALT])
        )
        topic_size = max(4, self.num_features // (2 * self.num_classes))
        return np.stack(
            [
                rng.choice(self.num_features, size=topic_size, replace=False)
                for _ in range(self.num_classes)
            ]
        )

    def generate_block(self, block: int):
        """All of one block's node data, drawn in a FIXED order from the
        block's own rng (labels -> out-edges -> features -> split coins).
        Returns ``(labels, edges, features, train, val, test)``; ``edges``
        are (m, 2) unique undirected pairs in GLOBAL indices whose source
        node lives in this block (partners may be anywhere)."""
        if not 0 <= block < self.num_blocks:
            raise IndexError(f"block {block} out of range [0, {self.num_blocks})")
        rng = self._block_rng(block)
        lo = block * self.block_size
        nb = min(self.block_size, self.num_nodes - lo)

        labels = rng.integers(0, self.num_classes, size=nb).astype(np.int64)

        # Zipf out-degree draws, vectorized over the block: repeat each
        # source by its target degree, flip one intra/inter coin per slot,
        # intra partners uniform over the SAME block's class members
        target = np.minimum(rng.zipf(self.zipf_a, size=nb), min(self.deg_cap, self.num_nodes - 1))
        src_local = np.repeat(np.arange(nb), target)
        total = int(target.sum())
        intra = rng.random(total) < self.p_intra
        partners = rng.integers(0, self.num_nodes, size=total)
        src_labels = labels[src_local]
        for c in range(self.num_classes):
            sel = intra & (src_labels == c)
            if not sel.any():
                continue
            members = np.flatnonzero(labels == c) + lo
            partners[sel] = members[rng.integers(0, len(members), size=int(sel.sum()))]
        src = src_local + lo
        a, b = np.minimum(src, partners), np.maximum(src, partners)
        keep = a != b
        edges = (
            np.unique(np.stack([a[keep], b[keep]], axis=1), axis=0)
            if keep.any()
            else np.zeros((0, 2), dtype=np.int64)
        )

        # vectorized _tfidf_features twin over the shared topic vocabularies
        words, on_topic_frac = 24, 0.17
        k_topic = max(1, int(round(words * on_topic_frac)))
        topics = self._topics
        on = topics[labels[:, None], rng.integers(0, topics.shape[1], size=(nb, k_topic))]
        off = rng.integers(0, self.num_features, size=(nb, words - k_topic))
        idx = np.concatenate([on, off], axis=1)
        vals = (rng.random((nb, words)) + 0.5).astype(np.float32)
        feats = np.zeros((nb, self.num_features), dtype=np.float32)
        feats[np.arange(nb)[:, None], idx] = vals
        row = feats.sum(axis=1, keepdims=True)
        row[row == 0] = 1.0
        feats /= row

        # streaming-friendly split: one uniform coin per node instead of the
        # global 20-per-class protocol (which needs every label at once)
        u = rng.random(nb)
        train = u < 0.10
        val = (u >= 0.10) & (u < 0.15)
        test = (u >= 0.15) & (u < 0.20)
        return labels, edges, feats, train, val, test

    def chunk_ranges(self, chunks: int) -> list[tuple[int, int]]:
        """``chunks`` near-equal contiguous node ranges covering the graph."""
        bounds = np.linspace(0, self.num_nodes, chunks + 1).astype(np.int64)
        return [(int(bounds[i]), int(bounds[i + 1])) for i in range(chunks)]

    @functools.cached_property
    def _edge_memo(self) -> dict:
        # plan construction asks for a range's edges twice (batch + cut
        # accounting); memoize per range, bounded by ranges actually used
        return {}

    def chunk_edges(self, lo: int, hi: int) -> tuple[np.ndarray, int]:
        """Edges of the node range ``[lo, hi)`` in LOCAL indices, plus the
        count of generated edges dropped for crossing the range boundary
        (the edge-cut numerator). Only blocks overlapping the range are
        generated; an edge with both endpoints inside always has its source
        endpoint in such a block, so the kept set equals the restriction of
        any containing range's kept set."""
        if not 0 <= lo < hi <= self.num_nodes:
            raise ValueError(f"bad chunk range [{lo}, {hi}) for {self.num_nodes} nodes")
        hit = self._edge_memo.get((lo, hi))
        if hit is not None:
            return hit
        parts, dropped = [], 0
        for blk in range(lo // self.block_size, -(-hi // self.block_size)):
            _, edges, *_ = self.generate_block(blk)
            touches = ((edges >= lo) & (edges < hi)).any(axis=1) if len(edges) else np.zeros(0, bool)
            inside = ((edges >= lo) & (edges < hi)).all(axis=1) if len(edges) else touches
            dropped += int(touches.sum() - inside.sum())
            parts.append(edges[inside])
        kept = np.concatenate(parts) if parts else np.zeros((0, 2), dtype=np.int64)
        # adjacent blocks can both source an edge that lands in the range
        kept = np.unique(kept, axis=0) if len(kept) else kept
        self._edge_memo[(lo, hi)] = (kept - lo, dropped)
        return kept - lo, dropped

    def chunk_batch(self, lo: int, hi: int, *, max_degree: int | None = None) -> GraphBatch:
        """Materialize node range ``[lo, hi)`` as a host-built GraphBatch
        (boundary-crossing edges dropped). ``max_degree`` caps the padded
        neighbor width like ``build_graph_batch``'s parameter."""
        feats, labels, train, val, test = [], [], [], [], []
        for blk in range(lo // self.block_size, -(-hi // self.block_size)):
            blk_lo = blk * self.block_size
            lab, _, f, tr, va, te = self.generate_block(blk)
            s = slice(max(lo - blk_lo, 0), min(hi - blk_lo, len(lab)))
            feats.append(f[s])
            labels.append(lab[s])
            train.append(tr[s])
            val.append(va[s])
            test.append(te[s])
        edges, _ = self.chunk_edges(lo, hi)
        neighbors, mask, norm = _padded_rows_from_edges(hi - lo, edges, max_degree)
        return GraphBatch(
            features=jnp.asarray(np.concatenate(feats)),
            neighbors=jnp.asarray(neighbors),
            mask=jnp.asarray(mask),
            norm=jnp.asarray(norm),
            labels=jnp.asarray(np.concatenate(labels), dtype=jnp.int32),
            train_mask=jnp.asarray(np.concatenate(train)),
            val_mask=jnp.asarray(np.concatenate(val)),
            test_mask=jnp.asarray(np.concatenate(test)),
            node_ids=jnp.arange(lo, hi, dtype=jnp.int32),
            num_classes=self.num_classes,
        )


def open_streamed(
    name: str,
    *,
    seed: int = 0,
    num_nodes: int | None = None,
    block_size: int = 4096,
    p_intra: float = 0.9,
) -> StreamedPowerlaw:
    """Open a ``STREAMED_DATASETS`` entry as a lazy block generator.

    ``num_nodes`` overrides the registry size (tests shrink the graph;
    benchmarks sweep sizes at fixed density knobs); ``block_size`` trades
    generation granularity for memory and NEVER changes the generated data
    of a block-aligned range of the same dataset name/seed/block_size.
    """
    if name not in STREAMED_DATASETS:
        raise KeyError(f"unknown streamed dataset {name!r}; have {sorted(STREAMED_DATASETS)}")
    n, d, c, zipf_a, deg_cap = STREAMED_DATASETS[name]
    return StreamedPowerlaw(
        name=name,
        num_nodes=n if num_nodes is None else num_nodes,
        num_features=d,
        num_classes=c,
        zipf_a=zipf_a,
        deg_cap=deg_cap,
        seed=seed,
        block_size=block_size,
        p_intra=p_intra,
    )


class DoubleBufferedLoader:
    """Iterate host pytrees as device-resident pytrees with the NEXT item's
    host->device transfer already dispatched while the caller computes on the
    current one.

    ``jax.device_put`` enqueues the copy asynchronously; by putting item
    ``t+1`` before yielding item ``t``, the transfer overlaps whatever the
    caller launches on ``t`` (the double-buffered ``device_put`` pattern —
    two items are in flight at any moment, never the whole stream). Used by
    the streamed-graph benches and examples to walk chunk batches a
    million-node graph can't hold on device all at once.
    """

    def __init__(self, source, device=None):
        self._source = source
        self._device = device

    def _put(self, item):
        return (
            jax.device_put(item, self._device)
            if self._device is not None
            else jax.device_put(item)
        )

    def __iter__(self):
        it = iter(self._source)
        try:
            nxt = self._put(next(it))
        except StopIteration:
            return
        for item in it:
            cur, nxt = nxt, self._put(item)
            yield cur
        yield nxt
