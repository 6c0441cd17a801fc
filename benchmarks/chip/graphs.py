"""The benchmark's own graph generators and chunk layouts.

Copies of the repository's seed-generated datasets (``repro.graphs.datasets``:
the planted citation stand-ins and the streamed power-law graphs), so that a
change to the program's generators cannot move the benchmark's inputs. The
draws are the same, in the same order, so a graph here equals the program's
graph of the same name and dataset seed.

``build(spec)`` turns a workload's ``graph`` entry into the stacked chunk
arrays the cell trains on: ``(chunks, n_pad, ...)`` numpy arrays in the
padded-neighbour layout (self-loop in slot 0, neighbours ascending, GCN
norm), plus the node and edge counts the FLOP count needs. Both the program
(through its plan container) and the reference read these arrays.

* ``kind: "planted"`` — a citation stand-in (cora), split into contiguous
  index chunks, each carrying its ``halo_hops``-hop halo; padding rows are
  isolated non-nodes (no edge slots, no loss).
* ``kind: "streamed"`` — a power-law graph generated block by block, split
  into contiguous node ranges with boundary-crossing edges dropped. Each
  block is generated once. The result is cached under ``.cache/`` beside
  this file, keyed by every generator parameter.
"""

from __future__ import annotations

import hashlib
import json
import os
import zlib

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
CACHE_DIR = os.path.join(HERE, ".cache")

# the stacked fields, in the order of the program's GraphBatch
FIELDS = ("features", "neighbors", "mask", "norm", "labels",
          "train_mask", "val_mask", "test_mask", "node_ids")

# name: (num_nodes, num_undirected_edges, num_features, num_classes)
PLANTED = {"cora": (2708, 5429, 1433, 7)}
# third SeedSequence word of the streamed generator's topic stream
_TOPIC_SALT = 0x7F000001


def _name_key(name: str) -> int:
    return zlib.crc32(name.encode()) & 0xFFFF


# ---------------------------------------------------------------- planted --


def _planted_edges(rng, labels, m, p_intra):
    n = labels.shape[0]
    by_class = [np.flatnonzero(labels == c) for c in range(labels.max() + 1)]
    edges: set = set()
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
            if x != y:
                edges.add((int(min(x, y)), int(max(x, y))))
    return np.array(sorted(edges), dtype=np.int64)[:m]


def _tfidf_features(rng, labels, num_features, words=24, on_topic_frac=0.17):
    n = labels.shape[0]
    c = labels.max() + 1
    feats = np.zeros((n, num_features), dtype=np.float32)
    topic_size = max(4, num_features // (2 * c))
    topics = [rng.choice(num_features, size=topic_size, replace=False) for _ in range(c)]
    k_topic = max(1, int(round(words * on_topic_frac)))
    for i in range(n):
        on = topics[labels[i]][rng.integers(0, topic_size, size=k_topic)]
        off = rng.integers(0, num_features, size=words - k_topic)
        idx = np.concatenate([on, off])
        feats[i, idx] = rng.random(idx.shape[0]).astype(np.float32) + 0.5
    row = feats.sum(axis=1, keepdims=True)
    row[row == 0] = 1.0
    return feats / row


def _standard_split(rng, labels, per_class=20, n_val=500, n_test=1000):
    n = labels.shape[0]
    train = np.zeros(n, dtype=bool)
    for cls in range(labels.max() + 1):
        members = np.flatnonzero(labels == cls)
        take = min(per_class, max(1, len(members) // 3))
        train[rng.choice(members, size=take, replace=False)] = True
    rest = rng.permutation(np.flatnonzero(~train))
    n_val = min(n_val, max(0, len(rest) - 1))
    n_test = min(n_test, max(0, len(rest) - n_val))
    val = np.zeros(n, dtype=bool)
    test = np.zeros(n, dtype=bool)
    val[rest[:n_val]] = True
    test[rest[n_val:n_val + n_test]] = True
    return train, val, test


def padded_rows(n: int, edges: np.ndarray, max_degree: int | None):
    """Undirected ``edges`` -> (neighbors, mask, norm) of width 1 + the
    (capped) largest degree: self-loop in slot 0, neighbours ascending, a
    cap keeping the lowest-index neighbours, and the symmetric GCN norm
    ``1/sqrt((d_i + 1)(d_j + 1))`` of the uncapped self-looped degree."""
    edges = np.asarray(edges).reshape(-1, 2)
    edges = np.unique(np.sort(edges[edges[:, 0] != edges[:, 1]], axis=1), axis=0)
    if len(edges):
        directed = np.concatenate([edges, edges[:, ::-1]])
        order = np.lexsort((directed[:, 1], directed[:, 0]))
        src, dst = directed[order, 0], directed[order, 1]
    else:
        src = dst = np.zeros(0, dtype=np.int64)
    deg = np.bincount(src, minlength=n)
    true_max = int(deg.max(initial=0))
    width = 1 + (true_max if max_degree is None else min(max_degree, true_max))
    starts = np.concatenate([[0], np.cumsum(deg)[:-1]])
    rank = np.arange(len(src)) - starts[src]
    keep = rank < width - 1
    neighbors = np.zeros((n, width), dtype=np.int32)
    mask = np.zeros((n, width), dtype=bool)
    neighbors[:, 0] = np.arange(n)
    mask[:, 0] = True
    neighbors[src[keep], 1 + rank[keep]] = dst[keep]
    mask[src[keep], 1 + rank[keep]] = True
    inv_sqrt = 1.0 / np.sqrt(deg + 1.0)
    norm = (inv_sqrt[:, None] * inv_sqrt[neighbors] * mask).astype(np.float32)
    return neighbors, mask, norm


def planted_graph(name: str, seed: int, p_intra: float = 0.9) -> dict:
    """The whole planted stand-in ``name`` as host arrays."""
    n, m, d, c = PLANTED[name]
    rng = np.random.default_rng(np.random.SeedSequence([_name_key(name), seed]))
    labels = rng.integers(0, c, size=n).astype(np.int64)
    edges = _planted_edges(rng, labels, m, p_intra)
    feats = _tfidf_features(rng, labels, d)
    train, val, test = _standard_split(rng, labels)
    neighbors, mask, norm = padded_rows(n, edges, None)
    return {"features": feats, "neighbors": neighbors, "mask": mask, "norm": norm,
            "labels": labels.astype(np.int32), "train_mask": train, "val_mask": val,
            "test_mask": test, "num_classes": c}


def halo(neighbors, mask, core, hops):
    reach = np.zeros(neighbors.shape[0], dtype=bool)
    reach[core] = True
    is_core = reach.copy()
    for _ in range(hops):
        sel = np.flatnonzero(reach)
        nxt = reach.copy()
        nxt[neighbors[sel][mask[sel]]] = True
        reach = nxt
    nodes = np.flatnonzero(reach)
    return nodes, is_core[nodes]


def halo_chunks(g: dict, chunks: int, hops: int) -> dict:
    """Contiguous index chunks of ``g``, each with its ``hops``-hop halo,
    as stacked ``(chunks, n_pad, ...)`` arrays plus ``core_mask``. A chunk's
    rows are its nodes in ascending order; an edge to a node outside the
    chunk is masked out in place (the slot keeps its position). Rows past a
    chunk's size are isolated non-nodes."""
    n = g["features"].shape[0]
    parts = np.array_split(np.arange(n), chunks)
    specs = [halo(g["neighbors"], g["mask"], p, hops) for p in parts]
    n_pad = max(len(nodes) for nodes, _ in specs)
    out = {k: [] for k in FIELDS + ("core_mask",)}
    for nodes, core in specs:
        k = len(nodes)
        remap = -np.ones(n, dtype=np.int64)
        remap[nodes] = np.arange(k)
        local = remap[g["neighbors"][nodes]]
        keep = g["mask"][nodes] & (local >= 0)
        local = np.where(keep, local, 0)
        deg = keep.sum(axis=1).astype(np.float64)
        inv = np.where(deg > 0, 1.0 / np.sqrt(np.maximum(deg, 1.0)), 0.0)
        norm = inv[:, None] * inv[local] * keep

        def pad(a, fill=0):
            return np.concatenate(
                [a, np.full((n_pad - k,) + a.shape[1:], fill, dtype=a.dtype)])

        out["features"].append(pad(g["features"][nodes]))
        out["neighbors"].append(pad(local.astype(np.int32)))
        out["mask"].append(pad(keep))
        out["norm"].append(pad(norm.astype(np.float32)))
        out["labels"].append(pad(g["labels"][nodes]))
        for s in ("train_mask", "val_mask", "test_mask"):
            out[s].append(pad(g[s][nodes]))
        out["node_ids"].append(pad(nodes.astype(np.int32), -1))
        out["core_mask"].append(pad(core))
    stacked = {k: np.stack(v) for k, v in out.items()}
    return stacked


# --------------------------------------------------------------- streamed --


def _streamed_block(spec: dict, block: int, topics: np.ndarray):
    """One block's (labels, edges, features, train, val, test), drawn in the
    program generator's order from the block's own rng."""
    n, c, d = spec["num_nodes"], spec["num_classes"], spec["num_features"]
    bs = spec["block_size"]
    rng = np.random.default_rng(
        np.random.SeedSequence([_name_key(spec["name"]), spec["dataset_seed"], block]))
    lo = block * bs
    nb = min(bs, n - lo)
    labels = rng.integers(0, c, size=nb).astype(np.int64)
    target = np.minimum(rng.zipf(spec["zipf_a"], size=nb), min(spec["deg_cap"], n - 1))
    src_local = np.repeat(np.arange(nb), target)
    total = int(target.sum())
    intra = rng.random(total) < spec["p_intra"]
    partners = rng.integers(0, n, size=total)
    src_labels = labels[src_local]
    for cls in range(c):
        sel = intra & (src_labels == cls)
        if not sel.any():
            continue
        members = np.flatnonzero(labels == cls) + lo
        partners[sel] = members[rng.integers(0, len(members), size=int(sel.sum()))]
    src = src_local + lo
    a, b = np.minimum(src, partners), np.maximum(src, partners)
    keep = a != b
    edges = (np.unique(np.stack([a[keep], b[keep]], axis=1), axis=0)
             if keep.any() else np.zeros((0, 2), dtype=np.int64))
    words, k_topic = 24, max(1, int(round(24 * 0.17)))
    on = topics[labels[:, None], rng.integers(0, topics.shape[1], size=(nb, k_topic))]
    off = rng.integers(0, d, size=(nb, words - k_topic))
    idx = np.concatenate([on, off], axis=1)
    vals = (rng.random((nb, words)) + 0.5).astype(np.float32)
    feats = np.zeros((nb, d), dtype=np.float32)
    feats[np.arange(nb)[:, None], idx] = vals
    row = feats.sum(axis=1, keepdims=True)
    row[row == 0] = 1.0
    feats /= row
    u = rng.random(nb)
    return labels, edges, feats, u < 0.10, (u >= 0.10) & (u < 0.15), (u >= 0.15) & (u < 0.20)


def streamed_chunks(spec: dict) -> dict:
    """The streamed graph of ``spec`` cut into ``spec["chunks"]`` contiguous
    node ranges (boundary-crossing edges dropped), stacked."""
    n, c, d = spec["num_nodes"], spec["num_classes"], spec["num_features"]
    rng = np.random.default_rng(np.random.SeedSequence(
        [_name_key(spec["name"]), spec["dataset_seed"], _TOPIC_SALT]))
    topic_size = max(4, d // (2 * c))
    topics = np.stack([rng.choice(d, size=topic_size, replace=False) for _ in range(c)])
    blocks = [_streamed_block(spec, b, topics) for b in range(-(-n // spec["block_size"]))]
    cols = [np.concatenate([blk[i] for blk in blocks]) for i in range(6)]
    labels, _, feats, train, val, test = cols
    edges = np.concatenate([blk[1] for blk in blocks])
    bounds = np.linspace(0, n, spec["chunks"] + 1).astype(np.int64)
    sizes = np.diff(bounds)
    n_pad = int(sizes.max())
    owner = np.searchsorted(bounds, edges, side="right") - 1
    inside = owner[:, 0] == owner[:, 1]
    kept = np.unique(edges[inside], axis=0)
    kept_owner = np.searchsorted(bounds, kept[:, 0], side="right") - 1
    rows = []
    for ch in range(spec["chunks"]):
        lo, hi = int(bounds[ch]), int(bounds[ch + 1])
        rows.append(padded_rows(hi - lo, kept[kept_owner == ch] - lo, spec["max_degree"]))
    width = max(r[0].shape[1] for r in rows)
    out = {k: [] for k in FIELDS + ("core_mask",)}
    for ch, (nbr, msk, nrm) in enumerate(rows):
        lo, hi = int(bounds[ch]), int(bounds[ch + 1])
        k, w = hi - lo, nbr.shape[1]

        def pad(a, fill=0, cols=0):
            a = np.pad(a, ((0, n_pad - k),) + ((0, cols),) * (a.ndim - 1),
                       constant_values=fill)
            return a

        out["features"].append(pad(feats[lo:hi]))
        out["neighbors"].append(pad(nbr, cols=width - w))
        out["mask"].append(pad(msk, cols=width - w))
        out["norm"].append(pad(nrm, cols=width - w))
        out["labels"].append(pad(labels[lo:hi].astype(np.int32)))
        out["train_mask"].append(pad(train[lo:hi]))
        out["val_mask"].append(pad(val[lo:hi]))
        out["test_mask"].append(pad(test[lo:hi]))
        out["node_ids"].append(pad(np.arange(lo, hi, dtype=np.int32), -1))
        out["core_mask"].append(pad(np.ones(k, dtype=bool)))
    return {k: np.stack(v) for k, v in out.items()}


# ------------------------------------------------------------------ build --


def _cache_path(spec: dict) -> str:
    key = hashlib.sha256(json.dumps(spec, sort_keys=True).encode()).hexdigest()[:16]
    return os.path.join(CACHE_DIR, f"{spec['name']}-{key}.npz")


def _load_or_make(spec: dict, make) -> dict:
    path = _cache_path(spec)
    if os.path.exists(path):
        with np.load(path) as z:
            return {k: z[k] for k in z.files}
    arrays = make()
    os.makedirs(CACHE_DIR, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp.npz"
    np.savez(tmp, **arrays)
    os.replace(tmp, path)
    return arrays


def build(spec: dict) -> dict:
    """The stacked chunk arrays of a workload's ``graph`` entry, with
    ``num_classes``, ``num_nodes`` and ``num_slots`` (real neighbour slots,
    self-loops included, each node counted once) for the FLOP count."""
    if spec["kind"] == "planted":
        g = planted_graph(spec["name"], spec["dataset_seed"], spec.get("p_intra", 0.9))
        stacked = halo_chunks(g, spec["chunks"], spec["halo_hops"])
        stacked["num_nodes"] = np.asarray(g["features"].shape[0])
        stacked["num_slots"] = np.asarray(int(g["mask"].sum()))
    elif spec["kind"] == "streamed":
        stacked = dict(_load_or_make(spec, lambda: streamed_chunks(spec)))
        stacked["num_nodes"] = np.asarray(int(stacked["core_mask"].sum()))
        stacked["num_slots"] = np.asarray(int(stacked["mask"].sum()))
        g = {"num_classes": spec["num_classes"]}
    else:
        raise ValueError(f"unknown graph kind {spec['kind']!r}")
    stacked["num_classes"] = np.asarray(g["num_classes"])
    return stacked
