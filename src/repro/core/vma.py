"""Varying-manual-axes (vma) helpers for ``shard_map`` bodies.

Inside ``shard_map``, `lax.scan` requires carry input/output types to agree
on which mesh axes they vary over, and ``lax.switch`` requires the same of
its branches' outputs. Freshly-built values (``jnp.zeros(...)``) are
unvarying; if a scan body or a sibling branch mixes in varying operands the
types disagree and tracing fails. ``match_vma`` pre-casts a pytree to vary
over the union of the reference operands' axes (plus any extras), and is a
no-op where that union is empty (outside shard_map).
"""

from __future__ import annotations

import jax
from jax import lax


def vma_of(*refs) -> frozenset[str]:
    """Union of the varying-manual-axes sets across every leaf of ``refs``."""
    axes: frozenset[str] = frozenset()
    for r in refs:
        for leaf in jax.tree_util.tree_leaves(r):
            axes = axes | jax.typeof(leaf).vma
    return axes


def match_vma(init, *refs, extra: tuple[str, ...] = ()):
    """Cast every leaf of ``init`` to vary over vma(refs) ∪ extra."""
    want = vma_of(*refs) | frozenset(extra)
    if not want:
        return init

    def fix(a):
        need = tuple(sorted(want - jax.typeof(a).vma))
        return lax.pcast(a, need, to="varying") if need else a

    return jax.tree_util.tree_map(fix, init)
