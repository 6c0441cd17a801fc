"""Model FLOPs of one training step, from a configuration and a graph's
counts.

Counted: dense matrix products (a multiply-add is 2 FLOPs) and the
aggregation over real neighbour slots (the masked-in slots, self-loops
included), forward and backward. Each graph node and each real slot is
counted once: halo copies of a node in several chunks, padding rows and
padding slots are recomputation and waste, and are not counted, so a change
that removes them raises the utilisation and cannot inflate it.
Elementwise work (activations, dropout, softmax) is not counted.

Backward: every product's weight gradient, plus its input gradient where an
earlier layer holds parameters (the input features need none); the
aggregation's input gradient always (it feeds back to the layer's own
weights), and for GAT also the gradient of the attention weights.
"""

from __future__ import annotations


def train_step_flops(cfg: dict, num_nodes: int, num_slots: int, in_dim: int,
                     num_classes: int) -> float:
    n, s = float(num_nodes), float(num_slots)
    d = in_dim
    total = 0.0
    seen_params = False
    for layer in cfg["layers"]:
        kind = layer["kind"]
        if kind == "gcn":
            out = num_classes if layer["out"] == "classes" else layer["out"]
            mm = 2 * n * d * out
            agg = 2 * s * out
            total += mm + agg  # forward
            total += mm + (mm if seen_params else 0.0) + agg  # backward
            d, seen_params = out, True
        elif kind == "gat":
            heads = layer["heads"]
            out = num_classes if layer["out"] == "classes" else layer["out"]
            mm = 2 * n * d * heads * out
            att = 2 * 2 * n * heads * out  # a_src . Wh and a_dst . Wh
            agg = 2 * s * heads * out
            total += mm + att + agg
            total += mm + (mm if seen_params else 0.0) + 2 * att + 2 * agg
            d = heads * out if layer["concat"] else out
            seen_params = True
    return total
