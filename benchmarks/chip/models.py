"""A configuration file -> the program's model, and the benchmark's weights.

The program's model is assembled from the configuration's layer list out of
the program's public ``SeqLayer`` and layer functions (``models.gnn.
layers``), each GAT layer with its own head count. The
initial weights are the benchmark's own, drawn from the seed in one jitted
call on the device, in the pytree the program's model takes (one dict per
layer), so that the program and the reference start from the same numbers.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def layer_dims(cfg: dict, in_dim: int, num_classes: int) -> list[tuple[int, int]]:
    """(input width, output width) of every layer of the configuration."""
    dims, d = [], in_dim
    for layer in cfg["layers"]:
        out = layer.get("out")
        if out == "classes":
            out = num_classes
        if layer["kind"] == "gcn":
            dims.append((d, out))
            d = out
        elif layer["kind"] == "gat":
            dims.append((d, out))
            d = layer["heads"] * out if layer["concat"] else out
        else:
            dims.append((d, d))
    return dims


def _glorot(key, shape):
    lim = jnp.sqrt(6.0 / (shape[-2] + shape[-1]))
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


def init_params(cfg: dict, in_dim: int, num_classes: int, key) -> list:
    """The configuration's initial weights from ``key``: Glorot-uniform
    matrices and attention vectors, zero biases."""

    def make(key):
        out = []
        keys = jax.random.split(key, len(cfg["layers"]))
        for layer, (din, dout), k in zip(cfg["layers"], layer_dims(cfg, in_dim, num_classes), keys):
            if layer["kind"] == "gcn":
                out.append({"w": _glorot(k, (din, dout)), "b": jnp.zeros((dout,))})
            elif layer["kind"] == "gat":
                h = layer["heads"]
                kw, ks, kd = jax.random.split(k, 3)
                out.append({
                    "w": _glorot(kw, (h, din, dout)),
                    "a_src": _glorot(ks, (h, dout, 1))[..., 0],
                    "a_dst": _glorot(kd, (h, dout, 1))[..., 0],
                    "b": jnp.zeros((h, dout)),
                })
            else:
                out.append({})
        return out

    return jax.jit(make)(key)


def program_model(cfg: dict, in_dim: int, num_classes: int):
    """The program's ``GNNModel`` for the configuration."""
    from repro.models.gnn import layers as L
    from repro.models.gnn.net import GNNModel, SeqLayer

    backend = cfg["backend"]
    if cfg["program"] != "seq":
        raise ValueError(f"unknown program builder {cfg['program']!r}")
    seq = []
    for i, (layer, (din, dout)) in enumerate(
        zip(cfg["layers"], layer_dims(cfg, in_dim, num_classes))
    ):
        kind = layer["kind"]
        if kind == "gcn":
            seq.append(SeqLayer(
                f"gcn_{i}",
                lambda key, din=din, dout=dout: L.init_gcn(key, din, dout),
                lambda p, g, h, rng, train: L.gcn_layer(p, g, h, backend=backend),
            ))
        elif kind == "gat":
            seq.append(SeqLayer(
                f"gat_{i}",
                lambda key, din=din, dout=dout, h=layer["heads"]: L.init_gat(
                    key, din, dout, heads=h),
                lambda p, g, h, rng, train, lyr=layer: L.gat_layer(
                    p, g, h, concat=lyr["concat"], attn_dropout=lyr["attn_dropout"],
                    negative_slope=lyr["negative_slope"], rng=rng, train=train,
                    backend=backend),
            ))
        elif kind == "dropout":
            seq.append(SeqLayer(
                f"dropout_{i}", lambda key: {},
                lambda p, g, h, rng, train, r=layer["rate"]: L.dropout(h, r, rng, train),
            ))
        elif kind == "elu":
            seq.append(SeqLayer(f"elu_{i}", lambda key: {},
                                lambda p, g, h, rng, train: jax.nn.elu(h)))
        elif kind == "relu":
            seq.append(SeqLayer(f"relu_{i}", lambda key: {},
                                lambda p, g, h, rng, train: jax.nn.relu(h)))
        elif kind == "log_softmax":
            seq.append(SeqLayer(f"log_softmax_{i}", lambda key: {},
                                lambda p, g, h, rng, train: jax.nn.log_softmax(h, axis=-1)))
        else:
            raise ValueError(f"layer kind {kind!r} has no seq assembly")
    return GNNModel(layers=tuple(seq), in_dim=in_dim, out_dim=num_classes)
