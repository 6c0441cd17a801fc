"""The plain reference: a configuration's layer list written out from the
layer equations in ``jax.numpy``, one chunk at a time.

It imports nothing of the program. It reads the benchmark's chunk arrays
(``graphs.build``) and the benchmark's initial weights (``models.
init_params``), and draws its own dropout masks: the mask of layer ``i`` of
chunk ``c`` in a step with key ``k`` is ``bernoulli(split(fold_in(k, c),
L)[i], 1 - rate, shape)``, at the shape of that layer's input in the chunk's
padded layout (attention dropout: ``(rows, slots, heads)``). That is the
published per-(chunk, layer) dropout of the pipeline, so the reference and
the program drop the same units.

* GCN (Kipf & Welling eq. 2): ``H' = A_hat H W + b``, with ``A_hat`` the
  chunk's normalised neighbour slots, summed slot by slot.
* GAT (Velickovic et al. eqs. 3-4): ``e_ij = LeakyReLU(a_src . W h_i +
  a_dst . W h_j)`` over the live slots of row ``i``, ``alpha = softmax_j``,
  attention dropout on ``alpha``, ``h_i' = sum_j alpha_ij W h_j + b``,
  heads concatenated or averaged.
* Loss: the mean negative log-likelihood over the rows whose
  ``train_mask & core_mask`` is set, across all chunks.
* Adam with decoupled weight decay, as the configuration states.

Every product is exact in float32 (``passes=0``). The same code with each
product made of three bfloat16 passes, the nearest precision below the
"highest" the configurations state, is the control that ``correct`` must
reject (``control_passes``).
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

_NEG = -1e9


def _head(x):
    """Float32 ``x`` rounded to the nearest bfloat16 (ties to even), held in
    float32. Integer arithmetic on the bits, not a cast, so that no compiler
    may keep the dropped bits."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000), jnp.float32)


def _split(x):
    """``x`` as a bfloat16 head and a bfloat16 tail, both held in float32."""
    hi = _head(x)
    return hi, _head(x - hi)


def _three(op):
    """The bilinear ``op(a, b)`` with every product made of three bfloat16
    passes (head times head, head times tail, tail times head), forward and
    backward, as a TPU computes float32 products at "high" precision."""

    def passes(f, x, y):
        (xh, xl), (yh, yl) = _split(x), _split(y)
        return f(xh, yh) + f(xh, yl) + f(xl, yh)

    @jax.custom_vjp
    def prod(a, b):
        return passes(op, a, b)

    def fwd(a, b):
        return prod(a, b), (a, b)

    def bwd(res, ct):
        a, b = res
        da = passes(lambda c, y: jax.vjp(lambda a_: op(a_, y), a)[1](c)[0], ct, b)
        db = passes(lambda x, c: jax.vjp(lambda b_: op(x, b_), b)[1](c)[0], a, ct)
        return da, db

    prod.defvjp(fwd, bwd)
    return prod


def _products(passes: int):
    """``(einsum, multiply)`` whose every product of two operands is exact
    (``passes=0``) or made of three bfloat16 passes (``passes=3``)."""
    exact = functools.partial(jnp.einsum, precision="highest")
    if passes == 0:
        return exact, jnp.multiply
    return (lambda spec, a, b: _three(functools.partial(exact, spec))(a, b),
            _three(jnp.multiply))


def control_passes(cfg: dict) -> int:
    """Passes of the control: the nearest precision below the
    configuration's, "high" (three bfloat16 passes) for float32 at
    "highest"."""
    if cfg["dtype"] != "float32" or cfg["matmul_precision"] != "highest":
        raise ValueError("the control is defined for float32 at 'highest' only")
    return 3


def _dropout(x, rate, key):
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)


def _gcn(p, h, nbr, norm, ein, mul):
    hw = ein("nf,fo->no", h, p["w"])
    agg = jnp.zeros(hw.shape, hw.dtype)
    for d in range(nbr.shape[1]):
        agg = agg + mul(norm[:, d:d + 1], hw[nbr[:, d]])
    return agg + p["b"]


def _gat(layer, p, h, nbr, mask, key, train, ein, mul):
    hw = ein("nf,hfo->nho", h, p["w"])
    e_dst_row = ein("nho,ho->nh", hw, p["a_src"])  # i, the receiving row
    e_src_row = ein("nho,ho->nh", hw, p["a_dst"])  # j, the sending slot
    scores = jax.nn.leaky_relu(e_dst_row[:, None, :] + e_src_row[nbr], layer["negative_slope"])
    scores = jnp.where(mask[..., None], scores, _NEG)
    alpha = jax.nn.softmax(scores, axis=1) * mask[..., None]
    if train and layer["attn_dropout"] > 0:
        alpha = _dropout(alpha, layer["attn_dropout"], key)
    out = jnp.zeros(hw.shape, hw.dtype)
    for d in range(nbr.shape[1]):
        out = out + mul(alpha[:, d, :, None], hw[nbr[:, d]])
    out = out + p["b"]
    if layer["concat"]:
        return out.reshape(out.shape[0], -1)
    return out.mean(axis=1)


def forward(layers, params, chunk, keys, train=True, passes=0):
    """Log-probabilities of one chunk: ``chunk`` holds its ``features``,
    ``neighbors``, ``mask``, ``norm``; ``keys`` one dropout key per layer;
    ``passes`` as ``_products``."""
    ein, mul = _products(passes)
    h = chunk["features"]
    for i, (layer, p) in enumerate(zip(layers, params)):
        kind = layer["kind"]
        if kind == "dropout":
            h = _dropout(h, layer["rate"], keys[i]) if train else h
        elif kind == "gcn":
            h = _gcn(p, h, chunk["neighbors"], chunk["norm"], ein, mul)
        elif kind == "gat":
            h = _gat(layer, p, h, chunk["neighbors"], chunk["mask"], keys[i], train, ein, mul)
        elif kind == "elu":
            h = jax.nn.elu(h)
        elif kind == "relu":
            h = jax.nn.relu(h)
        elif kind == "log_softmax":
            h = jax.nn.log_softmax(h, axis=-1)
        else:
            raise ValueError(f"reference has no layer kind {kind!r}")
    return h


def _chunk_loss(layers, passes, params, chunk, keys):
    logp = forward(layers, params, chunk, keys, train=True, passes=passes)
    nll = -jnp.take_along_axis(logp, chunk["labels"][:, None], axis=-1)[:, 0]
    return jnp.sum(nll * chunk["m"])


@functools.lru_cache(maxsize=None)
def _grad_fn(layers_json: str, passes: int):
    """One jitted loss-and-gradient per layer list and passes, shared by
    every ``Reference`` of them, so that many seeds compile it once."""
    return jax.jit(jax.value_and_grad(
        functools.partial(_chunk_loss, json.loads(layers_json), passes)))


class Reference:
    """The reference for one configuration over one cell's chunk arrays;
    with ``passes=3``, the control (``control_passes``)."""

    def __init__(self, cfg, stacked, *, passes=0, chunk_ids=None):
        self.layers = cfg["layers"]
        self.opt = cfg["optimizer"]
        self.stacked = stacked
        n_chunks = stacked["features"].shape[0]
        self.chunk_ids = list(range(n_chunks)) if chunk_ids is None else list(chunk_ids)
        self.m = (stacked["train_mask"] & stacked["core_mask"]).astype(np.float32)
        self.count = float(self.m[self.chunk_ids].sum())
        self._grad = _grad_fn(json.dumps(self.layers), passes)

    def _chunk(self, c):
        s = self.stacked
        return {
            "features": jnp.asarray(s["features"][c]),
            "neighbors": jnp.asarray(s["neighbors"][c]),
            "mask": jnp.asarray(s["mask"][c]),
            "norm": jnp.asarray(s["norm"][c]),
            "labels": jnp.asarray(s["labels"][c]),
            "m": jnp.asarray(self.m[c]),
        }

    def loss_and_grads(self, params, step_key):
        """Mean loss and its gradients over the chunks, chunk by chunk."""
        total, grads = 0.0, None
        for c in self.chunk_ids:
            keys = jax.random.split(jax.random.fold_in(step_key, c), len(self.layers))
            loss, g = self._grad(params, self._chunk(c), keys)
            total = total + loss
            grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
        scale = 1.0 / max(self.count, 1.0)
        return float(total) * scale, jax.tree_util.tree_map(lambda x: x * scale, grads)

    def adam(self, params, grads, state, t):
        o = self.opt
        mu, nu = state

        def one(p, g, m, v):
            m = o["b1"] * m + (1 - o["b1"]) * g
            v = o["b2"] * v + (1 - o["b2"]) * g * g
            m_hat = m / (1 - o["b1"] ** t)
            v_hat = v / (1 - o["b2"] ** t)
            u = -o["lr"] * m_hat / (jnp.sqrt(v_hat) + o["eps"]) - o["lr"] * o["weight_decay"] * p
            return p + u, m, v

        out = jax.tree_util.tree_map(one, params, grads, mu, nu)
        leaves = jax.tree_util.tree_structure(params)
        flat = leaves.flatten_up_to(out)
        p = leaves.unflatten([x[0] for x in flat])
        m = leaves.unflatten([x[1] for x in flat])
        v = leaves.unflatten([x[2] for x in flat])
        return p, (m, v)

    def observe(self, params0, step_keys):
        """What the correctness check compares, over ``len(step_keys)``
        steps from ``params0``: each step's loss, the first step's gradient
        and the parameters after the last step."""
        if self.opt.get("weight_decay_mode", "decoupled") != "decoupled":
            raise ValueError("the reference implements decoupled weight decay only")
        params = jax.tree_util.tree_map(jnp.asarray, params0)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        state = (zeros, zeros)
        losses, first_grads = [], None
        for t, key in enumerate(step_keys, start=1):
            loss, grads = self.loss_and_grads(params, key)
            losses.append(loss)
            if first_grads is None:
                first_grads = grads
            params, state = self.adam(params, grads, state, t)
        return {"losses": losses, "grads": first_grads, "params": params}
