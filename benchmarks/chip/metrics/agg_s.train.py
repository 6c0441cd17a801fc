"""Device self-seconds per step of the aggregation in a training cell's
traced window, averaged over the cell's chips: the ops whose ``op_name``
path holds the program's ``gnn.agg`` scope, forward and backward
(``scopes.py``; read from ``ctx["trace"]["scopes"]``)."""

import scopes


def read(ctx):
    reduced = (ctx.get("trace") or {}).get("scopes")
    if ctx.get("kind") != "train" or not reduced:
        return None
    return scopes.agg_s(reduced["scopes"])
