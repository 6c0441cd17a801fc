"""Device self-seconds per step of the pipeline executor in a training
cell's traced window, averaged over the cell's chips: the ops whose
innermost ``pipe.*`` scope is ``pipe.exec`` or ``pipe.wire`` (stash
banking, gradient slots, zero fills, the work dispatch, ring hops;
``scopes.py``; read from ``ctx["trace"]["scopes"]``)."""

import scopes


def read(ctx):
    reduced = (ctx.get("trace") or {}).get("scopes")
    if ctx.get("kind") != "train" or not reduced:
        return None
    return scopes.exec_s(reduced["scopes"])
