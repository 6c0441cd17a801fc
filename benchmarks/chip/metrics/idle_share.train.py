"""Device idle share of a training cell's traced window, averaged over the
cell's chips: 1 - (union of the device's op intervals / window)."""


def read(ctx):
    if ctx.get("kind") != "train" or "trace" not in ctx:
        return None
    devs = list(ctx["trace"]["shares"]["devices"].values())
    if not devs:
        return None
    return 100.0 * sum(d["idle_share"] for d in devs) / len(devs)
