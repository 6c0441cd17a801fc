"""Model FLOP utilisation of a training cell: the step's model FLOPs
(``flops.train_step_flops``: each node and real neighbour slot once) times
the steps of the untraced window, over the window's seconds, over the chips'
bf16 peak (``peaks.py``)."""

import peaks


def read(ctx):
    if ctx.get("kind") != "train":
        return None
    win = ctx["window"]
    peak = peaks.peaks(ctx["device_kind"])["bf16_flops"] * ctx["chips"]
    return 100.0 * ctx["flops_per_step"] * win["steps"] / win["seconds"] / peak
