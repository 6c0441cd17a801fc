"""Peak device memory of a training cell as a share of the fullest chip's
limit, read after the windows: the larger of the runtime's
``peak_bytes_in_use`` and the compiled step's bytes by the compiler's memory
analysis (arguments, outputs, temporaries, code), over ``bytes_limit``. The
TPU runtime's counter leaves out a program's temporaries, which hold most of
a wide gather's bytes."""


def read(ctx):
    mem = ctx.get("memory") or {}
    if ctx.get("kind") != "train" or not mem.get("bytes_limit"):
        return None
    return 100.0 * mem["peak_bytes"] / mem["bytes_limit"]
