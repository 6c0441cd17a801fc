"""Three-term roofline from compiled dry-run artifacts (no real hardware).

Terms (seconds, per step, per chip — ``cost_analysis()``/HLO are per-device
under SPMD, verified empirically):

    compute    = device_FLOPs / peak_FLOPs
    memory     = device_HLO_bytes / HBM_bw
    collective = device_collective_bytes / (links × link_bw)

``collective_bytes`` is not in cost_analysis; we parse the optimized HLO and
sum the *output* shapes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute (a standard proxy for bytes-on-wire; an
all-reduce moves ~2× its size ring-wise — we report the raw sum plus a
per-op-type breakdown so the dominant collective is visible).
"""

from __future__ import annotations

import dataclasses
import re

@dataclasses.dataclass(frozen=True)
class HW:
    """Published peaks of one chip."""

    peak_flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # bytes/s per chip
    ici_bw: float  # bytes/s per link
    ici_links: int  # usable links/chip on a 2D torus (2 axes × 2 dirs)


# Keyed by ``jax.Device.device_kind``. TPU v5e: Google Cloud documentation,
# "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s ICI over 4 links).
PEAKS = {
    "TPU v5 lite": HW(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9, ici_links=4),
}


def peaks_for(device_kind: str) -> HW:
    """The peaks of ``device_kind``; a kind not in ``PEAKS`` is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: {sorted(PEAKS)}"
        ) from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
# matches e.g.: "  %x = bf16[8,128]{1,0} all-gather(...)" and tuple results
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[\w\[\]{},: ]+?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
    re.M,
)


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-op-type output bytes of every collective in the HLO (per device).
    '-start' ops counted, '-done' skipped (same tensor)."""
    out: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        type_str, op = m.group(1), m.group(2)
        line = m.group(0)
        if "-done(" in line:
            continue
        out[op] += _shape_bytes(type_str)
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def model_flops(cfg, shape, *, training: bool) -> float:
    """Analytic MODEL_FLOPS: 6·N_active·D for training, 2·N_active·D for a
    forward/serve step (D = tokens processed in the step)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch  # decode: one token per sequence
    return 2.0 * n_active * tokens


def roofline_report(
    *,
    device_flops: float,
    device_bytes: float,
    device_collective: dict[str, int],
    chips: int,
    model_flops_global: float,
    hw: HW,
) -> dict:
    compute_s = device_flops / hw.peak_flops
    memory_s = device_bytes / hw.hbm_bw
    coll_s = device_collective["total"] / (hw.ici_links * hw.ici_bw)
    terms = {"compute_s": compute_s, "memory_s": memory_s, "collective_s": coll_s}
    dominant = max(terms, key=terms.get)
    useful = model_flops_global / max(device_flops * chips, 1.0)
    return {
        **terms,
        "dominant": dominant,
        "bound_s": max(terms.values()),
        "model_flops_global": model_flops_global,
        "hlo_flops_global": device_flops * chips,
        "useful_flops_ratio": useful,
        "collective_breakdown": {
            k: v for k, v in device_collective.items() if k != "total" and v
        },
    }
