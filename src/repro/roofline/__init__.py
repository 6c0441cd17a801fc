from repro.roofline.analysis import (
    HW,
    PEAKS,
    collective_bytes,
    peaks_for,
    roofline_report,
    model_flops,
)
from repro.roofline.stage_report import (
    layout_slots,
    live_slots,
    sparse_stage_report,
    stage_report,
)

__all__ = [
    "HW",
    "PEAKS",
    "peaks_for",
    "collective_bytes",
    "roofline_report",
    "model_flops",
    "stage_report",
    "sparse_stage_report",
    "layout_slots",
    "live_slots",
]
