"""Fig 4 analogue — accuracy vs chunks: the paper's collapse + our fixes.

sequential = paper-faithful (edges dropped at chunk boundaries);
greedy     = structure-aware partitions (beyond-paper);
halo       = exact k-hop ghost nodes (beyond-paper; should match full batch).

The schedule-comparison columns rerun the halo config under 1F1B,
interleaved 1F1B and zero-bubble zb-h1 (split B/W backward): accuracy must
NOT move (per-chunk gradients are reduced in a canonical order, so every
schedule's update is bit-identical) while the bubble/peak-activation
accounting does — schedules buy speed and memory, never model quality. The
``engine=compiled`` columns rerun the same halo config through the compiled
SPMD engine under every schedule (fill-drain on the fused scan,
1F1B/interleaved/zb-h1 on the scheduled executor): same plan, same seed, so
their accuracy sitting next to the host rows is the
schedule×engine-equivalence smoke — and those rows' metrics now come from
``CompiledGNNPipeline.evaluate``, the forward-only jitted scheduled
program, so the compiled eval path is exercised (and must agree) too.
"""

from __future__ import annotations

from benchmarks.common import PipelineCLIConfig, emit
from repro.launch.train import run_gnn


def _args(dataset, epochs, *, strategy="sequential", **pipeline):
    """One fig4 cell's run_gnn namespace off the shared pipeline CLI bundle."""
    return PipelineCLIConfig(**pipeline).namespace(
        mode="gnn", dataset=dataset, strategy=strategy,
        epochs=epochs, seed=0, log_every=0,
    )


def run(*, dataset="cora", epochs=60, strategies=("sequential", "greedy", "halo")):
    rows = []
    full = run_gnn(_args(dataset, epochs))
    emit(f"fig4/{dataset}/full_batch", full["avg_epoch_s"] * 1e6,
         f"val_acc={full['val_acc']:.3f}")
    rows.append(("full", 1, full["val_acc"]))
    halo4 = None
    for strategy in strategies:
        for chunks in (2, 4):
            r = run_gnn(_args(dataset, epochs, strategy=strategy, stages=4, chunks=chunks))
            if strategy == "halo" and chunks == 4:
                halo4 = r  # fill-drain baseline, reused for the schedule rows
            emit(
                f"fig4/{dataset}/{strategy}_chunks{chunks}",
                r["avg_epoch_s"] * 1e6,
                f"val_acc={r['val_acc']:.3f};edge_cut={r['edge_cut']:.3f}",
            )
            rows.append((strategy, chunks, r["val_acc"]))
    # schedule-equivalence columns: same halo config, every schedule
    for schedule in ("fill_drain", "1f1b", "interleaved", "zb-h1", "zb-v"):
        if schedule == "fill_drain" and halo4 is not None:
            r = halo4  # identical config already trained above
        else:
            r = run_gnn(_args(
                dataset, epochs, strategy="halo",
                stages=4, chunks=4, schedule=schedule, pipe_devices=2,
            ))
        emit(
            f"fig4/{dataset}/halo_chunks4_{schedule}",
            r["avg_epoch_s"] * 1e6,
            f"val_acc={r['val_acc']:.3f};bubble={r['bubble_fraction']:.3f};"
            f"peak_live={r['peak_live_activations']}",
        )
        rows.append((f"halo/{schedule}", 4, r["val_acc"]))
    # engine-equivalence columns: same halo plan/seed on the compiled engine
    # under every schedule — fill-drain runs the fused scan, 1F1B and
    # interleaved the scheduled executor. Accuracy must sit on top of the
    # host fill-drain row for all of them (schedule- AND engine-invariance).
    # (interleaved and zb-v take their default 2-device ring)
    for schedule in ("fill_drain", "1f1b", "interleaved", "zb-h1", "zb-v"):
        r = run_gnn(_args(
            dataset, epochs, strategy="halo", engine="compiled",
            stages=4, chunks=4, schedule=schedule,
        ))
        emit(
            f"fig4/{dataset}/halo_chunks4_compiled_{schedule}",
            r["avg_epoch_s"] * 1e6,
            f"val_acc={r['val_acc']:.3f};engine=compiled;"
            f"peak_live={r['peak_live_activations']}",
        )
        rows.append((f"halo/compiled/{schedule}", 4, r["val_acc"]))
    # partition-invariance column: the SAME halo config under the profiled
    # (cost-model) balance — moving layer boundaries must not move accuracy,
    # only the per-stage cost profile (partitioning reorders work, never math)
    r = run_gnn(_args(
        dataset, epochs, strategy="halo", engine="compiled",
        stages=4, chunks=4, schedule="1f1b", partition="profiled",
    ))
    emit(
        f"fig4/{dataset}/halo_chunks4_compiled_1f1b_profiled",
        r["avg_epoch_s"] * 1e6,
        f"val_acc={r['val_acc']:.3f};engine=compiled;"
        f"balance={'-'.join(map(str, r['balance']))}",
    )
    rows.append(("halo/compiled/1f1b/profiled", 4, r["val_acc"]))
    return rows
