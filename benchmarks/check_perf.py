"""CI perf-regression gate over the fig3 engine × schedule table.

Compares a freshly produced ``BENCH_fig3.json`` (``python -m benchmarks.run
--only fig3 --json-out DIR``) against the committed baseline
(``benchmarks/BENCH_fig3.json``) and exits non-zero if the compiled engine
regressed:

  * **speed** — by default each compiled row's step time is NORMALIZED by the
    same run's host fill-drain step time at the same chunk count, so the
    gate compares machine-independent ratios: a compiled/host ratio more
    than ``--threshold`` (default 1.20, i.e. >20%) above the baseline's
    ratio fails. ``--absolute`` compares raw seconds instead (only
    meaningful when baseline and current ran on identical hardware). Row
    step times are per-epoch MEDIANS (fig3 writes them that way): on
    shared CI-class hosts a few scheduler hiccups inflate a mean 2-3x,
    which is noise, not regression;
  * **coverage** — every compiled row present in the baseline must exist in
    the current table (a silently vanished row is a regression too);
  * **memory** — the scheduled executor's 1F1B peak live activations must
    stay strictly below the fill-drain compiled accounting at every chunk
    count >= 4 (the schedule-aware engine's headline memory invariant; this
    check is deterministic, not timing-based);
  * **partition** — the profiled (cost-model) partitioner's compiled step
    time on the deliberately imbalanced GCN stack must beat the
    layer-count-uniform split's in the same run (``partition/*`` rows; the
    comparison is run-internal, like the zero-bubble gate, so machine speed
    cancels). Missing or zero host fill-drain normalizer rows fail with a
    named-row error instead of silently shrinking the comparison set;
  * **sparse** — the pallas and padded backends' compiled steps on the
    power-law fixture (``sparse/{padded|bucketed}/chunksC`` rows; both
    aggregate the GCN over the degree buckets) must both be present and
    report ``updates_match``: fig3 asserts each measured config's one-step
    update against a host fill-drain dense reference, which never buckets,
    at oracle tolerance, so a bucketed path that computes something else
    fails here, not in prod. The layout's speed against the plain padded
    layout is the kernels gate's (``check_kernels``);
  * **scale** — the streamed-graph growth rows (``scale/nN/chunksC``, from
    fig3's ``_scale_bench``): every row's one-step update must have matched
    the host fill-drain oracle in the same run that was timed
    (``updates_match``), and the run-internal growth ratio
    step(n)/step(n_min) must stay within ``--threshold`` of the baseline's
    ratio — the sizes are stepped interleaved, so machine speed cancels and
    the ratio isolates how step time grows with the graph;
  * **overlap** — the double-buffered wire rows (``overlap/*`` from fig3's
    ``_overlap_bench``): both modes' one-step updates must have matched the
    host fill-drain oracle in the same run (``updates_match`` — the
    retiming is bit-identical dataflow), and the speed rule is
    PLATFORM-CONDITIONAL on the row's traced ``overlap_fraction``. When the
    profiler shows the runtime actually hid collectives under same-device
    compute (fraction > 0.05), the double-buffered step must beat/match the
    serialized step within ``--threshold``. When the fraction is ~0 — CI's
    forced-host CPU rings are lockstep single-threaded executors where no
    schedule can hide a collective, and wire latency 2 adds ticks by
    construction — the gate bounds the retimed program's per-TICK cost
    instead (``step_s/num_ticks`` double-buffer <= serialized), i.e. the
    step-time cost must stay below the statically-accounted tick inflation;
  * **auto** — the ``--auto`` planner rows (``auto/pick`` + ``auto/hand/*``
    from fig3's ``_auto_bench``): the pick's measured step must be within
    ``--threshold`` of the BEST measured hand-picked config in the same
    interleaved run (``auto-pick``, run-internal so machine speed cancels),
    and its predicted step time must stay within ``--auto-pred-ratio`` of
    the measurement in either direction (``auto-prediction`` — loose, since
    forced-host per-tick dispatch is unmodeled, but it catches a broken
    cost model). Both rules fail by name;
  * **zero-bubble** — at every chunk count >= 4 the compiled zb-h1 row must
    beat or match the same run's compiled 1F1B step time (within the same
    ``--threshold`` slack the speed gate uses), its bubble fraction must sit
    strictly below 1F1B's, and its peak-live accounting must not exceed
    1F1B's (the last two are deterministic). zb-h1's step-time win comes
    from filling the drain bubble with deferred weight-grad (W) work, which
    needs ticks to actually run concurrently — so produce the table under
    forced host devices (the CI gate uses 4; see below), not on the serial
    lane substrate where a drained bubble saves nothing.

The gate also covers the **serving** table (``BENCH_serve.json``, produced
by ``repro.launch.serve_gnn --json-out``): pass ``--serving-current`` to
check it against the committed ``benchmarks/BENCH_serve.json``. Every
baseline serving row must be present (fail-by-name, like the fig3 coverage
rule), report a positive achieved throughput, and keep its p99 latency —
normalized by the same run's warm single-batch eval call time, so machine
speed cancels exactly like the host-normalized fig3 ratios — within
``--serving-threshold`` of the baseline's normalized p99.

And the **kernel microbench** table (``BENCH_kernels.json``, produced by
``benchmarks.run --only kernels --json-out``): pass ``--kernels-current``
to check the padded-vs-degree-bucketed aggregation op rows — coverage,
output agreement, a strict run-internal bucketed win, and the
bucketed/padded time ratio vs the committed baseline (see
``check_kernels``).

Intentional regressions (e.g. trading speed for a feature) are overridden by
applying the ``perf-regression-ok`` label to the PR — the CI job skips the
gate when the label is present — and committing a refreshed baseline.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \
        python -m benchmarks.run --fast --only fig3 --json-out /tmp/bench
    python -m benchmarks.check_perf --current /tmp/bench/BENCH_fig3.json

    XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \
        python -m repro.launch.serve_gnn --qps 50 --duration 5 --json-out /tmp/serve
    python -m benchmarks.check_perf --serving-current /tmp/serve/BENCH_serve.json
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

DEFAULT_BASELINE = Path(__file__).resolve().parent / "BENCH_fig3.json"
DEFAULT_SERVING_BASELINE = Path(__file__).resolve().parent / "BENCH_serve.json"
DEFAULT_KERNELS_BASELINE = Path(__file__).resolve().parent / "BENCH_kernels.json"


def _chunks_of(key: str) -> int:
    return int(key.rsplit("chunks", 1)[1])


def normalized_ratios(rows: dict) -> tuple[dict[str, float], list[str]]:
    """compiled-row step time / same-run host fill-drain step time.

    Returns (ratios, problems): a compiled row whose host fill-drain
    normalizer is missing or has a non-positive step time is reported in
    ``problems`` by NAME — it must become a gate failure, not a silent drop
    (a table with a broken normalizer used to shrink the comparison set
    quietly; a key missing from the BASELINE side was never reported at
    all, and a zero step time would otherwise be a division crash or an
    infinite ratio depending on which side it landed)."""
    out: dict[str, float] = {}
    problems: list[str] = []
    for key, row in sorted(rows.items()):
        if not key.startswith("compiled/"):
            continue
        host_key = f"host/fill_drain/chunks{_chunks_of(key)}"
        host = rows.get(host_key)
        if host is None:
            problems.append(f"{key}: normalizer row {host_key} is missing")
        elif not host["step_s"] > 0:
            problems.append(
                f"{key}: normalizer row {host_key} has non-positive "
                f"step_s {host['step_s']!r}"
            )
        else:
            out[key] = row["step_s"] / host["step_s"]
    return out, problems


def check(baseline: dict, current: dict, *, threshold: float, absolute: bool,
          auto_pred_ratio: float = 25.0) -> list[str]:
    failures: list[str] = []
    b_rows, c_rows = baseline["rows"], current["rows"]

    for key in sorted(b_rows):
        if key.startswith(
            ("compiled/", "partition/", "sparse/", "scale/", "overlap/", "auto/")
        ) and key not in c_rows:
            failures.append(f"coverage: baseline row {key} missing from current run")

    if absolute:
        pairs = {
            k: (b_rows[k]["step_s"], c_rows[k]["step_s"])
            for k in b_rows
            if k.startswith("compiled/") and k in c_rows
        }
    else:
        nb, b_problems = normalized_ratios(b_rows)
        nc, c_problems = normalized_ratios(c_rows)
        failures.extend(f"normalizer(baseline): {p}" for p in b_problems)
        failures.extend(f"normalizer(current): {p}" for p in c_problems)
        pairs = {k: (nb[k], nc[k]) for k in nb if k in nc}
        # every baseline comparison must still be computable: a current run
        # missing the host fill-drain normalizer (or the compiled row) for a
        # baseline key would otherwise shrink the comparison set silently —
        # in the limit to zero pairs, turning the gate into a no-op pass
        for k in sorted(set(nb) - set(nc)):
            failures.append(
                f"coverage: cannot compare {k} — its row or its host "
                f"fill_drain normalizer is missing from the current run"
            )
    if not pairs:
        failures.append("coverage: no comparable compiled rows between baseline and current")

    unit = "s" if absolute else "x host"
    for key in sorted(pairs):
        base, cur = pairs[key]
        status = "ok"
        if cur > base * threshold:
            status = f"REGRESSED >{(threshold - 1):.0%}"
            failures.append(
                f"perf: {key} {cur:.4f}{unit} vs baseline {base:.4f}{unit} "
                f"(allowed {base * threshold:.4f})"
            )
        print(f"  {key:40s} baseline {base:8.4f}{unit}  current {cur:8.4f}{unit}  {status}")

    # memory invariant: scheduled 1F1B strictly below fill-drain accounting
    for key, row in sorted(c_rows.items()):
        if not key.startswith("compiled/1f1b/"):
            continue
        chunks = _chunks_of(key)
        if chunks < 4:
            continue
        fd = c_rows.get(f"compiled/fill_drain/chunks{chunks}")
        peak = row.get("peak_live")
        fd_peak = fd and fd.get("peak_live_accounted")
        if peak is None or fd_peak is None:
            failures.append(f"memory: {key} peak-live accounting missing")
        elif not peak < fd_peak:
            failures.append(
                f"memory: {key} peak_live {peak} not strictly below "
                f"fill-drain accounting {fd_peak}"
            )

    # zero-bubble invariants: at chunks >= 4 compiled zb-h1 must beat or
    # match compiled 1F1B's step time (same run, same threshold slack as the
    # speed gate), undercut its bubble strictly, and not exceed its
    # peak-live accounting
    for key, row in sorted(c_rows.items()):
        if not key.startswith("compiled/zb-h1/"):
            continue
        chunks = _chunks_of(key)
        if chunks < 4:
            continue
        ob = c_rows.get(f"compiled/1f1b/chunks{chunks}")
        if ob is None:
            failures.append(f"zero-bubble: {key} has no compiled 1f1b row to compare")
            continue
        if row["step_s"] > ob["step_s"] * threshold:
            failures.append(
                f"zero-bubble: {key} step {row['step_s']:.4f}s does not beat/"
                f"match 1f1b {ob['step_s']:.4f}s (allowed "
                f"{ob['step_s'] * threshold:.4f})"
            )
        if not row["bubble"] < ob["bubble"]:
            failures.append(
                f"zero-bubble: {key} bubble {row['bubble']:.3f} not strictly "
                f"below 1f1b's {ob['bubble']:.3f}"
            )
        peak, ob_peak = row.get("peak_live"), ob.get("peak_live")
        if peak is None or ob_peak is None:
            failures.append(f"zero-bubble: {key} peak-live accounting missing")
        elif peak > ob_peak:
            failures.append(
                f"zero-bubble: {key} peak_live {peak} exceeds 1f1b's {ob_peak}"
            )

    # partition gate: on the deliberately imbalanced stack the profiled
    # partitioner's measured compiled step must beat the layer-count-uniform
    # split (same run, deterministic comparison — the partitioner's whole
    # claim is that cost-aware boundaries shorten the slowest stage's tick)
    for key, row in sorted(c_rows.items()):
        if not key.startswith("partition/profiled/"):
            continue
        uni = c_rows.get(f"partition/uniform/chunks{_chunks_of(key)}")
        if uni is None:
            failures.append(f"partition: {key} has no uniform row to compare")
            continue
        if not row["step_s"] < uni["step_s"]:
            failures.append(
                f"partition: {key} step {row['step_s']:.4f}s does not beat "
                f"the uniform split's {uni['step_s']:.4f}s "
                f"(balance {row.get('balance')} vs {uni.get('balance')})"
            )

    # sparse gate: both measured configs' one-step updates must have
    # matched the host fill-drain dense reference at oracle tolerance (fig3
    # computes updates_match in the SAME run it times, so speed can never
    # be bought with wrong math unnoticed). No step race: both backends
    # bucket this fixture, so the rows run one program on the CPU; the
    # kernels gate times the bucketed op against the plain padded one
    for key, row in sorted(c_rows.items()):
        if not key.startswith("sparse/bucketed/"):
            continue
        pad = c_rows.get(f"sparse/padded/chunks{_chunks_of(key)}")
        if pad is None:
            failures.append(f"sparse: {key} has no padded row to compare")
            continue
        for name, r in (("bucketed", row), ("padded", pad)):
            if not r.get("updates_match"):
                failures.append(
                    f"sparse: {key.rsplit('/', 2)[0]}/{name} update diverged from "
                    f"the host fill-drain reference "
                    f"(max_update_diff={r.get('max_update_diff')!r})"
                )

    # scale gate: the streamed-graph growth rows (``scale/nN/chunksC``).
    # Every current row's one-step update must have matched the host
    # fill-drain oracle in the SAME run fig3 timed (updates_match), and the
    # run-internal growth ratio step(n)/step(n_min) must stay within
    # ``threshold`` of the baseline's same ratio — fig3 steps all sizes
    # interleaved, so machine speed cancels out of the ratio entirely and
    # what remains is genuinely how step time grows with the graph
    c_scale = {row["nodes"]: (key, row)
               for key, row in c_rows.items() if key.startswith("scale/")}
    b_scale = {row["nodes"]: (key, row)
               for key, row in b_rows.items() if key.startswith("scale/")}
    for n, (key, row) in sorted(c_scale.items()):
        if not row.get("updates_match"):
            failures.append(
                f"scale: {key} update diverged from the host fill-drain "
                f"oracle (max_update_diff={row.get('max_update_diff')!r})"
            )
    if b_scale and c_scale:
        bmin, cmin = min(b_scale), min(c_scale)
        b0, c0 = b_scale[bmin][1]["step_s"], c_scale[cmin][1]["step_s"]
        if not (b0 > 0 and c0 > 0):
            failures.append(
                f"scale: non-positive anchor step_s (baseline n{bmin}: {b0!r}, "
                f"current n{cmin}: {c0!r})"
            )
        else:
            for n in sorted((set(b_scale) & set(c_scale)) - {bmin, cmin}):
                base = b_scale[n][1]["step_s"] / b0
                cur = c_scale[n][1]["step_s"] / c0
                status = "ok"
                if cur > base * threshold:
                    status = f"REGRESSED >{(threshold - 1):.0%}"
                    failures.append(
                        f"scale: {c_scale[n][0]} growth ratio {cur:.3f}x vs "
                        f"baseline {base:.3f}x (allowed {base * threshold:.3f})"
                    )
                print(f"  {c_scale[n][0]:40s} baseline {base:8.3f}x-min "
                      f"current {cur:8.3f}x-min  {status}")

    # auto gate: the ``--auto`` planner rows from fig3's ``_auto_bench``.
    # Two named rules:
    #   * auto-pick — the planner's pick, measured interleaved with the
    #     hand-picked configs in the same run (machine speed cancels), must
    #     be within ``threshold`` of the BEST measured hand-picked config: a
    #     planner that picks badly is a regression even when every engine
    #     got faster;
    #   * auto-prediction — the pick's predicted step time must stay within
    #     ``auto_pred_ratio`` of its measurement (either direction). The
    #     bound is deliberately loose: on forced-host CPU the unmodeled
    #     per-tick dispatch dominates absolute step time (the partition
    #     rows show the same gap), but a prediction off by the full ratio
    #     cap means the cost model broke, not that the machine drifted.
    pick = c_rows.get("auto/pick")
    hands = {k: v for k, v in c_rows.items() if k.startswith("auto/hand/")}
    if pick is None:
        if "auto/pick" in b_rows:
            failures.append("auto-pick: baseline has auto/pick but the "
                            "current run produced none")
    else:
        if not hands:
            failures.append(
                "auto-pick: auto/pick present but no auto/hand/* rows to "
                "compare the pick against"
            )
        else:
            best_key = min(hands, key=lambda k: hands[k]["step_s"])
            best = hands[best_key]["step_s"]
            status = "ok"
            if not best > 0:
                failures.append(
                    f"auto-pick: best hand row {best_key} has non-positive "
                    f"step_s {best!r}"
                )
            elif pick["step_s"] > best * threshold:
                status = "REGRESSED"
                failures.append(
                    f"auto-pick: planner pick ({pick.get('schedule')}/"
                    f"chunks{pick.get('chunks')}, {pick['step_s']:.4f}s) not "
                    f"within {threshold:.2f}x of best hand-picked {best_key} "
                    f"({best:.4f}s)"
                )
            if best > 0:
                print(f"  {'auto/pick':40s} vs best hand ({best_key}) "
                      f"{pick['step_s'] / best:8.3f}x  {status}")
        pred, meas = pick.get("predicted_step_s"), pick["step_s"]
        if not (pred and pred > 0 and meas > 0):
            failures.append(
                f"auto-prediction: auto/pick predicted_step_s {pred!r} / "
                f"step_s {meas!r} unusable"
            )
        else:
            off = max(pred / meas, meas / pred)
            status = "ok"
            if off > auto_pred_ratio:
                status = "REGRESSED"
                failures.append(
                    f"auto-prediction: predicted {pred:.4f}s vs measured "
                    f"{meas:.4f}s — off by {off:.1f}x (allowed "
                    f"{auto_pred_ratio:.1f}x)"
                )
            print(f"  {'auto/pick':40s} predicted/measured "
                  f"{pred / meas:8.3f}x  {status}")

    # overlap gate: the double-buffered wire rows (``overlap/*`` from
    # fig3's ``_overlap_bench``). Both rows must have matched the host
    # fill-drain oracle in the SAME run that was timed (updates_match).
    # The speed rule is platform-conditional, keyed on the traced
    # ``overlap_fraction`` the row carries:
    #   * fraction > 0.05 — the runtime demonstrably hid collectives under
    #     compute, so the double-buffered STEP must beat/match the
    #     serialized step within ``threshold`` (run-internal, interleaved
    #     stepping, machine speed cancels);
    #   * fraction ~0 — a lockstep single-threaded executor (CI's
    #     forced-host CPU rings) runs every collective inline on the device
    #     thread, so NO scheduling can win wall-clock and retiming to wire
    #     latency 2 adds ticks by construction. There the gate bounds the
    #     retimed program's per-TICK cost instead:
    #     step_s/num_ticks (double-buffer) <= step_s/num_ticks (serialized)
    #     — equivalently, the retimed step's slowdown must stay below its
    #     statically-accounted tick inflation. The early-posted transfers
    #     must make ticks cheaper (slack absorbs the rendezvous wait), not
    #     dearer (e.g. the extra wire buffers thrashing cache).
    for key, row in sorted(c_rows.items()):
        if not key.startswith("overlap/double-buffer/"):
            continue
        ser_key = f"overlap/serialized/chunks{_chunks_of(key)}"
        ser = c_rows.get(ser_key)
        if ser is None:
            failures.append(f"overlap: {key} has no serialized row {ser_key} to compare")
            continue
        for name, r in (("double-buffer", row), ("serialized", ser)):
            if not r.get("updates_match"):
                failures.append(
                    f"overlap: overlap/{name} update diverged from the host "
                    f"fill-drain reference "
                    f"(max_update_diff={r.get('max_update_diff')!r})"
                )
        frac = row.get("overlap_fraction")
        if frac is None:
            failures.append(f"overlap: {key} missing overlap_fraction (overlap_report)")
            continue
        if frac > 0.05:
            status = "ok"
            if row["step_s"] > ser["step_s"] * threshold:
                status = "REGRESSED"
                failures.append(
                    f"overlap: {key} step {row['step_s'] * 1e3:.2f}ms not <= "
                    f"serialized {ser['step_s'] * 1e3:.2f}ms x{threshold} "
                    f"despite traced overlap_fraction {frac:.3f}"
                )
            print(f"  {key:40s} step vs serialized "
                  f"{row['step_s'] / ser['step_s']:8.3f}x "
                  f"(overlap {frac:.3f})  {status}")
        else:
            ticks, s_ticks = row.get("num_ticks"), ser.get("num_ticks")
            if not ticks or not s_ticks:
                failures.append(
                    f"overlap: {key} tick accounting missing "
                    f"(num_ticks={ticks!r}, serialized={s_ticks!r})"
                )
                continue
            cur, base = row["step_s"] / ticks, ser["step_s"] / s_ticks
            status = "ok"
            if cur > base:
                status = "REGRESSED"
                failures.append(
                    f"overlap: {key} per-tick step {cur * 1e3:.2f}ms "
                    f"(T={ticks}) not <= serialized {base * 1e3:.2f}ms "
                    f"(T={s_ticks}) — the double-buffered tick must absorb "
                    f"its early-posted transfers"
                )
            print(f"  {key:40s} per-tick {cur * 1e3:8.3f}ms vs serialized "
                  f"{base * 1e3:8.3f}ms (overlap {frac:.3f})  {status}")
    return failures


def check_serving(baseline: dict, current: dict, *, threshold: float) -> list[str]:
    """The serving gate over ``BENCH_serve.json`` tables.

    Rules, all fail-by-name like the fig3 gates:

      * every ``serving/`` row in the baseline must exist in the current run
        (coverage), and the current run must contain at least one;
      * each current row must report a positive ``achieved_qps`` over a
        positive query count (a zero-throughput run is a broken server, not
        a latency data point);
      * p99 latency is compared as a RATIO over the same run's warm
        single-batch ``eval_call_s`` — the machine-cancelling normalizer the
        serving driver measures at warmup — and must stay within
        ``threshold`` of the baseline's ratio. Queueing makes p99 noisier
        than a step-time median, hence the separate (looser) serving
        threshold. A missing or non-positive normalizer on either side is a
        named failure, never a silent drop."""
    failures: list[str] = []
    b_rows = {k: v for k, v in baseline.get("rows", {}).items() if k.startswith("serving/")}
    c_rows = {k: v for k, v in current.get("rows", {}).items() if k.startswith("serving/")}

    for key in sorted(b_rows):
        if key not in c_rows:
            failures.append(f"serving-coverage: baseline row {key} missing from current run")
    if not c_rows:
        failures.append("serving-coverage: current run has no serving/ rows")

    def ratio(side, key, row):
        call = row.get("eval_call_s")
        if call is None or not call > 0:
            failures.append(
                f"serving-normalizer({side}): {key} eval_call_s {call!r} "
                f"missing or non-positive"
            )
            return None
        p99 = row.get("p99_s")
        if p99 is None or not p99 > 0:
            failures.append(f"serving-normalizer({side}): {key} p99_s {p99!r} unusable")
            return None
        return p99 / call

    for key in sorted(c_rows):
        row = c_rows[key]
        if not row.get("queries", 0) > 0:
            failures.append(f"serving: {key} served no queries")
        if not row.get("achieved_qps", 0) > 0:
            failures.append(f"serving: {key} achieved_qps {row.get('achieved_qps')!r} not positive")
        cur = ratio("current", key, row)
        base_row = b_rows.get(key)
        if base_row is None:
            continue  # a NEW row has no baseline ratio yet — coverage runs above
        base = ratio("baseline", key, base_row)
        if cur is None or base is None:
            continue
        status = "ok"
        if cur > base * threshold:
            status = f"REGRESSED >{(threshold - 1):.0%}"
            failures.append(
                f"serving: {key} p99/eval_call {cur:.2f}x vs baseline "
                f"{base:.2f}x (allowed {base * threshold:.2f}x)"
            )
        print(f"  {key:40s} baseline {base:8.2f}x  current {cur:8.2f}x  {status}")
    return failures


def check_kernels(baseline: dict, current: dict, *, threshold: float) -> list[str]:
    """The kernel-microbench gate over ``BENCH_kernels.json`` tables.

    Covers the padded-vs-degree-bucketed aggregation op rows
    (``kernels/{spmm|gat}/{padded|bucketed}``, produced by
    ``benchmarks.kernels_bench`` at the skewed-fixture shapes). Rules:

      * coverage — every ``kernels/`` row in the baseline must exist in the
        current run, which must contain at least one (fail-by-name);
      * correctness — each bucketed row must report ``outputs_match``: the
        bench compares the bucketed op's output against the padded op's on
        the same graph at float tolerance in the same run it times;
      * sparse win — per op family the bucketed op's time must be STRICTLY
        below the padded op's in the same run (run-internal, so machine
        speed and interpret-vs-compiled mode cancel);
      * ratio — the bucketed/padded time ratio must stay within
        ``threshold`` of the baseline's ratio (the machine-cancelling
        regression check: a bucketed path that silently lost half its win
        still "beats padded" but fails here)."""
    failures: list[str] = []
    b_rows = {k: v for k, v in baseline.get("rows", {}).items() if k.startswith("kernels/")}
    c_rows = {k: v for k, v in current.get("rows", {}).items() if k.startswith("kernels/")}

    for key in sorted(b_rows):
        if key not in c_rows:
            failures.append(f"kernels-coverage: baseline row {key} missing from current run")
    if not c_rows:
        failures.append("kernels-coverage: current run has no kernels/ rows")

    def ratio(rows, which):
        for key, row in sorted(rows.items()):
            if not key.endswith("/bucketed"):
                continue
            pad = rows.get(key.rsplit("/", 1)[0] + "/padded")
            if pad is None:
                failures.append(f"kernels({which}): {key} has no padded row to compare")
                continue
            if not pad["t_us"] > 0:
                failures.append(
                    f"kernels({which}): {key} padded normalizer t_us "
                    f"{pad['t_us']!r} not positive"
                )
                continue
            yield key, row, row["t_us"] / pad["t_us"]

    c_ratios = {}
    for key, row, r in ratio(c_rows, "current"):
        c_ratios[key] = r
        if not row.get("outputs_match"):
            failures.append(
                f"kernels: {key} output diverged from the padded op's "
                f"(max_abs_diff={row.get('max_abs_diff')!r})"
            )
        if not r < 1.0:
            failures.append(
                f"kernels: {key} at {r:.2f}x the padded op's time — the "
                f"bucketed layout must win strictly at the skewed shapes"
            )
    for key, _, base in ratio(b_rows, "baseline"):
        cur = c_ratios.get(key)
        if cur is None:
            continue  # coverage failure already recorded above
        status = "ok"
        if cur > base * threshold:
            status = f"REGRESSED >{(threshold - 1):.0%}"
            failures.append(
                f"kernels: {key} bucketed/padded ratio {cur:.3f} vs baseline "
                f"{base:.3f} (allowed {base * threshold:.3f})"
            )
        print(f"  {key:40s} baseline {base:8.3f}x  current {cur:8.3f}x  {status}")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--baseline", default=str(DEFAULT_BASELINE))
    ap.add_argument("--current", default=None,
                    help="fresh BENCH_fig3.json (required unless --serving-current is given)")
    ap.add_argument("--threshold", type=float, default=1.20,
                    help="max allowed current/baseline slowdown factor (1.20 = +20%%)")
    ap.add_argument("--absolute", action="store_true",
                    help="compare raw seconds instead of host-normalized ratios")
    ap.add_argument("--auto-pred-ratio", type=float, default=25.0,
                    help="max allowed predicted/measured step-time ratio (either "
                         "direction) for the auto/pick row — loose on purpose: "
                         "forced-host CPU dispatch overhead is unmodeled, but a "
                         "prediction this far off means the cost model broke")
    ap.add_argument("--serving-baseline", default=str(DEFAULT_SERVING_BASELINE))
    ap.add_argument("--serving-current", default=None,
                    help="fresh BENCH_serve.json from repro.launch.serve_gnn --json-out")
    ap.add_argument("--serving-threshold", type=float, default=2.0,
                    help="max allowed normalized-p99 slowdown factor for serving rows "
                         "(looser than --threshold: open-loop queueing tails are noisy)")
    ap.add_argument("--kernels-baseline", default=str(DEFAULT_KERNELS_BASELINE))
    ap.add_argument("--kernels-current", default=None,
                    help="fresh BENCH_kernels.json from benchmarks.run --only kernels --json-out")
    ap.add_argument("--kernels-threshold", type=float, default=1.30,
                    help="max allowed bucketed/padded ratio growth for kernel rows "
                         "(microbench medians are noisier than pipeline steps)")
    args = ap.parse_args()
    if args.current is None and args.serving_current is None and args.kernels_current is None:
        ap.error("provide --current, --serving-current and/or --kernels-current")

    failures = []
    if args.current is not None:
        with open(args.baseline) as f:
            baseline = json.load(f)
        with open(args.current) as f:
            current = json.load(f)
        print(f"perf gate: baseline={args.baseline} threshold={args.threshold:.2f} "
              f"mode={'absolute' if args.absolute else 'host-normalized'}")
        failures += check(baseline, current, threshold=args.threshold,
                          absolute=args.absolute,
                          auto_pred_ratio=args.auto_pred_ratio)
    if args.serving_current is not None:
        with open(args.serving_baseline) as f:
            serving_baseline = json.load(f)
        with open(args.serving_current) as f:
            serving_current = json.load(f)
        print(f"serving gate: baseline={args.serving_baseline} "
              f"threshold={args.serving_threshold:.2f} (p99 / warm eval call)")
        failures += check_serving(
            serving_baseline, serving_current, threshold=args.serving_threshold
        )
    if args.kernels_current is not None:
        with open(args.kernels_baseline) as f:
            kernels_baseline = json.load(f)
        with open(args.kernels_current) as f:
            kernels_current = json.load(f)
        print(f"kernels gate: baseline={args.kernels_baseline} "
              f"threshold={args.kernels_threshold:.2f} (bucketed / padded op time)")
        failures += check_kernels(
            kernels_baseline, kernels_current, threshold=args.kernels_threshold
        )
    if failures:
        print("\nPERF GATE FAILED:")
        for msg in failures:
            print(f"  - {msg}")
        print("(intentional? apply the 'perf-regression-ok' PR label and "
              "commit a refreshed benchmarks/BENCH_fig3.json / BENCH_serve.json "
              "/ BENCH_kernels.json)")
        return 1
    print("perf gate passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
