"""Compiled SPMD pipeline parallelism via shard_map.

This is the production-mesh generalization of the paper's technique: the
host-driven torchgpipe queue schedule becomes a single compiled program —
one `lax.scan` tick per pipeline slot, `lax.ppermute` moving activations
stage→stage over the mesh's ``stage_axis``. Three executors ship:
``spmd_pipeline`` (GPipe fill-drain, one stage per device, AD through the
scan), ``spmd_pipeline_interleaved`` (circular placement, V virtual stages
per device — the bubble shrinks by ~V; see ``repro.core.schedule``), and
``spmd_pipeline_scheduled`` (any validated ``WorkItem`` timeline — 1F1B /
interleaved 1F1B / zero-bubble zb-h1 with its split B/W backward and
deferred-weight-grad residual stash — lowered to static per-tick index
arrays, mixed fwd/bwd ticks with explicit ``jax.vjp`` backward stages and
an activation stash sized to the schedule's live window instead of S·C).
``spmd_pipeline_scheduled_eval`` is the forward-only twin (compiled
inference/eval: no vjp, no gradient buffers); every scheduled executor has
a ``_lanes`` substrate for hosts with fewer devices than the placement.

Contract (everything below happens *inside* shard_map):

  * ``stage_fn(my_in, state_mb) -> (y, state_mb')`` — this device's whole
    stage (its layers_per_stage layers). Parameters/extras are closed over;
    build them with ``make_scanned_stage`` for the homogeneous case or
    hand-roll for heterogeneous stages (e.g. zamba2's 5 mamba slots + 1
    weight-shared attention slot).
  * ``x``: any pytree whose leaves are (num_micro, ...) — this device's data,
    already microbatched. A single array is the LM case; the GNN engine sends
    a whole pytree (activations + padded subgraph + chunk id) so the graph
    travels stage→stage with the activations, and ``y`` must mirror ``x``'s
    structure. Stage 0 consumes microbatch ``t`` at tick ``t``; the last
    stage emits it at tick ``t + S - 1``.
  * ``state``: optional per-microbatch persistent state (KV/SSM caches for
    decode), leaves shaped (num_micro, ...); the pipeline slices microbatch
    ``c`` in, writes the update back, and returns the final state.

GPipe's activation re-materialization is the ``remat`` flag (jax.checkpoint
around the per-tick stage body). Gradients flow through ``ppermute``/scan —
the backward pipeline — and FSDP all-gathers inside ``stage_fn`` transpose
into gradient reduce-scatters (ZeRO-3) automatically.

Scheduled-executor tick contract (see ``LoweredTimeline`` in
``repro.core.schedule`` for the slot-routing fields): every scan tick, on
every device, in this order —

  1. bank the arriving forward wire into activation-stash slot
     ``in_fslot[t, d]`` and the arriving backward wire into cotangent slot
     ``in_bslot[t, d]`` (idle devices bank into the sacrificial slot);
  2. read the tick's stage input from ``work_fslot`` / cotangent from
     ``work_bslot`` / deferred-W residual from ``work_wslot``, run the
     phase's work fn (fwd, fused bwd, or the zb-h1 split: ``bwd_b`` emits
     the upstream cotangent + banks a residual at ``store_wslot``,
     ``bwd_w`` turns a residual into parameter grads);
  3. accumulate grads into the per-(layer, chunk) slot of ``gbuf`` (slot C
     is sacrificial), then ``ppermute`` both wires one ring hop.

Wire-parity rule (``lowered.wire_latency``): with latency 1 (serialized,
the default) each tick's outputs ride the single wire pair issued AFTER the
work and are banked at tick t+1 — the collective sits on the critical path
of every tick. With latency 2 (double-buffered; timelines must be retimed
by ``repro.core.schedule.retime_timeline`` first) each direction holds TWO
buffers of alternating parity — ``wire`` (in flight since tick t-1, banked
now) and ``pending`` (this device's previous outputs, posted onto the ring
BEFORE the tick's work runs). A tick-t output is pending at t+1 and banked
at t+2, so consecutive ticks' transfers occupy opposite buffers and the
``ppermute`` for tick t+1's arrivals overlaps tick t's compute. The lanes
substrate mirrors the same two-buffer dataflow with tuple rotation. This is
pure retiming: banked values, stash traffic and gradient order are
unchanged, so updates stay bit-identical to the serialized path.

Stash sizes are the free-list results ``n_fslots``/``n_bslots``/
``n_wslots`` — the schedule's true live windows, NOT S*C — each +1 for the
sacrificial slot. After the scan, per-chunk gradients reduce in canonical
descending-chunk order (gathered over the optional ``data_axis`` first, in
descending replica order), then ``psum`` over the stage ring — which is why
every schedule, placement, and data-parallel width produces bit-identical
updates.

Named scopes: each executor runs under ``jax.named_scope("pipe.exec")`` —
stash banking, slot picks, gradient-slot accumulation, the work dispatch and
the drain reduction — and its collectives (ring hops, the data-axis gather,
the closing psums) under ``"pipe.wire"``. The stage work a ``work_fn`` or
``stage_fn`` brings nests its own scopes inside, so a device profile reads
executor overhead as the ops whose innermost ``pipe.*`` scope is one of
these two.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax


def _wire(collective, *args, **kwargs):
    """``collective(*args, **kwargs)`` under the ``pipe.wire`` scope."""
    with jax.named_scope("pipe.wire"):
        return collective(*args, **kwargs)


@jax.named_scope("pipe.exec")
def spmd_pipeline(
    stage_fn: Callable[[Any, Any], tuple[Any, Any]],
    x: Any,
    *,
    stage_axis: str,
    num_stages: int,
    state: Any = None,
    remat: bool = False,
    scatter_dim: int | None = None,
    reduce: str = "psum",
    vma_refs: tuple = (),
):
    """Fill-drain pipeline. Returns (outputs, final_state); ``outputs`` is
    the last stage's per-microbatch output. With ``reduce="psum"`` (default)
    it is psum-broadcast across the stage axis (shaped like ``x``); with
    ``scatter_dim=d`` it is reduce-scattered along that output dim instead —
    cheaper on the wire and it leaves downstream work (LM head, loss)
    sharded over the stage axis instead of redundantly replicated.
    ``reduce="none"`` skips the collective entirely: outputs are zero on
    every stage but the last, so a caller differentiating *inside* the
    pipeline program can compute a local loss and psum only the gradients —
    keeping collectives out of the transposed path."""
    if reduce not in ("psum", "none"):
        raise ValueError(f"reduce must be 'psum' or 'none', got {reduce!r}")
    stage = lax.axis_index(stage_axis)
    is_first = stage == 0
    is_last = stage == num_stages - 1
    tree_map = jax.tree_util.tree_map
    num_micro = jax.tree_util.tree_leaves(x)[0].shape[0]

    fn = stage_fn
    if remat:
        fn = jax.checkpoint(stage_fn)

    def tick_body(body_carry, t):
        prev_in, st = body_carry
        c = t - stage  # microbatch this stage works on at tick t
        mb_idx = jnp.clip(c, 0, num_micro - 1)
        valid = (c >= 0) & (c < num_micro)

        fresh = tree_map(
            lambda a: lax.dynamic_index_in_dim(
                a, jnp.clip(t, 0, num_micro - 1), 0, keepdims=False
            ),
            x,
        )
        my_in = tree_map(lambda f, p: jnp.where(is_first, f, p), fresh, prev_in)

        st_mb = jax.tree_util.tree_map(
            lambda a: lax.dynamic_index_in_dim(a, mb_idx, 0, keepdims=False), st
        )
        y, st_mb_new = fn(my_in, st_mb)
        # fill/drain ticks compute garbage; route their state writes to the
        # sacrificial slot num_micro (slice-sized traffic per tick — a full
        # per-tick jnp.where over the buffer would read+write the whole
        # cache every tick).
        w_idx = jnp.where(valid, mb_idx, num_micro)
        st = jax.tree_util.tree_map(
            lambda a, u: lax.dynamic_update_index_in_dim(a, u, w_idx, 0),
            st,
            st_mb_new,
        )

        nxt = _wire(
            lax.ppermute, y, stage_axis,
            perm=[(i, (i + 1) % num_stages) for i in range(num_stages)],
        )
        # y is emitted as a scan output (ys), NOT carried in an accumulator:
        # a carried buffer would be saved per tick as an AD residual
        # (~ticks × buffer bytes); stacked ys cost one buffer total.
        return (nxt, st), y

    from repro.core.vma import match_vma

    prev0 = match_vma(
        tree_map(lambda a: jnp.zeros_like(a[0]), x), x, vma_refs, extra=(stage_axis,)
    )
    if state is None:
        state = ()
    # append the sacrificial garbage-tick slot (stripped after the scan)
    state = jax.tree_util.tree_map(
        lambda a: jnp.concatenate([a, jnp.zeros_like(a[:1])], axis=0), state
    )
    state = match_vma(state, x, state, vma_refs, extra=(stage_axis,))
    (_, state), ys = lax.scan(
        tick_body,
        (prev0, state),
        jnp.arange(num_micro + num_stages - 1),
    )
    state = jax.tree_util.tree_map(lambda a: a[:num_micro], state)
    # last stage emitted microbatch m at tick m + S - 1; drop the fill ticks
    outputs = tree_map(lambda a: a[num_stages - 1 :], ys)
    outputs = tree_map(lambda a: jnp.where(is_last, a, jnp.zeros_like(a)), outputs)
    if reduce == "none":
        return outputs, state
    if scatter_dim is None:
        outputs = _wire(lax.psum, outputs, stage_axis)
    else:
        outputs = tree_map(
            lambda a: _wire(
                lax.psum_scatter, a, stage_axis, scatter_dimension=scatter_dim, tiled=True
            ),
            outputs,
        )
    return outputs, state


@jax.named_scope("pipe.exec")
def spmd_pipeline_interleaved(
    stage_fn: Callable[[jax.Array, Any], Any],
    x: jax.Array,
    *,
    stage_axis: str,
    num_devices: int,
    num_virtual: int,
    remat: bool = False,
    vma_refs: tuple = (),
):
    """Circular/interleaved pipeline: each of the D devices on ``stage_axis``
    hosts V virtual stages placed round-robin (virtual stage k = v·D + d on
    device d = k mod D), so one ``ppermute`` neighbour hop advances the
    model; microbatches circulate the ring V times. Fill is D - 1 ticks out
    of V·C + D - 1 total — the fill-drain bubble divided by ~V — at the cost
    of V smaller weight shards resident per device.

    ``stage_fn(v, h) -> y`` applies this device's v-th virtual stage
    (``v`` is a traced int32 scalar in [0, V); build it with
    ``make_interleaved_stage``). ``x`` is (num_micro, micro_batch, ...) with
    num_micro >= num_devices; outputs (same shape) are the last virtual
    stage's per-microbatch results, psum-broadcast over ``stage_axis``.

    Steady-state routing: device d's tick-t work is microbatch
    c = (t - d) mod C of round v = (t - d) // C. The wire value arriving at
    device d ≥ 1 each tick is exactly its current microbatch; device 0 banks
    arrivals from device D-1 in a C-slot rotating buffer until that
    microbatch's next round comes up (write precedes read inside a tick, so
    C = D also works). Gradients flow through ppermute/scan + the buffer —
    the backward pipeline — exactly as in ``spmd_pipeline``.
    """
    from repro.core.vma import match_vma

    D, V = num_devices, num_virtual
    C = x.shape[0]
    if C < D:
        raise ValueError(f"interleaved pipeline needs num_micro ({C}) >= devices ({D})")
    d = lax.axis_index(stage_axis)
    is_first = d == 0
    is_last = d == D - 1

    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    def tick_body(carry, t):
        prev, buf = carry
        # bank the arriving wire value: it is the sender's tick-(t-1) output,
        # i.e. microbatch (t - 1 - sender) mod C. Garbage fill/drain ticks
        # route to the sacrificial slot C.
        sender = jnp.where(is_first, D - 1, d - 1)
        sender_rel = t - 1 - sender
        in_valid = (sender_rel >= 0) & (sender_rel < V * C)
        w_idx = jnp.where(in_valid, jnp.mod(sender_rel, C), C)
        buf = lax.dynamic_update_index_in_dim(buf, prev, w_idx, 0)

        # this device's work item
        rel = t - d
        c = jnp.mod(rel, C)
        v = jnp.clip(rel // C, 0, V - 1)
        first_round = is_first & (rel < C)
        fresh = lax.dynamic_index_in_dim(x, jnp.clip(c, 0, C - 1), 0, keepdims=False)
        stored = lax.dynamic_index_in_dim(buf, jnp.clip(c, 0, C - 1), 0, keepdims=False)
        my_in = jnp.where(first_round, fresh, stored)
        y = fn(v, my_in)

        nxt = _wire(lax.ppermute, y, stage_axis, perm=[(i, (i + 1) % D) for i in range(D)])
        return (nxt, buf), y

    prev0 = match_vma(jnp.zeros_like(x[0]), x, vma_refs, extra=(stage_axis,))
    buf0 = match_vma(
        jnp.zeros((C + 1,) + x.shape[1:], x.dtype), x, vma_refs, extra=(stage_axis,)
    )
    T = V * C + D - 1
    (_, _), ys = lax.scan(tick_body, (prev0, buf0), jnp.arange(T))
    # device D-1 runs (v = V-1, chunk c) at tick (V-1)·C + c + D - 1
    outputs = ys[(V - 1) * C + D - 1 :]
    outputs = jnp.where(is_last, outputs, jnp.zeros_like(outputs))
    return _wire(lax.psum, outputs, stage_axis)


@jax.named_scope("pipe.exec")
def spmd_pipeline_scheduled(
    work_fn: Callable[..., tuple],
    lowered,
    *,
    stage_axis: str,
    wire_like: jax.Array,
    grads_like: Any,
    vma_refs: tuple = (),
    data_axis: str | None = None,
):
    """Schedule-aware pipeline executor: runs an arbitrary (validated,
    ring-compatible) ``WorkItem`` timeline — 1F1B, interleaved 1F1B, or any
    mixed fwd/bwd order — as one ``lax.scan`` over ticks inside the compiled
    program, with explicit backward stages instead of AD through the scan.

    ``lowered`` is a ``repro.core.schedule.LoweredTimeline``: static per-tick
    ``(phase, stage, chunk, slot)`` index arrays baked into the program as
    constants; each device reads its column via ``lax.axis_index``. Device
    columns are RING POSITIONS, not physical device ids: a
    ``repro.core.schedule.Placement`` rotates stages around the ring by
    re-devicing the ``WorkItem`` timeline before lowering, and picks which
    physical device occupies which position through the mesh's device order
    — both leave this executor's hop pattern (i -> i + 1 and its transpose)
    untouched, which is exactly why only ring-compatible placements lower.

    ``work_fn(phase, stage, chunk, h_in, ct, w_res) -> (y, d_h, w_out,
    grads, loss_sum, count)`` executes one work item (all six args traced
    scalars/arrays; ``w_res``/``w_out`` are residual PAIRS of wire-shaped
    buffers — the banked stage input and the applied cotangent — stashed as
    two parallel single-wire stashes so no per-tick concat materializes):

      * fwd: ``y`` is the stage output (uniform wire shape); everything else
        must be zeros;
      * bwd (fused): ``d_h`` is the cotangent for the upstream stage's
        output and ``grads`` this item's parameter gradients (full-params
        pytree, zero outside the stage's layers — a ``jax.vjp`` of the stage
        wrt the full params gives exactly that). The LAST stage derives its
        own cotangent from the loss and reports (loss_sum, count); other
        stages consume the banked ``ct`` and report zeros;
      * bwd_b (zero-bubble input-grad half): like bwd but ``grads`` stays
        zero; instead ``w_out`` carries the residual — the banked stage
        input and the applied cotangent — for the matching deferred W item;
      * bwd_w (deferred weight-grad half): consumes ``w_res`` from the
        residual stash, emits only ``grads``;
      * idle: all-zeros.

    Dataflow per tick: bank the two arriving wire values (forward ring hop
    carries activations, its transpose carries cotangents) into the stash
    slots the lowering assigned, read the work item's input/cotangent/
    residual slots, run ``work_fn``, store ``w_out`` into the B item's
    residual slot (``store_wslot`` — no wire hop, B and W share a device),
    accumulate ``grads`` into the item's *per-chunk* slot, and ``ppermute``
    the outputs. Fill/drain garbage routes to sacrificial slots — the same
    trick as ``spmd_pipeline``'s state writes.

    The activation stash holds ``n_fslots`` slots — the schedule's real
    per-device live-activation window (1F1B's min(S-s, C) memory lever),
    not the fill-drain C — and backward runs *explicitly* (no AD through the
    scan), so no per-tick residuals accumulate either. The W residual stash
    (``n_wslots`` slots, empty for fused-backward schedules) is the
    zero-bubble schedule's deferred-W window.

    Gradients are accumulated per chunk and reduced AFTER the scan in the
    canonical descending-chunk order (the fill-drain drain order the host
    engine uses), so every schedule produces a bit-identical update; the
    returned ``(grads, loss_sum, count)`` are psum-replicated over
    ``stage_axis`` (each device contributes exactly its stages' layer
    gradients, zeros elsewhere).

    ``data_axis`` composes the ring with graph data parallelism on a 2-D
    ``(data, stage)`` mesh: each data replica runs this executor over its
    own contiguous shard of the chunks (replica ``r`` owns global chunks
    ``[r*C_local, (r+1)*C_local)``), and the per-chunk gradient buffers are
    ``all_gather``-ed over the axis so the post-scan reduction can walk ALL
    global chunks in the same canonical descending order. Each (layer,
    chunk) gradient is nonzero on exactly one replica and one stage, so the
    gather + ordered sum (and the stage psum after it) only ever add zeros
    to the single real addend — the data axis changes WHERE chunks run,
    never the float associativity of the update.

    ``lowered.wire_latency == 2`` selects the DOUBLE-BUFFERED wire dataflow
    (the module docstring's wire-parity rule): each direction carries a
    (wire, pending) buffer pair — the tick banks ``wire`` (outputs of tick
    t-2), issues the ``ppermute`` of ``pending`` (outputs of tick t-1)
    BEFORE running ``work_fn``, and parks its own outputs as the next
    pending. Nothing downstream of the early ppermute is read by the tick's
    work, so the collective has the whole tick of compute to hide behind;
    the dataflow is a pure retiming — the banked values, stash traffic and
    gradient accumulation order are identical, so updates stay bit-identical
    to the serialized latency-1 executor.
    """
    from repro.core.schedule import PHASE_BWD, PHASE_BWD_W
    from repro.core.vma import match_vma

    C = lowered.num_chunks
    T, D = lowered.num_ticks, lowered.num_devices
    if lowered.wire_latency not in (1, 2):
        raise ValueError(f"unsupported wire_latency {lowered.wire_latency}")
    double = lowered.wire_latency == 2
    d = lax.axis_index(stage_axis)
    tree_map = jax.tree_util.tree_map

    idx = {
        name: jnp.asarray(getattr(lowered, name))
        for name in ("phase", "stage", "chunk", "work_fslot", "in_fslot",
                     "work_bslot", "in_bslot", "work_wslot", "store_wslot")
    }

    def pick(name, t):
        row = lax.dynamic_index_in_dim(idx[name], t, 0, keepdims=False)
        return lax.dynamic_index_in_dim(row, d, 0, keepdims=False)

    zero_wire = jnp.zeros_like(wire_like)
    fstash0 = jnp.zeros((lowered.n_fslots + 1,) + wire_like.shape, wire_like.dtype)
    bstash0 = jnp.zeros((lowered.n_bslots + 1,) + wire_like.shape, wire_like.dtype)
    wstash0 = tuple(
        jnp.zeros((lowered.n_wslots + 1,) + wire_like.shape, wire_like.dtype)
        for _ in range(2)
    )
    gbuf0 = tree_map(lambda p: jnp.zeros((C + 1,) + p.shape, p.dtype), grads_like)
    fwd_perm = [(i, (i + 1) % D) for i in range(D)]
    bwd_perm = [(i, (i - 1) % D) for i in range(D)]

    def tick_body(carry, t):
        wires, fstash, bstash, wstash, gbuf, loss, count = carry
        wire_f, wire_b = wires[0], wires[1]
        # bank arrivals BEFORE the work reads (same-tick deliver-then-consume)
        fstash = lax.dynamic_update_index_in_dim(fstash, wire_f, pick("in_fslot", t), 0)
        bstash = lax.dynamic_update_index_in_dim(bstash, wire_b, pick("in_bslot", t), 0)
        if double:
            # post tick t+1's arrivals (tick t-1's outputs, parked in the
            # pending buffers) before this tick's work: no value below reads
            # next_f/next_b, so XLA may run the collective under the compute
            next_f = _wire(lax.ppermute, wires[2], stage_axis, perm=fwd_perm)
            next_b = _wire(lax.ppermute, wires[3], stage_axis, perm=bwd_perm)
        h_in = lax.dynamic_index_in_dim(fstash, pick("work_fslot", t), 0, keepdims=False)
        ct_in = lax.dynamic_index_in_dim(bstash, pick("work_bslot", t), 0, keepdims=False)
        # fused-backward schedules allocate no residual slots; skip the
        # wire-sized stash reads/writes entirely on their hot path
        if lowered.n_wslots:
            w_res = tuple(
                lax.dynamic_index_in_dim(w, pick("work_wslot", t), 0, keepdims=False)
                for w in wstash
            )
        else:
            w_res = (zero_wire, zero_wire)
        phase = pick("phase", t)
        y, d_h, w_out, grads, loss_sum, cnt = work_fn(
            phase, pick("stage", t), pick("chunk", t), h_in, ct_in, w_res
        )
        if lowered.n_wslots:
            # a B tick banks its residual for the matching deferred W (the
            # read above precedes this write, so slot reuse inside a tick is
            # safe)
            wstash = tuple(
                lax.dynamic_update_index_in_dim(w, v, pick("store_wslot", t), 0)
                for w, v in zip(wstash, w_out)
            )
        # per-chunk gradient slots (sacrificial slot C on ticks that produce
        # no parameter gradients — fwd, bwd_b, idle): slice-sized read+write
        # per tick, reduced canonically after the scan
        gc = jnp.where((phase == PHASE_BWD) | (phase == PHASE_BWD_W), pick("chunk", t), C)
        gslot = tree_map(
            lambda b: lax.dynamic_index_in_dim(b, gc, 0, keepdims=False), gbuf
        )
        gbuf = tree_map(
            lambda b, acc, g: lax.dynamic_update_index_in_dim(b, acc + g, gc, 0),
            gbuf, gslot, grads,
        )
        if double:
            wires = (next_f, next_b, y, d_h)
        else:
            wires = (
                _wire(lax.ppermute, y, stage_axis, perm=fwd_perm),
                _wire(lax.ppermute, d_h, stage_axis, perm=bwd_perm),
            )
        return (
            wires, fstash, bstash, wstash, gbuf,
            loss + loss_sum, count + cnt,
        ), None

    carry0 = (
        (zero_wire,) * (4 if double else 2), fstash0, bstash0, wstash0, gbuf0,
        jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
    )
    axes = (stage_axis,) if data_axis is None else (data_axis, stage_axis)
    carry0 = match_vma(carry0, grads_like, vma_refs, extra=axes)
    (_, _, _, _, gbuf, loss, count), _ = lax.scan(tick_body, carry0, jnp.arange(T))

    # canonical reduction: per layer, chunks in DESCENDING order — the host
    # engine's fill-drain drain order — so floats accumulate identically no
    # matter which schedule produced the per-chunk gradients
    grads = tree_map(lambda b: jnp.zeros(b.shape[1:], b.dtype), gbuf)
    if data_axis is None:
        for c in reversed(range(C)):
            grads = tree_map(lambda g, b, c=c: g + b[c], grads, gbuf)
    else:
        # gather every replica's per-chunk slots (leaves (dp, C+1, ...)) and
        # reduce over GLOBAL chunks in the same descending order a single
        # replica would use: global chunk r*C + c descends as (r, c) descends
        # lexicographically. Exact, not just close — see the docstring.
        dp = lax.axis_size(data_axis)
        r_self = lax.axis_index(data_axis)

        def gather(x):
            # an all_gather written as a psum of zero-padded slots: each slot
            # has one nonzero addend, so the sum is exact, and the result is
            # invariant over the data axis, as a replicated output must be
            slots = jnp.zeros((dp,) + x.shape, x.dtype)
            slots = lax.dynamic_update_index_in_dim(slots, x, r_self, 0)
            return _wire(lax.psum, slots, data_axis)

        gall = tree_map(gather, gbuf)
        for r in reversed(range(dp)):
            for c in reversed(range(C)):
                grads = tree_map(lambda g, b, r=r, c=c: g + b[r, c], grads, gall)
        loss = jnp.sum(gather(loss))
        count = jnp.sum(gather(count))
    grads = _wire(lax.psum, grads, stage_axis)
    loss = _wire(lax.psum, loss, stage_axis)
    count = _wire(lax.psum, count, stage_axis)
    return grads, loss, count


@jax.named_scope("pipe.exec")
def spmd_pipeline_scheduled_lanes(
    work_fn: Callable[..., tuple],
    lowered,
    *,
    wire_like: jax.Array,
    grads_like: Any,
):
    """Sub-device-count substrate of ``spmd_pipeline_scheduled``: the same
    per-tick dataflow with the device ring as per-LANE carries inside one
    program — ``ppermute`` becomes a static rotation of the lane tuple,
    psum a plain sum.

    The lane loop is a static Python loop, so each lane's ``lax.switch``
    dispatch stays a real XLA conditional executing ONE branch per tick.
    (Emulating the ring with ``vmap(axis_name=...)`` instead would batch the
    switch predicate and compute every branch in every lane — a ~(2S+1)×
    FLOP blow-up; this substrate does D single-branch dispatches per tick,
    the ring's aggregate work executed sequentially.) Numerics are identical
    to the shard_map substrate: same banking, same canonical descending-chunk
    gradient reduction — per (layer, chunk) slot exactly one lane ever
    contributes, so the shared gradient buffer accumulates the same floats
    the psum would.

    ``lowered.wire_latency == 2`` mirrors the double-buffered wire dataflow
    (module docstring wire-parity rule) with tuple rotation: the tick banks
    the in-flight ``wire`` tuples, rotates the ``pending`` tuples into the
    next wires, and parks its own lane outputs as pending — outputs reach
    the neighbour lane's stash exactly two ticks after production, matching
    the retimed index arrays and the shard_map substrate bit-for-bit."""
    from repro.core.schedule import PHASE_BWD, PHASE_BWD_W

    C = lowered.num_chunks
    T, D = lowered.num_ticks, lowered.num_devices
    if lowered.wire_latency not in (1, 2):
        raise ValueError(f"unsupported wire_latency {lowered.wire_latency}")
    double = lowered.wire_latency == 2
    tree_map = jax.tree_util.tree_map

    idx = {
        name: jnp.asarray(getattr(lowered, name))
        for name in ("phase", "stage", "chunk", "work_fslot", "in_fslot",
                     "work_bslot", "in_bslot", "work_wslot", "store_wslot")
    }

    def pick(name, t, d):  # d is a static lane index
        row = lax.dynamic_index_in_dim(idx[name], t, 0, keepdims=False)
        return row[d]

    # per-LANE stash tuples, not one (D, ...) stacked array: a stacked stash
    # would need a chained ``.at[d].set`` per lane per tick, which XLA
    # materializes as whole-stash copies — measured 1.6x step time on the
    # zb-h1 residual stash. Tuple carries keep every lane's update a single
    # in-place dynamic-update-slice.
    zero_wire = jnp.zeros_like(wire_like)
    wires0 = (zero_wire,) * D
    fstash0 = tuple(
        jnp.zeros((lowered.n_fslots + 1,) + wire_like.shape, wire_like.dtype)
        for _ in range(D)
    )
    bstash0 = tuple(
        jnp.zeros((lowered.n_bslots + 1,) + wire_like.shape, wire_like.dtype)
        for _ in range(D)
    )
    wstash0 = tuple(
        tuple(
            jnp.zeros((lowered.n_wslots + 1,) + wire_like.shape, wire_like.dtype)
            for _ in range(2)
        )
        for _ in range(D)
    )
    gbuf0 = tree_map(lambda p: jnp.zeros((C + 1,) + p.shape, p.dtype), grads_like)

    def tick_body(carry, t):
        wires, fstash, bstash, wstash, gbuf, loss, count = carry
        wire_f, wire_b = wires[0], wires[1]
        fstash, bstash, wstash = list(fstash), list(bstash), list(wstash)
        ys, dhs = [], []
        for d in range(D):  # static: one single-branch dispatch per lane
            fstash[d] = lax.dynamic_update_index_in_dim(
                fstash[d], wire_f[d], pick("in_fslot", t, d), 0
            )
            bstash[d] = lax.dynamic_update_index_in_dim(
                bstash[d], wire_b[d], pick("in_bslot", t, d), 0
            )
            h_in = lax.dynamic_index_in_dim(
                fstash[d], pick("work_fslot", t, d), 0, keepdims=False
            )
            ct_in = lax.dynamic_index_in_dim(
                bstash[d], pick("work_bslot", t, d), 0, keepdims=False
            )
            if lowered.n_wslots:
                w_res = tuple(
                    lax.dynamic_index_in_dim(
                        w, pick("work_wslot", t, d), 0, keepdims=False
                    )
                    for w in wstash[d]
                )
            else:  # fused-backward schedule: no residual traffic at all
                w_res = (zero_wire, zero_wire)
            phase = pick("phase", t, d)
            y, d_h, w_out, grads, loss_sum, cnt = work_fn(
                phase, pick("stage", t, d), pick("chunk", t, d), h_in, ct_in, w_res
            )
            if lowered.n_wslots:
                wstash[d] = tuple(
                    lax.dynamic_update_index_in_dim(
                        w, v, pick("store_wslot", t, d), 0
                    )
                    for w, v in zip(wstash[d], w_out)
                )
            gc = jnp.where(
                (phase == PHASE_BWD) | (phase == PHASE_BWD_W), pick("chunk", t, d), C
            )
            gslot = tree_map(
                lambda b: lax.dynamic_index_in_dim(b, gc, 0, keepdims=False), gbuf
            )
            gbuf = tree_map(
                lambda b, acc, g: lax.dynamic_update_index_in_dim(b, acc + g, gc, 0),
                gbuf, gslot, grads,
            )
            loss, count = loss + loss_sum, count + cnt
            ys.append(y)
            dhs.append(d_h)
        if double:
            # rotate last tick's parked outputs into the in-flight wires and
            # park this tick's outputs: two-tick producer→stash delay, the
            # lane image of the early-posted ppermute pair
            wires = (
                tuple(wires[2][(d - 1) % D] for d in range(D)),
                tuple(wires[3][(d + 1) % D] for d in range(D)),
                tuple(ys), tuple(dhs),
            )
        else:
            # the ring hops: lane d's activation to lane d+1, cotangent to d-1
            wires = (
                tuple(ys[(d - 1) % D] for d in range(D)),
                tuple(dhs[(d + 1) % D] for d in range(D)),
            )
        return (
            wires, tuple(fstash), tuple(bstash), tuple(wstash),
            gbuf, loss, count,
        ), None

    carry0 = (
        (wires0,) * (4 if double else 2), fstash0, bstash0, wstash0, gbuf0,
        jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
    )
    (_, _, _, _, gbuf, loss, count), _ = lax.scan(tick_body, carry0, jnp.arange(T))
    grads = tree_map(lambda b: jnp.zeros(b.shape[1:], b.dtype), gbuf)
    for c in reversed(range(C)):  # canonical: the fill-drain drain order
        grads = tree_map(lambda g, b, c=c: g + b[c], grads, gbuf)
    return grads, loss, count


def _eval_out_slot(lowered):
    """Per-tick output-buffer slot: last-stage forward ticks write their
    chunk's result, everything else routes to the sacrificial slot C."""
    import numpy as np

    from repro.core.schedule import PHASE_FWD

    last = (lowered.phase == PHASE_FWD) & (lowered.stage == lowered.num_stages - 1)
    return np.where(last, lowered.chunk, lowered.num_chunks).astype(np.int32)


@jax.named_scope("pipe.exec")
def spmd_pipeline_scheduled_eval(
    work_fn: Callable[..., jax.Array],
    lowered,
    *,
    stage_axis: str,
    wire_like: jax.Array,
    vma_refs: tuple = (),
):
    """Forward-only twin of ``spmd_pipeline_scheduled`` — the compiled
    eval/inference path. Runs a ``forward_only`` ``LoweredTimeline`` (see
    ``repro.core.schedule.forward_timeline``): no vjp, no cotangent wire, no
    gradient buffers — just the activation ring, a stash collapsed to the
    wire-slack window (one slot for fill-drain forwards), and an output
    buffer collecting the LAST stage's per-chunk results.

    ``work_fn(phase, stage, chunk, h_in) -> y`` runs one forward item (idle
    ticks must return zeros). Returns the (num_chunks, *wire) outputs,
    psum-replicated over ``stage_axis`` (exactly one device writes each
    chunk — the one hosting the last stage)."""
    from repro.core.vma import match_vma

    C = lowered.num_chunks
    T, D = lowered.num_ticks, lowered.num_devices
    d = lax.axis_index(stage_axis)

    idx = {
        name: jnp.asarray(getattr(lowered, name))
        for name in ("phase", "stage", "chunk", "work_fslot", "in_fslot")
    }
    idx["out_slot"] = jnp.asarray(_eval_out_slot(lowered))

    def pick(name, t):
        row = lax.dynamic_index_in_dim(idx[name], t, 0, keepdims=False)
        return lax.dynamic_index_in_dim(row, d, 0, keepdims=False)

    zero_wire = jnp.zeros_like(wire_like)
    fstash0 = jnp.zeros((lowered.n_fslots + 1,) + wire_like.shape, wire_like.dtype)
    out0 = jnp.zeros((C + 1,) + wire_like.shape, wire_like.dtype)
    fwd_perm = [(i, (i + 1) % D) for i in range(D)]

    def tick_body(carry, t):
        wire_f, fstash, out = carry
        fstash = lax.dynamic_update_index_in_dim(fstash, wire_f, pick("in_fslot", t), 0)
        h_in = lax.dynamic_index_in_dim(fstash, pick("work_fslot", t), 0, keepdims=False)
        y = work_fn(pick("phase", t), pick("stage", t), pick("chunk", t), h_in)
        out = lax.dynamic_update_index_in_dim(out, y, pick("out_slot", t), 0)
        wire_f = _wire(lax.ppermute, y, stage_axis, perm=fwd_perm)
        return (wire_f, fstash, out), None

    carry0 = match_vma((zero_wire, fstash0, out0), vma_refs, extra=(stage_axis,))
    (_, _, out), _ = lax.scan(tick_body, carry0, jnp.arange(T))
    return _wire(lax.psum, out[:C], stage_axis)


@jax.named_scope("pipe.exec")
def spmd_pipeline_scheduled_eval_lanes(
    work_fn: Callable[..., jax.Array],
    lowered,
    *,
    wire_like: jax.Array,
):
    """Sub-device-count substrate of ``spmd_pipeline_scheduled_eval``: the
    ring as a static lane loop inside one program (same trade-offs as
    ``spmd_pipeline_scheduled_lanes`` — every ``lax.switch`` stays a
    single-branch conditional). The output buffer is shared across lanes;
    only the last-stage lane ever writes a real slot."""
    C = lowered.num_chunks
    T, D = lowered.num_ticks, lowered.num_devices

    idx = {
        name: jnp.asarray(getattr(lowered, name))
        for name in ("phase", "stage", "chunk", "work_fslot", "in_fslot")
    }
    idx["out_slot"] = jnp.asarray(_eval_out_slot(lowered))

    def pick(name, t, d):
        row = lax.dynamic_index_in_dim(idx[name], t, 0, keepdims=False)
        return row[d]

    zero_wire = jnp.zeros_like(wire_like)
    wires0 = (zero_wire,) * D
    fstash0 = tuple(
        jnp.zeros((lowered.n_fslots + 1,) + wire_like.shape, wire_like.dtype)
        for _ in range(D)
    )
    out0 = jnp.zeros((C + 1,) + wire_like.shape, wire_like.dtype)

    def tick_body(carry, t):
        wire_f, fstash, out = carry
        fstash = list(fstash)
        ys = []
        for d in range(D):
            fstash[d] = lax.dynamic_update_index_in_dim(
                fstash[d], wire_f[d], pick("in_fslot", t, d), 0
            )
            h_in = lax.dynamic_index_in_dim(
                fstash[d], pick("work_fslot", t, d), 0, keepdims=False
            )
            y = work_fn(pick("phase", t, d), pick("stage", t, d), pick("chunk", t, d), h_in)
            out = lax.dynamic_update_index_in_dim(out, y, pick("out_slot", t, d), 0)
            ys.append(y)
        wire_f = tuple(ys[(d - 1) % D] for d in range(D))
        return (wire_f, tuple(fstash), out), None

    (_, _, out), _ = lax.scan(tick_body, (wires0, fstash0, out0), jnp.arange(T))
    return out[:C]


# --------------------------------------------------- homogeneous helpers --


def make_gather_fn(gather_mask: Any, axis_name: str) -> Callable[[Any], Any]:
    """ZeRO-3 gather: all-gather each leaf whose (static, same-structure)
    ``gather_mask`` entry is True along its first dim. AD transposes the
    gather into a gradient reduce-scatter."""
    flat_mask = jax.tree_util.tree_leaves(
        gather_mask, is_leaf=lambda x: isinstance(x, bool)
    )

    def gather(params: Any) -> Any:
        flat, treedef = jax.tree_util.tree_flatten(params)
        assert len(flat) == len(flat_mask), (len(flat), len(flat_mask))
        out = [
            lax.all_gather(leaf, axis_name, axis=0, tiled=True) if m else leaf
            for leaf, m in zip(flat, flat_mask)
        ]
        return jax.tree_util.tree_unflatten(treedef, out)

    return gather


def make_scanned_stage(
    block_fn: Callable[[Any, Any, Any], Any],
    params_local: Any,  # leaves (layers_per_stage, ...)
    extras_local: Any,
    *,
    gather_fn: Callable[[Any], Any] | None = None,
) -> Callable:
    """Homogeneous stateless stage: scan ``block_fn`` over this stage's
    layers. ``block_fn(layer_params, layer_extras, h) -> h``."""

    def stage_fn(h, state_mb):
        from repro.core.vma import match_vma

        def one_layer(c, xs):
            lp, ex = xs
            if gather_fn is not None:
                lp = gather_fn(lp)
            return block_fn(lp, ex, c), None

        # params may vary over more mesh axes than h (e.g. fsdp gathers);
        # the layer-scan carry must match the body output's vma
        h = match_vma(h, params_local, extras_local, h)
        h, _ = lax.scan(one_layer, h, (params_local, extras_local))
        return h, state_mb

    return stage_fn


def make_interleaved_stage(
    block_fn: Callable[[Any, Any, Any], Any],
    params_local: Any,  # leaves (num_virtual, layers_per_stage, ...)
    extras_local: Any,
    *,
    gather_fn: Callable[[Any], Any] | None = None,
) -> Callable:
    """Homogeneous interleaved stage for ``spmd_pipeline_interleaved``:
    selects this device's v-th virtual-stage slice, then scans ``block_fn``
    over its layers_per_stage layers."""

    def stage_fn(v, h):
        from repro.core.vma import match_vma

        pv = jax.tree_util.tree_map(
            lambda a: lax.dynamic_index_in_dim(a, v, 0, keepdims=False), params_local
        )
        ev = jax.tree_util.tree_map(
            lambda a: lax.dynamic_index_in_dim(a, v, 0, keepdims=False), extras_local
        )

        def one_layer(c, xs):
            lp, ex = xs
            if gather_fn is not None:
                lp = gather_fn(lp)
            return block_fn(lp, ex, c), None

        h = match_vma(h, pv, ev, h)
        h, _ = lax.scan(one_layer, h, (pv, ev))
        return h

    return stage_fn


def make_scanned_stage_stateful(
    block_fn: Callable[[Any, Any, Any, Any], tuple[Any, Any]],
    params_local: Any,
    extras_local: Any,
    *,
    gather_fn: Callable[[Any], Any] | None = None,
) -> Callable:
    """Homogeneous stateful stage (decode/prefill-cache): state_mb leaves are
    (layers_per_stage, ...) and ride the layer scan as xs/ys.
    ``block_fn(layer_params, layer_extras, h, cache_i) -> (h, cache_i')``."""

    def stage_fn(h, state_mb):
        from repro.core.vma import match_vma

        def one_layer(c, xs):
            lp, ex, cache_i = xs
            if gather_fn is not None:
                lp = gather_fn(lp)
            c, cache_out = block_fn(lp, ex, c, cache_i)
            return c, cache_out

        h = match_vma(h, params_local, extras_local, state_mb, h)
        h, new_cache = lax.scan(one_layer, h, (params_local, extras_local, state_mb))
        return h, new_cache

    return stage_fn
