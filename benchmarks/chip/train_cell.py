"""A training cell: the program's compiled pipeline step, driven from the
seed through its first steps and then through the measured window.

``TrainCell`` builds the graph, the program's model and compiled engine once;
``first_steps(seed)`` makes the weights, runs the steps the reference
follows through the window's own call, and returns the state the window
continues from; ``window`` times steps; ``reference`` and ``numbers``
decide ``correct``. ``readings.py`` reuses the same object over many seeds.
"""

from __future__ import annotations

import gc
import sys
import time

import jax
import numpy as np

import check
import flops
import graphs
import models
from reference import Reference, control_passes

FAULTS = ("state_unchanged", "half_batch")


def base_key(seed: int):
    """A key from any non-negative seed, the bits above 32 folded in."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def _host(tree):
    return jax.tree_util.tree_map(lambda x: np.asarray(x), tree)


class TrainCell:
    def __init__(self, cfg: dict, traffic: dict, *, fault: str | None = None):
        from repro.core.microbatch import MicroBatch, MicroBatchPlan
        from repro.core.pipeline import GPipeConfig, make_engine
        from repro.graphs.data import GraphBatch
        from repro.train import optimizer as opt_lib

        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
        self.cfg, self.traffic, self.fault = cfg, traffic, fault
        self.stacked = graphs.build(traffic["graph"])
        self.chunks = self.stacked["features"].shape[0]
        self.in_dim = self.stacked["features"].shape[-1]
        self.classes = int(self.stacked["num_classes"])
        model = models.program_model(cfg, self.in_dim, self.classes)
        pipe = traffic["pipeline"]
        self.engine = make_engine(model, GPipeConfig(
            balance=tuple(pipe["balance"]), chunks=self.chunks, schedule=pipe["schedule"],
            engine="compiled", backend=cfg["backend"]))
        s = self.stacked
        self.plan = MicroBatchPlan(
            strategy=traffic["graph"]["kind"], chunks=self.chunks, rebuild_seconds=0.0,
            edge_cut=0.0,
            batches=[MicroBatch(
                graph=GraphBatch(**{f: s[f][c] for f in graphs.FIELDS},
                                 num_classes=self.classes),
                core_mask=s["core_mask"][c]) for c in range(self.chunks)],
        )
        o = cfg["optimizer"]
        if o.get("weight_decay_mode") != "decoupled":
            raise ValueError("the program's Adam decays decoupled; the configuration must say so")
        self.optimizer = opt_lib.adam(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                                      weight_decay=o["weight_decay"])
        self.flops_per_step = flops.train_step_flops(
            cfg, int(s["num_nodes"]), int(s["num_slots"]), self.in_dim, self.classes)
        self._clean = self._step = self._mid = None

    def _program(self, params, opt_state, rng):
        """The jitted step and the arguments between state and key, with
        the cell's fault, if any, planted."""
        if self._clean is None:
            step, args = self.engine.step_program(params, opt_state, self.plan, rng,
                                                  self.optimizer)
            self._clean = (step, tuple(args[2:-1]))
        step, mid = self._clean
        if self.fault == "half_batch":
            # the loss mask, (chunks, rows): the mean over the first half
            mid = (*mid[:-1], mid[-1].at[self.chunks // 2:].set(False))
        if self.fault == "state_unchanged":
            inner = step

            def step(p, o, *a):
                return p, o, inner(p, o, *a)[2]
        return step, mid

    def first_steps(self, seed: int, n: int):
        """Weights from ``seed``, then ``n`` steps through the window's own
        call. Returns the state to continue from and what the check reads."""
        key = base_key(seed)
        key, init_key = jax.random.split(key)
        params = models.init_params(self.cfg, self.in_dim, self.classes, init_key)
        opt_state = self.optimizer.init(params)
        losses, rngs, grads = [], [], None
        for k in range(n):
            key, rng = jax.random.split(key)
            rngs.append(rng)
            if k == 0:
                self._step, self._mid = self._program(params, opt_state, rng)
            params, opt_state, loss = self._step(params, opt_state, *self._mid, rng)
            losses.append(float(loss))
            if k == 0:
                b1 = self.cfg["optimizer"]["b1"]
                grads = _host(jax.tree_util.tree_map(lambda m: m / (1 - b1), opt_state.mu))
        obs = {"losses": losses, "grads": grads, "params": _host(params)}
        return {"params": params, "opt_state": opt_state, "key": key}, obs, rngs

    def window(self, state: dict, seconds: float, *, annotate: bool = False) -> dict:
        """Steps until ``seconds`` have passed, each fed the last one's
        state and awaited. Returns the steps, their seconds, each step's
        seconds and their losses (still on the device)."""
        step, mid = self._step, self._mid
        p, o, key = state["params"], state["opt_state"], state["key"]
        losses, ends = [], []
        if annotate:
            from jax.profiler import TraceAnnotation as span
        t0 = time.perf_counter()
        while True:
            if annotate:
                with span("bench.rng"):
                    key, rng = jax.random.split(key)
                with span("bench.dispatch"):
                    p, o, loss = step(p, o, *mid, rng)
                with span("bench.wait"):
                    loss.block_until_ready()
            else:
                key, rng = jax.random.split(key)
                p, o, loss = step(p, o, *mid, rng)
                loss.block_until_ready()
            losses.append(loss)
            ends.append(time.perf_counter())
            elapsed = ends[-1] - t0
            if elapsed >= seconds:
                break
        state.update(params=p, opt_state=o, key=key)
        return {"steps": len(losses), "seconds": elapsed, "losses": losses,
                "step_seconds": list(np.diff([t0, *ends]))}

    def program_bytes(self, state: dict) -> int:
        """The device bytes the compiled step holds while it runs, by the
        compiler's memory analysis: arguments, outputs not aliased to them,
        temporaries and code."""
        step, mid = self._clean
        m = step.lower(state["params"], state["opt_state"], *mid, state["key"]) \
            .compile().memory_analysis()
        return int(m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
                   + m.temp_size_in_bytes + m.generated_code_size_in_bytes)

    def free(self) -> None:
        """Drop the program's device state before the reference runs."""
        self._clean = self._step = self._mid = None
        self.engine = self.plan = None
        gc.collect()

    def reference(self, seed: int, rngs: list, *, control: bool = False,
                  chunk_ids=None) -> dict:
        """The reference's (or, with ``control``, the control's) observations
        over the same steps from the same weights."""
        key = base_key(seed)
        _, init_key = jax.random.split(key)
        params0 = _host(models.init_params(self.cfg, self.in_dim, self.classes, init_key))
        ref = Reference(self.cfg, self.stacked, chunk_ids=chunk_ids,
                        passes=control_passes(self.cfg) if control else 0)
        obs = ref.observe(params0, rngs)
        return {"losses": obs["losses"], "grads": _host(obs["grads"]),
                "params": _host(obs["params"]), "params0": params0}

    def numbers(self, prog: dict, ref: dict) -> dict:
        return check.training_numbers(prog, ref, ref["params0"])


class GcTimer:
    """The collector's passes and their seconds while it is registered."""

    def __init__(self):
        self.passes, self.seconds, self._t = 0, 0.0, None

    def __call__(self, phase, _info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.passes += 1
            self.seconds += time.perf_counter() - self._t

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *_exc):
        gc.callbacks.remove(self)


def describe_window(win: dict, gc_timer: GcTimer) -> str:
    """One line on how the window's time fell: the steady step, the
    slowest step and where it fell, and the collector's share."""
    t = np.asarray(win["step_seconds"])
    slow = int(np.argmax(t))
    return (f"bench: window {win['steps']} steps in {win['seconds']:.6f} s; step median "
            f"{np.median(t):.6f} s, slowest {t[slow]:.6f} s (step {slow + 1}); "
            f"gc {gc_timer.passes} passes, {gc_timer.seconds:.6f} s")


def run(cfg, traffic, args, *, device, t_start, fault, traced, memory_facts,
        counter) -> dict:
    """One run of a training cell: set-up and the first steps, the window,
    the traced window with ``--trace 1``, then the reference."""
    cell = TrainCell(cfg, traffic, fault=fault)
    state, prog, rngs = cell.first_steps(args.seed, traffic["check_steps"])
    # what set-up left is kept: no collector pass in the window walks it
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    counter.open = True
    with GcTimer() as gc_timer:
        win = cell.window(state, args.seconds)
    counter.open = False
    sys.stderr.write(describe_window(win, gc_timer) + "\n")
    ctx = {"kind": "train", "chips": device["count"], "device_kind": device["kind"],
           "flops_per_step": cell.flops_per_step, "window": win}
    failed = int(sum(not np.isfinite(float(x)) for x in win.pop("losses")))
    if args.trace:
        _, ctx["trace"] = traced(lambda s: cell.window(state, s, annotate=True),
                                 traffic["trace_seconds"])
    ctx["memory"] = memory_facts()
    mem = ctx["memory"]
    mem["program_bytes"] = cell.program_bytes(state)
    mem["peak_bytes"] = max(mem["counter_bytes"], mem["program_bytes"])
    sys.stderr.write(f"bench: device bytes: runtime peak {mem['counter_bytes']}, "
                     f"compiled step {mem['program_bytes']}, limit {mem['bytes_limit']}\n")
    del state
    gc.unfreeze()
    cell.free()
    ref = cell.reference(args.seed, rngs)
    return {
        "ctx": ctx,
        "numbers": cell.numbers(prog, ref),
        "attempted": win["steps"],
        "failed": failed,
        "metrics": {"setup_s": {"value": setup_s, "unit": "s"},
                    "step_s": {"value": win["seconds"] / win["steps"], "unit": "s"}},
    }
