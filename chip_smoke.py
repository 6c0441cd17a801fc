"""Chip smoke test: the pipelined paper GAT, end to end on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the stage ring across four chips

One chip, three phases:

* ``train-padded`` and ``train-pallas`` train the paper's GAT (8 heads x 8
  hidden per head, published dropout) on the seed-generated cora stand-in
  through the training entry point: compiled engine, 4 stages (on one
  device, the lane substrate), 4 halo chunks, 1F1B, once per aggregation
  backend. Each then checks the one-step loss and gradients of the compiled
  step against the host engine on the same plan and seed; ``train-pallas``
  also checks them against the padded backend and that its compiled step
  holds the Pallas kernels (``tpu_custom_call``).
* ``serve`` answers queries through the serving entry point with
  ``--verify``: every served prediction against a full-batch forward.

Four chips, one phase: ``ring4`` runs the 4-stage compiled ring for
fill-drain and 1F1B, and a 2 (data) x 2 (stage) mesh, each one step's loss
and gradients against the host engine's fill-drain on the same plan and
seed, and checks that each step's outputs span 4 distinct devices.

Each phase runs in its own process, since a process that has touched JAX
holds the chip; this parent never imports JAX. A phase that fails, or a
host without a TPU, makes the script exit non-zero without a result. The
last line of standard output is the result as one JSON object.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
BUDGET_S = 1140  # the whole run, compilation included
RESULT = "PHASE_RESULT "
SEED = 0
EPOCHS = 5
# the README's engine-equivalence tolerance on losses
LOSS_ATOL = 1e-4
# Served predictions against the full-batch forward. The TPU tiles each
# bucket-shaped matmul by its own shape, so a served row need not be
# bit-identical to the same row of the full-graph forward: the last bits
# of an f32 accumulation move. On one v5e, 2 of 40 served rows differed,
# by at most 5.96e-7 (~2 ULP of log-probabilities near -2; the ULP grows
# with the magnitude). This bound leaves room for larger log-probabilities
# and stays far below any changed prediction.
SERVE_VERIFY_ATOL = 1e-5
# One-step gradients against the reference engine's, leaf by leaf:
# max |g - g_ref| <= GRAD_RTOL * max |g_ref|. They are taken with f32
# matmuls at "highest" precision, so the gap measures the engines' order of
# f32 sums, not the TPU's default rounding of matmul operands to bf16 (that
# gap is reported beside it). On 4 CPU devices the worst leaf of the fused
# fill-drain ring on cora was 2.4e-5 of its largest entry; a chunk or stage
# left out or counted twice moves a leaf by O(1) of its largest entry.
GRAD_RTOL = 1e-3
PALLAS_ENV = ("REPRO_PALLAS_INTERPRET", "REPRO_PALLAS_FORCE_KERNEL")
TRAIN_FLAGS = (
    "--mode gnn --dataset cora --engine compiled --stages 4 --chunks 4 "
    "--schedule 1f1b --strategy halo"
).split()


# ------------------------------------------------------------- phases --


def _require(ok, what: str) -> None:
    """A check of the smoke itself: raises, and survives ``python -O``."""
    if not ok:
        raise RuntimeError(what)


def _device_facts() -> dict:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev.platform!r} devices")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}


def _peak_bytes() -> int:
    import jax

    return max(d.memory_stats()["peak_bytes_in_use"] for d in jax.devices())


def _check_pallas_on_device() -> None:
    from repro.kernels import runtime_interpret, use_kernel_forward

    for name in PALLAS_ENV:
        _require(name not in os.environ, f"{name} is set: the kernels would not run as on the chip")
    _require(not runtime_interpret(), "Pallas would run in interpret mode")
    _require(use_kernel_forward(), "the ops would run the jnp oracle, not the kernels")


def _grad_gap(a, b) -> float:
    """Largest gap of gradients ``a`` from ``b``, leaf by leaf, relative to
    the leaf's largest entry of ``b``."""
    import jax
    import numpy as np

    gaps = []
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        diff, scale = float(np.max(np.abs(x - y))), float(np.max(np.abs(y)))
        gaps.append(diff / scale if scale else (0.0 if diff == 0.0 else float("inf")))
    return max(gaps)


def _finite(tree) -> bool:
    import jax
    import numpy as np

    return all(np.isfinite(np.asarray(x)).all() for x in jax.tree_util.tree_leaves(tree))


def _first_step(engine, plan, optimizer, seed):
    """Params, optimizer state and rng as the training loop derives them
    for its first step (``launch.train._train_pipeline``)."""
    import jax

    key, init_key = jax.random.split(jax.random.PRNGKey(seed))
    params = engine.init_params(init_key)
    _, rng = jax.random.split(key)
    return params, optimizer.init(params), rng


def _timed_step(engine, plan, optimizer, seed, steps=0):
    """Run the engine's first train step, then time ``steps`` more. Returns
    (compile_s, step_s, the first step's (params, opt_state, loss), a
    callable that lowers the step). ``compile_s`` is the first call less a
    steady step (tracing, lowering and compiling); both are None when
    ``steps`` is 0."""
    import jax

    params, opt_state, rng = _first_step(engine, plan, optimizer, seed)
    step, args = engine.step_program(params, opt_state, plan, rng, optimizer)
    t0 = time.perf_counter()
    first = jax.block_until_ready(step(*args))
    first_s = time.perf_counter() - t0
    p, o, times = first[0], first[1], []
    for _ in range(steps):
        t0 = time.perf_counter()
        p, o, _ = jax.block_until_ready(step(p, o, *args[2:]))
        times.append(time.perf_counter() - t0)
    step_s = sorted(times)[len(times) // 2] if times else None
    compile_s = first_s - step_s if times else None
    return compile_s, step_s, first, functools.partial(step.lower, *args)


def _grads(engine, plan, probe, seed, precision="highest"):
    """Loss and gradients of the engine's first train step, with f32
    matmuls at ``precision``; ``probe`` is an ``optimizer.grad_probe()``."""
    import jax

    params, state, rng = _first_step(engine, plan, probe, seed)
    with jax.default_matmul_precision(precision):
        _, grads, loss = engine.train_step(params, state, plan, rng, probe)
    return float(loss), grads


def phase_train(backend: str) -> dict:
    import dataclasses

    import numpy as np

    from repro.core.cli import PipelineCLIConfig
    from repro.core.microbatch import make_plan
    from repro.core.pipeline import make_engine
    from repro.graphs import load_dataset
    from repro.launch import train as train_cli
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.gnn.net import build_paper_gat
    from repro.train import optimizer as opt_lib

    device = _device_facts()
    if backend == "pallas":
        _check_pallas_on_device()
    enable_compile_cache()
    args = train_cli.build_parser().parse_args(
        TRAIN_FLAGS + ["--backend", backend, "--epochs", str(EPOCHS),
                       "--log-every", "0", "--seed", str(SEED)]
    )
    trained = train_cli.run_gnn(args)  # the CLI's own path, EPOCHS steps
    _require(np.isfinite(trained["train_loss"]), f"training diverged: {trained}")

    # one step, compiled against the host engine on the same plan and seed
    g = load_dataset(args.dataset, seed=SEED)
    plan = make_plan(g, args.chunks, strategy=args.strategy, halo_hops=2, seed=SEED)
    optimizer = opt_lib.adam(5e-3, weight_decay=5e-4)  # the trainer's
    probe = opt_lib.grad_probe()
    config = PipelineCLIConfig.from_args(args).gpipe_config()

    def model_for(backend, **kw):
        return build_paper_gat(g.num_features, g.num_classes, backend=backend, **kw)

    # the trainer's model: the fused pallas GAT kernel has no attention dropout
    kw = {"attn_dropout": 0.0} if backend == "pallas" else {}
    model = model_for(backend, **kw)
    engine = make_engine(model, config)
    _, _, (params, _, loss), lower = _timed_step(engine, plan, optimizer, SEED)
    loss = float(loss)
    host = make_engine(model, dataclasses.replace(config, engine="host"))
    h_params, h_opt, h_rng = _first_step(host, plan, optimizer, SEED)
    _, _, h_loss = host.train_step(h_params, h_opt, plan, h_rng, optimizer)
    _, grads = _grads(engine, plan, probe, SEED)
    gaps = {"host_loss": abs(loss - float(h_loss)),
            "host_grads": _grad_gap(grads, _grads(host, plan, probe, SEED)[1])}
    ok = (np.isfinite(loss) and _finite(params) and gaps["host_loss"] <= LOSS_ATOL
          and gaps["host_grads"] <= GRAD_RTOL)
    if backend == "pallas":
        # the persistent cache holds the program the step just ran, so
        # this compile reads it back
        kernels = lower().compile().as_text().count("tpu_custom_call")
        _require(kernels, "the pallas step holds no Pallas kernel")
        padded = make_engine(model_for("padded", attn_dropout=0.0),
                             dataclasses.replace(config, backend="padded"))
        _, _, (_, _, p_loss), _ = _timed_step(padded, plan, optimizer, SEED)
        gaps["padded_loss"] = abs(loss - float(p_loss))
        gaps["padded_grads"] = _grad_gap(grads, _grads(padded, plan, probe, SEED)[1])
        ok = ok and gaps["padded_loss"] <= LOSS_ATOL and gaps["padded_grads"] <= GRAD_RTOL
    return {
        "ok": bool(ok),
        "device": device,
        # the CLI run's: its first epoch traces, compiles and steps once
        "compile_s": trained["first_epoch_s"] - trained["median_epoch_s"],
        "step_s": trained["median_epoch_s"],
        "first_step_loss": loss,
        "final_loss": trained["train_loss"],
        "val_acc": trained["val_acc"],
        "max_gap": gaps,
        "peak_bytes": _peak_bytes(),
        **({"tpu_custom_calls": kernels} if backend == "pallas" else {}),
    }


def phase_serve() -> dict:
    from repro.launch import serve_gnn
    from repro.launch.compile_cache import enable_compile_cache

    device = _device_facts()
    enable_compile_cache()
    args = serve_gnn.build_parser().parse_args(
        "--dataset cora --engine compiled --stages 4 --qps 20 --duration 2 "
        f"--verify --verify-atol {SERVE_VERIFY_ATOL} --seed {SEED}".split()
    )
    try:
        summary = serve_gnn.run(args)
    except SystemExit as exc:  # --verify found diverging predictions
        return {"ok": False, "device": device, "error": str(exc)}
    ok = summary["queries"] == 40 and summary["verify_mismatches"] == 0
    return {
        "ok": bool(ok),
        "device": device,
        "compile_s": summary["warmup_s"],
        "step_s": summary["eval_call_s"],
        "final_loss": None,
        "max_gap": {"served_vs_full_batch": summary["verify_max_diff"]},
        "verify_exact": summary["verify_exact"],
        "queries": summary["queries"],
        "p50_s": summary["p50_s"],
        "p99_s": summary["p99_s"],
        "peak_bytes": _peak_bytes(),
    }


def phase_ring4() -> dict:
    import jax
    import numpy as np

    from repro.core.cli import PipelineCLIConfig
    from repro.core.microbatch import make_plan
    from repro.core.pipeline import make_engine
    from repro.graphs import load_dataset
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.gnn.net import build_paper_gat
    from repro.train import optimizer as opt_lib

    device = _device_facts()
    _require(device["count"] == 4, f"the ring needs 4 chips: {device}")
    enable_compile_cache()
    g = load_dataset("cora", seed=SEED)
    model = build_paper_gat(g.num_features, g.num_classes)
    plan = make_plan(g, 4, strategy="halo", halo_hops=2, seed=SEED)
    probe = opt_lib.grad_probe()
    cases = {
        "fill_drain-4": PipelineCLIConfig(engine="compiled", schedule="fill_drain",
                                          stages=4, chunks=4),
        "1f1b-4": PipelineCLIConfig(engine="compiled", schedule="1f1b", stages=4, chunks=4),
        "1f1b-dp2x2": PipelineCLIConfig(engine="compiled", schedule="1f1b", stages=2,
                                        chunks=4, data_parallel=2),
    }
    hosts: dict = {}  # (stages, precision) -> the host fill-drain's (loss, grads)
    rows, ok = {}, True
    for name, cli in cases.items():
        engine = make_engine(model, cli.gpipe_config())
        # the timed step is the probe's: forward and backward, no update
        compile_s, step_s, (params, grads, loss), _ = _timed_step(
            engine, plan, probe, SEED, steps=3
        )
        loss = float(loss)
        spans = {d.id for d in jax.tree_util.tree_leaves(params)[0].sharding.device_set}
        _, grads_hi = _grads(engine, plan, probe, SEED)
        host = make_engine(model, PipelineCLIConfig(stages=cli.stages, chunks=4).gpipe_config())
        for precision in ("default", "highest"):
            if (cli.stages, precision) not in hosts:
                hosts[cli.stages, precision] = _grads(host, plan, probe, SEED, precision)
        h_loss, h_grads = hosts[cli.stages, "default"]
        row = {
            "compile_s": compile_s,
            "step_s": step_s,
            "first_step_loss": loss,
            "devices": len(spans),
            "max_gap": {"host_loss": abs(loss - h_loss),
                        "host_grads": _grad_gap(grads_hi, hosts[cli.stages, "highest"][1]),
                        "host_grads_default_precision": _grad_gap(grads, h_grads)},
        }
        row["ok"] = bool(
            len(spans) == 4 and np.isfinite(loss) and _finite(grads_hi)
            and row["max_gap"]["host_loss"] <= LOSS_ATOL
            and row["max_gap"]["host_grads"] <= GRAD_RTOL
        )
        print(f"[chip_smoke] ring4 {name}: {json.dumps(row)}", flush=True)
        rows[name] = row
        ok = ok and row["ok"]
    return {"ok": bool(ok), "device": device, "cases": rows, "peak_bytes": _peak_bytes()}


PHASES = {
    "train-padded": lambda: phase_train("padded"),
    "train-pallas": lambda: phase_train("pallas"),
    "serve": phase_serve,
    "ring4": phase_ring4,
}


# ------------------------------------------------------------- parent --


def _run_phase(name: str, deadline: float) -> dict | None:
    """Run one phase in a child process; its result, or None on failure."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(ROOT, "src"), env.get("PYTHONPATH")) if p
    )
    t0 = time.perf_counter()
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--phase", name],
            capture_output=True, text=True, env=env, cwd=ROOT,
            timeout=max(deadline - time.monotonic(), 1.0),
        )
    except subprocess.TimeoutExpired as exc:
        sys.stderr.write(f"[chip_smoke] phase {name} ran out of time\n")
        sys.stderr.write((exc.stderr or b"")[-4000:].decode(errors="replace")
                         if isinstance(exc.stderr, bytes) else (exc.stderr or "")[-4000:])
        return None
    wall = time.perf_counter() - t0
    result = None
    for line in r.stdout.splitlines():
        if line.startswith(RESULT):
            result = json.loads(line[len(RESULT):])
        else:
            print(line, flush=True)
    if r.returncode != 0 or result is None or not result.get("ok"):
        sys.stderr.write(r.stderr[-8000:])
        sys.stderr.write(f"[chip_smoke] phase {name} failed (exit {r.returncode})\n")
        if result is not None:
            print(f"[chip_smoke] {name}: {json.dumps(result)}", flush=True)
        return None
    result["wall_s"] = wall
    print(f"[chip_smoke] {name}: {json.dumps(result)}", flush=True)
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train and serve on one chip; 4: the stage ring "
                         "across four chips, and nothing else")
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.phase:  # child: run one phase, report it on the last line
        print(RESULT + json.dumps(PHASES[args.phase]()), flush=True)
        return 0

    for name in PALLAS_ENV:
        if name in os.environ:
            sys.stderr.write(
                f"[chip_smoke] {name} is set; unset it to run the kernels as on the chip\n"
            )
            return 1
    deadline = time.monotonic() + BUDGET_S
    phases = ("ring4",) if args.chips == 4 else ("train-padded", "train-pallas", "serve")
    device = None
    for name in phases:
        result = _run_phase(name, deadline)
        if result is None:
            return 1
        device = result["device"]
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
