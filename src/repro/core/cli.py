"""One definition of the pipeline CLI surface.

The five engine/schedule flags (``--engine/--schedule/--chunks/--partition/
--placement``, plus their ``--stages`` / ``--pipe-devices`` companions) used
to be re-declared by every driver and benchmark — ``launch/train.py``,
``launch/serve_gnn.py``, ``benchmarks/fig3.py``, ``benchmarks/fig4.py`` and
the example each carried their own copy, free to drift. ``add_pipeline_args``
puts them on a parser once; ``PipelineCLIConfig`` is the parsed bundle, with
the two translations every caller was hand-rolling:

  * ``gpipe_config(balance)`` — the assembled ``GPipeConfig`` that
    ``make_engine`` consumes (placement string parsed, interleaved's
    default 2-device ring applied, engine name riding along);
  * ``namespace(**extra)`` — an argparse-shaped namespace for drivers such
    as ``run_gnn`` that are invoked programmatically (the benchmarks build
    their sweep cells this way instead of via ``types.SimpleNamespace``
    literals).

``benchmarks/common.py`` re-exports both names for the benchmark scripts.
"""

from __future__ import annotations

import dataclasses
import types

from repro.core.pipeline import GPipeConfig
from repro.core.schedule import Placement

ENGINE_CHOICES = ("host", "compiled")
SCHEDULE_CHOICES = ("fill_drain", "gpipe", "1f1b", "interleaved", "zb-h1", "zb-v")
PARTITION_CHOICES = ("uniform", "profiled")
BACKEND_CHOICES = ("padded", "dense", "pallas")
OVERLAP_CHOICES = ("off", "double-buffer", "async")

# layer-count split of the 6-layer sequential paper model
UNIFORM_BALANCES = {2: (3, 3), 3: (2, 2, 2), 4: (2, 1, 1, 2), 6: (1,) * 6}


def add_pipeline_args(
    ap,
    *,
    engine: str = "host",
    schedule: str = "fill_drain",
    chunks: int = 1,
    stages: int = 1,
    backend: str = "padded",
):
    """Declare the pipeline flag set on ``ap`` (an ``argparse`` parser or
    group). Keyword defaults let each driver keep its own entry point
    defaults (training starts on the host engine, serving on compiled)."""
    ap.add_argument("--engine", default=engine, choices=list(ENGINE_CHOICES),
                    help="pipeline engine: host-driven GPipe queue loop or "
                         "one compiled SPMD program (shard_map/ppermute); both "
                         "accept any --schedule")
    ap.add_argument("--schedule", default=schedule, choices=list(SCHEDULE_CHOICES))
    ap.add_argument("--stages", type=int, default=stages)
    ap.add_argument("--chunks", type=int, default=chunks)
    ap.add_argument("--pipe-devices", type=int, default=None,
                    help="interleaved: physical devices (virtual stages = stages/devices)")
    ap.add_argument("--partition", default="uniform", choices=list(PARTITION_CHOICES),
                    help="stage balance: layer-count split or the cost-model "
                         "partitioner (profiles per-layer fwd/B/W on a padded chunk, "
                         "minimizes the schedule's weighted makespan)")
    ap.add_argument("--placement", default=None,
                    help="stage->device ring placement as comma ints, e.g. "
                         "'1,2,3,0' (validated against the lowering's ring check)")
    ap.add_argument("--backend", default=backend, choices=list(BACKEND_CHOICES),
                    help="aggregation backend for the GNN layers: padded "
                         "neighbor gathers (default), dense masked adjacency, "
                         "or the Pallas kernels over the degree-bucketed "
                         "layout")
    ap.add_argument("--data-parallel", type=int, default=1,
                    help="graph-partition replicas on the data axis of a "
                         "(data, stage) mesh (compiled engine): chunks are "
                         "sharded data_parallel ways, gradients reduced over "
                         "the axis in the canonical chunk order, so the "
                         "update stays bit-identical to 1 replica")
    ap.add_argument("--overlap", default="off", choices=list(OVERLAP_CHOICES),
                    help="communication/compute overlap (compiled engine): "
                         "double-buffer retimes the tick arrays so each "
                         "ppermute pair is posted one tick before its "
                         "arrivals are consumed (bit-identical updates); "
                         "async additionally requests XLA's latency-hiding "
                         "scheduler (core.overlap_report)")
    ap.add_argument("--auto", action="store_true",
                    help="self-tuning planner (core.autotune.plan_pipeline): "
                         "profile per-layer costs once, enumerate schedule x "
                         "chunks x balance x placement, pick the argmin "
                         "predicted step time — overrides --schedule/--chunks/"
                         "--partition/--placement")
    ap.add_argument("--auto-budget", type=int, default=None,
                    help="cap on the number of candidate configurations the "
                         "--auto planner evaluates (ranked enumeration order; "
                         "default: exhaustive)")
    ap.add_argument("--dry-run", action="store_true",
                    help="with --auto: print the ranked candidate table and "
                         "exit without training")
    return ap


@dataclasses.dataclass
class PipelineCLIConfig:
    """The parsed pipeline flag bundle — every driver/benchmark's single
    route from CLI-level knobs to an assembled ``GPipeConfig``."""

    engine: str = "host"
    schedule: str = "fill_drain"
    chunks: int = 1
    stages: int = 1
    partition: str = "uniform"
    placement: str | None = None
    pipe_devices: int | None = None
    backend: str = "padded"
    data_parallel: int = 1
    overlap: str = "off"
    auto: bool = False
    auto_budget: int | None = None
    dry_run: bool = False

    @classmethod
    def from_args(cls, args) -> "PipelineCLIConfig":
        """Lift the flag set off an argparse namespace (missing attributes
        fall back to the flag defaults, so programmatic namespaces may stay
        minimal)."""
        d = {f.name: getattr(args, f.name, f.default) for f in dataclasses.fields(cls)}
        return cls(**d)

    @property
    def resolved_pipe_devices(self) -> int | None:
        """--pipe-devices with the round-robin default applied: interleaved
        and zb-v place V = stages/2 virtual stages on 2 physical devices
        unless told otherwise."""
        if self.schedule in ("interleaved", "zb-v") and self.pipe_devices is None:
            return 2
        return self.pipe_devices

    def parsed_placement(self) -> Placement | None:
        """The --placement comma string as a validated ``Placement``."""
        if not self.placement:
            return None
        return Placement(tuple(int(x) for x in self.placement.split(",")))

    def uniform_balance(self) -> tuple[int, ...]:
        """The layer-count split of the 6-layer paper model for --stages."""
        try:
            return UNIFORM_BALANCES[self.stages]
        except KeyError:
            raise ValueError(
                f"--stages {self.stages} has no uniform split of the 6-layer "
                f"paper model; supported: {sorted(UNIFORM_BALANCES)}"
            ) from None

    def gpipe_config(self, balance=None) -> GPipeConfig:
        """The assembled engine config. ``balance`` defaults to the uniform
        layer-count split; the profiled partitioner passes its own. An
        explicit ``--pipe-devices`` beyond the host's devices is an error
        for the compiled engine, not a quiet run on the one-device lane
        substrate."""
        if self.engine == "compiled" and self.pipe_devices is not None:
            import jax

            if jax.device_count() < self.pipe_devices:
                raise ValueError(
                    f"--pipe-devices {self.pipe_devices} asks for more devices "
                    f"than this host has ({jax.device_count()})"
                )
        return GPipeConfig(
            balance=tuple(balance if balance is not None else self.uniform_balance()),
            chunks=self.chunks,
            schedule=self.schedule,
            num_devices=self.resolved_pipe_devices,
            placement=self.parsed_placement(),
            engine=self.engine,
            backend=self.backend,
            data_parallel=self.data_parallel,
            overlap=self.overlap,
        )

    def namespace(self, **extra) -> types.SimpleNamespace:
        """An argparse-shaped namespace carrying this flag set plus
        driver-specific extras — how the benchmarks invoke ``run_gnn``."""
        d = dataclasses.asdict(self)
        d.update(extra)
        return types.SimpleNamespace(**d)
