"""Fleet-scale FL simulation engine.

Scales the paper's 5-UE Table-I system to 10k-1M clients: pluggable cell
geometry and batched multi-cell channel generation (`topology` —
orthogonal cells by default, or ``HexInterference`` hex cells with
frequency reuse, co-channel SINR, mobility and handover), the closed-form
trade-off solver vmapped over cells on-device with a damped inter-cell
interference fixed point (`solver`), partial participation / stragglers /
round deadlines / handover policies / async arrival times (`scheduler`),
and the full round compiled as a single `jax.lax.scan` with no host
round-trips (`engine`).  Aggregation modes: the paper's synchronous
FedSGD barrier (default), FedBuff-style buffered aggregation with
staleness-discounted merging (``run_fleet(..., mode="async")``,
configured by ``AsyncConfig``), and — orthogonal to both — two-tier
edge/cloud hierarchical aggregation (``FleetConfig(cloud_period=n)``).

Observability is opt-in (`telemetry`): ``FleetConfig(telemetry=
TelemetryConfig(...))`` rides fixed-size per-round summaries (histograms,
staleness / gradient drift, solver diagnostics) through the scan into
``FleetResult.telemetry``; ``SpanRecorder`` captures host phase spans as
Chrome-trace JSON (``process_recorder()`` is the process-wide one, which
also holds JAX's trace/lower/compile spans; ``compile_report()`` sums
them per program), and ``TelemetrySink`` implementations (memory / JSONL
/ CSV) receive per-round records via ``run_fleet(..., sink=...)``.
"""

from repro.fleet.engine import (  # noqa: F401
    FleetConfig, FleetResult, build_simulation, resolve_geometry,
    resolve_task, run, run_fleet, time_to_loss)
from repro.fleet.scheduler import AsyncConfig, ScheduleConfig  # noqa: F401
from repro.fleet.solver import SolverConfig  # noqa: F401
from repro.fleet.telemetry import (  # noqa: F401
    CSVSink, JSONLSink, MemorySink, SpanRecorder, TelemetryConfig,
    TelemetrySink, compile_report, emit_result, process_recorder,
    sink_for_path)
from repro.fleet.task import (  # noqa: F401
    FleetTask, LinearRegressionTask, SyntheticMLPTask, TransformerTask,
    make_task)
from repro.fleet.topology import (  # noqa: F401
    CellGeometry, FleetTopology, HexInterference, OrthogonalCells,
    make_geometry)
