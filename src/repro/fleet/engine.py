"""Scan-compiled fleet rounds: channel -> solver -> FedSGD -> aggregation.

One FL round is: realize the channel through the configured cell geometry
(``fleet/topology.py``: orthogonal annular cells, or hex cells with
frequency reuse, co-channel SINR coupling, mobility and handover), draw
the participation schedule, run the closed-form trade-off solver per cell
(Prop. 1 + Eq. (21), all on-device — under interference the per-cell
solves iterate inside the solver's damped fixed point), train masked
local models (magnitude pruning at each client's rho_i*), lose packets at
the solved PER, aggregate Eq. (5), and track latency /
convergence-bound statistics.  The entire ``rounds`` loop compiles as a
single ``jax.lax.scan`` — zero host round-trips, which is what lets
10k-1M-client runs approach hardware speed.

Aggregation is single-tier by default (every round is a global merge);
``FleetConfig(cloud_period=n)`` switches on the two-tier hierarchy of
arXiv:2305.09042 — per-cell *edge* models aggregate their own clients
every round/event and a backhaul-priced *cloud* merge reconciles the
edges every n rounds/events (sync and async, both kernels).

Two aggregation modes share the per-round control path (``_round_control``):

* ``mode="sync"`` (default) — the paper's FedSGD barrier: every scheduled
  client reports before the server updates, so the round lasts as long as
  the slowest uplink (Eq. 4 makespan).
* ``mode="async"`` — FedBuff-style buffered aggregation: clients report at
  their *own* realized latency (``scheduler.arrival_times``); each scan
  step is one server event that merges the earliest ``buffer_size``
  arrivals with staleness-discounted weights
  (``core.aggregation.buffered_weights``) against a ring buffer of the
  last ``max_staleness + 1`` param versions.  With ``buffer_size = 0``
  (whole cohort) and full participation the event timeline degenerates to
  the round barrier and async equals sync (equivalence-tested).

Data/model: everything task-specific lives behind the ``FleetTask``
protocol (``fleet/task.py``) — the engine only sees ``init_params``,
``client_batch``, ``loss``, ``eval_metrics`` and the fused-kernel hooks.
The default task (built from ``FleetConfig``'s legacy ``feature_dim`` /
``hidden`` / ... fields via ``resolve_task``) is the original
``SyntheticMLPTask`` — bit-identical trajectories to the pre-task engine;
``TransformerTask`` runs production-model causal-LM rounds and
``LinearRegressionTask`` pins exact convergence rates.  Each client's
local batch derives from a *fixed* per-client fold of the data key —
identical samples each round (the FL fixed-local-dataset setting).  Below
``cache_data``'s memory limit the batches are materialized once at build
time; above it they regenerate on the fly inside the scan, so memory
stays bounded by the cell-chunked gradient accumulation (sync) or by
``buffer_size`` (async).  Local batches share one static per-task batch
size (shape-uniform for vmap); the heterogeneous K_i act through
aggregation weights and the latency model, as in the paper's Eqs. (2)-(5).

Client-gradient hot path: ``FleetConfig.kernel`` selects the vmap + AD
"reference" batch or the task's fused kernel hook
(``FleetTask.kernel_grads``): the MLP task streams client tiles through
the block-sparse Pallas/XLA kernels (``kernels/fleet_fused.py``); generic
tasks stream clients through ``fleet_fused.masked_scan_grads`` with
per-layer tile grids (``FleetTask.tile_grid``) — either way compute never
materializes the (clients, params) gradient batch.  See docs/fleet.md.

Sharding: pass a mesh from ``launch.mesh`` and the cell axis of every
population/fading tensor is placed on the mesh's cell axis — "cells" on
a two-axis fleet mesh (``make_fleet_mesh``; the client axis of (C, I)
arrays then also shards over "data"), or "data" on the legacy
single-axis mesh (NamedSharding); inside the round the flattened
*client* axis of the gradient batch is constrained to "data" and the
solver's per-cell batch to the cell axis, so XLA partitions control and
gradient work across devices in both layouts.

Cohort compute: with a partial schedule (or ``cohort_gather=True``) the
control pass emits the schedule as a dense (C, m) index batch
(``scheduler.participation_cohort`` — same single Gumbel draw as the
mask) and the engine gathers weights, pruning rates and client batches
along it before the gradient pass, so the hot path — and the
interference-free Algorithm-1 solve, which runs over the gathered
cohort and scatters back — scales with m, not I.
``FleetConfig.control_chunk`` additionally blocks the solve over cells —
and, in async mode, the per-event rebuild of the (C, I) in-flight state —
bounding the control pass's working set at million-client fleets.
"""

from __future__ import annotations

import contextlib
import dataclasses
import warnings
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import aggregation as AGG
from repro.core import closed_form as CF
from repro.core import pruning, wireless
from repro.core.convergence import ConvergenceBound, SmoothnessParams
from repro.fleet import scheduler as SCHED
from repro.fleet import solver as SOLVER
from repro.fleet import topology as TOPO
from repro.fleet import task as TASK
from repro.fleet import telemetry as TEL

PyTree = Any


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Everything a fleet run needs; all fields have Table-I-flavoured
    defaults.  Units: seconds / Hz / watts follow ``wireless.WirelessConfig``;
    ``weight`` is the dimensionless trade-off lambda of problem (12).

    The *task* (model + data + loss) is ``task``; when None, the legacy
    synthetic-task fields below build a ``SyntheticMLPTask`` (bit-identical
    to the pre-task engine — setting them away from their defaults emits a
    ``DeprecationWarning``; pass ``task=SyntheticMLPTask(...)`` instead).
    """

    topology: TOPO.FleetTopology = dataclasses.field(
        default_factory=TOPO.FleetTopology)
    # Cell geometry (placement + inter-cell coupling): None resolves to
    # TOPO.OrthogonalCells() — the pre-geometry engine, bit-identical.
    # TOPO.HexInterference(...) switches on hex placement, frequency
    # reuse, co-channel SINR coupling (the solver then runs its damped
    # interference fixed point inside the scan), mobility and handover.
    geometry: Optional[TOPO.CellGeometry] = None
    schedule: SCHED.ScheduleConfig = dataclasses.field(
        default_factory=SCHED.ScheduleConfig)
    async_config: SCHED.AsyncConfig = dataclasses.field(
        default_factory=SCHED.AsyncConfig)
    wireless: wireless.WirelessConfig = dataclasses.field(
        default_factory=wireless.WirelessConfig)
    smoothness: SmoothnessParams = dataclasses.field(
        default_factory=SmoothnessParams)
    solver: SOLVER.SolverConfig = dataclasses.field(
        default_factory=SOLVER.SolverConfig)
    weight: float = 0.0004            # lambda
    rounds: int = 50                  # sync rounds / async server events
    lr: float = 1e-2
    seed: int = 0
    # the model-pluggable task substrate (fleet/task.py); None -> legacy
    # fields below via resolve_task()
    task: Optional[TASK.FleetTask] = None
    # DEPRECATED synthetic-task fields (pre-task engine API): used only
    # when task is None, to build the equivalent SyntheticMLPTask
    feature_dim: int = 32
    hidden: tuple[int, ...] = (16,)
    num_classes: int = 4
    local_batch: int = 8
    data_noise: float = 0.5
    test_samples: int = 512
    # gradient accumulation: cells per scan chunk (0 = whole fleet at once)
    cell_chunk: int = 0
    # Cohort compute: gather the scheduled clients into a dense (C, m)
    # batch before the gradient pass (and route the per-cell solver over
    # the gathered cohort when the cells are interference-free), so the
    # hot path scales with cohort size instead of fleet size.  None =
    # auto: on exactly when the schedule is partial.  True forces the
    # gather (a full schedule then gathers the identity cohort — same
    # values in the same order); False pins the legacy full-fleet masked
    # scan.  The schedule draw itself is shared
    # (scheduler.participation_cohort ranks the same single Gumbel
    # tensor), so all control randomness is unchanged; gathered gradient
    # sums reassociate float addition, which is why partial-participation
    # trajectories match the legacy path to ~1e-6 under x64 rather than
    # bitwise (tests/test_cohort_equivalence.py pins the matrix).
    cohort_gather: Optional[bool] = None
    # Control-pass chunking: cells per solver block (0 = all cells in one
    # vmap).  Bounds the Algorithm-1 working set (the solver's while_loop
    # temporaries are the control pass's memory peak at 1M clients);
    # random draws stay full-shape and frozen solver lanes are
    # idempotent, so chunked solves are bit-identical to the global vmap.
    # Ignored when an interference graph couples the cells (the damped
    # SINR fixed point is global by construction) or when a custom
    # solve_fn is plugged in.  In async mode the same knob also blocks
    # the per-event rebuild of the (C, I) in-flight carry (_start_state),
    # again bit-identically — the rebuild is elementwise over cells.
    control_chunk: int = 0
    # client-gradient hot path: "reference" is the vmap + AD batch;
    # "fused" runs the task's fused kernel hook (the MLP task streams
    # tiles of clients through kernels/fleet_fused.py and never
    # materializes the (clients, params) gradient batch; generic tasks
    # stream clients through masked_scan_grads on their per-layer tile
    # grids).  "fused_xla" / "fused_pallas" pin the MLP-kernel
    # implementation (fused = Pallas on TPU, XLA elsewhere; Pallas runs
    # interpret off-TPU).
    kernel: str = "reference"
    # reference-path mask rule: "magnitude" (paper-style unstructured)
    # or "block" (block-norm threshold masks on the task's tile grid —
    # what the fused path always uses; set it on the reference path to
    # equivalence-test fused trajectories)
    mask_kind: str = "magnitude"
    # block edge for the legacy SyntheticMLPTask's block pruning (small:
    # the fleet MLP's matrices are far below one 128x128 MXU pass);
    # explicit tasks carry their own grids (FleetTask.tile_grid)
    prune_block: int = 8
    # Materialize every client's (fixed) local batch once at build time
    # instead of re-deriving it from the PRNG inside every scan step —
    # identical draws, amortized threefry/erfinv cost.  None = auto: cache
    # unless the per-client batches would exceed ~512 MB (the 1M-client
    # regime keeps the streaming regeneration).
    cache_data: Optional[bool] = None
    # Two-tier hierarchical aggregation (cf. arXiv:2305.09042): 0 (the
    # default) is the paper's single-tier global step.  n >= 1 keeps a
    # per-cell *edge* model that aggregates its own clients every round
    # (sync) / event (async) and merges into the cloud model every n
    # rounds/events, priced at the wireless backhaul
    # (WirelessConfig.backhaul_s).  cloud_period = 1 merges every round —
    # numerically the single-tier rule (within summation-order float
    # noise), which is what pins the implementation.
    cloud_period: int = 0
    # Non-IID client data: Dirichlet concentration of the per-client
    # label / token-pool skew inside the default SyntheticMLPTask (None =
    # IID, bit-identical draws).  Explicit tasks carry their own
    # dirichlet_alpha field; setting both is an error.
    dirichlet_alpha: Optional[float] = None
    # Opt-in in-scan telemetry (fleet/telemetry.py): fixed-size per-round
    # summaries — per-cell PER/SINR/latency/rho/bandwidth histograms,
    # staleness distribution (async), gradient-norm / mask-density drift,
    # solver diagnostics — ride the scan as extra ``tel_*`` metric keys
    # and come out as ``FleetResult.telemetry``.  None (the default)
    # leaves the compiled program structurally unchanged: trajectories
    # are bit-identical to a build without the telemetry module.
    telemetry: Optional[TEL.TelemetryConfig] = None


_LEGACY_TASK_FIELDS = ("feature_dim", "hidden", "num_classes", "local_batch",
                       "data_noise", "test_samples")


def resolve_task(cfg: FleetConfig) -> TASK.FleetTask:
    """The run's task: ``cfg.task``, or the legacy-field SyntheticMLPTask.

    Non-default legacy task fields with no explicit task emit a
    ``DeprecationWarning`` — the old ``FleetConfig(feature_dim=...,
    hidden=...)`` API keeps producing bit-identical trajectories through
    the shim, but new code should pass ``task=SyntheticMLPTask(...)``.
    """
    if cfg.task is not None:
        if cfg.dirichlet_alpha is not None:
            raise ValueError(
                "FleetConfig.dirichlet_alpha only applies to the default "
                "SyntheticMLPTask; set dirichlet_alpha on the explicit "
                "task instead (both SyntheticMLPTask and TransformerTask "
                "carry the field).")
        return cfg.task
    defaults = {f.name: f.default for f in dataclasses.fields(FleetConfig)}

    def norm(v):  # list-vs-tuple spellings of the same value are equal
        return tuple(v) if isinstance(v, (list, tuple)) else v

    if any(norm(getattr(cfg, n)) != norm(defaults[n])
           for n in _LEGACY_TASK_FIELDS):
        warnings.warn(
            "FleetConfig's synthetic-task fields (feature_dim, hidden, "
            "num_classes, local_batch, data_noise, test_samples) are "
            "deprecated; pass FleetConfig(task=SyntheticMLPTask(...)) "
            "instead.", DeprecationWarning, stacklevel=3)
    return TASK.SyntheticMLPTask(
        feature_dim=cfg.feature_dim, hidden=tuple(cfg.hidden),
        num_classes=cfg.num_classes, local_batch=cfg.local_batch,
        data_noise=cfg.data_noise, test_samples=cfg.test_samples,
        prune_block=cfg.prune_block, dirichlet_alpha=cfg.dirichlet_alpha)


def resolve_geometry(cfg: FleetConfig) -> TOPO.CellGeometry:
    """The run's cell geometry: ``cfg.geometry`` or orthogonal cells."""
    return cfg.geometry if cfg.geometry is not None else TOPO.OrthogonalCells()


@dataclasses.dataclass
class FleetResult:
    """Per-round (sync) / per-server-event (async) trajectories.

    ``latencies`` is the realized duration of each round/event in seconds;
    ``wall_clock`` is its cumulative sum — the simulated time axis, which
    is what makes sync-vs-async time-to-target-loss comparable.
    ``staleness`` is the cohort-mean merge age in server versions (all
    zeros for sync).  ``telemetry`` holds the opt-in in-scan summaries
    (``FleetConfig.telemetry``) keyed without their ``tel_`` scan prefix
    — ``per_hist`` / ``sinr_hist`` / ``grad_norm`` / ... — or None when
    telemetry was off.
    """

    losses: np.ndarray            # (rounds,)
    accuracy: np.ndarray          # (rounds,) task eval metric
    latencies: np.ndarray         # (rounds,) realized round latency, s (Eq. 4)
    deadlines: np.ndarray         # (rounds, C) solver deadlines t~*, s
    mean_prune: np.ndarray        # (rounds,) scheduled-client mean rho
    mean_per: np.ndarray          # (rounds,) effective per-client loss prob
    participants: np.ndarray      # (rounds,) clients aggregated per round
    bandwidth_util: np.ndarray    # (rounds, C) sum B_i / B per cell
    learning_cost: np.ndarray     # (rounds,) m-weighted Eq. (11) sum, fleet
    bound_final: float            # Theorem 1 on realized averages
    params: PyTree
    wall_clock: np.ndarray = None  # (rounds,) cumulative simulated time, s
    staleness: np.ndarray = None   # (rounds,) mean merge age, versions
    mode: str = "sync"
    telemetry: Optional[dict] = None  # opt-in in-scan summaries (no prefix)


_CACHE_LIMIT_BYTES = 512 << 20


def _make_batch_fn(task: TASK.FleetTask, state: PyTree, cfg: FleetConfig,
                   data_key: jax.Array):
    """flat client indices -> batch pytree (every leaf leading-dim clients).

    When the whole fleet's data fits ``_CACHE_LIMIT_BYTES`` (or
    ``cfg.cache_data`` forces it), every client's fixed batch is derived
    from the PRNG *once* here and scan steps just gather rows — the draws
    are bit-identical to the streaming path, which re-runs
    ``task.client_batch`` inside the scan and stays the default above the
    memory limit.
    """
    n = cfg.topology.num_clients

    def generate(flat_idx):
        return jax.vmap(
            lambda ci: task.client_batch(state, data_key, ci))(flat_idx)

    cache = cfg.cache_data
    if cache is None:
        shapes = jax.eval_shape(generate,
                                jax.ShapeDtypeStruct((n,), jnp.int32))
        nbytes = sum(int(np.prod(l.shape, dtype=np.int64)) * l.dtype.itemsize
                     for l in jax.tree_util.tree_leaves(shapes))
        cache = task.cache_batches and nbytes <= _CACHE_LIMIT_BYTES
    if not cache:
        return generate, None
    data = generate(jnp.arange(n, dtype=jnp.int32))

    def gather(flat_idx):
        return jax.tree.map(lambda a: a[flat_idx], data)

    return gather, data


def _client_grad(task: TASK.FleetTask, params: PyTree, rho_i: jnp.ndarray,
                 batch: PyTree, cfg: FleetConfig, mask_kind: str = None,
                 thresh: jnp.ndarray = None) -> tuple[jnp.ndarray, PyTree]:
    """Masked local gradient: rho-level masks, grad at the pruned point,
    gradient re-masked (exactly the 5-client path's client_grad).  The
    mask rule follows ``mask_kind`` (default ``cfg.mask_kind``):
    unstructured magnitude pruning (paper-style) or block-norm threshold
    masks on the task's tile grid (the fused kernel's).  ``thresh``, where
    given, is rho_i's magnitude threshold computed beforehand
    (``pruning.magnitude_thresholds``), and the masks only compare with it.

    Callers ``vmap`` it over clients, and JAX names the first scope under
    a ``vmap`` ``vmap(<scope>)`` in the ``op_name`` path: ``fleet.client``
    takes that place, so ``fleet.mask`` and ``fleet.backprop`` keep their
    own names there."""
    with jax.named_scope("fleet.client"):
        with jax.named_scope("fleet.mask"):
            if thresh is not None:
                masks = pruning.threshold_masks(params, thresh)
            elif (mask_kind or cfg.mask_kind) == "block":
                masks = pruning.block_masks(params, rho_i,
                                            block=task.tile_grid(params))
            else:
                masks = pruning.magnitude_masks(params, rho_i)
            pruned = pruning.apply_masks(params, masks)
        with jax.named_scope("fleet.backprop"):
            loss, g = jax.value_and_grad(
                lambda p: task.loss(p, batch))(pruned)
        with jax.named_scope("fleet.mask"):
            return loss, pruning.apply_masks(g, masks)


def _kernel_impl(cfg: FleetConfig) -> str:
    return {"fused": "auto", "fused_xla": "xla",
            "fused_pallas": "pallas"}[cfg.kernel]


def _chunk_accumulate(step, arrays: tuple, chunk: int):
    """Sum ``step(*slice)`` over consecutive axis-0 slices of ``arrays``.

    Full ``chunk``-sized slices run under one ``lax.scan``; a ragged
    remainder runs as one exact-sized call.  Unlike zero-padding the last
    chunk, no phantom rows ever reach the batch builder or the backward
    pass — padding previously cost up to ``chunk - 1`` cells of dead
    gradient work per round.
    """
    c = arrays[0].shape[0]
    n_full = c // chunk
    rem = c - n_full * chunk
    out = None
    if n_full:
        stacked = tuple(
            a[:n_full * chunk].reshape((n_full, chunk) + a.shape[1:])
            for a in arrays)
        shapes = jax.eval_shape(step, *(a[0] for a in stacked))
        init = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)

        def body(acc, sl):
            return jax.tree.map(jnp.add, acc, step(*sl)), None

        out, _ = jax.lax.scan(body, init, stacked)
    if rem:
        tail = step(*(a[n_full * chunk:] for a in arrays))
        out = tail if out is None else jax.tree.map(jnp.add, out, tail)
    return out


def _constrain_clients(tree, mesh):
    """Constrain the leading (flat client) axis of batch leaves to the mesh
    "data" axis — the fleet gradient batch shards over devices client-wise
    (the ROADMAP's client-axis sharding direction)."""
    if mesh is None or "data" not in mesh.axis_names:
        return tree
    n = mesh.shape["data"]

    def put(a):
        if a.ndim >= 1 and a.shape[0] % n == 0:
            return jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, P("data")))
        return a

    return jax.tree.map(put, tree)


def _fleet_grads(task: TASK.FleetTask, params: PyTree, rho: jnp.ndarray,
                 agg_w: jnp.ndarray, sched_w: jnp.ndarray, batch_fn,
                 cfg: FleetConfig, data=None, mesh=None, cohort=None):
    """Weighted-sum gradients over the fleet, cell-chunked.

    Returns (grad_wsum pytree, sum agg_w, mean scheduled loss).  agg_w is
    K_i * C_i (Eq. 5 numerator weight, zero for lost/unscheduled clients);
    sched_w weights the loss metric (scheduled clients).

    ``cfg.kernel`` picks the hot path: "reference" vmaps per-client AD
    and reduces the (clients, params) gradient batch; "fused*" builds the
    round's block-ranking state once (``task.kernel_prepare``) and streams
    client tiles through ``task.kernel_grads`` so only the accumulated sum
    is ever materialized.

    ``cohort`` (the control pass's (C, m) scheduled index batch) gathers
    every per-client input — weights, pruning rates, cached batches, or
    the streaming batch indices — into the dense cohort batch *before*
    the chunk scan, so local training, the fused kernels' client axis and
    the Eq.-(5) reduction all run over C*m clients instead of C*I.
    Unscheduled clients carry zero aggregation weight, so dropping them
    changes only the association of the float sums (~1e-6 under x64).

    ``data`` is the optional cached batch pytree from ``_make_batch_fn``
    — when present, batches ride the chunk scan as contiguous slices
    (a general gather over a 100 MB table thrashes caches at 100k+
    clients; the cohort path gathers m/I of the rows up front instead);
    otherwise ``batch_fn`` regenerates them per chunk — on the cohort
    path only the scheduled clients' batches are ever derived.
    """
    c, i = rho.shape
    idx = jnp.arange(c * i, dtype=jnp.int32).reshape(rho.shape)
    if cohort is not None:
        take = lambda a: jnp.take_along_axis(a, cohort, axis=-1)
        idx, rho = take(idx), take(rho)
        agg_w, sched_w = take(agg_w), take(sched_w)
        i = cohort.shape[-1]
    chunk = cfg.cell_chunk if 0 < cfg.cell_chunk < c else c

    arrays = [idx, rho, agg_w, sched_w]
    data_def = None
    if data is not None:
        data_leaves, data_def = jax.tree_util.tree_flatten(data)
        if cohort is not None:
            flat = idx.reshape(-1)
            with jax.named_scope("fleet.data"):
                arrays += [a[flat].reshape((c, i) + a.shape[1:])
                           for a in data_leaves]
        else:
            arrays += [a.reshape((c, i) + a.shape[1:]) for a in data_leaves]

    def batches(c_idx, extra):
        with jax.named_scope("fleet.data"):
            if extra:
                leaves = [a.reshape((-1,) + a.shape[2:]) for a in extra]
                return jax.tree_util.tree_unflatten(data_def, leaves)
            return batch_fn(c_idx.reshape(-1))

    if cfg.kernel == "reference":
        # a large model's magnitude thresholds come from one selection for
        # the whole chunk, outside the client vmap (the weights are shared)
        select = cfg.mask_kind == "magnitude" and \
            pruning.uses_selection(params)

        def step(c_idx, c_rho, c_w, c_lw, *extra):
            batch = _constrain_clients(batches(c_idx, extra), mesh)
            if select:
                with jax.named_scope("fleet.mask"), \
                        jax.named_scope("fleet.mask.select"):
                    thresh = pruning.magnitude_thresholds(
                        params, c_rho.reshape(-1))
                losses, grads = jax.vmap(
                    lambda b, t: _client_grad(task, params, None, b, cfg,
                                              thresh=t)
                )(batch, thresh)
            else:
                losses, grads = jax.vmap(
                    lambda b, ri: _client_grad(task, params, ri, b, cfg)
                )(batch, c_rho.reshape(-1))
            w_flat = c_w.reshape(-1)
            lw_flat = c_lw.reshape(-1)
            g = jax.tree.map(
                lambda g: jnp.einsum("c,c...->...", w_flat, g), grads)
            return (g, jnp.sum(w_flat), jnp.sum(losses * lw_flat),
                    jnp.sum(lw_flat))
    else:
        # once per round: the full ranking of every layer's tile norms —
        # per-client masks below are one searchsorted each
        with jax.named_scope("fleet.mask"):
            prep = task.kernel_prepare(params)

        def step(c_idx, c_rho, c_w, c_lw, *extra):
            batch = _constrain_clients(batches(c_idx, extra), mesh)
            w_flat = c_w.reshape(-1)
            with jax.named_scope("fleet.backprop"):
                g, losses = task.kernel_grads(params, prep, batch,
                                              c_rho.reshape(-1), w_flat,
                                              impl=_kernel_impl(cfg))
            lw_flat = c_lw.reshape(-1)
            return (g, jnp.sum(w_flat), jnp.sum(losses * lw_flat),
                    jnp.sum(lw_flat))

    g_wsum, w_sum, loss_sum, loss_w = _chunk_accumulate(
        step, tuple(arrays), chunk)
    mean_loss = loss_sum / jnp.maximum(loss_w, 1.0)
    return g_wsum, w_sum, mean_loss


def _cohort_enabled(cfg: FleetConfig) -> bool:
    """Resolve ``cfg.cohort_gather``: auto (None) turns the cohort path on
    exactly when the schedule is partial — the only case where the gather
    shrinks the compute batch."""
    if cfg.cohort_gather is not None:
        return bool(cfg.cohort_gather)
    s = cfg.schedule
    return (s.participation != "full"
            and 0 < s.participants_per_cell < cfg.topology.clients_per_cell)


class RoundControl(NamedTuple):
    """One key's worth of per-round system state, identical for both modes:
    channel draw, schedule draw, solver output, realized latencies."""

    mask: jnp.ndarray       # (C, I) participation
    strag: jnp.ndarray      # (C, I) survived straggler churn
    arrivals: jnp.ndarray   # (C, I) packet success indicators (pre-masking)
    sol: SOLVER.CellSolution
    t_client: jnp.ndarray   # (C, I) realized downlink+compute+uplink, s
    m_round: jnp.ndarray    # (C,) scheduled-subset Eq.-(11) coefficient
    # realized per-client uplink SINR in dB — only computed under
    # telemetry (the SINR histogram's input); None otherwise
    sinr_db: Optional[jnp.ndarray] = None
    # (C, m) scheduled client indices (ascending per cell) when the
    # cohort path is on — the gradient pass gathers its dense compute
    # batch along these; None on the legacy full-fleet path
    cohort: Optional[jnp.ndarray] = None


def _solve_cells_chunked(chunk: int, h_up, num_samples, cpu_hz, tx_power,
                         max_prune, m_round, mask, cap, **kw):
    """``SOLVER.solve_fleet`` over consecutive blocks of cells.

    Full ``chunk``-sized blocks run under one ``lax.map``; a ragged
    remainder runs as one exact-sized call.  The cells are independent
    (no interference here — the caller guards that) and frozen Algorithm-1
    lanes are idempotent under extra iterations, so the concatenated
    solutions are bit-identical to the single global vmap; only the
    solver's peak working set changes (``chunk`` cells instead of C).
    """
    arrays = [h_up, num_samples, cpu_hz, tx_power, max_prune, m_round, mask]
    has_cap = cap is not None
    if has_cap:
        arrays.append(cap)

    def solve_block(blk):
        blk = list(blk)
        cap_b = blk.pop() if has_cap else None
        return SOLVER.solve_fleet(blk[0], blk[1], blk[2], blk[3], blk[4],
                                  blk[5], blk[6], cap_b, **kw)

    c = h_up.shape[0]
    chunk = min(chunk, c)
    n_full = c // chunk
    rem = c - n_full * chunk
    parts = []
    if n_full:
        stacked = tuple(
            a[:n_full * chunk].reshape((n_full, chunk) + a.shape[1:])
            for a in arrays)
        mapped = jax.lax.map(solve_block, stacked)
        parts.append(jax.tree.map(
            lambda a: a.reshape((n_full * chunk,) + a.shape[2:]), mapped))
    if rem:
        parts.append(solve_block(tuple(a[n_full * chunk:] for a in arrays)))
    if len(parts) == 1:
        return parts[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *parts)


def _make_control_fn(cfg: FleetConfig, pop: TOPO.ClientPopulation,
                     solve_fn=None, mesh=None):
    """Build the per-key control pass shared by the sync round and the
    async start/restart: channel -> schedule -> solver -> latency -> packet
    draws.  Both modes consume keys in the same order, which is what makes
    the buffer-equals-cohort async run reproduce sync draws exactly.

    The channel realization comes from the configured ``CellGeometry``
    (``cfg.geometry``); when it reports an interference graph the solver
    runs its damped SINR fixed point (still inside this one traced
    function — the engine stays a single scan) and the realized uplink
    latencies price the converged interference PSD.

    On the cohort path (``_cohort_enabled``) the schedule is also emitted
    as a dense (C, m) index batch; interference-free fleets then run the
    Algorithm-1 solve over the *gathered* cohort arrays and scatter the
    solution back, with non-cohort clients taking exactly the fill the
    full solve gives non-participants (rho = 0, B = 0, q = 0 — so
    everything downstream of the solver, including the packet draw
    shapes, is unchanged).  ``cfg.control_chunk`` further blocks the
    solve over cells so the solver's working set stays bounded at
    million-client fleets (bit-identical: frozen lanes are idempotent).

    ``solve_fn(h_up, mask, m_round, cap, interference) -> CellSolution``
    swaps the on-device vmapped solver for another implementation — the
    5-UE host reference path (``federated/system.py``) plugs the numpy
    ``solve_alternating`` (with its own host-side fixed point) in here, so
    *every* draw and latency term stays this one code path and the
    cross-path equivalence can only be broken by the solvers themselves.
    """
    w = cfg.wireless
    n0, b_hz = w.noise_psd_w_per_hz, w.bandwidth_hz
    geo = resolve_geometry(cfg)
    tcfg = cfg.telemetry
    use_cohort = _cohort_enabled(cfg)

    def control(rkey: jax.Array) -> RoundControl:
        k_fade, k_part, k_strag, k_arr = jax.random.split(rkey, 4)

        with jax.named_scope("fleet.channel"):
            chan = geo.round_channel(k_fade, pop, cfg.topology)
        h_up, h_down = chan.h_up, chan.h_down
        with jax.named_scope("fleet.schedule"):
            if use_cohort:
                mask, cohort = SCHED.participation_cohort(
                    k_part, cfg.schedule, pop.num_samples)
            else:
                mask = SCHED.participation_mask(k_part, cfg.schedule,
                                                pop.num_samples)
                cohort = None
            ho = SCHED.handover_mask(chan.served_home, cfg.schedule)
            if ho is not None:
                mask = mask * ho
            # The round's Eq.-(11) surrogate coefficient is the *scheduled*
            # subset's: under partial participation each cell's one-round
            # subproblem is over the drawn clients, not the full census.
            m_round = CF.surrogate_m(pop.num_samples, cfg.smoothness.beta,
                                     cfg.smoothness.xi1, cfg.smoothness.xi2,
                                     cfg.smoothness.weight_bound, xp=jnp,
                                     mask=mask)

            # Broadcast latency is fixed before the uplink control problem,
            # so a configured round deadline caps the solver's t~ by what
            # remains after the downlink + aggregation (time-triggered FL).
            r_d = CF.downlink_rate(b_hz, w.tx_power_bs_w, h_down, n0, xp=jnp)
            t_d = jnp.max(jnp.where(mask > 0, w.model_bits / r_d, 0.0),
                          axis=-1, keepdims=True)
            cap = None
            if cfg.schedule.has_deadline:
                cap = jnp.maximum(cfg.schedule.round_deadline_s
                                  - w.aggregation_latency_s - t_d[..., 0],
                                  0.0)

        solve_kw = dict(
            bandwidth_hz=b_hz, noise_psd=n0, waterfall_m0=w.waterfall_m0,
            model_bits=w.model_bits, cycles_per_sample=w.cycles_per_sample,
            weight=cfg.weight, solver=cfg.solver)
        gathered = (use_cohort and solve_fn is None
                    and chan.interference is None
                    and cohort.shape[-1] < mask.shape[-1])
        with jax.named_scope("fleet.solve"):
            if solve_fn is not None:
                sol = solve_fn(h_up, mask, m_round, cap, chan.interference)
            elif gathered:
                # Solve the dense cohort system: the per-cell vertex walk
                # and bandwidth inversion run over m gathered clients, not
                # the whole census.  The solver treats masked-out clients
                # as inert exactly (rho = B = q = 0, breakpoints at +inf),
                # so scattering those fills back reproduces the full
                # solve's fleet-shaped fields; per-cell reductions
                # (deadline, inner cost) reassociate float sums, hence the
                # cohort path's ~1e-6 (not bitwise) equivalence.
                takec = lambda a: jnp.take_along_axis(a, cohort, axis=-1)
                args_c = (takec(h_up), takec(pop.num_samples),
                          takec(pop.cpu_hz), takec(pop.tx_power),
                          takec(pop.max_prune), m_round, takec(mask), cap)
                if 0 < cfg.control_chunk < mask.shape[0]:
                    sol_c = _solve_cells_chunked(cfg.control_chunk, *args_c,
                                                 **solve_kw)
                else:
                    sol_c = SOLVER.solve_fleet(*args_c, **solve_kw)
                rows = jnp.arange(mask.shape[0])[:, None]

                def scat(v):
                    full = jnp.zeros(mask.shape, v.dtype)
                    return full.at[rows, cohort].set(v)

                sol = sol_c._replace(prune=scat(sol_c.prune),
                                     bandwidth=scat(sol_c.bandwidth),
                                     per=scat(sol_c.per))
            elif (0 < cfg.control_chunk < mask.shape[0]
                  and chan.interference is None):
                sol = _solve_cells_chunked(
                    cfg.control_chunk, h_up, pop.num_samples, pop.cpu_hz,
                    pop.tx_power, pop.max_prune, m_round, mask, cap,
                    **solve_kw)
            else:
                sol = SOLVER.solve_fleet(
                    h_up, pop.num_samples, pop.cpu_hz, pop.tx_power,
                    pop.max_prune, m_round, mask, cap,
                    interference=chan.interference,
                    diagnostics=tcfg is not None and tcfg.solver,
                    mesh=mesh, **solve_kw)

        # Realized per-client latency (Eq. 4 terms, broadcast over cells);
        # with interference the realized uplink rate prices the solver's
        # converged co-channel PSD (SINR, not SNR).
        i_psd = 0.0 if sol.interference_psd is None \
            else sol.interference_psd[:, None]
        t_c = CF.training_latency(sol.prune, pop.num_samples,
                                  w.cycles_per_sample, pop.cpu_hz, xp=jnp)
        r_u = CF.uplink_rate(sol.bandwidth, pop.tx_power, h_up, n0,
                             interference_psd=i_psd, xp=jnp)
        t_u = CF.upload_latency(sol.prune, w.model_bits, r_u, xp=jnp)
        t_client = t_d + t_c + t_u

        # The SINR histogram's input: only computed when telemetry asks
        # for it (no PRNG involved, so the draw sequence is unchanged).
        sinr_db = None
        if tcfg is not None:
            sinr = CF.uplink_sinr(sol.bandwidth, pop.tx_power, h_up, n0,
                                  interference_psd=i_psd, xp=jnp)
            sinr_db = 10.0 * jnp.log10(sinr)

        strag = SCHED.straggler_mask(k_strag, cfg.schedule, mask.shape)
        # Packet indicators C_i ~ Bernoulli(1 - q_i), drawn up-front (the
        # outcome is decided at transmission; async merges it later).
        arrivals = (jax.random.uniform(k_arr, sol.per.shape)
                    >= sol.per).astype(jnp.result_type(float))
        return RoundControl(mask=mask, strag=strag, arrivals=arrivals,
                            sol=sol, t_client=t_client, m_round=m_round,
                            sinr_db=sinr_db, cohort=cohort)

    return control


# ---------------------------------------------------------------------------
# Synchronous (barrier) rounds
# ---------------------------------------------------------------------------

def _merge_eval(metrics: dict, task: TASK.FleetTask, state: PyTree,
                params: PyTree) -> dict:
    """Fold the task's eval metrics into the round metrics ("accuracy" is
    required; extra task metrics ride along under an ``eval_`` prefix)."""
    ev = dict(task.eval_metrics(state, params))
    metrics["accuracy"] = ev.pop("accuracy")
    metrics.update({f"eval_{k}": v for k, v in ev.items()})
    return metrics


def _round_activity(cfg: FleetConfig, pop: TOPO.ClientPopulation,
                    ctl: RoundControl):
    """(active, arrivals, agg_w) masks of a sync round/edge round: who was
    scheduled, survived churn, beat the deadline, and landed a packet."""
    w = cfg.wireless
    on_time = SCHED.on_time_mask(ctl.t_client + w.aggregation_latency_s,
                                 cfg.schedule)
    active = ctl.mask * ctl.strag * on_time
    arrivals = ctl.arrivals * active
    return active, arrivals, pop.num_samples * arrivals        # K_i C_i


def _round_metrics(cfg: FleetConfig, pop: TOPO.ClientPopulation,
                   ctl: RoundControl, active, arrivals, mean_loss):
    """The sync round's metric dict (minus task eval) + the q_eff field.

    The effective loss prob folds scheduling, stragglers and deadline
    misses into q — the Theorem-1 view of partial participation."""
    w = cfg.wireless
    mask, sol, t_client = ctl.mask, ctl.sol, ctl.t_client
    makespan = jnp.max(jnp.where(mask > 0, t_client, -jnp.inf), axis=-1) \
        + w.aggregation_latency_s
    round_lat = jnp.max(SCHED.clamp_round_latency(makespan, cfg.schedule))
    n_sched = jnp.maximum(jnp.sum(mask), 1.0)
    q_eff = 1.0 - active * (1.0 - sol.per)
    k_all = pop.num_samples
    learning = jnp.sum(
        ctl.m_round[:, None] * k_all * (q_eff + k_all * sol.prune) * mask)
    metrics = {
        "loss": mean_loss,
        "round_latency": round_lat,
        "deadline": sol.deadline,
        "mean_prune": jnp.sum(sol.prune * mask) / n_sched,
        "mean_per": jnp.sum(q_eff * mask) / n_sched,
        "participants": jnp.sum(arrivals),
        "bandwidth_util": jnp.sum(sol.bandwidth, axis=-1) / w.bandwidth_hz,
        "learning_cost": learning,
    }
    if cfg.telemetry is not None:
        metrics.update(TEL.control_summaries(
            cfg.telemetry, sol, t_client, ctl.sinr_db, w.bandwidth_hz))
    return metrics, q_eff


def _make_apply_round_fn(cfg: FleetConfig, task: TASK.FleetTask,
                         state: PyTree, pop: TOPO.ClientPopulation,
                         batch_fn, data, mesh=None):
    """The model/aggregation half of a sync round: consume a RoundControl
    (from the scan's on-device solver *or* a host-side reference solver —
    how ``federated/system.py`` reuses this) and produce the FedSGD update
    plus metrics."""

    def apply_round(carry, ctl: RoundControl):
        params, per_sum, prune_sum = carry
        mask, sol = ctl.mask, ctl.sol
        active, arrivals, agg_w = _round_activity(cfg, pop, ctl)

        with jax.named_scope("fleet.gradient"):
            g_wsum, w_sum, mean_loss = _fleet_grads(
                task, params, sol.prune, agg_w, mask, batch_fn, cfg,
                data=data, mesh=mesh, cohort=ctl.cohort)
        denom = jnp.where(w_sum > 0, w_sum, 1.0)
        with jax.named_scope("fleet.merge"):
            new_params = jax.tree.map(
                lambda p, g: jnp.where(
                    w_sum > 0, (p - cfg.lr * g / denom).astype(p.dtype), p),
                params, g_wsum)

        metrics, q_eff = _round_metrics(cfg, pop, ctl, active, arrivals,
                                        mean_loss)
        tcfg = cfg.telemetry
        if tcfg is not None and tcfg.gradients:
            n_sched = jnp.maximum(jnp.sum(mask), 1.0)
            metrics.update(TEL.grad_summaries(
                tcfg, TEL.tree_sq_norm(g_wsum) / (denom * denom),
                jnp.sum((1.0 - sol.prune) * mask) / n_sched))
        with jax.named_scope("fleet.eval"):
            metrics = _merge_eval(metrics, task, state, new_params)
        return (new_params, per_sum + q_eff, prune_sum + sol.prune * mask), \
            metrics

    return apply_round


def _make_round_fn(cfg: FleetConfig, task: TASK.FleetTask, state: PyTree,
                   pop: TOPO.ClientPopulation, data_key: jax.Array,
                   mesh=None):
    control = _make_control_fn(cfg, pop, mesh=mesh)
    batch_fn, data = _make_batch_fn(task, state, cfg, data_key)
    apply_round = _make_apply_round_fn(cfg, task, state, pop, batch_fn, data,
                                       mesh=mesh)

    def round_fn(carry, rkey):
        return apply_round(carry, control(rkey))

    return round_fn


# ---------------------------------------------------------------------------
# Two-tier hierarchical aggregation (edge per cell, periodic cloud merge)
# ---------------------------------------------------------------------------

def _cloud_view(edge: PyTree, acc_w: jnp.ndarray,
                k_cell: jnp.ndarray) -> PyTree:
    """Weighted mean of the per-cell edge models — the Eq.-(5) rule one
    tier up (reuses ``core.aggregation.aggregate`` so the merge rule stays
    the shared, equivalence-tested implementation).

    Each cell weighs in with the Eq.-(5) weight mass it actually merged
    since the last cloud sync (``acc_w``); with ``cloud_period = 1`` the
    merged cloud model is then *algebraically* the single-tier global
    update — the degeneracy that pins the implementation.  A period with
    no arrivals anywhere falls back to the static per-cell sample totals
    (an unweighted data-size mean of unchanged edges).
    """
    w = jnp.where(jnp.sum(acc_w) > 0, acc_w, k_cell)
    return AGG.aggregate(edge, w, jnp.ones_like(w))


def _cell_grad_step(task: TASK.FleetTask, cfg: FleetConfig, params_c: PyTree,
                    rho_c, agg_w_c, sched_w_c, batch_c):
    """One cell's weighted gradient sums *at that cell's edge params*.

    The per-cell analogue of ``_fleet_grads``'s chunk step: the reference
    path vmaps per-client AD, the fused path runs the task's streaming
    kernel with the cell's own ranking state — both kernels drive the
    edge tier.
    """
    if cfg.kernel == "reference":
        losses, grads = jax.vmap(
            lambda b, ri: _client_grad(task, params_c, ri, b, cfg)
        )(batch_c, rho_c)
        g = jax.tree.map(
            lambda gg: jnp.einsum("c,c...->...", agg_w_c, gg), grads)
    else:
        with jax.named_scope("fleet.mask"):
            prep = task.kernel_prepare(params_c)
        with jax.named_scope("fleet.backprop"):
            g, losses = task.kernel_grads(params_c, prep, batch_c, rho_c,
                                          agg_w_c, impl=_kernel_impl(cfg))
    return (g, jnp.sum(agg_w_c), jnp.sum(losses * sched_w_c),
            jnp.sum(sched_w_c))


def _make_two_tier_round_fn(cfg: FleetConfig, task: TASK.FleetTask,
                            state: PyTree, pop: TOPO.ClientPopulation,
                            data_key: jax.Array, mesh=None):
    """Sync two-tier round: per-cell edge FedSGD every round, cloud merge
    every ``cfg.cloud_period`` rounds (cf. arXiv:2305.09042).

    Each cell's BS holds an *edge* model theta_c; every round its own
    scheduled clients train against theta_c (per-cell Eq.-(5) weights) and
    the edge steps locally.  On merge rounds the cloud averages the edge
    models (``_cloud_view``), broadcasts the result back, and the round
    pays the backhaul latency (``WirelessConfig.backhaul_s``).  Metrics
    evaluate the *cloud view* — the weighted edge mean — every round so
    sync/two-tier loss trajectories share one definition.

    The scan consumes ``(round_key, round_index)`` pairs; the gradient
    pass is a ``lax.scan`` over cells (each cell needs its own params, so
    the flat-client chunking — and the mesh client-axis sharding — of the
    single-tier path does not apply).
    """
    if mesh is not None:
        warnings.warn(
            "two-tier aggregation (cloud_period >= 1) runs the gradient "
            "pass as a per-cell scan and does not shard client work over "
            "the mesh; the mesh placement of population tensors still "
            "applies but per-round compute stays serial over cells.",
            stacklevel=3)
    control = _make_control_fn(cfg, pop, mesh=mesh)
    batch_fn, data = _make_batch_fn(task, state, cfg, data_key)
    w = cfg.wireless
    c, i = cfg.topology.shape
    k_cell = jnp.sum(pop.num_samples, axis=-1)                  # (C,)
    idx = jnp.arange(c * i, dtype=jnp.int32).reshape((c, i))
    data_leaves, data_def = (jax.tree_util.tree_flatten(data)
                             if data is not None else ([], None))
    data_cells = [a.reshape((c, i) + a.shape[1:]) for a in data_leaves]

    tcfg = cfg.telemetry
    grad_tel = tcfg is not None and tcfg.gradients

    def cell_body(_, inp):
        theta_c, idx_c, rho_c, aggw_c, schedw_c = inp[:5]
        extra = inp[5:]
        with jax.named_scope("fleet.data"):
            if extra:
                batch_c = jax.tree_util.tree_unflatten(data_def, list(extra))
            else:
                batch_c = batch_fn(idx_c)
        g, wsum, lsum, lw = _cell_grad_step(task, cfg, theta_c, rho_c,
                                            aggw_c, schedw_c, batch_c)
        denom = jnp.where(wsum > 0, wsum, 1.0)
        theta2 = jax.tree.map(
            lambda p, gg: jnp.where(
                wsum > 0, (p - cfg.lr * gg / denom).astype(p.dtype), p),
            theta_c, g)
        out = (theta2, wsum, lsum, lw)
        if grad_tel:  # this cell's edge-step norm^2 (telemetry only)
            out = out + (TEL.tree_sq_norm(g) / (denom * denom),)
        return None, out

    def round_fn(carry, xs):
        rkey, ridx = xs
        edge, acc_w, per_sum, prune_sum = carry
        ctl = control(rkey)
        active, arrivals, agg_w = _round_activity(cfg, pop, ctl)

        # Cohort path: each cell's scan slice carries only its m scheduled
        # clients — the edge tier's per-cell gradient work scales with the
        # cohort exactly like the single-tier chunk scan.
        rho_r, schedw_r = ctl.sol.prune, ctl.mask
        idx_r, aggw_r, cells_r = idx, agg_w, data_cells
        if ctl.cohort is not None:
            take = lambda a: jnp.take_along_axis(a, ctl.cohort, axis=-1)
            idx_r, rho_r = take(idx), take(rho_r)
            aggw_r, schedw_r = take(aggw_r), take(schedw_r)
            m = ctl.cohort.shape[-1]
            flat = idx_r.reshape(-1)
            cells_r = [a.reshape((c * i,) + a.shape[2:])[flat]
                       .reshape((c, m) + a.shape[2:]) for a in data_cells]

        with jax.named_scope("fleet.gradient"):
            _, cell_out = jax.lax.scan(
                cell_body, None,
                (edge, idx_r, rho_r, aggw_r, schedw_r, *cells_r))
        edge2, wsums, lsums, lws = cell_out[:4]
        mean_loss = jnp.sum(lsums) / jnp.maximum(jnp.sum(lws), 1.0)

        acc2 = acc_w + wsums
        with jax.named_scope("fleet.cloud_merge"):
            cloud = _cloud_view(edge2, acc2, k_cell)
            do_merge = (ridx % cfg.cloud_period) == (cfg.cloud_period - 1)
            edge3 = jax.tree.map(
                lambda e, cl: jnp.where(do_merge, jnp.broadcast_to(
                    cl, e.shape).astype(e.dtype), e), edge2, cloud)
            acc3 = jnp.where(do_merge, jnp.zeros_like(acc2), acc2)

        metrics, q_eff = _round_metrics(cfg, pop, ctl, active, arrivals,
                                        mean_loss)
        metrics["round_latency"] = metrics["round_latency"] \
            + jnp.where(do_merge, w.backhaul_s, 0.0)
        if grad_tel:
            n_sched = jnp.maximum(jnp.sum(ctl.mask), 1.0)
            metrics.update(TEL.grad_summaries(
                tcfg, jnp.sum(cell_out[4]),
                jnp.sum((1.0 - ctl.sol.prune) * ctl.mask) / n_sched))
        with jax.named_scope("fleet.eval"):
            metrics = _merge_eval(metrics, task, state, cloud)
        return (edge3, acc3, per_sum + q_eff,
                prune_sum + ctl.sol.prune * ctl.mask), metrics

    return round_fn


# ---------------------------------------------------------------------------
# Asynchronous (FedBuff-style buffered) events
# ---------------------------------------------------------------------------

class AsyncState(NamedTuple):
    """Per-client in-flight state carried through the async scan.

    Every (C, I) field describes the update each client is *currently*
    computing/uploading; it is overwritten when the client restarts after
    its update is merged.  The (C,) fields snapshot the per-cell solver
    telemetry at the cohort's start so event metrics report the control
    that actually produced the merged updates.
    """

    ready: jnp.ndarray        # (C, I) absolute arrival time, s
    start_ver: jnp.ndarray    # (C, I) server version at download
    rho: jnp.ndarray          # (C, I) pruning rate in flight
    per: jnp.ndarray          # (C, I) solved packet error prob
    sched: jnp.ndarray        # (C, I) participation mask at start
    alive: jnp.ndarray        # (C, I) survived churn & finite latency
    arrive: jnp.ndarray       # (C, I) packet success indicator
    m_cell: jnp.ndarray       # (C,) surrogate m at start
    deadline_c: jnp.ndarray   # (C,) solver deadline t~*, s
    bwutil_c: jnp.ndarray     # (C,) sum B_i / B
    per_sum: jnp.ndarray      # (C, I) Theorem-1 q accumulator
    prune_sum: jnp.ndarray    # (C, I) Theorem-1 rho accumulator


def _map_cell_blocks(fn, chunk: int, operands):
    """Apply ``fn`` (pytree of leading-(C, ...) arrays -> pytree) over
    consecutive cell blocks, mirroring ``_solve_cells_chunked``: full
    ``chunk``-sized blocks run under one ``lax.map``, a ragged remainder
    runs as one exact-sized call, and the results concatenate on the cell
    axis.  ``fn`` must be elementwise over cells (no cross-cell
    reductions), which makes the blocked result bit-identical to
    ``fn(operands)`` — only the peak working set changes.
    """
    c = jax.tree_util.tree_leaves(operands)[0].shape[0]
    chunk = min(chunk, c)
    n_full = c // chunk
    rem = c - n_full * chunk
    parts = []
    if n_full:
        stacked = jax.tree.map(
            lambda a: a[:n_full * chunk].reshape(
                (n_full, chunk) + a.shape[1:]), operands)
        mapped = jax.lax.map(fn, stacked)
        parts.append(jax.tree.map(
            lambda a: a.reshape((n_full * chunk,) + a.shape[2:]), mapped))
    if rem:
        parts.append(fn(jax.tree.map(lambda a: a[n_full * chunk:], operands)))
    if len(parts) == 1:
        return parts[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *parts)


def _fresh_state(t_client, mask, strag, arrivals, prune, per, bandwidth,
                 deadline, m_round, *, now, version, retry,
                 b_hz) -> AsyncState:
    """A just-launched AsyncState for one control draw (any cell slice)."""
    ready = SCHED.arrival_times(now, t_client, retry)
    alive = strag * jnp.isfinite(t_client).astype(jnp.result_type(float))
    return AsyncState(
        ready=ready,
        start_ver=jnp.full(mask.shape, version, jnp.int32),
        rho=prune, per=per, sched=mask, alive=alive,
        arrive=arrivals, m_cell=m_round,
        deadline_c=deadline,
        bwutil_c=jnp.sum(bandwidth, axis=-1) / b_hz,
        per_sum=jnp.zeros_like(mask),
        prune_sum=jnp.zeros_like(mask))


def _merge_state(new: AsyncState, prev: AsyncState,
                 coh: jnp.ndarray) -> AsyncState:
    """Cohort members adopt the fresh launch; everyone else stays in
    flight.  Elementwise over cells (chunk-safe)."""
    pick = lambda n, p: jnp.where(coh > 0, n, p)
    return AsyncState(
        ready=pick(new.ready, prev.ready),
        start_ver=pick(new.start_ver, prev.start_ver),
        rho=pick(new.rho, prev.rho), per=pick(new.per, prev.per),
        sched=pick(new.sched, prev.sched), alive=pick(new.alive, prev.alive),
        arrive=pick(new.arrive, prev.arrive),
        # per-cell telemetry refreshes with every solve (all cells resolve)
        m_cell=new.m_cell, deadline_c=new.deadline_c, bwutil_c=new.bwutil_c,
        per_sum=prev.per_sum, prune_sum=prev.prune_sum)


def _start_state(ctl: RoundControl, now, version, prev: Optional[AsyncState],
                 coh: Optional[jnp.ndarray], cfg: FleetConfig) -> AsyncState:
    """(Re)launch clients: cohort members (or everyone, at init) adopt the
    fresh control draw and an arrival time at their own latency.

    ``cfg.control_chunk`` blocks the per-event rebuild over cells (the
    same knob that blocks the solver): the twelve (C, I)/(C,) in-flight
    carries are rebuilt ``chunk`` cells at a time under ``lax.map``, so a
    million-client async event's transient state fits the cohort memory
    budget.  Every operation is elementwise over cells, so the blocked
    rebuild is bit-identical to the global one (pinned by
    tests/test_fleet_async.py).
    """
    cell_args = (ctl.t_client, ctl.mask, ctl.strag, ctl.arrivals,
                 ctl.sol.prune, ctl.sol.per, ctl.sol.bandwidth,
                 ctl.sol.deadline, ctl.m_round)
    retry = cfg.async_config.retry_backoff_s
    b_hz = cfg.wireless.bandwidth_hz

    def build(ops):
        new = _fresh_state(*ops[0], now=now, version=version, retry=retry,
                           b_hz=b_hz)
        if len(ops) == 1:
            return new
        return _merge_state(new, ops[1], ops[2])

    if prev is None:
        return build((cell_args,))
    c = ctl.mask.shape[0]
    if not (0 < cfg.control_chunk < c):
        return build((cell_args, prev, coh))
    return _map_cell_blocks(build, cfg.control_chunk, (cell_args, prev, coh))


def _make_async_step(cfg: FleetConfig, task: TASK.FleetTask, state: PyTree,
                     pop: TOPO.ClientPopulation, data_key: jax.Array,
                     mesh=None):
    """One server event: fill the buffer with the K earliest arrivals,
    merge them (staleness-discounted) against the param ring buffer, bump
    the version, restart the merged clients with a fresh control draw.

    Two-tier (``cfg.cloud_period >= 1``): the buffered updates merge into
    each contributor's *home-cell edge model* (per-cell Eq.-(5) weights
    via one segment-sum) instead of the global model; every
    ``cloud_period`` events the cloud averages the edges, pays the
    backhaul latency, and pushes the merged model into the ring buffer —
    clients always download (and compute stale gradients against) *cloud*
    checkpoints, so the ring-buffer staleness machinery is unchanged.
    Per-client gradients are explicit here (the buffer bounds their
    memory); with a fused kernel configured they use the same block-norm
    threshold masks the kernel applies, so fused-config trajectories stay
    mask-rule-consistent across tiers.
    """
    acfg = cfg.async_config
    w = cfg.wireless
    n = cfg.topology.num_clients
    c_cells, i_per_cell = cfg.topology.shape
    two_tier = cfg.cloud_period >= 1
    k_buf = acfg.cohort_buffer(n)
    hist_len = acfg.history_len
    control = _make_control_fn(cfg, pop, mesh=mesh)
    batch_fn, _ = _make_batch_fn(task, state, cfg, data_key)
    k_flat = pop.num_samples.reshape(-1)
    k_cell = jnp.sum(pop.num_samples, axis=-1)

    def gather(a: jnp.ndarray, sel: jnp.ndarray) -> jnp.ndarray:
        return a.reshape(-1)[sel]

    def step(carry, rkey):
        if two_tier:
            hist, head, version, now, st, edge, acc_w = carry
        else:
            hist, head, version, now, st = carry

        # -- 1. the buffer fills with the K earliest pending arrivals
        sel, t_fill = SCHED.select_arrivals(st.ready, k_buf)
        now2 = t_fill + w.aggregation_latency_s
        coh = jnp.zeros((n,), dtype=float).at[sel].set(1.0) \
            .reshape(st.ready.shape)

        # -- 2. staleness-discounted merge weights (shared FedBuff rule)
        tau = version - gather(st.start_ver, sel)
        w_merge = AGG.buffered_weights(
            k_flat[sel], gather(st.arrive * st.sched * st.alive, sel), tau,
            kind=acfg.staleness_discount, alpha=acfg.staleness_alpha,
            max_staleness=acfg.max_staleness, xp=jnp)

        # -- 3. gradients at each client's *download* version (ring buffer)
        ldtype = jnp.result_type(float)
        batch = _constrain_clients(batch_fn(sel), mesh)
        if cfg.kernel == "reference" or two_tier:
            # under a fused-kernel config the per-client grads here use the
            # kernel's block-norm threshold masks, not magnitude masks
            mk = None if cfg.kernel == "reference" else "block"

            def one(b_i, rho_i, tau_i):
                slot = (head - jnp.clip(tau_i, 0, hist_len - 1)) % hist_len
                stale_params = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, slot, 0, keepdims=False), hist)
                return _client_grad(task, stale_params, rho_i, b_i, cfg,
                                    mask_kind=mk)

            with jax.named_scope("fleet.gradient"):
                losses, grads = jax.vmap(one)(batch, gather(st.rho, sel),
                                              tau)
            if not two_tier:  # two-tier merges per cell from `grads` below
                g_wsum = jax.tree.map(
                    lambda g: jnp.einsum("c,c...->...", w_merge, g), grads)
        else:
            # Fused path: bucket the buffer by ring slot (= param version)
            # so each populated slot streams through the fused kernel
            # once; empty slots are skipped by lax.cond, so the common
            # low-staleness event costs ~one kernel sweep, not hist_len.
            rho_sel = gather(st.rho, sel)
            slot_all = (head - jnp.clip(tau, 0, hist_len - 1)) % hist_len
            g_wsum = jax.tree.map(
                lambda a: jnp.zeros(a.shape[1:], a.dtype), hist)
            losses = jnp.zeros(sel.shape, ldtype)
            for s in range(hist_len):
                in_slot = (slot_all == s)

                def compute(s=s, in_slot=in_slot):
                    p_s = jax.tree.map(lambda a: a[s], hist)
                    with jax.named_scope("fleet.mask"):
                        prep = task.kernel_prepare(p_s)
                    with jax.named_scope("fleet.backprop"):
                        g, l = task.kernel_grads(p_s, prep, batch, rho_sel,
                                                 w_merge * in_slot,
                                                 impl=_kernel_impl(cfg))
                    return g, jnp.where(in_slot, l, 0.0).astype(ldtype)

                shapes = jax.eval_shape(compute)
                with jax.named_scope("fleet.gradient"):
                    g_s, l_s = jax.lax.cond(
                        jnp.any(in_slot), compute,
                        lambda: jax.tree.map(
                            lambda sh: jnp.zeros(sh.shape, sh.dtype), shapes))
                g_wsum = jax.tree.map(
                    lambda a, b: a + b.astype(a.dtype), g_wsum, g_s)
                losses = losses + l_s
        w_sum = jnp.sum(w_merge)
        denom = jnp.where(w_sum > 0, w_sum, 1.0)
        params = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, head, 0,
                                                   keepdims=False), hist)
        if two_tier:
            # merge the buffered updates into their home-cell edge models
            # (per-cell Eq.-(5) weights, one segment-sum per leaf)
            cell_id = sel // i_per_cell
            den = jax.ops.segment_sum(w_merge, cell_id,
                                      num_segments=c_cells)       # (C,)

            def edge_update(e, g):
                shape = (-1,) + (1,) * (g.ndim - 1)
                num = jax.ops.segment_sum(w_merge.reshape(shape) * g,
                                          cell_id, num_segments=c_cells)
                d = jnp.maximum(den, 1e-30).reshape(shape)
                return jnp.where((den > 0).reshape(shape),
                                 (e - cfg.lr * num / d).astype(e.dtype), e)

            with jax.named_scope("fleet.cloud_merge"):
                edge2 = jax.tree.map(edge_update, edge, grads)
                acc2 = acc_w + den
                cloud = _cloud_view(edge2, acc2, k_cell)
            do_merge = ((version + 1) % cfg.cloud_period) == 0
            acc_out = jnp.where(do_merge, jnp.zeros_like(acc2), acc2)
            edge_out = jax.tree.map(
                lambda e, cl: jnp.where(do_merge, jnp.broadcast_to(
                    cl, e.shape).astype(e.dtype), e), edge2, cloud)
            # clients only ever download cloud checkpoints: the ring
            # buffer re-pins the current checkpoint between merges
            new_params = jax.tree.map(
                lambda p, cl: jnp.where(do_merge, cl.astype(p.dtype), p),
                params, cloud)
            eval_params = cloud
            now2 = now2 + jnp.where(do_merge, w.backhaul_s, 0.0)
        else:
            with jax.named_scope("fleet.merge"):
                new_params = jax.tree.map(
                    lambda p, g: jnp.where(
                        w_sum > 0,
                        (p - cfg.lr * g / denom).astype(p.dtype), p),
                    params, g_wsum)
            eval_params = new_params
        version2 = version + 1
        head2 = (head + 1) % hist_len
        hist2 = jax.tree.map(
            lambda a, p: jax.lax.dynamic_update_index_in_dim(a, p, head2, 0),
            hist, new_params)

        # -- 4. event metrics over the merged cohort (same definitions as
        # the sync round, so buffer-equals-cohort trajectories coincide)
        sched_coh = coh * st.sched
        n_sched = jnp.maximum(jnp.sum(sched_coh), 1.0)
        loss_w = gather(st.sched, sel)
        mean_loss = jnp.sum(losses * loss_w) / jnp.maximum(jnp.sum(loss_w),
                                                           1.0)
        q_eff = 1.0 - st.sched * st.alive * (1.0 - st.per)
        fresh = (tau <= acfg.max_staleness).astype(
            jnp.result_type(float))
        participants = jnp.sum(
            gather(st.arrive * st.sched * st.alive, sel) * fresh)
        k_all = pop.num_samples
        learning = jnp.sum(jnp.where(
            coh > 0,
            st.m_cell[:, None] * k_all * (q_eff + k_all * st.rho) * st.sched,
            0.0))

        per_sum2 = st.per_sum + jnp.where(coh > 0, q_eff, 1.0)
        prune_sum2 = st.prune_sum + jnp.where(coh > 0, st.rho * st.sched, 0.0)

        metrics = {
            "loss": mean_loss,
            "round_latency": now2 - now,
            "deadline": st.deadline_c,
            "mean_prune": jnp.sum(coh * st.rho * st.sched) / n_sched,
            "mean_per": jnp.sum(coh * q_eff * st.sched) / n_sched,
            "participants": participants,
            "bandwidth_util": st.bwutil_c,
            "learning_cost": learning,
            "staleness": jnp.mean(tau.astype(jnp.result_type(float))),
            "sim_time": now2,
        }
        tcfg = cfg.telemetry
        if tcfg is not None:
            metrics.update(
                TEL.staleness_summary(tcfg, tau, acfg.max_staleness))
            if tcfg.gradients:
                # the cohort-aggregate update norm (two-tier recombines
                # the per-client grads; single-tier reuses g_wsum)
                g_tel = g_wsum if not two_tier else jax.tree.map(
                    lambda g: jnp.einsum("c,c...->...", w_merge, g), grads)
                metrics.update(TEL.grad_summaries(
                    tcfg, TEL.tree_sq_norm(g_tel) / (denom * denom),
                    jnp.sum(coh * (1.0 - st.rho) * st.sched) / n_sched))
        with jax.named_scope("fleet.eval"):
            metrics = _merge_eval(metrics, task, state, eval_params)

        # -- 5. merged clients re-download version2 and start a new cycle;
        # the restart's control draw doubles as the event's control-
        # telemetry snapshot (same draw whether telemetry is on or off)
        ctl2 = control(rkey)
        if tcfg is not None:
            metrics.update(TEL.control_summaries(
                tcfg, ctl2.sol, ctl2.t_client, ctl2.sinr_db,
                w.bandwidth_hz))
        st2 = _start_state(ctl2, now2, version2, st, coh, cfg)
        st2 = st2._replace(per_sum=per_sum2, prune_sum=prune_sum2)
        if two_tier:
            return (hist2, head2, version2, now2, st2, edge_out,
                    acc_out), metrics
        return (hist2, head2, version2, now2, st2), metrics

    return step


def _shard_cells(tree, mesh):
    """Place the leading (cell) axis of every array on the mesh's cell
    axis: "cells" on a two-axis fleet mesh (``launch.mesh.make_fleet_mesh``
    — the client axis of (C, I) arrays then additionally shards over
    "data"), falling back to "data" on the legacy single-axis mesh."""
    if mesh is None:
        return tree
    axis = "cells" if "cells" in mesh.axis_names else "data"
    if axis not in mesh.axis_names:
        return tree
    n = mesh.shape[axis]
    n_data = mesh.shape["data"] if (axis == "cells"
                                    and "data" in mesh.axis_names) else 0

    def put(a):
        if a.ndim < 1 or a.shape[0] % n != 0:
            return a
        spec = [axis] + [None] * (a.ndim - 1)
        if n_data > 1 and a.ndim >= 2 and a.shape[1] % n_data == 0:
            spec[1] = "data"
        return jax.device_put(a, NamedSharding(mesh, P(*spec)))

    return jax.tree.map(put, tree)


@dataclasses.dataclass
class Simulation:
    """A built (but not yet executed) fleet run.

    ``simulate(params, round_keys)`` is the single jitted scan over rounds
    (sync) or server events (async); calling it again reuses the compiled
    executable (benchmarks time cold vs warm this way).  ``finalize``
    converts its output to a FleetResult.
    """

    cfg: FleetConfig
    simulate: Any
    params: PyTree
    round_keys: jnp.ndarray
    num_samples: jnp.ndarray
    mode: str = "sync"
    two_tier: bool = False

    def _edge_mean(self, edge: PyTree, acc_w: np.ndarray) -> PyTree:
        """Host-side cloud view: merged-weight-mass mean of the edges
        (falling back to sample totals when nothing merged since the last
        cloud sync — matching ``_cloud_view``)."""
        acc_w = np.asarray(acc_w, dtype=np.float64)
        if acc_w.sum() <= 0:
            acc_w = np.sum(np.asarray(self.num_samples, dtype=np.float64),
                           axis=-1)
        w = acc_w / acc_w.sum()

        def mean(a):
            a = np.asarray(a)
            return np.tensordot(w.astype(a.dtype), a, axes=1)

        return jax.tree.map(mean, edge)

    def finalize(self, carry, metrics) -> FleetResult:
        """Convert the scan output (device arrays) into a host FleetResult,
        including the Theorem-1 bound on the realized (q, rho) averages.

        Two-tier carries hold per-cell edge models; the returned ``params``
        is the cloud view (weighted edge mean — equal to the last cloud
        merge when the final round merged)."""
        cfg = self.cfg
        metrics, tel = TEL.split_metrics(metrics)
        if self.mode == "async":
            if self.two_tier:
                hist, head, _, _, st, edge, acc_w = carry
                params = self._edge_mean(edge, acc_w)
            else:
                hist, head, _, _, st = carry
                params = jax.tree.map(
                    lambda a: np.asarray(a)[int(head)], hist)
            per_sum, prune_sum = st.per_sum, st.prune_sum
        else:
            if self.two_tier:
                edge, acc_w, per_sum, prune_sum = carry
                params = self._edge_mean(edge, acc_w)
            else:
                params, per_sum, prune_sum = carry
                params = jax.tree.map(np.asarray, params)
        avg_per = np.asarray(per_sum).reshape(-1) / cfg.rounds
        avg_prune = np.asarray(prune_sum).reshape(-1) / cfg.rounds
        bound = ConvergenceBound(cfg.smoothness,
                                 np.asarray(self.num_samples).reshape(-1))
        latencies = np.asarray(metrics["round_latency"])
        if "sim_time" in metrics:
            wall = np.asarray(metrics["sim_time"])
        else:
            wall = np.cumsum(latencies)
        staleness = (np.asarray(metrics["staleness"])
                     if "staleness" in metrics
                     else np.zeros_like(latencies))
        return FleetResult(
            losses=np.asarray(metrics["loss"]),
            accuracy=np.asarray(metrics["accuracy"]),
            latencies=latencies,
            deadlines=np.asarray(metrics["deadline"]),
            mean_prune=np.asarray(metrics["mean_prune"]),
            mean_per=np.asarray(metrics["mean_per"]),
            participants=np.asarray(metrics["participants"]),
            bandwidth_util=np.asarray(metrics["bandwidth_util"]),
            learning_cost=np.asarray(metrics["learning_cost"]),
            bound_final=float(bound.bound(cfg.rounds, avg_per, avg_prune)),
            params=params,
            wall_clock=wall,
            staleness=staleness,
            mode=self.mode,
            telemetry=(None if tel is None
                       else {k: np.asarray(v) for k, v in tel.items()}),
        )


def _span(recorder: Optional[TEL.SpanRecorder], name: str):
    return recorder.span(name) if recorder is not None \
        else contextlib.nullcontext()


def _build_common(cfg: FleetConfig, mesh=None,
                  recorder: Optional[TEL.SpanRecorder] = None):
    """Shared setup of the scan engine and the host-stepped reference path:
    resolve the task, drop the population, build data/model, and (when the
    task knows its physical size) override the wireless model bits D_M.
    With a ``recorder`` each stage is a ``fleet.build.*`` span."""
    task = resolve_task(cfg)
    geo = resolve_geometry(cfg)
    topo = cfg.topology
    root = jax.random.PRNGKey(cfg.seed)
    k_pop, k_task, k_init, k_test, k_data, k_rounds = jax.random.split(root, 6)

    with _span(recorder, "fleet.build.population"):
        pop = geo.make_population(k_pop, topo, cfg.wireless.tx_power_ue_w)
        pop = _shard_cells(pop, mesh)
    with _span(recorder, "fleet.build.task"):
        state = task.build(k_task, k_test)
    with _span(recorder, "fleet.build.init"):
        params = task.init_params(k_init)
        mb = task.model_bits(params)
    if mb is not None:
        cfg = dataclasses.replace(
            cfg, wireless=cfg.wireless.replace(model_bits=float(mb)))

    keys = jax.random.split(k_rounds, cfg.rounds + 1)
    return cfg, task, state, params, pop, k_data, keys


def build_simulation(cfg: FleetConfig, mesh=None, mode: str = "sync",
                     recorder: Optional[TEL.SpanRecorder] = None
                     ) -> Simulation:
    """Drop the fleet, build the data/model, jit the round/event scan.

    Args:
      cfg: the run configuration (topology, schedule, wireless, solver,
        task).
      mesh: optional ``launch.mesh`` mesh; the cell axis of every
        population tensor is placed on its "data" axis and the flat client
        axis of the gradient batch is constrained to it inside the round.
      mode: ``"sync"`` (FedSGD barrier rounds) or ``"async"`` (FedBuff
        buffered events; see ``FleetConfig.async_config``).
      recorder: the ``telemetry.SpanRecorder`` that receives the
        ``fleet.build`` span and its stages ``fleet.build.population``,
        ``.task``, ``.init`` and ``.round`` (default: the process
        recorder, ``telemetry.process_recorder()``).  They are host
        times: nothing waits for the device here, so device work a
        stage dispatches may finish after its span closes.  The round
        program itself compiles on the first ``simulate`` call.

    Returns:
      A ``Simulation`` whose ``simulate(params, round_keys)`` runs
      ``cfg.rounds`` rounds/events as one compiled program.  Both modes
      derive per-round keys from the same ``rounds + 1`` split so their
      channel/schedule draws line up (async uses the extra key to launch
      the initial cohort).
    """
    if mode not in ("sync", "async"):
        raise ValueError(f"mode must be 'sync' or 'async', got {mode!r}")
    if cfg.kernel not in ("reference", "fused", "fused_xla", "fused_pallas"):
        raise ValueError(
            "kernel must be 'reference', 'fused', 'fused_xla' or "
            f"'fused_pallas', got {cfg.kernel!r}")
    if cfg.mask_kind not in ("magnitude", "block"):
        raise ValueError(
            f"mask_kind must be 'magnitude' or 'block', got {cfg.mask_kind!r}")
    if cfg.cloud_period < 0:
        raise ValueError(
            f"cloud_period must be >= 0 (0 = single-tier), got "
            f"{cfg.cloud_period}")
    if cfg.control_chunk < 0:
        raise ValueError(
            f"control_chunk must be >= 0 (0 = solve all cells at once), "
            f"got {cfg.control_chunk}")
    rec = recorder if recorder is not None else TEL.process_recorder()
    with rec.span("fleet.build", mode=mode,
                  clients=cfg.topology.num_clients):
        cfg, task, state, params, pop, k_data, keys = _build_common(
            cfg, mesh, rec)
        with rec.span("fleet.build.round"):
            simulate, round_keys = _make_simulate(cfg, task, state, pop,
                                                  k_data, keys, mesh, mode)
    return Simulation(cfg=cfg, simulate=simulate, params=params,
                      round_keys=round_keys, num_samples=pop.num_samples,
                      mode=mode, two_tier=cfg.cloud_period >= 1)


def _make_simulate(cfg: FleetConfig, task: TASK.FleetTask, state: PyTree,
                   pop: TOPO.ClientPopulation, k_data: jax.Array,
                   keys: jnp.ndarray, mesh, mode: str):
    """(jitted ``simulate(params, round_keys)``, its round keys)."""
    topo = cfg.topology
    two_tier = cfg.cloud_period >= 1

    if mode == "sync":
        zeros_ci = jnp.zeros(topo.shape)
        if two_tier:
            round_fn = _make_two_tier_round_fn(cfg, task, state, pop, k_data,
                                               mesh=mesh)
            steps = jnp.arange(cfg.rounds, dtype=jnp.int32)

            @jax.jit
            def simulate(params, round_keys):
                edge0 = jax.tree.map(
                    lambda p: jnp.repeat(p[None], topo.num_cells, axis=0),
                    params)
                acc0 = jnp.zeros((topo.num_cells,))
                return jax.lax.scan(round_fn,
                                    (edge0, acc0, zeros_ci, zeros_ci),
                                    (round_keys, steps))
        else:
            round_fn = _make_round_fn(cfg, task, state, pop, k_data,
                                      mesh=mesh)

            @jax.jit
            def simulate(params, round_keys):
                return jax.lax.scan(round_fn, (params, zeros_ci, zeros_ci),
                                    round_keys)

        round_keys = keys[:cfg.rounds]
    else:
        step_fn = _make_async_step(cfg, task, state, pop, k_data, mesh=mesh)
        control = _make_control_fn(cfg, pop, mesh=mesh)
        hist_len = cfg.async_config.history_len

        @jax.jit
        def simulate(params, round_keys):
            # Launch the whole fleet at t = 0 with the first key, park the
            # initial params in ring-buffer slot 0, then scan the events.
            st0 = _start_state(control(round_keys[0]), jnp.zeros(()),
                               jnp.asarray(0, jnp.int32), None, None, cfg)
            hist0 = jax.tree.map(
                lambda a: jnp.zeros((hist_len,) + a.shape,
                                    a.dtype).at[0].set(a), params)
            carry0 = (hist0, jnp.asarray(0, jnp.int32),
                      jnp.asarray(0, jnp.int32), jnp.zeros(()), st0)
            if two_tier:
                edge0 = jax.tree.map(
                    lambda p: jnp.repeat(p[None], topo.num_cells, axis=0),
                    params)
                carry0 = carry0 + (edge0, jnp.zeros((topo.num_cells,)))
            return jax.lax.scan(step_fn, carry0, round_keys[1:])

        round_keys = keys

    return simulate, round_keys


def run_fleet(cfg: FleetConfig, mesh=None, progress: bool = False,
              mode: str = "sync",
              sink: Optional[TEL.TelemetrySink] = None,
              recorder: Optional[TEL.SpanRecorder] = None) -> FleetResult:
    """Simulate ``cfg.rounds`` fleet FL rounds/events as one compiled scan.

    Args:
      cfg: run configuration; ``cfg.rounds`` counts synchronous rounds or
        asynchronous server events depending on ``mode``.
      mesh: optional device mesh (cells shard over its "data" axis).
      progress: print a per-round digest *after* the scan returns (the
        whole run is one device program — there is nothing to stream from
        inside it): every rounds//10-th round plus the final one.
      mode: ``"sync"`` or ``"async"`` (FedBuff buffered aggregation).
      sink: optional ``telemetry.TelemetrySink``; the run's header and
        per-round records (``telemetry.round_records``) are emitted into
        it after the scan returns (the sink is not closed).
      recorder: the ``telemetry.SpanRecorder`` that records the
        ``fleet.build`` (with its stages), ``fleet.simulate`` and
        ``fleet.finalize`` wall-clock spans (exportable as Chrome-trace
        JSON via ``recorder.write``); default: the process recorder.

    Returns:
      A ``FleetResult``; trajectories are indexed by round (sync) or
      server event (async), with ``wall_clock`` as the common time axis.
      ``result.telemetry`` carries the in-scan summaries when
      ``cfg.telemetry`` is set.
    """
    rec = recorder if recorder is not None else TEL.process_recorder()
    sim = build_simulation(cfg, mesh=mesh, mode=mode, recorder=rec)
    with rec.span("fleet.simulate", rounds=cfg.rounds):
        carry, metrics = sim.simulate(sim.params, sim.round_keys)
        jax.block_until_ready(metrics)
    with rec.span("fleet.finalize"):
        result = sim.finalize(carry, metrics)
    if sink is not None:
        TEL.emit_result(result, sink, meta={
            "clients": cfg.topology.num_clients, "kernel": cfg.kernel,
            "cloud_period": cfg.cloud_period})

    if progress:
        shown = sorted(set(range(0, cfg.rounds, max(cfg.rounds // 10, 1)))
                       | {cfg.rounds - 1})
        for rnd in shown:
            print(f"[fleet] round {rnd:4d} loss={result.losses[rnd]:.4f} "
                  f"acc={result.accuracy[rnd]:.4f}")
    return result


# ``engine.run(..., mode="async")`` reads naturally at call sites that
# treat the mode as data; it is the same function.
run = run_fleet


def time_to_loss(result: FleetResult, target: float) -> float:
    """Simulated seconds until the training loss first reaches ``target``.

    Uses ``result.wall_clock`` (cumulative realized latency), so sync and
    async runs compare on the same physical time axis.  Returns ``inf`` if
    the run never reaches the target.
    """
    hit = np.flatnonzero(np.asarray(result.losses) <= target)
    if hit.size == 0:
        return float("inf")
    return float(result.wall_clock[hit[0]])
