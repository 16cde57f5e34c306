"""Batched trade-off solver: Algorithm 1 on-device, vmapped over cells,
with an optional damped inter-cell interference fixed point.

The jax port of ``core.tradeoff.solve_alternating``.  Both paths call the
same ``core.closed_form`` implementations of Proposition 1 (pruning
vertex) and Eq. (21) (minimum-bandwidth Newton inversion); this module
only adds the alternating driver, expressed as a ``lax.while_loop`` whose
per-cell updates freeze once the inner cost converges — reproducing the
host solver's early-exit semantics element-wise, which keeps the whole
thing jit/vmap/scan-compatible (no host round-trips, no data-dependent
shapes).

``solve_fleet`` vmaps the single-cell solver over the leading cell axis so
per-round control for the entire fleet is one XLA program.  With an
``interference`` graph (``fleet.topology.InterferenceGraph``) the cells
*couple*: each cell's SINR includes the co-channel interference PSD
generated by its neighbors' allocated bandwidths, so the per-cell solves
sit inside a damped fixed point —

    I^(t+1) = I^(t) + d * (F(solve(N0 + I^(t))) - I^(t)),

where ``F`` recomputes the interference PSD from the allocation
(``topology.interference_psd``).  Starting from I = 0 the map is monotone
(more interference -> more bandwidth demanded -> more interference), so
the damped iterates climb to the fixed point; the loop is an outer
``lax.while_loop`` that freezes when the iterate moves less than
``SolverConfig.fp_rtol`` relative to the noise floor — the whole coupled
solve still traces into the engine's round scan with zero host
round-trips.
"""

from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import closed_form as CF
from repro.fleet import topology as TOPO


def _constrain_cell_axis(tree, mesh):
    """Pin the leading cell axis of every (C, ...) array in ``tree`` to the
    mesh's cell axis ("cells" on a two-axis fleet mesh, else "data") with
    ``with_sharding_constraint`` — each cell's Algorithm-1 solve is
    independent, so XLA partitions the vmapped solver across devices
    cell-wise.  No-op without a mesh or for non-divisible axes."""
    if mesh is None:
        return tree
    axis = "cells" if "cells" in mesh.axis_names else "data"
    if axis not in mesh.axis_names:
        return tree
    n = mesh.shape[axis]

    def put(a):
        if a is None or getattr(a, "ndim", 0) < 1 or a.shape[0] % n != 0:
            return a
        return jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, PartitionSpec(axis)))

    return jax.tree.map(put, tree)


@dataclasses.dataclass(frozen=True, init=False)
class SolverConfig:
    """Static knobs of the alternating solver (hashable: safe to close over).

    ``fp_*`` govern the inter-cell interference fixed point (only used
    when ``solve_fleet`` receives an interference graph): iteration cap,
    damping factor d in (0, 1], and the relative freeze tolerance on the
    interference-PSD iterate (``fp_rtol = 0`` always runs the full
    ``fp_iters`` — what the cross-path equivalence tests use so the host
    and device paths take identical iteration counts).
    """

    max_iters: int = 16       # Algorithm-1 alternations (cap; see while_loop)
    bw_iters: int = 12        # Eq.-(21) Newton steps (quadratic: reaches the
                              # compute dtype's noise floor by ~10)
    rtol: float = 1e-8        # convergence freeze threshold on inner cost;
                              # clamped to a few ulp of the compute dtype
                              # (1e-8 can never fire in float32)
    fp_iters: int = 8         # interference fixed-point cap
    fp_damping: float = 0.5   # damping d of the interference iterate
    fp_rtol: float = 1e-3     # freeze tolerance, relative to N0 + max I

    def __init__(self, max_iters: int = 16, bw_iters: int = 12,
                 rtol: float = 1e-8, fp_iters: int = 8,
                 fp_damping: float = 0.5, fp_rtol: float = 1e-3,
                 grow_iters=None):
        if grow_iters is not None:
            warnings.warn(
                "SolverConfig.grow_iters is deprecated and ignored: the "
                "Eq.-(21) inversion is a safeguarded Newton solve with no "
                "bracket-growth phase (drop the argument).",
                DeprecationWarning, stacklevel=2)
        object.__setattr__(self, "max_iters", max_iters)
        object.__setattr__(self, "bw_iters", bw_iters)
        object.__setattr__(self, "rtol", rtol)
        object.__setattr__(self, "fp_iters", fp_iters)
        object.__setattr__(self, "fp_damping", fp_damping)
        object.__setattr__(self, "fp_rtol", fp_rtol)

    @property
    def grow_iters(self) -> int:
        """Deprecated: the bracket-growth phase no longer exists."""
        warnings.warn("SolverConfig.grow_iters is deprecated and always 0.",
                      DeprecationWarning, stacklevel=2)
        return 0


class CellSolution(NamedTuple):
    """Per-cell solver output; every field broadcast over leading cell dims.

    ``interference_psd`` / ``fp_iterations`` / ``fp_residual`` /
    ``fp_residuals`` are populated only by interference-coupled solves
    (``solve_fleet(..., interference=...)``) and stay ``None`` on the
    orthogonal path.  ``fp_residual`` is the final damped-iterate
    movement ``max |I^(t+1) - I^(t)|`` in W/Hz — finite even when the
    loop stops at the ``fp_iters`` cap, so non-convergence is observable
    instead of silent; ``fp_residuals`` (``solve_fleet(...,
    diagnostics=True)``) is the whole residual trajectory, NaN-padded
    past the realized iteration count.
    """

    prune: jnp.ndarray        # rho_i*       (..., I)
    bandwidth: jnp.ndarray    # B_i*         (..., I)
    deadline: jnp.ndarray     # t~*          (...,)
    per: jnp.ndarray          # q_i(B_i*)    (..., I)
    inner_cost: jnp.ndarray   # (14a)        (...,)
    iterations: jnp.ndarray   # alternations until freeze   (...,)
    feasible: jnp.ndarray     # finite B, sum B_i <= B      (...,)
    interference_psd: Optional[jnp.ndarray] = None   # (...,) W/Hz
    fp_iterations: Optional[jnp.ndarray] = None      # scalar
    fp_residual: Optional[jnp.ndarray] = None        # scalar, W/Hz
    fp_residuals: Optional[jnp.ndarray] = None       # (fp_iters,), W/Hz


def solve_cell(h_up: jnp.ndarray, num_samples: jnp.ndarray,
               cpu_hz: jnp.ndarray, tx_power: jnp.ndarray,
               max_prune: jnp.ndarray, m: jnp.ndarray,
               mask: Optional[jnp.ndarray] = None,
               deadline_cap: Optional[jnp.ndarray] = None, *,
               bandwidth_hz: float, noise_psd, waterfall_m0: float,
               model_bits: float, cycles_per_sample: float, weight: float,
               solver: SolverConfig = SolverConfig()) -> CellSolution:
    """Algorithm 1 for one cell of I clients; all array inputs shaped (I,).

    Args:
      h_up: uplink power gains h_i^u (linear, dimensionless — NOT dB; the
        urban path-loss model converts 128.1 + 37.6 log10(d_km) dB to
        linear in ``topology.path_loss_linear``).
      num_samples: local dataset sizes K_i (samples).
      cpu_hz: client compute speeds f_i in cycles/second (Hz).
      tx_power: client transmit powers p_i in watts.
      max_prune: per-client pruning-rate ceilings rho_i^max in [0, 1].
      m: the cell's Eq.-(11) surrogate coefficient (see
        ``closed_form.surrogate_m``; units 1/samples so m K_i q_i is
        dimensionless).
      mask: optional (I,) participation mask — non-participants get
        rho = 0, B = 0 and contribute nothing to the vertex walk or cost.
      deadline_cap: optional scalar upper bound on the solved deadline t~
        in seconds — the time-triggered-FL scenario (cf. arXiv:2408.01765):
        the Eq.-(16) minimum pruning rates are re-derived at the capped
        deadline, and clients that cannot meet it even at rho_i^max get
        B = 0 (unschedulable this round) instead of an infinite allocation.
      bandwidth_hz: cell uplink budget B in Hz.
      noise_psd: effective noise power spectral density in W/Hz — N0, or
        N0 + interference PSD when this cell's solve sits inside the
        coupled fixed point (a traced per-cell scalar is fine).
      waterfall_m0: waterfall PER constant m0 (dimensionless SNR threshold).
      model_bits: uncompressed model payload D_M in bits.
      cycles_per_sample: local-training cost d^c in CPU cycles per sample.
      weight: the trade-off lambda in [0, 1] (dimensionless).
      solver: static iteration counts / tolerance (``SolverConfig``).

    Returns:
      A ``CellSolution``: pruning rates rho_i* in [0, 1], bandwidths B_i*
      in Hz, deadline t~* in seconds, packet error probabilities
      q_i(B_i*) in [0, 1], the Eq.-(14a) inner cost, alternations until
      freeze, and a feasibility flag (finite B with sum B_i <= B).
    """
    lam = weight
    k = num_samples.astype(h_up.dtype)
    if mask is None:
        mask = jnp.ones_like(h_up)
    participating = mask > 0.0
    n_part = jnp.maximum(jnp.sum(mask, axis=-1), 1.0)

    def no_prune_latency(bw):
        r = CF.uplink_rate(bw, tx_power, h_up, noise_psd, xp=jnp)
        t_u = CF.upload_latency(jnp.zeros_like(bw), model_bits, r, xp=jnp)
        t_c0 = CF.training_latency(jnp.zeros_like(bw), k, cycles_per_sample,
                                   cpu_hz, xp=jnp)
        return t_u + t_c0

    def inner_cost(deadline, bw, rho):
        q = CF.packet_error_rate(bw, tx_power, h_up, noise_psd, waterfall_m0,
                                 xp=jnp)
        learning = m * jnp.sum(mask * k * (q + k * rho), axis=-1)
        return (1.0 - lam) * deadline + lam * learning

    # One alternation in three named parts (``fleet.solve.vertex`` /
    # ``.bandwidth`` / ``.cost``), so a device profile splits Algorithm 1.
    def body(state):
        bw, dl, rho, prev_cost, done, iters = state
        with jax.named_scope("fleet.solve.vertex"):
            t_np = no_prune_latency(bw)
            dl2, rho2 = CF.pruning_vertex(t_np, k, lam, m, max_prune,
                                          xp=jnp, mask=mask)
            if deadline_cap is not None:
                dl2 = jnp.minimum(dl2, deadline_cap)
                rho2 = jnp.minimum(
                    CF.prune_rates_for_deadline(t_np, dl2, xp=jnp),
                    max_prune) * mask
        with jax.named_scope("fleet.solve.bandwidth"):
            bw2 = CF.bandwidth_for_deadline(
                rho2, dl2, k, cpu_hz, cycles_per_sample, model_bits,
                tx_power, h_up, noise_psd, iters=solver.bw_iters, xp=jnp)
            if deadline_cap is not None:  # unschedulable at rho^max: sit out
                bw2 = jnp.where(jnp.isfinite(bw2), bw2, 0.0)
                bw2 = jnp.where(participating, bw2, 0.0)
                # A binding cap voids Lemma 2's feasibility guarantee: the
                # deadline-meeting minimum can oversubscribe B.  Keep the
                # max-cardinality schedulable subset (ascending-demand
                # prefix) and sideline the rest for this round.
                order = jnp.argsort(bw2)
                fits = jnp.cumsum(jnp.take(bw2, order)) \
                    <= bandwidth_hz * (1.0 + 1e-9)
                keep = jnp.zeros_like(bw2).at[order].set(
                    fits.astype(bw2.dtype))
                bw2 = bw2 * keep
            bw2 = jnp.where(participating, bw2, 0.0)
        with jax.named_scope("fleet.solve.cost"):
            cost = inner_cost(dl2, bw2, rho2)
            conv = jnp.abs(prev_cost - cost) <= eff_rtol * jnp.maximum(
                jnp.abs(cost), 1.0)
            bw = jnp.where(done, bw, bw2)
            dl = jnp.where(done, dl, dl2)
            rho = jnp.where(done, rho, rho2)
            prev_cost = jnp.where(done, prev_cost, cost)
            iters = iters + jnp.where(done, 0, 1)
            return bw, dl, rho, prev_cost, done | conv, iters

    bw0 = mask * (bandwidth_hz / n_part)
    # A freeze threshold below the compute dtype's resolution never fires
    # (f32 cost deltas are either 0 or >= ~1e-7 relative), which used to pin
    # every cell at the full alternation cap; clamp to a few ulp.
    eff_rtol = max(solver.rtol, 4.0 * float(jnp.finfo(bw0.dtype).eps))
    state = (bw0, jnp.asarray(jnp.inf, bw0.dtype),
             jnp.zeros_like(bw0), jnp.asarray(jnp.inf, bw0.dtype),
             jnp.asarray(False), jnp.asarray(0, jnp.int32))

    # Convergence-gated alternations: frozen cells are idempotent, so the
    # while_loop (vmapped: runs until *every* cell froze or the cap hits)
    # returns bit-identical results to the fixed-trip loop while costing
    # only the fleet's realized max alternation count (~3-5, not 16).
    def cond(state):
        return jnp.logical_not(state[4]) & (state[5] < solver.max_iters)

    bw, dl, rho, cost, _, iters = jax.lax.while_loop(
        cond, lambda s: body(s), state)

    per = CF.packet_error_rate(bw, tx_power, h_up, noise_psd, waterfall_m0,
                               xp=jnp) * mask
    feasible = jnp.all(jnp.isfinite(bw), axis=-1) \
        & (jnp.sum(bw, axis=-1) <= bandwidth_hz * (1.0 + 1e-6))
    return CellSolution(prune=rho, bandwidth=bw, deadline=dl, per=per,
                        inner_cost=cost, iterations=iters, feasible=feasible)


def solve_fleet(h_up: jnp.ndarray, num_samples: jnp.ndarray,
                cpu_hz: jnp.ndarray, tx_power: jnp.ndarray,
                max_prune: jnp.ndarray, m: jnp.ndarray,
                mask: Optional[jnp.ndarray] = None,
                deadline_cap: Optional[jnp.ndarray] = None, *,
                bandwidth_hz: float, noise_psd: float, waterfall_m0: float,
                model_bits: float, cycles_per_sample: float, weight: float,
                solver: SolverConfig = SolverConfig(),
                interference: Optional[TOPO.InterferenceGraph] = None,
                diagnostics: bool = False, mesh=None) -> CellSolution:
    """vmap of ``solve_cell`` over the leading cell axis.

    Array args are (C, I) except ``m`` (1/samples) and ``deadline_cap``
    (seconds), which are (C,); scalars and units as in ``solve_cell``
    (gains linear, bandwidth Hz, noise W/Hz, payload bits, power W).  The
    whole fleet's per-round control resolves as one XLA program.

    Without ``interference`` each cell owns an independent bandwidth
    budget ``bandwidth_hz`` and the vmapped sub-problems never couple
    (the paper's per-BS setting, unchanged).  With an
    ``InterferenceGraph`` the cells solve inside the damped interference
    fixed point described in the module docstring: every iteration solves
    all cells at effective noise N0 + I_c, recomputes I from the
    allocation, and damps; the returned ``CellSolution`` carries the
    converged per-cell ``interference_psd`` and the number of fixed-point
    iterations taken.

    Returns:
      A ``CellSolution`` with every field carrying the leading cell dim:
      (C, I) for per-client fields, (C,) for deadline / cost / flags.
      Interference-coupled solves also report the final fixed-point
      residual (``fp_residual``, the last damped-iterate movement in
      W/Hz — inspect it instead of assuming convergence at ``fp_iters``)
      and, with ``diagnostics=True``, the per-iteration residual
      trajectory ``fp_residuals`` (shape ``(fp_iters,)``, NaN past the
      realized iteration count) for the telemetry layer.

    ``mesh`` (optional, interference-free path only) constrains the
    leading cell axis of the solver's inputs and outputs to the mesh's
    cell axis (see ``_constrain_cell_axis``), so the vmapped per-cell
    solves partition across devices.
    """
    if mask is None:
        mask = jnp.ones_like(h_up)

    if interference is None:
        fn = partial(solve_cell, bandwidth_hz=bandwidth_hz,
                     noise_psd=noise_psd, waterfall_m0=waterfall_m0,
                     model_bits=model_bits,
                     cycles_per_sample=cycles_per_sample, weight=weight,
                     solver=solver)
        args = (h_up, num_samples, cpu_hz, tx_power, max_prune, m, mask)
        if deadline_cap is not None:
            args = args + (deadline_cap,)
        args = _constrain_cell_axis(args, mesh)
        return _constrain_cell_axis(jax.vmap(fn)(*args), mesh)

    # -- interference-coupled solve: damped fixed point over cells ----------
    def solve_at(i_psd):
        def cell(h, k, f, p, mp, m_c, msk, n_c, *cap):
            return solve_cell(
                h, k, f, p, mp, m_c, msk, cap[0] if cap else None,
                bandwidth_hz=bandwidth_hz, noise_psd=n_c,
                waterfall_m0=waterfall_m0, model_bits=model_bits,
                cycles_per_sample=cycles_per_sample, weight=weight,
                solver=solver)

        noise_c = noise_psd + i_psd
        args = (h_up, num_samples, cpu_hz, tx_power, max_prune, m, mask,
                noise_c)
        if deadline_cap is not None:
            args = args + (deadline_cap,)
        return jax.vmap(cell)(*args)

    dtype = jnp.result_type(h_up.dtype, float)
    i0 = jnp.zeros(h_up.shape[:-1], dtype)
    shapes = jax.eval_shape(solve_at, i0)
    sol0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    # fp_rtol = 0 never freezes -> both the device loop and the host
    # reference run exactly fp_iters iterations (what the cross-path
    # equivalence pins); the scale anchors at the noise floor so an
    # interference-free fleet freezes immediately.
    damping = solver.fp_damping

    resid0 = jnp.full((solver.fp_iters,), jnp.nan, dtype) if diagnostics \
        else None

    def fp_cond(state):
        _, _, _, it, done, _, _ = state
        return jnp.logical_not(done) & (it < solver.fp_iters)

    def fp_body(state):
        i_cur, _, _, it, _, _, resid = state
        sol = solve_at(i_cur)
        i_raw = TOPO.interference_psd(sol.bandwidth, tx_power, interference,
                                      bandwidth_hz)
        i_new = i_cur + damping * (i_raw - i_cur)
        err = jnp.max(jnp.abs(i_new - i_cur))
        scale = noise_psd + jnp.max(i_cur)
        done = err <= solver.fp_rtol * scale
        if resid is not None:
            resid = resid.at[it].set(err)
        return (i_new, i_cur, sol, it + 1, done, err, resid)

    _, i_solved, sol, fp_it, _, fp_err, fp_resid = jax.lax.while_loop(
        fp_cond, fp_body,
        (i0, i0, sol0, jnp.asarray(0, jnp.int32), jnp.asarray(False),
         jnp.asarray(jnp.inf, dtype), resid0))
    return sol._replace(interference_psd=i_solved, fp_iterations=fp_it,
                        fp_residual=fp_err, fp_residuals=fp_resid)
