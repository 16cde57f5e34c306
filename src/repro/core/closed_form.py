"""Array-namespace-generic closed forms of the trade-off paper (§II, §IV).

One implementation of every closed-form piece — rates (Eqs. 1/3), waterfall
PER, latency terms (Eqs. 2/4), the Proposition-1 pruning vertex and the
Eq.-(21) minimum-bandwidth inversion (safeguarded Newton on the concave
rate curve) — shared by two execution paths:

* ``xp = numpy``     — the host-side reference path (``core.wireless`` /
  ``core.tradeoff`` delegate here), preserving the original scalar-loop
  semantics including converged early exit.
* ``xp = jax.numpy`` — the fleet path (``repro.fleet.solver``): every
  function is jit/vmap-safe (no data-dependent Python control flow; loops
  run through ``lax.fori_loop``), so per-round control for 10k-1M clients
  compiles into the round scan with no host round-trips.

Functions take an explicit ``xp`` module; tensors may carry arbitrary
leading batch dims (cells, grid combos).  The numpy path forces float64
(matching the original modules); the jax path follows input dtypes so it
respects an ambient ``jax.enable_x64(True)`` context.
"""

from __future__ import annotations

import numpy as np

__all__ = [
    "uplink_sinr",
    "uplink_rate",
    "downlink_rate",
    "packet_error_rate",
    "training_latency",
    "upload_latency",
    "prune_rates_for_deadline",
    "pruning_vertex",
    "min_bandwidth_for_rates",
    "bandwidth_for_deadline",
    "surrogate_m",
]

_LN2 = float(np.log(2.0))


def _f(x, xp):
    """Coerce to the namespace's float array (float64 on the numpy path)."""
    if xp is np:
        return np.asarray(x, dtype=np.float64)
    return xp.asarray(x)


def _iterate(body, state, n: int, xp, done=None):
    """Run ``state = body(state)`` ``n`` times.

    numpy: a Python loop honouring the optional ``done(state)`` early-exit
    (the original modules' behaviour).  jax: a ``lax.fori_loop`` with the
    full trip count — fixed shape, scan/vmap/jit safe; ``body`` must be
    idempotent once converged (all bodies here mask their updates).
    """
    if xp is np:
        for _ in range(n):
            if done is not None and done(state):
                break
            state = body(state)
        return state
    import jax
    return jax.lax.fori_loop(0, n, lambda _, s: body(s), state)


# ---------------------------------------------------------------------------
# Rates / PER / latency terms (Eqs. 1-4 + waterfall PER)
# ---------------------------------------------------------------------------

def uplink_sinr(bandwidth, tx_power, h_up, noise_psd, interference_psd=0.0,
                xp=np):
    """Uplink SINR p_i h_i^u / (B_i (N0 + I)); inf at B_i = 0.

    Interference enters exactly as extra noise power spectral density
    (``interference_psd``, W/Hz — see ``fleet.topology.interference_psd``
    for the co-channel mean-field model), so every closed form of the
    orthogonal system generalizes by the substitution N0 -> N0 + I.  With
    the default ``interference_psd = 0`` this is the paper's Eq.-(3) SNR
    bit-for-bit.

    Units: ``bandwidth`` Hz, ``tx_power`` W, ``h_up`` linear power gain
    (dimensionless; convert dB as 10^(-dB/10)), ``noise_psd`` and
    ``interference_psd`` W/Hz.  Returns the dimensionless SINR.
    """
    b = _f(bandwidth, xp)
    with np.errstate(divide="ignore", invalid="ignore"):
        sinr = _f(tx_power, xp) * _f(h_up, xp) \
            / (b * (noise_psd + interference_psd))
    return xp.where(b > 0.0, sinr, xp.inf)


def uplink_rate(bandwidth, tx_power, h_up, noise_psd, interference_psd=0.0,
                xp=np):
    """Eq. (3): R_i^u = B_i log2(1 + SINR_i); 0 at B_i = 0.

    Units: ``bandwidth`` Hz, ``tx_power`` W, ``h_up`` linear power gain
    (dimensionless; convert dB as 10^(-dB/10)), ``noise_psd`` /
    ``interference_psd`` W/Hz.  Returns the achievable rate in
    bits/second; interference-free (the default) is the paper's form.
    """
    b = _f(bandwidth, xp)
    with np.errstate(divide="ignore", invalid="ignore"):
        sinr = uplink_sinr(b, tx_power, h_up, noise_psd,
                           interference_psd=interference_psd, xp=xp)
        r = b * xp.log2(1.0 + sinr)
    return xp.where(b > 0.0, r, 0.0)


def downlink_rate(bandwidth_hz, tx_power_bs, h_down, noise_psd, xp=np):
    """Eq. (1): the broadcast uses the full bandwidth B.

    Units: ``bandwidth_hz`` Hz, ``tx_power_bs`` W, ``h_down`` linear power
    gain, ``noise_psd`` W/Hz; returns bits/second.
    """
    snr = tx_power_bs * _f(h_down, xp) / (bandwidth_hz * noise_psd)
    return bandwidth_hz * xp.log2(1.0 + snr)


def packet_error_rate(bandwidth, tx_power, h_up, noise_psd, m0,
                      interference_psd=0.0, xp=np):
    """q_i = 1 - exp(-m0 / SINR_i^hz) with SINR per Hz p h / (B (N0 + I));
    increasing in B_i (Lemma 1) and in the interference PSD.

    Units: ``bandwidth`` Hz, ``tx_power`` W, ``h_up`` linear gain,
    ``noise_psd`` / ``interference_psd`` W/Hz, ``m0`` the dimensionless
    waterfall threshold; returns a probability in [0, 1).  The
    interference-free default reduces to the paper's waterfall PER
    q_i = 1 - exp(-m0 B_i N0 / (p_i h_i^u)) bit-for-bit.
    """
    # NOTE: the exponent is spelled -m0 b N_eff / (p h) rather than
    # -m0 / uplink_sinr so the I = 0 default keeps the paper path's exact
    # rounding (reciprocal-of-quotient rounds differently) — the bit
    # compatibility the default-geometry engine trajectories pin.
    b = _f(bandwidth, xp)
    return 1.0 - xp.exp(-m0 * b * (noise_psd + interference_psd)
                        / (_f(tx_power, xp) * _f(h_up, xp)))


def training_latency(prune_rate, num_samples, cycles_per_sample, cpu_hz, xp=np):
    """Eq. (2): t_i^c = (1 - rho_i) K_i d^c / f_i.

    Units: ``prune_rate`` in [0, 1], ``num_samples`` samples,
    ``cycles_per_sample`` CPU cycles/sample, ``cpu_hz`` cycles/second (Hz);
    returns seconds.
    """
    return (1.0 - _f(prune_rate, xp)) * _f(num_samples, xp) \
        * cycles_per_sample / _f(cpu_hz, xp)


def upload_latency(prune_rate, model_bits, rate_up, xp=np):
    """t_i^u = (1 - rho_i) D_M / R_i^u; inf when the rate is 0.

    Units: ``model_bits`` bits, ``rate_up`` bits/second; returns seconds.
    """
    r = _f(rate_up, xp)
    with np.errstate(divide="ignore"):
        t = (1.0 - _f(prune_rate, xp)) * model_bits / r
    return xp.where(r > 0.0, t, xp.inf)


# ---------------------------------------------------------------------------
# Proposition 1 (+ Eq. 16): the pruning sub-problem vertex
# ---------------------------------------------------------------------------

def prune_rates_for_deadline(t_np, deadline, xp=np):
    """Eq. (16): rho_i^min(t~) = max{1 - t~/t_i^np, 0}.

    Both ``t_np`` (per-client no-pruning latency) and ``deadline`` are in
    seconds; returns pruning rates in [0, 1].
    """
    return xp.maximum(1.0 - deadline / _f(t_np, xp), 0.0)


def pruning_vertex(t_np, num_samples, weight, m, max_prune, xp=np, mask=None):
    """Proposition 1, vectorised: optimal deadline t~* and pruning rates.

    g(t~) = (1-lam) t~ + lam m sum_i K_i^2 rho_i^min(t~) is convex
    piecewise-linear with breakpoints at the no-pruning latencies t_i^np.
    The rightward slope at t is (1-lam) - lam m sum_{t_i^np > t} K_i^2/t_i^np
    — nondecreasing in t — so the optimum is the smallest vertex (t~min or a
    breakpoint) whose slope is already >= 0.  Vertices are enumerated via a
    sort + suffix-sum (O(I log I), no Python walk), which is what makes the
    same code serve both the 5-UE host path and vmapped fleet cells.

    ``mask`` (optional, same shape as ``t_np``) excludes non-participating
    clients from the vertex set, the slope and the returned rates.
    Returns ``(t_star, rho)``; an infinite t~max (some UE with zero uplink
    rate) degenerates to ``(inf, ones)`` exactly as the original solver did.

    Units: ``t_np`` seconds, ``num_samples`` samples, ``weight`` the
    dimensionless lambda, ``m`` 1/samples, ``max_prune`` in [0, 1];
    returns (t~* in seconds, rho* in [0, 1]).
    """
    t_np = _f(t_np, xp)
    k = _f(num_samples, xp)
    lam = weight
    if mask is None:
        mask = xp.ones_like(t_np)
    else:
        mask = _f(mask, xp)
    participating = mask > 0.0

    neg_inf = -xp.inf
    t_max = xp.max(xp.where(participating, t_np, neg_inf), axis=-1,
                   keepdims=True)
    t_min = xp.max(xp.where(participating, t_np * (1.0 - _f(max_prune, xp)),
                            neg_inf), axis=-1, keepdims=True)

    # Slope weights K_i^2 / t_i^np (0 for non-participants / infinite t^np).
    with np.errstate(divide="ignore", invalid="ignore"):
        w = xp.where(participating, k * k / t_np, 0.0)
    w = xp.where(xp.isfinite(w), w, 0.0)

    # Sort breakpoints ascending; non-participants to +inf so they fall
    # outside [t_min, t_max] and never become vertices.
    t_break = xp.where(participating, t_np, xp.inf)
    order = xp.argsort(t_break, axis=-1)
    t_sorted = xp.take_along_axis(t_break, order, axis=-1)
    w_sorted = xp.take_along_axis(w, order, axis=-1)
    csum = xp.cumsum(w_sorted, axis=-1)
    total = csum[..., -1:]

    # Candidate vertices: t~min plus every breakpoint.  The active set at
    # candidate t is {t_i^np > t}; with ties, side="right" drops the whole
    # tied group, matching the strict inequality of the reference walk.
    cands = xp.concatenate([t_min, t_sorted], axis=-1)
    if t_sorted.ndim == 1:  # host path / vmapped fleet cells trace as 1-D
        idx = xp.searchsorted(t_sorted, cands, side="right")
    else:  # explicitly batched call
        idx = _batched_searchsorted(t_sorted, cands, xp)
    prefix = xp.concatenate(
        [xp.zeros(csum.shape[:-1] + (1,), dtype=csum.dtype), csum], axis=-1)
    prefix_at = xp.take_along_axis(prefix, idx, axis=-1)
    slope = (1.0 - lam) - lam * m * (total - prefix_at)

    valid = (cands >= t_min) & (cands <= t_max) & (slope >= 0.0)
    t_star = xp.min(xp.where(valid, cands, xp.inf), axis=-1, keepdims=True)
    # No valid vertex (lam ~ 1): the walk's default is t~max.
    t_star = xp.where(xp.isfinite(t_star), t_star, t_max)

    degenerate = ~xp.isfinite(t_max)
    t_star = xp.where(degenerate, xp.inf, t_star)
    rho = xp.minimum(prune_rates_for_deadline(t_np, t_star, xp=xp),
                     _f(max_prune, xp))
    rho = xp.where(degenerate, 1.0, rho) * mask
    return xp.squeeze(t_star, axis=-1), rho


def _batched_searchsorted(sorted_vals, queries, xp):
    """searchsorted(side="right") over matching leading batch dims."""
    # counts of sorted_vals <= query, via broadcast compare; shapes are
    # (..., I) x (..., Q) -> (..., Q).  Used only on the jax path where
    # per-cell client counts are modest (vmapped over cells).
    le = sorted_vals[..., None, :] <= queries[..., :, None]
    return xp.sum(le.astype(xp.int32), axis=-1)


# ---------------------------------------------------------------------------
# Eq. (21): minimum bandwidth meeting a rate / deadline (bisection)
# ---------------------------------------------------------------------------

def min_bandwidth_for_rates(target_rate, tx_power, h_up, noise_psd,
                            iters: int = 80, xp=np):
    """Invert R^u(B) = target (Lemma 1: R^u is increasing in B).

    Solved by safeguarded Newton on f(B) = B ln(1 + c/B) - target ln 2
    with c = p h / N0.  f is increasing and *concave* in B, so from any
    positive start the first Newton step lands at-or-below the root and
    the iteration then climbs monotonically with quadratic convergence —
    a handful of log evaluations replaces the former bracket-growth +
    bisection.  ``iters`` caps the Newton count (clamped — quadratic
    convergence needs far fewer steps than a bisection depth).

    Interference-aware use: pass ``noise_psd = N0 + I_psd`` — every form
    here depends on noise only through the effective PSD (see
    ``uplink_sinr``).

    Targets at/above the capacity ceiling p h / (N0 ln 2) return inf.

    Units: ``target_rate`` bits/second, ``tx_power`` W, ``h_up`` linear
    gain, ``noise_psd`` W/Hz; returns the minimum bandwidth in Hz.
    """
    target, p, h = xp.broadcast_arrays(_f(target_rate, xp), _f(tx_power, xp),
                                       _f(h_up, xp))
    ceiling = p * h / (noise_psd * _LN2)
    feasible = target < ceiling
    pos = target > 0.0

    safe_target = xp.where(pos, target, 1.0)
    c = xp.where(feasible & pos, p * h / noise_psd, 1.0)
    t_ln2 = safe_target * _LN2
    with np.errstate(divide="ignore", invalid="ignore", over="ignore"):
        raw_snr = c / safe_target
        # clip away infs before log2; 1e300 overflows narrow dtypes, so cap
        # at the dtype max there (the numpy/float64 path keeps the original
        # constant bit-for-bit)
        big = 1e300 if xp is np else min(1e300, float(xp.finfo(raw_snr.dtype).max))
        snr_at_target = xp.clip(raw_snr, 0.0, big)
        b0 = safe_target / xp.maximum(xp.log2(1.0 + snr_at_target), 1e-12)
    b0 = xp.maximum(b0, 1.0)
    # Near the capacity ceiling the root diverges as B* -> c / (2 eps)
    # with eps = 1 - target/ceiling; from the low-SNR guess Newton only
    # *doubles* per step in that regime, so seed with the asymptote there
    # (gated to eps < 1/2, where it is within ~2x of the true root —
    # taking it unconditionally would start far above the root at low
    # targets and waste the budget halving back down).
    eps_gap = xp.maximum(1.0 - t_ln2 / c, xp.asarray(1e-12, b0.dtype))
    b0 = xp.where(eps_gap < 0.5, xp.maximum(b0, c / (2.0 * eps_gap)), b0)
    tiny = xp.asarray(np.finfo(np.float32).tiny, b0.dtype)

    def _newton(state):
        (b,) = state
        s = c / b
        ln1p = xp.log1p(s)
        fval = b * ln1p - t_ln2
        fprime = xp.maximum(ln1p - s / (1.0 + s), tiny)
        b2 = b - fval / fprime
        # concavity guarantees monotone convergence once past step one;
        # the guard only catches a wild first step from a far-off guess
        return (xp.where(b2 > 0.0, b2, 0.5 * b),)

    def _converged(state):
        (b,) = state
        s = c / b
        return bool(np.all(np.abs(b * np.log1p(s) - t_ln2)
                           <= 1e-12 * np.maximum(t_ln2, 1.0)))

    (b,) = _iterate(_newton, (b0,), min(max(iters, 1), 24), xp,
                    done=_converged if xp is np else None)
    out = xp.where(pos, b, 0.0)
    return xp.where(feasible | ~pos, out, xp.inf)


def bandwidth_for_deadline(prune, deadline, num_samples, cpu_hz,
                           cycles_per_sample, model_bits, tx_power, h_up,
                           noise_psd, iters: int = 80, xp=np):
    """Eq. (21): per-UE minimum bandwidth meeting the deadline.

    ``prune`` may carry leading batch dims (grid search / cells);
    ``deadline`` broadcasts against it (a missing trailing client dim is
    added).  Zero payload -> 0 bandwidth; positive payload with no slack
    -> inf (infeasible deadline).

    Units: ``deadline`` seconds, ``num_samples`` samples, ``cpu_hz`` Hz,
    ``cycles_per_sample`` cycles/sample, ``model_bits`` bits, ``tx_power``
    W, ``h_up`` linear gain, ``noise_psd`` W/Hz; returns Hz.
    """
    prune = _f(prune, xp)
    deadline = _f(deadline, xp)
    if deadline.ndim < prune.ndim:
        deadline = deadline[..., None]
    prune, deadline = xp.broadcast_arrays(prune, deadline)
    t_c = training_latency(prune, num_samples, cycles_per_sample, cpu_hz, xp=xp)
    slack = deadline - t_c
    payload = (1.0 - prune) * model_bits
    with np.errstate(divide="ignore", invalid="ignore"):
        target = payload / slack
    bw = min_bandwidth_for_rates(
        xp.where((payload > 0) & (slack > 0), target, 0.0),
        tx_power, h_up, noise_psd, iters=iters, xp=xp)
    bw = xp.where(payload <= 0.0, 0.0, bw)
    return xp.where((payload > 0.0) & (slack <= 0.0), xp.inf, bw)


# ---------------------------------------------------------------------------
# Eq. (11): surrogate coefficient m (for device-side cost evaluation)
# ---------------------------------------------------------------------------

def surrogate_m(num_samples, beta, xi1, xi2, weight_bound, xp=np, mask=None):
    """m = max{8 xi1 / (d K), 2 beta^2 I D^2 / (d K^2)}, d = 1 - 8 xi2.

    With ``mask``, the population (I, K) is the participating subset —
    the fleet engine's per-cell surrogate.  Reduces over the last axis.

    Units: ``num_samples`` samples; beta, xi1, xi2, ``weight_bound`` (D)
    are the dimensionless Assumption-1/2 constants.  Returns m in
    1/samples, so m K_i (q_i + K_i rho_i) is a dimensionless cost.
    """
    k = _f(num_samples, xp)
    if mask is not None:
        k = k * _f(mask, xp)
    d = 1.0 - 8.0 * xi2
    k_tot = xp.sum(k, axis=-1)
    count = xp.sum((k > 0).astype(k.dtype), axis=-1)
    k_tot = xp.maximum(k_tot, 1e-30)
    return xp.maximum(8.0 * xi1 / (d * k_tot),
                      2.0 * beta**2 * count * weight_bound**2 / (d * k_tot**2))
