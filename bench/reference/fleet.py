"""Plain reference of synchronous federated rounds over orthogonal cells.

One round, as the paper (arXiv:2205.14271) and the fleet semantics define it:

1. Rayleigh fading on the urban path loss 128.1 + 37.6 log10(d_km) dB.
2. The schedule: every client, or a uniform draw of ``m`` per cell (the
   ``m`` largest of one Gumbel draw per client).
3. Algorithm 1 per cell over its scheduled clients: alternate the
   Proposition-1 pruning vertex and the Eq.-(21) least bandwidth (Newton on
   the concave rate curve) until the Eq.-(14a) cost stops moving.  The
   vertex is found here by testing every candidate against every client
   (an I x I comparison), not by a sort.
4. Packet loss drawn at the waterfall PER of the solved bandwidth.
5. Each scheduled client trains on its fixed local batch with global
   magnitude masks at its own rate (one sort of the weights a round), and
   the server applies Eq. (5): the K_i C_i-weighted mean gradient, times
   the learning rate.

Keys are laid out as the system under test lays them out, so the reference
sees the same draws; it takes no array from the system.  ``fault`` plants
the faults that the correctness check must catch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import precision as PR

LN2 = float(np.log(2.0))


def dbm_to_watt(dbm: float) -> float:
    return 10.0 ** (dbm / 10.0) * 1e-3


def seed_key(seed: int):
    """A key from a seed of up to 64 bits (``PRNGKey`` keeps only 32)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def run_keys(seed: int):
    """(init key, round-order key) of a benchmark run."""
    k_init, k_order = jax.random.split(seed_key(seed))
    return k_init, k_order


ROUND_POOL = 16


def round_key(deployment_seed: int, k_order, call):
    """Key of round ``call``: every seed plays the same ``ROUND_POOL``
    rounds (fading, schedule, packet loss drawn from the deployment's
    seed), each pass over them in an order of its own drawn from the run's
    seed.  Algorithm 1 iterates until every cell converges, so the draws
    set a round's work; a shared pool gives every seed the same work."""
    pool = jax.random.split(jax.random.fold_in(
        jax.random.PRNGKey(deployment_seed), 1), ROUND_POOL)
    cycle, pos = jnp.divmod(jnp.asarray(call), ROUND_POOL)
    order = jax.random.permutation(jax.random.fold_in(k_order, cycle),
                                   ROUND_POOL)
    return pool[order[pos]]


def thresholds(params, rho):
    """Global magnitude threshold of each client's rate: the linear
    interpolation of the sorted |w| of every matrix, at the float32
    position rho (n - 1), in the matrices' common dtype (``result_type``,
    the dtype of their concatenation, so a float32 matrix among bfloat16
    ones makes it float32)."""
    mats = [l for l in jax.tree_util.tree_leaves(params) if l.ndim >= 2]
    dt = jnp.result_type(*mats)
    mags = jnp.sort(jnp.concatenate([jnp.abs(l).reshape(-1) for l in mats]))
    n = jnp.float32(mags.shape[0])
    pos = jnp.clip(rho, 0.0, 1.0).astype(jnp.float32) * (n - 1.0)
    lo, hi = jnp.floor(pos), jnp.ceil(pos)
    w_hi = pos - lo
    v_lo = mags[lo.astype(jnp.int32)]
    v_hi = mags[hi.astype(jnp.int32)]
    return v_lo * (1.0 - w_hi).astype(dt) + v_hi * w_hi.astype(dt)


def masks(params, thresh):
    """Keep each matrix's weights with |w| > ``thresh``; every other leaf
    whole."""
    return jax.tree.map(lambda l: (jnp.abs(l) > thresh) if l.ndim >= 2
                        else jnp.ones(l.shape, bool), params)


class Fleet:
    """The deployment: population, wireless constants and the schedule."""

    def __init__(self, spec: dict, task):
        self.spec = spec
        self.task = task
        topo, w = spec["topology"], spec["wireless"]
        self.shape = (topo["num_cells"], topo["clients_per_cell"])
        self.bw_hz = float(w["bandwidth_hz"])
        self.n0 = dbm_to_watt(w["noise_psd_dbm_per_hz"])
        self.p_ue = dbm_to_watt(w["tx_power_ue_dbm"])
        self.p_bs = dbm_to_watt(w["tx_power_bs_dbm"])
        self.m0 = 10.0 ** (w["waterfall_m0_db"] / 10.0)
        self.cycles = float(w["cycles_per_sample"])
        self.t_agg = float(w["aggregation_latency_s"])
        self.model_bits = float(w["model_bits"])
        sm = spec["smoothness"]
        self.beta, self.xi1, self.xi2, self.dbound = (
            sm["beta"], sm["xi1"], sm["xi2"], sm["weight_bound"])
        self.lam = float(spec["weight"])
        sol = spec["solver"]
        self.max_iters, self.bw_iters = sol["max_iters"], sol["bw_iters"]
        self.rtol = max(sol["rtol"], 4.0 * float(np.finfo(np.float32).eps))
        sched = spec["schedule"]
        m = sched.get("participants_per_cell", 0)
        full = sched["participation"] == "full" or not 0 < m < self.shape[1]
        self.m = self.shape[1] if full else m
        self.partial = not full
        self.lr = float(spec["lr"])

        root = jax.random.PRNGKey(int(spec["deployment_seed"]))
        k_pop, k_task, _, _, k_data, _ = jax.random.split(root, 6)
        self._population(k_pop, topo)
        task.build(k_task, k_data)

    def _population(self, key, topo):
        k_drop, k_cpu, k_samp = jax.random.split(key, 3)
        dist = jax.random.uniform(k_drop, self.shape,
                                  minval=topo["min_dist_m"],
                                  maxval=topo["max_dist_m"])
        self.gain = 10.0 ** (-(128.1 + 37.6 * jnp.log10(dist / 1000.0)) / 10.0)
        lo, hi = topo["cpu_hz_range"]
        self.cpu = jax.random.uniform(k_cpu, self.shape, minval=lo, maxval=hi)
        lo, hi = topo["samples_range"]
        self.samples = jax.random.randint(k_samp, self.shape, lo,
                                          hi + 1).astype(jnp.float32)
        self.max_prune = jnp.full(self.shape, float(topo["max_prune"]))

    def set_model_bits(self, params) -> None:
        """D_M: the model's bits where the task says so, else Table I's."""
        if self.spec["wireless"].get("model_bits_from_params"):
            self.model_bits = float(sum(
                l.size * l.dtype.itemsize * 8
                for l in jax.tree_util.tree_leaves(params)))

    # -- control ------------------------------------------------------------

    def _rate(self, bw, h):
        snr = jnp.where(bw > 0, self.p_ue * h / (bw * self.n0), jnp.inf)
        return jnp.where(bw > 0, bw * jnp.log2(1.0 + snr), 0.0)

    def _per(self, bw, h):
        return 1.0 - jnp.exp(-self.m0 * bw * self.n0 / (self.p_ue * h))

    def _vertex(self, t_np, k, mp, m_c):
        """Proposition 1: the least candidate deadline at which the slope
        of the convex piecewise-linear cost is no longer negative."""
        lam = self.lam
        t_max = jnp.max(t_np)
        t_min = jnp.max(t_np * (1.0 - mp))
        w = k * k / t_np
        w = jnp.where(jnp.isfinite(w), w, 0.0)
        cands = jnp.concatenate([t_min[None], t_np])
        later = (t_np[None, :] > cands[:, None]).astype(w.dtype)
        slope = (1.0 - lam) - lam * m_c * (later @ w)
        ok = (cands >= t_min) & (cands <= t_max) & (slope >= 0.0)
        t_star = jnp.min(jnp.where(ok, cands, jnp.inf))
        t_star = jnp.where(jnp.isfinite(t_star), t_star, t_max)
        degenerate = ~jnp.isfinite(t_max)
        t_star = jnp.where(degenerate, jnp.inf, t_star)
        rho = jnp.minimum(jnp.maximum(1.0 - t_star / t_np, 0.0), mp)
        return t_star, jnp.where(degenerate, 1.0, rho)

    def _min_bandwidth(self, target, h):
        """Least B with B log2(1 + p h / (B N0)) = target (Eq. 21)."""
        ceiling = self.p_ue * h / (self.n0 * LN2)
        feasible = target < ceiling
        pos = target > 0.0
        tgt = jnp.where(pos, target, 1.0)
        c = jnp.where(feasible & pos, self.p_ue * h / self.n0, 1.0)
        t_ln2 = tgt * LN2
        snr = jnp.clip(c / tgt, 0.0, float(jnp.finfo(jnp.float32).max))
        b = jnp.maximum(tgt / jnp.maximum(jnp.log2(1.0 + snr), 1e-12), 1.0)
        gap = jnp.maximum(1.0 - t_ln2 / c, 1e-12)
        b = jnp.where(gap < 0.5, jnp.maximum(b, c / (2.0 * gap)), b)
        tiny = float(np.finfo(np.float32).tiny)
        for _ in range(min(max(self.bw_iters, 1), 24)):
            s = c / b
            ln1p = jnp.log1p(s)
            step = (b * ln1p - t_ln2) / jnp.maximum(ln1p - s / (1.0 + s), tiny)
            b2 = b - step
            b = jnp.where(b2 > 0.0, b2, 0.5 * b)
        return jnp.where(feasible | ~pos, jnp.where(pos, b, 0.0), jnp.inf)

    def _bandwidth(self, rho, deadline, k, f, h):
        t_c = (1.0 - rho) * k * self.cycles / f
        slack = deadline - t_c
        payload = (1.0 - rho) * self.model_bits
        target = jnp.where((payload > 0) & (slack > 0), payload / slack, 0.0)
        bw = self._min_bandwidth(target, h)
        bw = jnp.where(payload <= 0.0, 0.0, bw)
        return jnp.where((payload > 0.0) & (slack <= 0.0), jnp.inf, bw)

    def _solve_cell(self, h, k, f, mp, m_c):
        """Algorithm 1 for one cell's m scheduled clients, (m,) arrays."""
        lam, m = self.lam, h.shape[0]

        def cost(dl, bw, rho):
            learning = m_c * jnp.sum(k * (self._per(bw, h) + k * rho))
            return (1.0 - lam) * dl + lam * learning

        def body(_, st):
            bw, dl, rho, prev, done = st
            t_np = self.model_bits / self._rate(bw, h) + k * self.cycles / f
            dl2, rho2 = self._vertex(t_np, k, mp, m_c)
            bw2 = self._bandwidth(rho2, dl2, k, f, h)
            c2 = cost(dl2, bw2, rho2)
            conv = jnp.abs(prev - c2) <= self.rtol * jnp.maximum(jnp.abs(c2),
                                                                 1.0)
            keep = lambda old, new: jnp.where(done, old, new)
            return (keep(bw, bw2), keep(dl, dl2), keep(rho, rho2),
                    keep(prev, c2), done | conv)

        dt = h.dtype
        inf = jnp.asarray(jnp.inf, dt)
        init = (jnp.full((m,), self.bw_hz / m, dt), inf, jnp.zeros((m,), dt),
                inf, False)
        bw, dl, rho, _, _ = jax.lax.fori_loop(0, self.max_iters, body, init)
        return bw, dl, rho

    @functools.partial(jax.jit, static_argnums=(0, 2))
    def control(self, rkey, low: bool = False):
        """One round's channel, schedule, Algorithm-1 solve and packet draws.
        Every per-client field is (C, I); unscheduled clients get 0.  With
        ``low`` (a control run) Algorithm 1 computes in bfloat16, one
        precision below the float32 the wireless model is stated in."""
        c, i = self.shape
        k_fade, k_part, _, k_arr = jax.random.split(rkey, 4)
        k_up, k_down = jax.random.split(k_fade)
        h_up = self.gain * jax.random.exponential(k_up, self.shape)
        h_down = self.gain * jax.random.exponential(k_down, self.shape)
        if self.partial:
            z = jax.random.gumbel(k_part, self.shape)
            cohort = jnp.sort(jax.lax.top_k(z, self.m)[1], axis=-1)
        else:
            cohort = jnp.broadcast_to(jnp.arange(i), self.shape)
        take = lambda a: jnp.take_along_axis(a, cohort, axis=-1)
        rows = jnp.arange(c)[:, None]
        mask = jnp.zeros(self.shape).at[rows, cohort].set(1.0)

        k_s = take(self.samples)
        d = 1.0 - 8.0 * self.xi2
        k_tot = jnp.sum(k_s, axis=-1)
        m_c = jnp.maximum(8.0 * self.xi1 / (d * k_tot),
                          2.0 * self.beta ** 2 * self.m * self.dbound ** 2
                          / (d * k_tot ** 2))
        r_d = self.bw_hz * jnp.log2(1.0 + self.p_bs * take(h_down)
                                    / (self.bw_hz * self.n0))
        t_d = jnp.max(self.model_bits / r_d, axis=-1)

        dt = jnp.bfloat16 if low else jnp.float32
        solved = jax.vmap(self._solve_cell)(*(
            a.astype(dt) for a in (take(h_up), k_s, take(self.cpu),
                                   take(self.max_prune), m_c)))
        bw_c, dl, rho_c = (a.astype(jnp.float32) for a in solved)
        scat = lambda v: jnp.zeros(self.shape).at[rows, cohort].set(v)
        rho, bw = scat(rho_c), scat(bw_c)
        per = self._per(bw, h_up) * mask

        t_c = (1.0 - rho) * self.samples * self.cycles / self.cpu
        r_u = self._rate(bw, h_up)
        t_u = jnp.where(r_u > 0, (1.0 - rho) * self.model_bits / r_u, jnp.inf)
        t_client = t_d[:, None] + t_c + t_u
        arrived = (jax.random.uniform(k_arr, self.shape) >= per)
        active = mask * jnp.isfinite(t_client + self.t_agg)
        agg_w = self.samples * arrived * active
        return dict(cohort=cohort, rho=rho * mask, q_eff=1.0 - active * (
            1.0 - per), deadline=dl, bandwidth_util=jnp.sum(bw, -1)
            / self.bw_hz, agg_w=take(agg_w), rho_c=rho_c)

    # -- learning -----------------------------------------------------------

    def _client(self, params, thresh, idx, precision):
        keep = masks(params, thresh)
        pruned = jax.tree.map(lambda l, m: jnp.where(m, l, 0).astype(
            jnp.float32), params, keep)
        batch = self.task.client_batch(idx)
        loss, g = jax.value_and_grad(
            lambda p: self.task.loss(p, batch, precision))(pruned)
        return loss, jax.tree.map(lambda g, m: jnp.where(m, g, 0.0), g, keep)

    @functools.partial(jax.jit, static_argnums=(0, 3, 4))
    def update(self, params, ctl, precision="f32", fault=None):
        """Eq. (5) from this round's control: new params and mean loss."""
        c, _ = self.shape
        idx = (jnp.arange(c)[:, None] * self.shape[1]
               + ctl["cohort"]).reshape(-1)
        wts = ctl["agg_w"].reshape(-1)
        thresh = thresholds(params, ctl["rho_c"].reshape(-1))
        n = idx.shape[0]
        if fault == "half_batch":        # the second half of the clients
            wts = wts * (jnp.arange(n) < n // 2)
        chunk = min(self.task.client_chunk, n)
        pad = (-n) % chunk
        blocks = lambda a: jnp.pad(a, (0, pad)).reshape(-1, chunk)

        def body(acc, xs):
            i, th, w, live = xs
            loss, g = jax.vmap(lambda t, j: self._client(params, t, j,
                                                         precision))(th, i)
            g = jax.tree.map(lambda a: jnp.einsum(
                "c,c...->...", w, a, precision=jax.lax.Precision.HIGHEST), g)
            gs, ws, ls = acc
            return (jax.tree.map(jnp.add, gs, g), ws + jnp.sum(w),
                    ls + jnp.sum(jnp.where(live, loss, 0.0))), None

        zero = jax.tree.map(lambda l: jnp.zeros(l.shape, jnp.float32), params)
        live = blocks(jnp.ones((n,), bool))
        (gsum, wsum, lsum), _ = jax.lax.scan(
            body, (zero, 0.0, 0.0),
            (blocks(idx), blocks(thresh), blocks(wts), live))
        denom = jnp.where(wsum > 0, wsum, 1.0)
        dt = PR.state_dtype(self.task.param_dtype, precision)
        new = jax.tree.map(
            lambda p, g: jnp.where(wsum > 0, p.astype(jnp.float32)
                                   - self.lr * g / denom, p.astype(jnp.float32)
                                   ).astype(dt), params, gsum)
        if fault == "stale":
            new = params
        return new, lsum / n

    def round(self, params, rkey, precision="f32", fault=None):
        ctl = self.control(rkey, precision != "f32")
        new, loss = self.update(params, ctl, precision, fault)
        if fault == "answer":
            loss = loss * 1.05
        return new, loss, ctl
