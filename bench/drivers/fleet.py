"""Driver for fleet cells: synchronous federated rounds through
``build_simulation(cfg).simulate``, one call per round.

The configuration gives the deployment (population ranges, Table-I wireless
constants, the task's model) and the mix gives how it is driven (schedule,
learning rate, the engine's chunking).  A fleet topology may sit in either
file, not in both.

The program bakes the population and the client data into its compiled
round (they are closed-over constants), so a seed that drew them would make
every run compile afresh.  The configuration's ``deployment_seed`` therefore
fixes them.  ``--seed`` draws the weights and the order in which the run
plays a fixed pool of rounds (``reference.fleet.round_key``): the rounds'
draws set Algorithm 1's iteration counts, so every seed gets the same
work.

Set-up builds the simulation once, makes the weights on the device in one
jitted call, and drives the compiled round through its first ``CHECKED``
rounds: the reference follows those rounds after the window has closed.
The window then continues from there with the same object.
"""

from __future__ import annotations

import contextlib
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

import program
from reference import fleet as REF
from reference import tasks as REF_TASKS
from yardstick import flops as FLOPS

# the benchmark directory this driver was loaded from: the configuration's
# model family is found there (families.py)
BENCH = pathlib.Path(__file__).resolve().parents[1]
CHECKED = 3                 # rounds that the reference follows
KEY_CALLS = 1 << 15         # round keys drawn up front (one per call)
FAULTS = ("stale", "half_batch", "answer")
NUMBERS = ("init_gap", "loss_gap", "grad_gap", "grad_dir_gap", "change_gap",
           "rho_gap", "per_gap", "deadline_gap", "bandwidth_gap")
LEAF_FLOOR = 1e-3           # leaves below this share of the median leaf's
                            # reference gradient move by rounding alone


def resolve(config: dict, traffic: dict) -> dict:
    """The fleet spec: the configuration's deployment plus the mix."""
    dep, mix = dict(config["fleet"]), dict(traffic["fleet"])
    if "topology" in dep and "topology" in mix:
        raise ValueError("topology is given by both the config and the mix")
    spec = {**dep, **mix, "deployment_seed": config["deployment_seed"]}
    spec["task"] = {**config.get("task", {}), **mix.get("task", {})}
    if spec["schedule"].get("straggler_prob", 0) or \
            "round_deadline_s" in spec["schedule"]:
        raise ValueError("the fleet reference covers no stragglers or "
                         "deadlines")
    return spec


def _program_config(config: dict, spec: dict):
    from repro.core.convergence import SmoothnessParams
    from repro.core.wireless import WirelessConfig
    from repro.fleet import (FleetConfig, FleetTopology, ScheduleConfig,
                             SolverConfig, SyntheticMLPTask, TransformerTask)
    topo, w = spec["topology"], spec["wireless"]
    task = spec["task"]
    if "model" in config:
        fleet_task = TransformerTask(
            arch=program.arch(config, BENCH), seq_len=task["seq_len"],
            local_batch=task["local_batch"], pool_clients=task["pool_clients"])
    else:
        fleet_task = SyntheticMLPTask(
            feature_dim=task["feature_dim"], hidden=tuple(task["hidden"]),
            num_classes=task["num_classes"], local_batch=task["local_batch"],
            data_noise=task["data_noise"])
    model_bits = w["model_bits"]
    if w.get("model_bits_from_params"):
        shapes = jax.eval_shape(fleet_task.init_params, jax.random.PRNGKey(0))
        model_bits = float(sum(l.size * l.dtype.itemsize * 8
                               for l in jax.tree_util.tree_leaves(shapes)))
    sm, sol, sched = spec["smoothness"], spec["solver"], spec["schedule"]
    return FleetConfig(
        topology=FleetTopology(
            num_cells=topo["num_cells"],
            clients_per_cell=topo["clients_per_cell"],
            min_dist_m=topo["min_dist_m"], max_dist_m=topo["max_dist_m"],
            cpu_hz_range=tuple(topo["cpu_hz_range"]),
            samples_range=tuple(topo["samples_range"]),
            max_prune=topo["max_prune"]),
        schedule=ScheduleConfig(
            participation=sched["participation"],
            participants_per_cell=sched.get("participants_per_cell", 0)),
        wireless=WirelessConfig(
            bandwidth_hz=w["bandwidth_hz"],
            noise_psd_w_per_hz=REF.dbm_to_watt(w["noise_psd_dbm_per_hz"]),
            tx_power_ue_w=REF.dbm_to_watt(w["tx_power_ue_dbm"]),
            tx_power_bs_w=REF.dbm_to_watt(w["tx_power_bs_dbm"]),
            waterfall_m0=10.0 ** (w["waterfall_m0_db"] / 10.0),
            model_bits=model_bits,
            cycles_per_sample=w["cycles_per_sample"],
            aggregation_latency_s=w["aggregation_latency_s"]),
        smoothness=SmoothnessParams(beta=sm["beta"], xi1=sm["xi1"],
                                    xi2=sm["xi2"],
                                    weight_bound=sm["weight_bound"]),
        solver=SolverConfig(max_iters=sol["max_iters"],
                            bw_iters=sol["bw_iters"], rtol=sol["rtol"]),
        weight=spec["weight"], rounds=1, lr=spec["lr"],
        seed=spec["deployment_seed"], task=fleet_task,
        kernel=spec["engine"]["kernel"],
        mask_kind=spec["engine"]["mask_kind"],
        cohort_gather=spec["engine"].get("cohort_gather"),
        control_chunk=spec["engine"].get("control_chunk", 0),
        cell_chunk=spec["engine"].get("cell_chunk", 0))


def _host(tree):
    return jax.tree.map(lambda a: np.asarray(a), tree)


def leaf_gap(prog: dict, ref: dict, direction: bool = False) -> float:
    """Worst leaf of |‖prog‖ - ‖ref‖| / max(‖ref‖, median leaf ‖ref‖),
    over the leaves whose reference norm is at least LEAF_FLOOR of the
    median's.  With ``direction``, ‖prog - ref‖ in place of the gap of
    the norms, which also sees a change of direction."""
    pl = jax.tree_util.tree_leaves_with_path(prog)
    rl = dict((jax.tree_util.keystr(p), v)
              for p, v in jax.tree_util.tree_leaves_with_path(ref))
    norms = []
    for path, a in pl:
        a = np.asarray(a, np.float64)
        b = np.asarray(rl[jax.tree_util.keystr(path)], np.float64)
        r = float(np.linalg.norm(b))
        gap = np.linalg.norm(a - b) if direction else \
            abs(np.linalg.norm(a) - r)
        norms.append((float(gap), r))
    med = float(np.median([r for _, r in norms]))
    kept = [(g, r) for g, r in norms if r >= LEAF_FLOOR * med]
    return max(g / max(r, med) for g, r in kept)


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.spec = resolve(config, traffic)
        self.ref_task = REF_TASKS.make(config, self.spec["task"], BENCH)
        # set by a control or a fault run: the reference in the program's
        # place, as (precision, fault)
        self.stand_in = None

    # -- set-up ---------------------------------------------------------

    def setup(self) -> None:
        """Build and compile the round once, then ``start`` from the seed."""
        from repro.fleet import build_simulation
        self.cfg = _program_config(self.config, self.spec)
        sim = build_simulation(self.cfg)
        self.init_fn = jax.jit(sim_task(self.cfg).init_params)
        if self.stand_in is None:
            self.run = sim.simulate
        else:
            self.run = self._stand_in_run(*self.stand_in)
        self.start()

    def start(self) -> None:
        """Weights and round keys from ``self.seed``, then the first CHECKED
        rounds through the compiled round, recorded for the reference."""
        k_init, k_rounds = REF.run_keys(self.seed)
        self.params = self.init_fn(k_init)
        dep = self.spec["deployment_seed"]
        keys = jax.jit(jax.vmap(lambda i: REF.round_key(dep, k_rounds, i)))(
            jnp.arange(KEY_CALLS))
        self.keys = np.asarray(keys)[:, None, :]
        self.calls = 0
        self.p0 = _host(self.params)
        self.losses, self.first = [], None
        for _ in range(CHECKED):
            out = self._call()
            carry, metrics = out
            jax.block_until_ready(out)
            self.losses.append(float(metrics["loss"][0]))
            if self.first is None:
                self.p1 = _host(carry[0])
                self.first = dict(
                    rho=np.asarray(carry[2]), q_eff=np.asarray(carry[1]),
                    deadline=np.asarray(metrics["deadline"][0]),
                    bandwidth_util=np.asarray(metrics["bandwidth_util"][0]))
        self.p3 = _host(self.params)
        self.window_losses = []
        self.pending = None

    def probe(self, stand_in=None) -> dict:
        """Readings of the first CHECKED rounds from ``self.seed``, with the
        compiled round or, for a control or a fault, the reference in its
        place (calibration only)."""
        program_run = self.run
        if stand_in is not None:
            self.run = self._stand_in_run(*stand_in)
        try:
            self.start()
            self.last_ref = self.reference()
            return self.readings(self.last_ref)
        finally:
            self.run = program_run

    def look(self) -> dict:
        """Where the last probe's ``bandwidth_gap`` came from: the cell,
        both sides' share of its band and deadline there, and the pool
        round that was played first (calibration only)."""
        fp, fr = self.first, self.last_ref["first"]
        gap = np.abs(fp["bandwidth_util"] - fr["bandwidth_util"])
        c = int(np.argmax(gap))
        _, k_rounds = REF.run_keys(self.seed)
        order = jax.random.permutation(jax.random.fold_in(k_rounds, 0),
                                       REF.ROUND_POOL)
        return {"cell": c, "pool_round": int(order[0]),
                "bandwidth_prog": float(fp["bandwidth_util"][c]),
                "bandwidth_ref": float(fr["bandwidth_util"][c]),
                "deadline_prog": float(fp["deadline"][c]),
                "deadline_ref": float(fr["deadline"][c]),
                "rho_prog": np.asarray(fp["rho"][c]).tolist(),
                "rho_ref": np.asarray(fr["rho"][c]).tolist()}

    def _precision(self):
        """The products' precision as the configuration states it, around
        every trace and call of the program (the chip's default if none)."""
        precision = self.spec["task"].get("matmul_precision", "default")
        if precision == "default":
            return contextlib.nullcontext()
        return jax.default_matmul_precision(precision)

    def _call(self):
        with self._precision():
            out = self.run(self.params, self.keys[self.calls])
        self.params = out[0][0]
        self.calls += 1
        return out

    def _stand_in_run(self, precision: str, fault):
        fleet = REF.Fleet(self.spec, self.ref_task)
        fleet.set_model_bits(jax.eval_shape(
            self.ref_task.init, jax.random.PRNGKey(0)))

        def run(params, keys):
            new, loss, ctl = fleet.round(params, jnp.asarray(keys[0]),
                                         precision, fault)
            carry = (new, ctl["q_eff"], ctl["rho"])
            return carry, {"loss": loss[None],
                           "deadline": ctl["deadline"][None],
                           "bandwidth_util": ctl["bandwidth_util"][None]}
        return run

    # -- the window -----------------------------------------------------

    def step(self) -> int:
        """Dispatch one round; wait for the one before it, so that at most
        two are in flight."""
        out = self._call()
        if self.pending is not None:
            jax.block_until_ready(self.pending)
        self.pending = out[1]["loss"]
        self.window_losses.append(self.pending)
        return self.cfg.rounds

    def sync(self) -> None:
        jax.block_until_ready(self.params)

    def failed(self) -> int:
        losses = np.concatenate([np.asarray(l) for l in self.window_losses]) \
            if self.window_losses else np.zeros(0)
        return int(np.sum(~np.isfinite(losses)))

    def end_to_end(self, units: int, seconds: float) -> dict:
        return {"fleet_rounds_per_s": (units / seconds, "rounds/s")}

    def work(self, units: int) -> dict:
        """What ``units`` rounds need: the dense forward and backward of
        every round's scheduled clients."""
        topo, task = self.spec["topology"], self.spec["task"]
        sched = self.spec["schedule"]
        m = sched.get("participants_per_cell", 0)
        per_cell = topo["clients_per_cell"]
        if sched["participation"] == "full" or not 0 < m < per_cell:
            m = per_cell
        clients = topo["num_cells"] * m
        if "model" in self.config:
            per = self.ref_task.family.train_flops(
                self.config["model"], task["local_batch"], task["seq_len"])
        else:
            dims = [task["feature_dim"], *task["hidden"], task["num_classes"]]
            per = FLOPS.mlp_train_flops(dims, task["local_batch"])
        return {"units": units,
                "flops": units * clients * per}

    def programs(self) -> list[str]:
        """Text of the compiled round the window ran (from the cache)."""
        if self.stand_in is not None:
            return []
        with self._precision():
            compiled = self.run.lower(self.params, self.keys[0]).compile()
        return [compiled.as_text()]

    def release(self) -> None:
        self.run = self.params = self.pending = None
        self.window_losses = [np.asarray(l) for l in self.window_losses]

    # -- correctness ----------------------------------------------------

    def reference(self) -> dict:
        """The reference's first CHECKED rounds from the same seed."""
        fleet = REF.Fleet(self.spec, self.ref_task)
        k_init, k_rounds = REF.run_keys(self.seed)
        params = jax.jit(self.ref_task.init)(k_init)
        fleet.set_model_bits(params)
        out = {"p0": _host(params), "losses": []}
        with jax.default_matmul_precision("highest"):
            for t in range(CHECKED):
                params, loss, ctl = fleet.round(
                    params, REF.round_key(self.spec["deployment_seed"],
                                          k_rounds, t))
                out["losses"].append(float(loss))
                if t == 0:
                    out["p1"] = _host(params)
                    out["first"] = {k: np.asarray(ctl[k]) for k in
                                    ("rho", "q_eff", "deadline",
                                     "bandwidth_util")}
        out["p3"] = _host(params)
        return out

    def readings(self, ref: dict) -> dict:
        """Every number compared, program against reference."""
        lr = self.spec["lr"]
        f32 = lambda t: jax.tree.map(lambda a: np.asarray(a, np.float64), t)
        p0, p1, p3 = f32(self.p0), f32(self.p1), f32(self.p3)
        r0, r1, r3 = f32(ref["p0"]), f32(ref["p1"]), f32(ref["p3"])
        grad_p = jax.tree.map(lambda a, b: (a - b) / lr, p0, p1)
        grad_r = jax.tree.map(lambda a, b: (a - b) / lr, r0, r1)
        grad_gap = leaf_gap(grad_p, grad_r)
        grad_dir_gap = leaf_gap(grad_p, grad_r, direction=True)
        change_gap = leaf_gap(jax.tree.map(np.subtract, p3, p0),
                              jax.tree.map(np.subtract, r3, r0))
        lp, lr_ = np.asarray(self.losses), np.asarray(ref["losses"])
        fp, fr = self.first, ref["first"]
        dl_p, dl_r = fp["deadline"], fr["deadline"]
        return {
            "init_gap": max(float(np.max(np.abs(np.asarray(a, np.float64)
                                                - np.asarray(b, np.float64))))
                            for a, b in zip(jax.tree.leaves(self.p0),
                                            jax.tree.leaves(ref["p0"]))),
            "loss_gap": float(np.max(np.abs(lp - lr_) / np.abs(lr_))),
            "grad_gap": grad_gap,
            "grad_dir_gap": grad_dir_gap,
            "change_gap": change_gap,
            "rho_gap": float(np.max(np.abs(fp["rho"] - fr["rho"]))),
            "per_gap": float(np.max(np.abs(fp["q_eff"] - fr["q_eff"]))),
            "deadline_gap": float(np.max(np.abs(dl_p - dl_r) / np.abs(dl_r))),
            "bandwidth_gap": float(np.max(np.abs(fp["bandwidth_util"]
                                                 - fr["bandwidth_util"]))),
        }


def sim_task(cfg):
    from repro.fleet import resolve_task
    return resolve_task(cfg)
