"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds, against the peaks of
the report's ``device_kind`` (``PEAKS``):

  compute    = HLO_FLOPs_per_chip / peak_FLOP/s
  memory     = HLO_bytes_per_chip / HBM_bw
  collective = collective_operand_bytes_per_chip / link_bw

``compiled.cost_analysis()`` is evaluated on the post-SPMD per-device
module, so its flops / bytes-accessed numbers are already per chip.
Collective bytes are not in cost_analysis: we parse the optimized HLO and
sum *operand* sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute (start variants included, done variants
skipped so async pairs are not double-counted).
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Optional



@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks."""
    flops: float             # bf16 FLOP/s
    hbm_bw: float            # HBM bytes/s
    hbm_bytes: float         # HBM capacity
    ici_bw: float            # interconnect bytes/s per link


# Keyed by ``jax.Device.device_kind``.  TPU v5e: Google Cloud
# documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s,
# 1,600 Gbit/s of interconnect over 4 links (50 GB/s each).
PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
                             ici_bw=50e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; a kind not in ``PEAKS`` is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" (known: {sorted(PEAKS)})") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

# shape token: dtype[1,2,3] — layout suffix {..} optional
_SHAPE_RE = re.compile(
    r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")

# `%name = <ty> opcode(` — opcode group captures the collective kind
_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s+("
    + "|".join(_COLL_OPS)
    + r")(-start)?\(")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


@dataclasses.dataclass
class CollectiveStats:
    counts: dict            # opcode -> #ops
    bytes_by_op: dict       # opcode -> summed operand bytes
    total_bytes: int

    def as_dict(self) -> dict:
        return {"counts": self.counts, "bytes_by_op": self.bytes_by_op,
                "total_bytes": self.total_bytes}


def collective_stats(hlo_text: str) -> CollectiveStats:
    """Sum operand bytes of every collective in (post-SPMD) HLO text."""
    counts: dict = {}
    by_op: dict = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group(1)
        # operand list = everything after the opcode's open paren
        operands = line[m.end():]
        nbytes = sum(_shape_bytes(d, dims)
                     for d, dims in _SHAPE_RE.findall(operands))
        counts[op] = counts.get(op, 0) + 1
        by_op[op] = by_op.get(op, 0) + nbytes
    return CollectiveStats(counts, by_op,
                           sum(by_op.values()))


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str                     # "16x16" | "2x16x16"
    chips: int
    flops_per_chip: float         # loop-aware (hlo_cost), per device
    bytes_per_chip: float         # loop-aware HBM-traffic model
    collective_bytes_per_chip: float   # ICI bytes (ring-algorithm model)
    peak_memory_per_chip: float   # from memory_analysis
    argument_bytes: float
    output_bytes: float
    temp_bytes: float
    collectives: dict             # opcode -> {count, bytes}
    model_flops: float            # 6ND (train) / 2ND (prefill/decode), global
    wall_s: float                 # lower+compile wall time
    device_kind: str              # key into PEAKS
    raw_xla_flops: float = 0.0    # cost_analysis() (loop bodies counted once)
    raw_xla_bytes: float = 0.0

    # -- derived ------------------------------------------------------------

    @property
    def t_compute(self) -> float:
        return self.flops_per_chip / chip_peaks(self.device_kind).flops

    @property
    def t_memory(self) -> float:
        return self.bytes_per_chip / chip_peaks(self.device_kind).hbm_bw

    @property
    def t_collective(self) -> float:
        return (self.collective_bytes_per_chip
                / chip_peaks(self.device_kind).ici_bw)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO flops x chips) — fraction of compiled compute
        that is 'useful' model math (catches remat/redundancy waste)."""
        hlo_global = self.flops_per_chip * self.chips
        return self.model_flops / hlo_global if hlo_global else 0.0

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d.update(t_compute=self.t_compute, t_memory=self.t_memory,
                 t_collective=self.t_collective, bottleneck=self.bottleneck,
                 useful_flops_ratio=self.useful_flops_ratio)
        return d

    def row(self) -> str:
        return (f"{self.arch:22s} {self.shape:12s} {self.mesh:8s} "
                f"cmp={self.t_compute*1e3:9.3f}ms "
                f"mem={self.t_memory*1e3:9.3f}ms "
                f"col={self.t_collective*1e3:9.3f}ms "
                f"[{self.bottleneck:10s}] "
                f"useful={self.useful_flops_ratio:6.1%} "
                f"hbm={self.peak_memory_per_chip/2**30:7.2f}GiB")


def attention_flops(cfg, shape) -> float:
    """Analytic attention score+value FLOPs (the quadratic term that 6ND
    misses — dominant at 32k+ context).  Causal halving applied; sliding
    windows cap the key range; recurrent mixers count ~0 here (their
    state update is linear and covered by the param term)."""
    b, s = shape.global_batch, shape.seq_len
    h, hd = cfg.num_heads, cfg.head_dim_
    total = 0.0
    for stage in cfg.stages:
        for spec in stage.blocks:
            if spec.kind in ("attn", "local_attn", "mla"):
                window = None
                if spec.kind == "local_attn":
                    window = cfg.local_window
                if shape.name == "long_500k" and cfg.long_context_window:
                    window = min(window or 10**18, cfg.long_context_window)
                if spec.kind == "mla" and cfg.mla is not None:
                    qd = cfg.mla.nope_dim + cfg.mla.rope_dim
                    vd = cfg.mla.v_head_dim
                else:
                    qd = vd = hd
                if shape.mode == "decode":
                    keys = min(s, window) if window else s
                    total += stage.repeats * 2.0 * b * h * (qd + vd) * keys
                else:
                    keys = min(s, window) if window else s
                    # causal: query i sees ~min(i, keys) keys; average s/2
                    # for full attention, ~keys for windowed
                    avg = keys / 2.0 if window is None else keys
                    total += stage.repeats * 2.0 * b * h * (qd + vd) * s * avg
            elif spec.kind == "cross_attn":
                mem = cfg.num_memory_tokens
                if shape.mode == "decode":
                    total += stage.repeats * 2.0 * b * h * 2 * hd * mem
                else:
                    total += stage.repeats * 2.0 * b * h * 2 * hd * s * mem
    return total


def model_flops(cfg, shape, active_params: int) -> float:
    """Global useful model FLOPs for one step.

    train: 6*N*D + 3*attn (fwd 2ND + bwd 4ND), D = batch*seq tokens
    prefill: 2*N*D + attn
    decode: 2*N*batch + attn (one token per sequence, full KV range)
    """
    attn = attention_flops(cfg, shape)
    if shape.mode == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * active_params * tokens + 3.0 * attn
    if shape.mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * active_params * tokens + attn
    return 2.0 * active_params * shape.global_batch + attn


def active_param_count(cfg, params_shape) -> int:
    """Parameter count with MoE experts scaled to the activated top-k.

    Expert-stacked leaves are identified by shape: an ffn leaf whose
    leading (post-layer-stack) dim equals num_experts."""
    import jax

    total = 0
    e = cfg.moe.num_experts if cfg.moe is not None else -1
    for path, leaf in jax.tree_util.tree_leaves_with_path(params_shape):
        p = "/".join(str(getattr(x, "key", getattr(x, "idx", x)))
                     for x in path)
        n = 1
        for s in leaf.shape:
            n *= int(s)
        if cfg.moe is not None and "ffn" in p and "router" not in p \
                and e in leaf.shape[:-1]:
            n = n * cfg.moe.top_k // e
        total += n
    return total


def save_report(report: RooflineReport, path: str) -> None:
    with open(path, "w") as f:
        json.dump(report.as_dict(), f, indent=1)


def load_report(path: str) -> dict:
    with open(path) as f:
        return json.load(f)
