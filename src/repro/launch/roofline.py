"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds (DESIGN.md §6):

  compute    = HLO_FLOPs_per_chip / peak_FLOP/s
  memory     = HLO_bytes_per_chip / HBM_bw
  collective = collective_bytes_per_chip / (links * link_bw)

HLO_FLOPs / bytes come from ``compiled.cost_analysis()`` (the partitioned,
i.e. per-chip, module). collective_bytes is NOT in cost_analysis: we parse
the optimized HLO text and sum the output-shape bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
(output bytes ~ bytes moved per chip; reduce-scatter input>output and
all-gather output>input roughly cancel across a typical module — recorded
as a known approximation).

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

import numpy as np

PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # bytes/s
ICI_BW = 50e9  # bytes/s per link (use 1 link as the conservative unit)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  %all-reduce.5 = bf16[16,512]{1,0} all-reduce(
# The kind must be the instruction's opcode (right after the result
# shape), not an operand name: ``get-tuple-element(%all-reduce.1)`` reads
# a tuple collective's result and moves no bytes.
_OP_RE = re.compile(
    r"=\s*([a-z0-9]+)\[([\d,]*)\]\S*\s+(" + "|".join(_COLLECTIVES)
    + r")(?:-done)?\(")
# tuple-result collectives:  = (bf16[8,128], bf16[8,128]) all-reduce(
# (TPU layouts such as {1,0:T(8,128)} put parentheses inside the tuple).
# An async pair counts once, at its ``-done``; ``-start`` never matches.
_TUPLE_RE = re.compile(
    r"=\s*\((.*?)\)\s*(" + "|".join(_COLLECTIVES) + r")(?:-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    nb = _DTYPE_BYTES.get(dtype)
    if nb is None:
        return 0
    if not dims:
        return nb
    return nb * int(np.prod([int(d) for d in dims.split(",") if d]))


@dataclass
class CollectiveStats:
    bytes_by_kind: dict = field(default_factory=dict)
    count_by_kind: dict = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())

    @property
    def total_count(self) -> int:
        return sum(self.count_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    stats = CollectiveStats()

    def add(kind, nbytes):
        stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0) + nbytes
        stats.count_by_kind[kind] = stats.count_by_kind.get(kind, 0) + 1

    for line in hlo_text.splitlines():
        m = _TUPLE_RE.search(line)  # tuple results first (multi-operand)
        if m:
            shapes, kind = m.groups()
            nbytes = sum(_shape_bytes(d, s)
                         for d, s in _SHAPE_RE.findall(shapes))
            add(kind, nbytes)
            continue
        m = _OP_RE.search(line)
        if m:
            dtype, dims, kind = m.groups()
            add(kind, _shape_bytes(dtype, dims))
    return stats


@dataclass
class Roofline:
    flops_per_chip: float
    bytes_per_chip: float
    collective_bytes: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops_per_chip: float = 0.0
    useful_ratio: float = 0.0
    collectives: CollectiveStats | None = None

    def as_dict(self) -> dict:
        d = {
            "flops_per_chip": self.flops_per_chip,
            "bytes_per_chip": self.bytes_per_chip,
            "collective_bytes": self.collective_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "model_flops_per_chip": self.model_flops_per_chip,
            "useful_ratio": self.useful_ratio,
        }
        if self.collectives:
            d["collective_bytes_by_kind"] = self.collectives.bytes_by_kind
            d["collective_count_by_kind"] = self.collectives.count_by_kind
        return d


def analyze(cost: dict, hlo_text: str,
            model_flops_per_chip: float = 0.0) -> Roofline:
    """Roofline terms from the trip-count-aware HLO cost engine
    (launch/hlo_cost.py). ``cost`` (= compiled.cost_analysis()) is kept in
    the record as the XLA cross-check of the non-loop part — XLA counts
    while bodies once, so it under-counts scanned models."""
    from repro.launch.hlo_cost import analyze_hlo

    totals = analyze_hlo(hlo_text)
    flops = float(totals.flops)
    nbytes = float(totals.bytes)
    coll = CollectiveStats(
        bytes_by_kind={k: float(v) for k, v in totals.collective_bytes.items()},
        count_by_kind={k: float(v)
                       for k, v in totals.collective_counts.items()})
    compute_s = flops / PEAK_FLOPS
    memory_s = nbytes / HBM_BW
    collective_s = coll.total_bytes / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    return Roofline(
        flops_per_chip=flops, bytes_per_chip=nbytes,
        collective_bytes=float(coll.total_bytes),
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        bottleneck=bottleneck,
        model_flops_per_chip=model_flops_per_chip,
        useful_ratio=(model_flops_per_chip / flops) if flops else 0.0,
        collectives=coll)


def model_flops(cfg, shape, num_chips: int) -> float:
    """6 * N * D with N = active params (MoE: routed subset), D = tokens
    processed; decode shapes process B tokens per step."""
    from repro.launch.specs import count_params

    n_total = count_params(cfg)
    if cfg.is_moe:
        # active = total - (inactive experts' FFN params)
        per_expert = 3 * cfg.d_model * cfg.d_ff * cfg.num_layers
        inactive = (cfg.num_experts - cfg.experts_per_token) * per_expert
        n_active = n_total - inactive
    else:
        n_active = n_total
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        factor = 6  # fwd 2ND + bwd 4ND
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        factor = 2
    else:  # decode: one token per sequence
        tokens = shape.global_batch
        factor = 2
    return factor * n_active * tokens / num_chips
