"""Roofline analysis from compiled dry-run artifacts (no hardware needed).

Three terms per (arch × shape × mesh), all in seconds-per-step per device:

    compute    = HLO_FLOPs / PEAK_FLOPS_BF16
    memory     = HLO_bytes_accessed / HBM_BW
    collective = Σ link_bytes(op) / ICI_BW

Scan-awareness (verified on this XLA build, DESIGN.md §5): cost_analysis and
the HLO text count a ``lax.scan`` body ONCE regardless of trip count, so deep
models lowered as scans would be undercounted ~L×.  We therefore lower each
scan *block* separately under identical shardings and compose:

    total(term) = cost(full_graph) + Σ_groups (count_g - 1) × cost(block_g)

The composition is property-tested against an unrolled reference in
tests/test_roofline.py.

Collective link-bytes use post-SPMD per-device operand shapes from
``compiled.as_text()`` with ring-algorithm factors: all-gather and
all-to-all move (n-1)/n of the gathered bytes, reduce-scatter (n-1)/n of the
input, all-reduce 2(n-1)/n, collective-permute 1×.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from .mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_COLL_RE = re.compile(
    r"=\s+(?:\()?([a-z0-9]+)\[([\d,]*)\][^ ]*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


@dataclass
class CollectiveStats:
    counts: Dict[str, int] = field(default_factory=dict)
    link_bytes: float = 0.0
    raw_bytes: float = 0.0
    by_op: Dict[str, float] = field(default_factory=dict)


def _shape_bytes(dtype: str, dims: str) -> float:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum per-device collective traffic from post-SPMD HLO text."""
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        dtype, dims, op = m.groups()
        nbytes = _shape_bytes(dtype, dims)
        g = _GROUPS_RE.search(line)
        if g:
            group_size = int(g.group(2))
        else:
            gb = _GROUPS_BRACE_RE.search(line)
            group_size = len(gb.group(1).split(",")) if gb else 2
        n = max(group_size, 2)
        if op == "all-reduce":
            moved = 2.0 * (n - 1) / n * nbytes
        elif op == "all-gather":
            moved = (n - 1) / n * nbytes          # printed shape = output
        elif op == "reduce-scatter":
            moved = (n - 1) * nbytes              # printed shape = output (1/n)
        elif op == "all-to-all":
            moved = (n - 1) / n * nbytes
        else:                                     # collective-permute
            moved = nbytes
        stats.counts[op] = stats.counts.get(op, 0) + 1
        stats.by_op[op] = stats.by_op.get(op, 0.0) + moved
        stats.link_bytes += moved
        stats.raw_bytes += nbytes
    return stats


@dataclass
class GraphCost:
    flops: float = 0.0              # per device
    bytes_accessed: float = 0.0     # per device
    collectives: CollectiveStats = field(default_factory=CollectiveStats)

    def scaled(self, k: float) -> "GraphCost":
        c = CollectiveStats(dict(self.collectives.counts),
                            self.collectives.link_bytes * k,
                            self.collectives.raw_bytes * k,
                            {o: b * k for o, b in self.collectives.by_op.items()})
        return GraphCost(self.flops * k, self.bytes_accessed * k, c)

    def __add__(self, other: "GraphCost") -> "GraphCost":
        c = CollectiveStats(
            {o: self.collectives.counts.get(o, 0) + other.collectives.counts.get(o, 0)
             for o in set(self.collectives.counts) | set(other.collectives.counts)},
            self.collectives.link_bytes + other.collectives.link_bytes,
            self.collectives.raw_bytes + other.collectives.raw_bytes,
            {o: self.collectives.by_op.get(o, 0.0) + other.collectives.by_op.get(o, 0.0)
             for o in set(self.collectives.by_op) | set(other.collectives.by_op)})
        return GraphCost(self.flops + other.flops,
                         self.bytes_accessed + other.bytes_accessed, c)


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as a dict (empty when the backend gives
    no analysis)."""
    return compiled.cost_analysis() or {}


def graph_cost(compiled) -> GraphCost:
    ca = cost_analysis_dict(compiled)
    return GraphCost(
        flops=float(ca.get("flops", 0.0)),
        bytes_accessed=float(ca.get("bytes accessed", 0.0)),
        collectives=parse_collectives(compiled.as_text()),
    )


@dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    flops_per_dev: float
    bytes_per_dev: float
    link_bytes_per_dev: float
    model_flops: float              # analytic 6·N·D (global)
    hlo_total_flops: float          # per-dev flops × n_devices
    useful_ratio: float             # model_flops / hlo_total_flops
    bottleneck: str
    step_time_s: float              # max of the three terms (no overlap)
    mfu_bound: float                # model_flops / (chips·peak·step_time)

    def as_dict(self) -> Dict[str, Any]:
        return dict(self.__dict__)


def roofline_terms(total: GraphCost, n_devices: int, model_flops: float
                   ) -> Roofline:
    compute_s = total.flops / PEAK_FLOPS_BF16
    memory_s = total.bytes_accessed / HBM_BW
    collective_s = total.collectives.link_bytes / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    step = max(terms.values())
    hlo_total = total.flops * n_devices
    return Roofline(
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        flops_per_dev=total.flops, bytes_per_dev=total.bytes_accessed,
        link_bytes_per_dev=total.collectives.link_bytes,
        model_flops=model_flops, hlo_total_flops=hlo_total,
        useful_ratio=model_flops / hlo_total if hlo_total else 0.0,
        bottleneck=bottleneck, step_time_s=step,
        mfu_bound=(model_flops / (n_devices * PEAK_FLOPS_BF16 * step)
                   if step > 0 else 0.0),
    )


def analytic_model_flops(cfg, seq_len: int, global_batch: int, kind: str,
                         n_params: int, n_active: int) -> float:
    """6·N·D train / 2·N·D per forward-token (prefill & decode)."""
    if kind == "train":
        return 6.0 * n_active * seq_len * global_batch
    if kind == "prefill":
        return 2.0 * n_active * seq_len * global_batch
    return 2.0 * n_active * global_batch        # decode: one token per row
