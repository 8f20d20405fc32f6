"""Reduction of a profiler trace to device busy time, kernel time and idle
gaps, on the host's ``perf_counter`` clock.

The harness wraps every traced batch in a ``jax.profiler.TraceAnnotation``
named :data:`MARK` and notes ``perf_counter_ns()`` as it enters; the median
difference between an annotation's start in the trace and its noted time
is the offset between the two clocks.  Everything returned here is in
seconds, with instants on the ``perf_counter`` clock.
"""
from __future__ import annotations

import glob
import os
import re
from statistics import median

MARK = "bench.batch"
TPU_PLANE = "/device:TPU:"
TPU_OPS_LINE = "XLA Ops"
TPU_MODULES_LINE = "XLA Modules"
# where operations run per platform: (plane prefix, ops line, modules line);
# on the CPU, XLA runs on the host's client threads and has no modules line
LAYOUT = {"tpu": (TPU_PLANE, TPU_OPS_LINE, TPU_MODULES_LINE),
          "cpu": ("/host:CPU", "tf_XLA", None)}


def xplane_path(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _events(plane, line_ok):
    out = []
    for line in plane.lines:
        if line_ok(line.name):
            out.extend((e.name, e.start_ns, e.duration_ns) for e in line.events)
    return out


def annotations(pd, name: str = MARK):
    """``(start_ns, dur_ns)`` of every host annotation named ``name``."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            out.extend((s, d) for n, s, d in _events(plane, lambda _: True)
                       if n == name)
    return sorted(out)


def merge(intervals):
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def op_family(name: str) -> str:
    """``fusion.12`` -> ``fusion``: an HLO instruction name without its
    numeric suffix.  On a TPU an op's event is named by its whole HLO text,
    ``%fusion.12 = f32[8]{0} fusion(...)``: the name is what precedes the
    ``=``."""
    if name.startswith("%"):
        name = name[1:].split(" = ", 1)[0]
    return re.sub(r"\.\d+$", "", name)


def reduce_trace(pd, marks_ns, plane_prefix: str = TPU_PLANE,
                 ops_line: str = TPU_OPS_LINE,
                 modules_line: str = TPU_MODULES_LINE,
                 kernel: str = "masked_first_fit") -> dict:
    """Reduce one trace.

    ``marks_ns``: the ``perf_counter_ns()`` noted as each traced batch
    began, in order.  The traced window runs from the first annotation's
    start to the last one's end.  Device figures are averaged over the
    planes that match ``plane_prefix``; operations are read from the lines
    whose name starts with ``ops_line``, whole programs from the line named
    ``modules_line`` (see :data:`LAYOUT`).
    """
    ann = annotations(pd)
    if not ann or len(ann) != len(marks_ns):
        raise ValueError(f"{len(ann)} batch annotations in the trace against "
                         f"{len(marks_ns)} batches traced")
    offset = median(s - m for (s, _), m in zip(ann, marks_ns))
    lo = ann[0][0]
    hi = ann[-1][0] + ann[-1][1]
    planes = [p for p in pd.planes if p.name.startswith(plane_prefix)]
    if not planes:
        raise ValueError(f"no plane named {plane_prefix}* in the trace")
    busy_ns, ops, kernels, modules, merged = 0, {}, [], [], None
    for p in planes:
        evs = [(n, s, d) for n, s, d in
               _events(p, lambda ln: ln.startswith(ops_line))
               if s + d > lo and s < hi]
        m = merge((s, s + d) for _, s, d in evs)
        busy_ns += sum(e - s for s, e in clip(m, lo, hi))
        for n, s, d in evs:
            fam = op_family(n)
            ops[fam] = ops.get(fam, 0) + d
            if fam == kernel:
                kernels.append((s, d))
        modules.extend(sorted((s, d) for n, s, d in _events(
            p, lambda ln: ln == modules_line) if s + d > lo and s < hi))
        if merged is None:
            merged = m
    gaps = []
    prev = lo
    for s, e in clip(merged, lo, hi) + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    to_s = lambda ns: (ns - offset) * 1e-9          # noqa: E731  (perf clock)
    n_planes = len(planes)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9 / n_planes,
        "ops": sorted(((k, v * 1e-9 / n_planes) for k, v in ops.items()),
                      key=lambda kv: -kv[1]),
        "kernel_events": [(to_s(s), d * 1e-9) for s, d in sorted(kernels)],
        "modules": [(to_s(s), d * 1e-9) for s, d in modules],
        "gaps": sorted(((to_s(s), (e - s) * 1e-9) for s, e in gaps),
                       key=lambda g: -g[1]),
    }


def kernels_per_module(red: dict):
    """How many kernel events fall inside each program execution, in
    program order."""
    ks = red["kernel_events"]
    out, j = [], 0
    for s, d in red["modules"]:
        n = 0
        while j < len(ks) and ks[j][0] < s + d:
            if ks[j][0] >= s:
                n += 1
            j += 1
        out.append(n)
    return out


def label_gaps(gaps, spans, top: int = 10):
    """Name each of the ``top`` longest gaps by the innermost host span
    open at its midpoint.  ``spans``: ``(name, start_s, dur_s)`` on the
    perf clock."""
    out = []
    for start, dur in gaps[:top]:
        mid = start + dur / 2
        inner = None
        for name, s, d in spans:
            if s <= mid <= s + d and (inner is None or d < inner[1]):
                inner = (name, d)
        out.append([inner[0] if inner else "host (no span open)", dur])
    return out
