#!/usr/bin/env python3
"""Device-idle time split by the program span open on the host, and a
traced run with the program's spans on the profiler's own clock.

``idle_by_span(gaps, spans)`` splits idle intervals by the innermost span
open over each part of them (``none`` where no span is open).  The
``unspanned_idle_share`` metric applies it to the harness's idle gaps and
``repro.obs`` spans, which ``bench/tracereduce.py`` puts on one clock with
an offset estimated from the batch annotations.

The program can do without the estimate: a tracer made with
``profiler=True`` mirrors every span into the JAX profiler, onto the host
plane beside the device's operations.  Run as a script, this module runs
one cell as ``bench/run.py`` does with that mirror on in the traced part,
and prints one JSON object: the harness's result line, the idle split on
the shared clock and on the offset clock, the ten longest gaps labelled
both ways with the ``py.gc`` spans inside them, the registry's counters
over the window, per-call medians of the spans that split a device call,
and the check-in rate over the first :data:`bench.run.PROFILE_SECONDS`
of the window (the profiled part) and over the rest (after the profiler
has stopped; ``pause_s`` is the stop).  ``--mirror 0`` traces without the
mirror and without the shared-clock report; ``--trace 0`` runs untraced
and gives the rates alone (``--metrics 1`` adds the registry's
counters)::

    python3 bench/spanclock.py --workload biased_hp.r500 --seed 7 \\
        --seconds 51 --trace 1
"""
from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
import time
from bisect import bisect_left
from pathlib import Path
from statistics import median

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import tracereduce                       # noqa: E402

NONE = "none"
# the spans a device call is split into (``accel.engine.match_chunk_jax``)
CALL_SPANS = ("accel.jax.pack", "accel.jax.put", "accel.jax.run",
              "accel.jax.fetch")


# ------------------------------------------------------------ the split

def innermost(spans):
    """Disjoint ``(start, end, name)`` pieces of the spans' union, each
    named by the innermost span open over it.  ``spans``: ``(name, start,
    dur)``, properly nested (one host thread); a span that outlives its
    parent is cut at the parent's end."""
    pieces, stack, t = [], [], -math.inf

    def close(limit):
        nonlocal t
        while stack and stack[-1][0] <= limit:
            end, name = stack.pop()
            if end > t:
                pieces.append((t, end, name))
                t = end

    for name, s, d in sorted(spans, key=lambda x: (x[1], -x[2])):
        close(s)
        if stack and s > t:
            pieces.append((t, s, stack[-1][1]))
        t = s
        end = s + d
        if stack:
            end = min(end, stack[-1][0])
        stack.append((end, name))
    close(math.inf)
    return pieces


def idle_by_span(gaps, spans) -> dict:
    """Seconds of the idle ``gaps`` (``(start, dur)``) under each innermost
    span of ``spans`` (``(name, start, dur)``, the same clock), and under
    :data:`NONE` where no span is open."""
    pieces = innermost(spans)
    starts = [p[0] for p in pieces]
    out = {}
    for g0, gd in gaps:
        g1 = g0 + gd
        covered = 0.0
        i = max(0, bisect_left(starts, g0) - 1)
        while i < len(pieces) and pieces[i][0] < g1:
            s, e, name = pieces[i]
            d = min(e, g1) - max(s, g0)
            if d > 0:
                out[name] = out.get(name, 0.0) + d
                covered += d
            i += 1
        if gd > covered:
            out[NONE] = out.get(NONE, 0.0) + gd - covered
    return out


def span_share(ctx, names) -> float | None:
    """Share of the traced part of a ``bench/run.py`` window inside the
    program spans named in ``names``, in percent; None without such a
    span."""
    total = sum(d for name, _, d in ctx["spans"] if name in names)
    if not total or not ctx["traced_s"]:
        return None
    return 100.0 * total / ctx["traced_s"]


def label_at(pieces, t) -> str:
    """The innermost span open at ``t`` (pieces from :func:`innermost`)."""
    i = bisect_left([p[0] for p in pieces], t + 1e-15) - 1
    if i >= 0 and pieces[i][0] <= t < pieces[i][1]:
        return pieces[i][2]
    return NONE


# ------------------------------------------------------------ the xplane

def host_spans(pd, names):
    """``(name, start_s, dur_s)`` of the host-plane events named in
    ``names`` (the program's mirrored spans), on the profiler's clock."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                           for e in line.events if e.name in names)
    return sorted(out, key=lambda x: x[1])


def device_gaps(pd, plane_prefix=tracereduce.TPU_PLANE,
                ops_line=tracereduce.TPU_OPS_LINE):
    """``(start_s, dur_s)`` of the idle gaps of the first device plane
    between the first batch annotation's start and the last one's end, on
    the profiler's clock (as ``tracereduce.reduce_trace`` finds them)."""
    ann = tracereduce.annotations(pd)
    lo, hi = ann[0][0], ann[-1][0] + ann[-1][1]
    plane = next(p for p in pd.planes if p.name.startswith(plane_prefix))
    busy = tracereduce.merge(
        (s, s + d) for _, s, d in tracereduce._events(
            plane, lambda ln: ln.startswith(ops_line)))
    gaps, prev = [], lo
    for s, e in tracereduce.clip(busy, lo, hi) + [(hi, hi)]:
        if s > prev:
            gaps.append((prev * 1e-9, (s - prev) * 1e-9))
        prev = max(prev, e)
    return gaps


def shared_clock_report(pd, red, marks_ns, tracer, plane_prefix, ops_line,
                        top: int = 10) -> dict:
    """Idle attribution on the shared clock beside the offset one."""
    events = [e for e in tracer.events if e.get("ph") == "X"]
    spans = [(e["name"], e["ts"], e["dur"]) for e in events]
    names = {n for n, _, _ in spans}
    mirrored = host_spans(pd, names)
    gaps = device_gaps(pd, plane_prefix, ops_line)
    pieces = innermost(mirrored)
    ann = tracereduce.annotations(pd)
    offset_s = median(s - m for (s, _), m in zip(ann, marks_ns)) * 1e-9
    # the tracer's clock (µs since its creation) on the perf clock
    base = -tracer.us(0.0) * 1e-6
    perf = [(n, base + s * 1e-6, d * 1e-6) for n, s, d in spans]
    # the offset's error: each mirrored span against its tracer twin
    by_name = {}
    for n, s, _ in perf:
        by_name.setdefault(n, []).append(s)
    for starts in by_name.values():
        starts.sort()
    err = []
    for n, s, _ in mirrored:
        twins = by_name[n]
        j = bisect_left(twins, s - offset_s)
        near = [abs(twins[k] + offset_s - s) for k in (j - 1, j)
                if 0 <= k < len(twins)]
        err.append(min(near))
    gc_perf = [(s, d, e.get("args", {}).get("generation"))
               for e, (_, s, d) in zip(events, perf) if e["name"] == "py.gc"]
    old = [(NONE if n == "host (no span open)" else n, d)
           for n, d in tracereduce.label_gaps(red["gaps"], perf, top=top)]
    longest = sorted(gaps, key=lambda g: -g[1])[:top]
    rows = []
    for (g0, gd), (old_name, old_dur) in zip(longest, old):
        gcs = [(round(d, 6), gen) for s, d, gen in gc_perf
               if s + offset_s < g0 + gd and s + d + offset_s > g0]
        split = idle_by_span([(g0, gd)], mirrored)
        rows.append({"dur_s": gd, "shared": label_at(pieces, g0 + gd / 2),
                     "split": dict(sorted(split.items(),
                                          key=lambda kv: -kv[1])[:6]),
                     "offset_label": old_name, "offset_dur_s": old_dur,
                     "py_gc": gcs})
    return {
        "idle_by_span": idle_by_span(gaps, mirrored),
        "idle_by_span_offset": idle_by_span(red["gaps"], perf),
        "top_gaps": rows,
        "labels_agree": sum(r["shared"] == r["offset_label"] for r in rows),
        "offset_error_s_median": median(err) if err else None,
        "mirrored_spans": len(mirrored), "tracer_spans": len(spans),
    }


def call_medians(tracer) -> dict:
    """Per-call count and median duration (s) of the device call's spans
    and of the drain's grant and scalar-tail spans."""
    durs = {}
    for e in tracer.events:
        if e.get("ph") == "X":
            durs.setdefault(e["name"], []).append(e["dur"] * 1e-6)
    return {n: {"count": len(durs[n]), "median_s": median(durs[n]),
                "sum_s": sum(durs[n])}
            for n in ("accel.match",) + CALL_SPANS
            + ("sim.grants", "sim.drain_scalar", "py.gc") if n in durs}


# ------------------------------------------------------------ the run

def report(name: str, seed: int, seconds: float, trace: bool,
           metrics: bool = False, mirror: bool = True, **run_kw) -> dict:
    """One run of cell ``name`` (``bench.run.run_cell``'s arguments) with
    the shared-clock report (``mirror``) and the rate split; see the
    module's text."""
    from bench import run, system
    from repro import obs

    sims, marks = [], {}
    make, tracing_cls = system.make_simulator, run.Tracing

    def consumed():
        return sum(s.checkins_seen + s.checkins_skipped for s in sims[1:])

    def note(phase):
        # the first part ends where a traced run's profile ends (before the
        # profiler stops) or, untraced, at the first batch edge after the
        # profiled seconds; the rest starts once the profiler has stopped
        t = time.perf_counter()
        if phase == "end":
            marks.update(t1=t, t2=t, n1=consumed(),
                         reg1=counters(obs.get_registry()))
        else:
            marks["t2"] = t

    def made(ep, *a, **k):
        # the window starts with the second replay (the first warms up)
        sim = make(ep, *a, **k)
        sims.append(sim)
        if len(sims) == 2:
            marks["t0"] = time.perf_counter()
            marks["reg0"] = counters(obs.get_registry())
        if len(sims) >= 2:
            go = sim.step_until

            def step_until(until=None):
                done = go(until)
                t = marks["t_last"] = time.perf_counter()
                if not trace and "t1" not in marks and \
                        t >= marks["t0"] + run.PROFILE_SECONDS:
                    note("end")
                return done
            sim.step_until = step_until
        return sim

    made_tracing = []
    system.make_simulator = made
    if trace:
        run.Tracing = _mirrored(tracing_cls, made_tracing, note, mirror)
    elif metrics:
        obs.enable(tracing=False, metrics=True)
    try:
        line, _ = run.run_cell(name, seed, seconds, trace, **run_kw)
        n_end = consumed()
        end = made_tracing[0].extra.pop("counters") if made_tracing \
            else counters(obs.get_registry())
    finally:
        system.make_simulator, run.Tracing = make, tracing_cls
        if metrics and not trace:
            obs.disable()
    out = {"line": line, "workload": name, "seed": seed, "trace": int(trace)}
    if made_tracing:
        out.update(made_tracing[0].extra)
    if end:
        # over the whole window, and over its first part alone
        out["counters"] = _minus(end, marks["reg0"])
    if "t1" in marks and marks["t_last"] > marks["t2"]:
        out["first_s"] = marks["t1"] - marks["t0"]
        out["pause_s"] = marks["t2"] - marks["t1"]
        out["rest_s"] = marks["t_last"] - marks["t2"]
        out["rate_first"] = marks["n1"] / out["first_s"]
        out["rate_rest"] = (n_end - marks["n1"]) / out["rest_s"]
        if end:
            out["counters_first"] = _minus(marks["reg1"], marks["reg0"])
    return out


def _minus(a: dict, b: dict) -> dict:
    return {k: v - b.get(k, 0.0) for k, v in a.items()}


def main(argv=None) -> int:
    from bench.run import NoChip
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--metrics", type=int, choices=(0, 1), default=0)
    ap.add_argument("--mirror", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    try:
        out = report(args.workload, args.seed, args.seconds,
                     bool(args.trace), bool(args.metrics),
                     bool(args.mirror))
    except NoChip as e:
        print(f"spanclock: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


def counters(reg) -> dict:
    if not reg.enabled:
        return {}
    return {m["name"]: m["value"] for m in reg.snapshot()
            if m["kind"] == "counter"}


def _mirrored(base, made, note, mirror):
    """``bench.run.Tracing`` with the program's spans mirrored into the
    profiler (``mirror``), the xplane kept for the shared-clock report, the
    registry's counters kept at the end of the window, and the end of the
    profile and the profiler's stop passed to ``note``."""

    class Mirrored(base):
        def start(self, t0):
            from repro import obs
            super().start(t0)
            # fresh instances with the mirror on; nothing ran in between
            self.tracer, self.registry = obs.enable(
                tracing=True, metrics=True, max_events=4_000_000,
                profiler=mirror)
            self.extra = {}
            made.append(self)

        def _end_profile(self, t1):
            note("end")
            super()._end_profile(t1)
            note("resume")

        def stop(self):
            self.extra["counters"] = counters(self.registry)
            super().stop()

        def reduce(self, platform):
            plane, ops, mods = tracereduce.LAYOUT[platform]
            try:
                pd = tracereduce.load(tracereduce.xplane_path(str(self.dir)))
                red = tracereduce.reduce_trace(pd, self.marks, plane, ops,
                                               mods)
                if mirror:
                    self.extra.update(shared_clock_report(
                        pd, red, self.marks, self.tracer, plane, ops))
                self.extra["calls"] = call_medians(self.tracer)
                return red
            finally:
                shutil.rmtree(self.dir, ignore_errors=True)

    return Mirrored


if __name__ == "__main__":
    sys.exit(main())
