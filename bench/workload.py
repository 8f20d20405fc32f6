"""The one traffic generator: a configuration file plus a traffic file plus a
seed give one episode's inputs, as plain arrays and dicts.

Nothing here imports the program.  The job model and the fleet model are
copies of ``repro.sim.traces.generate_jobs`` and
``repro.sim.devices.DeviceGenerator.sample_chunk`` / ``GeneratorStream``
(the Venn paper's §5.1 job model and Fig. 2/8a fleet), with one change: the
jobs are stratified quantiles of the same distributions, and the job trace
is fixed per cell.  ``--seed`` draws the device stream (a Poisson process
whose totals vary by well under a percent at these rates) and the
scheduler's own random draws, so two seeds run the same jobs against
different fleets.

A traffic file (``bench/traffic/<name>.json``) holds ``base_rate``
(check-ins/s before the diurnal swing), ``num_jobs`` and
``mean_interarrival_s``, and the replay's ``episode_sim_s`` and
``batch_sim_s``.  Other keys (``basis``) are notes.

An episode is ``{"jobs": [...], "chunks": [...], "requirements": [...],
"sched_seed": int, ...}``; ``chunks`` are dicts of read-only arrays
(``times``, ``cpu``, ``mem``, ``speed``, ``resp_z``, ``fail_u``).
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
DAY = 24 * 3600.0
CHUNK_SECONDS = 6 * 3600.0      # longest chunk span, as the program's stream
CHUNK_ROWS = 250_000.0          # rows a chunk span aims at, likewise
JOB_TRACE_SEED = 12345          # every seed of a cell runs the same jobs


def load_json(kind: str, name: str, bench_dir: Path = ROOT) -> dict:
    """``bench/<kind>/<name>.json``: a configuration or a traffic mix."""
    path = Path(bench_dir) / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    with open(path) as fh:
        return json.load(fh)


def sub_seeds(seed: int, n: int):
    """``n`` independent 32-bit seeds from one ``--seed`` of any size."""
    return [int(s) for s in
            np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)]


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _loguniform_q(u: np.ndarray, lo: float, hi: float) -> np.ndarray:
    return np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))


def make_jobs(cfg: dict, traffic: dict, seed: int) -> list:
    """The job trace: Poisson arrivals of mean gap
    ``mean_interarrival_s``.  Sizes follow ``cfg["jobs"]`` (the §5.1 model:
    log-uniform demand, rounds and task time, deadline scaled with demand).

    The trace is the cell's, not the seed's: strata of demand, rounds, task
    time and class paired, ordered and spaced by fixed draws from
    ``seed``, which :func:`make_episode` fixes per cell.  A seed's run
    differs in its device stream and its scheduler's draws, not in its
    jobs: ordering the jobs by the seed moved the device calls per replay
    by up to 30% and the check-in rate by 13% between seeds."""
    jm = cfg["jobs"]
    fixed = np.random.default_rng(0)
    rng = np.random.default_rng(seed)
    n = int(traffic["num_jobs"])
    demand = np.rint(_loguniform_q(_strata(n), jm["demand_lo"],
                                   jm["demand_hi"])).astype(int)
    rounds = np.rint(_loguniform_q(fixed.permutation(_strata(n)),
                                   jm["rounds_lo"], jm["rounds_hi"])).astype(int)
    task = _loguniform_q(fixed.permutation(_strata(n)),
                         jm["task_time_lo"], jm["task_time_hi"])
    classes = cfg["job_classes"]
    cls = fixed.permutation(np.arange(n) % len(classes))
    order = rng.permutation(n)
    gaps = -traffic["mean_interarrival_s"] * np.log1p(-_strata(n))
    arrivals = np.cumsum(rng.permutation(gaps))
    jobs = []
    lo, hi = math.log(jm["demand_lo"]), math.log(jm["demand_hi"])
    for i, k in enumerate(order.tolist()):
        d = int(demand[k])
        frac = (math.log(d) - lo) / (hi - lo)
        jobs.append({
            "job_id": i,
            "cls": classes[int(cls[k])],
            "demand": d,
            "rounds": int(rounds[k]),
            "arrival": float(arrivals[i]),
            "task_mean": float(task[k]),
            "task_sigma": float(jm["task_sigma"]),
            "quorum": float(jm["quorum"]),
            "deadline": float(jm["deadline_lo"]
                              + frac * (jm["deadline_hi"] - jm["deadline_lo"])),
        })
    return jobs


class Fleet:
    """The check-in process: diurnal non-homogeneous Poisson arrivals
    (thinning), log-normal correlated cpu/mem, speed tied to cpu, and
    pre-drawn response-time normals and failure uniforms per row."""

    def __init__(self, fleet: dict, base_rate: float, seed: int):
        self.f = fleet
        self.base_rate = float(base_rate)
        self.rng = np.random.default_rng(seed)

    def rate(self, ts):
        f = self.f
        return self.base_rate * (1.0 + f["diurnal_amplitude"] * np.sin(
            2 * np.pi * (ts - f["diurnal_phase"]) / DAY))

    def max_rate(self) -> float:
        return self.base_rate * (1.0 + self.f["diurnal_amplitude"])

    def chunk(self, t0: float, t1: float) -> dict:
        f, rng = self.f, self.rng
        lam = self.max_rate()
        n = rng.poisson(lam * (t1 - t0))
        ts = np.sort(rng.uniform(t0, t1, size=n))
        times = ts[rng.uniform(0, lam, size=n) < self.rate(ts)]
        n = len(times)
        z = rng.standard_normal((n, 2))
        c = f["cap_corr"]
        z2 = c * z[:, 0] + math.sqrt(1 - c * c) * z[:, 1]
        cpu = f["cpu_med"] * np.exp(f["cpu_sigma"] * z[:, 0])
        mem = f["mem_med"] * np.exp(f["mem_sigma"] * z2)
        speed = (cpu / f["cpu_med"]) ** f["speed_exponent"] * np.exp(
            f["speed_noise_sigma"] * rng.standard_normal(n))
        out = {"times": times, "cpu": cpu, "mem": mem, "speed": speed,
               "resp_z": rng.standard_normal(n), "fail_u": rng.uniform(size=n)}
        for a in out.values():
            a.setflags(write=False)
        return out

    def chunks(self, horizon: float) -> list:
        """Time-sorted chunks covering ``[0, horizon)``, each spanning about
        ``CHUNK_ROWS`` rows at the peak rate (600 s to 6 h)."""
        out, t0 = [], 0.0
        lam = max(self.max_rate(), 1e-9)
        span = min(CHUNK_SECONDS, max(600.0, CHUNK_ROWS / lam))
        while t0 < horizon:
            t1 = min(t0 + span, horizon)
            ck = self.chunk(t0, t1)
            if len(ck["times"]):
                out.append(ck)
            t0 = t1
        return out


def make_episode(config: str, traffic: str, seed: int,
                 bench_dir: Path = ROOT, overrides: dict | None = None) -> dict:
    """One episode's inputs for ``config`` under ``traffic`` from ``seed``;
    ``overrides`` replaces traffic parameters (the tests' small sizes)."""
    cfg = load_json("configs", config, bench_dir)
    tr = dict(load_json("traffic", traffic, bench_dir), **(overrides or {}))
    s_fleet, s_sched = sub_seeds(seed, 2)
    return {
        "config": cfg,
        "traffic": tr,
        "jobs": make_jobs(cfg, tr, JOB_TRACE_SEED),
        "chunks": Fleet(cfg["fleet"], tr["base_rate"], s_fleet).chunks(
            tr["episode_sim_s"]),
        "requirements": cfg["requirements"],
        "sched_seed": s_sched,
        "fail_base": cfg["fleet"]["fail_base"],
        "fail_slow_boost": cfg["fleet"]["fail_slow_boost"],
    }


def checkin_count(ep: dict) -> int:
    return sum(len(c["times"]) for c in ep["chunks"])
