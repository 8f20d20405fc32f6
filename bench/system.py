"""The system under test, driven from one episode's inputs.

This is the only module of the benchmark that imports the program: it turns
the generated episode into the program's own objects (``Job``,
``Requirement``, ``DeviceChunk``) and runs ``Simulator`` with a fresh
``VennScheduler``, one batch of ``batch_sim_s`` simulated seconds per
``step_until`` call.
"""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from repro.core.manager import VennScheduler          # noqa: E402
from repro.core.types import Job, Requirement          # noqa: E402
from repro.sim.devices import DeviceChunk              # noqa: E402
from repro.sim.simulator import SimConfig, Simulator   # noqa: E402


class ReplayStream:
    """The episode's pre-generated check-in chunks as a ``ChunkStream``.
    Each replay hands out fresh ``DeviceChunk`` objects (the simulator
    writes ``atom_ids`` into them) over the same read-only arrays."""

    def __init__(self, ep: dict):
        self.fail_base = ep["fail_base"]
        self.fail_slow_boost = ep["fail_slow_boost"]
        self._chunks = [DeviceChunk(**c) for c in ep["chunks"]]
        self._i = 0

    def next_chunk(self):
        if self._i >= len(self._chunks):
            return None
        self._i += 1
        return self._chunks[self._i - 1]


def make_jobs(ep: dict) -> list:
    reqs = {r["name"]: Requirement.of(r["name"], **r["mins"])
            for r in ep["requirements"]}
    return [Job(job_id=j["job_id"], requirement=reqs[j["cls"]],
                demand_per_round=j["demand"], total_rounds=j["rounds"],
                arrival_time=j["arrival"], task_time_mean=j["task_mean"],
                task_time_sigma=j["task_sigma"], quorum_fraction=j["quorum"],
                deadline=j["deadline"]) for j in ep["jobs"]]


def make_simulator(ep: dict, engine="array", replan=None) -> Simulator:
    """A fresh simulator over a fresh replay of the episode.  ``engine`` and
    ``replan`` default to the program's own choices (the array drain on the
    platform's backend, the replan mode ``auto``)."""
    v = ep["config"]["venn"]
    sched = VennScheduler(seed=ep["sched_seed"], num_tiers=v["num_tiers"],
                          epsilon=v["epsilon"],
                          supply_window=v["supply_window_s"], replan=replan)
    cfg = SimConfig(max_time=ep["traffic"]["episode_sim_s"],
                    max_round_retries=v["max_round_retries"])
    return Simulator(make_jobs(ep), sched, cfg=cfg, stream=ReplayStream(ep),
                     engine=engine, record_grants=True)


def answers(sim: Simulator) -> dict:
    """What a run produced, in the reference's record shapes."""
    rounds = [(r.job_id, r.round_index, r.submit, r.alloc_complete,
               r.complete, r.demand, r.responses, r.failures, r.retries)
              for r in sim.metrics.rounds]
    return {"grants": sim.grant_log, "rounds": rounds,
            "finished": {j.job_id: j.completion_time for j in sim.jobs
                         if j.completion_time is not None}}
