"""Plain reference of the Venn resource manager and its fleet simulation.

A straightforward, per-check-in implementation of the semantics the program
must reproduce, written from the Venn paper (arXiv 2312.08298, Algorithms 1
and 2, §4.4) and the simulation's documented lifecycle.  It imports nothing
of the program and takes only the generated episode (jobs, check-in arrays,
seeds), so it can judge what the program's timed path produced.

The simulation:

* jobs arrive and submit one request per round (demand, quorum =
  ceil(quorum x demand)); a filled request waits for responses until
  ``deadline`` after its fill; quorum completes the round, otherwise the
  round is retried (at most ``max_round_retries`` attempts);
* each check-in is decided at its time: the first candidate request of its
  atom, in priority order, that is unfilled and whose speed band accepts the
  device, gets it; a granted device answers after a log-normal response
  time and fails with a speed-dependent probability (both pre-drawn per
  row);
* events at one instant: check-ins first, then control events in push
  order.

The scheduler (VENN-SCHED) recomputes the plan lazily at the first
check-in after any request arrival or completion, and at a check-in of an
atom its plan does not cover:

* atoms: a check-in's atom is the set of requirement classes it
  satisfies, coded as an integer whose bit b is the b-th class in the order
  first asked, exact for any number of classes; atoms are interned to ids
  in first-seen order, the new atoms of one classification in ascending
  code order;
* supply: per-atom check-in counts over a trailing 24 h window of 60 s
  buckets, each check-in counted under the atom it fell in when the stream
  reached it; the rate is count / min(window, max(now - first check-in,
  bucket));
* IRS (Algorithm 1): jobs of a group in ascending (remaining demand, job
  id); groups claim atoms scarcest first; from the most abundant group down,
  a group takes the shared atoms of a scarcer intersecting group while its
  queue pressure (queue length / allocated rate) is higher; each atom then
  lists its owners and then the other eligible groups scarcest first;
* tiers (Algorithm 2): a group's head request, once per attempt, with a
  profile of at least 4V responses, draws a tier u and is restricted to the
  tier's speed band when V + g_u c < 1 + c.

``speed_dtype`` casts every check-in speed before use: the lower-precision
control runs this same reference with ``np.float32``.
"""
from __future__ import annotations

import heapq
import math
import random

import numpy as np

ARRIVAL, RESPONSE, DEADLINE = 0, 1, 2


class _Req:
    __slots__ = ("job", "round", "demand", "granted", "responses", "failures",
                 "quorum", "alloc_t", "complete_t", "aborted", "submit")

    def __init__(self, job, rnd, demand, quorum, submit, aborted):
        self.job, self.round, self.demand = job, rnd, demand
        self.granted = self.responses = self.failures = 0
        self.quorum, self.submit, self.aborted = quorum, submit, aborted
        self.alloc_t = self.complete_t = None


class _Job:
    __slots__ = ("id", "cls", "demand", "rounds", "arrival", "task_mean",
                 "task_sigma", "quorum", "deadline", "done", "current",
                 "finished_t", "speeds", "rts")

    def __init__(self, d):
        self.id, self.cls, self.demand = d["job_id"], d["cls"], d["demand"]
        self.rounds, self.arrival = d["rounds"], d["arrival"]
        self.task_mean, self.task_sigma = d["task_mean"], d["task_sigma"]
        self.quorum, self.deadline = d["quorum"], d["deadline"]
        self.done = 0
        self.current = None
        self.finished_t = None
        self.speeds, self.rts = [], []      # profile of successful responses

    def remaining(self):
        r = self.current
        return max(r.demand - r.granted, 0) if r is not None else self.demand


class _Group:
    __slots__ = ("name", "jobs", "elig", "rates", "supply", "alloc")

    def __init__(self, name):
        self.name = name
        self.jobs = []
        self.elig = set()           # atom ids
        self.rates = {}             # atom id -> rate, ascending atom id
        self.supply = 0.0
        self.alloc = {}             # atom id -> rate, insertion order

    def pending(self):
        return [j for j in self.jobs
                if j.current is not None and j.current.demand > j.current.granted]

    def queue(self):
        return float(sum(1 for j in self.jobs if j.current is not None))

    def alloc_rate(self):
        return sum(self.alloc.values())


def _pct(sorted_vals, q):
    n = len(sorted_vals)
    return float(sorted_vals[min(n - 1, max(0, int(math.ceil(q * n)) - 1))])


def _pressure(q, rate):
    if q <= 0:
        return 0.0
    if rate <= 0:
        return float("inf")
    return q / rate


class Reference:
    def __init__(self, ep: dict, speed_dtype=np.float64):
        cfg = ep["config"]
        v = cfg["venn"]
        self.V = int(v["num_tiers"])
        if float(v["epsilon"]) != 0.0:
            raise ValueError("the reference implements epsilon = 0 only")
        self.window = float(v["supply_window_s"])
        self.bucket = float(v["supply_bucket_s"])
        self.max_samples = int(v["profile_samples"])
        self.tail_q = float(v["tail_q"])
        self.retries = int(v["max_round_retries"])
        self.fail_base = float(ep["fail_base"])
        self.fail_boost = float(ep["fail_slow_boost"])
        self.rng = random.Random(ep["sched_seed"] + 1)
        self.req_mins = {r["name"]: r["mins"] for r in ep["requirements"]}
        self.jobs = [_Job(d) for d in ep["jobs"]]
        self.chunks = [dict(c, speed=np.asarray(c["speed"], dtype=speed_dtype)
                            .astype(np.float64)) for c in ep["chunks"]]
        # scheduler state
        self.names = []             # requirement names, in order first asked
        self.bit = {}               # name -> its bit in an atom's code
        self.version = 0
        self.groups = {}            # name -> _Group, in order first asked
        self.atom_id = {}           # code -> interned id (first-seen order)
        self.codes = []             # interned id -> code
        self.dirty = True
        self.covered = set()        # atom ids the plan covers
        self.slots = {}             # atom id -> [[req, lo, hi], ...]
        self.live = None            # _live_atoms() until the next change
        self.tier = {}              # id(req) -> (lo, hi) of tiered requests
        self.decided = {}           # job id -> (round, attempt) decided
        # supply: absorbed counts per (bucket, atom); first check-in time
        self.counts = {}            # bucket -> {atom id: count}
        self.totals = {}            # atom id -> count inside the window
        self.t0 = None
        self.abs_ci, self.abs_row = 0, 0    # next check-in to absorb
        # simulation state
        self.heap = []
        self.seq = 0
        self.open = 0
        self.n_done = 0
        self.grants = []
        self.rounds = []

    # ------------------------------------------------------------ stream

    def _intern(self, code):
        if code not in self.atom_id:
            self.atom_id[code] = len(self.codes)
            self.codes.append(code)
            self.live = None
        return self.atom_id[code]

    def _atoms_of(self, cpu, mem):
        """Interned atom ids of the check-ins ``cpu``, ``mem``: the classes
        each satisfies as a bitmask, packed into little-endian 64-bit words
        (one word up to 64 classes), new codes interned ascending."""
        n, R = len(cpu), len(self.names)
        if R == 0:
            return np.full(n, self._intern(0), dtype=np.int64)
        sat = np.ones((n, R), dtype=bool)
        for b, name in enumerate(self.names):
            m = self.req_mins[name]
            for cap, arr in (("cpu", cpu), ("mem", mem)):
                if cap in m:
                    sat[:, b] &= arr >= m[cap]
        packed = np.packbits(sat, axis=1, bitorder="little")
        words = np.pad(packed, ((0, 0), (0, -packed.shape[1] % 8))) \
            .view("<u8")
        if words.shape[1] == 1:
            uniq, inverse = np.unique(words[:, 0], return_inverse=True)
            codes = uniq.tolist()
        else:
            uniq, inverse = np.unique(words, axis=0, return_inverse=True)
            codes = [sum(w << (64 * k) for k, w in enumerate(row))
                     for row in uniq.tolist()]
        for c in sorted(codes):
            self._intern(c)
        lut = np.array([self.atom_id[c] for c in codes], dtype=np.int64)
        return lut[inverse.reshape(-1)]

    def _classify(self, ci, start):
        """Atom ids of chunk ``ci`` from row ``start``."""
        ck = self.chunks[ci]
        if not self.names or len(ck["cpu"]) == start:
            self._intern(0)
        atoms = self._atoms_of(ck["cpu"][start:], ck["mem"][start:])
        if "atoms" not in ck:
            ck["atoms"] = atoms
        else:
            ck["atoms"][start:] = atoms
        ck["version"] = self.version

    def _absorb(self, now):
        """Count every check-in up to ``now`` under its current atom."""
        while self.abs_ci < len(self.chunks):
            ck = self.chunks[self.abs_ci]
            if "atoms" not in ck:
                return
            t = ck["times"]
            hi = int(np.searchsorted(t, now, side="right"))
            lo = self.abs_row
            if hi > lo:
                if self.t0 is None:
                    self.t0 = float(t[lo])
                b = (t[lo:hi] // self.bucket).astype(np.int64)
                radix = len(self.codes)
                key = b * radix + ck["atoms"][lo:hi]
                u, n = np.unique(key, return_counts=True)
                for k, c in zip(u.tolist(), n.tolist()):
                    bk, a = divmod(k, radix)
                    bc = self.counts.setdefault(bk, {})
                    bc[a] = bc.get(a, 0) + c
                    self.totals[a] = self.totals.get(a, 0) + c
                self.abs_row = hi
            if hi < len(t):
                return
            self.abs_ci += 1
            self.abs_row = 0

    def _rates(self, now):
        """Per-atom rates over the trailing window (atoms with traffic)."""
        horizon = int(math.ceil((now - self.window) / self.bucket))
        for b in sorted(b for b in self.counts if b < horizon):
            for c, n in self.counts.pop(b).items():
                self.totals[c] -= n
        t0 = self.t0 if self.t0 is not None else 0.0
        span = min(self.window, max(now - t0, self.bucket))
        return {c: n / span for c, n in self.totals.items() if n > 0}

    # ------------------------------------------------------------ replan

    def _has(self, atom, name):
        return (self.codes[atom] >> self.bit[name]) & 1

    def _replan(self, now):
        self.dirty = False
        self.live = None
        self._absorb(now)
        rates = self._rates(now)
        seen = sorted(rates)
        active = [g for g in self.groups.values() if g.pending()]
        for g in active:
            g.rates = {c: rates[c] for c in seen if self._has(c, g.name)}
            g.elig = set(g.rates)
            g.supply = sum(g.rates.values())
            g.alloc = {}
        order = {}
        for g in active:
            order[g.name] = [j for _, _, j in sorted(
                (float(j.remaining()), j.id, j) for j in g.pending())]
        # Algorithm 1: scarcest first claims, then pressure-driven transfers
        claimed = set()
        for g in sorted(active, key=lambda g: (g.supply, g.name)):
            for c in g.rates:
                if c not in claimed:
                    g.alloc[c] = g.rates[c]
                    claimed.add(c)
        for gj in sorted(active, key=lambda g: (-g.supply, g.name)):
            donors = sorted((gk for gk in active if gk is not gj
                             and gk.supply < gj.supply
                             and gk.elig & gj.elig),
                            key=lambda g: (-g.supply, g.name))
            for gk in donors:
                if _pressure(gj.queue(), gj.alloc_rate()) > \
                        _pressure(gk.queue(), gk.alloc_rate()):
                    shared = [c for c in gj.rates if c in gk.alloc]
                    for c in shared:
                        gj.alloc[c] = gj.alloc.get(c, 0.0) + gk.alloc.pop(c)
                else:
                    break
        prio = {}
        for g in active:
            for c in g.rates:
                if c not in prio:
                    owners = [h for h in active if c in h.alloc]
                    rest = sorted((h for h in active
                                   if c in h.elig and c not in h.alloc),
                                  key=lambda h: (h.supply, h.name))
                    prio[c] = owners + rest
        # Algorithm 2 for each group's head request
        tier = {}
        for g in active:
            job = order[g.name][0]
            req = job.current
            if self.decided.get(job.id) == (req.round, req.aborted):
                if id(req) in self.tier:
                    tier[id(req)] = self.tier[id(req)]
                continue
            self.decided[job.id] = (req.round, req.aborted)
            band = self._decide(job, req, g.alloc_rate())
            if band is not None:
                tier[id(req)] = band
        self.tier = tier
        lowered = {}
        for g in active:
            rows = []
            for pos, job in enumerate(order[g.name]):
                req = job.current
                lo, hi = -math.inf, math.inf
                if pos == 0 and id(req) in tier:
                    lo, hi = tier[id(req)]
                rows.append([req, lo, hi])
            lowered[g.name] = rows
        self.covered = set(seen)
        self.slots = {c: [s for g in prio.get(c, ()) for s in lowered[g.name]]
                      for c in seen}

    def _decide(self, job, req, rate):
        V = self.V
        speeds = np.asarray(job.speeds[-self.max_samples:])
        rts = np.asarray(job.rts[-self.max_samples:])
        n = len(rts)
        t_sched = req.demand - req.granted
        t_sched = t_sched / rate if rate > 0 else float("inf")
        if n >= 8:
            t_resp = float(np.sort(rts)[min(n - 1, int(0.95 * n))])
        else:
            t_resp = job.task_mean * math.exp(1.645 * job.task_sigma)
        if V <= 1 or n < 4 * V or t_sched <= 0:
            return None
        order = np.argsort(speeds)
        sp = speeds[order]
        u = self.rng.randrange(V)
        lo = 0.0 if u == 0 else float(sp[(u * n) // V])
        hi = float("inf") if u == V - 1 else float(sp[min(((u + 1) * n) // V,
                                                          n - 1)])
        i0 = int(np.searchsorted(sp, lo, side="left"))
        i1 = int(np.searchsorted(sp, hi, side="left"))
        tier_rt = np.sort(rts[order][i0:i1])
        t0 = _pct(np.sort(rts), self.tail_q)
        g = 1.0
        if len(tier_rt) and math.isfinite(t0) and t0 > 0:
            g = _pct(tier_rt, self.tail_q) / t0
        c = t_resp / t_sched
        return (lo, hi) if V + g * c < c + 1.0 else None

    # ------------------------------------------------------------ events

    def _push(self, t, kind, payload):
        heapq.heappush(self.heap, (t, self.seq, kind, payload))
        self.seq += 1

    def _submit(self, job, rnd, now, aborted=0):
        req = _Req(job, rnd, job.demand, math.ceil(job.quorum * job.demand),
                   now, aborted)
        job.current = req
        self.open += 1
        if job.cls not in self.groups:
            self.groups[job.cls] = _Group(job.cls)
            self.bit[job.cls] = len(self.names)
            self.names.append(job.cls)
            self.version += 1
        g = self.groups[job.cls]
        if job not in g.jobs:
            g.jobs.append(job)
        self.dirty = True

    def _leave(self, req, now):
        """A request ends (completed or aborted): the scheduler forgets it."""
        job = req.job
        g = self.groups[job.cls]
        if job.rounds - job.done == 0 and job in g.jobs:
            g.jobs.remove(job)
        self.dirty = True

    def _complete(self, req, now):
        req.complete_t = now
        job = req.job
        job.done += 1
        self.rounds.append((job.id, req.round, req.submit, req.alloc_t, now,
                            req.demand, req.responses, req.failures,
                            req.aborted))
        self._leave(req, now)
        job.current = None
        if job.done >= job.rounds:
            job.finished_t = now
            self.n_done += 1
        else:
            self._submit(job, job.done, now)

    def _event(self, t, kind, p):
        if kind == ARRIVAL:
            self._submit(p, p.done, t)
            return
        req = p[0] if kind == RESPONSE else p
        if req.complete_t is not None or req.job.current is not req:
            return
        if kind == RESPONSE:
            _, speed, rt, ok = p
            if ok:
                req.job.speeds.append(speed)
                req.job.rts.append(rt)
                req.responses += 1
            else:
                req.failures += 1
            if req.responses >= req.quorum and req.alloc_t is not None:
                self._complete(req, t)
            return
        if req.responses >= req.quorum:
            self._complete(req, t)
            return
        job = req.job
        self._leave(req, t)
        job.current = None
        if req.aborted + 1 >= self.retries:
            job.done += 1
            if job.done >= job.rounds:
                job.finished_t = t
                self.n_done += 1
                return
        self._submit(job, job.done, t, aborted=req.aborted + 1)

    def _grant(self, req, t, speed, z, u):
        req.granted += 1
        self.grants.append((t, req.job.id, req.round))
        job = req.job
        filled = req.granted >= req.demand
        if filled:
            self.open -= 1
            self.live = None
        rt = job.task_mean / (speed if speed > 1e-3 else 1e-3) \
            * math.exp(job.task_sigma * z)
        ok = not (u < self.fail_base + self.fail_boost / (1.0 + speed))
        self._push(t + rt, RESPONSE, (req, speed, rt, ok))
        if filled and req.alloc_t is None:
            req.alloc_t = t
            self._push(t + job.deadline, DEADLINE, req)
        return filled

    # ------------------------------------------------------------ decide

    def _live_atoms(self):
        """Atom ids whose check-ins need a decision: uncovered atoms (the
        plan is recomputed there) and atoms with an unfilled candidate.
        Kept until a replan, a fill or a new atom changes it."""
        if self.live is None:
            self.live = np.ones(len(self.codes), dtype=bool)
            for a in self.covered:
                self.live[a] = any(s[0].demand > s[0].granted
                                   for s in self.slots[a])
        return self.live

    def run(self, horizon: float) -> dict:
        for j in self.jobs:
            self._push(j.arrival, ARRIVAL, j)
        self.ci, self.row = 0, 0
        if self.chunks:
            self._classify(0, 0)
        n_jobs = len(self.jobs)
        while self.n_done < n_jobs:
            if self._drain(horizon):
                break
            if not self.heap or self.heap[0][0] > horizon:
                break
            t, _, kind, p = heapq.heappop(self.heap)
            self._event(t, kind, p)
        return {"grants": self.grants, "rounds": self.rounds,
                "finished": {j.id: j.finished_t for j in self.jobs
                             if j.finished_t is not None}}

    def _drain(self, horizon):
        """Decide check-ins up to the next control event; True once a
        check-in lies past ``horizon``."""
        while self.ci < len(self.chunks):
            ck = self.chunks[self.ci]
            times = ck["times"]
            if self.row >= len(times):
                # the next chunk is classified as soon as the stream
                # reaches it, before any control event in between
                self.ci += 1
                self.row = 0
                if self.ci < len(self.chunks):
                    self._classify(self.ci, 0)
                continue
            if ck["version"] != self.version:
                self._classify(self.ci, self.row)
            top = self.heap[0][0] if self.heap else math.inf
            if times[self.row] > min(top, horizon):
                return times[self.row] > horizon and top > horizon
            stop = int(np.searchsorted(times, min(top, horizon), side="right"))
            if not self.open:
                self.row = stop
                continue
            atoms, speed = ck["atoms"], ck["speed"]
            if self.dirty:
                idx = [self.row]
            else:
                idx = (np.flatnonzero(self._live_atoms()[atoms[self.row:stop]])
                       + self.row).tolist()
            self.row = stop
            for i in idx:
                t = float(times[i])
                atom = int(atoms[i])
                s = float(speed[i])
                replan = self.dirty or atom not in self.covered
                if replan:
                    self._replan(t)
                req = None
                for r, lo, hi in self.slots.get(atom, ()):
                    if r.demand > r.granted and lo <= s < hi:
                        req = r
                        break
                if req is not None:
                    filled = self._grant(req, t, s, float(ck["resp_z"][i]),
                                         float(ck["fail_u"][i]))
                    if filled or self.heap[0][0] < top:
                        replan = True
                if replan:                  # the plan, the open set or the
                    self.row = i + 1        # next event moved: re-scan
                    break
        return False


def run_reference(ep: dict, horizon: float, speed_dtype=np.float64) -> dict:
    """Grants ``(time, job, round)``, round records ``(job, round, submit,
    filled, complete, demand, responses, failures, retries)`` and job
    finish times of one episode simulated to ``horizon``."""
    return Reference(ep, speed_dtype).run(horizon)
