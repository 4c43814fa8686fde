"""Elastic local-SGD membership — workers that straggle, fault, or leave
degrade throughput instead of stalling the cloud.

Reference: H2O-3's DL trains Hogwild!-plus-model-averaging over a
peer-to-peer cloud that *survives* node trouble via UDP heartbeat gossip
(``water/H2O.java`` heartbeats, ``water/Paxos.java`` membership). Our port
trains as one SPMD program, where a single stalled participant stalls
everything — the opposite robustness profile. This module rebuilds the
reference's elasticity on TPU-native primitives (ROADMAP item 3; the
MXNET-MPI grouped-communicator embedding and the heterogeneous-worker
scheduling of PAPERS.md):

- a **worker** is a PR 9 mesh slice (``slice_meshes(k)``) leased for the
  lifetime of the group through the :class:`~h2o3_tpu.orchestration.
  scheduler.MeshScheduler` (``lease(small=True)``), running K local epochs
  per round on its own data shard;
- a **round** is the local-SGD averaging barrier: live workers' parameters
  are weighted-averaged (weights = shard weight-sums, renormalized over
  whoever reported) and the average is re-broadcast;
- a **heartbeat/progress registry** (round counters + wall-clock leases —
  the TPU-native stand-in for UDP heartbeats) drives a SUSPECT → EJECTED
  state machine: a worker that exhausts its PR 8 dispatch-retry budget
  (``ops/map_reduce.ejection_scope``), blows the per-round deadline, or
  stops heartbeating is ejected; its shards are reassigned to survivors at
  the next round boundary;
- a **(re)joining** worker catches up by cloning the latest averaged model
  before entering the next round (JOINING → ACTIVE at the boundary);
- below the ``H2O3TPU_ELASTIC_MIN_WORKERS`` quorum the build cancels with
  partial results through the PR 8 ``Job.keep_partial()`` path.

State machine (docs/RELIABILITY.md "Elastic training")::

             round reported on time
      ┌────────────────────────────────┐
      ▼                                │
   ACTIVE ──round deadline blown──▶ SUSPECT ──late result──▶ JOINING
      ▲                                │                        │
      │        lease expired ──────────┤── one grace round      │
      │                                ▼                        │
      └──── admitted at boundary ◀─ EJECTED ◀──────────────────-┘
            (clone latest average)     ▲     (rejoin() only)
     retry budget exhausted / fault ───┘

Membership is visible live: ``GET /3/Cloud`` serves a ``workers`` view
(per-worker state / round / last-heartbeat) from :data:`ELASTIC_STATS`,
and ``h2o3_elastic_rounds_total`` / ``h2o3_elastic_ejections_total{reason}``
/ ``h2o3_elastic_workers`` ride in ``/metrics``.
"""

from __future__ import annotations

import contextlib
import os
import queue
import threading
import time
import uuid
import weakref

from h2o3_tpu.utils import lockwitness
from h2o3_tpu.utils import telemetry as _tm
from h2o3_tpu.utils import timeline as _tl
from h2o3_tpu.utils.tracing import TRACER

# -- worker states (the membership state machine) ---------------------------

JOINING, ACTIVE, SUSPECT, EJECTED = "JOINING", "ACTIVE", "SUSPECT", "EJECTED"

#: ejection causes (the ``reason`` label of h2o3_elastic_ejections_total)
R_HEARTBEAT, R_DEADLINE = "heartbeat", "deadline"
R_RETRY, R_FAULT, R_LEFT = "retry_exhausted", "fault", "left"


def min_workers_from_env(default: int = 1) -> int:
    """Quorum: live workers below this cancel the build with partial
    results (``H2O3TPU_ELASTIC_MIN_WORKERS``, default 1 — any survivor
    finishes the job)."""
    try:
        return max(int(os.environ.get("H2O3TPU_ELASTIC_MIN_WORKERS", "")
                       or default), 1)
    except ValueError:
        return default


def lease_secs_from_env(default: float = 30.0) -> float:
    """Heartbeat lease: a worker silent longer than this is considered
    dead, not slow (``H2O3TPU_ELASTIC_LEASE_SECS``)."""
    try:
        return float(os.environ.get("H2O3TPU_ELASTIC_LEASE_SECS", "")
                     or default)
    except ValueError:
        return default


def round_deadline_from_env() -> float:
    """Explicit per-round deadline in seconds
    (``H2O3TPU_ELASTIC_ROUND_DEADLINE_SECS``; 0 = adaptive — see
    :meth:`ElasticGroup._deadline_for`)."""
    try:
        return float(os.environ.get("H2O3TPU_ELASTIC_ROUND_DEADLINE_SECS",
                                    "") or 0.0)
    except ValueError:
        return 0.0


#: hard cap on any single round wait — the backstop that makes a wedged
#: first round (no duration history yet) terminate at all
ROUND_CAP_SECS = 600.0


# -- process-wide membership view (GET /3/Cloud → "workers") ----------------

class _ElasticStats:
    """Rollup behind the ``/3/Cloud`` ``workers`` membership view. Groups
    are per-build; the view must outlive them (a poller watching a finished
    build still sees its final membership). Bounded: the most recent 8
    groups are retained."""

    _MAX_GROUPS = 8

    def __init__(self):
        self._lock = lockwitness.lock("parallel.elastic._ElasticStats._lock")
        self._groups: "dict[str, list[dict]]" = {}
        self._order: list[str] = []

    def update(self, group_id: str, rows: "list[dict]") -> None:
        with self._lock:
            if group_id not in self._groups:
                self._order.append(group_id)
                while len(self._order) > self._MAX_GROUPS:
                    self._groups.pop(self._order.pop(0), None)
            self._groups[group_id] = rows

    def rows(self) -> "list[dict]":
        """Every retained worker row, newest group first."""
        with self._lock:
            out: list[dict] = []
            for gid in reversed(self._order):
                out.extend(self._groups.get(gid, ()))
            return out

    def reset(self) -> None:
        with self._lock:
            self._groups = {}
            self._order = []


#: the process-wide membership view (``GET /3/Cloud`` → ``workers``)
ELASTIC_STATS = _ElasticStats()

#: live groups, for :func:`drain` (weak — a collected group needs no drain)
_LIVE_GROUPS: "weakref.WeakSet" = weakref.WeakSet()


def live_rows() -> "list[dict]":
    """Membership rows of groups whose build is STILL RUNNING — the health
    evaluator's view (utils/health.py). :data:`ELASTIC_STATS` keeps
    finished groups for ``/3/Cloud`` pollers, but a completed build's
    workers stopped heartbeating *legitimately*: rating their silence
    against the lease would page on every finished build forever."""
    out: "list[dict]" = []
    for g in list(_LIVE_GROUPS):
        with g._cond:
            if not g.started or g._stop:
                continue
            out.extend(g._rows_locked())
    return out


def live_groups() -> "list[ElasticGroup]":
    """Groups whose build is still running — the ops-plane remediation
    seam (:mod:`h2o3_tpu.ops_plane.actions` picks the stalled worker's
    group here rather than reaching into :data:`_LIVE_GROUPS`)."""
    out: list = []
    for g in list(_LIVE_GROUPS):
        with g._cond:
            if g.started and not g._stop:
                out.append(g)
    return out


def drain(timeout: float = 30.0) -> None:
    """Join every elastic worker thread still alive.

    An EJECTED worker released from a stalled dispatch finishes that
    dispatch in the background (daemon thread, result discarded) — harmless
    in a server, but a test process exiting the interpreter while XLA
    is mid-dispatch aborts. Chaos scenarios call this after releasing their
    injected stalls."""
    deadline = time.monotonic() + timeout
    for g in list(_LIVE_GROUPS):
        for w in list(g._workers.values()):
            t = w.thread
            if t is not None and t.is_alive():
                t.join(timeout=max(deadline - time.monotonic(), 0.1))


# -- the group --------------------------------------------------------------

class _Worker:
    """One membership slot: a dedicated thread holding one slice lease for
    the group's lifetime, fed rounds through a bounded-poll inbox."""

    __slots__ = ("wid", "state", "shards", "round_done", "last_heartbeat",
                 "ejected_reason", "suspect_round", "thread", "inbox",
                 "devices", "busy_seconds", "rounds_done", "strikes",
                 "exhausted_site")

    def __init__(self, wid: int):
        self.wid = wid
        self.state = ACTIVE
        self.shards: list[int] = []
        self.round_done = 0
        self.last_heartbeat = time.monotonic()
        self.ejected_reason: str | None = None
        self.suspect_round: int | None = None
        self.thread: threading.Thread | None = None
        self.inbox: queue.Queue = queue.Queue()
        self.devices: tuple = ()
        self.busy_seconds = 0.0
        self.rounds_done = 0
        # consecutive deadline misses (reset by an ON-TIME report): a
        # straggler that oscillates miss→late-post→rejoin→miss would
        # otherwise never be ejected — strike 2 ends the cycle
        self.strikes = 0
        # dispatch site an exhausted retry budget was recorded at (set by
        # the map_reduce ejection hook, consumed into the ejection record)
        self.exhausted_site: str | None = None


class ElasticGroup:
    """Membership + round barrier for elastic local-SGD training.

    The driver (``models/deeplearning.py`` ``_fit_elastic``) owns the math;
    the group owns WHO participates: it runs per-worker round thunks on
    dedicated slice-leased threads, applies the per-round deadline and
    heartbeat leases at each barrier, ejects the dead and the chronically
    slow, reassigns their shards, and admits (re)joiners. Thread-safe: every
    shared field mutates under one condition variable, and every wait on it
    is bounded (timeout + predicate recheck — the WTX001 contract)."""

    def __init__(self, n_workers: int, *, scheduler=None,
                 group_id: str | None = None, job=None,
                 lease_secs: float | None = None,
                 round_deadline_secs: float | None = None,
                 shards: "dict[int, list[int]] | None" = None):
        self.n = int(n_workers)
        self.group_id = group_id or f"elastic_{uuid.uuid4().hex[:8]}"
        self._scheduler = scheduler
        self._job = job
        self.lease_secs = (lease_secs if lease_secs is not None
                           else lease_secs_from_env())
        env_deadline = round_deadline_from_env()
        self.round_deadline_secs = (
            round_deadline_secs if round_deadline_secs is not None
            else env_deadline)
        self._cond = lockwitness.condition(
            "parallel.elastic.ElasticGroup._cond")
        self._workers = {w: _Worker(w) for w in range(self.n)}
        if shards:
            for wid, sids in shards.items():
                self._workers[wid].shards = list(sids)
        else:
            for wid in self._workers:
                self._workers[wid].shards = [wid]
        self._orphan_shards: list[int] = []
        self._reports: "dict[int, dict]" = {}
        self._round = 0
        self._stop = False
        self._join_requests: "set[int]" = set()
        self._round_ema: float | None = None
        self.rounds_completed = 0
        self.ejections: "list[dict]" = []
        self.started = False
        _LIVE_GROUPS.add(self)

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "ElasticGroup":
        with self._cond:
            self.started = True
        for w in self._workers.values():
            t = threading.Thread(target=self._worker_main, args=(w,),
                                 name=f"elastic-{self.group_id}-w{w.wid}",
                                 daemon=True)
            with self._cond:
                w.thread = t
            t.start()
        self._publish()
        return self

    def shutdown(self, timeout: float = 5.0) -> None:
        with self._cond:
            self._stop = True
        for w in self._workers.values():
            w.inbox.put(None)
        for w in self._workers.values():
            with self._cond:
                ejected = w.state == EJECTED
            t = w.thread
            if t is not None and not ejected:
                # bounded join of HEALTHY workers only: an ejected one is
                # expected-stuck inside the very dispatch it was ejected
                # for — waiting on it would re-inherit the hang this layer
                # exists to survive (its daemon thread drains in the
                # background; tests call :func:`drain` before exiting)
                t.join(timeout=timeout)
        self._publish()

    # -- worker side ---------------------------------------------------------

    def _worker_main(self, w: _Worker) -> None:
        lease_cm = (self._scheduler.lease(small=True, algo="elastic")
                    if self._scheduler is not None
                    else contextlib.nullcontext())
        with lease_cm as lease:
            if lease is not None:
                with self._cond:
                    w.devices = tuple(lease.devices)
                    w.last_heartbeat = time.monotonic()
            while True:
                try:
                    item = w.inbox.get(timeout=0.25)
                except queue.Empty:
                    with self._cond:
                        if self._stop:
                            return
                    continue
                if item is None:
                    return
                rnd, thunk = item
                self.heartbeat(w.wid)
                t0 = time.monotonic()
                err: BaseException | None = None
                out = None
                try:
                    with _tl.worker_scope(w.wid), \
                            _eject_scope(self, w.wid), \
                            TRACER.span(f"elastic_round:{rnd}",
                                        kind="elastic",
                                        attrs={"worker": w.wid,
                                               "group": self.group_id}):
                        out = thunk()
                except BaseException as e:   # noqa: BLE001 — a worker death
                    err = e                  # is a membership event, never
                                             # a group/build crash
                self._post(w, rnd, out, err, time.monotonic() - t0)

    def heartbeat(self, wid: int) -> None:
        """Progress signal — the UDP heartbeat analog. Workers call it at
        round pickup and between shard dispatches; the sweep reads staleness
        against :attr:`lease_secs`."""
        with self._cond:
            self._workers[wid].last_heartbeat = time.monotonic()

    def _post(self, w: _Worker, rnd: int, out, err, busy_s: float) -> None:
        reason = None
        with self._cond:
            w.last_heartbeat = time.monotonic()
            w.busy_seconds += busy_s
            if err is not None:
                if w.state != EJECTED:   # a swept worker can't eject twice
                    from h2o3_tpu.ops.map_reduce import DispatchFailed
                    reason = (R_RETRY if isinstance(err, DispatchFailed)
                              else R_FAULT)
                    self._eject_locked(w, reason, error=err,
                                       site=w.exhausted_site)
                w.exhausted_site = None
            elif w.state == ACTIVE and rnd == self._round:
                self._reports.setdefault(rnd, {})[w.wid] = out
                w.round_done = rnd
                w.rounds_done += 1
                w.strikes = 0          # on-time report clears the record
            elif w.state == SUSPECT:
                # straggler finished AFTER its round closed: the stale
                # result is discarded and the worker re-enters as a
                # catch-up join — it clones the latest average at the
                # next boundary instead of polluting this one
                w.state = JOINING
                w.suspect_round = None
                self._join_requests.add(w.wid)
            # EJECTED / stale posts: discarded outright
            self._cond.notify_all()
        if reason is not None:
            self._publish()

    # -- coordinator side ----------------------------------------------------

    def live_workers(self) -> "list[int]":
        with self._cond:
            return sorted(w.wid for w in self._workers.values()
                          if w.state == ACTIVE)

    def owned_shards(self, wid: int) -> "list[int]":
        with self._cond:
            return list(self._workers[wid].shards)

    def request_join(self, wid: int) -> None:
        """Ask for slot ``wid`` (an EJECTED or never-started worker) to
        re-enter at the next round boundary; it catches up by cloning the
        latest averaged model (the driver's thunks always start from the
        broadcast average, so the clone is the admission itself)."""
        with self._cond:
            w = self._workers[wid]
            if w.state in (ACTIVE, SUSPECT):
                return
            w.state = JOINING
            w.ejected_reason = None
            w.suspect_round = None
            w.strikes = 0       # an explicit (re)join starts a clean record
            self._join_requests.add(wid)
        self._publish()

    def eject(self, wid: int, reason: str = R_LEFT) -> None:
        """Explicit departure (a worker 'leaving' the cloud)."""
        with self._cond:
            w = self._workers[wid]
            if w.state != EJECTED:
                self._eject_locked(w, reason)
        self._publish()

    def preempt_reassign(self, wid: int,
                         reason: str = "ops_preempt") -> "list[int]":
        """Ops-plane preemptive reassignment: eject a silent worker NOW and
        move its shards to the least-loaded survivors immediately, instead
        of waiting for the round-boundary sweep to notice the lease expire.
        Returns the shard ids that found a new home (empty when the worker
        was already ejected or held none). The worker can re-enter later
        via :meth:`request_join` — that is the action's rollback."""
        with self._cond:
            w = self._workers.get(wid)
            if w is None or w.state == EJECTED:
                return []
            before = set(w.shards)
            self._eject_locked(w, reason)
            self._reassign_orphans_locked()
            moved = sorted(before - set(self._orphan_shards))
        self._publish()
        return moved

    def _deadline_for(self) -> float:
        if self.round_deadline_secs > 0:
            d = self.round_deadline_secs
            if self._round <= 1:
                # round 1 is also the compile round: a tight steady-state
                # deadline must not mass-suspect workers that are merely
                # waiting on XLA (fault ejection still fires immediately)
                d = max(d, 60.0)
            return min(d, ROUND_CAP_SECS)
        if self._round_ema is None:
            # no history yet (round 1 is also the compile round): only the
            # hard cap bounds it
            return ROUND_CAP_SECS
        return min(max(5.0 * self._round_ema, 2.0), ROUND_CAP_SECS)

    def run_round(self, rnd: int, thunks: "dict[int, callable]"
                  ) -> "dict[int, object]":
        """Dispatch ``thunks`` (one per live worker), wait for reports under
        the per-round deadline, then apply the membership sweep at the
        boundary: suspect the missing, eject the dead/chronically slow,
        reassign orphaned shards, admit joiners. Returns the reports that
        made it — averaging over exactly these IS the weight
        renormalization over survivors."""
        t0 = time.monotonic()
        with self._cond:
            self._round = rnd
            self._reports.setdefault(rnd, {})
        for wid, thunk in thunks.items():
            self._workers[wid].inbox.put((rnd, thunk))
        deadline = t0 + self._deadline_for()
        with self._cond:
            while True:
                missing = [wid for wid in thunks
                           if wid not in self._reports[rnd]
                           and self._workers[wid].state == ACTIVE]
                if not missing:
                    break
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                # bounded wait + recheck (WTX001): a lost notify or a dead
                # worker re-polls within 250 ms, never parks forever
                self._cond.wait(timeout=min(left, 0.25))
            # -- boundary sweep (all under the one lock) --
            for wid in missing:
                self._suspect_locked(self._workers[wid])
            self._sweep_suspects_locked()
            self._reassign_orphans_locked()
            self._admit_joins_locked(rnd)
            reports = dict(self._reports.pop(rnd, {}))
            self.rounds_completed += 1
            if reports:
                # EMA over rounds that actually reported — the adaptive
                # deadline tracks real round wall, not deadline timeouts
                wall = time.monotonic() - t0
                self._round_ema = (wall if self._round_ema is None
                                   else 0.5 * self._round_ema + 0.5 * wall)
        _tm.ELASTIC_ROUNDS.inc()
        self._publish()
        return reports

    # -- state machine (all *_locked run under self._cond) -------------------

    def _suspect_locked(self, w: _Worker) -> None:
        if w.state != ACTIVE:
            return
        w.strikes += 1
        if w.strikes >= 2:
            # second consecutive deadline miss: a straggler that posts late
            # and rejoins between misses (ACTIVE→SUSPECT→JOINING→ACTIVE)
            # would oscillate forever — the strike counter survives the
            # catch-up join and ends the cycle (docs: blows the per-round
            # deadline twice ⇒ ejected)
            self._eject_locked(w, R_DEADLINE)
            return
        w.state = SUSPECT
        w.suspect_round = self._round

    def _sweep_suspects_locked(self) -> None:
        now = time.monotonic()
        for w in self._workers.values():
            if w.state != SUSPECT:
                continue
            if now - w.last_heartbeat > self.lease_secs:
                # silent past its lease: dead, not slow
                self._eject_locked(w, R_HEARTBEAT)
            elif self._round - (w.suspect_round or self._round) >= 1:
                # still heartbeating but missed a second boundary: a
                # chronic straggler holds the whole cloud's averaging
                # cadence hostage — eject it (it can rejoin and catch up)
                self._eject_locked(w, R_DEADLINE)

    def _eject_locked(self, w: _Worker, reason: str, error=None,
                      site: str | None = None) -> None:
        w.state = EJECTED
        w.ejected_reason = reason
        w.suspect_round = None
        # graftlint: ok(_locked suffix: every caller holds self._cond)
        self._orphan_shards.extend(w.shards)
        w.shards = []
        rec = {"worker": w.wid, "reason": reason, "round": self._round,
               "at_monotonic": time.monotonic()}
        if error is not None:
            rec["error"] = f"{type(error).__name__}: {error}"
        if site is not None:
            # which dispatch site burned the retry budget — recorded by the
            # map_reduce ejection hook at the site itself, where the name
            # is still known even if the exception gets wrapped on the way
            rec["site"] = site
        # graftlint: ok(_locked suffix: every caller holds self._cond)
        self.ejections.append(rec)
        _tm.ELASTIC_EJECTIONS.labels(reason=reason).inc()
        _tl.TIMELINE.record("elastic", f"eject:w{w.wid}:{reason}")
        if self._job is not None:
            # served by JobV3 as workers_ejected so pollers watch
            # membership decay live
            with self._job._lock:
                self._job.workers_ejected = \
                    getattr(self._job, "workers_ejected", 0) + 1

    def _reassign_orphans_locked(self) -> None:
        """An ejected worker's data shards move to the least-loaded
        survivors at the round boundary (lowest shard count, ties to the
        lowest id — deterministic), so coverage of the training data
        survives membership decay."""
        if not self._orphan_shards:
            return
        live = sorted((w for w in self._workers.values()
                       if w.state == ACTIVE),
                      key=lambda w: (len(w.shards), w.wid))
        if not live:
            return      # nobody to take them — retry at the next boundary
        for sid in sorted(self._orphan_shards):
            tgt = min(live, key=lambda w: (len(w.shards), w.wid))
            tgt.shards.append(sid)
        # graftlint: ok(_locked suffix: every caller holds self._cond)
        self._orphan_shards = []

    def _admit_joins_locked(self, rnd: int) -> None:
        for wid in sorted(self._join_requests):
            w = self._workers[wid]
            if w.state != JOINING:
                continue
            w.state = ACTIVE
            w.round_done = rnd
            w.last_heartbeat = time.monotonic()
            # rebalance: orphans first, else steal one shard from the
            # most-loaded peer (never its last one)
            if not w.shards:
                donor = max((p for p in self._workers.values()
                             if p.state == ACTIVE and len(p.shards) > 1),
                            key=lambda p: (len(p.shards), -p.wid),
                            default=None)
                if donor is not None:
                    w.shards.append(donor.shards.pop())
        # graftlint: ok(_locked suffix: every caller holds self._cond)
        self._join_requests.clear()

    # -- views ---------------------------------------------------------------

    @property
    def ejected_total(self) -> int:
        with self._cond:
            return len(self.ejections)

    def membership(self) -> "dict[int, str]":
        with self._cond:
            return {w.wid: w.state for w in self._workers.values()}

    def summary(self) -> dict:
        """Build-level rollup for model output."""
        with self._cond:
            by_reason: dict = {}
            for e in self.ejections:
                by_reason[e["reason"]] = by_reason.get(e["reason"], 0) + 1
            return {
                "group": self.group_id,
                "workers": self.n,
                "live": sum(1 for w in self._workers.values()
                            if w.state == ACTIVE),
                "rounds": self.rounds_completed,
                "ejections": [dict(e) for e in self.ejections],
                "ejections_by_reason": by_reason,
                "per_worker": {
                    w.wid: {"state": w.state,
                            "rounds_done": w.rounds_done,
                            "busy_seconds": round(w.busy_seconds, 4),
                            "shards": list(w.shards)}
                    for w in self._workers.values()},
            }

    def rows(self) -> "list[dict]":
        """Membership rows (public — the ops-plane remediation reads gaps
        here without reaching into the condition lock)."""
        with self._cond:
            return self._rows_locked()

    def _rows_locked(self) -> "list[dict]":
        now = time.monotonic()
        return [{"worker": w.wid, "group": self.group_id, "state": w.state,
                 "round": w.round_done,
                 "last_heartbeat_ago_ms":
                     round((now - w.last_heartbeat) * 1e3, 1),
                 "devices": list(w.devices), "shards": list(w.shards),
                 "ejected_reason": w.ejected_reason}
                for w in self._workers.values()]

    def _publish(self) -> None:
        with self._cond:
            rows = self._rows_locked()
            live = sum(1 for w in self._workers.values()
                       if w.state == ACTIVE)
        ELASTIC_STATS.update(self.group_id, rows)
        _tm.ELASTIC_WORKERS.set(live)


@contextlib.contextmanager
def _eject_scope(group: ElasticGroup, wid: int):
    """Bind the map_reduce ejection hook for one worker's round: an
    exhausted dispatch-retry budget deep inside any dispatch site records
    the SITE NAME as this worker's pending ejection cause — the
    DispatchFailed that follows unwinds only the worker's round, and
    :meth:`ElasticGroup._post` folds the site into the ejection record
    (the name is known here, at the site, even if the exception gets
    wrapped on the way out)."""
    from h2o3_tpu.ops.map_reduce import ejection_scope

    def hook(what: str, history: list) -> None:
        with group._cond:
            group._workers[wid].exhausted_site = what
        _tl.TIMELINE.record("elastic",
                            f"retry_exhausted:w{wid}:{what}")

    with ejection_scope(hook):
        yield
