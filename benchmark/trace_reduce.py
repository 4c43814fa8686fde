"""From a profiler trace (``.xplane.pb``) to numbers.

``load`` reads the file with nothing but JAX (``jax.profiler.ProfileData``)
into plain ``Event`` lists; ``reduce`` turns those into what the per-layer
metrics read: the traced window, busy seconds per device, self seconds per
operation name, the idle gaps and what the host was doing in each.

What a trace holds (looked at by hand on the v5e, PR 22):

- one plane per chip, ``/device:TPU:<n>``. Its line ``XLA Modules`` holds
  one event per run of a compiled program, named ``jit_<function>(<hash>)``.
  Its line ``XLA Ops`` holds one event per executed HLO instruction, and the
  event's name is the instruction's whole text (``%fusion.591 = s8[11000000]
  {...} fusion(...), kind=kCustom, calls=...``): the instruction's name and
  opcode are parsed out of it. Instruction names repeat from one program to
  the next, so an operation is keyed by the program that holds it in time:
  ``jit__boost_scan_jit/fusion.591``. Control flow nests: a ``while`` event
  spans the events of its body. So time by name is SELF time (an event's
  duration minus its children's), and busy time is the union of intervals;
- the host plane ``/host:CPU``, one line per thread. The benchmark's spans
  are on the line ``python3`` as ``bench:<name>``
  (``jax.profiler.TraceAnnotation``), on the same clock as the device planes.
  With the Python tracer on, that thread's Python calls are there too, as
  ``$file.py:<line> <function>``.

On CPU (the rehearsal) there is no device plane; the host's XLA executor
threads carry events with an ``hlo_op`` stat, and those stand in as ONE
pseudo-device so that the same code path runs. Nothing from such a run is
ever written under a device metric's name: ``run.py`` refuses to report off
a TPU outside ``--selftest``.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MODULE_RUN = re.compile(r"^(?P<module>.+?)\(\d+\)$")
WINDOW = "bench:window"
PY_EVENT = re.compile(r"^\$(?P<file>[^:]+\.py):\d+ (?P<func>.+)$")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")
#: operations that only contain others: never compute beside a collective
CONTROL_FLOW = re.compile(r"^(while|conditional|call)(\.|$)")
#: one instruction of an HLO module's text: its name, then (after the shape,
#: whose layout annotations are upper-case) its opcode
HLO_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>[\w.\-]+) = .*?\s(?P<opcode>[a-z][a-z0-9\-]*)\(")
HLO_OP_NAME = re.compile(r'op_name="(?P<op_name>[^"]*)"')
HLO_MODULE = re.compile(r"^HloModule (?P<module>[\w.\-]+)", re.M)
#: gaps shorter than this are summed under one label, unattributed
SMALL_GAP_S = 1e-4


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float        # seconds on the trace's clock
    dur: float          # seconds
    stats: dict = dataclasses.field(default_factory=dict, compare=False)

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    #: per chip, the events of its ``XLA Ops`` line, by start time; an
    #: event's name is its instruction's name, its opcode is in its stats
    devices: list[list[Event]]
    #: host line (thread) name -> its events, by start time
    host: dict[str, list[Event]]
    #: "device" for real device planes, "cpu-rehearsal" for the stand-in
    kind: str
    #: per chip, the events of its ``XLA Modules`` line (program runs)
    modules: list[list[Event]] = dataclasses.field(default_factory=list)


def op_event(name: str, start: float, dur: float, stats: dict) -> Event:
    """A device operation's event, its name cut down from the instruction's
    text to the instruction's name, the opcode kept in the stats."""
    m = HLO_INSTRUCTION.match(name)
    if m is None:
        return Event(name, start, dur, stats)
    return Event(m["name"], start, dur, {**stats, "opcode": m["opcode"]})


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices: list[list[Event]] = []
    modules: list[list[Event]] = []
    host: dict[str, list[Event]] = {}
    for plane in data.planes:
        on_device = DEVICE_PLANE.match(plane.name) is not None
        if not on_device and not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            if on_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            make = op_event if on_device else Event
            events = sorted(
                (make(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                      {k: v for k, v in e.stats})
                 for e in line.events), key=lambda e: (e.start, -e.dur))
            if not on_device:
                host.setdefault(line.name, []).extend(events)
            elif line.name == OPS_LINE:
                devices.append(events)
            else:
                modules.append(events)
    kind = "device"
    if not devices:
        kind = "cpu-rehearsal"
        stand_in = sorted(
            (Event(f"{e.stats.get('hlo_module', '')}/{e.name}", e.start,
                   e.dur, e.stats)
             for evs in host.values() for e in evs if "hlo_op" in e.stats),
            key=lambda e: (e.start, -e.dur))
        devices = [stand_in] if stand_in else []
    return Trace(devices, host, kind, modules)


def hlo_index(text: str) -> dict[str, dict]:
    """``<module>/<instruction>`` -> {"opcode", "op_name"} from a compiled
    module's text (``executable.as_text()``). A trace says that
    ``fusion.808`` ran, which says little; the ``op_name`` the instruction
    was traced from (``.../shard_map/.../scatter-add``) says what it is, and
    only the module's text has it."""
    head = HLO_MODULE.search(text)
    module = head["module"] if head else ""
    out: dict[str, dict] = {}
    for line in text.splitlines():
        m = HLO_INSTRUCTION.match(line)
        if m is None:
            continue
        op = HLO_OP_NAME.search(line)
        out[f"{module}/{m['name']}"] = {
            "opcode": m["opcode"], "op_name": op["op_name"] if op else ""}
    return out


def in_modules(ops: list[Event], runs: list[Event]) -> list[Event]:
    """``ops`` renamed ``<module>/<instruction>`` after the program run that
    holds each in time (``runs``: one chip's ``XLA Modules`` events, which
    do not overlap). An operation inside no run keeps its name."""
    starts = [r.start for r in runs]
    out = []
    for e in ops:
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.start < runs[i].end:
            m = MODULE_RUN.match(runs[i].name)
            module = m["module"] if m else runs[i].name
            e = Event(f"{module}/{e.name}", e.start, e.dur,
                      {**e.stats, "module": module})
        out.append(e)
    return out


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Union of intervals, as disjoint sorted intervals."""
    out: list[tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        elif b > a:
            out.append((a, b))
    return out


def covered(intervals: list[tuple[float, float]], t0: float, t1: float) -> float:
    """Seconds of ``[t0, t1]`` that disjoint ``intervals`` cover."""
    return sum(max(0.0, min(b, t1) - max(a, t0)) for a, b in intervals)


def self_seconds(events: list[Event]) -> dict[str, float]:
    """Self time by name over one line of properly nested events (sorted by
    start, longest first): an event's duration minus its children's."""
    out: dict[str, float] = {}
    stack: list[Event] = []
    for e in events:
        while stack and stack[-1].end <= e.start:
            stack.pop()
        if stack:
            parent = stack[-1]
            inside = min(e.end, parent.end) - e.start
            out[parent.name] = out.get(parent.name, 0.0) - max(inside, 0.0)
        out[e.name] = out.get(e.name, 0.0) + e.dur
        stack.append(e)
    return out


def _clip(events: list[Event], t0: float, t1: float) -> list[Event]:
    out = []
    for e in events:
        a, b = max(e.start, t0), min(e.end, t1)
        if b > a:
            out.append(Event(e.name, a, b - a, e.stats))
    return out


class Reduction:
    """A trace cut to its window (the ``bench:window`` span, or the extent
    of the device events when there is none)."""

    def __init__(self, trace: Trace, program_files: frozenset[str] = frozenset(),
                 hlo: dict[str, dict] | None = None):
        self.kind = trace.kind
        self.host = trace.host
        self._program_files = program_files
        #: what ``hlo_index`` knows of each instruction; a matcher sees it
        #: merged into the event's own stats
        self.hlo = hlo or {}
        spans = self.spans(WINDOW)
        if spans:
            self.t0, self.t1 = spans[0][0], spans[-1][1]
        elif any(trace.devices):
            self.t0 = min(evs[0].start for evs in trace.devices if evs)
            self.t1 = max(max(e.end for e in evs)
                          for evs in trace.devices if evs)
        else:
            self.t0 = self.t1 = 0.0
        self.window_s = self.t1 - self.t0
        runs = trace.modules or [[] for _ in trace.devices]
        self.devices = [_clip(in_modules(evs, r), self.t0, self.t1)
                        for evs, r in zip(trace.devices, runs)]
        #: device seconds by program (``jit__boost_scan_jit``), averaged
        #: over the chips
        self.module_s: dict[str, float] = {}
        for r in runs:
            for e in _clip(r, self.t0, self.t1):
                m = MODULE_RUN.match(e.name)
                key = m["module"] if m else e.name
                self.module_s[key] = (self.module_s.get(key, 0.0)
                                      + e.dur / len(runs))
        self.busy_intervals = [merge([(e.start, e.end) for e in evs])
                               for evs in self.devices]
        n = max(len(self.devices), 1)
        #: seconds an operation ran, averaged over the chips
        self.busy_s = sum(covered(iv, self.t0, self.t1)
                          for iv in self.busy_intervals) / n
        per_dev = [self_seconds(evs) for evs in self.devices]
        #: self seconds by operation name, averaged over the chips
        self.op_self_s: dict[str, float] = {}
        for d in per_dev:
            for name, s in d.items():
                self.op_self_s[name] = self.op_self_s.get(name, 0.0) + s / n
        self._stats = {e.name: {**e.stats, **self.hlo.get(e.name, {})}
                       for evs in self.devices for e in evs}

    # -- operations -----------------------------------------------------------

    def op_seconds(self, match) -> float:
        """Self seconds, averaged over the chips, of the operations for
        which ``match(name, stats)`` holds."""
        return sum(s for name, s in self.op_self_s.items()
                   if match(name, self._stats.get(name, {})))

    def top_ops(self, n: int = 10) -> list[list]:
        """The operations that took most self time, each named with what
        is known of it: ``jit__boost_scan_jit/fusion.591 [fusion:
        jit(take_along_axis)/gather]``."""
        ranked = sorted(self.op_self_s.items(), key=lambda kv: -kv[1])
        out = []
        for name, s in ranked[:n]:
            if s <= 0:
                break
            stats = self._stats[name]
            if stats.get("opcode"):
                tail = "/".join(stats.get("op_name", "").split("/")[-2:])
                name = f"{name} [{stats['opcode']}{': ' + tail if tail else ''}]"
            out.append([name, s])
        return out

    def busy_within(self, t0: float, t1: float) -> float:
        n = max(len(self.busy_intervals), 1)
        return sum(covered(iv, t0, t1) for iv in self.busy_intervals) / n

    def exposed_seconds(self, match) -> float:
        """Seconds, averaged over the chips, in which a matching operation
        ran and no other operation did."""
        def contains_others(e: Event) -> bool:
            # a ``while`` that spans its body is not compute running beside
            # the collective
            return CONTROL_FLOW.match(
                self._stats[e.name].get("opcode")
                or e.name.rpartition("/")[2]) is not None

        total = 0.0
        for evs in self.devices:
            mine = merge([(e.start, e.end) for e in evs
                          if match(e.name, self._stats[e.name])])
            others = merge([(e.start, e.end) for e in evs
                            if not match(e.name, self._stats[e.name])
                            and not contains_others(e)])
            total += sum(b - a - covered(others, a, b) for a, b in mine)
        return total / max(len(self.devices), 1)

    # -- the host side ----------------------------------------------------------

    def spans(self, name: str) -> list[tuple[float, float]]:
        """The benchmark's spans of that name (``bench:...``), on the
        trace's clock."""
        return sorted((e.start, e.end) for evs in self.host.values()
                      for e in evs if e.name == name)

    def _main_line(self) -> list[Event]:
        """The host thread that carries the benchmark's spans."""
        for evs in self.host.values():
            if any(e.name.startswith("bench:") for e in evs):
                return evs
        return []

    def _label(self, stack: list[Event]) -> str:
        bench = [e.name for e in stack if e.name.startswith("bench:")]
        inner = None
        for e in reversed(stack):
            m = PY_EVENT.match(e.name)
            if m and m["file"] in self._program_files:
                inner = f"{m['file']}:{m['func']}"
                break
        parts = ([bench[-1]] if bench else ["(outside the benchmark's spans)"])
        if inner:
            parts.append(inner)
        return " > ".join(parts)

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The idle time of the first chip inside the window, summed by what
        the host was doing at each gap's midpoint: the innermost benchmark
        span and, under the Python tracer, the innermost function of the
        program. Largest first."""
        if not self.busy_intervals:
            return []
        busy = self.busy_intervals[0]
        edges = [self.t0] + [t for iv in busy for t in iv] + [self.t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] - edges[i] > 0]
        by_label: dict[str, float] = {}
        host = sorted(self._main_line(), key=lambda e: (e.start, -e.dur))
        stack: list[Event] = []
        k = 0
        for a, b in gaps:
            if b - a < SMALL_GAP_S:
                label = f"(gaps under {SMALL_GAP_S * 1e3:g} ms)"
            else:
                mid = 0.5 * (a + b)
                while k < len(host) and host[k].start <= mid:
                    stack.append(host[k])
                    k += 1
                stack = [e for e in stack if e.end > mid]
                label = self._label(stack)
            by_label[label] = by_label.get(label, 0.0) + (b - a)
        ranked = sorted(by_label.items(), key=lambda kv: -kv[1])
        return [[label, s] for label, s in ranked[:n]]


def program_files(root: str) -> frozenset[str]:
    """Base names of the program's Python files: the Python tracer names an
    event by base name only."""
    out = set()
    for _dir, _sub, files in os.walk(os.path.join(root, "h2o3_tpu")):
        out.update(f for f in files if f.endswith(".py"))
    return frozenset(out)


def is_collective(name: str, stats: dict) -> bool:
    """By the opcode; by the instruction's name where there is none (which
    misses a ``psum`` that was traced as ``psum_invariant.N``)."""
    return COLLECTIVE.match(
        stats.get("opcode") or name.rpartition("/")[2]) is not None
