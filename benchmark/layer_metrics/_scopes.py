"""The program's own names in a trace — shared by the ``builder.*_s`` and
``program.*_share`` readers that read them, and the one place their match
patterns live.

Since PR 24 the program names itself, in any ``jax.profiler`` session:

- **host spans**: every ``timed_event`` and ``TRACER`` span is a
  ``jax.profiler.TraceAnnotation`` of its own name on the host thread that
  ran it: ``<algo>:fit``, ``<algo>:prepare.edges``, ``<algo>:prepare.bin``,
  ``<algo>:chunk`` (``<algo>`` is the builder's ``algo``: ``gbm``,
  ``xgboost``), on the device planes' clock;
- **scopes**: the boost program's parts sit under ``jax.named_scope``, so an
  instruction's ``op_name`` (from the compiled module's text, which
  ``trace_reduce.hlo_index`` merges into an event's stats) holds them as
  path components: ``jit(_boost_scan_jit)/while/body/closed_call/level3/
  route/jit(take_along_axis)/gather``. A round is ``sample``, ``grad``,
  ``level0`` ... ``level<depth-1>`` (each ``hist``, ``split``, ``route``),
  ``leaves``, ``update``. A fusion carries ONE ``op_name``, its root's.

A program without them (the parent of PR 24) leaves nothing to find: every
function here then returns None, never 0.
"""

import sys

#: the parts of a boosting round; an operation's part is the first path
#: component of its ``op_name`` that is one of these
PARTS = ("sample", "grad", "hist", "split", "route", "leaves", "update")


def part_of(op_name: str) -> str | None:
    """``.../level3/route/jit(take_along_axis)/gather`` -> ``route``; None
    for an operation under no part."""
    for component in op_name.split("/"):
        if component in PARTS:
            return component
    return None


def log(msg: str) -> None:
    print(f"# benchmark: {msg}", file=sys.stderr, flush=True)


def program_module(r) -> str | None:
    """``jit__boost_scan_jit``: the compiled program the configuration
    names, as the trace's ``XLA Modules`` line calls it."""
    program = r.cell.config.get("program")
    return "jit_" + program.rpartition(":")[2] if program else None


def seconds_by_part(r) -> dict[str, float] | None:
    """Self seconds inside the window, averaged over the chips, of the
    configuration's program by part; None where no operation carries one.
    What carries no part is split in two: ``(loops)``, the self time of
    the control flow that only holds others (the scan's own ``while``), and
    ``(unscoped)``, the rest. ``self_seconds`` takes an operation whose
    event starts a hair before its predecessor's ends for that one's child,
    and its whole duration then stays in the enclosing ``while``: so
    ``(loops)`` swings from run to run (0.004 to 0.8 s on the v5e, PR 24)
    and the sum can pass the program's own time. Logged once a run."""
    if r.trace is None:
        return None
    cached = getattr(r.trace, "seconds_by_part", None)
    if cached is not None:
        return cached or None
    from benchmark.trace_reduce import CONTROL_FLOW
    module = program_module(r) or ""

    def under(part, loops=False):
        return lambda name, stats: (
            name.startswith(module + "/")
            and part_of(stats.get("op_name", "")) == part
            and (part is not None or loops == bool(CONTROL_FLOW.match(
                stats.get("opcode") or name.rpartition("/")[2]))))

    out = {part: r.trace.op_seconds(under(part)) for part in PARTS}
    out = {part: s for part, s in out.items() if s > 0}
    if out:
        out["(loops)"] = r.trace.op_seconds(under(None, loops=True))
        out["(unscoped)"] = r.trace.op_seconds(under(None))
        total = sum(out.values())
        log(f"{module} by scope, {total:.3f} s of {r.trace.busy_s:.3f} s "
            "busy: " + ", ".join(
                f"{k} {s:.3f} s ({100 * s / total:.2f}%)"
                for k, s in sorted(out.items(), key=lambda kv: -kv[1])))
    r.trace.seconds_by_part = out
    return out or None


def part_share(r, part: str) -> float | None:
    """Share of the device's busy time inside the window of the operations
    under scope ``part``, in percent."""
    by_part = seconds_by_part(r)
    if by_part is None or part not in by_part or r.trace.busy_s <= 0:
        return None
    return 100.0 * by_part[part] / r.trace.busy_s


def program_spans(r, what: str) -> list[tuple[float, float]]:
    """The program's host spans ``<algo>:<what>`` that start inside the
    window, cut to it, by start time."""
    if r.trace is None:
        return []
    t0, t1 = r.trace.t0, r.trace.t1
    return [(a, min(b, t1))
            for a, b in r.trace.spans(f"{r.facts['algo']}:{what}")
            if t0 <= a < t1]


def idle_within(r, spans: list[tuple[float, float]]) -> float:
    """Seconds of ``spans`` in which the chips ran nothing (averaged over
    the chips)."""
    return sum(b - a - r.trace.busy_within(a, b) for a, b in spans)
