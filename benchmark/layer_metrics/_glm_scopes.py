"""The GLM program's own names in a trace — shared by the ``glm.*`` readers,
and the one place their match patterns live (``_scopes.py`` holds the tree
engine's and is not edited; its helpers that know nothing of trees are used
from here).

Since PR 26 ``models/glm.py`` names itself, in any ``jax.profiler`` session:

- **host spans** (``timed_event`` -> ``tracing.annotation``):
  ``glm:expand`` around ``_make_data_info`` + ``DataInfo.expand`` in ``_fit``
  and around the expansion in ``GLMModel._score_raw`` (so the training
  metrics' second expansion is one too); ``glm:irls`` around the whole
  ``_irls_fit``; ``glm:megastep`` around each dispatch-and-fetch;
  ``glm:metrics`` around ``_holdout_metrics`` (every builder:
  ``<algo>:metrics``);
- **scopes** inside ``_irls_step`` (``jax.named_scope``, read from an
  instruction's ``op_name`` in the compiled module's text): ``eta`` (X·beta
  at HIGHEST precision), ``weights`` (mu, W, z), ``gram`` (X'WX, X'Wz),
  ``solve`` (Cholesky and the two triangular solves), ``deviance``. A fusion
  carries ONE ``op_name``, its root's: ``eta``'s product fused into the
  elementwise pass that consumes it reads as ``weights``, which is why the
  three row passes are one metric (``glm.rowpass_share``);
- **counters** ``h2o3_glm_iterations_total``, ``h2o3_glm_megasteps_total``
  (one blocking fetch each), gauge ``h2o3_glm_expanded_width``.

A program without them (the parent of PR 26) leaves nothing to find: every
function here then returns None, never 0.
"""

from benchmark.plugins import load

PARTS = ("eta", "weights", "gram", "solve", "deviance")
ROW_PASSES = ("eta", "weights", "deviance")


def part_of(op_name: str) -> str | None:
    """``jit(_irls_megastep)/while/body/jit(_irls_step)/gram/dot_general``
    -> ``gram``; None for an operation under no part."""
    for component in op_name.split("/"):
        if component in PARTS:
            return component
    return None


def seconds_by_part(r) -> dict[str, float] | None:
    """Self seconds inside the window, averaged over the chips, of the
    configuration's program by part, with ``(loops)`` and ``(unscoped)`` as
    ``_scopes.seconds_by_part`` defines them (and the same artifact: the
    ``while``'s self time swings, and the sum can pass the program's own
    time). None where no operation carries a part. Logged once a run."""
    if r.trace is None:
        return None
    cached = getattr(r.trace, "glm_seconds_by_part", None)
    if cached is not None:
        return cached or None
    from benchmark.trace_reduce import CONTROL_FLOW
    scopes = load("layer_metrics", "_scopes")
    module = scopes.program_module(r) or ""

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
        scopes.log(
            f"{module} by scope, {total:.4f} s of {r.trace.busy_s:.4f} s "
            f"busy (the module itself {r.trace.module_s.get(module, 0):.4f} "
            "s): " + ", ".join(
                f"{k} {s:.4f} s ({100 * s / total:.2f}%)"
                for k, s in sorted(out.items(), key=lambda kv: -kv[1])))
    r.trace.glm_seconds_by_part = out
    return out or None


def parts_share(r, parts: tuple[str, ...]) -> float | None:
    """Share of the device's busy time inside the window of the operations
    under the scopes ``parts``, in percent."""
    by_part = seconds_by_part(r)
    if by_part is None or r.trace.busy_s <= 0:
        return None
    found = [by_part[p] for p in parts if p in by_part]
    return 100.0 * sum(found) / r.trace.busy_s if found else None


def iterations(r) -> float:
    """IRLS iterations the window's builds ran (the program's counter)."""
    from benchmark import counters
    return counters.delta(r.before, r.after, "h2o3_glm_iterations_total")
