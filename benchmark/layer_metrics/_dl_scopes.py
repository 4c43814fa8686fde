"""The DeepLearning program's own names in a trace — shared by the ``dl.*``
readers, and the one place their match patterns live (``_scopes.py`` holds
the tree engine's and is not edited; its helpers that know nothing of trees
are used from here).

Since PR 32 ``models/deeplearning.py`` names itself, in any ``jax.profiler``
session:

- **host spans** (``timed_event`` -> ``tracing.annotation``):
  ``deeplearning:prepare`` around ``_prepare`` (``DataInfo.make``,
  ``expand``, the initial weights); ``deeplearning:epochs`` around the
  dispatches of ``_train_epochs`` AND the one fetch of the loss series that
  waits for them, so it is a synced time; ``deeplearning:fit`` and
  ``deeplearning:metrics`` as every builder;
- **scopes** inside ``_train_epochs`` (``jax.named_scope``, read from an
  instruction's ``op_name`` in the compiled module's text): ``shuffle`` (the
  permutation and the row gather), ``dropout`` (the random bits, the masks and
  their application), ``forward``, ``loss``, ``regularize``, ``optimizer``,
  ``constrain``. Autodiff wraps a name: the forward products are under
  ``jvp(forward)``, the backward pass under ``transpose(jvp(forward))``;
  ``scope_of`` strips the wrappers and says whether the outermost was a
  transpose. A fusion carries ONE ``op_name``, its root's;
- **counters** ``h2o3_dl_updates_total``, ``h2o3_dl_samples_total`` (added on
  the host, a dispatch's worth at a time), gauge ``h2o3_dl_parameters``.

A program without them (the parent of PR 32) leaves nothing to find: every
function here then returns None, never 0.
"""

import re

from benchmark.plugins import load

SCOPES = ("shuffle", "dropout", "forward", "loss", "regularize", "optimizer",
          "constrain")
_WRAPPED = re.compile(r"^(transpose|jvp|vmap|remat|checkpoint)\((.*)\)$")


def scope_of(op_name: str) -> tuple[str, bool] | None:
    """``.../while/body/closed_call/transpose(jvp(forward))/dot_general`` ->
    ``("forward", True)``: the first path component that is a scope once
    autodiff's wrappers are off, and whether it was under a transpose (the
    backward pass). None for an operation under no scope."""
    for component in op_name.split("/"):
        backward = False
        while (m := _WRAPPED.match(component)):
            backward = backward or m[1] == "transpose"
            component = m[2]
        if component in SCOPES:
            return component, backward
    return None


def seconds_by_scope(r) -> dict[str, float] | None:
    """Self seconds inside the window, averaged over the chips, of the
    configuration's program by scope (the backward pass of ``forward`` apart,
    as ``forward'``), with ``(loops)`` and ``(unscoped)`` as
    ``_scopes.seconds_by_part`` defines them (and the same artifact: a
    ``while``'s self time swings, and the sum can pass the program's own
    time). None where no operation carries a scope. Logged once a run."""
    if r.trace is None:
        return None
    cached = getattr(r.trace, "dl_seconds_by_scope", None)
    if cached is not None:
        return cached or None
    from benchmark.trace_reduce import CONTROL_FLOW
    scopes = load("layer_metrics", "_scopes")
    module = scopes.program_module(r) or ""

    def key_of(name, stats):
        if not name.startswith(module + "/"):
            return None
        found = scope_of(stats.get("op_name", ""))
        if found is not None:
            return found[0] + ("'" if found[1] and found[0] == "forward" else "")
        loop = CONTROL_FLOW.match(stats.get("opcode") or name.rpartition("/")[2])
        return "(loops)" if loop else "(unscoped)"

    out = {key: r.trace.op_seconds(lambda n, st, key=key: key_of(n, st) == key)
           for key in (*SCOPES, "forward'")}
    out = {key: s for key, s in out.items() if s > 0}
    if out:
        for key in ("(loops)", "(unscoped)"):
            out[key] = r.trace.op_seconds(
                lambda n, st, key=key: key_of(n, st) == key)
        total = sum(out.values())
        scopes.log(
            f"{module} by scope, {total:.4f} s of {r.trace.busy_s:.4f} s "
            f"busy (the module itself {r.trace.module_s.get(module, 0):.4f} "
            "s): " + ", ".join(
                f"{k} {s:.4f} s ({100 * s / total:.2f}%)"
                for k, s in sorted(out.items(), key=lambda kv: -kv[1])))
    r.trace.dl_seconds_by_scope = out
    return out or None


def scopes_share(r, names: tuple[str, ...]) -> float | None:
    """Share of the device's busy time inside the window of the operations
    under the scopes ``names``, in percent."""
    by_scope = seconds_by_scope(r)
    if by_scope is None or r.trace.busy_s <= 0:
        return None
    found = [by_scope[n] for n in names if n in by_scope]
    return 100.0 * sum(found) / r.trace.busy_s if found else None


def updates(r) -> float:
    """Minibatch updates the window's builds dispatched (the program's
    counter)."""
    from benchmark import counters
    return counters.delta(r.before, r.after, "h2o3_dl_updates_total")


def update_seconds(r) -> float | None:
    """Device seconds of the configuration's program in the window over the
    updates dispatched in it: what one update costs, shuffle and all."""
    if r.trace is None:
        return None
    scopes = load("layer_metrics", "_scopes")
    s = r.trace.module_s.get(scopes.program_module(r) or "")
    n = updates(r)
    return s / n if s and n > 0 else None


def shape(r) -> tuple[int, int, int] | None:
    """(parameters, minibatch, input width) of the window's network: the
    program's gauge, the configuration's ``mini_batch_size`` and the
    expanded width its data file states."""
    from benchmark import counters
    P = counters.value(r.after, "h2o3_dl_parameters")
    params, data = r.cell.config.get("params", {}), r.cell.config.get("data", {})
    if P <= 0 or "mini_batch_size" not in params or "expanded_columns" not in data:
        return None
    return int(P), int(params["mini_batch_size"]), int(data["expanded_columns"])
