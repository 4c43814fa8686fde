"""graftlint env-discipline rule (ENV) — import-time capture of tunables.

``H2O3TPU_*`` environment variables are the package's runtime tunables:
batch windows, SLO targets, budgets, retry counts. A module-level read —
``WINDOW_S = float(os.environ.get("H2O3TPU_SCORE_WINDOW_MS", ...))`` —
freezes the value at IMPORT time, so anything that sets the variable
after the first import is silently ignored: ``monkeypatch.setenv`` in
tests, a launcher exporting config before calling ``serve()``, a
scenario tuning a knob between runs. That is exactly the bug ISSUE 13's
batcher satellite fixed (the fixed scoring window could never be changed
once ``serving.batcher`` was imported).

- **ENV001** — a read of an ``H2O3TPU_*`` variable (``os.environ.get``,
  ``os.getenv``, ``os.environ[...]``) in code that executes at import
  time: module level, a class body, a decorator, or a function
  DEFAULT (defaults evaluate at ``def`` time). Reads inside function
  bodies resolve per call and are fine — that is the fix shape: a
  ``*_from_env()`` helper called at construction/use time. Deliberate
  one-shot captures carry an inline ``# graftlint: ok(<reason>)``.

Pre-existing sites ship warn-only in the baseline
(``tools/baseline.json``) — new ones fail the run.
"""

from __future__ import annotations

import ast

from h2o3_tpu.tools.core import Finding, PackageIndex, dotted_name

#: env-read call forms (dotted receiver suffixes)
_GET_CALLS = {"os.environ.get", "environ.get", "os.getenv", "getenv"}
#: env-read subscript receivers
_SUBSCRIPTS = {"os.environ", "environ"}


def _env_name(node: ast.AST) -> str | None:
    """The H2O3TPU_* variable a Call/Subscript reads, or None."""
    if isinstance(node, ast.Call):
        name = dotted_name(node.func)
        if name not in _GET_CALLS or not node.args:
            return None
        key = node.args[0]
    elif isinstance(node, ast.Subscript):
        if dotted_name(node.value) not in _SUBSCRIPTS:
            return None
        key = node.slice
    else:
        return None
    if isinstance(key, ast.Constant) and isinstance(key.value, str) \
            and key.value.startswith("H2O3TPU_"):
        return key.value
    return None


def _runtime_nodes(tree: ast.Module) -> set[int]:
    """ids of nodes that execute at CALL time, not import time: function
    and lambda BODIES. Defaults and decorators stay import-time — they
    evaluate when the ``def`` executes."""
    out: set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for stmt in node.body:
                for sub in ast.walk(stmt):
                    out.add(id(sub))
        elif isinstance(node, ast.Lambda):
            for sub in ast.walk(node.body):
                out.add(id(sub))
    return out


def check(index: PackageIndex) -> list[Finding]:
    findings: list[Finding] = []
    for mod in index.modules.values():
        runtime = _runtime_nodes(mod.tree)
        for node in ast.walk(mod.tree):
            if id(node) in runtime:
                continue
            var = _env_name(node)
            if var is None:
                continue
            findings.append(Finding(
                "ENV001", mod.path, node.lineno, "",
                f"`{var}` read at import time — the value freezes before "
                "late env changes (tests' monkeypatch.setenv, launcher "
                "exports) can land; resolve it at construction/call time "
                "via a *_from_env() helper",
                detail=f"import-time-env:{var}"))
    return findings
