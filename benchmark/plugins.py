"""Find the harness's parts by the names the data files use.

A configuration, a traffic mix, a driver, a generator, a reference and a
per-layer metric each sit in a file of their own under ``benchmark/<kind>/``;
the name in ``BENCHMARK.json`` or in a data file is the file's stem. A later
PR adds a part as a new file and edits none. Metric names hold dots
(``kernel.hist_share``), so modules are loaded by path, not by import name.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def path(kind: str, name: str, ext: str) -> str:
    return os.path.join(HERE, kind, name + ext)


def load(kind: str, name: str):
    """The module ``benchmark/<kind>/<name>.py``."""
    file = path(kind, name, ".py")
    if not os.path.isfile(file):
        raise SystemExit(f"benchmark: no {kind[:-1]} named {name!r} "
                         f"({os.path.relpath(file, ROOT)} is missing)")
    mod_name = f"benchmark.{kind}.{name.replace('.', '_')}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, file)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def import_object(path: str):
    """``package.module:name`` of the program, as a configuration names its
    builder or its compiled program."""
    import importlib
    module, _, name = path.partition(":")
    return getattr(importlib.import_module(module), name)


def load_json(kind: str, name: str) -> dict:
    file = path(kind, name, ".json")
    if not os.path.isfile(file):
        raise SystemExit(f"benchmark: {os.path.relpath(file, ROOT)} is missing")
    with open(file) as f:
        return json.load(f)


def names(kind: str, ext: str = ".py") -> list[str]:
    """Every part of one kind, by listing its directory."""
    return sorted(f[: -len(ext)] for f in os.listdir(os.path.join(HERE, kind))
                  if f.endswith(ext) and not f.startswith("_"))
