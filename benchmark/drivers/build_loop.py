"""Driver ``build_loop``: model builds back to back on one resident frame.

Set-up: the training frame, made on the device from the seed; then
``warmup_builds`` whole builds through the public path, which load or compile
every program the window will run. Window: ``builder.train()`` again and
again — the same parameters and ``seed=`` every time — until ``--seconds``
have passed. A build starts only while the previous build's wall still fits
into what is left, and one always runs, so a run overruns its window by less
than one build. Each model leaves the DKV when the next one exists.

With ``--trace 1`` the window is ONE build under the profiler.

End-to-end: ``train_work_per_s_chip`` = sum of work over the window's builds /
sum of their ``train()`` walls / chips. A build's work is the configuration's
``work_unit``: rows times ``work_factor`` (a parameter of the builder, or a
key of the finished model's ``output`` such as the trees it really built).
"""

from __future__ import annotations

import hashlib
import sys
import time
import types

import numpy as np


def log(msg: str) -> None:
    print(f"# build_loop: {msg}", file=sys.stderr, flush=True)


def work_factor(spec: dict, builder, model) -> float:
    if "param" in spec:
        return float(builder.params[spec["param"]])
    return float(model.output[spec["output"]])


def fingerprint(model) -> str | None:
    """A digest of every array of the model's trees, bit for bit; None for
    a model without trees."""
    trees = model.output.get("trees")
    if not trees:
        return None
    h = hashlib.sha256()
    for t in trees:
        for field in ("feat", "thresh_bin", "thresh_val", "na_left",
                      "is_split", "leaf", "gain", "cover"):
            h.update(np.ascontiguousarray(getattr(t, field)).tobytes())
    return h.hexdigest()


def run(cell):
    import jax

    from benchmark import counters, plugins
    from benchmark.cell import Outcome
    from h2o3_tpu.utils.registry import DKV

    cfg, traffic, spans = cell.config, cell.traffic, cell.spans
    Builder = plugins.import_object(cfg["builder"])
    data = dict(cfg["data"], rows=cell.size(cfg["data"], "rows"))
    response = data["response"]

    with spans.span("frame.make"):
        frame = plugins.load("generators", data["generator"]).make(
            cell.seed, 0, data)
        jax.block_until_ready([v.data for v in frame.vecs])

    def build(span: str):
        builder = Builder(**cfg["params"])
        with spans.span(span):
            return builder, builder.train(y=response, training_frame=frame)

    prints: list[str | None] = []
    previous = None
    for _ in range(int(traffic["warmup_builds"])):
        builder, model = build("warmup")
        prints.append(fingerprint(model))
        if previous is not None:
            DKV.remove(previous.key)
        previous = model
    log(f"warm-up walls {[round(w, 3) for w in spans.walls('warmup')]} s")

    before = counters.snapshot()
    compiles_before = cell.compiles.requests
    attempted = failed = 0
    work = 0.0
    trees = 0
    with cell.profiler():
        t0 = cell.open_window()
        with spans.span("window"):
            while True:
                attempted += 1
                t = time.perf_counter()
                try:
                    builder, model = build("train")
                except Exception as e:   # noqa: BLE001 — counted, then fatal
                    failed += 1
                    log(f"build {attempted} failed: {type(e).__name__}: {e}")
                    break
                wall = time.perf_counter() - t
                prints.append(fingerprint(model))
                work += data["rows"] * work_factor(cfg["work_factor"],
                                                   builder, model)
                trees += int(model.output.get("ntrees", 0))
                if previous is not None:
                    DKV.remove(previous.key)
                previous = model
                if cell.trace or time.perf_counter() - t0 + wall > cell.seconds:
                    break
    after = counters.snapshot()
    no_compiles = cell.compiles.check_since(compiles_before, before, after)
    walls = spans.walls("train", since=t0)
    log(f"{len(walls)} build(s) in the window, walls "
        f"{[round(w, 3) for w in walls]} s")

    params = builder.params
    nbins = params.get("nbins")
    facts = {
        "algo": Builder.algo, "chips": cell.chips, "rows": data["rows"],
        "rows_per_chip": frame.plen // cell.chips,
        "features": data["features"], "builds": len(walls), "trees": trees,
        "train_walls": walls, "work": work, "nbins": nbins,
        "depth": params.get("max_depth"),
    }
    if cell.trace and cfg.get("program"):
        # the module that ran, so that a trace's ``fusion.808`` can be named
        newest = plugins.import_object(cfg["program"]).executables()[-1]
        facts["hlo_text"] = newest.as_text()

    ctx = types.SimpleNamespace(
        cell=cell, config=cfg, traffic=traffic, data=data, builder=Builder,
        params=params, model=previous, fingerprints=prints)
    checks = {}
    if failed == 0:
        for name in cfg["checks"]:
            with spans.span(f"check.{name}"):
                checks[name] = plugins.load("checks", name).check(ctx)
            log(f"check {name} ({spans.total(f'check.{name}'):.1f} s): "
                f"{checks[name]}")
    checks["no_compiles_in_window"] = no_compiles
    checks["every_build_finished"] = {"ok": failed == 0 and len(walls) > 0}

    end_to_end = {}
    if walls:
        end_to_end["train_work_per_s_chip"] = work / sum(walls) / cell.chips
    return Outcome(attempted=attempted, failed=failed, checks=checks,
                   end_to_end=end_to_end, facts=facts, before=before,
                   after=after)
