"""Driver ``score_open_loop``: ``POST /3/Score/<model>`` at a fixed rate.

Set-up: ``train_rows`` rows of the cell's data made on the device; the
configuration's model trained through the normal path at the source's own
``ntrees`` (scoring cost goes with trees x depth, not with training rows);
``H2OServer`` started in THIS process, which holds the chip; the pool of
request bodies made from the seed, with ``model.predict``'s answer for every
row of it; every batch bucket the mix can reach warmed over HTTP. Window:
``loadgen.py``, a process of its own that never imports JAX, sends on its
Poisson schedule at the traffic file's ``rate_per_s`` for ``--seconds``.

With ``--trace 1`` the window is ``trace_seconds`` of the same traffic under
the profiler. ``--set sweep=<r1>,<r2>,...`` (by hand, to find the knee) runs
the window once per rate in one process and logs each rate's summary.

End-to-end: ``score_p50_ms`` and ``score_p99_ms``, from the instant a request
was due, over the requests due in the window, failures at the time limit.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import urllib.request

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
LOADGEN = os.path.join(os.path.dirname(HERE), "loadgen.py")
#: /3/Score against predict: the scorer is one fused program, predict runs op
#: by op, so ``f0 + lr * sum`` may round once there and twice here (PR 21)
ATOL_VS_PREDICT = 2 * float(np.finfo(np.float32).eps)
#: predict (float32) against the float64 traversal: the margin is a float32
#: sum of up to 50 leaves, so it carries up to 50 x 2^-24 x |margin| (about
#: 1e-5 at |margin| 3) and typically a seventh of that; the logistic scales
#: it by at most a quarter. 1.08e-6 was the most over 10,000 rows on the v5e
#: (PR 22). A model scored in bf16 would be off by 1e-3.
ATOL_VS_TRAVERSAL = 4e-6


def log(msg: str) -> None:
    print(f"# score_open_loop: {msg}", file=sys.stderr, flush=True)


def post(url: str, rows, columns) -> dict:
    from benchmark import loadgen
    req = urllib.request.Request(
        url, data=loadgen.body_of(rows, columns), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())


def fire(cell, spec: dict, work_dir: str, tag: str) -> list[dict]:
    """One window of load: start the generator, open the window when it is
    ready, wait for it, read its records."""
    spec = dict(spec, out=os.path.join(work_dir, f"records-{tag}.json"))
    spec_file = os.path.join(work_dir, f"spec-{tag}.json")
    with open(spec_file, "w") as f:
        json.dump(spec, f)
    proc = subprocess.Popen([sys.executable, LOADGEN, spec_file],
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                            text=True)
    try:
        ready = proc.stdout.readline().strip()
        if ready != "READY":
            raise RuntimeError(f"load generator said {ready!r}, not READY")
        if cell.t_window is None:
            cell.open_window()
        with cell.spans.span("window"):
            proc.stdin.write("GO\n")
            proc.stdin.flush()
            proc.wait(timeout=spec["seconds"] + 2 * spec["time_limit_s"] + 30)
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait()
    if proc.returncode != 0:
        raise RuntimeError(f"load generator exited {proc.returncode}")
    with open(spec["out"]) as f:
        return json.load(f)


def run(cell):
    import jax

    from benchmark import counters, loadgen, plugins
    from benchmark.cell import Outcome
    from benchmark.reference.tree_traverse import bernoulli_p1
    from h2o3_tpu.api import H2OServer
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.serving import SCORING

    cfg, traffic, spans = cell.config, cell.traffic, cell.spans
    data = dict(cfg["data"], rows=cell.size(traffic, "train_rows"))
    response, features = data["response"], int(data["features"])
    columns = [f"x{j}" for j in range(features)]
    work_dir = os.path.join(cell.work_dir, "loadgen")
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)

    with spans.span("frame.make"):
        frame = plugins.load("generators", data["generator"]).make(
            cell.seed, 0, data)
        jax.block_until_ready([v.data for v in frame.vecs])
    with spans.span("model.train"):
        # the source's own value of every parameter a build cell reduced
        source = cfg.get("source_values", {})
        params = {k: source.get(k, v) for k, v in cfg["params"].items()}
        model = plugins.import_object(cfg["builder"])(**params).train(
            y=response, training_frame=frame)

    with spans.span("pool.make"):
        max_rows = cell.size(traffic, "max_rows")
        pool_rows = loadgen.make_pool(cell.seed, cell.size(traffic, "pool"),
                                      traffic["min_rows"], max_rows, features)
        X = np.asarray([r for rows in pool_rows for r in rows], np.float32)
        pred = model.predict(Frame.from_arrays(
            {c: X[:, j] for j, c in enumerate(columns)}))
        key = pred.names[-1]                 # probability of the second class
        want = pred.vecs[-1].to_numpy()[: len(X)].astype(np.float64)
        pool, at = [], 0
        for rows in pool_rows:
            pool.append({"rows": rows, "want": want[at: at + len(rows)].tolist()})
            at += len(rows)
        pool_file = os.path.join(work_dir, "pool.json")
        with open(pool_file, "w") as f:
            json.dump(pool, f)

    SCORING.reset()
    server = H2OServer(port=0).start()
    url = f"{server.url}/3/Score/{model.key}"
    try:
        with spans.span("warmup"):
            for bucket in traffic["warm_buckets"]:
                n = min(bucket, len(X))
                got = post(url, X[:n].astype(float).tolist(), columns)
                if got["rows"] != n:
                    raise RuntimeError(f"warm-up of {n} rows scored "
                                       f"{got['rows']}")
        spec = {"host": server.host, "port": server.port,
                "path": f"/3/Score/{model.key}", "columns": columns,
                "pool_file": pool_file, "seed": cell.seed,
                "connections": cell.size(traffic, "connections"),
                "time_limit_s": traffic["time_limit_s"],
                "prediction_key": key, "atol": ATOL_VS_PREDICT}
        rate = cell.size(traffic, "rate_per_s")
        sweep = [float(r) for r in str(traffic.get("sweep") or "").split(",")
                 if r]
        seconds = traffic["trace_seconds"] if cell.trace else cell.seconds
        before = counters.snapshot()
        compiles_before = cell.compiles.requests
        with cell.profiler():
            for r in sweep or [float(rate)]:
                records = fire(cell, dict(spec, rate_per_s=r, seconds=seconds),
                               work_dir, f"{r:g}")
                summary = loadgen.summarize(records, traffic["time_limit_s"])
                log(f"rate {r:g}/s: {summary}")
        after = counters.snapshot()
        no_compiles = cell.compiles.check_since(compiles_before, before, after)
        traces = _request_traces()
    finally:
        server.stop()
        SCORING.reset()

    with spans.span("check.traversal"):
        n = min(cell.size(traffic, "traversal_rows"), len(X))
        diff = float(np.max(np.abs(bernoulli_p1(model, X[:n]) - want[:n])))
    checks = {
        "every_response_equals_predict": {
            "ok": summary["wrong"] == 0 and summary["requests"] > 0,
            "wrong": summary["wrong"], "atol": ATOL_VS_PREDICT},
        "predict_equals_numpy_traversal": {
            "ok": diff <= ATOL_VS_TRAVERSAL, "rows": n, "max_abs_diff": diff,
            "atol": ATOL_VS_TRAVERSAL},
        "no_request_failed": {"ok": summary["failed"] == 0,
                              "failed": summary["failed"]},
        "no_compiles_in_window": no_compiles,
    }
    log(f"p99 over {summary['requests']} requests, {summary['beyond_p99']} "
        "beyond it")
    facts = {"chips": cell.chips, "summary": summary, "rate_per_s": r,
             "seconds": seconds, "request_traces": traces}
    end_to_end = {"score_p50_ms": summary["score_p50_ms"],
                  "score_p99_ms": summary["score_p99_ms"]}
    return Outcome(attempted=summary["requests"], failed=summary["failed"],
                   checks=checks, end_to_end=end_to_end, facts=facts,
                   before=before, after=after)


def _request_traces() -> list[dict]:
    """(request seconds, ``score:dispatch`` seconds) of the completed
    ``POST /3/Score`` traces the program's ``TRACER`` ring still holds (the
    last 128) that carry a dispatch span: the batch leaders'."""
    from h2o3_tpu.utils.tracing import TRACER
    out = []
    for head in TRACER.list_traces():
        if not head["name"].startswith("POST /3/Score"):
            continue
        try:
            spans = TRACER.get_trace(head["trace_id"])["spans"]
        except KeyError:
            continue
        dispatch = [s for s in spans if s["name"] == "score:dispatch"]
        if dispatch:
            out.append({"request_s": head["dur_ns"] * 1e-9,
                        "dispatch_s": sum(s["end_ns"] - s["start_ns"]
                                          for s in dispatch) * 1e-9})
    return out
