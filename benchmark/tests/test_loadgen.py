"""The open-loop load generator against a fake server: due times, lateness,
the percentile and its sample count."""

import http.server
import json
import os
import subprocess
import sys
import threading
import time

from benchmark import loadgen

LOADGEN = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "loadgen.py")


def test_schedule_is_seeded_poisson_inside_the_window():
    plan = loadgen.schedule(7, 200.0, 10.0, 32)
    assert plan == loadgen.schedule(7, 200.0, 10.0, 32)
    assert plan != loadgen.schedule(8, 200.0, 10.0, 32)
    dues = [d for d, _ in plan]
    assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < 10.0
    assert 1800 < len(plan) < 2200                  # 2000 +- 4.5 sigma
    assert {i for _, i in plan} == set(range(32))


def test_request_sizes_are_log_uniform():
    import random
    rng = random.Random(1)
    sizes = [loadgen.rows_of_request(rng, 1, 1024) for _ in range(20000)]
    assert min(sizes) == 1 and max(sizes) == 1024
    small = sum(1 for s in sizes if s <= 32) / len(sizes)
    assert 0.45 < small < 0.56                       # half the decades
    assert 130 < sum(sizes) / len(sizes) < 165       # mean about 148


def test_percentile_is_nearest_rank_and_counts_what_lies_beyond():
    values = [float(v) for v in range(1, 1001)]
    assert loadgen.percentile(values, 0.50) == 500.0
    assert loadgen.percentile(values, 0.99) == 990.0
    records = [{"ok": True, "right": True, "latency_s": v / 1e3,
                "late_s": 0.0, "rows": 1} for v in values]
    s = loadgen.summarize(records, 30.0)
    assert s["requests"] == 1000 and s["beyond_p99"] == 10
    assert s["score_p99_ms"] == 990.0


def test_a_failure_counts_at_the_time_limit():
    ok = {"ok": True, "right": True, "latency_s": 0.01, "late_s": 0.0,
          "rows": 1}
    bad = {"ok": False, "right": False, "latency_s": 0.001, "late_s": 0.0,
           "rows": 0}
    s = loadgen.summarize([ok] * 90 + [bad] * 10, 30.0)
    assert s["failed"] == 10 and s["score_p99_ms"] == 30000.0
    assert s["score_p50_ms"] == 10.0


class _Server:
    """Answers with each row's sum; holds ONE lock for ``stall_s`` while it
    serves request number ``stall_at``, so every request that arrives
    meanwhile waits behind it."""

    def __init__(self, stall_at: int, stall_s: float):
        lock, count = threading.Lock(), [0]

        class Handler(http.server.BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.0"            # as the program's server

            def log_message(self, *a):
                pass

            def do_POST(self):
                body = json.loads(self.rfile.read(
                    int(self.headers["Content-Length"])))
                with lock:
                    count[0] += 1
                    if count[0] == stall_at:
                        time.sleep(stall_s)
                if self.path.endswith("/refuse"):
                    self.send_response(503)
                    self.end_headers()
                    return
                out = json.dumps({"predictions": {
                    "p": [sum(r) for r in body["rows"]]}}).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(out)))
                self.end_headers()
                self.wfile.write(out)

        self.httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=self.httpd.serve_forever, daemon=True).start()

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def _fire(tmp_path, server, path="/score", **over):
    rows = loadgen.make_pool(3, 8, 1, 16, 4)
    pool = [{"rows": r, "want": [sum(x) for x in r]} for r in rows]
    (tmp_path / "pool.json").write_text(json.dumps(pool))
    spec = {"host": "127.0.0.1", "port": server.httpd.server_address[1],
            "path": path, "columns": ["a", "b", "c", "d"],
            "pool_file": str(tmp_path / "pool.json"), "seed": 5,
            "connections": 2, "time_limit_s": 5.0, "prediction_key": "p",
            "atol": 1e-9, "rate_per_s": 50.0, "seconds": 2.0,
            "out": str(tmp_path / "records.json"), **over}
    (tmp_path / "spec.json").write_text(json.dumps(spec))
    proc = subprocess.Popen([sys.executable, LOADGEN,
                             str(tmp_path / "spec.json")],
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                            text=True)
    assert proc.stdout.readline().strip() == "READY"
    proc.stdin.write("GO\n")
    proc.stdin.flush()
    assert proc.wait(timeout=60) == 0
    return spec, json.loads((tmp_path / "records.json").read_text())


def test_open_loop_keeps_its_schedule_and_checks_every_answer(tmp_path):
    server = _Server(stall_at=-1, stall_s=0.0)
    try:
        spec, records = _fire(tmp_path, server)
    finally:
        server.stop()
    plan = loadgen.schedule(spec["seed"], 50.0, 2.0, 8)
    assert [r["due_s"] for r in records] == [d for d, _ in plan]
    s = loadgen.summarize(records, spec["time_limit_s"])
    assert s["failed"] == 0 and s["wrong"] == 0
    assert s["late_ms_p99"] < 20.0 and s["score_p99_ms"] < 200.0


def test_a_stall_is_paid_by_every_request_due_during_it(tmp_path):
    """Half a second behind one lock, two connections: a generator that
    timed from the send, or waited for replies, would see two slow
    requests. From the due instant, everything due in that half second is
    slow, and the late sends are reported as late."""
    server = _Server(stall_at=20, stall_s=0.5)
    try:
        spec, records = _fire(tmp_path, server)
    finally:
        server.stop()
    assert all(r["ok"] and r["right"] for r in records)
    slow = [r for r in records if r["latency_s"] > 0.1]
    assert len(slow) >= 10
    assert max(r["latency_s"] for r in records) >= 0.45
    assert max(r["late_s"] for r in records) >= 0.2


def test_a_refused_request_is_a_failure(tmp_path):
    server = _Server(stall_at=-1, stall_s=0.0)
    try:
        spec, records = _fire(tmp_path, server, path="/refuse", seconds=0.5)
    finally:
        server.stop()
    s = loadgen.summarize(records, spec["time_limit_s"])
    assert s["requests"] > 0 and s["failed"] == s["requests"]
    assert s["score_p50_ms"] == 5000.0


def test_the_generator_never_imports_jax():
    src = open(LOADGEN).read()
    assert "import jax" not in src and "h2o3_tpu" not in src.split('"""')[2]
