"""Open-loop HTTP load generator — a process of its own, standard library
only. It never imports JAX, so it can run beside the process that holds the
chip, and its threads do not share the server's GIL.

The driver (``drivers/score_open_loop.py``) imports this module for the
generator functions, writes a spec and a pool of request bodies, and starts

    python3 benchmark/loadgen.py <spec.json>

which loads the pool, prints ``READY``, waits for a line on standard input,
then sends on a Poisson schedule at the FIXED rate of the traffic file for
``seconds`` seconds, waits for what is still in flight, and writes one record
per request to the spec's ``out`` file.

Open loop: a request is sent when it is DUE whether or not earlier ones have
come back, and its latency counts from the due instant, so a stall of the
server is paid by every request that was due during it. How late each send
really left is recorded (``late_s``): a generator that cannot keep its own
schedule would otherwise pass for a slow, or a fast, server. A request that
fails, is refused (any status but 200) or times out is a failure, and its
latency counts as the time limit.
"""

from __future__ import annotations

import http.client
import json
import math
import queue
import random
import sys
import threading
import time


# -- the generators: everything from the seed ---------------------------------

def rows_of_request(rng: random.Random, min_rows: int, max_rows: int) -> int:
    """An integer, log-uniform on [min_rows, max_rows]."""
    u = rng.uniform(math.log(min_rows), math.log(max_rows + 1))
    return min(max_rows, max(min_rows, int(math.exp(u))))


def make_pool(seed: int, pool: int, min_rows: int, max_rows: int,
              features: int) -> list[list[list[float]]]:
    """``pool`` requests, each a list of rows of ``features`` standard
    normals rounded to float32's 7 digits."""
    rng = random.Random(seed)
    out = []
    for _ in range(pool):
        n = rows_of_request(rng, min_rows, max_rows)
        out.append([[round(rng.gauss(0.0, 1.0), 6) for _ in range(features)]
                    for _ in range(n)])
    return out


def body_of(rows: list[list[float]], columns: list[str]) -> bytes:
    """The request as it goes on the wire: rows as lists ordered by
    ``columns``; no ``priority``, no ``slo_ms`` (the default policy)."""
    return json.dumps({"rows": rows, "columns": columns},
                      separators=(",", ":")).encode()


def schedule(seed: int, rate_per_s: float, seconds: float,
             pool: int) -> list[tuple[float, int]]:
    """``(due offset in seconds, index into the pool)`` of every request
    due inside the window: Poisson arrivals at ``rate_per_s``."""
    rng = random.Random(seed + 1)
    out, t = [], rng.expovariate(rate_per_s)
    while t < seconds:
        out.append((t, rng.randrange(pool)))
        t += rng.expovariate(rate_per_s)
    return out


# -- the arithmetic -------------------------------------------------------------

def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q`` of
    the sample at or below it."""
    ordered = sorted(values)
    rank = max(1, math.ceil(q * len(ordered)))
    return ordered[rank - 1]


def summarize(records: list[dict], time_limit_s: float) -> dict:
    """Latency percentiles (ms) over every request that was due, a failure
    counted at the time limit; failures; the sender's lateness."""
    lat = [r["latency_s"] if r["ok"] else time_limit_s for r in records]
    late = [r["late_s"] for r in records]
    n = len(records)
    return {
        "requests": n,
        "failed": sum(1 for r in records if not r["ok"]),
        "wrong": sum(1 for r in records if r["ok"] and not r["right"]),
        "score_p50_ms": 1e3 * percentile(lat, 0.50) if n else None,
        "score_p99_ms": 1e3 * percentile(lat, 0.99) if n else None,
        "beyond_p99": n - math.ceil(0.99 * n) if n else 0,
        "late_ms_p99": 1e3 * percentile(late, 0.99) if n else None,
        "rows": sum(r["rows"] for r in records if r["ok"]),
    }


# -- the sender -------------------------------------------------------------------

def right_answer(payload: dict, want: list[float], key: str,
                 atol: float) -> bool:
    got = payload.get("predictions", {}).get(key)
    return (isinstance(got, list) and len(got) == len(want)
            and all(abs(g - w) <= atol for g, w in zip(got, want)))


def worker(host: str, port: int, path: str, spec: dict, pool: list,
           jobs: "queue.Queue", records: list, t0: float) -> None:
    conn = http.client.HTTPConnection(host, port, timeout=spec["time_limit_s"])
    headers = {"Content-Type": "application/json", "Connection": "keep-alive"}
    while True:
        job = jobs.get()
        if job is None:
            conn.close()
            return
        due, idx = job
        body, want = pool[idx]["body"], pool[idx]["want"]
        sent = time.perf_counter() - t0
        rec = {"due_s": due, "late_s": sent - due, "rows": len(want),
               "ok": False, "right": False, "status": 0}
        try:
            conn.request("POST", path, body=body, headers=headers)
            resp = conn.getresponse()
            data = resp.read()
            rec["status"] = resp.status
            rec["ok"] = resp.status == 200
            if rec["ok"]:
                rec["right"] = right_answer(json.loads(data), want,
                                            spec["prediction_key"],
                                            spec["atol"])
        except (OSError, http.client.HTTPException, ValueError) as e:
            rec["error"] = f"{type(e).__name__}: {e}"
            conn.close()
        rec["latency_s"] = time.perf_counter() - t0 - due
        records.append(rec)


def main(spec_path: str) -> int:
    with open(spec_path) as f:
        spec = json.load(f)
    with open(spec["pool_file"]) as f:
        pool = [{"body": body_of(p["rows"], spec["columns"]), "want": p["want"]}
                for p in json.load(f)]
    plan = schedule(spec["seed"], spec["rate_per_s"], spec["seconds"],
                    len(pool))
    jobs: queue.Queue = queue.Queue()
    records: list[dict] = []
    print("READY", flush=True)
    sys.stdin.readline()                       # the driver opens the window
    t0 = time.perf_counter()
    threads = [threading.Thread(
        target=worker, daemon=True,
        args=(spec["host"], spec["port"], spec["path"], spec, pool, jobs,
              records, t0)) for _ in range(spec["connections"])]
    for th in threads:
        th.start()
    for due, idx in plan:
        wait = due - (time.perf_counter() - t0)
        if wait > 0:
            time.sleep(wait)
        jobs.put((due, idx))
    for _ in threads:
        jobs.put(None)
    deadline = time.perf_counter() + spec["time_limit_s"] + 5.0
    for th in threads:
        th.join(max(0.0, deadline - time.perf_counter()))
    done = list(records)
    seen = {r["due_s"] for r in done}
    for due, idx in plan:                      # never came back: a failure
        if due not in seen:
            done.append({"due_s": due, "late_s": 0.0, "ok": False,
                         "right": False, "status": 0, "rows": 0,
                         "latency_s": spec["time_limit_s"],
                         "error": "no reply before the generator gave up"})
    with open(spec["out"], "w") as f:
        json.dump(sorted(done, key=lambda r: r["due_s"]), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
