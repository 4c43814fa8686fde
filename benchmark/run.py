"""Run ONE cell of BENCHMARK.json once and print one line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

needs the chips the cell asks for: off a TPU, or with another number of
chips, it exits non-zero and prints nothing that looks like a result. It
generates every input on the device from ``--seed``, sets up (counted as
``setup_s``), measures for ``--seconds``, checks the outputs, and prints as
its LAST line of standard output one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device``, and with ``--trace 1``
``breakdown``. benchmark/README.md says where each number comes from.

    python3 benchmark/run.py --selftest [--workload <name>]

is the CPU rehearsal: the same code at toy sizes with the kernel in
interpret mode. Its lines carry ``"not_a_result"``; nothing it prints is a
measurement.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()   # setup_s counts from here

import argparse      # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import subprocess    # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg: str) -> None:
    print(f"# benchmark: {msg}", file=sys.stderr, flush=True)


def load_manifest() -> dict:
    """BENCHMARK.json, with the cells that are written but not admitted
    (benchmark/candidates.json, same schema) appended: they run by hand,
    the driver never sees them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    extra = os.path.join(HERE, "candidates.json")
    if os.path.isfile(extra):
        with open(extra) as f:
            cand = json.load(f)
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            have = {e["name"] for e in manifest[key]}
            manifest[key] = manifest[key] + [
                e for e in cand.get(key, []) if e["name"] not in have]
    return manifest


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def device_block(devices) -> dict:
    stats = [d.memory_stats() or {} for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                     for s in stats)}


def per_layer(cell, out, manifest: dict, device: dict):
    """(metrics, breakdown, device additions) of a traced run: every reader
    under layer_metrics/ that applies to this driver and finds something."""
    from benchmark import plugins, roofline, trace_reduce
    from benchmark.cell import Reading
    reduction = None
    if cell.trace:
        trace = trace_reduce.load(trace_reduce.find_xplane(cell.trace_dir))
        reduction = trace_reduce.Reduction(
            trace, trace_reduce.program_files(ROOT),
            trace_reduce.hlo_index(out.facts.get("hlo_text", "")))
        log(f"trace: {reduction.kind}, {len(reduction.devices)} device "
            f"line(s), window {reduction.window_s:.3f} s, busy "
            f"{reduction.busy_s:.3f} s")
    peak = None if cell.selftest else roofline.peak_row(device["kind"])
    reading = Reading(cell=cell, facts=out.facts, spans=cell.spans,
                      before=out.before, after=out.after, trace=reduction,
                      peak=peak, memory_peak_bytes=device["memory_peak_bytes"])
    reported = {m["name"] for m in manifest["end_to_end"]
                if applies(m, cell.name)}
    listed = {m["name"]: m for m in manifest["per_layer"]
              if applies(m, cell.name) and m["moves"] in reported}
    metrics = {}
    driver = cell.traffic["driver"]
    # a new driver that hands back the facts of one that is there says so
    # (READS_LIKE), and the readers written for that one apply to it too
    drivers = {driver, *getattr(plugins.load("drivers", driver),
                                "READS_LIKE", ())}
    for name in plugins.names("layer_metrics"):
        mod = plugins.load("layer_metrics", name)
        if not drivers & set(mod.DRIVERS):
            continue
        value = mod.read(reading)
        if value is None:
            continue
        if name not in listed:
            log(f"{name} = {value} {mod.UNIT} (not listed for this cell in "
                "BENCHMARK.json: left out of the line)")
            continue
        metrics[name] = {"value": value, "unit": listed[name]["unit"]}
    extra, breakdown = {}, None
    if reduction is not None:
        extra = {"busy_s": reduction.busy_s, "window_s": reduction.window_s}
        breakdown = {"device_ops": reduction.top_ops(10),
                     "idle_gaps": reduction.idle_gaps(10)}
    return metrics, breakdown, extra


def run_cell(args) -> int:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip():
        # a fixed path inside the checkout: the path is part of the key
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
            HERE, ".cache", "jax")
    # the sub-second programs (binning, a scorer bucket) are cached too
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    manifest = load_manifest()
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        sys.exit(f"benchmark: no workload {args.workload!r}; have "
                 f"{sorted(cells)}")
    entry = cells[args.workload]
    chips = int(entry["chips"])
    if args.selftest:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}")

    sys.path.insert(0, ROOT)
    from benchmark import plugins
    from benchmark.cell import Cell
    from benchmark.counters import CompileWatch
    from benchmark.spans import Spans
    config = plugins.load_json("configs", entry["config"])
    traffic = plugins.load_json("traffic", entry["traffic"])
    for pair in args.set or []:
        # by hand only: a parameter of the traffic mix, overridden
        k, _, v = pair.partition("=")
        try:
            traffic[k] = json.loads(v)
        except ValueError:
            traffic[k] = v
    driver = plugins.load("drivers", traffic["driver"])

    import jax
    devices = jax.devices()
    want = "cpu" if args.selftest else "tpu"
    if devices[0].platform != want or len(devices) != chips:
        sys.exit(f"benchmark: {args.workload} needs {chips} {want} device(s);"
                 f" JAX found {len(devices)} x {devices[0].platform} "
                 f"({devices[0].device_kind})")
    try:
        from h2o3_tpu.utils import compile_cache
    except ImportError:
        sys.exit("benchmark: the program (h2o3_tpu/) is not in this checkout")
    compile_cache.enable(default_on=True)
    if args.selftest:
        from h2o3_tpu.ops import pallas_hist
        pallas_hist._INTERPRET = True

    cell = Cell(name=args.workload, config=config, traffic=traffic,
                chips=chips, seed=args.seed, seconds=float(args.seconds),
                trace=bool(args.trace), selftest=args.selftest,
                work_dir=os.path.join(HERE, ".cache", "work", args.workload),
                spans=Spans(), compiles=CompileWatch().install())
    out = driver.run(cell)

    device = device_block(devices)
    line = {"correct": all(c["ok"] for c in out.checks.values()),
            "attempted": out.attempted, "failed": out.failed}
    if cell.trace:
        metrics, breakdown, extra = per_layer(cell, out, manifest, device)
        device.update(extra)
        line.update(metrics=metrics, device=device)
        if breakdown is not None:
            line["breakdown"] = breakdown
    else:
        units = {m["name"]: m["unit"] for m in manifest["end_to_end"]
                 if applies(m, cell.name)}
        values = dict(out.end_to_end,
                      setup_s=cell.t_window - T_PROCESS_START)
        line.update(metrics={k: {"value": v, "unit": units[k]}
                             for k, v in values.items() if k in units},
                    device=device)
    line.update(workload=cell.name, seed=cell.seed, checks=out.checks)
    if args.set:
        line["overridden_by_hand"] = args.set
    if args.selftest:
        line["not_a_result"] = ("CPU rehearsal at toy sizes, kernel in "
                                "interpret mode: no number here is a "
                                "measurement")
    print(json.dumps(line), flush=True)
    return 0


def selftest_all(args) -> int:
    """Every cell, both modes, each in a process of its own (this one never
    touches JAX, so the children are free to)."""
    manifest = load_manifest()
    bad = 0
    for w in manifest["workloads"]:
        for trace in (0, 1):
            cmd = [sys.executable, os.path.abspath(__file__), "--selftest",
                   "--workload", w["name"], "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", str(trace)]
            proc = subprocess.run(cmd, capture_output=True, text=True)
            last = (proc.stdout.strip().splitlines() or [""])[-1]
            ok = proc.returncode == 0 and json.loads(last or "{}").get(
                "correct") is True
            print(f"selftest {w['name']} --trace {trace}: "
                  f"{'ok' if ok else 'FAILED'}", flush=True)
            if not ok:
                bad += 1
                print(proc.stderr[-3000:], file=sys.stderr)
                print(last, file=sys.stderr)
    print("selftest: a CPU rehearsal, not a result", flush=True)
    return 1 if bad else 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--selftest", action="store_true")
    ap.add_argument("--set", action="append", metavar="KEY=VALUE",
                    help="by hand: override one parameter of the traffic mix "
                         "(the line then says so)")
    args = ap.parse_args()
    if args.selftest and args.seconds is None:
        args.seconds = 2.0
    if args.selftest and not args.workload:
        return selftest_all(args)
    if not args.workload or args.seconds is None:
        ap.error("--workload and --seconds are required")
    return run_cell(args)


if __name__ == "__main__":
    sys.exit(main())
