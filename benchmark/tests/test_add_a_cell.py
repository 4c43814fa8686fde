"""A later PR adds a configuration, a traffic mix, a driver and a per-layer
metric as NEW files plus entries in BENCHMARK.json, and edits no file that is
there. Proved in a throw-away copy: the new cell runs (CPU rehearsal) and
reports the new metric, and every file that was there is byte for byte what
it was."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

from benchmark import plugins

ROOT = plugins.ROOT


def digest(tree):
    out = {}
    for d, sub, files in os.walk(tree):
        sub[:] = [s for s in sub if s not in (".cache", "__pycache__")]
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, tree)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_cell_of_new_files_only(tmp_path):
    copy = tmp_path / "checkout"
    copy.mkdir()
    shutil.copytree(os.path.join(ROOT, "benchmark"), copy / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    os.symlink(os.path.join(ROOT, "h2o3_tpu"), copy / "h2o3_tpu")
    before = digest(copy / "benchmark")

    bench = copy / "benchmark"
    config = json.loads((bench / "configs" / "gbm-higgs-64.json").read_text())
    config.update(name="toy-gbm", source="https://example.org/toy")
    config["params"]["ntrees"] = 2
    (bench / "configs" / "toy-gbm.json").write_text(json.dumps(config))
    traffic = json.loads((bench / "traffic" / "build-repeat.json").read_text())
    traffic.update(driver="toy_loop", python_tracer=False)
    (bench / "traffic" / "toy-repeat.json").write_text(json.dumps(traffic))
    (bench / "drivers" / "toy_loop.py").write_text(
        "from benchmark import plugins\n\n"
        "READS_LIKE = ('build_loop',)\n\n"
        "def run(cell):\n"
        "    out = plugins.load('drivers', 'build_loop').run(cell)\n"
        "    out.facts['toy'] = 42.0\n"
        "    return out\n")
    (bench / "layer_metrics" / "toy.answer.py").write_text(
        "LAYER, UNIT, MOVES = 'toy', 'count', 'train_work_per_s_chip'\n"
        "DRIVERS = ('toy_loop',)\n\n"
        "def read(r):\n"
        "    return r.facts['toy'] + r.facts['builds']\n")

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({"name": "toy-gbm", "source": config["source"],
                                "file": "benchmark/configs/toy-gbm.json",
                                "reduced": ["ntrees"], "why": "a toy"})
    manifest["workloads"].append({"name": "toy-cell", "config": "toy-gbm",
                                  "traffic": "toy-repeat", "chips": 1,
                                  "why": "a toy"})
    manifest["per_layer"].append({
        "name": "toy.answer", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "toy",
        "moves": "train_work_per_s_chip", "workloads": ["toy-cell"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))

    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax"))
    proc = subprocess.run(
        [sys.executable, str(bench / "run.py"), "--selftest", "--workload",
         "toy-cell", "--trace", "1"], cwd=copy, env=env,
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and "not_a_result" in line
    assert line["metrics"]["toy.answer"] == {"value": 43.0, "unit": "count"}
    # the metrics that were there report in the new cell too
    assert "builder.syncs_per_tree" in line["metrics"]

    after = digest(copy / "benchmark")
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        "configs/toy-gbm.json", "traffic/toy-repeat.json",
        "drivers/toy_loop.py", "layer_metrics/toy.answer.py"}
