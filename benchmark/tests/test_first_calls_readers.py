"""The six readers of what set-up's first calls paid (``entry.executables``,
``entry.trace_s``, ``entry.lower_s``, ``entry.backend_s``, ``frame.rollup_s``,
``entry.first_build_dark_s``) on hand-built snapshots of the program's
counters at the window's first instant."""

import types

import pytest

from benchmark import plugins
from benchmark.cell import Reading
from benchmark.spans import Spans

SECONDS = "h2o3_first_call_seconds_total"
EXECUTABLES = "h2o3_executables_total"
ROLLUP_SECONDS = "h2o3_rollup_seconds_total"
ENTRY = ("entry.executables", "entry.trace_s", "entry.lower_s",
         "entry.backend_s", "entry.first_build_dark_s")


def snapshot():
    """A set-up: the frame's own operations outside a build (0.3 s of
    roll-ups among them), a build's phases, and roll-ups of 0.9 s the build
    asked for, whose own first call took 0.6 s of it."""
    def seconds(phase, trace, lower, backend):
        return [(SECONDS, {"phase": phase, "stage": s}, v) for s, v in
                (("trace", trace), ("lower", lower), ("backend", backend))]
    return {"metrics": [
        *seconds("(outside a build)", 0.5, 0.25, 2.0),
        *seconds("gbm:chunk", 2.0, 1.0, 0.5),
        *seconds("gbm:prepare.bin", 0.125, 0.125, 0.25),
        *seconds("frame:rollups", 0.25, 0.125, 0.25),
        (EXECUTABLES, {"phase": "(outside a build)", "source": "cache"}, 30.0),
        (EXECUTABLES, {"phase": "gbm:chunk", "source": "cache"}, 8.0),
        (EXECUTABLES, {"phase": "gbm:chunk", "source": "compiler"}, 2.0),
        (EXECUTABLES, {"phase": "frame:rollups", "source": "cache"}, 1.0),
        (ROLLUP_SECONDS, {"phase": "(outside a build)"}, 0.3),
        (ROLLUP_SECONDS, {"phase": "gbm:train"}, 0.9),
        ("h2o3_rollups_total", {"kind": "numeric"}, 28.0),
        ("h2o3_rollups_total", {"kind": "cat"}, 2.0),
    ]}


def reading(before, warmups=(8.0,), train_walls=(2.5, 2.4, 2.6)):
    spans = Spans()
    t = 0.0
    for wall in warmups:
        spans.records.append(("warmup", t, t + wall))
        t += wall
    return Reading(cell=types.SimpleNamespace(config={}),
                   facts={"algo": "gbm", "builds": len(train_walls),
                          "train_walls": list(train_walls)},
                   spans=spans, before=before, after={}, trace=None,
                   peak=None, memory_peak_bytes=0)


def read(name, r):
    return plugins.load("layer_metrics", name).read(r)


@pytest.mark.parametrize("name", ENTRY + ("frame.rollup_s",))
def test_the_readers_name_their_layer_and_what_they_move(name):
    metric = plugins.load("layer_metrics", name)
    layer = "frame" if name == "frame.rollup_s" else "entry"
    unit = "count" if name == "entry.executables" else "s"
    assert (metric.LAYER, metric.UNIT, metric.MOVES) == (layer, unit, "setup_s")
    drivers = (("build_loop",) if name == "entry.first_build_dark_s"
               else ("build_loop", "score_open_loop"))
    assert metric.DRIVERS == drivers


def test_each_reader_takes_the_counters_absolute_value_at_the_end_of_set_up():
    r = reading(snapshot())
    # whatever the window added is in ``after`` and is not read
    r.after = {"metrics": [(SECONDS, {"phase": "x", "stage": "trace"}, 99.0),
                           (EXECUTABLES, {"phase": "x", "source": "cache"}, 99.0),
                           (ROLLUP_SECONDS, {"phase": "x"}, 99.0)]}
    assert read("entry.executables", r) == 41.0
    assert read("entry.trace_s", r) == pytest.approx(2.875)
    assert read("entry.lower_s", r) == pytest.approx(1.5)
    assert read("entry.backend_s", r) == pytest.approx(3.0)
    assert read("frame.rollup_s", r) == pytest.approx(1.2)


def test_the_roll_ups_reader_logs_how_many_ran_and_what_one_cost(capsys):
    assert read("frame.rollup_s", reading(snapshot())) == pytest.approx(1.2)
    assert ("# benchmark: roll-ups in set-up: 30 (cat 2, numeric 28) in "
            "1.200 s, 0.900 s of them asked for inside a build; 40.00 ms a "
            "round trip") in capsys.readouterr().err


def test_the_dark_part_leaves_two_phases_out_of_its_sum(capsys):
    r = reading(snapshot())
    # 8.0 warm-up - 2.5 steady (the median) - (3.5 + 0.5) under the build's
    # own phases - 0.9 of the roll-ups it asked for, whose 0.625 s of first
    # calls are NOT taken off again, nor the 2.75 s of first calls and the
    # 0.3 s of roll-ups outside a build
    assert read("entry.first_build_dark_s", r) == pytest.approx(0.6)
    assert ("# benchmark: first build 8.000 s = a steady build 2.500 s + under "
            "its own phases trace 2.125 s + lower 1.125 s + backend 0.750 s + "
            "its roll-ups 0.900 s + dark 0.600 s") in capsys.readouterr().err


def test_the_dark_part_is_not_floored():
    r = reading(snapshot(), warmups=(6.0,))
    assert read("entry.first_build_dark_s", r) == pytest.approx(-1.4)


def test_the_dark_part_is_left_out_without_one_warm_up_build_or_a_window():
    assert read("entry.first_build_dark_s",
                reading(snapshot(), warmups=())) is None
    # two warm-up builds: the counters do not say whose a first call was
    assert read("entry.first_build_dark_s",
                reading(snapshot(), warmups=(8.0, 2.5))) is None
    assert read("entry.first_build_dark_s",
                reading(snapshot(), train_walls=())) is None


@pytest.mark.parametrize("name", ENTRY + ("frame.rollup_s",))
def test_a_program_without_the_counters_leaves_every_reader_out(name):
    # PR 35's parent: other counters, none of these
    parent = {"metrics": [("h2o3_metric_hist_total", {"path": "matmul"}, 1.0),
                          ("h2o3_compile_seconds_total", {"site": "s"}, 4.0)]}
    assert read(name, reading(parent)) is None


def test_a_counter_that_reads_zero_is_a_reading():
    before = snapshot()
    before["metrics"] = [row for row in before["metrics"]
                         if row[0] != ROLLUP_SECONDS] + [
        (ROLLUP_SECONDS, {"phase": "(outside a build)"}, 0.0)]
    assert read("frame.rollup_s", reading(before)) == 0.0


def test_the_table_is_logged_by_the_trace_reader(capsys):
    from h2o3_tpu.utils.costs import COSTS
    COSTS.record_first_call("gbm:chunk", "jit(reader_test_probe)", 123.0,
                            1.0, 2.0, True)
    assert read("entry.trace_s", reading(snapshot())) is not None
    err = capsys.readouterr().err
    assert ("# benchmark: first calls in set-up: gbm:chunk "
            "jit(reader_test_probe) 1 123.000 1.000 2.000") in err
    # and a program without the counters logs nothing
    read("entry.trace_s", reading({"metrics": []}))
    assert "first calls" not in capsys.readouterr().err
