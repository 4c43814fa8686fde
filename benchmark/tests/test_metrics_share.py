"""``builder.metrics_share`` and ``builder.metric_hist_matmul_share`` on
``test_scopes.py``'s hand-built trace and on counter snapshots."""

import pytest
from test_scopes import program_host, read, reading, scan

from benchmark import plugins
from benchmark.trace_reduce import Event


@pytest.mark.parametrize("name", ["builder.metrics_share",
                                  "builder.metric_hist_matmul_share"])
def test_the_readers_name_their_layer_and_what_they_move(name):
    metric = plugins.load("layer_metrics", name)
    assert (metric.LAYER, metric.UNIT, metric.MOVES, metric.DRIVERS) == (
        "builder", "%", "train_work_per_s_chip", ("build_loop",))


@pytest.mark.parametrize("algo", ["gbm", "glm"])
def test_the_metrics_spans_over_the_train_spans(algo):
    host = program_host(algo) + [Event(f"{algo}:metrics", 21.0, 4.5)]
    r = reading(scan(), host, algo=algo)
    assert read("builder.metrics_share", r) == pytest.approx(100 * 4.5 / 30.0)


def test_a_metrics_span_that_started_before_the_window_is_not_counted():
    host = program_host() + [Event("gbm:metrics", -3.0, 1.0),
                             Event("gbm:metrics", 21.0, 3.0)]
    r = reading(scan(), host)
    assert read("builder.metrics_share", r) == pytest.approx(100 * 3.0 / 30.0)


def test_without_the_span_or_a_trace_the_share_is_left_out():
    r = reading(scan(), program_host())
    assert read("builder.metrics_share", r) is None
    r.trace = None
    assert read("builder.metrics_share", r) is None


def test_the_matmul_share_is_read_off_the_counter_at_the_windows_end():
    r = reading(scan(), program_host())
    name = "h2o3_metric_hist_total"
    r.after = {"metrics": [(name, {"path": "matmul"}, 3.0)]}
    assert read("builder.metric_hist_matmul_share", r) == 100.0
    r.after = {"metrics": [(name, {"path": "matmul"}, 3.0),
                           (name, {"path": "scatter"}, 1.0)]}
    assert read("builder.metric_hist_matmul_share", r) == 75.0
    # a program without the counter (PR 34's parent) leaves the metric out
    r.after = {"metrics": [("h2o3_route_levels_total", {"path": "select"}, 6.0)]}
    assert read("builder.metric_hist_matmul_share", r) is None
