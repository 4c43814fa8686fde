"""The five readers of the program's own names (``layer_metrics/_scopes.py``)
on traces built by hand: one device line, one host line, and a compiled
module's text whose ``op_name``s hold the scopes."""

import types

import pytest

from benchmark import plugins
from benchmark import trace_reduce as tr
from benchmark.cell import Reading
from benchmark.trace_reduce import Event

MODULE = "jit__boost_scan_jit"
PROGRAM = "h2o3_tpu.models.gbm:_boost_scan_jit"
BODY = "jit(_boost_scan_jit)/while/body/closed_call"
HLO = f"""HloModule {MODULE}, is_scheduled=true
  %fusion.1 = s8[100]{{0}} fusion(%a), kind=kLoop, calls=%f1, metadata={{op_name="{BODY}/level0/route/jit(take_along_axis)/gather"}}
  %fusion.2 = f32[100]{{0}} fusion(%a), kind=kLoop, calls=%f2, metadata={{op_name="{BODY}/level1/route/gather"}}
  %hist_pallas.3 = f32[1,1,65,3]{{3,2,1,0}} custom-call(%c, %b), custom_call_target="tpu_custom_call", metadata={{op_name="{BODY}/level1/hist/jit(hist_pallas)/pallas_call"}}
  %fusion.4 = f32[28,130,3]{{2,1,0}} fusion(%a), kind=kLoop, calls=%f4, metadata={{op_name="{BODY}/level1/hist/sub"}}
  %fusion.5 = f32[2]{{0}} fusion(%a), kind=kLoop, calls=%f5, metadata={{op_name="{BODY}/level1/split/reduce_max"}}
  %fusion.6 = f32[100]{{0}} fusion(%a), kind=kLoop, calls=%f6, metadata={{op_name="{BODY}/update/add"}}
  %copy.7 = f32[100]{{0}} copy(%a), metadata={{op_name="{BODY}"}}
  %while.8 = (f32[100]{{0}}) while(%t), condition=%c, body=%b, metadata={{op_name="jit(_boost_scan_jit)/while"}}
"""


def reading(device, host, hlo=HLO, algo="gbm", builds=1, program=PROGRAM):
    """A build's trace: ``device`` on one chip's ``XLA Ops`` line, the
    boost program running from 10 to 20, ``host`` on the main thread under
    a window from 0 to 30."""
    host = [Event("bench:window", 0.0, 30.0), Event("bench:train", 0.0, 30.0),
            *host]
    trace = tr.Trace([device], {"python3": host}, "device",
                     modules=[[Event(f"{MODULE}(123)", 10.0, 10.0)]])
    cell = types.SimpleNamespace(config={"program": program})
    return Reading(cell=cell, facts={"algo": algo, "builds": builds},
                   spans=None, before={}, after={},
                   trace=tr.Reduction(trace, hlo=tr.hlo_index(hlo)),
                   peak=None, memory_peak_bytes=0)


def scan():
    """The boost program, 10 to 20: a ``while`` that holds 1 s of each of
    two route gathers, 1.5 s of the kernel, 0.5 s each of the subtraction,
    the split search and the update, 0.25 s of a copy with no scope; and,
    before it, 4 s of binning from 2 to 6."""
    return [Event("fusion.9", 2.0, 4.0), Event("while.8", 10.0, 10.0),
            Event("fusion.1", 10.0, 1.0), Event("fusion.2", 11.0, 1.0),
            Event("hist_pallas.3", 12.0, 1.5), Event("fusion.4", 13.5, 0.5),
            Event("fusion.5", 14.0, 0.5), Event("fusion.6", 14.5, 0.5),
            Event("copy.7", 15.0, 0.25)]


def program_host(algo="gbm"):
    return [Event(f"{algo}:fit", 0.5, 29.0),
            Event(f"{algo}:prepare.edges", 1.0, 1.0),
            Event(f"{algo}:prepare.bin", 2.0, 0.5),
            Event(f"{algo}:chunk", 10.0, 10.5)]


def read(name, r):
    return plugins.load("layer_metrics", name).read(r)


def test_a_part_is_a_path_component_of_the_op_name():
    scopes = plugins.load("layer_metrics", "_scopes")
    assert scopes.part_of(f"{BODY}/level3/route/jit(take_along_axis)/gather") == "route"
    assert scopes.part_of(f"{BODY}/level1/hist/while/body/closed_call/scatter-add") == "hist"
    assert scopes.part_of(f"{BODY}/leaves/scatter-add") == "leaves"
    assert scopes.part_of(f"{BODY}/grad/jit(_grad_hess)/logistic") == "grad"
    # a function that happens to be called like a part is not the scope
    assert scopes.part_of(f"{BODY}/jit(split)/slice") is None
    assert scopes.part_of(BODY) is None and scopes.part_of("") is None


def test_shares_by_scope_are_self_time_over_busy_time():
    r = reading(scan(), program_host())
    assert r.trace.busy_s == 14.0               # 4 of binning, 10 of the scan
    assert read("program.route_share", r) == pytest.approx(100 * 2.0 / 14.0)
    assert read("program.hist_scope_share", r) == pytest.approx(100 * 2.0 / 14.0)
    assert read("program.split_share", r) == pytest.approx(100 * 0.5 / 14.0)
    by_part = plugins.load("layer_metrics", "_scopes").seconds_by_part(r)
    # the scan's own while and the copy carry no scope; binning is another
    # program and is not the boost program's remainder
    assert by_part["(loops)"] == pytest.approx(10.0 - 5.25)
    assert by_part["(unscoped)"] == pytest.approx(0.25)
    assert by_part["update"] == 0.5 and "grad" not in by_part
    assert sum(by_part.values()) == pytest.approx(10.0)


def test_the_kernel_by_pattern_lies_inside_the_hist_scope():
    r = reading(scan(), program_host())
    assert read("kernel.hist_share", r) == pytest.approx(100 * 1.5 / 14.0)
    assert read("program.hist_scope_share", r) >= read("kernel.hist_share", r)


def test_a_program_without_scopes_reads_none_not_zero():
    bare = "\n".join(line.split(", metadata=")[0] for line in HLO.splitlines())
    r = reading(scan(), program_host(), hlo=bare)
    for name in ("program.route_share", "program.split_share",
                 "program.hist_scope_share"):
        assert read(name, r) is None
    # scopes, but none called split: that share alone is left out
    r = reading(scan(), program_host(), hlo=HLO.replace("/split/", "/other/"))
    assert read("program.split_share", r) is None
    assert read("program.route_share", r) is not None


def test_edges_idle_is_the_span_less_what_the_device_ran_inside_it():
    # prepare.edges 1..2; the device is busy from 1.75 (an early dispatch)
    device = [Event("fusion.0", 1.75, 0.25)] + scan()
    r = reading(device, program_host())
    assert read("builder.edges_idle_s", r) == pytest.approx(0.75)
    # two builds in the window: seconds a build
    host = program_host() + [Event("gbm:prepare.edges", 21.0, 0.5)]
    r = reading(device, host, builds=2)
    assert read("builder.edges_idle_s", r) == pytest.approx((0.75 + 0.5) / 2)


def test_bin_device_is_busy_time_up_to_the_first_chunk_after_the_span():
    # prepare.bin opens at 2; binning runs 2..6; the f0 fetch drains it and
    # the first chunk opens at 10; a second chunk (20.5) is not read
    host = program_host() + [Event("gbm:chunk", 20.5, 5.0)]
    device = scan() + [Event("fusion.9", 21.0, 3.0)]
    r = reading(device, host)
    assert read("builder.bin_device_s", r) == pytest.approx(4.0)


def test_the_builders_own_algo_names_the_spans():
    r = reading(scan(), program_host("xgboost"), algo="xgboost")
    assert read("builder.bin_device_s", r) == pytest.approx(4.0)
    assert read("builder.edges_idle_s", r) == pytest.approx(1.0)
    # another builder's spans are not this build's
    r = reading(scan(), program_host("gbm"), algo="xgboost")
    assert read("builder.bin_device_s", r) is None
    assert read("builder.edges_idle_s", r) is None


def test_without_the_spans_or_a_trace_nothing_is_reported():
    r = reading(scan(), [])                      # the parent: no program span
    assert read("builder.edges_idle_s", r) is None
    assert read("builder.bin_device_s", r) is None
    r = reading(scan(), program_host()[:3])      # a span, but no chunk after
    assert read("builder.bin_device_s", r) is None
    r.trace = None                               # --trace 0
    for name in ("builder.edges_idle_s", "builder.bin_device_s",
                 "program.route_share", "program.split_share",
                 "program.hist_scope_share"):
        assert read(name, r) is None


def test_a_span_that_started_before_the_window_is_not_this_windows():
    host = [Event("gbm:prepare.edges", -1.0, 1.5)] + program_host()[2:]
    r = reading(scan(), host)
    assert read("builder.edges_idle_s", r) is None
