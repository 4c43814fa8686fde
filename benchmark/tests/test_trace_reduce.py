"""The reduction from a profiler trace to numbers: on a trace built by hand,
on the trace recorded on the v5e (tests/data/), and — the reading of the
``.xplane.pb`` itself — on a trace this test records on the CPU."""

import gzip
import json
import os

import pytest

from benchmark import trace_reduce as tr
from benchmark.trace_reduce import Event

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def by_hand():
    """One chip. A ``while`` from 0 to 10 holds ``a`` (1-3) and ``b`` (4-7);
    ``c`` runs 12-13. The window is 0-14. The host is inside
    ``gbm.py:_prepare`` from 9 to 12.5 and nowhere in the program after."""
    device = [Event("while.1", 0.0, 10.0), Event("a", 1.0, 2.0),
              Event("b", 4.0, 3.0), Event("c", 12.0, 1.0)]
    host = [Event("bench:window", 0.0, 14.0), Event("bench:train", 0.5, 13.0),
            Event("$gbm.py:643 _prepare", 9.0, 3.5),
            Event("$other.py:1 helper", 9.5, 2.0)]
    return tr.Trace([device], {"python3": host}, "device")


def test_busy_is_the_union_and_time_by_name_is_self_time():
    r = tr.Reduction(by_hand(), frozenset({"gbm.py"}))
    assert (r.t0, r.t1, r.window_s) == (0.0, 14.0, 14.0)
    assert r.busy_s == 11.0                        # [0, 10] and [12, 13]
    assert r.op_self_s == {"while.1": 5.0, "a": 2.0, "b": 3.0, "c": 1.0}
    assert r.top_ops(2) == [["while.1", 5.0], ["b", 3.0]]
    assert r.op_seconds(lambda name, stats: name in ("a", "b")) == 5.0
    assert r.busy_within(9.0, 12.5) == 1.5


def test_gaps_are_named_by_what_the_host_was_doing():
    r = tr.Reduction(by_hand(), frozenset({"gbm.py"}))
    # [10, 12]: midpoint 11, inside _prepare (other.py is not the program's)
    # [13, 14]: midpoint 13.5, inside bench:window only
    assert r.idle_gaps() == [["bench:train > gbm.py:_prepare", 2.0],
                             ["bench:window", 1.0]]


def test_the_window_clips_what_lies_outside():
    trace = by_hand()
    trace.host["python3"][0] = Event("bench:window", 2.0, 10.5)    # 2 .. 12.5
    r = tr.Reduction(trace, frozenset({"gbm.py"}))
    assert r.busy_s == 8.5                         # [2, 10] and [12, 12.5]
    assert r.op_self_s["a"] == 1.0 and r.op_self_s["c"] == 0.5


def test_two_chips_are_averaged():
    trace = by_hand()
    trace.devices.append([Event("a", 0.0, 7.0)])
    r = tr.Reduction(trace)
    assert r.busy_s == (11.0 + 7.0) / 2
    assert r.op_self_s["a"] == (2.0 + 7.0) / 2


def test_hlo_text_names_the_instructions():
    text = """
  %fused_computation.47 (p: f32[65]) -> f32[65] {
    ROOT %scatter-add.266 = f32[65]{0:T(128)S(1)} scatter(%p, %t), to_apply=%r, metadata={op_name="jit(f)/shard_map/while/body/scatter-add"}
  }
  ROOT %fusion.808 = f32[65]{0:T(128)S(1)} fusion(%a, %b), kind=kCustom, calls=%fused_computation.47, metadata={op_name="jit(f)/shard_map/while/body/scatter-add" stack_frame_id=41}
  %psum_invariant.54 = f32[28,65,3]{2,0,1:T(8,128)S(1)} all-reduce(%copy.732), channel_id=1, metadata={op_name="jit(f)/shard_map/psum_invariant"}
  %all-reduce.8 = (f32[64]{0:T(128)S(1)}, f32[64]{0:T(128)S(1)}) all-reduce(%x, %y), channel_id=3
  %hist_pallas.44 = f32[1,1,2016,3]{3,2,1,0:T(8,128)S(1)} custom-call(%c, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jit(hist_pallas)/pallas_call"}
"""
    idx = tr.hlo_index("HloModule jit_f, is_scheduled=true\n" + text)
    assert idx["jit_f/fusion.808"]["opcode"] == "fusion"
    assert idx["jit_f/fusion.808"]["op_name"].endswith("scatter-add")
    assert idx["jit_f/psum_invariant.54"]["opcode"] == "all-reduce"
    assert idx["jit_f/all-reduce.8"]["opcode"] == "all-reduce"
    assert idx["jit_f/hist_pallas.44"]["opcode"] == "custom-call"
    assert tr.is_collective("jit_f/psum_invariant.54",
                            idx["jit_f/psum_invariant.54"])
    assert not tr.is_collective("jit_f/psum_invariant.54", {})  # name alone
    assert tr.is_collective("jit_f/all-reduce.8", {})


def test_a_device_event_is_named_by_its_instruction_and_its_program():
    """On the v5e an ``XLA Ops`` event's name is the instruction's text, and
    instruction names repeat from program to program."""
    text = ("%fusion.9 = f32[11000000]{0:T(1024)} fusion(f32[255]{0:T(256)S(1)}"
            " %get-tuple-element.127, s32[11000832]{0:T(1024)} %pad), "
            "kind=kCustom, calls=%fused_computation.clone")
    e = tr.op_event(text, 1.0, 2.0, {})
    assert (e.name, e.stats["opcode"]) == ("fusion.9", "fusion")
    w = tr.op_event("%while.2 = (s32[]{:T(128)}, f32[255]{0:T(256)S(1)}) "
                    "while((s32[]{:T(128)}) %tuple.25), condition=%c, body=%b",
                    0.0, 9.0, {})
    assert (w.name, w.stats["opcode"]) == ("while.2", "while")
    runs = [Event("jit_searchsorted(18351882933179078675)", 0.0, 4.0),
            Event("jit__boost_scan_jit(8274583228133865498)", 5.0, 4.0)]
    named = tr.in_modules([e, tr.op_event(text, 6.0, 1.0, {}),
                           Event("stray", 4.5, 0.1)], runs)
    assert [x.name for x in named] == [
        "jit_searchsorted/fusion.9", "jit__boost_scan_jit/fusion.9", "stray"]
    trace = tr.Trace([[w, e]], {}, "device", [runs])
    r = tr.Reduction(trace)
    assert r.module_s == {"jit_searchsorted": 4.0, "jit__boost_scan_jit": 4.0}
    assert r.op_self_s == {"jit_searchsorted/while.2": 7.0,
                           "jit_searchsorted/fusion.9": 2.0}
    assert r.top_ops(1) == [["jit_searchsorted/while.2 [while]", 7.0]]


def test_exposed_collective_time_is_what_compute_does_not_cover():
    # a collective 2-6 on a line where compute runs 0-3 and 5-8 beside it
    # (two streams in one line, as a nested sibling)
    device = [Event("while.1", 0.0, 10.0), Event("compute.1", 0.0, 3.0),
              Event("all-reduce.1", 2.0, 4.0), Event("compute.2", 5.0, 3.0)]
    r = tr.Reduction(tr.Trace([device], {}, "device"))
    assert r.exposed_seconds(tr.is_collective) == 2.0         # [3, 5]


def test_reading_an_xplane_file_recorded_here(tmp_path):
    """The file format and ``ProfileData``: on the CPU there is no device
    plane, so the XLA executor's events stand in as one pseudo-device."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:window"):
        with jax.profiler.TraceAnnotation("bench:train"):
            for _ in range(3):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    trace = tr.load(tr.find_xplane(str(tmp_path)))
    assert trace.kind == "cpu-rehearsal" and len(trace.devices) == 1
    r = tr.Reduction(trace)
    assert len(r.spans("bench:train")) == 1
    assert 0 < r.busy_s <= r.window_s
    assert any(name.startswith("jit__lambda/dot") for name in r.op_self_s)


def test_the_trace_recorded_on_the_v5e():
    """The first 12 s of a traced ``gbm64-higgs-build`` window on the v5e
    (``what`` in the file says which). Checked against what the file itself
    shows by other routes: busy time, the union of the ``XLA Ops`` events,
    against the sum of the ``XLA Modules`` events, a different line of the
    same trace; the kernel's seconds against its thirteen events (six
    levels of two trees and one more, 0.217 to 0.232 s each, summed by
    hand: 2.93834 s); the longest idle gap against the host's quantile pass,
    which the program's own timeline also shows."""
    with gzip.open(os.path.join(DATA, "v5e_gbm_build.json.gz"), "rt") as f:
        rec = json.load(f)
    trace = tr.Trace(
        [[Event(n, s, d, {"opcode": op}) for n, op, s, d in evs]
         for evs in rec["devices"]],
        {"python3": [Event(n, s, d) for n, s, d in rec["host_main"]]},
        rec["kind"],
        [[Event(n, s, d) for n, s, d in evs] for evs in rec["modules"]])
    r = tr.Reduction(trace, frozenset(rec["program_files"]))
    assert r.kind == "device" and len(r.devices) == 1
    assert r.window_s == pytest.approx(12.0, abs=1e-9)
    assert r.busy_s == pytest.approx(sum(r.module_s.values()), rel=1e-4)
    assert r.busy_s == pytest.approx(11.596759, abs=1e-5)
    assert r.module_s["jit__boost_scan_jit"] == pytest.approx(11.265414, abs=1e-5)
    assert r.module_s["jit_searchsorted"] == pytest.approx(0.259503, abs=1e-5)

    def kernel(name, stats):
        return name.rpartition("/")[2].startswith("hist_pallas")
    calls = [e for e in r.devices[0] if kernel(e.name, e.stats)]
    assert len(calls) == 13 and all(0.21 < e.dur < 0.24 for e in calls)
    assert all(e.stats["opcode"] == "custom-call" for e in calls)
    assert r.op_seconds(kernel) == pytest.approx(2.93834, abs=1e-5)
    # the scan's ``while`` spans 11.27 s and owns 2% of it: the time
    # between the operations of its body
    assert 0.2 < r.op_self_s["jit__boost_scan_jit/while.27"] < 0.3
    # self times add up to busy time but for the few events that overlap
    assert sum(r.op_self_s.values()) == pytest.approx(r.busy_s, rel=0.03)
    assert r.top_ops(1)[0][0] == "jit__boost_scan_jit/fusion.554 [fusion]"
    gaps = r.idle_gaps(2)
    assert gaps[0][0] == "bench:train > quantile.py:compute_bin_edges"
    assert gaps[0][1] == pytest.approx(0.318373, abs=1e-5)
    assert r.window_s - r.busy_s == pytest.approx(
        sum(s for _, s in r.idle_gaps(1000)), rel=1e-6)
