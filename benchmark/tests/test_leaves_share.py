"""``program.leaves_share`` on ``test_scopes.py``'s hand-built trace: the
``leaves`` scope read as ``route``'s is, by self time over busy time."""

import pytest
from test_scopes import BODY, HLO, program_host, read, reading, scan

from benchmark import plugins
from benchmark.trace_reduce import Event

#: the last level's totals as the kernel's one-feature call (the
#: instruction takes the jitted function's name) and the rows' read of
#: their leaf value
LEAVES = f"""  %hist_pallas.10 = f32[1,1,64,3]{{3,2,1,0}} custom-call(%c, %b), custom_call_target="tpu_custom_call", metadata={{op_name="{BODY}/leaves/jit(hist_pallas)/pallas_call"}}
  %fusion.11 = f32[100]{{0}} fusion(%a), kind=kLoop, calls=%f11, metadata={{op_name="{BODY}/leaves/reduce_sum"}}
"""


def test_the_reader_names_its_layer_and_what_it_moves():
    metric = plugins.load("layer_metrics", "program.leaves_share")
    assert (metric.LAYER, metric.UNIT, metric.MOVES, metric.DRIVERS) == (
        "program", "%", "train_work_per_s_chip", ("build_loop",))


def test_a_leaves_scope_reads_its_share_of_busy_time():
    device = scan() + [Event("hist_pallas.10", 15.25, 0.5),
                       Event("fusion.11", 15.75, 0.25)]
    r = reading(device, program_host(), hlo=HLO + LEAVES)
    assert r.trace.busy_s == 14.0
    assert read("program.leaves_share", r) == pytest.approx(100 * 0.75 / 14.0)
    # the call is the kernel's by its name, in the leaves scope by its path
    assert read("kernel.hist_share", r) == pytest.approx(100 * 2.0 / 14.0)
    assert read("program.route_share", r) == pytest.approx(100 * 2.0 / 14.0)


@pytest.mark.parametrize("hlo", [
    HLO,                                               # scopes, none `leaves`
    "\n".join(line.split(", metadata=")[0]             # no scopes at all
              for line in (HLO + LEAVES).splitlines())],
    ids=["no-leaves-scope", "no-scopes"])
def test_without_the_scope_the_share_is_left_out(hlo):
    r = reading(scan(), program_host(), hlo=hlo)
    assert read("program.leaves_share", r) is None


def test_no_trace_reads_none():
    r = reading(scan(), program_host())
    r.trace = None
    assert read("program.leaves_share", r) is None
