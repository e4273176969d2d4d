"""The benchmark cells ``small_msg.4chip`` (the upstream bench on four
ranks) and ``datapath.1chip`` (the combine plugin on one rank), run end
to end through ``cellrun.run_cell`` on the CPU's virtual devices at
sizes a test can hold: ``correct`` comes out true for the library, with
a gap of 0.0, and false for each fault a cell can have and for the
control (the reference in the library's place, in bfloat16).

``small_msg.4chip`` runs 2**4..2**14 elements, which crosses the latency
tier (below 8 KiB), the eager protocol and the rendezvous protocol
(above 32 KiB); ``datapath.1chip`` runs 2**4..2**6."""
import time

import jax
import pytest

from benchmark import cellrun, ops, spec
from benchmark.tests.test_correct import FAULTS

SIZES = {"small_msg.4chip": [4, 14], "datapath.1chip": [4, 6]}


def _run(name, call, seed=2**40 + 11):
    cell = spec.resolve_cell(name)
    cell.traffic["size_pow2"] = SIZES[name]
    return cellrun.run_cell(cell, seed, 0.2, False,
                            jax.devices()[:cell.chips], time.perf_counter(),
                            call=call)


@pytest.mark.parametrize("name", sorted(SIZES))
def test_library_is_correct(name):
    res = _run(name, ops.call)
    assert res["correct"], res["checks"]
    assert res["checks"]["max_abs_gap"]["value"] == 0.0
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["window"]["compiles"] == 0
    assert set(res["metrics"]) == {m.name for m in
                                   spec.resolve_cell(name).end_to_end}


@pytest.mark.parametrize("name,fault", [
    (n, f) for n in sorted(SIZES) for f in sorted(FAULTS)
    if not (f == "no_exchange" and n.endswith(".1chip"))])
def test_fault_is_not_correct(name, fault):
    res = _run(name, FAULTS[fault])
    assert not res["correct"], (fault, res["checks"])


def _traced(n, busy_s, calls):
    """A traced run's record: ``calls`` combine calls of 2**20 fp32
    elements per operand, on ``n`` ranks, the busiest chip busy
    ``busy_s`` of a 1 s window."""
    from benchmark import peaks, trace_reduce

    g = ops.Geometry(1 << 20, 2 << 20, 1 << 20)
    tr = trace_reduce.TraceSummary(window_s=1.0, busy_s={0: busy_s,
                                                         1: busy_s / 2})
    return {"calls": [(("combine", 1 << 20), g, 1e-3)] * calls, "n": n,
            "itemsize": 4, "trace": tr,
            "peak": peaks.peaks("TPU v5 lite")}


def test_plugin_roofline_reads_the_hbm_bound():
    """Both operands read and the result written once, at the HBM peak,
    over the busiest chip's busy time; nothing on more than one rank or
    without a trace."""
    read = spec.load_reader(spec.ROOT, "plugin_roofline")
    least = 10 * 3 * (1 << 20) * 4 / 819e9
    assert read(_traced(1, 2 * least, 10)) == pytest.approx(50.0)
    assert read(_traced(4, 2 * least, 10)) is None
    assert read(dict(_traced(1, 2 * least, 10), trace=None)) is None


def test_busy_us_is_busiest_chip_per_call():
    read = spec.load_reader(spec.ROOT, "busy_us.small4")
    assert read(_traced(4, 0.002, 100)) == pytest.approx(20.0)
    assert read(dict(_traced(4, 0.002, 100), trace=None)) is None
    assert read(_traced(4, 0.002, 0)) is None
