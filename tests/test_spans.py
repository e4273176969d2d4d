"""The span tree of one ACCL call (``obs.trace``): names and nesting per
op, self time recorded as ``accl_span_self_seconds{span}``, the call id
from the root to a later async wait, and the Chrome export's clock
against the profiler's host plane."""
import glob

import pytest

from accl_tpu import dataType, reduceFunction
from accl_tpu.obs import metrics, trace

N = 8  # elements per call: one eager segment, the latency tier


@pytest.fixture(autouse=True)
def _tracing():
    """Each test starts with metrics on, tracing off and no events."""
    metrics.enable()
    trace.stop()
    trace.clear()
    yield
    trace.stop()
    trace.clear()


def _buf(accl, count=N, fill=1.0):
    b = accl.create_buffer(count, dataType.float32)
    b.host[:] = fill
    b.sync_to_device()
    return b


def _spans():
    """The traced region's complete events, outermost first."""
    evs = [e for e in trace.TRACER.to_chrome_trace()["traceEvents"]
           if e["ph"] == "X"]
    return sorted(evs, key=lambda e: (e["ts"], -e["dur"]))


def _trees(evs):
    """Nest the events by containment: ``[(name, [children...]), ...]``
    per root (timestamps are us with sub-us rounding, hence the slack)."""
    out, stack = [], []
    for e in evs:
        end = e["ts"] + e["dur"]
        while stack and stack[-1][0] + 1e-3 < end:
            stack.pop()
        node = (e["name"], [])
        (stack[-1][1][1] if stack else out).append(node)
        stack.append((e["ts"] + e["dur"], node))
    return out


def _shape(node):
    name, kids = node
    return (name, [_shape(k) for k in kids])


def _wait(op):
    return (f"req.{op}.wait", [("req.block", []), ("req.finalize", [])])


def _allreduce(accl):
    a, b = _buf(accl), _buf(accl)
    return lambda: accl.allreduce(a, b, N, reduceFunction.SUM)


def _bcast(accl):
    a = _buf(accl)
    return lambda: accl.bcast(a, N, root=0)


def _sendrecv(accl):
    a, b = _buf(accl), _buf(accl)

    def run():
        accl.send(a, N, src=0, dst=1, tag=71)
        accl.recv(b, N, src=0, dst=1, tag=71)
    return run


_LAUNCHED = [("accl.plan", []), ("accl.launch", [])]

CASES = {
    "allreduce": (_allreduce, [("accl.allreduce",
                                _LAUNCHED + [_wait("allreduce")])]),
    "bcast": (_bcast, [("accl.bcast", _LAUNCHED + [_wait("bcast")])]),
    # the send parks; the recv's post matches it and the delivery's move
    # program launches under the recv's match
    "sendrecv": (_sendrecv, [
        ("accl.send", [("accl.match", []), _wait("send")]),
        ("accl.recv", [("accl.match", [("accl.launch", [])]),
                       _wait("recv")])]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_span_tree_names_and_nesting(accl, case):
    make, want = CASES[case]
    run = make(accl)
    run()                   # warm: the traced call hits the program cache
    trace.start()
    run()
    trace.stop()
    assert [_shape(t) for t in _trees(_spans())] == want


def test_program_cache_miss_is_a_compile_span(accl):
    a, b = _buf(accl, 37), _buf(accl, 37)
    trace.start()
    accl.allreduce(a, b, 37, reduceFunction.MAX)   # a key no test used
    accl.allreduce(a, b, 37, reduceFunction.MAX)
    trace.stop()
    first, second = [_shape(t) for t in _trees(_spans())]
    assert first[1][1] == ("accl.launch", [("accl.compile", [])])
    assert second[1][1] == ("accl.launch", [])


def test_self_times_sum_to_root_duration(accl):
    run = _allreduce(accl)
    run()
    before = metrics.snapshot()
    trace.start()
    run()
    trace.stop()
    d = metrics.delta(before)["histograms"]
    selfs = {k: h for k, h in d.items()
             if k.startswith(metrics.SPAN_SELF + "{")}
    assert set(selfs) == {f'{metrics.SPAN_SELF}{{span="{n}"}}' for n in (
        "accl.allreduce", "accl.plan", "accl.launch",
        "req.allreduce.wait", "req.block", "req.finalize")}
    assert all(h["count"] == 1 and h["buckets"] == {}
               for h in selfs.values())
    [root] = [e for e in _spans() if e["name"] == "accl.allreduce"]
    total_us = sum(h["sum"] for h in selfs.values()) * 1e6
    assert total_us == pytest.approx(root["dur"], rel=0.02)


def test_async_wait_carries_the_call_id(accl):
    a, b = _buf(accl), _buf(accl)
    accl.allreduce(a, b, N, reduceFunction.SUM)
    trace.start()
    req = accl.allreduce(a, b, N, reduceFunction.SUM, run_async=True)
    accl.bcast(a, N, root=0)            # another call between the two
    req.wait()
    trace.stop()
    evs = _spans()
    [root] = [e for e in evs if e["name"] == "accl.allreduce"]
    [wait] = [e for e in evs if e["name"] == "req.allreduce.wait"]
    [other] = [e for e in evs if e["name"] == "accl.bcast"]
    assert root["args"]["call"] == wait["args"]["call"] == req.call_id
    assert other["args"]["call"] != req.call_id
    # the wait ran after its root closed: it is a tree of its own
    assert wait["ts"] >= root["ts"] + root["dur"]


def test_tracing_off_records_no_span_series(accl):
    run = _allreduce(accl)
    before = metrics.snapshot()
    run()
    d = metrics.delta(before)
    assert not any(k.startswith(metrics.SPAN_SELF)
                   for k in d["histograms"])
    assert len(trace.TRACER) == 0
    assert trace.current_call() is None


def test_chrome_ts_on_the_profiler_host_clock(accl, tmp_path):
    """A span's Chrome-JSON ``ts`` and its TraceAnnotation on the xplane
    host plane (the capture's ``profile_start_time`` plus the event's
    offset) name the same instant, within 1 ms."""
    import jax
    from jax.profiler import ProfileData

    run = _allreduce(accl)
    run()
    trace.start()
    with jax.profiler.trace(str(tmp_path)):
        run()
    trace.stop()
    [root] = [e for e in _spans() if e["name"] == "accl.allreduce"]
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    start = [dict(p.stats) for p in pd.planes
             if p.name == "Task Environment"][0]["profile_start_time"]
    host = [ev for p in pd.planes if p.name == "/host:CPU"
            for line in p.lines for ev in line.events
            if ev.name == "accl.allreduce"]
    assert len(host) == 1
    assert abs((start + host[0].start_ns) / 1e3 - root["ts"]) < 1000.0
    assert dict(host[0].stats)["call"] == root["args"]["call"]
