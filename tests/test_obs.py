"""Telemetry subsystem tests (ISSUE r8): the per-operation metrics
matrix, Chrome-trace schema, stats() round-trip, disabled-path overhead
budget, registry semantics, and the logging satellites."""
import json
import time

import numpy as np
import pytest

from accl_tpu import dataType, reduceFunction
from accl_tpu.constants import operation
from accl_tpu.obs import metrics, trace

N = 8  # elements per call in the matrix (1 eager segment at fp32)


@pytest.fixture(autouse=True)
def _obs_defaults():
    """Every test starts from the default telemetry state (metrics on,
    tracing off) and restores it — the registry is process-global."""
    metrics.enable()
    trace.stop()
    yield
    metrics.enable()
    trace.stop()


def _op_totals(delta: dict, op_name: str):
    """(calls, bytes) summed over every label set of one operation."""
    calls = sum(v for k, v in delta["counters"].items()
                if k.startswith("accl_calls_total{")
                and f'op="{op_name}"' in k)
    nbytes = sum(v for k, v in delta["counters"].items()
                 if k.startswith("accl_bytes_total{")
                 and f'op="{op_name}"' in k)
    return calls, nbytes


def _mkbuf(accl, count=N, dt=dataType.float32, fill=1.0):
    buf = accl.create_buffer(count, dt)
    buf.host[:] = fill
    buf.sync_to_device()
    return buf


# one recipe per operation enum member: (prepare(accl) -> run callable,
# expected payload bytes). prepare runs OUTSIDE the measured window so
# pair-protocol setup (the send a recv needs) never pollutes the count.
def _recipes(accl):
    world = accl.world_size
    SUM = reduceFunction.SUM

    def r_copy():
        a, b = _mkbuf(accl), _mkbuf(accl)
        return lambda: accl.copy(a, b, N), N * 4

    def r_combine():
        a, b, c = _mkbuf(accl), _mkbuf(accl), _mkbuf(accl)
        return lambda: accl.combine(N, SUM, a, b, c), N * 4

    def r_send():
        a, b = _mkbuf(accl), _mkbuf(accl)
        # the matching recv drains the posted segments AFTER the window
        return (lambda: accl.send(a, N, src=0, dst=1, tag=91),
                N * 4,
                lambda: accl.recv(b, N, src=0, dst=1, tag=91))

    def r_recv():
        a, b = _mkbuf(accl), _mkbuf(accl)
        accl.send(a, N, src=2, dst=3, tag=92)   # outside the window
        return lambda: accl.recv(b, N, src=2, dst=3, tag=92), N * 4

    def r_put():
        a, b = _mkbuf(accl), _mkbuf(accl)
        return lambda: accl.put(a, b, N, src=0, dst=1), N * 4

    def r_bcast():
        a = _mkbuf(accl)
        return lambda: accl.bcast(a, N, root=0), N * 4

    def r_scatter():
        a, b = _mkbuf(accl, N * world), _mkbuf(accl)
        return lambda: accl.scatter(a, b, N, root=0), N * world * 4

    def r_gather():
        a, b = _mkbuf(accl), _mkbuf(accl, N * world)
        return lambda: accl.gather(a, b, N, root=0), N * 4

    def r_allgather():
        a, b = _mkbuf(accl), _mkbuf(accl, N * world)
        return lambda: accl.allgather(a, b, N), N * 4

    def r_reduce():
        a, b = _mkbuf(accl), _mkbuf(accl)
        return lambda: accl.reduce(a, b, N, 0, SUM), N * 4

    def r_allreduce():
        a, b = _mkbuf(accl), _mkbuf(accl)
        return lambda: accl.allreduce(a, b, N, SUM), N * 4

    def r_reduce_scatter():
        a, b = _mkbuf(accl, N * world), _mkbuf(accl)
        return lambda: accl.reduce_scatter(a, b, N, SUM), N * world * 4

    def r_alltoall():
        a, b = _mkbuf(accl, N * world), _mkbuf(accl, N * world)
        return lambda: accl.alltoall(a, b, N), N * world * 4

    def r_barrier():
        return lambda: accl.barrier(), 0

    return {
        operation.copy: r_copy,
        operation.combine: r_combine,
        operation.send: r_send,
        operation.recv: r_recv,
        operation.put: r_put,
        operation.bcast: r_bcast,
        operation.scatter: r_scatter,
        operation.gather: r_gather,
        operation.allgather: r_allgather,
        operation.reduce: r_reduce,
        operation.allreduce: r_allreduce,
        operation.reduce_scatter: r_reduce_scatter,
        operation.alltoall: r_alltoall,
        operation.barrier: r_barrier,
    }


#: members with no direct host-call path: config is not a data op, nop is
#: the firmware filler, and the collective-matmul / fused-a2a scenarios
#: dispatch through device_api/jit (no eager host call to count)
_UNCOUNTED = {operation.config, operation.nop,
              operation.allgather_matmul, operation.matmul_reduce_scatter,
              operation.alltoall_matmul, operation.matmul_alltoall}


def test_matrix_covers_every_operation(accl):
    """The matrix below must cover EVERY operation enum member (minus the
    documented no-host-path set) — adding an op without telemetry
    coverage fails here."""
    assert set(_recipes(accl)) | _UNCOUNTED == set(operation)


@pytest.mark.parametrize("op", sorted(set(operation) - _UNCOUNTED,
                                      key=lambda o: o.value),
                         ids=lambda o: o.name)
def test_op_counter_and_bytes_increment_once_per_call(accl, op):
    """Tier-1 matrix (ISSUE r8): one host call = exactly one
    accl_calls_total bump and exactly the call's payload bytes, for every
    operation member send/recv through alltoall/barrier."""
    got = _recipes(accl)[op]()
    run, expect_bytes = got[0], got[1]
    drain = got[2] if len(got) > 2 else None
    before = metrics.snapshot()
    run()
    d = metrics.delta(before)
    if drain is not None:
        drain()
    calls, nbytes = _op_totals(d, op.name)
    assert calls == 1.0, f"{op.name}: {calls} calls counted"
    assert nbytes == expect_bytes, f"{op.name}: {nbytes} bytes counted"
    # and a second identical call counts again (no warn-once semantics)
    got = _recipes(accl)[op]()
    before = metrics.snapshot()
    got[0]()
    if len(got) > 2:
        got[2]()
    assert _op_totals(metrics.delta(before), op.name)[0] == 1.0


def test_dispatch_histogram_and_algorithm_labels(accl):
    before = metrics.snapshot()
    a, b = _mkbuf(accl), _mkbuf(accl)
    accl.allreduce(a, b, N, reduceFunction.SUM)
    d = metrics.delta(before)
    [(k, h)] = [(k, h) for k, h in d["histograms"].items()
                if k.startswith("accl_dispatch_seconds")
                and 'op="allreduce"' in k]
    assert h["count"] == 1 and h["sum"] > 0
    # the algorithm label names the family that actually dispatched —
    # a 32-byte allreduce rides the latency tier's flat star (round 13)
    assert any('algorithm="flat"' in k and 'op="allreduce"' in k
               for k in d["counters"])
    # the dispatch histogram resolves a sub-threshold dispatch at µs
    # resolution itself: no second series observes the same interval
    assert set(h["buckets"]) == {repr(e) for e in metrics.US_BUCKETS}
    assert not any(k.startswith("accl_latency_dispatch_seconds")
                   for k in d["histograms"])


def test_metrics_disabled_records_nothing(accl):
    before = metrics.snapshot()
    metrics.disable()
    a, b = _mkbuf(accl), _mkbuf(accl)
    accl.allreduce(a, b, N, reduceFunction.SUM)
    metrics.enable()
    d = metrics.delta(before)
    assert d["counters"] == {} and d["histograms"] == {}


def test_disabled_overhead_budget(accl):
    """Acceptance (ISSUE r8): with telemetry disabled, the ONLY code a
    no-obs build would not run is the guard checks — one tick + note_call
    + the call's null spans (root, plan, launch, the request's wait,
    block and finalize) + the call-id read + two inc()s per collective
    dispatch. Bound their cost at 5% of one measured allreduce dispatch
    (a generous multiple of the 1% budget, for CI noise; the obs_overhead
    bench lane reports the precise figures on silicon)."""
    a, b = _mkbuf(accl, 1024), _mkbuf(accl, 1024)
    accl.allreduce(a, b, 1024, reduceFunction.SUM,
                   from_device=True, to_device=True)  # warm the program
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        accl.allreduce(a, b, 1024, reduceFunction.SUM,
                       from_device=True, to_device=True)
        ts.append(time.perf_counter() - t0)
    t_op = float(np.median(ts))

    metrics.disable()
    trace.stop()
    n = 5000
    t0 = time.perf_counter()
    for _ in range(n):
        tick = metrics.tick()
        with trace.root("accl.allreduce", count=1024):
            with trace.span("accl.plan"):
                pass
            with trace.span("accl.launch"):
                pass
            metrics.inc("accl_algorithm_selected_total")
            metrics.inc("accl_sendrecv_protocol_total")
            metrics.note_call(operation.allreduce, 4096, dataType.float32,
                              None, tick)
            trace.current_call()
            with trace.span("req.allreduce.wait"):
                with trace.span("req.block"):
                    pass
                with trace.span("req.finalize"):
                    pass
    per_dispatch_guard = (time.perf_counter() - t0) / n
    metrics.enable()
    assert per_dispatch_guard < 0.05 * t_op, (
        f"disabled-telemetry guard {per_dispatch_guard * 1e6:.2f}us vs "
        f"dispatch {t_op * 1e6:.1f}us")


def test_trace_disabled_by_default_no_events(accl):
    trace.clear()
    a, b = _mkbuf(accl), _mkbuf(accl)
    accl.allreduce(a, b, N, reduceFunction.SUM)
    assert len(trace.TRACER) == 0


def test_trace_file_is_valid_chrome_trace(accl, tmp_path):
    """Acceptance (ISSUE r8): a profile() region plus obs.trace produces
    a Chrome-trace JSON that loads standalone — the event array carries
    complete ('X') spans with ts/dur/pid/tid plus track metadata."""
    trace.clear()
    trace.start()
    try:
        a, b = _mkbuf(accl), _mkbuf(accl)
        with accl.profile(str(tmp_path / "xprof")):
            accl.allreduce(a, b, N, reduceFunction.SUM)
            accl.barrier()
    finally:
        trace.stop()
    path = trace.TRACER.write(str(tmp_path / "host.trace.json"))
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and evs
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)
    xs = [e for e in evs if e["ph"] == "X"]
    assert xs
    for e in xs:
        assert isinstance(e["ts"], (int, float))
        assert e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    names = {e["name"] for e in xs}
    assert {"accl.allreduce", "req.allreduce.wait",
            "accl.barrier"} <= names


def test_capture_context_writes_file(accl, tmp_path):
    a, b = _mkbuf(accl), _mkbuf(accl)
    # foreign spans recorded before the capture must NOT leak into it
    trace.start()
    accl.bcast(a, N, root=0)
    trace.stop()
    p = str(tmp_path / "cap.trace.json")
    with trace.capture(p):
        accl.copy(a, b, N)
    assert not trace.enabled()
    with open(p) as f:
        names = {e["name"] for e in json.load(f)["traceEvents"]
                 if e["ph"] == "X"}
    assert "accl.copy" in names
    assert "accl.bcast" not in names      # region-scoped, not global
    assert len(trace.TRACER) > 0          # ...and nothing was cleared
    trace.clear()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_size_bucket_edges():
    assert metrics.size_bucket(0) == "<=1KiB"
    assert metrics.size_bucket(1024) == "<=1KiB"
    assert metrics.size_bucket(1025) == "<=4KiB"
    assert metrics.size_bucket(1 << 20) == "<=1MiB"
    assert metrics.size_bucket(64 << 20) == "<=64MiB"
    assert metrics.size_bucket((64 << 20) + 1) == ">64MiB"


def test_registry_snapshot_delta_and_prometheus():
    reg = metrics.MetricsRegistry()
    reg.inc("x_total", 2.0, (("op", "a"),))
    reg.gauge_max("hw", 3.0)
    reg.gauge_max("hw", 1.0)           # high-water never moves down
    reg.observe("lat_seconds", 2e-6, (("op", "a"),))
    reg.observe("lat_seconds", 5e-3, (("op", "a"),))
    s1 = reg.snapshot()
    assert s1["schema"] == metrics.SCHEMA_VERSION
    assert s1["counters"]['x_total{op="a"}'] == 2.0
    assert s1["gauges"]["hw"] == 3.0
    h = s1["histograms"]['lat_seconds{op="a"}']
    assert h["count"] == 2 and h["sum"] == pytest.approx(5.002e-3)
    reg.inc("x_total", 1.0, (("op", "a"),))
    d = metrics.MetricsRegistry.delta(s1, reg.snapshot())
    assert d["counters"] == {'x_total{op="a"}': 1.0}
    assert d["histograms"] == {}
    prom = reg.to_prometheus()
    assert 'x_total{op="a"} 3' in prom
    assert 'lat_seconds_bucket{op="a",le="+Inf"} 2' in prom
    assert 'lat_seconds_count{op="a"} 2' in prom
    # cumulative buckets: the 4us edge holds the 2us sample
    assert 'lat_seconds_bucket{op="a",le="4e-06"} 1' in prom
    # valid JSON out of the box
    json.loads(reg.to_json())


def test_sendrecv_protocol_split_counters(accl):
    before = metrics.snapshot()
    a, b = _mkbuf(accl), _mkbuf(accl)
    accl.send(a, N, src=4, dst=5, tag=93)         # small -> eager
    accl.recv(b, N, src=4, dst=5, tag=93)
    d = metrics.delta(before)
    assert d["counters"].get(
        'accl_sendrecv_protocol_total{protocol="eager"}') == 1.0
    # a payload past max_eager_size takes the rendezvous tier
    big = accl.config.max_eager_size // 4 + 256
    c, e = _mkbuf(accl, big), _mkbuf(accl, big)
    before = metrics.snapshot()
    accl.send(c, big, src=4, dst=5, tag=94)
    accl.recv(e, big, src=4, dst=5, tag=94)
    d = metrics.delta(before)
    assert d["counters"].get(
        'accl_sendrecv_protocol_total{protocol="rendezvous"}') == 1.0


def test_rx_pool_highwater_gauge(accl):
    a, b = _mkbuf(accl), _mkbuf(accl)
    accl.send(a, N, src=6, dst=7, tag=95)   # parks 1 eager segment
    accl.recv(b, N, src=6, dst=7, tag=95)
    hw = metrics.snapshot()["gauges"].get(
        "accl_rx_pool_occupancy_highwater", 0)
    assert hw >= 1.0


def test_stats_embeds_metrics_delta_since_initialize(accl):
    a, b = _mkbuf(accl), _mkbuf(accl)
    accl.allreduce(a, b, N, reduceFunction.SUM)
    s = accl.stats()
    calls, _ = _op_totals(s["metrics"], "allreduce")
    assert calls >= 1.0
    assert s["metrics"]["schema"] == metrics.SCHEMA_VERSION


# ---------------------------------------------------------------------------
# logging satellites
# ---------------------------------------------------------------------------

def test_log_records_carry_process_prefix(monkeypatch):
    import logging as _logging

    from accl_tpu.utils import logging as alog

    monkeypatch.setattr(alog, "_proc_prefix", None)
    monkeypatch.setenv("ACCL_PROC_ID", "3")
    assert alog._resolve_prefix() == " p3"
    rec = _logging.LogRecord("accl_tpu.t", _logging.INFO, __file__, 1,
                             "msg", (), None)
    assert alog._ContextFilter().filter(rec) and rec.accl_ctx == " p3"
    # the installed handler's formatter renders the prefix
    alog.get_logger("t")
    h = _logging.getLogger("accl_tpu").handlers[0]
    assert " p3]" in h.format(rec)


def test_log_prefix_empty_without_context(monkeypatch):
    from accl_tpu.utils import logging as alog

    monkeypatch.setattr(alog, "_proc_prefix", None)
    monkeypatch.delenv("ACCL_PROC_ID", raising=False)
    assert alog._resolve_prefix() == ""
    # unknown is NOT cached: a context appearing later must win
    monkeypatch.setenv("ACCL_PROC_ID", "1")
    assert alog._resolve_prefix() == " p1"


def test_log_level_env_honored_after_first_call(monkeypatch):
    import logging as _logging

    from accl_tpu.utils import logging as alog

    root = _logging.getLogger("accl_tpu")
    old = root.level
    try:
        monkeypatch.setenv("ACCL_LOG_LEVEL", "DEBUG")
        alog.get_logger("t2")
        assert root.level == _logging.DEBUG
        # the satellite contract: a LATER env change takes effect too
        monkeypatch.setenv("ACCL_LOG_LEVEL", "ERROR")
        alog.get_logger("t2")
        assert root.level == _logging.ERROR
        # an unchanged env does not fight a programmatic override
        alog.set_log_level("INFO")
        alog.get_logger("t2")
        assert root.level == _logging.INFO
    finally:
        root.setLevel(old)
        alog._seen_env = alog._UNREAD

def test_request_and_match_event_counters(accl):
    """request.py + sendrecv.py wiring: request retirements count by
    terminal status with a whole-request latency histogram, and the
    matching engine counts park/match events."""
    before = metrics.snapshot()
    a, b = _mkbuf(accl), _mkbuf(accl)
    accl.send(a, N, src=0, dst=2, tag=96)      # no recv yet -> parks
    accl.recv(b, N, src=0, dst=2, tag=96)      # drains the parked send
    d = metrics.delta(before)
    c = d["counters"]
    assert c.get('accl_match_events_total{event="send_parked"}') == 1.0
    assert c.get('accl_match_events_total{event="recv_matched"}') == 1.0
    assert c.get('accl_requests_total{op="send",status="completed"}') >= 1.0
    assert c.get('accl_requests_total{op="recv",status="completed"}') >= 1.0
    assert any(k.startswith("accl_request_duration_seconds")
               for k in d["histograms"])


def test_latency_histogram_us_bucket_geometry():
    """Round-13 satellite: accl_latency_dispatch_seconds uses the
    µs-resolution bucket override (2x-spaced through the µs decade) in
    BOTH export formats, while every other histogram keeps the default
    edges — a 5 µs and a 100 µs observation must land in different
    bins (the default 4x buckets put 64-256 µs in ONE bin)."""
    metrics.observe("accl_latency_dispatch_seconds", 5e-6,
                    (("path", "test"),))
    metrics.observe("accl_latency_dispatch_seconds", 100e-6,
                    (("path", "test"),))
    snap = metrics.snapshot()
    h = snap["histograms"]['accl_latency_dispatch_seconds{path="test"}']
    assert len(h["buckets"]) == len(metrics.US_BUCKETS)
    assert set(h["buckets"]) == {repr(e) for e in metrics.US_BUCKETS}
    assert h["buckets"][repr(8e-06)] == 1      # the 5 µs observation
    assert h["buckets"][repr(0.000128)] == 1   # the 100 µs observation
    assert h["count"] == 2
    # a default-bucket histogram is untouched by the override
    metrics.observe("accl_request_duration_seconds", 5e-6,
                    (("op", "test"),))
    hd = metrics.snapshot()["histograms"][
        'accl_request_duration_seconds{op="test"}']
    assert len(hd["buckets"]) == len(metrics.BUCKETS)
    # prometheus exposition carries the µs edges cumulatively
    text = metrics.to_prometheus()
    line = [ln for ln in text.splitlines()
            if ln.startswith("accl_latency_dispatch_seconds_bucket")
            and 'path="test"' in ln and 'le="0.000128"' in ln]
    assert line and line[0].rstrip().endswith(" 2")


# ---------------------------------------------------------------------------
# round 20: fallback-counter completeness — every plan-decline site in a
# fused family counts EXACTLY once per traced program
# ---------------------------------------------------------------------------

def test_fallback_counter_counts_every_decline_site_once(monkeypatch):
    """A full backward through each fused custom-VJP family on a
    kernel-less rung hits every decline site the family owns — the
    forward, the dual dx kernel, and the fused dw kernel — and each
    counts exactly ONCE under its own op label, nothing more and
    nothing less. A missing label here means a decline went silent; a
    doubled one means a site counts per-leg instead of per-program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map
    from accl_tpu.ops import collective_alltoall as ca
    from accl_tpu.ops import collective_matmul as cm

    monkeypatch.setattr(cm, "_kernels_available", lambda: False)
    mesh = Mesh(np.array(jax.devices()[:4]), ("accl",))
    key = 'accl_cmatmul_fallback_total{op="%s",reason="no_interpret"}'

    def fb_delta(fn):
        before = metrics.snapshot()
        fn()
        d = metrics.delta(before)["counters"]
        return {k: v for k, v in d.items()
                if k.startswith("accl_cmatmul_fallback_total")}

    def grad_trace(entry, xshape, wshape, overlap=True):
        def body(xs, ws):
            return jax.grad(
                lambda args: jnp.sum(entry(args[0], args[1], "accl",
                                           None, overlap)))((xs, ws))

        f = shard_map(body, mesh=mesh, in_specs=(P("accl"), P(None)),
                      out_specs=(P("accl"), P(None)), check_vma=False)
        jax.make_jaxpr(f)(jnp.zeros(xshape, jnp.float32),
                          jnp.zeros(wshape, jnp.float32))

    # collective-matmul family: fwd + dual dx + fused dw, once each
    d = fb_delta(lambda: grad_trace(cm.all_gather_matmul,
                                    (4 * 8, 32), (32, 16)))
    assert d == {key % "allgather_matmul": 1,
                 key % "matmul_reduce_scatter": 1,
                 key % "allgather_matmul_dw": 1}
    d = fb_delta(lambda: grad_trace(cm.matmul_reduce_scatter,
                                    (4 * 8, 32), (32, 16)))
    assert d == {key % "matmul_reduce_scatter": 1,
                 key % "allgather_matmul": 1,
                 key % "matmul_reduce_scatter_dw": 1}
    # MoE a2a family: both directions share the fused-dw site
    el, C, dm, h = 2, 16, 32, 64
    d = fb_delta(lambda: grad_trace(ca.alltoall_matmul,
                                    (4 * 4 * el, C, dm), (el, dm, h)))
    assert d == {key % "alltoall_matmul": 1,
                 key % "matmul_alltoall": 1,
                 key % "moe_a2a_dw": 1}
    d = fb_delta(lambda: grad_trace(ca.matmul_alltoall,
                                    (4 * el, 4 * C, h), (el, h, dm)))
    assert d == {key % "matmul_alltoall": 1,
                 key % "alltoall_matmul": 1,
                 key % "moe_a2a_dw": 1}
    # a requested baseline counts NOTHING at any site in the family:
    # overlap=False covers fwd + dx, moe_dw_overlap=False covers dw
    saved = ca.get_dw_overlap_enabled()
    try:
        ca.set_dw_overlap_enabled(False)
        d = fb_delta(lambda: grad_trace(ca.alltoall_matmul,
                                        (4 * 4 * el, C, dm),
                                        (el, dm, h), overlap=False))
        assert d == {}
    finally:
        ca.set_dw_overlap_enabled(saved)
