"""The readers of the library's span self-time series
(``accl_span_self_seconds{span}``) on a made-up ``metrics_delta``, and on
one from a program that records no such series."""
import pytest

from benchmark import spec


def _series(span, seconds, count):
    return (f'accl_span_self_seconds{{span="{span}"}}',
            {"buckets": {}, "sum": seconds, "count": count})


def _run(hists, calls=4):
    return {"calls": [None] * calls,
            "metrics_delta": {"counters": {}, "gauges": {},
                              "histograms": dict(hists)}}


RUN = _run([
    _series("accl.allreduce", 40e-6, 2),
    _series("accl.send", 10e-6, 1),
    _series("accl.recv", 10e-6, 1),
    _series("accl.plan", 30e-6, 2),
    _series("accl.launch", 60e-6, 3),
    _series("accl.match", 8e-6, 2),
    _series("req.allreduce.wait", 4e-6, 2),
    _series("req.block", 400e-6, 4),
    _series("req.finalize", 40e-6, 4),
    ('accl_dispatch_seconds{op="allreduce"}',
     {"buckets": {}, "sum": 1.0, "count": 2}),
])


@pytest.mark.parametrize("name, want", [
    ("plan_us.small", 15.0),
    ("plan_us.large", 15.0),
    ("launch_us.small", 20.0),
    ("match_us.small", 4.0),
    ("wait_us.small", 100.0),
    ("wait_us.large", 100.0),
    # every span's self time but req.block's, over the 4 calls: the
    # dispatch histogram is no span and is left out too
    ("host_us.small", (40 + 10 + 10 + 30 + 60 + 8 + 4 + 40) / 4),
    ("host_us.large", (40 + 10 + 10 + 30 + 60 + 8 + 4 + 40) / 4),
])
def test_reader_on_made_up_series(name, want):
    assert spec.load_reader(spec.ROOT, name)(RUN) == pytest.approx(want)


@pytest.mark.parametrize("family", ["plan_us", "launch_us", "match_us",
                                    "wait_us", "host_us"])
def test_reader_without_the_series_reads_nothing(family):
    """A program that records no span series (tracing off, or a commit
    before the series existed) leaves the metric out of the line."""
    read = spec.load_reader(spec.ROOT, family + ".small")
    bare = _run([('accl_dispatch_seconds{op="allreduce"}',
                  {"buckets": {}, "sum": 1.0, "count": 2})])
    assert read(bare) is None
    assert read(_run([])) is None


def test_host_us_needs_calls():
    assert spec.load_reader(spec.ROOT, "host_us.small")(
        _run(RUN["metrics_delta"]["histograms"].items(), calls=0)) is None


def test_every_span_metric_is_in_the_benchmark():
    bench = spec.load_benchmark()
    names = {m["name"]: m for m in bench["per_layer"]}
    for n in ("plan_us.small", "plan_us.large", "launch_us.small",
              "match_us.small", "wait_us.small", "wait_us.large",
              "host_us.small", "host_us.large"):
        assert names[n]["source"] == "program_counter"
        assert names[n]["workloads"] == (["small_msg.1chip"]
                                         if n.endswith(".small")
                                         else ["grad_sync.4chip"])
