"""Cost of one ``obs.trace`` span site, on this host's CPU.

    JAX_PLATFORMS=cpu python tools/span_cost.py

Prints one JSON line, in us per span (enter and exit of an empty body):
``off`` (tracing off: the null context), ``on`` (tracing on: the
annotation, the event tuple and the self-time counter) and
``on_profiling`` (tracing on inside a ``jax.profiler`` capture with the
benchmark's options, no Python tracer, where the annotation records
too). Each is the median of 7 rounds of 20,000
spans, nested two deep as on the library's path, less the same loop
with no span.
"""
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from accl_tpu.obs import trace  # noqa: E402

N, ROUNDS = 20_000, 7


def per_span_us() -> float:
    out = []
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        for _ in range(N // 2):
            pass
        t1 = time.perf_counter()
        for _ in range(N // 2):
            with trace.root("accl.bench"):
                with trace.span("accl.plan"):
                    pass
        out.append(((time.perf_counter() - t1) - (t1 - t0)) / N * 1e6)
        trace.clear()
    return statistics.median(out)


def main() -> None:
    import jax

    trace.stop()
    off = per_span_us()
    trace.start()
    on = per_span_us()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        profiling = per_span_us()
        jax.profiler.stop_trace()
    trace.stop()
    print(json.dumps({"off": off, "on": on, "on_profiling": profiling,
                      "platform": jax.devices()[0].platform}))


if __name__ == "__main__":
    main()
