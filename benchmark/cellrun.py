"""One run of one cell: set-up, the measured window, the check, the
metrics. ``run.py`` is the command line around :func:`run_cell`.

Set-up opens an ``accl_tpu.ACCL()`` session on the cell's chips, makes
every input buffer on the device in one jitted call from the seed, and
warms up each (op, size) pair of the traffic mix twice, on buffers of
its own. The window's output buffers are then made anew, filled with
NaN, so that a call that writes nothing is seen. The window issues
the rounds of calls that the mix's generator draws from the seed, from
one caller, each call synchronous and timed from API entry (or from its
arrival, in open loop) to its output being ready on the device, and
closes at the first round boundary after ``seconds``. It keeps the outputs of one
call per pair, drawn from the seed; once the window has closed and
the peak memory has been read, those are compared with the plain
reference (``reference.py``).
"""
from __future__ import annotations

import collections
import gc
import math
import shutil
import sys
import tempfile
import time
import traceback
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from benchmark import generators, ops, reference
from benchmark.spec import Cell

#: inputs are whole multiples of 2**-10 below 2**10 in magnitude: every
#: partial sum of four of them is exact in float32, in any order, and
#: most of them are not bfloat16 numbers
GRID_BITS = 10
MAGNITUDE_BITS = 10

Pair = Tuple[str, int]
_MISSES = 'accl_program_cache_total{event="miss"}'


def _log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def _key(seed: int):
    """A PRNG key from any non-negative whole number (64 bits and more)."""
    import jax

    words = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0]) >> 1),
                              int(words[1]) >> 1)


def make_inputs(seed: int, elems: List[int], n: int, sharding):
    """One input array per element count, ``(n, elems)`` sharded one row
    per rank, made on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    lim = 1 << (GRID_BITS + MAGNITUDE_BITS)
    scale = np.float32(2.0 ** -GRID_BITS)

    def make(key):
        keys = jax.random.split(key, len(elems))
        return tuple(jax.random.randint(k, (n, e), -lim, lim, jnp.int32)
                     .astype(jnp.float32) * scale
                     for k, e in zip(keys, elems))

    made = jax.jit(make, out_shardings=tuple(sharding for _ in elems))(
        _key(seed))
    return dict(zip(elems, made))


def make_poison(elems: List[int], n: int, sharding):
    """NaN-filled ``(n, elems)`` arrays, one per entry, in one call."""
    import jax
    import jax.numpy as jnp

    shapes = tuple(elems)

    def make():
        return tuple(jnp.full((n, e), jnp.nan, jnp.float32) for e in shapes)

    return list(jax.jit(make, out_shardings=tuple(
        sharding for _ in shapes))())


class CompileCounter:
    """Counts JAX traces and backend compiles while armed."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if self.armed and event in self.EVENTS:
            self.count += 1


class _GcPauses:
    """Collections by generation and their total seconds, for the log."""

    def __init__(self):
        self.count = [0, 0, 0]
        self.seconds = 0.0
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t0
            self.count[info["generation"]] += 1


class Session:
    """The system under test: an ACCL session and the cell's buffers."""

    def __init__(self, cell: Cell, seed: int, devices, call: Callable):
        import jax

        import accl_tpu

        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.call_fn = call
        self.compile_counter = CompileCounter()
        if len(devices) == jax.device_count():
            self.acc = accl_tpu.ACCL()   # rank order as ACCL() builds it
        else:
            self.acc = accl_tpu.ACCL(devices=list(devices))
        self.n = self.acc.world_size
        if self.n != cell.chips:
            raise RuntimeError(f"session has {self.n} ranks, the cell "
                               f"asks for {cell.chips}")
        self.sharding = self.acc.global_comm().sharding()
        self.dtype = getattr(accl_tpu.dataType, self.config["dtype"])
        self.itemsize = np.dtype(self.config["dtype"]).itemsize
        self.generator = generators.load(self.traffic, cell.root)
        self.pairs: List[Pair] = self.generator.pairs(self.traffic)
        self.op = {name: ops.load(name, cell.root)
                   for name in {p[0] for p in self.pairs}}
        self.geom = {p: ops.geometry(self.op[p[0]], p[1], self.traffic,
                                     self.n, self.itemsize)
                     for p in self.pairs}
        in_elems = sorted({g.in_elems for g in self.geom.values()})
        self.inputs = make_inputs(seed, in_elems, self.n, self.sharding)
        self.in_bufs = {e: self._buffer(e, self.inputs[e])
                        for e in in_elems}
        self.out_bufs: Dict[Pair, object] = {}

    def reseed(self, seed: int) -> None:
        """New inputs from ``seed`` and fresh output buffers, on the same
        session (its programs stay compiled)."""
        in_elems = sorted(self.inputs)
        self.out_bufs, self.in_bufs, self.inputs = {}, {}, {}
        self.inputs = make_inputs(seed, in_elems, self.n, self.sharding)
        self.in_bufs = {e: self._buffer(e, self.inputs[e])
                        for e in in_elems}
        self.fresh_outputs()

    def _buffer(self, elems: int, array):
        buf = self.acc.create_buffer(elems, self.dtype)
        buf.device_store(array)
        return buf

    def fresh_outputs(self) -> None:
        """Output buffers for every pair, NaN on the device (bcast's
        in-place buffer starts as its input)."""
        self.out_bufs = {}
        need = [p for p in self.pairs if not self.op[p[0]].inplace]
        poison = make_poison([self.geom[p].out_elems for p in need], self.n,
                             self.sharding)
        for p, arr in zip(need, poison):
            self.out_bufs[p] = self._buffer(self.geom[p].out_elems, arr)
        for p in self.pairs:
            if self.op[p[0]].inplace:
                e = self.geom[p].in_elems
                self.out_bufs[p] = self._buffer(e, self.inputs[e])

    def issue(self, pair: Pair):
        """One synchronous call; returns its output array, ready."""
        import jax

        op = self.op[pair[0]]
        g = self.geom[pair]
        dst = self.out_bufs[pair]
        src = dst if op.inplace else self.in_bufs[g.in_elems]
        self.call_fn(self.acc, op, src, dst, g.count, self.config)
        out = dst.device_view()
        jax.block_until_ready(out)
        return out

    def close(self) -> None:
        self.out_bufs = {}
        self.in_bufs = {}
        self.acc.deinit()


def _peak_bytes(devices) -> Optional[int]:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks) if any(peaks) else None


def _algorithms(delta: dict) -> List[str]:
    """(op, algorithm, size bucket) the library dispatched, from
    ``accl_calls_total``."""
    out = []
    for key, v in sorted(delta.get("counters", {}).items()):
        if key.startswith("accl_calls_total{"):
            out.append(f"{key[len('accl_calls_total'):]}={v:g}")
    return out


def _mean_us(keyed) -> str:
    """Mean call time in us per key, from (key, seconds) pairs: sums over
    many calls, for the log only."""
    tot: Dict[object, List[float]] = {}
    for k, s in keyed:
        t = tot.setdefault(k, [0.0, 0])
        t[0] += s
        t[1] += 1
    return " ".join(f"{k}={v[0] / v[1] * 1e6:.1f}" for k, v in tot.items())


class Window:
    """What the measured window recorded."""

    def __init__(self):
        self.calls: List[Tuple[Pair, float]] = []
        self.failed = 0
        self.rounds = 0
        #: one output per pair, drawn from the seed (reservoir of 1)
        self.kept: Dict[Pair, object] = {}
        self.t_first = 0.0
        self.seconds = 0.0
        self.compiles = 0
        self.delta: dict = {}
        self.trace_dir: Optional[str] = None
        self.gc: Optional["_GcPauses"] = None


def setup(cell: Cell, seed: int, devices, call: Callable = ops.call
          ) -> Session:
    """The session, its inputs from ``seed``, every pair warmed up twice
    on buffers of its own, and the window's output buffers (NaN)."""
    sess = Session(cell, seed, devices, call)
    sess.fresh_outputs()
    for _ in range(2):
        for p in sess.pairs:
            sess.issue(p)
    sess.fresh_outputs()
    # what set-up left behind (a cold run's compile artifacts above all)
    # stays out of the window's garbage collections: without this a
    # run that compiled read a third slower than one that loaded
    gc.collect()
    gc.freeze()
    return sess


def measure(sess: Session, seed: int, seconds: float, trace: bool
            ) -> Window:
    """Rounds of calls until the first round boundary after
    ``seconds``; keeps a sample of outputs drawn from ``seed``. A call
    that carries its arrival (``(op, size, at)``) waits for it and is
    timed from it."""
    import jax

    from accl_tpu.obs import metrics as accl_metrics
    from accl_tpu.obs import trace as accl_trace
    from benchmark import trace_reduce

    jax.block_until_ready([b.device_view() for b in sess.out_bufs.values()])
    w = Window()
    seen: Dict[Pair, int] = collections.Counter()
    keep_rng = np.random.default_rng([seed, 1])
    before = accl_metrics.snapshot()
    if trace:
        w.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        accl_trace.start()
        jax.profiler.start_trace(w.trace_dir, profiler_options=opts)
    span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
    counter = sess.compile_counter
    counter.count = 0
    counter.armed = True
    pauses = _GcPauses()
    gc.callbacks.append(pauses)
    w.t_first = time.perf_counter()
    span.__enter__()
    for rnd in sess.generator.rounds(sess.traffic, seed):
        for item in rnd:
            p = tuple(item[:2])
            t0 = time.perf_counter()
            if len(item) > 2:
                t0 = max(t0, w.t_first + item[2])
                time.sleep(max(0.0, t0 - time.perf_counter()))
            try:
                out = sess.issue(p)
            except Exception:  # noqa: BLE001 — counted, the loop goes on
                traceback.print_exc()
                w.failed += 1
                continue
            w.calls.append((p, time.perf_counter() - t0))
            # a uniform sample of one call per pair: keep the k-th call
            # of the pair with chance 1/k
            seen[p] += 1
            if seen[p] == 1 or keep_rng.random() < 1.0 / seen[p]:
                w.kept[p] = out
        w.rounds += 1
        if time.perf_counter() - w.t_first >= seconds:
            break
    span.__exit__(None, None, None)
    w.seconds = time.perf_counter() - w.t_first
    counter.armed = False
    gc.callbacks.remove(pauses)
    w.compiles = counter.count
    w.gc = pauses
    if trace:
        jax.profiler.stop_trace()
        accl_trace.stop()
    w.delta = accl_metrics.delta(before)
    return w


def check(w: Window, inputs: dict, geom: dict, op: dict, n: int,
          config: dict) -> Tuple[float, int, int]:
    """(widest gap, elements compared, calls compared) of the kept
    outputs against the plain reference; empties ``w.kept``."""
    worst, compared, checked = 0.0, 0, 0
    for p in list(w.kept):
        g = geom[p]
        gap, m = reference.check(op[p[0]], g.count, n, config,
                                 inputs[g.in_elems], w.kept.pop(p))
        worst = max(worst, gap)
        compared += m
        checked += 1
    return worst, compared, checked


def checks_of(worst: float, checked: int, failed: int, pairs: int,
              config: dict) -> dict:
    return {
        "max_abs_gap": {"value": worst,
                        "limit": float(config["limits"]["max_abs_gap"])},
        "pairs_checked": {"value": checked, "limit": pairs},
        "failed_calls": {"value": failed, "limit": 0},
    }


def is_correct(checks: dict) -> bool:
    gap = checks["max_abs_gap"]
    return (gap["value"] <= gap["limit"] and not math.isnan(gap["value"])
            and checks["pairs_checked"]["value"]
            >= checks["pairs_checked"]["limit"]
            and checks["failed_calls"]["value"] == 0)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_process: float, call: Callable = ops.call) -> dict:
    """Set up, measure, check; return the result line as a dict."""
    import jax

    from benchmark import peaks as peaks_mod, trace_reduce

    sess = setup(cell, seed, devices, call)
    w = measure(sess, seed, seconds, trace)
    memory_peak = _peak_bytes(devices)
    setup_s = w.t_first - t_process
    n = sess.n
    _log(f"window: {w.rounds} rounds, {len(w.calls)} calls, {w.failed} "
         f"failed, {w.seconds:.6f} s; set-up {setup_s:.6f} s; compiles in "
         f"window {w.compiles}; program-cache misses in window "
         f"{w.delta['counters'].get(_MISSES, 0):g}")
    for line in _algorithms(w.delta):
        _log("dispatched", line)
    _log(f"garbage collections in window by generation {w.gc.count}, "
         f"{w.gc.seconds:.6f} s")
    _log("mean call us by op:", _mean_us((p[0], s) for p, s in w.calls))
    _log("mean call us by fifth of the window:", _mean_us(
        (5 * i // len(w.calls), s) for i, (_, s) in enumerate(w.calls)))

    # the check: after the window, with the session's state freed
    inputs, geom, op = sess.inputs, sess.geom, sess.op
    sess.close()
    del sess
    t_check = time.perf_counter()
    worst, compared, checked = check(w, inputs, geom, op, n, cell.config)
    _log(f"check: {checked} calls, {compared} elements, "
         f"{time.perf_counter() - t_check:.3f} s")

    dev0 = devices[0]
    peak = peaks_mod.peaks(dev0.device_kind) if dev0.platform == "tpu" \
        else None
    summary = None
    if trace:
        summary = trace_reduce.reduce_dir(w.trace_dir, n)
        shutil.rmtree(w.trace_dir, ignore_errors=True)
    itemsize = np.dtype(cell.config["dtype"]).itemsize
    run = {
        "calls": [(p, geom[p], s) for p, s in w.calls],
        "setup_s": setup_s, "window_s": w.seconds, "n": n,
        "itemsize": itemsize, "peak": peak, "metrics_delta": w.delta,
        "trace": summary, "config": cell.config, "traffic": cell.traffic,
    }
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = m.reader(run)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}

    checks = checks_of(worst, checked, w.failed, len(geom), cell.config)
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": memory_peak}
    result = {"correct": is_correct(checks),
              "attempted": len(w.calls) + w.failed, "failed": w.failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_mean_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["window"] = {"rounds": w.rounds, "calls": len(w.calls),
                        "seconds": w.seconds, "compiles": w.compiles}
    result["checks"] = checks
    return result


def print_checks(checks: dict) -> None:
    """The numbers compared, each beside its limit: the last lines on
    standard error."""
    for name, c in checks.items():
        _log(f"check {name} {c['value']!r} limit {c['limit']!r}")
