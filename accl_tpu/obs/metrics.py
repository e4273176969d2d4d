"""Process-local collective metrics registry (the PERFCNT bank, made a
registry).

Counters, gauges and histograms keyed by a metric name plus a label
tuple — the canonical label set for collective calls is ``(operation,
algorithm, dtype, size-bucket)``. One :class:`MetricsRegistry` instance
(:data:`REGISTRY`) serves the whole process; the module-level helpers
(:func:`inc`, :func:`observe`, :func:`gauge_max`, :func:`note_call`)
are the hot-path entry points and check :data:`ENABLED` first — a
disabled call is one boolean read and a return, no allocation.

Metric catalog (see docs/observability.md for the field reference):

=============================================  =========  =================
name                                           kind       labels
=============================================  =========  =================
``accl_calls_total``                           counter    op, algorithm, dtype, bucket
``accl_bytes_total``                           counter    op, algorithm, dtype, bucket
``accl_dispatch_seconds``                      histogram  op (µs-resolution buckets)
``accl_span_self_seconds``                     sum+count  span (self time of each obs.trace span, recorded while tracing is on)
``accl_sendrecv_protocol_total``               counter    protocol (eager | rendezvous | eager_cross | rendezvous_cross)
``accl_requests_total``                        counter    op, status
``accl_request_duration_seconds``              histogram  op
``accl_match_events_total``                    counter    event (send/recv x matched/parked)
``accl_sched_events_total``                    counter    event (park | resume | repump)
``accl_rx_pool_occupancy_highwater``           gauge      (none)
``accl_rx_pool_exhausted_total``               counter    (none)
``accl_algorithm_fallback_total``              counter    op, algorithm
``accl_algorithm_selected_total``              counter    op, algorithm
``accl_cmatmul_fallback_total``                counter    op (cmatmul pair + ``_dw`` siblings, a2a pair, ``moe_a2a_dw``, ``moe_alltoall``, ``zero_fsdp``, ``pp_relay``, ``pp_pipeline``), reason (vmem_miss — no arm fits, n-blocked streaming included | no_interpret | threshold | geometry)
``accl_pp_relay_total``                        counter    path (fused | ppermute; pipeline relay dispatch)
``accl_kv_seconds``                            histogram  kvop (get | set | incr)
``accl_session_handshake_retries_total``       counter    (none)
``accl_fabric_moves_total``                    counter    kind (single | batch)
``accl_cmdlist_executes_total``                counter    steps
``accl_sched_plan_total``                      counter    op, shape, source
``accl_sched_plan_cache_total``                counter    event (hit | miss | evict)
``accl_select_decline_total``                  counter    op, reason
``accl_dcn_wire_bytes_total``                  counter    op, dtype, stage (pre | post: two-tier cross-slice leg bytes before/after compression, per dispatch resolution)
``accl_program_cache_total``                   counter    event (hit | miss | evict)
``accl_program_cache_size``                    gauge      (none)
``accl_latency_dispatch_seconds``              histogram  path (µs-resolution buckets; eager_send | prefill | decode | verify | handoff | migrate | publish)
``accl_flash_decode_fallback_total``           counter    reason (mode | geometry | vmem_miss)
``accl_flash_prefill_fallback_total``          counter    reason (mode | geometry | vmem_miss)
``accl_serving_tokens_total``                  counter    phase (prefill | decode | verify), accepted (true | false)
``accl_serving_sessions``                      gauge      replica, phase (prefill | decode: fleet occupancy per endpoint)
``accl_serving_handoff_bytes_total``           counter    dtype (KV page bytes shipped by handoffs/migrations, in the pool's at-rest dtype)
``accl_serving_router_declines_total``         counter    reason (no_free_slots | dead_replica | codec_mismatch | queue_full: admission-queue overflow shed)
``accl_serving_router_queue_depth``            gauge      (none; parked sessions in the bounded FIFO admission queue)
``accl_serving_router_queue_timeouts_total``   counter    (none; parked sessions expired past queue_timeout_s)
``accl_rx_pool_batch_total``                   counter    outcome (reserved | exhausted: all-or-nothing page-batch claims)
``accl_sendrecv_page_batch_total``             counter    outcome (batched | fallback: page-batch eager sends vs per-payload fallback)
``accl_fault_injected_total``                  counter    point, kind (fault.py chaos harness)
``accl_rpc_retry_total``                       counter    point (RetryPolicy absorbed transients)
``accl_peer_death_total``                      counter    proc (heartbeat-lease death verdicts)
``accl_session_epoch_total``                   counter    (none; recover() epoch bumps)
``accl_recover_total``                         counter    mode (full | shrink: survivor-subset recoveries)
``accl_comm_invalidated_total``                counter    (none; communicators spanning a dead rank)
``accl_zero_replica_total``                    counter    event (write: per replicate-PROGRAM built, trace-time like the prefetch counter; restore: per restore call)
``accl_flight_events_total``                   counter    kind (obs/flight.py ring events — one bump per recorded event; catalog in docs/observability.md)
``accl_cluster_snapshot_total``                counter    event (published: per rank snapshot pushed to the KV | merged: per rank folded by ``cluster_stats()`` | stale: per merged rank past the staleness bound)
``accl_recal_total``                           counter    outcome (applied | advisory | insufficient_data: one per ``maybe_recalibrate`` pass — obs/recal.py)
``accl_publish_total``                         counter    outcome (committed: version landed on every replica's shadow slot | stale: epoch bump / death verdict / injected fault during the landing window — NOTHING landed; models/publish.py)
``accl_publish_bytes_total``                   counter    dtype (decode-layout payload bytes of each committed publication)
``accl_publish_version``                       gauge      replica, slot (staged | live: the weight version each replica holds in its shadow vs serving slot)
=============================================  =========  =================

Export formats: :meth:`MetricsRegistry.snapshot` (flat, JSON-safe dict),
:meth:`MetricsRegistry.delta` (difference of two snapshots — what
``ACCL.stats()`` embeds, scoped since ``initialize()``),
:meth:`MetricsRegistry.to_json` and :meth:`MetricsRegistry.to_prometheus`
(text exposition format, scrape-ready).
"""
from __future__ import annotations

import json
import threading
import time
from typing import Dict, Iterable, Optional, Tuple

#: THE module-level hot-path guard. Flip with :func:`enable` /
#: :func:`disable`; every helper below checks it before touching the
#: registry, so a disabled process pays one attribute read per call site.
ENABLED = True

#: histogram bucket upper bounds in SECONDS (log-spaced, 1 µs .. 10 s);
#: one shared geometry keeps the Prometheus exposition cumulative and
#: the snapshot schema stable
BUCKETS = (1e-6, 4e-6, 16e-6, 64e-6, 256e-6, 1e-3, 4e-3, 16e-3,
           64e-3, 256e-3, 1.0, 10.0)

#: microsecond-resolution bucket geometry for the dispatch histograms:
#: the default 4x-spaced buckets put everything from 64 µs to 256 µs in
#: ONE bin — a sub-threshold op whose whole budget is tens of µs gets no
#: usable p99 out of that. 2x spacing through the µs decade, coarse tail
#: for the pathological cases.
US_BUCKETS = (1e-6, 2e-6, 4e-6, 8e-6, 16e-6, 32e-6, 64e-6, 128e-6,
              256e-6, 512e-6, 1e-3, 4e-3, 16e-3, 256e-3, 10.0)

#: span self time (:func:`note_span`): one series per span name carrying
#: only a sum and a count, so a span exit costs no bucket search
SPAN_SELF = "accl_span_self_seconds"

#: per-metric bucket geometry overrides (by metric NAME, before the
#: label block); anything absent uses :data:`BUCKETS`
_BUCKET_OVERRIDES = {
    "accl_dispatch_seconds": US_BUCKETS,
    "accl_latency_dispatch_seconds": US_BUCKETS,
    SPAN_SELF: (),
}


def _buckets_for(key: str):
    name, _, _ = key.partition("{")
    return _BUCKET_OVERRIDES.get(name, BUCKETS)

_KiB = 1024


def size_bucket(nbytes: int) -> str:
    """Power-of-four byte bucket label: '<=1KiB', '<=4KiB', ... '>64MiB'.
    Coarse on purpose — the label cardinality is what bounds registry
    growth (ops x algos x dtypes x buckets)."""
    edge = _KiB
    while edge < nbytes:
        if edge >= 64 * _KiB * _KiB:
            return ">64MiB"
        edge *= 4
    if edge >= _KiB * _KiB:
        return f"<={edge // (_KiB * _KiB)}MiB"
    return f"<={edge // _KiB}KiB"


def _label_str(labels: Tuple[Tuple[str, str], ...]) -> str:
    if not labels:
        return ""
    return "{" + ",".join(f'{k}="{v}"' for k, v in labels) + "}"


class MetricsRegistry:
    """Thread-safe counters / gauges / histograms with flat string keys.

    Keys are the Prometheus series identity ``name{label="value",...}``
    so snapshots are JSON-safe by construction and the exposition format
    is a straight dump of the tables.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, float] = {}
        # gauges hold (value); high-water gauges only move up via gauge_max
        self._gauges: Dict[str, float] = {}
        # histograms hold [bucket_counts..., sum, count]
        self._hists: Dict[str, list] = {}

    # -- write side --------------------------------------------------------

    def inc(self, name: str, value: float = 1.0,
            labels: Tuple[Tuple[str, str], ...] = ()) -> None:
        key = name + _label_str(labels)
        with self._lock:
            self._counters[key] = self._counters.get(key, 0.0) + value

    def set_gauge(self, name: str, value: float,
                  labels: Tuple[Tuple[str, str], ...] = ()) -> None:
        key = name + _label_str(labels)
        with self._lock:
            self._gauges[key] = value

    def gauge_max(self, name: str, value: float,
                  labels: Tuple[Tuple[str, str], ...] = ()) -> None:
        """High-water gauge: only ever moves up (rx-pool occupancy)."""
        key = name + _label_str(labels)
        with self._lock:
            if value > self._gauges.get(key, float("-inf")):
                self._gauges[key] = value

    def observe(self, name: str, value: float,
                labels: Tuple[Tuple[str, str], ...] = ()) -> None:
        key = name + _label_str(labels)
        edges = _BUCKET_OVERRIDES.get(name, BUCKETS)
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = [0] * len(edges) + [0.0, 0]
                self._hists[key] = h
            for i, edge in enumerate(edges):
                if value <= edge:
                    h[i] += 1
                    break
            h[-2] += value
            h[-1] += 1

    def add_sum(self, key: str, value: float) -> None:
        """One sample into a bucketless series (sum and count) whose key
        the caller built once: the span self-time hot path."""
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = [0.0, 0]
            h[0] += value
            h[1] += 1

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()

    # -- read side ---------------------------------------------------------

    def snapshot(self) -> dict:
        """Flat, JSON-serializable copy of every table. Histograms export
        as ``{"buckets": {le: n}, "sum": s, "count": n}``."""
        with self._lock:
            hists = {
                k: {"buckets": {repr(e): h[i]
                                for i, e in enumerate(_buckets_for(k))},
                    "sum": h[-2], "count": h[-1]}
                for k, h in self._hists.items()
            }
            return {"schema": SCHEMA_VERSION,
                    "counters": dict(self._counters),
                    "gauges": dict(self._gauges),
                    "histograms": hists}

    @staticmethod
    def delta(since: dict, now: Optional[dict] = None) -> dict:
        """Difference of two :meth:`snapshot` dicts (``now`` defaults to a
        fresh snapshot of :data:`REGISTRY`): counters and histogram
        sums/counts subtract; gauges report their CURRENT value (a
        high-water mark has no meaningful difference)."""
        if now is None:
            now = REGISTRY.snapshot()
        prev_c = since.get("counters", {})
        counters = {k: v - prev_c.get(k, 0.0)
                    for k, v in now.get("counters", {}).items()
                    if v != prev_c.get(k, 0.0)}
        prev_h = since.get("histograms", {})
        hists = {}
        for k, h in now.get("histograms", {}).items():
            p = prev_h.get(k, {"buckets": {}, "sum": 0.0, "count": 0})
            if h["count"] == p["count"]:
                continue
            hists[k] = {
                "buckets": {le: n - p["buckets"].get(le, 0)
                            for le, n in h["buckets"].items()},
                "sum": h["sum"] - p["sum"],
                "count": h["count"] - p["count"],
            }
        return {"schema": SCHEMA_VERSION,
                "counters": counters,
                "gauges": dict(now.get("gauges", {})),
                "histograms": hists}

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.snapshot(), indent=indent, sort_keys=True)

    def to_prometheus(self) -> str:
        """Prometheus text exposition (0.0.4): counters and gauges dump
        directly; histograms expand to cumulative ``_bucket`` series plus
        ``_sum``/``_count``, with the standard ``+Inf`` bucket."""
        lines = []
        with self._lock:
            for k in sorted(self._counters):
                lines.append(f"{k} {self._counters[k]:g}")
            for k in sorted(self._gauges):
                lines.append(f"{k} {self._gauges[k]:g}")
            for k in sorted(self._hists):
                h = self._hists[k]
                name, _, labels = k.partition("{")
                labels = ("{" + labels) if labels else ""
                inner = labels[1:-1] if labels else ""
                cum = 0
                for i, edge in enumerate(_buckets_for(k)):
                    cum += h[i]
                    sep = "," if inner else ""
                    lines.append(
                        f'{name}_bucket{{{inner}{sep}le="{edge:g}"}} {cum}')
                sep = "," if inner else ""
                lines.append(f'{name}_bucket{{{inner}{sep}le="+Inf"}} '
                             f"{h[-1]}")
                lines.append(f"{name}_sum{labels} {h[-2]:g}")
                lines.append(f"{name}_count{labels} {h[-1]}")
        return "\n".join(lines) + ("\n" if lines else "")


#: snapshot/export schema version — embedded in BENCH artifacts and
#: ``ACCL.stats()`` so downstream tooling can detect drift
SCHEMA_VERSION = 1

#: the process-wide registry every helper below writes into
REGISTRY = MetricsRegistry()

#: recalibration sample hook (obs/recal.py installs it when
#: ``sched_online_recal`` arms): called as ``(op_name, nbytes,
#: seconds)`` for every timed :func:`note_call`. None when disarmed —
#: the default hot path pays one ``is None`` read.
RECAL_NOTE = None

#: flight-recorder dispatch hook (obs/flight.py installs it at import):
#: called as ``(op_name, algorithm, size_bucket)`` for every
#: :func:`note_call`, so the flight ring sees op dispatches with their
#: resolved algorithm without a per-op hook in accl.py.
FLIGHT_NOTE = None


def enable() -> None:
    global ENABLED
    ENABLED = True


def disable() -> None:
    global ENABLED
    ENABLED = False


def enabled() -> bool:
    return ENABLED


def reset() -> None:
    REGISTRY.reset()


def snapshot() -> dict:
    return REGISTRY.snapshot()


def delta(since: dict) -> dict:
    return MetricsRegistry.delta(since)


def to_prometheus() -> str:
    return REGISTRY.to_prometheus()


# ---------------------------------------------------------------------------
# hot-path helpers: every one checks ENABLED first and allocates nothing
# when disabled
# ---------------------------------------------------------------------------

def tick() -> float:
    """Start-of-dispatch timestamp; 0.0 (no clock read) when disabled."""
    if not ENABLED:
        return 0.0
    return time.perf_counter()


def note_call(op, nbytes: int, dtype=None, key: Optional[Iterable] = None,
              t0: float = 0.0) -> None:
    """One collective/primitive host call: bumps ``accl_calls_total`` and
    ``accl_bytes_total`` under (op, algorithm, dtype, size-bucket) and,
    when ``t0`` came from :func:`tick`, observes the host dispatch
    latency. ``key`` is the resolved program-cache key — the algorithm
    label is read off it (the Algorithm member the ``_spec_*`` builders
    embed) so selection is recorded exactly as dispatched."""
    if not ENABLED:
        return
    algo = "-"
    if key is not None:
        for part in key:
            # Algorithm enum members carry .value strings ('xla', 'ring'…)
            v = getattr(part, "value", None)
            if v is not None and part.__class__.__name__ == "Algorithm":
                algo = v
                break
    op_name = getattr(op, "name", str(op))
    bucket = size_bucket(int(nbytes))
    labels = (("op", op_name),
              ("algorithm", algo),
              ("dtype", getattr(dtype, "name", str(dtype))),
              ("bucket", bucket))
    REGISTRY.inc("accl_calls_total", 1.0, labels)
    REGISTRY.inc("accl_bytes_total", float(nbytes), labels)
    if FLIGHT_NOTE is not None:
        FLIGHT_NOTE(op_name, algo, bucket)
    if t0:
        dt = time.perf_counter() - t0
        REGISTRY.observe("accl_dispatch_seconds", dt,
                         (("op", op_name),))
        if RECAL_NOTE is not None:
            RECAL_NOTE(op_name, int(nbytes), dt)


def note_latency_dispatch(path: str, t0: float) -> None:
    """One sub-threshold (latency-tier) dispatch: observes host API
    entry → posted/launched into ``accl_latency_dispatch_seconds{path}``
    — the µs-resolution histogram (:data:`US_BUCKETS`; the default
    4x-spaced buckets cannot resolve a p99 for ops whose whole budget is
    tens of µs). ``path`` names the fast path that ran (``eager_send`` —
    the single-segment eager fast path; ``prefill`` /
    ``decode`` / ``verify`` — the serving tier's step-dispatch phases,
    observed by the ``models.decode`` step wrappers; ``handoff`` /
    ``migrate`` — the router's page transfers; ``publish`` — one full
    weight publication, re-shard through landing). No-op when
    disabled or when ``t0`` is 0.0 (the disabled :func:`tick`
    sentinel)."""
    if not ENABLED or not t0:
        return
    REGISTRY.observe("accl_latency_dispatch_seconds",
                     time.perf_counter() - t0, (("path", path),))


_SPAN_KEYS: Dict[str, str] = {}


def note_span(span: str, seconds: float) -> None:
    """One ``obs.trace`` span's self time into
    ``accl_span_self_seconds{span}`` (sum and count). The tracer calls
    this at every span exit while tracing is on; the series key is built
    once per span name."""
    if not ENABLED:
        return
    key = _SPAN_KEYS.get(span)
    if key is None:
        key = _SPAN_KEYS[span] = SPAN_SELF + _label_str((("span", span),))
    REGISTRY.add_sum(key, seconds)


def note_zero_prefetch(event: str, count: int = 1) -> None:
    """Layerwise-ZeRO prefetch accounting: bump
    ``accl_zero_prefetch_total{event}`` — ``event`` is ``"hit"`` (a
    layer's attention-bucket gather issued under the PREVIOUS layer's
    compute, the double-buffered schedule) or ``"decline"`` (prefetch
    disabled: the gather serializes behind the layer boundary). Counted
    at trace/build time like the cmatmul fallback counters, so the
    count is per compiled program, not per step."""
    if not ENABLED:
        return
    REGISTRY.inc("accl_zero_prefetch_total", float(count),
                 (("event", event),))


def inc(name: str, value: float = 1.0,
        labels: Tuple[Tuple[str, str], ...] = ()) -> None:
    if not ENABLED:
        return
    REGISTRY.inc(name, value, labels)


def observe(name: str, value: float,
            labels: Tuple[Tuple[str, str], ...] = ()) -> None:
    if not ENABLED:
        return
    REGISTRY.observe(name, value, labels)


def gauge_max(name: str, value: float,
              labels: Tuple[Tuple[str, str], ...] = ()) -> None:
    if not ENABLED:
        return
    REGISTRY.gauge_max(name, value, labels)


def set_gauge(name: str, value: float,
              labels: Tuple[Tuple[str, str], ...] = ()) -> None:
    if not ENABLED:
        return
    REGISTRY.set_gauge(name, value, labels)
