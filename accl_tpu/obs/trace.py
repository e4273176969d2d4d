"""Host-side span tracing: one span tree per ACCL call.

The timeline tier above the per-call counter (SURVEY.md §5: PERFCNT gives
per-call cycles, xprof gives the device timeline — THIS gives the host
protocol timeline). Every session call opens one tree::

    accl.<op>            root: API entry to return (a sync call's wait too)
      accl.plan          _arith and the _spec_* schedule selection and key
      accl.match         send/recv matcher work: capacity, rx pool, post
        accl.launch      a delivery the post triggered
      accl.launch        program-cache lookup, program call, astype, store
        accl.compile     a program-cache miss: the build and first call
      req.<op>.wait      the request's wait (sync calls; async waits later)
        req.block        block_until_ready, or the external-fulfilment wait
        req.finalize     finalizer, completion counters, queue retirement

beside the cross-process protocol phases (``xsend.*``/``xrecv.*``), pair
moves (``fabric.*``), ``cmdlist.execute`` and bench/autotune stages.
While tracing is on, each span is recorded three ways:

- inside a profiler capture, a ``jax.profiler.TraceAnnotation`` of the
  same name, so the span sits on the device trace's host plane;
- one Chrome trace event (``{"traceEvents": [...]}``, loads standalone
  in Perfetto and ``chrome://tracing``); ``pid`` is the controller's
  process index (``ACCL_PROC_ID`` under the launcher, the OS pid
  otherwise), ``tid`` a dense thread id;
- its self time — its duration minus its children's on the same thread
  — into ``accl_span_self_seconds{span}`` (a sum and a count) of the
  metrics registry, where the benchmark's per-layer metrics read it.

Call id: a root span draws one id from a process-wide count. The root
and ``req.<op>.wait`` carry it as annotation metadata and Chrome ``args``
(``call``), and so does the call's ``Request``, so an async wait made
later names its call (:func:`current_call`).

Clock: timestamps and durations are ``time.time_ns`` (CLOCK_REALTIME),
the clock the profiler stamps host events with. An xprof capture's host
event sits at its Task Environment plane's ``profile_start_time`` plus
the event's offset, so a host trace file and a capture of one region
line up with no sync mark.

Disabled by default: :func:`start` flips the one module-level flag; a
disabled :func:`span` or :func:`root` returns a shared null context —
one boolean read, no clock read, no allocation.

Multi-rank merge: ``python -m accl_tpu.obs.trace --merge out.json
rank*.json`` stitches per-rank trace files into ONE time-aligned
timeline. Alignment rides the epoch-entry KV handshake: the fabric
calls :func:`sync_mark` as each rank exits the epoch barrier, which
embeds an ``accl_sync`` record (label, ts, wall time) in that rank's
written trace; the merger shifts each rank's timestamps so the latest
common sync label coincides across files (barrier exits are
simultaneous to within the KV round-trip — the offset estimate's honest
error bar, reported per rank in the merged metadata). Missing or
corrupt inputs are reported and skipped; unknown arguments exit rc=2
with usage.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import os
import threading
import time
from typing import Dict, List, Optional

from . import metrics as _metrics

#: THE module-level hot-path guard; flipped by :func:`start` / :func:`stop`
ENABLED = False


class _Null:
    """The context of a disabled span site: stateless, so one shared
    instance serves every site and thread (``__exit__`` takes its three
    arguments by position: no tuple packed per site)."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, exc_type, exc, tb):
        return None


_NULL = _Null()

#: the profiler's host clock, in ns (module docstring: Clock)
_now = time.time_ns

#: call ids, one per root span; dense thread ids (``next`` on a count is
#: atomic under the GIL)
_CALL_IDS = itertools.count(1)
_TIDS = itertools.count()

_UNRESOLVED = object()
#: ``jax.profiler.TraceAnnotation``, resolved by the first span (None
#: where the profiler cannot be imported)
_ANNOTATION = _UNRESOLVED
_PID: Optional[int] = None


def _annotation_class():
    global _ANNOTATION
    try:
        from jax.profiler import TraceAnnotation as ann
    except ImportError:   # stripped profiler builds: JSON and counters only
        ann = None
    _ANNOTATION = ann
    return ann


def _pid() -> int:
    """Track identity, read once: the launcher's process id when running
    multi-controller (stable across hosts, 0-based — one track per rank
    group), else the OS pid. Never touches the JAX backend."""
    global _PID
    if _PID is None:
        pid = os.getpid()
        env = os.environ.get("ACCL_PROC_ID")
        if env is not None:
            try:
                pid = int(env)
            except ValueError:
                pass
        _PID = pid
    return _PID


class _Thread(threading.local):
    """Per-thread span state: the open spans (innermost last), the call
    id of the innermost open root, and the Chrome-trace tid."""

    def __init__(self):
        self.stack: List["_Span"] = []
        self.call: Optional[int] = None
        self.tid = next(_TIDS)


_THREAD = _Thread()


class _Span:
    """One span while tracing is on; :func:`span` and :func:`root` hand
    it out as the context manager."""

    __slots__ = ("name", "cat", "args", "call", "root", "t0", "child",
                 "ann", "stack", "prev")

    def __init__(self, name: str, cat: str, args: Optional[dict],
                 call: Optional[int], root: bool):
        self.name = name
        self.cat = cat
        self.args = args
        self.call = call
        self.root = root

    def __enter__(self):
        th = _THREAD
        if self.root:
            self.prev = th.call
            th.call = self.call
        ann = _ANNOTATION
        if ann is _UNRESOLVED:
            ann = _annotation_class()
        if ann is not None and ann.is_enabled():
            # a profiler is capturing: the span goes on its host plane
            ann = (ann(self.name) if self.call is None
                   else ann(self.name, call=self.call))
            ann.__enter__()
        else:
            ann = None
        self.ann = ann
        self.child = 0
        self.stack = th.stack
        self.stack.append(self)
        self.t0 = _now()
        return self

    def __exit__(self, *exc) -> bool:
        dur = _now() - self.t0
        stack = self.stack
        stack.pop()
        if stack:
            stack[-1].child += dur
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        th = _THREAD
        if self.root:
            th.call = self.prev
        TRACER._events.append((self.name, self.cat, self.t0, dur, th.tid,
                               self.call, self.args))
        _metrics.note_span(self.name, (dur - self.child) * 1e-9)
        return False


_PLAIN = (int, float, bool, str, type(None))


class SpanTracer:
    """The recorded events and sync marks, and their Chrome-trace
    export. An event is one tuple ``(name, cat, ts_ns, dur_ns, tid,
    call, args)`` (``dur_ns`` None for an instant); the dicts of the
    Chrome format are built only at export."""

    def __init__(self):
        self._lock = threading.Lock()
        self._events: List[tuple] = []
        # cross-rank alignment anchors: label -> {"ts": us, "wall": s},
        # written by sync_mark() as the fabric exits an epoch barrier
        self._syncs: Dict[str, dict] = {}

    # -- recording ---------------------------------------------------------

    def instant(self, name: str, cat: str = "host", **args) -> None:
        """Zero-duration marker (scope: thread)."""
        self._events.append((name, cat, _now(), None, _THREAD.tid, None,
                             args or None))

    def sync_mark(self, label: str) -> None:
        """Record a cross-rank alignment anchor: every rank calls this
        at the SAME protocol point (epoch-barrier exit), so the merger
        can equate the anchors across files. Recorded even while span
        collection is disabled — alignment must not depend on whether
        the user traced."""
        with self._lock:
            self._syncs[label] = {"ts": _now() / 1e3, "wall": time.time()}

    # -- export ------------------------------------------------------------

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._syncs.clear()

    def __len__(self) -> int:
        return len(self._events)

    def to_chrome_trace(self, since: int = 0) -> dict:
        """The standalone JSON object format: the event array plus
        process/thread name metadata so Perfetto labels the tracks.
        ``since`` exports only events recorded after that index (a
        ``len(tracer)`` snapshot) — how :func:`capture` scopes a region
        without clearing foreign spans."""
        with self._lock:
            events = self._events[since:]
            syncs = {k: dict(v) for k, v in self._syncs.items()}
        pid = _pid()
        out = [{"name": "process_name", "ph": "M", "pid": pid,
                "args": {"name": f"accl host p{pid}"}}]
        for tid in sorted({ev[4] for ev in events}):
            out.append({"name": "thread_name", "ph": "M", "pid": pid,
                        "tid": tid, "args": {"name": f"lane {tid}"}})
        for name, cat, ts, dur, tid, call, args in events:
            ev = {"name": name, "cat": cat, "ts": ts / 1e3, "pid": pid,
                  "tid": tid}
            if dur is None:
                ev.update(ph="i", s="t")
            else:
                ev.update(ph="X", dur=dur / 1e3)
            a = {k: (v if isinstance(v, _PLAIN) else str(v))
                 for k, v in (args or {}).items()}
            if call is not None:
                a["call"] = call
            if a:
                ev["args"] = a
            out.append(ev)
        return {"traceEvents": out, "displayTimeUnit": "ms",
                "accl_sync": {"proc": pid, "marks": syncs}}

    def write(self, path: str, since: int = 0) -> str:
        """Write the standalone Chrome-trace JSON; returns ``path``."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(since), f)
        return path


#: the process-wide tracer every module-level helper writes into
TRACER = SpanTracer()


def start() -> None:
    """Enable span collection (idempotent; events accumulate until
    :func:`stop`/:func:`clear`)."""
    global ENABLED
    ENABLED = True


def stop() -> None:
    global ENABLED
    ENABLED = False


def enabled() -> bool:
    return ENABLED


def clear() -> None:
    TRACER.clear()


def span(name: str, cat: str = "host", call: Optional[int] = None,
         **args):
    """Hot-path entry: a real span when tracing, the shared null context
    otherwise (one boolean read, no allocation). ``call`` is the call id
    the span carries where it runs outside its call's root (a request's
    wait)."""
    if not ENABLED:
        return _NULL
    return _Span(name, cat, args or None, call, False)


def root(name: str, cat: str = "collective", **args):
    """The root span of one session call (the shared null context while
    tracing is off): draws the call id that the spans and requests
    created inside it carry (:func:`current_call`)."""
    if not ENABLED:
        return _NULL
    return _Span(name, cat, args or None, next(_CALL_IDS), True)


def current_call() -> Optional[int]:
    """The call id of the innermost open root span on this thread; None
    outside every call or while tracing is off."""
    if not ENABLED:
        return None
    return _THREAD.call


def instant(name: str, cat: str = "host", **args) -> None:
    if not ENABLED:
        return
    TRACER.instant(name, cat, **args)


def write(path: str) -> Optional[str]:
    """Dump collected events (even after :func:`stop`); None if empty."""
    if len(TRACER) == 0:
        return None
    return TRACER.write(path)


def sync_mark(label: str) -> None:
    """Record a cross-rank alignment anchor in the process tracer (the
    fabric calls this as it exits an epoch barrier — see --merge)."""
    TRACER.sync_mark(label)


@contextlib.contextmanager
def capture(path: str):
    """Trace a region and write ONLY that region's spans on exit (events
    already in the process-global tracer stay there, untouched)::

        with obs.trace.capture("/tmp/accl_host_trace.json"):
            acc.allreduce(...)
    """
    was = ENABLED
    mark = len(TRACER)
    start()
    try:
        yield TRACER
    finally:
        if not was:
            stop()
        TRACER.write(path, since=mark)


# ---------------------------------------------------------------------------
# multi-rank merge CLI: python -m accl_tpu.obs.trace --merge out.json r*.json
# ---------------------------------------------------------------------------

_USAGE = """usage: python -m accl_tpu.obs.trace --merge OUT.json RANK.json [RANK.json ...]

Stitch per-rank Chrome traces (SpanTracer.write output) into ONE
time-aligned timeline. Ranks are aligned on the latest sync mark label
(the epoch-entry KV handshake anchor) present in every readable input;
inputs without a common mark merge unshifted (offset 0, flagged in the
output metadata). Missing or corrupt files are reported and skipped.
Exit codes: 0 merged (>= 1 input readable), 1 nothing merged, 2 usage.
"""


def _load_rank_trace(path: str):
    """One input file -> (doc, sync_marks) or None (reported, skipped)."""
    import sys
    try:
        with open(path) as f:
            doc = json.load(f)
        events = doc["traceEvents"]
        if not isinstance(events, list):
            raise ValueError("traceEvents is not a list")
    except (OSError, ValueError, KeyError) as e:
        print(f"[trace --merge] skipping {path}: {e}", file=sys.stderr)
        return None
    return doc


def merge_traces(paths) -> dict:
    """The --merge core, importable for tests: returns the merged
    Chrome-trace document with per-rank offset metadata under
    ``accl_merge``. Unreadable inputs are skipped (reported on stderr);
    an empty readable set yields a document with no events."""
    docs = []
    for p in paths:
        doc = _load_rank_trace(p)
        if doc is not None:
            docs.append((p, doc))
    # latest sync label common to every readable input (labels are
    # epoch-ordered by construction: "epoch0", "epoch1", ...)
    common = None
    marksets = [doc.get("accl_sync", {}).get("marks", {})
                for _, doc in docs]
    if docs:
        shared = set(marksets[0])
        for m in marksets[1:]:
            shared &= set(m)
        if shared:
            common = max(shared)
    out_events = []
    ranks = {}
    # the first rank with the common mark anchors the merged clock
    ref_ts = None
    if common is not None:
        ref_ts = marksets[0][common]["ts"]
    for (path, doc), marks in zip(docs, marksets):
        offset = 0.0
        aligned = False
        if common is not None and common in marks:
            offset = ref_ts - marks[common]["ts"]
            aligned = True
        proc = doc.get("accl_sync", {}).get("proc")
        for ev in doc["traceEvents"]:
            ev = dict(ev)
            if "ts" in ev:
                ev["ts"] = ev["ts"] + offset
            out_events.append(ev)
        ranks[path] = {"proc": proc, "offset_us": offset,
                       "aligned": aligned,
                       "sync_label": common if aligned else None}
    return {"traceEvents": out_events, "displayTimeUnit": "ms",
            "accl_merge": {"inputs": len(paths), "merged": len(docs),
                           "ranks": ranks}}


def _main(argv) -> int:
    import sys
    args = list(argv)
    if not args or args[0] in ("-h", "--help"):
        print(_USAGE, end="")
        return 0 if args else 2
    if args[0] != "--merge":
        print(f"[trace] unknown argument: {args[0]}", file=sys.stderr)
        print(_USAGE, end="", file=sys.stderr)
        return 2
    rest = args[1:]
    for a in rest:
        if a.startswith("-"):
            print(f"[trace] unknown argument: {a}", file=sys.stderr)
            print(_USAGE, end="", file=sys.stderr)
            return 2
    if len(rest) < 2:
        print("[trace] --merge needs OUT.json and >=1 input",
              file=sys.stderr)
        print(_USAGE, end="", file=sys.stderr)
        return 2
    out, inputs = rest[0], rest[1:]
    doc = merge_traces(inputs)
    if doc["accl_merge"]["merged"] == 0:
        print("[trace] nothing merged (no readable inputs)",
              file=sys.stderr)
        return 1
    d = os.path.dirname(out)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(out, "w") as f:
        json.dump(doc, f)
    m = doc["accl_merge"]
    print(f"[trace] merged {m['merged']}/{m['inputs']} rank traces "
          f"-> {out}")
    return 0


if __name__ == "__main__":
    import sys
    raise SystemExit(_main(sys.argv[1:]))
