"""Request objects and the in-flight call queue.

Mirrors the reference's request machinery (``driver/xrt/include/accl/
acclrequest.hpp:39-211``): every call returns a request handle carrying
status, return code and duration; ``wait(timeout)`` blocks on completion,
``test()`` polls. The reference serializes one op on the device at a time
through ``FPGAQueue``; here JAX's async dispatch plays that role — programs
are enqueued in issue order on each device stream — so the queue tracks
bookkeeping (status, timing, completion callbacks) rather than scheduling.

When the native C++ runtime is available (:mod:`accl_tpu.native`) the queue
and timing counters are backed by it, matching the reference's C++ host
driver; otherwise a pure-Python fallback is used.
"""
from __future__ import annotations

import enum
import threading
import time
from typing import Any, Callable, List, Optional

import jax

from . import fault as _fault
from .constants import ACCLTimeoutError, ACCLError, errorCode
from .obs import metrics as _metrics
from .obs import trace as _trace


class requestStatus(enum.Enum):
    """acclrequest.hpp operationStatus analog.

    ``PEER_FAILED`` is a TPU-only terminal status (round 14): the wait's
    progress pump detected a dead peer through the heartbeat leases and
    retired the request with a bounded-failure verdict instead of
    blocking past any timeout (docs/resilience.md)."""

    QUEUED = 0
    EXECUTING = 1
    COMPLETED = 2
    ERROR = 3
    PEER_FAILED = 4


class Request:
    """Handle for one in-flight collective call (BaseRequest analog)."""

    _next_id = 0
    _id_lock = threading.Lock()

    def __init__(self, scenario: str, outputs: Any = None,
                 finalizer: Optional[Callable[["Request"], None]] = None,
                 external: bool = False,
                 on_complete: Optional[Callable[["Request"], None]] = None,
                 progress: Optional[Callable[[], None]] = None,
                 comm: Any = None,
                 native_registry: Any = None):
        with Request._id_lock:
            Request._next_id += 1
            self.id = Request._next_id
        self.scenario = scenario
        #: the call that issued this request (``obs.trace`` call id; None
        #: while tracing is off), so a later wait names its call
        self.call_id = _trace.current_call()
        #: communicator the call was issued on (comm-scoped barrier drains)
        self.comm = comm
        # when the native C++ runtime backs the session, per-request timing
        # and retcode live in its request registry (the PERFCNT/RETCODE
        # exchange-memory analog, csrc/acclrt.cpp req_*)
        self._nreg = native_registry
        self._nid = native_registry.req_create() if native_registry else None
        self.status = requestStatus.QUEUED
        self.retcode = errorCode.COLLECTIVE_OP_SUCCESS
        self._outputs = outputs          # jax arrays to block on
        self._finalizer = finalizer      # post-completion host work (syncs)
        #: externally-completed requests (e.g. an unmatched recv waiting for a
        #: future send) only finish when fulfill()/ _complete() is called —
        #: the NOT_READY retry-queue analog (ccl_offload_control.c:2460-2478)
        self._external = external
        self._on_complete = on_complete
        #: cooperative-scheduler hook: run parked continuations while this
        #: request waits (the firmware's retry pump; without it a wait on a
        #: backpressured operation could never make progress)
        self._progress = progress
        #: resumption progress for multi-step operations (segments posted or
        #: delivered) — the retry queue's current_step analog
        self.current_step = 0
        self._start_ns = time.monotonic_ns()
        self._duration_ns: Optional[int] = None
        self._cv = threading.Condition()
        self._done = False
        self._error: Optional[BaseException] = None

    # ---- lifecycle -------------------------------------------------------

    def _complete(self, error: Optional[BaseException] = None) -> None:
        with self._cv:
            if self._done:
                return
            self._error = error
            if error is None:
                self.status = requestStatus.COMPLETED
            elif (isinstance(error, ACCLError)
                  and error.code == errorCode.PEER_FAILED):
                self.status = requestStatus.PEER_FAILED
                self.retcode = error.code
            else:
                self.status = requestStatus.ERROR
                if isinstance(error, ACCLError):
                    self.retcode = error.code
            if self._nid is not None:
                # native registry stamps the completion time and keeps the
                # retcode; read the authoritative duration back from it
                self._nreg.req_complete(self._nid, int(self.retcode))
                self._duration_ns = self._nreg.req_duration_ns(self._nid)
                self._nreg.req_free(self._nid)
                self._nid = None
            else:
                self._duration_ns = time.monotonic_ns() - self._start_ns
            self._done = True
            # a completed request no longer pins its device outputs: the
            # queue's bounded history would otherwise keep up to 256
            # payloads alive on the device
            self._outputs = None
            self._cv.notify_all()
        # retirement telemetry: completion counts by terminal status and
        # the whole-request latency (issue -> complete, the PERFCNT
        # duration) — the queue-level view the per-op dispatch histogram
        # does not cover (async waits, external fulfillment)
        _metrics.inc("accl_requests_total",
                     labels=(("op", self.scenario),
                             ("status", self.status.name.lower())))
        if _metrics.ENABLED and self._duration_ns is not None:
            _metrics.observe("accl_request_duration_seconds",
                             self._duration_ns / 1e9,
                             (("op", self.scenario),))
        if self._on_complete is not None:
            cb, self._on_complete = self._on_complete, None
            cb(self)

    def fulfill(self, outputs: Any = None,
                error: Optional[BaseException] = None) -> None:
        """Complete an externally-completed request (deferred recv delivery)."""
        with self._cv:
            if outputs is not None:
                self._outputs = outputs
            self._external = False
            self._cv.notify_all()
        if error is not None:
            self._complete(error)

    def cancel(self, error: Optional[BaseException] = None) -> None:
        """Abort an externally-completed request (soft_reset dropping the
        retry queue). A later wait() raises the cancellation error."""
        with self._cv:
            self._external = False
        self._complete(error or ACCLError(
            errorCode.NOT_READY_ERROR, f"{self.scenario} cancelled"))

    def wait(self, timeout: Optional[float] = None) -> None:
        """Block until done (CCLO::wait / BaseRequest::wait analog).
        Traced as ``req.<op>.wait``, carrying the call id of the call
        that issued the request."""
        if not _trace.ENABLED:
            return self._wait(timeout)
        with _trace.span(f"req.{self.scenario}.wait", cat="request",
                         call=self.call_id, req=self.id):
            self._wait(timeout)

    def _wait(self, timeout: Optional[float]) -> None:
        if self._external:
            with _trace.span("req.block"):
                # wait for fulfill() from a future matching post, pumping the
                # cooperative scheduler so parked operations can finish. The
                # poll interval is fault.WAIT_POLICY (the one backoff
                # implementation): it escalates while pumps make no progress
                # (idle waits park on the CV instead of spinning) and snaps
                # back to fast polling the moment anything moves.
                deadline = ((time.monotonic() + timeout)
                            if timeout is not None else None)
                idle = 0
                while True:
                    if _fault.ENABLED:
                        # the wait pump is a progress loop too: the chaos
                        # harness's rank death fires here for requests parked
                        # on external fulfillment (die/delay only — nothing
                        # absorbs a transient at this site)
                        _fault.point("rank.death", kinds=("die", "delay"))
                    if self._progress is not None:
                        try:
                            if self._progress():
                                idle = 0
                            else:
                                idle += 1
                        except ACCLError as e:
                            if e.code == errorCode.PEER_FAILED:
                                # bounded-failure verdict from the pump's
                                # liveness check: retire the request with the
                                # PEER_FAILED terminal status (counted), then
                                # surface the error to the caller
                                self._complete(e)
                            raise
                        interval = _fault.WAIT_POLICY.interval(idle)
                    with self._cv:
                        if self._cv.wait_for(
                            lambda: self._done or not self._external,
                            timeout=interval if self._progress else timeout,
                        ):
                            break
                        if self._progress is None:
                            raise ACCLTimeoutError(self.scenario)
                    if deadline is not None and time.monotonic() > deadline:
                        raise ACCLTimeoutError(self.scenario)
        if not self._done:
            try:
                with _trace.span("req.block"):
                    if self._outputs is not None:
                        jax.block_until_ready(self._outputs)
                with _trace.span("req.finalize"):
                    if self._finalizer is not None:
                        fin, self._finalizer = self._finalizer, None
                        fin(self)
                    self._complete()
            except BaseException as e:  # noqa: BLE001 - surfaced via retcode
                self._complete(e)
        with self._cv:
            if not self._done and not self._cv.wait_for(
                lambda: self._done, timeout=timeout
            ):
                raise ACCLTimeoutError(self.scenario)
        if self._error is not None:
            raise self._error

    def test(self) -> bool:
        """Non-blocking completion poll (CCLO::test analog)."""
        if self._done:
            return True
        if self._external:
            return False
        if self._outputs is None:
            return True
        # jax arrays expose is_ready on the committed data
        try:
            leaves = jax.tree_util.tree_leaves(self._outputs)
            return all(
                getattr(x, "is_ready", lambda: True)() for x in leaves
            )
        except Exception:  # pragma: no cover
            return False

    def get_retcode(self) -> errorCode:
        return self.retcode

    def get_duration_ns(self) -> int:
        """Per-call duration (FPGADevice::get_duration / PERFCNT analog)."""
        # snapshot under the CV: _complete() frees the native id concurrently
        with self._cv:
            if self._duration_ns is not None:
                return self._duration_ns
            if self._nid is not None:
                return self._nreg.req_duration_ns(self._nid)
            return time.monotonic_ns() - self._start_ns

    def __del__(self):
        # a request observed only through test() never reaches _complete():
        # release its native registry entry so long sessions don't leak
        try:
            if self._nid is not None:
                self._nreg.req_free(self._nid)
                self._nid = None
        except Exception:  # pragma: no cover - interpreter teardown
            pass

    def __repr__(self) -> str:
        return f"Request(id={self.id}, op={self.scenario}, status={self.status.name})"


class RequestQueue:
    """Bookkeeping FIFO of issued requests (FPGAQueue analog).

    Keeps a bounded history for introspection/debug dumps and lets callers
    drain all outstanding work (used by barrier and deinit).
    """

    def __init__(self, history: int = 256):
        self._lock = threading.Lock()
        self._inflight: List[Request] = []
        self._history: List[Request] = []
        self._max_history = history

    def push(self, req: Request) -> Request:
        with self._lock:
            self._inflight.append(req)
        return req

    def drain(self, timeout: Optional[float] = None, comm: Any = None) -> None:
        """Wait for everything issued so far (flush, like barrier's retry-queue
        flush in ccl_offload_control.c:2081-2090). Requests already failed or
        cancelled are skipped — their error surfaces on the caller's wait().
        With ``comm``, only that communicator's requests are flushed — a
        sub-communicator barrier must not block on unrelated traffic.

        ``timeout`` bounds the WHOLE drain: one shared deadline is computed
        up front and each request's wait gets the remaining budget (passing
        the full timeout to every wait in sequence made draining N parked
        requests take up to N×timeout)."""
        deadline = ((time.monotonic() + timeout)
                    if timeout is not None else None)
        with self._lock:
            pending = [r for r in self._inflight
                       if comm is None or r.comm is None or r.comm is comm]
        for r in pending:
            if r.status in (requestStatus.ERROR, requestStatus.PEER_FAILED):
                continue
            r.wait(timeout=(None if deadline is None
                            else max(deadline - time.monotonic(), 0.0)))
        with self._lock:
            for r in pending:
                if r in self._inflight:
                    self._inflight.remove(r)
                    self._history.append(r)
            del self._history[: -self._max_history]

    def retire(self, req: Request) -> None:
        with self._lock:
            if req in self._inflight:
                self._inflight.remove(req)
                self._history.append(req)
                del self._history[: -self._max_history]

    def has_inflight(self) -> bool:
        """True while any issued request has not completed — buffer donation
        must stand down then (an outstanding async Request may still hold
        device arrays that donation would delete)."""
        with self._lock:
            return bool(self._inflight)

    def cancel_externals(self) -> None:
        """Cancel parked externally-completed requests (unmatched async recvs);
        cancellation triggers their on_complete retirement."""
        with self._lock:
            parked = [r for r in self._inflight if r._external]
        for r in parked:
            r.cancel()

    @property
    def inflight(self) -> List[Request]:
        with self._lock:
            return list(self._inflight)
