"""Multi-process operation: one controller per rank group.

The reference runs one driver process per rank under ``mpirun``, wired to
its emulator through ZMQ (``test/host/xrt/include/fixture.hpp:48-144``,
``test/model/zmq/zmq_server.cpp``). This module is that fabric for the TPU
build, expressed through JAX's multi-controller runtime instead of MPI+ZMQ:

* process bring-up = ``jax.distributed.initialize`` (gloo TCP collectives
  on the CPU emulator rung; native ICI/DCN on real multi-host TPU);
* **device data plane** = every cross-process message moves as an SPMD
  ``ppermute`` program over a two-device *pair mesh* that both endpoint
  controllers enter — payload rides the interconnect (gloo TCP on the
  emulator rung, ICI/DCN on hardware), exactly like the collectives, and
  **never transits the coordination service**. This is the reference's
  defining control/data split: the host-side service only supervises
  (``/root/reference/README.md:5-13``); a rendezvous message is one
  device-to-device write (``ccl_offload_control.c:604-612``).
* **host control plane** = the coordination service's key-value store
  carries only headers: message announcements, the global move schedule,
  and barriers. A byte counter (:attr:`CrossProcessFabric.kv_bytes`)
  tracks every control write so tests can assert payload never rides it.

Protocol (two-sided semantics on an SPMD machine):

1. The sender *announces* a message under ``m/{sdev}.{ddev}/{seq}`` — a
   small JSON header (tag, wire dtype, count, eager/rendezvous kind) — and
   keeps the payload staged **on its own device** (jax arrays are
   immutable, so holding the shard reference is a zero-copy snapshot).
2. The receiver *matches* announcements against posted recvs on
   (src, tag | TAG_ANY) in seqn order, parking non-matching heads — the
   out-of-order matching of ``rxbuf_seek.cpp:50-66``.
3. On match the receiver *accepts*: it draws a globally unique index from
   an atomic KV counter and publishes a schedule record ``s/{idx}``.
4. Every controller *drives* the schedule in index order, entering the
   pair-mesh move program for each record it participates in. The global
   total order makes concurrent cross-traffic deadlock-free: the smallest
   outstanding move is always entered first by both of its endpoints.

Eager vs rendezvous keeps the firmware's observable split: an eager send
completes at announce time (bounded by a credit window of
staged-but-unmoved rx-buffer-sized segments — the rx pool backpressure;
credits free locally because the sender co-executes every move), while a
rendezvous send completes only when the move has executed (zero-copy
buffer handoff). Progress is cooperative, like the single-threaded
MicroBlaze dispatch loop: moves execute inside ACCL calls (send/recv/
barrier/request waits), not on a background thread.

Eager moves are BATCHED (the firmware's segment streaming,
``ccl_offload_control.c:628-649``): when a recv accepts an eager
announcement, every other parked eager announcement on the pair joins
the same schedule record — bounded by free rx-pool segments — and the
whole batch rides ONE pair-mesh byte move. Non-matched members land in
the receiver's rx pool, where later recvs match them locally with zero
coordinator traffic (the rx-buffer drain of ``rxbuf_seek.cpp:50-66``).
This amortizes the per-move collective entry — the dominant cost of a
small message — over the credit window, which is what makes the eager
tier stream instead of paying a full handshake per message.

Environment contract (set by :mod:`accl_tpu.launch`):

``ACCL_COORDINATOR``    host:port of process 0's coordination service
``ACCL_NUM_PROCS``      total process count
``ACCL_PROC_ID``        this process's id (0-based)
``ACCL_DEVS_PER_PROC``  virtual CPU devices per process (emulator rung)
"""
from __future__ import annotations

import collections
import dataclasses
import json
import os
import random
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from . import constants
from . import fault as _fault
from .constants import (ACCLError, ACCLPeerFailedError, ACCLTimeoutError,
                        errorCode)
from .obs import cluster as _cluster
from .obs import correlate as _correlate
from .obs import flight as _flight
from .obs import metrics as _metrics
from .obs import trace as _trace

# pre-built label tuples: the KV helpers sit under every control-plane
# round-trip, so even label construction stays off the hot path
_L_KV_GET = (("kvop", "get"),)
_L_KV_SET = (("kvop", "set"),)
_L_KV_INCR = (("kvop", "incr"),)

_ENV_COORD = "ACCL_COORDINATOR"
_ENV_NPROCS = "ACCL_NUM_PROCS"
_ENV_PID = "ACCL_PROC_ID"
_ENV_DEVS = "ACCL_DEVS_PER_PROC"

_initialized = False

# per-process fabric construction index: fabrics are created in SPMD
# program order, so the index aligns across processes (the fallback
# session-nonce channel is keyed by it)
_fabric_seq = 0

# deterministic per-process jitter PRNG for the shared poll backoff
# (thundering-herd avoidance: many ranks polling one KV key decorrelate
# without losing reproducibility — the seed is a pure function of the
# process index). Lazily built so the env read happens after launch.
_poll_rng: Optional[random.Random] = None


def _poll_jitter_rng() -> random.Random:
    global _poll_rng
    if _poll_rng is None:
        _poll_rng = random.Random(0x5EED0 + _fault._proc_index())
    return _poll_rng


def launched() -> bool:
    """True when running under the accl_tpu.launch environment."""
    return _ENV_COORD in os.environ


def ensure_initialized() -> None:
    """Connect this process to the coordination service (idempotent).

    Must run before the first JAX backend touch; :mod:`accl_tpu`'s package
    ``__init__`` calls it on import when the launch env is present — the
    analog of the reference fixture constructing one driver per rank at
    process start (fixture.hpp:87-92).
    """
    global _initialized
    if _initialized or not launched():
        return
    ndev = os.environ.get(_ENV_DEVS)
    if ndev:
        # force exactly ndev virtual devices, replacing any inherited
        # count (e.g. a test harness's XLA_FLAGS leaking into children)
        flags = [
            f
            for f in os.environ.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        flags.append(f"--xla_force_host_platform_device_count={ndev}")
        os.environ["XLA_FLAGS"] = " ".join(flags)
    import jax

    platform = os.environ.get("ACCL_PLATFORM",
                              os.environ.get("JAX_PLATFORMS", "cpu"))
    if platform in ("cpu", ""):
        # jax.config beats an inherited JAX_PLATFORMS env var
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=os.environ[_ENV_COORD],
        num_processes=int(os.environ[_ENV_NPROCS]),
        process_id=int(os.environ[_ENV_PID]),
    )
    _initialized = True


def active() -> bool:
    """True when JAX runs multi-controller (process_count > 1)."""
    import jax

    try:
        return jax.process_count() > 1
    except RuntimeError:
        return False


def _client():
    from jax._src import distributed

    client = distributed.global_state.client
    if client is None:
        raise ACCLError(
            errorCode.CONFIG_ERROR,
            "multi-process fabric requires jax.distributed to be initialized",
        )
    return client


class CrossProcessFabric:
    """Control plane + device-move scheduler between per-rank controllers.

    Endpoints are named by **global device ids** (the session table of
    ``communicator.cpp:25-52``), so sequence numbers and announcements are
    communicator-independent — two sub-communicators over the same device
    pair share one ordered stream, like the exchange-memory seqn registers
    (``dma_mover.cpp:581-610``).
    """

    def __init__(self, timeout: float, eager_window: int,
                 eager_seg_bytes: int = 16 * 1024,
                 retry_policy: Optional[_fault.RetryPolicy] = None,
                 heartbeat_interval_s: float = 1.0,
                 heartbeat_timeout_s: float = 20.0):
        import jax

        self.timeout = timeout
        #: THE coordination-RPC retry/backoff policy (fault.RetryPolicy):
        #: every KV helper routes through it, so transient faults —
        #: injected or real — are absorbed with one counted escalating
        #: backoff implementation instead of N ad-hoc ladders
        self._retry = retry_policy or _fault.RetryPolicy()
        #: deterministic jitter PRNG for the retry backoff, seeded per
        #: process so concurrent retries decorrelate reproducibly
        self._rng = random.Random(0xFA17 + jax.process_index())
        #: peer-liveness lease cadence/staleness window (docs/resilience.md);
        #: heartbeat_timeout <= 0 disables liveness entirely
        self.heartbeat_interval = float(heartbeat_interval_s)
        self.heartbeat_timeout = float(heartbeat_timeout_s)
        #: session epoch: bumped by ACCL.recover()'s elastic re-handshake;
        #: every epoch gets a fresh key namespace (bump_epoch)
        self.epoch = 0
        self._hb_count = 0          # heartbeats this process published
        self._hb_last = 0.0         # monotonic of the last publish
        self._peer_check_last = 0.0
        #: proc -> (last lease value seen, local monotonic when it changed):
        #: staleness is measured on THIS clock against value CHANGES, so
        #: cross-process clock skew cannot fake a death
        self._peer_seen: Dict[int, Tuple[Optional[str], float]] = {}
        self._dead_peers: set = set()
        #: processes a SURVIVOR-SUBSET recovery removed from the mesh
        #: (ACCL.recover shrink mode): unlike ordinary death verdicts —
        #: which clear at every epoch bump so elastic rejoin works — an
        #: excluded process is gone for the session: liveness sweeps
        #: skip it permanently (its lease will never reappear, and a
        #: ghost write from its stale process must not re-latch a
        #: verdict the mesh already acted on)
        self._excluded: set = set()
        #: credit window: max staged-but-unmoved eager segments per pair
        self.eager_window = max(int(eager_window), 1)
        self.eager_seg_bytes = max(int(eager_seg_bytes), 1)
        self._me = jax.process_index()
        self._dev_by_id = {d.id: d for d in jax.devices()}
        #: control bytes written to the KV store (keys + values) — the
        #: accounting that proves payload rides the device path
        self.kv_bytes = 0
        #: payload bytes moved by pair-mesh device programs this process
        #: participated in (each endpoint counts every move it entered)
        self.moved_bytes = 0
        #: job-unique session nonce (ADVICE r4 #1): key namespaces that
        #: must survive a crashed earlier run on the same coordination
        #: service derive from this, never from shared KV counters whose
        #: n-alignment one crash can poison
        global _fabric_seq
        #: per-process fabric construction index — SPMD construction
        #: order aligns it across processes, so it distinguishes
        #: multiple fabric instances within one job
        self.instance = _fabric_seq
        _fabric_seq += 1
        self.session = self._resolve_session()
        #: namespace prefix for EVERY fabric key (announcements,
        #: schedule, barriers, autotune decisions): unique per (job run,
        #: fabric instance), so a new fabric never collides with a dead
        #: session's leftover keys — per-pair seqs restart at 1, barrier
        #: counters at 0, the schedule at 1, all in a fresh namespace.
        #: (A single process restarting MID-job while its peers keep the
        #: old instance numbering is outside the contract: the launcher
        #: aborts the whole job when one controller dies, mpirun-style.)
        #: (8 nonce chars keep announce keys short — uniqueness is
        #: across a handful of runs sharing one coordination service)
        self.ns = f"accl/{self.session[-8:]}.{self.instance}"
        # sender state
        self._out_seq: Dict[Tuple[int, int], int] = {}
        self._reserved: set = set()
        self._staged: Dict[Tuple[int, int, int], object] = {}
        self._staged_segs: Dict[Tuple[int, int], int] = {}
        # receiver state
        self._fetch_seq: Dict[Tuple[int, int], int] = {}
        self._parked_ann: Dict[Tuple[int, int], Dict[int, dict]] = {}
        self._accepts: Dict[Tuple[int, int, int], Callable] = {}
        # receiver-side eager rx pool: moved-but-undrained payloads, the
        # rx-buffer stage of the reference protocol (segments land in
        # spare buffers BEFORE a recv is posted; rxbuf matching drains
        # them locally — rxbuf_seek.cpp:50-66). One batched move fills
        # many pool slots at once; a later recv that matches a pooled
        # message never touches the coordinator (VERDICT r4 weak #5: the
        # per-message announce->match->accept->move serialization put a
        # ~15 ms pair-collective entry under every 32 KiB message).
        self._pool: Dict[Tuple[int, int, int], tuple] = {}
        self._pool_segs: Dict[Tuple[int, int], int] = {}
        # headers of accepted-but-not-yet-moved batch members, keyed by
        # (sdev, ddev, seq) — consumed by _execute when the move lands
        self._batch_hdrs: Dict[Tuple[int, int, int], dict] = {}
        # consumed announcement keys awaiting lazy cleanup (deleted off
        # the critical path by idle pump cycles) — a FIFO: drained
        # oldest-first so the coordinator's oldest keys are cleaned
        # first instead of starving behind every newer batch (ADVICE r5)
        self._pending_deletes: collections.deque = collections.deque()
        # directory-read support flag: flipped off (with a warning) on
        # the first dir_get failure, switching fetch to per-seq try_get
        self._dir_get_ok = True
        # immutable zero landing pads / pad slices keyed (device id,
        # elems, dtype): the pow2 wire quantization makes these ~log2
        # (window) distinct shapes per pair, and rebuilding one per move
        # re-uploaded up to the whole window's bytes of zeros H2D on the
        # move's critical path
        self._zeros: Dict[tuple, object] = {}
        # global schedule cursor (next s/{idx} to consider): the
        # namespace is fresh per fabric instance, but snapshotting stays
        # cheap insurance against namespace reuse outside the contract
        # (e.g. a mid-job process restart with the env session nonce)
        self._cursor = self._kcount(_client(), f"{self.ns}/sn") + 1
        # pair-mesh move programs keyed (sdev, ddev, count, wire dtype)
        self._progs: Dict[tuple, tuple] = {}
        # barrier arrivals that timed out before their round completed:
        # name -> (target count still owed, participant count) — consumed
        # by the next call, which must use the same participant set
        self._barrier_pending: Dict[str, Tuple[int, int]] = {}
        # cluster metrics plane (obs/cluster.py): monotonic of the last
        # snapshot publish — the heartbeat cadence discipline
        self._obs_last = 0.0
        # death-verdict sets already flight-dumped: raise_if_peer_failed
        # fires on EVERY wait iteration once a verdict latches, and the
        # black box must dump once per verdict, not once per poll
        self._flight_dumped_deaths: set = set()
        # correlation ids carry this process index when armed
        _correlate.set_proc(self._me)
        # lease the session EAGERLY: a controller that dies before its
        # first wait loop ever runs must still be detectable — the lease
        # exists from bring-up, frozen the moment progress stops
        self._maybe_heartbeat(_client())

    def _resolve_session(self) -> str:
        """ACCL_SESSION (minted once per job by the launcher) when
        present; otherwise p0 mints a nonce from a p0-ONLY KV counter
        (single writer — no alignment to corrupt) and publishes it under
        this fabric's SPMD construction index. Residual exposure: on a
        long-lived external KV, a reader racing a NEW run's p0 could see
        the previous run's value — launcher runs are immune (env), and
        user-driven jax.distributed deployments should export
        ACCL_SESSION to close it."""
        env = os.environ.get("ACCL_SESSION")
        if env:
            return env
        import jax

        client = _client()
        key = f"accl/sess/{self.instance}"
        if self._me == 0:
            s = f"s{self._kincr(client, 'accl/sess_seq')}"
            # the crashed-rerun scenario this nonce exists for leaves the
            # key populated — the publish must OVERWRITE, or p0 raises
            # ALREADY_EXISTS exactly when the nonce matters most
            self._kset_force(client, key, s)
            # Handshake keys are namespaced by the FRESHLY MINTED nonce
            # (ADVICE r5): a reused coordination service holds the dead
            # run's ack keys, and the old un-namespaced blocking get
            # returned one of those stale values instantly — aborting
            # the rerun with CONFIG_ERROR exactly when the nonce
            # mattered. Under this run's nonce the ack key simply does
            # not exist until peer p has READ s, so p0 waits, never
            # compares against a ghost.
            for p in range(1, jax.process_count()):
                client.blocking_key_value_get(
                    f"accl/sess_ack/{self.instance}/{s}/{p}",
                    self._timeout_ms())
            # release the peers: the confirm is nonce-namespaced too, so
            # a peer that raced the overwrite and echoed a dead run's
            # nonce sees no confirm, re-reads, and CONVERGES on s
            self._kset_force(client, f"accl/sess_ok/{self.instance}/{s}",
                             "1")
            return s
        deadline = time.monotonic() + self.timeout
        # confirm-poll pacing rides THE retry policy (was a fixed
        # min(2s, timeout) poll): short first polls converge fast on the
        # common no-contention path, and the escalation tops out at the
        # LEGACY 2 s ceiling, not the RPC-retry cap — while p0 slowly
        # collects acks on a big world, hundreds of waiting ranks must
        # idle toward ~0.5 poll/s, not hammer the coordinator at the
        # 100 ms retry cadence. The ack write happens only when the
        # nonce CHANGES (once on the happy path), never per poll.
        pacing = dataclasses.replace(
            self._retry, max_s=max(self._retry.max_s, 2.0))
        attempt = 0
        s = None
        while True:
            s2 = client.blocking_key_value_get(key, self._timeout_ms())
            if s2 != s:
                s = s2
                self._kset_force(
                    client, f"accl/sess_ack/{self.instance}/{s}/{self._me}",
                    s)
            try:
                if _fault.ENABLED:
                    # an injected confirm-read fault (drop/fail) lands in
                    # the except arm below: counted as a handshake retry,
                    # converging exactly like a raced stale nonce
                    _fault.point("handshake.confirm")
                poll_ms = max(
                    int(pacing.interval(attempt, self._rng) * 1e3), 1)
                attempt += 1
                client.blocking_key_value_get(
                    f"accl/sess_ok/{self.instance}/{s}", poll_ms)
                return s
            except Exception:
                # no confirm for the nonce we echoed: either p0 is still
                # collecting (keep waiting) or we read a dead run's value
                # before p0's overwrite landed (the re-read converges on
                # the fresh nonce). Bounded by the session timeout.
                _metrics.inc("accl_session_handshake_retries_total")
                if time.monotonic() > deadline:
                    raise ACCLError(
                        errorCode.CONFIG_ERROR,
                        f"session nonce handshake timed out: no confirm "
                        f"for {s!r} within {self.timeout}s — is process 0 "
                        f"alive? Set ACCL_SESSION to a job-unique value "
                        f"to skip the bootstrap handshake entirely")

    # -- KV helpers (all writes tallied) -----------------------------------
    #
    # Every helper routes its coordination RPC through :meth:`_kv_call` —
    # THE retry/backoff implementation (fault.RetryPolicy, configured by
    # the ACCLConfig rpc_retry_* register tier): transient faults, whether
    # injected at the named point by the chaos harness or real
    # UNAVAILABLE/connection-reset RPC errors, are absorbed with counted
    # escalating jittered backoff (accl_rpc_retry_total{point}) bounded by
    # the session timeout; permanent errors (NOT_FOUND, ALREADY_EXISTS,
    # config mistakes) surface immediately, exactly as before.

    def _kv_call(self, point: str, fn: Callable, retry_real: bool = True):
        """Run one coordination RPC under the session retry policy.

        ``retry_real=False`` restricts absorption to INJECTED faults (the
        harness fires before the RPC, so a retry is always safe) while
        real errors propagate as before — for non-idempotent RPCs like
        the native atomic increment, where a blind re-issue after an
        ambiguous failure could apply twice."""
        if _fault.ENABLED:
            inner = fn

            def fn():
                _fault.point(point)
                return inner()

            check = (_fault.is_transient if retry_real
                     else (lambda e: isinstance(e, _fault.FaultInjected)))
        elif not retry_real:
            return fn()
        else:
            check = _fault.is_transient
        return self._retry.call(fn, point=point, rng=self._rng,
                                deadline_s=self.timeout, retryable=check)

    def _kset(self, client, key: str, value: str,
              point: str = "kv.set") -> None:
        self.kv_bytes += len(key) + len(value)
        t0 = _metrics.tick()

        def put():
            try:
                client.key_value_set(key, value)
            except Exception as e:
                # an ambiguous transient failure (connection reset AFTER
                # the coordinator applied the set) makes the policy's
                # retry land on ALREADY_EXISTS — but the retried
                # (key, value) pair is identical, so if the stored value
                # matches, the publish already succeeded. A genuinely
                # conflicting existing value still raises (that is a
                # protocol bug, not a retry echo).
                if "ALREADY_EXISTS" not in f"{type(e).__name__}: {e}":
                    raise
                if self._try_get_raw(client, key) != value:
                    raise

        self._kv_call(point, put)
        if t0:
            _metrics.observe("accl_kv_seconds", time.perf_counter() - t0,
                             _L_KV_SET)

    def _kset_force(self, client, key: str, value: str) -> None:
        """Tallied set that OVERWRITES — for bootstrap keys that may
        survive an earlier run on a long-lived coordination service."""
        self.kv_bytes += len(key) + len(value)

        def put():
            try:
                client.key_value_set(key, value, allow_overwrite=True)
            except TypeError:  # older client without the kwarg
                try:
                    client.key_value_delete(key)
                except Exception:
                    pass
                client.key_value_set(key, value)

        self._kv_call("kv.set", put)

    def _kincr(self, client, key: str, by: int = 1) -> int:
        self.kv_bytes += len(key) + 8
        t0 = _metrics.tick()
        try:
            # retry_real=False: the native increment is not idempotent —
            # a blind re-issue after an AMBIGUOUS real failure could
            # apply twice and leave a hole in the gap-free schedule
            # index. Injected faults fire before the RPC, so absorbing
            # them is always safe.
            n = int(self._kv_call(
                "kv.incr", lambda: client.key_value_increment(key, by),
                retry_real=False))
            if t0:
                _metrics.observe("accl_kv_seconds",
                                 time.perf_counter() - t0, _L_KV_INCR)
            return n
        except AttributeError:
            # Older coordination clients have no atomic increment.
            # Emulate with a DENSE CAS ladder: claim key#c<n> via
            # create-only sets (ALREADY_EXISTS = lost that slot, move
            # on), scanning forward from a monotonic hint. A claim only
            # succeeds on a previously unclaimed n, so the sequence has
            # no gaps — consumers that need gap-free counters (the
            # schedule index) stay correct — at O(contenders) RTTs per
            # increment. The counter VALUE key is never written (a
            # last-writer-wins mirror could regress); readers go
            # through :meth:`_kcount`, which scans the same ladder.
            if by != 1:
                raise ACCLError(
                    errorCode.CONFIG_ERROR,
                    "emulated KV increment supports by=1 only")
            n = int(self._try_get(client, key + "#hint") or 0)
            while True:
                nxt = n + 1
                if self._try_get(client, f"{key}#c{nxt}") is not None:
                    n = nxt
                    continue
                try:
                    self.kv_bytes += len(key) + 8
                    client.key_value_set(f"{key}#c{nxt}", "1")
                except Exception:
                    # ALREADY_EXISTS means we raced and slot nxt is
                    # taken — but a TRANSIENT RPC failure must retry the
                    # SAME slot, or the ladder gets a permanent hole
                    # that caps every later _kcount scan. Disambiguate
                    # by re-probing the slot.
                    if self._try_get(client, f"{key}#c{nxt}") is not None:
                        n = nxt
                    continue
                # hint is best-effort and <= some existing claim, so a
                # stale hint only costs extra forward probes
                self._kset_force(client, key + "#hint", str(nxt))
                if t0:
                    # the emulated ladder is ONE logical increment however
                    # many claim RTTs it took — observed as one sample
                    _metrics.observe("accl_kv_seconds",
                                     time.perf_counter() - t0, _L_KV_INCR)
                return nxt

    def _kcount(self, client, key: str) -> int:
        """Current value of a :meth:`_kincr` counter: the native value
        key when the client has atomic increments, else a forward scan
        of the emulation's claim ladder."""
        v = self._try_get(client, key)
        if v is not None:
            return int(v)
        n = int(self._try_get(client, key + "#hint") or 0)
        while self._try_get(client, f"{key}#c{n + 1}") is not None:
            n += 1
        return n

    @staticmethod
    def poll_sleep(idle_iters: int) -> None:
        """Escalating poll backoff for progress loops: fine-grained sleeps
        while the peer is mid-protocol (each eager message crosses ~5
        coordinator boundaries — announce, fetch, accept, schedule read,
        move — and every boundary costs one poll interval, so a flat 2 ms
        poll put a ~10 ms floor under the credit RTT; measured in
        benchmarks/mp_bandwidth.py), escalating once the loop has been
        idle long enough that the peer is evidently not about to respond.
        Escalation is quicker and deeper than the original 32-iter/2 ms
        ladder: each poll costs a KV RTT, and on a shared-core host the
        idle side's polling directly starves the busy peer (profiled:
        ~23% of the eager sender's wall time was idle-poll try_gets).

        Re-expressed through :data:`fault.POLL_POLICY` (round 14) so there
        is exactly ONE backoff implementation in the codebase: the same
        ~200 µs → 2 ms escalation over ~8 idle iterations, now with
        deterministic per-process jitter — many ranks polling the same KV
        key decorrelate (no thundering herd on the coordinator) without
        losing run-to-run reproducibility."""
        time.sleep(_fault.POLL_POLICY.interval(idle_iters,
                                               _poll_jitter_rng()))

    def _try_get(self, client, key: str) -> Optional[str]:
        """:meth:`_try_get_raw` under the ``kv.get`` injection point: an
        armed harness may fault the read, absorbed by the retry policy
        (counted). The disabled path is ONE boolean read on top of the
        raw RPC — this sits under every poll-loop iteration. Note the
        raw read maps ANY client failure to a miss (None), so a real
        transient kv.get error in production degrades to one poll-miss
        iteration — absorbed by the enclosing poll loop's backoff, not
        by the counted policy (docs/resilience.md)."""
        if not _fault.ENABLED:
            return self._try_get_raw(client, key)
        return self._retry.call(
            lambda: (_fault.point("kv.get"),
                     self._try_get_raw(client, key))[1],
            point="kv.get", rng=self._rng, deadline_s=self.timeout)

    @staticmethod
    def _try_get_raw(client, key: str) -> Optional[str]:
        """try_get that treats a missing key as None (the client raises
        NOT_FOUND rather than returning a sentinel). Older clients have
        no key_value_try_get at all — there, a ~1 ms blocking get is the
        emulation (present keys return immediately; the deadline error
        means missing). The AttributeError arm must not swallow into the
        generic None path: that made EVERY key look missing and stalled
        the whole eager protocol on such clients."""
        t0 = _metrics.tick()
        try:
            v = client.key_value_try_get(key)
            if t0:
                _metrics.observe("accl_kv_seconds",
                                 time.perf_counter() - t0, _L_KV_GET)
            return v
        except AttributeError:
            # 25 ms deadline: must cover a same-DC coordinator RTT, or
            # PRESENT keys read as missing and the protocol stalls; a
            # miss costs the full deadline, which only slows idle polls
            # (poll_sleep already backs off around them)
            try:
                v = client.blocking_key_value_get(key, 25)
            except Exception:
                v = None
            if t0:
                _metrics.observe("accl_kv_seconds",
                                 time.perf_counter() - t0, _L_KV_GET)
            return v
        except Exception:
            if t0:
                # a NOT_FOUND miss is still one coordinator RTT — the
                # histogram must see the polling loop's dominant case
                _metrics.observe("accl_kv_seconds",
                                 time.perf_counter() - t0, _L_KV_GET)
            return None

    def _timeout_ms(self) -> int:
        return max(int(self.timeout * 1000), 1)

    # -- sender side -------------------------------------------------------

    def next_seq(self, sdev: int, ddev: int) -> int:
        """Reserve the next sequence number on the pair. The reservation is
        tracked until :meth:`announce` / :meth:`announce_cancel` resolves it
        so :meth:`reset` can tombstone holes a dropped send would leave."""
        k = (sdev, ddev)
        self._out_seq[k] = self._out_seq.get(k, 0) + 1
        seq = self._out_seq[k]
        self._reserved.add((sdev, ddev, seq))
        return seq

    def nsegments(self, nbytes: int) -> int:
        """Eager staging cost in rx-buffer slots (fw segmentation geometry,
        ccl_offload_control.c:613-650)."""
        return max((int(nbytes) + self.eager_seg_bytes - 1)
                   // self.eager_seg_bytes, 1)

    def eager_credit_free(self, sdev: int, ddev: int, nseg: int) -> bool:
        """Whether ``nseg`` more staged segments fit the pair's window.

        A message larger than the whole window (e.g. a big compressed
        payload, which must ride eager for fw parity) is admitted when the
        pair has nothing staged — it takes the window exclusively;
        otherwise it could never be sent at all (the in-process pool path
        raises the same way only when no recv could ever drain it)."""
        used = self._staged_segs.get((sdev, ddev), 0)
        return used == 0 or used + nseg <= self.eager_window

    def eager_can_announce(self, sdev: int, ddev: int, seq: int,
                           nseg: int) -> bool:
        """Whether the eager send holding reserved ``seq`` may announce now.

        FIFO per pair on top of the credit window: while an EARLIER seq on
        the pair is still reserved-but-unannounced, later sends must queue
        behind it. Without this, a later send could take window credits
        and announce past the hole — the receiver's fetch cursor stalls at
        the unannounced seq, so those credits could never be freed by a
        move and the earlier (e.g. oversized, used==0-gated) send would
        starve forever: a send-order deadlock no recv posting can break.
        """
        for (s, d, q) in self._reserved:
            if s == sdev and d == ddev and q < seq:
                return False
        return self.eager_credit_free(sdev, ddev, nseg)

    def announce(self, sdev: int, ddev: int, tag: int, payload,
                 kind: str, nseg: int, seq: Optional[int] = None) -> int:
        """Stage the payload on-device and publish the message header.

        ``payload`` is a single-device jax array of shape (1, count) on the
        source device; immutability makes the held reference a snapshot
        (eager) and a zero-copy handle (rendezvous) at once.

        ``seq`` publishes under a sequence number reserved earlier with
        :meth:`next_seq` — a credit-starved send reserves its seq at issue
        time so later sends on the pair cannot overtake it (the receiver's
        fetch cursor stalls at the unannounced seq, so per-pair posting
        order IS delivery-visibility order, MPI non-overtaking semantics).
        """
        client = _client()
        if seq is None:
            seq = self.next_seq(sdev, ddev)
        self._reserved.discard((sdev, ddev, seq))
        credits = nseg if kind == "e" else 0
        self._staged[(sdev, ddev, seq)] = (payload, credits)
        if credits:
            k = (sdev, ddev)
            self._staged_segs[k] = self._staged_segs.get(k, 0) + credits
        header = {"tag": int(tag), "dt": str(payload.dtype),
                  "n": int(payload.shape[-1]), "k": kind, "g": int(nseg)}
        if _correlate.ENABLED:
            # sender-side correlation id (epoch, proc, seq) — a fresh
            # sender-scoped seq, NOT the per-pair wire seq, so the id is
            # unique across pairs. Key absent entirely when disarmed:
            # the announce header stays byte-identical on the wire.
            header["c"] = list(_correlate.stamp())
        # the header publish carries its own injection point: a dropped
        # announce is THE canonical eager-protocol fault (the header is
        # the message as far as the control plane knows) — absorbed by
        # the retry policy like any transient set, re-publishing the
        # same (seq, header) idempotently
        self._kset(client, f"{self.ns}/m/{sdev}.{ddev}/{seq}",
                   json.dumps(header), point="eager.announce")
        return seq

    def announce_cancel(self, sdev: int, ddev: int, seq: int) -> None:
        """Release a reserved-but-never-announced sequence number (a parked
        send cancelled by soft_reset): publishes a tombstone so the
        receiver's fetch cursor can advance past the hole."""
        self._reserved.discard((sdev, ddev, seq))
        self._kset(_client(), f"{self.ns}/m/{sdev}.{ddev}/{seq}",
                   json.dumps({"k": "x"}))

    def reset(self) -> None:
        """Local-state part of soft_reset (cfgFunc::reset_periph analog).

        Tombstones every reserved-but-unannounced sequence number so peer
        fetch cursors never stall on holes left by dropped sends. Announced
        in-flight messages are deliberately NOT retracted: a peer may
        already have fetched/accepted them, and retracting one side of a
        committed move would desynchronize the global schedule — like the
        reference, a soft reset is per-controller; a full distributed reset
        is all processes resetting at a barrier."""
        for (sdev, ddev, seq) in list(self._reserved):
            self.announce_cancel(sdev, ddev, seq)

    def send_pending(self, sdev: int, ddev: int, seq: int) -> bool:
        """True while the staged payload has not been moved yet."""
        return (sdev, ddev, seq) in self._staged

    # -- receiver side -----------------------------------------------------

    def _fetch(self, client, sdev: int, ddev: int) -> None:
        """Pull new announcements for the pair into the parked table with
        ONE directory read (a per-seq try_get+delete pair cost 2 KV
        round-trips per message — profiled as a top eager-loop cost).
        Consumed keys are deleted LAZILY (:meth:`_flush_deletes`, off the
        critical path); a directory delete would race a concurrent
        announce. Cancellation tombstones (kind "x") advance the cursor
        unparked."""
        k = (sdev, ddev)
        cur = self._fetch_seq.get(k, 1)
        prefix = f"{self.ns}/m/{sdev}.{ddev}/"
        new = {}
        if self._dir_get_ok:
            try:
                # through the retry policy (kv.get point): an injected or
                # real TRANSIENT fault is absorbed instead of permanently
                # demoting the fetch path to per-seq gets; only a
                # persistent failure (or a client without dir_get) still
                # takes the fallback below
                entries = self._kv_call(
                    "kv.get",
                    lambda: list(client.key_value_dir_get(prefix)))
                for key, v in entries:
                    try:
                        q = int(str(key).rsplit("/", 1)[1])
                    except ValueError:
                        continue
                    if q >= cur:
                        new[q] = v
            except Exception as e:
                # a client without dir_get (or a failing coordinator)
                # must NOT look like "no messages" — that turns an infra
                # fault into a phantom-lost-message recv timeout. Fall
                # back to the per-seq path permanently, and say so once.
                self._dir_get_ok = False
                from .utils.logging import get_logger
                get_logger("accl").warning(
                    "key_value_dir_get unavailable (%s: %s); falling "
                    "back to per-seq announcement fetch",
                    type(e).__name__, e)
        if not self._dir_get_ok:
            q = cur
            while True:
                v = self._try_get(client, prefix + str(q))
                if v is None:
                    break
                new[q] = v
                q += 1
        # contiguous advance only: a hole is a seq reserved but not yet
        # visible — later seqs stay unfetched until it lands (per-pair
        # non-overtaking)
        while cur in new:
            h = json.loads(new[cur])
            if h.get("k") != "x":
                self._parked_ann.setdefault(k, {})[cur] = h
            self._pending_deletes.append(prefix + str(cur))
            cur += 1
        self._fetch_seq[k] = cur
        if len(self._pending_deletes) > 256:
            self._flush_deletes(client, 64)

    def _flush_deletes(self, client, limit: int = 8) -> None:
        """Delete up to ``limit`` consumed announcement keys — called
        from idle pump cycles so cleanup RTTs never sit on the message
        critical path. popleft: oldest keys first (the list.pop() LIFO
        let the earliest keys linger for the whole session whenever new
        consumption outpaced idle cycles — ADVICE r5)."""
        while self._pending_deletes and limit > 0:
            client.key_value_delete(self._pending_deletes.popleft())
            limit -= 1

    def try_match(self, sdev: int, ddev: int,
                  tag: int) -> Optional[Tuple[int, dict]]:
        """Match a posted recv against announcements on (src, tag|ANY) in
        seqn order, skipping (parking) non-matching heads — the
        out-of-order matching table of ``rxbuf_seek.cpp:50-66``. The scan
        merges the rx POOL (payloads already moved by a batched eager
        accept) with still-parked announcements, in seq order — a pooled
        message is matchable exactly like a parked one, just already local.

        Non-consuming: the matched announcement stays parked (or pooled)
        until :meth:`accept` commits it, so a caller that rejects the
        match (count mismatch) leaves the message matchable by a
        corrected recv.
        """
        # local state first, coordinator only on a miss: the fetch cursor
        # is contiguous, so every unfetched announcement has a LARGER seq
        # than anything parked or pooled — a local tag match is already
        # the smallest matching seq, and a pool-hit recv pays zero KV
        # round-trips (profiled: the per-recv fetch RTT was a measurable
        # slice of the eager loop on the emulator rung).
        for attempt in range(2):
            parked = self._parked_ann.get((sdev, ddev), {})
            merged = dict(parked)
            for (s, d, q), (_arr, h) in self._pool.items():
                if (s, d) == (sdev, ddev):
                    merged[q] = h
            for seq in sorted(merged):
                h = merged[seq]
                if tag == constants.TAG_ANY or h["tag"] == tag:
                    return seq, h
            if attempt == 0:
                self._fetch(_client(), sdev, ddev)
        return None

    def pool_segments(self, sdev: int, ddev: int) -> int:
        """Occupied + reserved rx-pool segments on the pair (the
        receiver-side backpressure the eager window models)."""
        return self._pool_segs.get((sdev, ddev), 0)

    def accept(self, sdev: int, ddev: int, seq: int, header: dict,
               deliver: Callable) -> int:
        """Commit a match.

        Pooled message (payload already moved by an earlier batch):
        delivered immediately, zero coordinator traffic — the local
        rx-buffer drain of ``rxbuf_seek.cpp``.

        Parked eager announcement: BATCH-accept — every parked eager
        announcement on the pair (in seq order, bounded by free rx-pool
        segments) joins one schedule record and moves as ONE coalesced
        byte payload; the matched message delivers on arrival, the rest
        land in the pool for later recvs. This amortizes the pair-mesh
        collective entry (the dominant per-move cost) over the whole
        credit window, the way the firmware streams eager segments with
        up to 3 moves in flight (ccl_offload_control.c:628-649).

        Parked rendezvous announcement: the classic single-message
        zero-copy record (no byte-cast copy on the large-payload path).

        ``deliver(shard, header)`` runs on this (receiver) process when
        the payload is available, with the shard on the dst device."""
        client = _client()
        pooled = self._pool.pop((sdev, ddev, seq), None)
        if pooled is not None:
            arr, h = pooled
            k = (sdev, ddev)
            self._pool_segs[k] = max(
                self._pool_segs.get(k, 0) - h.get("g", 1), 0)
            deliver(arr, header)
            # keep the pipeline primed: accept announcements that have
            # accumulated since the last batch, so their move executes
            # while the app drains the remaining pool entries — but only
            # once a QUARTER-WINDOW is waiting. An unconditional prefetch
            # measured WORSE than none: it flushed every 1-2 parked
            # messages into its own move, locking the steady state at
            # tiny batches with the full fixed move cost each (profiled:
            # 48 msgs -> 16 moves of ~3). While the pool still holds
            # undrained entries there is no hurry; small remainders ship
            # when a blocked recv forces them.
            self._batch_collect(sdev, ddev,
                                min_segs=max(self.eager_window // 4, 2))
            return -1
        parked = self._parked_ann.get((sdev, ddev), {})
        header = parked.pop(seq, header)
        if header.get("k") != "e":
            # rendezvous: single zero-copy move record
            idx = self._kincr(client, f"{self.ns}/sn")
            self._accepts[(sdev, ddev, seq)] = (
                lambda arr, h=header: deliver(arr, h))
            rec = {"s": sdev, "d": ddev, "q": seq,
                   "n": header["n"], "dt": header["dt"]}
            self._kset(client, f"{self.ns}/s/{idx}", json.dumps(rec))
            return idx
        # eager: batch every parked eager announcement that fits the pool.
        # The matched message is always admitted (its recv is waiting and
        # drains it the moment the move lands — any overshoot is
        # transient); the rest reserve free pool segments in seq order.
        k = (sdev, ddev)
        self._pool_segs[k] = (self._pool_segs.get(k, 0)
                              + header.get("g", 1))
        self._batch_hdrs[(sdev, ddev, seq)] = header
        self._accepts[(sdev, ddev, seq)] = (
            lambda arr, h=header: deliver(arr, h))
        return self._batch_collect(sdev, ddev, lead=[(seq, header)])

    def _batch_collect(self, sdev: int, ddev: int,
                       lead: Optional[list] = None,
                       min_segs: int = 0) -> int:
        """Publish one coalesced eager-batch record: ``lead`` members
        (already reserved by the caller) plus every parked eager
        announcement that fits the rx pool's free segments, in seq
        order. Called with no ``lead`` it is the opportunistic PREFETCH:
        new announcements accepted into the pool with no recv waiting,
        so their single move overlaps the drain of the previous batch —
        the firmware's bounded-moves-in-flight eager streaming
        (ccl_offload_control.c:628-649). ``min_segs`` holds the prefetch
        back until enough traffic has accumulated to amortize the fixed
        per-move cost (a blocked recv passes 0: its lead member must
        ship now regardless of batch size)."""
        k = (sdev, ddev)
        parked = self._parked_ann.get(k, {})
        if lead is None:
            waiting = sum(h.get("g", 1) for h in parked.values()
                          if h.get("k") == "e")
            if waiting < min_segs:
                return -1
        members = list(lead or [])
        free = self.eager_window - self.pool_segments(sdev, ddev)
        for q in sorted(parked):
            h = parked[q]
            g = h.get("g", 1)
            if h.get("k") != "e" or g > free:
                continue
            members.append((q, h))
            free -= g
            self._pool_segs[k] = self._pool_segs.get(k, 0) + g
            parked.pop(q)
            self._batch_hdrs[(sdev, ddev, q)] = h
        if not members:
            return -1
        if len(members) > 2:
            # quantize the member count to a power of two: the sender's
            # per-batch concatenate is a distinct compiled program per
            # (count, shapes) signature, and organic counts never repeat
            # — truncation leaves the remainder parked for the NEXT batch
            # (which also smooths the move pipeline's cadence). Reserved
            # pool segments for the dropped tail are returned.
            keep = 1 << (len(members).bit_length() - 1)
            for q, h in members[keep:]:
                parked[q] = h
                del self._batch_hdrs[(sdev, ddev, q)]
                self._pool_segs[k] -= h.get("g", 1)
            members = members[:keep]
        client = _client()
        idx = self._kincr(client, f"{self.ns}/sn")
        rec = {"s": sdev, "d": ddev, "k": "b",
               "ms": [[q, h["n"], h["dt"]] for q, h in members]}
        dts = {h["dt"] for _q, h in members}
        if len(dts) == 1:
            # homogeneous batch (the common case): the move runs in the
            # payload dtype directly — no per-message byte bitcasts on
            # either side (profiled: 3 dispatches/message on the eager
            # loop)
            rec["wdt"] = next(iter(dts))
        self._kset(client, f"{self.ns}/s/{idx}", json.dumps(rec))
        return idx

    def pool_release(self, sdev: int, ddev: int, nseg: int) -> None:
        """Return drained rx-pool segments (recv copied the payload out)."""
        k = (sdev, ddev)
        self._pool_segs[k] = max(self._pool_segs.get(k, 0) - nseg, 0)

    # -- the mover ---------------------------------------------------------

    def _program(self, sdev: int, ddev: int, count: int, wdt: str):
        """Pair-mesh move program: one ppermute over Mesh([src, dst]) — the
        single RDMA WRITE analog (ccl_offload_control.c:604-612). Cached per
        (pair, shape, dtype); both endpoint processes compile identically.
        """
        key = (sdev, ddev, count, wdt)
        hit = self._progs.get(key)
        if hit is not None:
            return hit
        import jax
        from jax import lax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from jax import shard_map

        mesh = Mesh(np.array([self._dev_by_id[sdev], self._dev_by_id[ddev]]),
                    ("pair",))
        sharding = NamedSharding(mesh, P("pair"))
        prog = jax.jit(shard_map(
            lambda x: lax.ppermute(x, "pair", [(0, 1)]),
            mesh=mesh, in_specs=P("pair"), out_specs=P("pair"),
            check_vma=False))
        self._progs[key] = (prog, sharding)
        return prog, sharding

    @staticmethod
    def _to_bytes(x):
        """(1, n) any-dtype shard -> (1, n*itemsize) uint8 view (bitcast;
        lets one coalesced move carry a mixed-dtype eager batch)."""
        import jax
        import jax.numpy as jnp

        if x.dtype == jnp.uint8:
            return x
        return jax.lax.bitcast_convert_type(x, jnp.uint8).reshape(1, -1)

    @staticmethod
    def _from_bytes(b, dt: str, n: int):
        """Invert :meth:`_to_bytes` for one message of ``n`` elements."""
        import jax
        import numpy as _np

        npdt = _np.dtype(dt)
        if npdt == _np.uint8:
            return b[:, :n]
        return jax.lax.bitcast_convert_type(
            b.reshape(1, n, npdt.itemsize), npdt)

    def _execute_batch(self, rec: dict) -> None:
        """Run one coalesced eager-batch move: every member message rides
        a single pair-mesh byte program (one collective entry for the
        whole credit window instead of one per message). On the receive
        side the matched message delivers immediately; the rest fill the
        rx pool for later local matching."""
        import jax

        sdev, ddev = rec["s"], rec["d"]
        ms = rec["ms"]
        # homogeneous batches move in the payload dtype (no bitcasts);
        # mixed-dtype batches fall back to a uint8 byte wire
        wdt = rec.get("wdt", "uint8")
        unit = np.dtype(wdt).itemsize
        total = sum(int(n) * np.dtype(dt).itemsize for _q, n, dt in ms)
        # quantize the wire size to the next power of two: every distinct
        # move size is a distinct compiled pair program, and organic batch
        # sizes are all distinct — profiled, recompiles were ~40% of the
        # eager loop's wall time. Power-of-two buckets cap the program
        # cache at ~log2(window) entries per pair for <=2x padding.
        elems = total // unit
        wire = 1 << max(elems - 1, 1).bit_length()
        i_send = self._dev_by_id[sdev].process_index == self._me
        prog, sharding = self._program(sdev, ddev, wire, wdt)
        def zeros_on(dev, n):
            key = (dev.id, n, wdt)
            hit = self._zeros.get(key)
            if hit is None:
                if len(self._zeros) >= 64:
                    # sender pad sizes (wire - organic total) are
                    # unbounded in variety; a hard cap keeps the cache
                    # from becoming a slow device-memory leak under
                    # mixed-size traffic (receiver pads are pow2-bounded
                    # and re-cache immediately)
                    self._zeros.clear()
                hit = jax.device_put(np.zeros((1, n), np.dtype(wdt)), dev)
                self._zeros[key] = hit
            return hit

        if i_send:
            parts, freed = [], 0
            for q, _n, _dt in ms:
                shard, credits = self._staged.pop((sdev, ddev, int(q)))
                parts.append(shard if wdt != "uint8"
                             else self._to_bytes(shard))
                freed += credits
            if wire > elems:
                parts.append(zeros_on(self._dev_by_id[sdev], wire - elems))
            if len(parts) == 1:
                shard = parts[0]
            else:
                import jax.numpy as jnp

                shard = jnp.concatenate(parts, axis=-1)
        else:
            # cached landing pad (immutable; the move never donates it)
            shard = zeros_on(self._dev_by_id[ddev], wire)
        garr = jax.make_array_from_single_device_arrays(
            (2, wire), sharding, [shard])
        with _trace.span("fabric.batch_move", cat="fabric",
                         pair=f"{sdev}->{ddev}", members=len(ms),
                         nbytes=total):
            out = prog(garr)
            jax.block_until_ready(out)
        self.moved_bytes += total
        _metrics.inc("accl_fabric_moves_total",
                     labels=(("kind", "batch"),))
        if i_send:
            if freed:
                k = (sdev, ddev)
                self._staged_segs[k] = max(
                    self._staged_segs.get(k, 0) - freed, 0)
            return
        data = out.addressable_shards[0].data
        off = 0
        for q, n, dt in ms:
            n = int(n)
            if wdt != "uint8":
                arr = data[:, off:off + n]
                off += n
            else:
                nb = n * np.dtype(dt).itemsize
                arr = self._from_bytes(data[:, off:off + nb], dt, n)
                off += nb
            key = (sdev, ddev, int(q))
            header = self._batch_hdrs.pop(key)
            cb = self._accepts.pop(key, None)
            if cb is not None:
                cb(arr)
                # direct delivery drains its reserved pool segments now
                self.pool_release(sdev, ddev, header.get("g", 1))
            else:
                self._pool[key] = (arr, header)

    def _execute(self, rec: dict) -> None:
        """Enter the move program for one schedule record. Both endpoint
        processes call this with the same record at the same cursor; the
        collective blocks until the peer joins (cooperative progress).

        Entering a move is a COMMITMENT: like any SPMD collective (or an
        MPI rendezvous), it cannot be cancelled once entered, so a peer
        that died mid-protocol leaves this side blocked past any timeout.
        That failure mode is resolved at the job level — the launcher's
        mpirun-style abort semantics kill all controllers when one dies
        (launch.py), exactly like the reference's MPI harness."""
        import jax
        import jax.numpy as jnp

        if rec.get("k") == "b":
            return self._execute_batch(rec)
        sdev, ddev, seq = rec["s"], rec["d"], rec["q"]
        count, wdt = rec["n"], rec["dt"]
        i_send = self._dev_by_id[sdev].process_index == self._me
        prog, sharding = self._program(sdev, ddev, count, wdt)
        if i_send:
            shard, credits = self._staged.pop((sdev, ddev, seq))
        else:
            shard = jax.device_put(
                jnp.zeros((1, count), dtype=wdt), self._dev_by_id[ddev])
        garr = jax.make_array_from_single_device_arrays(
            (2, count), sharding, [shard])
        with _trace.span("fabric.move", cat="fabric",
                         pair=f"{sdev}->{ddev}", seq=seq):
            out = prog(garr)
            jax.block_until_ready(out)
        self.moved_bytes += count * np.dtype(wdt).itemsize
        _metrics.inc("accl_fabric_moves_total",
                     labels=(("kind", "single"),))
        if i_send:
            # return exactly the credits this message took (0 for
            # rendezvous — it never entered the eager window)
            if credits:
                k = (sdev, ddev)
                self._staged_segs[k] = max(
                    self._staged_segs.get(k, 0) - credits, 0)
        else:
            cb = self._accepts.pop((sdev, ddev, seq))
            cb(out.addressable_shards[0].data)
        # schedule records are never deleted mid-session: a third process
        # whose cursor has not reached this index yet must still read it to
        # skip — a hole would look like "not yet published" and stall its
        # scheduler. ~100 B/message in the coordinator, which dies with the
        # job (the reference's exchange memory persists the same way).

    def drive(self) -> bool:
        """Advance the global move schedule: execute (or skip) every
        published record from the cursor on, in index order — the
        cooperative dispatch loop (``wait_for_call`` round-robin,
        ccl_offload_control.c:2264-2288). Returns whether anything ran.

        Also refreshes this controller's heartbeat lease: progress IS
        liveness here (the cooperative single-threaded dispatch model),
        so the lease is renewed from the same loop that executes moves —
        a controller that stops driving stops leasing, and its peers'
        blocked waits can retire with PEER_FAILED instead of hanging."""
        if _fault.ENABLED:
            # the chaos harness's rank-death site: fires in the progress
            # loop like a real mid-protocol crash (RankDeath is a
            # BaseException — no protocol except-arm may swallow it).
            # die/delay only: nothing absorbs a transient here, so a
            # fail-kind spec would leak a raw FaultInjected into the app
            _fault.point("rank.death", kinds=("die", "delay"))
        client = _client()
        self._maybe_heartbeat(client)
        self._maybe_publish_obs(client)
        progressed = False
        while True:
            v = self._try_get(client, f"{self.ns}/s/{self._cursor}")
            if v is None:
                if not progressed:
                    # idle cycle: spend it on deferred announcement-key
                    # cleanup instead of pure polling
                    self._flush_deletes(client)
                return progressed
            rec = json.loads(v)
            sp = self._dev_by_id[rec["s"]].process_index
            dp = self._dev_by_id[rec["d"]].process_index
            if self._me in (sp, dp):
                self._execute(rec)
                progressed = True
            self._cursor += 1

    # -- peer liveness (heartbeat leases) ----------------------------------

    def set_resilience(self, retry_policy: _fault.RetryPolicy,
                       heartbeat_interval_s: float,
                       heartbeat_timeout_s: float) -> None:
        """Config write-through (the ``flash_bwd`` pattern): applied by
        the ACCL config setter on EVERY assignment, so a replaced config
        never leaves the fabric on a stale retry/liveness policy."""
        self._retry = retry_policy
        self.heartbeat_interval = float(heartbeat_interval_s)
        self.heartbeat_timeout = float(heartbeat_timeout_s)

    def _maybe_heartbeat(self, client) -> None:
        """Refresh this controller's lease key at most once per
        ``heartbeat_interval`` (the cheap common case is one monotonic
        read). The lease VALUE is a local counter, not a timestamp:
        peers measure staleness as value-unchanged-for-too-long on their
        OWN clock, so skew between hosts cannot fake a death."""
        if self.heartbeat_timeout <= 0:
            return
        now = time.monotonic()
        if now - self._hb_last < self.heartbeat_interval:
            return
        self._hb_last = now
        self._hb_count += 1
        self._kset_force(client, f"{self.ns}/hb/{self._me}",
                         str(self._hb_count))

    def _maybe_publish_obs(self, client) -> None:
        """Publish this rank's metrics snapshot to the epoch namespace
        at most once per ``cluster.PUBLISH_INTERVAL_S`` — the heartbeat
        cadence discipline: progress-driven (a rank that stops pumping
        goes stale, which the merge annotates), never blocking dispatch
        (one rate-limit check per drive() on the common path). Counted
        ``accl_cluster_snapshot_total{published}``."""
        if not _metrics.ENABLED:
            return
        now = time.monotonic()
        if now - self._obs_last < _cluster.PUBLISH_INTERVAL_S:
            return
        self._obs_last = now
        self._kset_force(client,
                         _cluster.KEY_FMT.format(ns=self.ns, proc=self._me),
                         _cluster.payload(self._me))

    def collect_obs(self, procs) -> Dict[int, Optional[str]]:
        """Pull every rank's latest published snapshot blob from the
        epoch namespace (None for a rank that has not published in this
        epoch) — the read side ``ACCL.cluster_stats()`` merges."""
        client = _client()
        out: Dict[int, Optional[str]] = {}
        for p in procs:
            out[int(p)] = self._try_get(
                client, _cluster.KEY_FMT.format(ns=self.ns, proc=p))
        return out

    def check_peers(self, procs: Optional[list] = None) -> List[int]:
        """Poll peer heartbeat leases (rate-limited to one sweep per
        ``heartbeat_interval``); returns the known-dead process ids among
        ``procs`` (default: every other process). A peer is dead when its
        OBSERVED lease value has not changed for ``heartbeat_timeout``
        seconds of local watching. A lease must exist before it can
        expire: a peer that has not published in this epoch yet (still
        importing, still recovering into the epoch) is merely unknown,
        not dead — its waits stay bounded by the ordinary operation
        timeouts instead. This is what lets recovering ranks race into a
        fresh epoch at different speeds without false-positive verdicts.
        Each death is counted once (``accl_peer_death_total{proc}``) and
        latched until the next epoch (``bump_epoch`` clears them)."""
        if self.heartbeat_timeout <= 0:
            return []
        # fast path FIRST: the wait loops call this per iteration, so
        # between sweeps the whole cost is one monotonic read and an
        # empty-set check — nothing below (import, process enumeration,
        # sorting) runs unless a sweep is due or a verdict is latched
        now = time.monotonic()
        if now - self._peer_check_last >= self.heartbeat_interval:
            self._peer_check_last = now
            import jax

            sweep = (range(jax.process_count()) if procs is None else procs)
            client = _client()
            for p in sweep:
                if (p == self._me or p in self._dead_peers
                        or p in self._excluded):
                    continue
                v = self._try_get(client, f"{self.ns}/hb/{p}")
                if v is None:
                    continue  # no lease in this epoch yet: unknown, not dead
                seen = self._peer_seen.get(p)
                if seen is None or seen[0] != v:
                    self._peer_seen[p] = (v, now)
                elif now - seen[1] > self.heartbeat_timeout:
                    self._dead_peers.add(p)
                    _metrics.inc("accl_peer_death_total",
                                 labels=(("proc", str(p)),))
                    # the verdict LATCH is the flight event — a survivor
                    # that never blocks on the dead rank (so never takes
                    # raise_if_peer_failed) still carries the death in
                    # its ring when recover() dumps it
                    _flight.record("peer_failed", what="lease_expired",
                                   dead=[p], epoch=self.epoch)
        if not self._dead_peers:
            return []
        if procs is None:
            return sorted(self._dead_peers)
        return sorted(p for p in self._dead_peers if p in procs)

    def raise_if_peer_failed(self, what: str,
                             procs: Optional[list] = None) -> None:
        """Bounded-failure verdict for blocked waits: raise
        :class:`ACCLPeerFailedError` when a peer this wait depends on is
        dead, instead of blocking until the (much longer) operation
        timeout. The no-death fast path costs one monotonic read."""
        dead = self.check_peers(procs)
        if dead:
            # black-box the verdict ONCE per dead set per epoch (this
            # raise fires on every wait iteration once a verdict is
            # latched — the flight dump must not)
            mark = (self.epoch, tuple(dead))
            if mark not in self._flight_dumped_deaths:
                self._flight_dumped_deaths.add(mark)
                _flight.record("peer_failed", what=what, dead=list(dead),
                               epoch=self.epoch)
                _flight.dump("peer_failed")
            raise ACCLPeerFailedError(dead, what)

    @property
    def dead_peers(self) -> List[int]:
        """Latched liveness verdicts (introspection for stats()/scan())."""
        return sorted(self._dead_peers)

    def exclude_peers(self, procs) -> None:
        """Remove processes from the fabric's world for the rest of the
        session (the shrink recovery's rank-loss commitment): liveness
        sweeps skip them forever — across epoch bumps, which clear
        ordinary verdicts — so a shrunk mesh never re-litigates a death
        it already recovered from."""
        self._excluded.update(int(p) for p in procs)

    @property
    def excluded_peers(self) -> List[int]:
        """Processes removed by survivor-subset recoveries (permanent,
        unlike the per-epoch ``dead_peers`` verdicts)."""
        return sorted(self._excluded)

    def bump_epoch(self) -> int:
        """Elastic re-handshake step (``ACCL.recover``): abandon the
        current key namespace WHOLESALE — a poisoned session's leftover
        announcements, schedule records, barrier counters and leases all
        live under the old nonce-derived prefix, so a fresh epoch suffix
        makes them unreachable rather than trying to repair them (the
        same crashed-rerun discipline the session nonce itself follows).
        Local per-pair protocol state resets with it: seqs restart at 1,
        the schedule cursor at the fresh namespace's counter, barrier
        rounds at 0. Compiled pair-move programs are pure functions of
        (pair, shape, dtype) and survive. Liveness verdicts clear — a
        recovered rank may rejoin (elastic rejoin), and a truly-gone rank
        is simply never heard from again in the new epoch."""
        self.epoch += 1
        self.ns = (f"accl/{self.session[-8:]}.{self.instance}"
                   f".e{self.epoch}")
        for d in (self._out_seq, self._staged, self._staged_segs,
                  self._fetch_seq, self._parked_ann, self._accepts,
                  self._pool, self._pool_segs, self._batch_hdrs,
                  self._barrier_pending, self._peer_seen):
            d.clear()
        self._reserved.clear()
        self._dead_peers.clear()
        self._pending_deletes.clear()
        self._hb_last = 0.0
        self._peer_check_last = 0.0
        self._hb_count = 0
        self._cursor = self._kcount(_client(), f"{self.ns}/sn") + 1
        # publish the epoch under the EPOCH-INDEPENDENT base prefix:
        # self.epoch alone is local state, and a controller that
        # restarts from scratch mid-job (outside today's recover()
        # contract — every participant must be a live fabric calling
        # recover() in step) would otherwise have no way to discover
        # which namespace the mesh moved to. This key is the hook the
        # restart-rejoin extension reads; last-writer-wins is fine (all
        # recovering controllers write the same value).
        self._kset_force(_client(),
                         f"accl/{self.session[-8:]}.{self.instance}/epoch",
                         str(self.epoch))
        # lease the new epoch immediately: recovering peers racing into
        # the epoch at different speeds see this controller as alive the
        # moment it arrives, not one progress-loop later
        self._maybe_heartbeat(_client())
        _metrics.inc("accl_session_epoch_total")
        _flight.record("epoch_bump", epoch=self.epoch)
        _correlate.set_epoch(self.epoch)
        # fresh epoch namespace: re-publish the snapshot promptly so the
        # cluster plane never goes dark across a recovery
        self._obs_last = 0.0
        return self.epoch

    # -- barrier -----------------------------------------------------------

    def barrier(self, name: str = "all",
                process_ids: Optional[list] = None,
                pump: Optional[Callable[[], bool]] = None) -> None:
        """Barrier over a process subset that keeps the mover driving while
        it waits — required because a peer may be blocked inside a pair
        move this process must co-execute before it can arrive. Scoped per
        ``name`` (one per communicator), fixing the all-process
        over-synchronization of the round-2 fabric. ``pump`` (the session's
        cooperative scheduler) is preferred over the raw mover so parked
        continuations — e.g. a credit-starved async send that still needs
        to announce — also progress while this process waits.

        One MONOTONIC counter per name, no epoch bookkeeping: arrival i
        belongs to round (i-1)//n and passes when the count reaches the
        round's full multiple of n. The counter persists in the
        coordinator, so a fabric created after an earlier session's
        teardown inherits a consistent state (any completed history is a
        multiple of n) instead of colliding with stale per-epoch keys.

        A TIMED-OUT arrival stays pending rather than being abandoned
        mid-round: the next barrier call on the same name re-waits on the
        recorded target instead of incrementing again. Otherwise the
        retry's own arrival would complete the broken round by itself and
        pass instantly with no peer present — a barrier that silently
        stops synchronizing. With the pending arrival consumed on retry,
        a timeout keeps fail-stop semantics: the retry blocks until the
        laggard actually arrives (like the per-epoch scheme it replaced),
        and per-process call counts stay matched 1:1 with arrivals."""
        import jax

        client = _client()
        n = len(process_ids) if process_ids is not None else jax.process_count()
        key = f"{self.ns}/b/{name}"
        pending = self._barrier_pending.get(key)
        if pending is not None and pending[1] != n:
            # a retry with a different participant set would silently
            # re-wait the stale round's target (ADVICE r3 #3) — the retry
            # contract is same-name, same-scope
            raise ACCLError(
                errorCode.CONFIG_ERROR,
                f"barrier {name!r}: retry with {n} participants, but the "
                f"pending timed-out round expected {pending[1]}")
        if pending is None:
            # the arrival rides the barrier.arrive injection point:
            # delay stretches the round (a laggard rank), fail/prob/drop
            # lose the arrival ATTEMPT (fired before the increment, so
            # the policy's retry never double-counts), die kills the rank
            arrive = self._kv_call(
                "barrier.arrive", lambda: self._kincr(client, key),
                retry_real=False)
            target = ((arrive - 1) // n + 1) * n
            self._barrier_pending[key] = (target, n)
        else:
            target = pending[0]
        deadline = time.monotonic() + self.timeout
        progress = pump or self.drive
        idle = 0
        while self._kcount(client, key) < target:
            if not progress():
                idle += 1
                self.poll_sleep(idle)
            else:
                idle = 0
            # bounded failure: a dead participant retires this wait with
            # PEER_FAILED well inside the timeout — the arrival stays
            # pending, so a post-recovery retry keeps the same-round
            # semantics documented above
            self.raise_if_peer_failed(f"barrier {name!r}",
                                      procs=process_ids)
            if time.monotonic() > deadline:
                raise ACCLTimeoutError(
                    f"barrier {name!r}: {self._kcount(client, key)}/"
                    f"{target} arrivals within {self.timeout}s")
        del self._barrier_pending[key]
        if name == "epoch":
            # the epoch-entry handshake: every participant exits this
            # round within one KV poll of each other, so its exit is the
            # cross-rank clock anchor the trace --merge CLI aligns on
            _trace.sync_mark(f"epoch{self.epoch}")
