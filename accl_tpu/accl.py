"""The public ACCL-TPU host API.

TPU-native re-expression of ``class ACCL`` (``driver/xrt/include/accl.hpp:
46-1148``, ``driver/xrt/src/accl.cpp:30-1461``): one method per primitive /
collective, buffer factories, communicator management, config calls and
debug dumps. Differences from the reference are architectural, not
functional:

* the CCLO offload engine + MicroBlaze firmware dispatch loop collapse into
  **compiled XLA programs** held in a :class:`ProgramCache` — the "call" is
  a cache lookup + program launch instead of a 15-word MMIO command;
* the FPGA/Sim/Coyote device backends collapse into the mesh the
  communicator is built over (real TPU devices or
  ``--xla_force_host_platform_device_count`` CPU devices — the emulator rung
  of the reference's test ladder);
* buffers are shards of global ``jax.Array``s, so payload never transits the
  host (the host only supervises, exactly like the reference's design goal).
"""
from __future__ import annotations

import time
from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import constants
from .arithconfig import DEFAULT_ARITH_CONFIG, ArithConfig
from .obs import cluster as _cluster
from .obs import correlate as _correlate
from .obs import flight as _flight
from .obs import metrics as _metrics
from .obs import recal as _recal
from .obs import trace as _trace
from .buffer import BaseBuffer, Buffer, BufferSlice, DummyBuffer
from .communicator import Communicator
from .config import ACCLConfig, Algorithm, TransportBackend
from .constants import (
    ACCLError,
    TAG_ANY,
    dataType,
    errorCode,
    operation,
    reduceFunction,
)
from .parallel import algorithms, primitives
from .parallel.compiler import ProgramCache
from .request import Request, RequestQueue, requestStatus
from .rxpool import CallQueue
from .sendrecv import MatchingEngine, RecvPost, SendPost
from .utils.logging import get_logger

log = get_logger("accl")

BufLike = Union[Buffer, BufferSlice]

# pre-built label tuples for the scheduler counters: the pump loop is the
# hottest host path, so even label construction stays off it
_L_PARK = (("event", "park"),)
_L_RESUME = (("event", "resume"),)
_L_REPUMP = (("event", "repump"),)
_L_EAGER = (("protocol", "eager"),)
_L_RDV = (("protocol", "rendezvous"),)
_L_EAGER_X = (("protocol", "eager_cross"),)
_L_RDV_X = (("protocol", "rendezvous_cross"),)

#: terminal request states a parked continuation must stand down on —
#: PEER_FAILED included: a request retired by the death verdict must not
#: keep announcing/matching (delivering into the caller's buffer after
#: the failure was surfaced)
_TERMINAL = (requestStatus.COMPLETED, requestStatus.ERROR,
             requestStatus.PEER_FAILED)


class ACCL:
    """Entry point. One instance supervises one device group.

    Construction + :meth:`initialize` mirror the reference bring-up sequence
    (``ACCL::initialize``, accl.cpp:1082-1130): capability check, communicator
    setup, arithmetic config registration, tuning parameters. The rx-buffer
    ring and spare rendezvous buffers have no TPU analog (XLA manages staging
    memory), so those steps dissolve.
    """

    # config is a write-through property: session knobs that steer
    # module-level kernel policy (the flash backward mode) are applied on
    # EVERY assignment — init, autotune adoption, runtime setters — so a
    # replaced config never leaves the kernel layer on a stale policy.
    @property
    def config(self) -> ACCLConfig:
        return self._config

    @config.setter
    def config(self, cfg: ACCLConfig) -> None:
        self._config = cfg
        from .ops import collective_alltoall as _a2a_ops
        from .ops import collective_matmul as _cm_ops
        from .ops import flash as _flash_ops

        _flash_ops.set_flash_bwd_mode(cfg.flash_bwd)
        _flash_ops.set_flash_decode_mode(cfg.flash_decode)
        _flash_ops.set_flash_prefill_mode(cfg.flash_prefill)
        _flash_ops.set_kv_cache_dtype(cfg.kv_cache_dtype)
        _flash_ops.set_kv_quant_scale(cfg.kv_quant_scale)
        _cm_ops.set_overlap_enabled(cfg.cmatmul_overlap)
        _cm_ops.set_overlap_thresholds(cfg.ag_matmul_threshold,
                                       cfg.rs_matmul_threshold)
        _cm_ops.set_overlap_class_thresholds(
            cfg.ag_matmul_class_thresholds, cfg.rs_matmul_class_thresholds)
        _cm_ops.set_wire_dtype(cfg.cmatmul_wire_dtype)
        _cm_ops.set_nblock_enabled(cfg.cmatmul_nblock)
        _a2a_ops.set_overlap_enabled(cfg.moe_overlap)
        _a2a_ops.set_overlap_threshold(cfg.a2a_matmul_threshold)
        _a2a_ops.set_dw_overlap_enabled(cfg.moe_dw_overlap)
        # the DCN cross-slice wire dtype (two-tier schedules) validates
        # and writes through like the cmatmul wire register
        from .parallel import hierarchical as _hier

        _hier.set_dcn_wire_dtype(cfg.dcn_wire_dtype)
        from .models import zero as _zero_model

        _zero_model.set_overlap_enabled(cfg.zero_overlap)
        _zero_model.set_prefetch_enabled(cfg.zero_prefetch)
        _zero_model.set_replicas_enabled(cfg.shard_replicas)
        from .models import publish as _publish_model

        _publish_model.set_fused_enabled(cfg.publish_fused)
        from .models import pipeline as _pp_model
        from .ops import pipeline_relay as _pp_relay

        _pp_model.set_schedule(cfg.pp_schedule)
        _pp_model.set_interleave(cfg.pp_interleave)
        _pp_model.set_cost_config(cfg)
        _pp_relay.set_overlap_enabled(cfg.pp_overlap)
        # the program cache's LRU bound follows the config on every
        # assignment (the setter can run from __init__ before the cache
        # exists — construction applies the bound itself then)
        if hasattr(self, "_programs"):
            self._programs.set_maxsize(cfg.program_cache_size)
        # the online-recalibration arm follows the config the same way:
        # arming installs the metrics-side sample hook, disarming removes
        # it (default-off keeps the timed hot path at one None read)
        _recal.set_enabled(cfg.sched_online_recal)
        # resilience registers write through to the live fabric (the
        # flash_bwd pattern): the retry/backoff policy and the heartbeat
        # lease cadence/staleness window follow every config assignment
        if getattr(self, "_fabric", None) is not None:
            from . import fault as _fault

            self._fabric.set_resilience(
                _fault.policy_from_config(cfg),
                cfg.heartbeat_interval_s, cfg.heartbeat_timeout_s)

    def __init__(
        self,
        devices: Optional[Sequence[jax.Device]] = None,
        config: Optional[ACCLConfig] = None,
    ):
        self.config = config or ACCLConfig()
        if devices is not None:
            self._devices = list(devices)  # explicit order is the caller's
        else:
            self._devices = list(jax.devices())
            if self.config.topology_order:
                from .utils.bringup import snake_order
                self._devices = snake_order(self._devices)
        self.comms: List[Communicator] = []
        self._programs = ProgramCache(self.config.program_cache_size)
        self._queue = RequestQueue()
        self._matchers: dict[int, MatchingEngine] = {}
        self._arith_configs = dict(DEFAULT_ARITH_CONFIG)
        # cooperative scheduler: parked calls resumable by current_step
        self._sched = CallQueue()
        self._parked_calls: dict[int, object] = {}
        self._next_call_id = 1
        self._initialized = False
        self.initialize()

    # ------------------------------------------------------------------
    # bring-up / teardown
    # ------------------------------------------------------------------

    def initialize(self) -> None:
        """accl.cpp:1082-1130 analog."""
        if self._initialized:
            return
        # fresh session: the once-per-pair fallback warning sets are
        # module-global and must not inherit a prior session's silence
        algorithms.reset_global_fallback_warnings()
        from .ops import collective_matmul as _cm_ops

        _cm_ops.reset_fallback_warnings()
        # the schedule-plan cache is module-global too: a new session's
        # config (declared torus shape, cost params, seeds) must
        # re-synthesize, never inherit another session's plans
        from .parallel import synth as _synth

        _synth.reset_plan_cache()
        # session epoch: bumped by every recover() and baked into the
        # program-cache keys AND the synth plan-cache keys, so a plan or
        # program resolved before a rank death is unreachable afterwards
        # even where the rest of the key collides (docs/resilience.md §5)
        self._epoch = 0
        _synth.set_session_epoch(0)
        # correlation ids (obs/correlate): armed by $ACCL_CORRELATE.
        # Every controller of a launch shares the environment, so the
        # wire-framing change (the optional eager header key / serving
        # control words) is symmetric across the mesh by construction.
        if _correlate.env_armed():
            _correlate.enable()
        _correlate.set_epoch(0)
        _correlate.set_proc(jax.process_index())
        if self.config.transport is None:
            from .utils.bringup import detect_backend

            self.config = self.config.replace(
                transport=detect_backend(self._devices))
        _ = self.parse_hwid()
        comm = Communicator(
            self._devices, max_segment_size=self.config.segment_size
        )
        self.comms.append(comm)
        self._matchers[id(comm)] = MatchingEngine(
            comm, rx_buffer_count=self.config.eager_rx_buffer_count)
        # native per-request timing registry (PERFCNT analog) when the C++
        # runtime backs the session
        self._reqreg = self._matchers[id(comm)]._native
        self._fabric = None
        # autotune decision-round counter, namespaced under the fabric's
        # session nonce (SPMD call discipline keeps it mesh-aligned)
        self._tune_round = 0
        if comm.is_multiprocess:
            from . import fault as _fault
            from .multiproc import CrossProcessFabric

            self._fabric = CrossProcessFabric(
                timeout=self.config.timeout,
                eager_window=self.config.eager_rx_buffer_count,
                eager_seg_bytes=self.config.eager_rx_buffer_size,
                retry_policy=_fault.policy_from_config(self.config),
                heartbeat_interval_s=self.config.heartbeat_interval_s,
                heartbeat_timeout_s=self.config.heartbeat_timeout_s)
        # metrics baseline: ACCL.stats() reports the delta since THIS
        # bring-up, so a long-lived process with several sessions gets
        # per-session attribution out of one process-global registry
        self._metrics_baseline = _metrics.snapshot()
        self._initialized = True
        log.info("initialized: %s", self.parse_hwid())

    def parse_hwid(self) -> dict:
        """Capability word decode (``ACCL::parse_hwid``, accl.cpp:1066-1080)."""
        plat = self._devices[0].platform if self._devices else "none"
        return {
            "platform": plat,
            "world_size": len(self._devices),
            "transport": (self.config.transport.value
                          if self.config.transport else "auto"),
            "arith_enabled": self.config.enable_arith,
            "compression_enabled": self.config.enable_compression,
            "device_kind": getattr(self._devices[0], "device_kind", plat)
            if self._devices
            else "none",
        }

    def scan(self) -> list:
        """Per-device topology/memory introspection — the ``xclbin_scan``
        analog (``driver/xrt/src/xclbin_scan.cpp``: ip_layout discovery of
        CCLO instances and connectivity; here: device kind, ICI coords,
        host process and live HBM stats per mesh participant). Ranks this
        controller owns also report its LIVE protocol state — in-flight
        queue depth, parked continuations, eager rx-pool free/total slots
        — so one scan is a real introspection surface, not just a static
        topology table (ISSUE r8)."""
        me = jax.process_index()
        pool = (self.matcher().rx_pool if self._matchers else None)
        live = {
            "queue_depth": len(self._queue.inflight),
            "parked_continuations": len(self._parked_calls),
            "rx_pool_free": pool.free_slots if pool else None,
            "rx_pool_total": pool.size if pool else None,
            # liveness verdicts this controller has latched (heartbeat
            # leases, docs/resilience.md): dead peer PROCESS ids —
            # every rank a listed process owns is presumed failed
            "dead_peers": (self._fabric.dead_peers
                           if self._fabric is not None else []),
            # processes a survivor-subset recovery removed for good
            # (distinct from the per-epoch dead_peers verdicts)
            "excluded_peers": (self._fabric.excluded_peers
                               if self._fabric is not None else []),
        }
        out = []
        for rank, d in enumerate(self._devices):
            rec = {
                "rank": rank,
                "id": d.id,
                "platform": d.platform,
                "kind": getattr(d, "device_kind", d.platform),
                "process_index": getattr(d, "process_index", 0),
            }
            if getattr(d, "process_index", 0) == me:
                # controller-local state: the supervising host's view for
                # the ranks it owns (a remote controller's scan reports
                # its own)
                rec.update(live)
            coords = getattr(d, "coords", None)
            if coords is not None:
                rec["coords"] = tuple(coords)          # ICI topology position
                rec["core_on_chip"] = getattr(d, "core_on_chip", 0)
            try:
                stats = d.memory_stats()
                if stats:
                    rec["bytes_in_use"] = stats.get("bytes_in_use")
                    rec["bytes_limit"] = stats.get("bytes_limit")
            except Exception:  # backends without memory stats (CPU)
                pass
            out.append(rec)
        return out

    def profile(self, log_dir: str):
        """Device-timeline trace over a region — the tracing tier above
        per-call ``Request.get_duration_ns`` (SURVEY.md §5: PERFCNT gives
        per-call cycles; xprof gives the full timeline)::

            with acc.profile("/tmp/trace"):
                acc.allreduce(...)

        View with TensorBoard / xprof."""
        return jax.profiler.trace(log_dir)

    def deinit(self) -> None:
        """Drain outstanding work and drop state (``ACCL::deinit``, accl.cpp:71-89)."""
        self._queue.cancel_externals()
        self._queue.drain(timeout=self.config.timeout)
        if _flight.had_fatal():
            # fatal teardown: the session saw a death/invalidation
            # verdict — preserve the protocol history before state drops
            _flight.dump("teardown")
        self._programs.clear()
        self._matchers.clear()
        self.comms.clear()
        self._initialized = False

    def soft_reset(self) -> None:
        """Drop pending sends/recvs, program cache and seq counters
        (cfgFunc::reset_periph, ccl_offload_control.c:2249-2261 — drops the
        retry queue and resets peripherals). Sequence counters reset with the
        matching state or the pair ordering would desync forever."""
        self._queue.cancel_externals()
        # drop the retry queue BEFORE matcher state: stale parked
        # continuations must never replay tail segments of a cancelled
        # message with fresh seqns
        self._sched.clear()
        self._parked_calls.clear()
        if self._fabric is not None:
            # tombstone reserved-but-unannounced cross-process seqs the
            # dropped continuations would otherwise strand (peer fetch
            # cursors must never stall on a hole)
            self._fabric.reset()
        for m in self._matchers.values():
            m.clear()
        for comm in self.comms:
            comm.reset_sequences()
        self._programs.clear()

    def recover(self, process_ids: Optional[List[int]] = None) -> int:
        """Elastic session re-handshake (docs/resilience.md): converge a
        FRESH session epoch after a peer failure, among every controller
        that calls this (SPMD-aligned, like any fabric operation) — the
        surviving ranks after a death verdict, plus any rank whose own
        session state was poisoned (an injected/caught ``RankDeath``)
        rejoining elastically.

        Steps, all through the existing reset paths: cancel parked
        externals and drop the cooperative retry queue; clear matcher /
        rx-pool / per-pair sequence state; invalidate the program and
        schedule-plan caches (a recovered mesh must re-resolve, never
        replay a dead epoch's plans); then bump the fabric epoch — a
        fresh nonce-derived key namespace, making every leftover
        announcement/schedule/barrier/lease key of the poisoned epoch
        unreachable — and re-run the bootstrap handshake (every
        participant arrives at the new epoch's barrier). Returns the new
        epoch number (0 when no fabric: single-process recovery is just
        the local resets).

        The caller contract is fail-stop-per-call, elastic-per-session:
        the interrupted collective is NOT resumed — its requests were
        retired with PEER_FAILED/cancel verdicts — the application
        re-issues work in the new epoch.

        **Shrink mode (survivor-subset recovery, round 15).** When a
        rank is TRULY gone — ``process_ids`` omitted while the fabric
        has latched death verdicts (``fabric.dead_peers``), or an
        explicit ``process_ids`` naming a strict subset of the mesh's
        processes — the session recovers onto the SURVIVOR mesh instead
        of waiting forever for the full world: ``process_ids`` defaults
        to every mesh process minus the dead set (the full-world
        re-handshake stays available by passing it explicitly), only the
        survivors meet at the epoch barrier, and after convergence the
        mesh itself shrinks (:meth:`_shrink_mesh`): the global
        communicator is rebuilt via ``Communicator.split`` over the
        surviving rank indices — dense new local ranks, original devices
        (and process ids) retained for addressing — while every
        communicator spanning a dead rank is invalidated and counted.
        Each recovery counts ``accl_recover_total{mode=shrink|full}``,
        and the session epoch is baked into the program- and
        schedule-plan cache keys so nothing resolved before the death is
        dispatchable after it."""
        # ONE local-reset implementation: soft_reset owns the ordering
        # invariants (retry queue dropped before matcher state, fabric
        # tombstones — harmless extra writes to the abandoned namespace)
        self.soft_reset()
        from .parallel import synth as _synth

        _synth.reset_plan_cache()
        epoch = 0
        mode = "full"
        dead_procs: List[int] = []
        if self._fabric is not None:
            mesh_procs = sorted({getattr(d, "process_index", 0)
                                 for d in self.comms[0].devices})
            process_ids, dead_procs, mode = self._recover_participants(
                process_ids, mesh_procs)
            epoch = self._fabric.bump_epoch()
            # bootstrap re-handshake: all recovering controllers meet at
            # the fresh namespace's first barrier round (the arrival
            # counter starts at 0 there by construction). process_ids
            # names the SURVIVOR set when a rank is truly gone and will
            # not rejoin; default is the full mesh (elastic rejoin)
            self._fabric.barrier("epoch", process_ids=process_ids,
                                 pump=self._pump)
            if dead_procs:
                self._shrink_mesh(dead_procs)
                # rank loss is a commitment: the excluded processes stay
                # outside the liveness sweeps for the whole session (an
                # epoch bump clears ordinary verdicts for elastic
                # rejoin; a shrunk-away process must never re-latch one)
                self._fabric.exclude_peers(dead_procs)
        # epoch-keyed caches: every recovery (the fabric-less rung
        # included) bumps the session epoch, so no pre-death program or
        # plan cache key can collide with a post-recovery resolution
        self._epoch += 1
        _synth.set_session_epoch(self._epoch)
        _metrics.inc("accl_recover_total", labels=(("mode", mode),))
        # the recovery itself is a flight-dump trigger: the dump holds
        # the death verdict / invalidation events that led here
        _flight.record("recover", mode=mode, fabric_epoch=epoch,
                       session_epoch=self._epoch,
                       dead_procs=sorted(dead_procs))
        _flight.dump("recover")
        log.info("recovered: session epoch %d (%s)", epoch, mode)
        return epoch

    def _recover_participants(self, process_ids, mesh_procs):
        """Resolve the epoch re-handshake participant set: ``(process_ids
        or None, dead mesh procs, mode)``. The round-15 ergonomics: with
        no ``process_ids`` and latched death verdicts on mesh processes,
        the SURVIVOR set is the default (a full-world re-handshake with a
        truly-gone rank can never converge; the full-world form stays
        available by passing the full list explicitly). An explicit
        ``process_ids`` that names a strict subset of the mesh's
        processes also shrinks."""
        dead = set(self._fabric.dead_peers)
        if process_ids is None:
            dead_procs = [p for p in mesh_procs if p in dead]
            if dead_procs:
                return ([p for p in mesh_procs if p not in dead],
                        dead_procs, "shrink")
            return None, [], "full"
        dead_procs = [p for p in mesh_procs if p not in set(process_ids)]
        return (list(process_ids), dead_procs,
                "shrink" if dead_procs else "full")

    def _shrink_mesh(self, dead_procs: List[int]) -> None:
        """Degrade the session's mesh after TRUE rank loss
        (docs/resilience.md §5): replace the global communicator with its
        ``split()`` over the surviving rank indices — dense new local
        ranks, original devices/process ids retained for addressing — and
        invalidate (never repair) every communicator that spans a dead
        rank: groups are cheap to re-create from the shrunk global
        communicator, and a program over a dead device could never
        converge. Surviving sub-communicators (all ranks alive) keep
        working untouched."""
        old = self.comms[0]
        dead_ranks = old.ranks_of_processes(dead_procs)
        if not dead_ranks:
            return
        survivors = [i for i in range(old.world_size)
                     if i not in set(dead_ranks)]
        new_global = old.split(survivors)
        # the survivor mesh genuinely LOST topology (vs an ordinary
        # sub-group): synth's degraded-decline accounting keys off this
        new_global.degraded_from = old.world_size
        keep: List[Communicator] = []
        for comm in self.comms:
            if comm.ranks_of_processes(dead_procs):
                comm.invalidate(
                    f"communicator spans rank(s) owned by dead controller "
                    f"process(es) {sorted(dead_procs)}; re-create the "
                    f"group from the shrunk global communicator")
                _metrics.inc("accl_comm_invalidated_total")
                _flight.record("comm_invalidated",
                               world_size=comm.world_size,
                               dead_procs=sorted(dead_procs))
                self._matchers.pop(id(comm), None)
            else:
                keep.append(comm)
        # one dump at verdict-creation time (the per-comm events above
        # are in it); the recover() caller dumps again post-convergence
        _flight.dump("comm_invalidated")
        self.comms = [new_global] + keep
        # the shrunk mesh IS the session's world now: scan(), world_size
        # and default-comm dispatch all follow it
        self._devices = new_global.devices
        self._matchers[id(new_global)] = MatchingEngine(
            new_global, rx_buffer_count=self.config.eager_rx_buffer_count)
        self._reqreg = self._matchers[id(new_global)]._native
        log.warning(
            "mesh shrunk %d -> %d ranks: dead rank(s) %s on controller "
            "process(es) %s", old.world_size, new_global.world_size,
            dead_ranks, sorted(dead_procs))

    # ------------------------------------------------------------------
    # config calls (cfgFunc runtime tier)
    # ------------------------------------------------------------------

    def set_timeout(self, seconds: float) -> None:
        self.config = self.config.replace(timeout=seconds)
        if self._fabric is not None:
            self._fabric.timeout = seconds

    def set_max_eager_size(self, nbytes: int) -> None:
        self.config = self.config.replace(max_eager_size=nbytes)

    def set_max_rendezvous_size(self, nbytes: int) -> None:
        self.config = self.config.replace(max_rendezvous_size=nbytes)

    def write_arithconfig(self, cfg: ArithConfig) -> None:
        """Register a datapath policy for a dtype pair (``ACCL::
        write_arithconfig``, common.cpp:50-73). Beyond the reference's
        float-cast pairs, quantized integer wires are supported:
        ``ArithConfig(float32, int8, quant_scale=s,
        arith_is_compressed=False)`` sends clip(round(x*s)) int8 on every
        hop and decompresses before any arithmetic.

        **Saturation bound (quantized SUM on hop-recompressing families):**
        RING/TREE/FLAT/PALLAS reduces recompress intermediate partial sums
        on every hop, so every partial must satisfy
        ``|partial sum| <= 127 / quant_scale`` — values beyond the wire
        range clip silently (the int8 wire has no overflow signalling,
        like any fixed-point fabric). Choose ``quant_scale <= 127 /
        (world_size * max|x|)`` for SUM, or use the XLA family, whose
        single decompress-gather-fold never re-enters the wire dtype."""
        if cfg.quant_scale is not None:
            if cfg.arith_is_compressed:
                raise ACCLError(
                    errorCode.COMPRESSION_NOT_SUPPORTED,
                    "quantized wire pairs must decompress before arithmetic "
                    "(set arith_is_compressed=False): integer sums across "
                    "ranks would overflow the wire dtype")
            if cfg.quant_scale <= 0:
                raise ACCLError(
                    errorCode.COMPRESSION_NOT_SUPPORTED,
                    f"quant_scale must be positive, got {cfg.quant_scale}")
            if cfg.compressed != dataType.int8:
                raise ACCLError(
                    errorCode.COMPRESSION_NOT_SUPPORTED,
                    "quant_scale applies to int8 wire dtypes only; float "
                    "wires are plain casts")
        self._arith_configs[(cfg.uncompressed, cfg.compressed)] = cfg

    def autotune(self, pows: Optional[Sequence[int]] = None,
                 reps: int = 3,
                 cache_path: Optional[str] = None) -> None:
        """Re-derive EVERY AUTO-selection threshold by measurement on the
        live mesh — allreduce ring/hier(/pallas on ICI) crossovers, the
        allgather/reduce_scatter ring crossovers, the rooted-op Pallas
        engage points, and the flat-tree rank/count/fan-in registers
        (adaptive tuning registers — see :mod:`accl_tpu.bench.autotune`).
        Drops the program cache so later calls re-select with the tuned
        config.

        ``cache_path`` makes the tuning durable like the reference's
        per-deployment register write (accl.cpp:1214-1224): a valid cache
        for THIS deployment (world size + transport fingerprint) is
        loaded instead of measuring; anything else — absent file,
        truncated JSON, different schema version, different mesh — falls
        back to measuring and overwrites the cache (atomic write). In a
        multi-process session process 0 alone reads the file and
        publishes the load-or-measure decision through the coordination
        service, so every controller takes the SAME branch — a racing
        exists-check would let one process load-and-return while the
        rest entered the collective measurement programs, hanging the
        mesh."""
        from .bench import autotune as _at

        def measure() -> ACCLConfig:
            kw = {"reps": reps}
            if pows is not None:
                kw["pows"] = pows
            return _at.autotune_session(self, **kw)

        if not cache_path:
            self.config = measure()
            self._programs.clear()
            return

        # topology-qualified fingerprint: world size alone would let a cache
        # tuned on a different mesh shape or chip generation (4x2 vs 8x1
        # ICI, v5e vs v6e) load silently with stale thresholds (ADVICE r3
        # #2) — the reference analog is one register file per installed
        # fabric, not per fabric SIZE
        hs = self.global_comm().hosts_shape()
        fp = {"world": self.world_size,
              "transport": (self.config.transport.value
                            if self.config.transport else None),
              "device": getattr(self._devices[0], "device_kind", "cpu"),
              "hosts": list(hs) if hs is not None else None,
              "schema": 2}

        def try_read():
            """(validated config, raw text), or (None, None) for any
            reason the cache cannot be used (absent / truncated / stale
            schema / other deployment) — all of which mean 'measure and
            overwrite'."""
            import os
            if not os.path.exists(cache_path):
                return None, None
            try:
                with open(cache_path) as f:
                    text = f.read()
                return ACCLConfig.from_json(text, expect_fingerprint=fp), text
            except Exception as e:
                get_logger("accl").warning(
                    "autotune cache %s unusable (%s); re-measuring",
                    cache_path, e)
                return None, None

        if self._fabric is not None:
            # decision must be mesh-uniform: p0 decides, everyone follows.
            # The decision key is namespaced by the fabric's job-unique
            # SESSION nonce plus a per-instance call counter: keys from a
            # crashed earlier run on the same coordination-service KV can
            # never collide, and there is no shared arrivals counter whose
            # n-alignment a mid-round crash could poison for every later
            # session (ADVICE r4 #1 — the previous KV-derived round split
            # decision blocks after a crash, deadlocking non-p0 processes
            # on a key p0 never writes). Call counts align because
            # autotune_configuration is an SPMD-collective call, like
            # every other fabric operation.
            from . import multiproc as _mp
            client = _mp._client()
            # fabric-namespaced: unique per (job run, fabric instance),
            # so neither a crashed earlier run nor a second ACCL
            # instance in the same job can collide with this key
            key = f"{self._fabric.ns}/tune/d/{self._tune_round}"
            self._tune_round += 1
            if jax.process_index() == 0:
                cfg, text = try_read()
                self._fabric._kset(client, key,
                                   "L" + text if cfg is not None else "M")
            decision = client.blocking_key_value_get(
                key, self._fabric._timeout_ms())
            if decision.startswith("L"):
                self.config = ACCLConfig.from_json(decision[1:])
            else:
                self.config = measure()
                if jax.process_index() == 0:
                    self.config.save(cache_path, fingerprint=fp)
            # exit barrier: no process proceeds past this round until all
            # have consumed the decision (keeps measure()'s collectives
            # and any follow-on traffic in step across the mesh)
            self._fabric.barrier("tune", pump=self._pump)
        else:
            cfg, _ = try_read()
            if cfg is not None:
                self.config = cfg
            else:
                self.config = measure()
                self.config.save(cache_path, fingerprint=fp)
        self._programs.clear()

    def config_call(self, function: constants.cfgFunc,
                    value: Optional[float] = None) -> None:
        """Housekeeping config call (``CCLO::Options.cfg_function`` →
        fw HOUSEKEEP_* dispatch, ccl_offload_control.c:2416-2451)."""
        cf = constants.cfgFunc
        if function in (cf.set_timeout, cf.set_max_eager_size,
                        cf.set_max_rendezvous_size) and value is None:
            raise ACCLError(
                errorCode.CONFIG_ERROR,
                f"{function.name} requires a value")
        if function == cf.reset_periph:
            self.soft_reset()
        elif function == cf.enable_pkt:
            # packetizer/depacketizer/rx-offload engines (fw :101-122) have
            # no TPU analog to start: transports are live once the mesh is
            pass
        elif function == cf.set_timeout:
            self.set_timeout(float(value))
        elif function == cf.set_max_eager_size:
            self.set_max_eager_size(int(value))
        elif function == cf.set_max_rendezvous_size:
            self.set_max_rendezvous_size(int(value))
        else:
            # open_port/open_con/close_con: session management dissolved
            # into the mesh definition (SURVEY.md §2.7) — nothing to open
            raise ACCLError(
                errorCode.CONFIG_ERROR,
                f"{function.name}: transport sessions are mesh axes on TPU; "
                "no dynamic session management exists")

    # ------------------------------------------------------------------
    # buffers / communicators
    # ------------------------------------------------------------------

    @property
    def world_size(self) -> int:
        return self.comms[0].world_size

    def global_comm(self) -> Communicator:
        return self.comms[0]

    def create_buffer(
        self,
        count: int,
        dtype: dataType,
        comm: Optional[Communicator] = None,
        host_data: Optional[np.ndarray] = None,
    ) -> Buffer:
        """``ACCL::create_buffer`` analog (accl.hpp)."""
        return Buffer(count, dtype, comm or self.comms[0], host_data=host_data)

    def dummy_buffer(self, comm: Optional[Communicator] = None) -> DummyBuffer:
        return DummyBuffer(comm or self.comms[0])

    def create_communicator(
        self, ranks: Sequence[int], parent: Optional[Communicator] = None
    ) -> Communicator:
        """Sub-communicator over a rank subset (``ACCL::create_communicator``;
        exercised by test.cpp:621-752 multi-comm tests)."""
        parent = parent or self.comms[0]
        comm = parent.split(ranks)
        self.comms.append(comm)
        self._matchers[id(comm)] = MatchingEngine(
            comm, rx_buffer_count=self.config.eager_rx_buffer_count)
        return comm

    def matcher(self, comm: Optional[Communicator] = None) -> MatchingEngine:
        # through the validity guard: a shrink recovery popped the
        # invalidated comms' engines, so the clear COMM_INVALIDATED
        # verdict must fire here too, never a bare KeyError
        return self._matchers[id(self._comm(comm))]

    def command_list(self, comm: Optional[Communicator] = None):
        """Record collective calls and run them as ONE device launch — the
        hostctrl command-stream / PL-kernel chained-command analog
        (:mod:`accl_tpu.cmdlist`): per-launch dispatch is paid once per
        sequence instead of once per op."""
        from .cmdlist import CommandList
        return CommandList(self, comm)

    # ------------------------------------------------------------------
    # internal op plumbing
    # ------------------------------------------------------------------

    def _check_rendezvous_size(self, nbytes: int, compressing: bool,
                               what: str) -> None:
        """Cap rendezvous messages at ``max_rendezvous_size`` — the
        HOUSEKEEP_RENDEZVOUS_MAX_SIZE register (fw :2442-2447): a rendezvous
        message is a single unsegmented move, so payloads beyond the cap
        have no protocol to ride."""
        if compressing:
            return  # compressed payloads take the (segmented) eager path
        if (nbytes > self.config.max_eager_size
                and nbytes > self.config.max_rendezvous_size):
            raise ACCLError(
                errorCode.INVALID_BUFFER_SIZE,
                f"{what}: {nbytes} B exceeds max_rendezvous_size "
                f"{self.config.max_rendezvous_size} B (raise it via "
                f"set_max_rendezvous_size)")

    def _check_count(self, buf: BaseBuffer, count: int, what: str) -> None:
        if buf.is_dummy:
            return
        if count > buf.count:
            raise ACCLError(
                errorCode.INVALID_BUFFER_SIZE,
                f"{what}: count {count} exceeds buffer count {buf.count}",
            )

    def _arith(
        self, dt: dataType, compress_dtype: Optional[dataType]
    ) -> Optional[ArithConfig]:
        """Resolve the dtype policy for a call (``ACCL::prepare_call``
        compression/arithcfg resolution, accl.cpp:1252-1372)."""
        if compress_dtype is None or compress_dtype == dt:
            return self._arith_configs.get((dt, dt))
        cfg = self._arith_configs.get((dt, compress_dtype))
        if cfg is None:
            raise ACCLError(
                errorCode.COMPRESSION_NOT_SUPPORTED,
                f"no arith config for ({dt.name}, {compress_dtype.name})",
            )
        if not self.config.enable_compression:
            raise ACCLError(errorCode.COMPRESSION_NOT_SUPPORTED, "compression disabled")
        return cfg

    def _input(self, buf: BufLike, count: int, from_device: bool) -> jax.Array:
        if not from_device:
            buf.sync_to_device()
        view = buf.device_view()
        return view[:, :count] if count != buf.count else view

    def _store(self, buf: BufLike, count: int, value: jax.Array) -> None:
        if count == buf.count:
            buf.device_store(value)
        else:
            full = buf.device_view()
            buf.device_store(jax.lax.dynamic_update_slice(
                full, value.astype(full.dtype), (0, 0)))

    def _finish(
        self,
        scenario: operation,
        out_buf: Optional[BufLike],
        outputs,
        to_device: bool,
        run_async: bool,
        comm: Optional[Communicator] = None,
    ) -> Optional[Request]:
        def finalizer(_req: Request) -> None:
            if out_buf is not None and not to_device:
                out_buf.sync_from_device()

        req = Request(scenario.name, outputs=outputs, finalizer=finalizer,
                      on_complete=self._queue.retire, comm=comm,
                      native_registry=self._reqreg)
        self._queue.push(req)
        if run_async:
            return req
        req.wait(timeout=self.config.timeout)
        return None

    def _key(self, comm: Communicator, op: operation, *extra):
        # the session epoch leads the key: recover() also clears the
        # cache, but the epoch makes a pre-death program unreachable by
        # construction even if a future refactor drops the clear
        return (self._epoch, id(comm), op, *extra)

    def _comm(self, comm: Optional[Communicator]) -> Communicator:
        """Resolve the call's communicator (default: the session-global
        one) and enforce the survivor-subset invalidation verdict: a
        group spanning a rank lost to a shrink recovery raises
        ``COMM_INVALIDATED`` instead of compiling a program that could
        never converge. One attribute read on the healthy path."""
        if comm is None:
            comm = self.comms[0]
        if comm._invalid_reason is not None:
            comm.check_valid()
        return comm

    # ------------------------------------------------------------------
    # per-op program specs: (cache key, builder) pairs shared by the
    # per-op call paths AND CommandList recording, so both always compile
    # and cache the SAME program for the same logical call — one source
    # of truth per op, no first-writer-wins divergence
    # ------------------------------------------------------------------

    def _spec_copy(self, comm, count: int, dtype: dataType):
        return (self._key(comm, operation.copy, count, dtype),
                lambda: primitives.build_copy(comm))

    def _spec_combine(self, comm, count: int, dtype: dataType,
                      function: reduceFunction):
        use_pallas = self.config.use_pallas and self.config.enable_arith
        return (self._key(comm, operation.combine, count, dtype, function,
                          use_pallas),
                lambda: primitives.build_combine(comm, function, dtype,
                                                 use_pallas=use_pallas))

    def _spec_bcast(self, comm, count: int, dtype: dataType, root: int,
                    compress_dtype, algorithm):
        arith = self._arith(dtype, compress_dtype)
        algo = algorithms.select(
            operation.bcast, count * constants.dtype_size(dtype),
            comm, self.config, algorithm)
        seg = self.config.segment_size
        return (self._key(comm, operation.bcast, count, dtype, root,
                          compress_dtype, algo, seg),
                lambda: algorithms.build_bcast(comm, root, algo, arith,
                                               dtype, seg))

    def _mesh_shape(self, comm, algo):
        """Resolved torus shape for a MULTIAXIS program — part of its
        cache key (a redeclared topology must not reuse a stale
        program); None for every other family."""
        if algo != Algorithm.MULTIAXIS:
            return None
        from .parallel import synth
        return synth.torus_shape(comm, self.config, allow_factor2d=True)

    def _pipeline_chunks(self, algo, plan):
        """Chunk count for a MULTIAXIS program — part of its cache key
        (a re-tuned ``sched_pipeline_chunks`` must not reuse a stale
        program) and the builder's pipelining switch. The resolved plan
        is authoritative (shape ``pipeline`` runs its own chunk param,
        a sequential ``multiaxis`` plan runs unchunked, exactly what
        the plan counters claim); an EXPLICIT ``algorithm=MULTIAXIS``
        request carries no plan and honors the session register — the
        bench lanes' per-arm A/B control."""
        if algo != Algorithm.MULTIAXIS:
            return 1
        if plan is not None:
            if plan.shape == "pipeline":
                return max(1, int(plan.param("pipeline_chunks", 1)))
            return 1
        return max(1, int(self.config.sched_pipeline_chunks))


    @staticmethod
    def _dcn_wire_inert(dtype: dataType, arith) -> bool:
        """Delegates to ``hierarchical.dcn_wire_inert`` — the ONE
        predicate, defined beside the codec it describes, for whether
        the DCN cross-slice wire can actually compress this call."""
        from .parallel import hierarchical as _hier
        return _hier.dcn_wire_inert(dtype, arith)

    def _twotier_params(self, comm, algo, plan):
        """(slices x per-slice shape, cross-slice wire dtype) for a
        TWOTIER program — both part of its cache key (a re-declared
        slice split or a re-tuned ``dcn_wire_dtype`` must not reuse a
        stale program) and the builder's arguments. The resolved plan
        is authoritative (the program built matches exactly what the
        plan counters and the wire-bytes accounting claim); an EXPLICIT
        ``algorithm=TWOTIER`` request carries no plan and resolves the
        physical ``hosts_shape`` (factor2d on single-host rigs — the
        bench A/B control) plus the session wire register."""
        if algo != Algorithm.TWOTIER:
            return (None, None)
        if plan is not None:
            return (plan.param("shape2d"),
                    plan.param("dcn_wire_dtype", "off"))
        return (algorithms._twotier_shape(comm, None),
                self.config.dcn_wire_dtype)

    def _spec_allgather(self, comm, count: int, dtype: dataType,
                        compress_dtype, algorithm):
        arith = self._arith(dtype, compress_dtype)
        algo, plan = algorithms.select_plan(
            operation.allgather, count * constants.dtype_size(dtype),
            comm, self.config, algorithm, count=count,
            wire_inert=self._dcn_wire_inert(dtype, arith))
        seg = self.config.segment_size
        bidir = self.config.bidirectional_rings
        ms = self._mesh_shape(comm, algo)
        pc = self._pipeline_chunks(algo, plan)
        ts, dw = self._twotier_params(comm, algo, plan)
        return (self._key(comm, operation.allgather, count, dtype,
                          compress_dtype, algo, seg, bidir, ms, pc, ts,
                          dw),
                lambda: algorithms.build_allgather(comm, algo, arith, dtype,
                                                   seg, bidir,
                                                   mesh_shape=ms or ts,
                                                   pipeline_chunks=pc,
                                                   dcn_wire_dtype=dw))

    def _spec_scatter(self, comm, count: int, dtype: dataType, root: int,
                      compress_dtype, algorithm):
        arith = self._arith(dtype, compress_dtype)
        # per-edge payload (each star edge moves `count` elements), matching
        # the gather/bcast/reduce selection convention
        algo = algorithms.select(
            operation.scatter, count * constants.dtype_size(dtype),
            comm, self.config, algorithm)
        seg = self.config.segment_size
        return (self._key(comm, operation.scatter, count, dtype, root,
                          compress_dtype, algo, seg),
                lambda: algorithms.build_scatter(comm, root, algo, arith,
                                                 dtype, seg))

    def _spec_gather(self, comm, count: int, dtype: dataType, root: int,
                     compress_dtype, algorithm):
        arith = self._arith(dtype, compress_dtype)
        algo = algorithms.select(
            operation.gather, count * constants.dtype_size(dtype),
            comm, self.config, algorithm)
        fanin = (self.config.gather_flat_tree_max_fanin
                 if algo == Algorithm.FLAT else 0)
        seg = self.config.segment_size
        return (self._key(comm, operation.gather, count, dtype, root,
                          compress_dtype, algo, fanin, seg),
                lambda: algorithms.build_gather(comm, root, algo, arith,
                                                fanin, dtype, seg))

    def _spec_alltoall(self, comm, count: int, dtype: dataType,
                       compress_dtype, algorithm):
        arith = self._arith(dtype, compress_dtype)
        # per-edge payload: each of the P fused trees moves `count` elements
        algo = algorithms.select(
            operation.alltoall, count * constants.dtype_size(dtype),
            comm, self.config, algorithm)
        seg = self.config.segment_size
        return (self._key(comm, operation.alltoall, count, dtype,
                          compress_dtype, algo, seg),
                lambda: algorithms.build_alltoall(comm, algo, arith,
                                                  dtype, seg))

    def _spec_reduce(self, comm, count: int, dtype: dataType, root: int,
                     function: reduceFunction, compress_dtype, algorithm):
        arith = self._arith(dtype, compress_dtype)
        if arith is not None and not arith.supports(function):
            raise ACCLError(errorCode.ARITH_ERROR, f"{function} unsupported")
        algo = algorithms.select(
            operation.reduce, count * constants.dtype_size(dtype),
            comm, self.config, algorithm, count=count)
        fanin = (self.config.gather_flat_tree_max_fanin
                 if algo == Algorithm.FLAT else 0)
        seg = self.config.segment_size
        return (self._key(comm, operation.reduce, count, dtype, root,
                          function, compress_dtype, algo, fanin, seg),
                lambda: algorithms.build_reduce(comm, root, function, dtype,
                                                algo, arith, fanin, seg))

    def _spec_allreduce(self, comm, count: int, dtype: dataType,
                        function: reduceFunction, compress_dtype, algorithm):
        arith = self._arith(dtype, compress_dtype)
        if arith is not None and not arith.supports(function):
            raise ACCLError(errorCode.ARITH_ERROR, f"{function} unsupported")
        algo, plan = algorithms.select_plan(
            operation.allreduce, count * constants.dtype_size(dtype),
            comm, self.config, algorithm, count=count,
            wire_inert=self._dcn_wire_inert(dtype, arith))
        fanin = (self.config.gather_flat_tree_max_fanin
                 if algo == Algorithm.FLAT else 0)
        seg = self.config.segment_size
        bidir = self.config.bidirectional_rings
        on_dcn = self.config.transport == TransportBackend.DCN
        ms = self._mesh_shape(comm, algo)
        pc = self._pipeline_chunks(algo, plan)
        ts, dw = self._twotier_params(comm, algo, plan)
        return (self._key(comm, operation.allreduce, count, dtype, function,
                          compress_dtype, algo, seg, fanin, bidir, on_dcn,
                          ms, pc, ts, dw),
                lambda: algorithms.build_allreduce(comm, function, dtype,
                                                   algo, arith, seg, fanin,
                                                   bidir, on_dcn=on_dcn,
                                                   mesh_shape=ms or ts,
                                                   pipeline_chunks=pc,
                                                   dcn_wire_dtype=dw))

    def _spec_reduce_scatter(self, comm, count: int, dtype: dataType,
                             function: reduceFunction, compress_dtype,
                             algorithm):
        arith = self._arith(dtype, compress_dtype)
        if arith is not None and not arith.supports(function):
            raise ACCLError(errorCode.ARITH_ERROR, f"{function} unsupported")
        algo, plan = algorithms.select_plan(
            operation.reduce_scatter,
            count * comm.world_size * constants.dtype_size(dtype),
            comm, self.config, algorithm,
            count=count * comm.world_size,
            wire_inert=self._dcn_wire_inert(dtype, arith))
        seg = self.config.segment_size
        bidir = self.config.bidirectional_rings
        ms = self._mesh_shape(comm, algo)
        pc = self._pipeline_chunks(algo, plan)
        ts, dw = self._twotier_params(comm, algo, plan)
        return (self._key(comm, operation.reduce_scatter, count, dtype,
                          function, compress_dtype, algo, seg, bidir, ms,
                          pc, ts, dw),
                lambda: algorithms.build_reduce_scatter(comm, function,
                                                        dtype, algo, arith,
                                                        seg, bidir,
                                                        mesh_shape=ms or ts,
                                                        pipeline_chunks=pc,
                                                        dcn_wire_dtype=dw))

    # ------------------------------------------------------------------
    # primitives: copy / combine
    # ------------------------------------------------------------------

    def copy(
        self,
        srcbuf: BufLike,
        dstbuf: BufLike,
        count: int,
        from_device: bool = False,
        to_device: bool = False,
        run_async: bool = False,
        comm: Optional[Communicator] = None,
    ) -> Optional[Request]:
        """Per-rank device copy (``ACCL::copy``; fw copy ccl_offload_control.c:533-549)."""
        t0 = _metrics.tick()
        with _trace.root("accl.copy", count=count):
            comm = self._comm(comm)
            self._check_count(srcbuf, count, "copy src")
            self._check_count(dstbuf, count, "copy dst")
            x = self._input(srcbuf, count, from_device)
            with _trace.span("accl.plan"):
                key, build = self._spec_copy(comm, count, srcbuf.dtype)
            with _trace.span("accl.launch"):
                y = self._programs.run(key, build, x).astype(
                    dstbuf.jnp_dtype)
                self._store(dstbuf, count, y)
            _metrics.note_call(operation.copy,
                               count * constants.dtype_size(srcbuf.dtype),
                               srcbuf.dtype, key, t0)
            return self._finish(operation.copy, dstbuf, y, to_device,
                                run_async, comm)

    def combine(
        self,
        count: int,
        function: reduceFunction,
        val1: BufLike,
        val2: BufLike,
        result: BufLike,
        val1_from_device: bool = False,
        val2_from_device: bool = False,
        to_device: bool = False,
        run_async: bool = False,
        comm: Optional[Communicator] = None,
    ) -> Optional[Request]:
        """Per-rank elementwise reduce of two buffers (``ACCL::combine``;
        fw combine :553-571; reduce_ops plugin)."""
        t0 = _metrics.tick()
        with _trace.root("accl.combine", count=count):
            comm = self._comm(comm)
            for b, w in ((val1, "combine op0"), (val2, "combine op1"),
                         (result, "combine res")):
                self._check_count(b, count, w)
            if val1.dtype != val2.dtype:
                raise ACCLError(errorCode.ARITH_ERROR,
                                "combine operand dtype mismatch")
            a = self._input(val1, count, val1_from_device)
            b = self._input(val2, count, val2_from_device)
            with _trace.span("accl.plan"):
                key, build = self._spec_combine(comm, count, val1.dtype,
                                                function)
            with _trace.span("accl.launch"):
                y = self._programs.run(key, build, a, b).astype(
                    result.jnp_dtype)
                self._store(result, count, y)
            _metrics.note_call(operation.combine,
                               count * constants.dtype_size(val1.dtype),
                               val1.dtype, key, t0)
            return self._finish(operation.combine, result, y, to_device,
                                run_async, comm)

    # ------------------------------------------------------------------
    # two-sided send / recv + one-sided put
    # ------------------------------------------------------------------

    def _segments(self, count: int, dt: dataType) -> List[tuple]:
        """Eager segmentation geometry: (offset, length) element spans of
        rx-buffer-sized chunks (fw send loop, ccl_offload_control.c:613-650).
        """
        seg_elems = max(self.config.eager_rx_buffer_size
                        // constants.dtype_size(dt), 1)
        return [(off, min(seg_elems, count - off))
                for off in range(0, count, seg_elems)]

    def _pump(self) -> bool:
        """Run the cooperative scheduler: retry parked calls, each resuming
        from its ``current_step`` (wait_for_call round-robin + retry queue,
        ccl_offload_control.c:2264-2288, :2460-2478). Keeps making full
        passes over the parked calls until one whole pass yields no
        progress — a single stuck continuation must not starve the others.
        Returns whether any continuation progressed (drives wait() backoff).

        Multi-process: also drives the cross-process move schedule, so a
        controller inside ANY ACCL call co-executes pair moves its peers
        have accepted (cooperative progress, like the firmware loop).
        """
        fab_progress = (self._fabric.drive()
                        if self._fabric is not None else False)
        return self._pump_parked() or fab_progress

    def _pump_parked(self) -> bool:
        any_progress = False
        while True:
            n = len(self._parked_calls)
            if n == 0:
                return any_progress
            progressed = False
            for _ in range(n):
                popped = self._sched.pop()
                if popped is None:
                    return any_progress or progressed
                call_id, step = popped
                cont = self._parked_calls.get(call_id)
                if cont is None:
                    continue
                _metrics.inc("accl_sched_events_total", labels=_L_REPUMP)
                new_step = cont(step)
                if new_step is None:
                    del self._parked_calls[call_id]
                    _metrics.inc("accl_sched_events_total", labels=_L_RESUME)
                    progressed = True
                else:
                    self._sched.push_retry(call_id, new_step)
                    if new_step != step:
                        progressed = True
            if not progressed:
                return any_progress
            any_progress = True

    # -- cross-process two-sided path (multiproc fabric) -------------------

    def _drive_until(self, pred, what: str) -> None:
        """Drive the full cooperative scheduler (parked continuations AND
        the cross-process mover — a parked async send may still need to
        announce while this process blocks here) until ``pred()`` holds;
        NOT_READY on session timeout, PEER_FAILED (well inside it) when
        the heartbeat leases say the peer this wait depends on is dead —
        the bounded-failure contract of docs/resilience.md."""
        from .multiproc import CrossProcessFabric

        deadline = time.monotonic() + self.config.timeout
        idle = 0
        while not pred():
            if not self._pump():
                idle += 1
                CrossProcessFabric.poll_sleep(idle)
            else:
                idle = 0
            if self._fabric is not None:
                self._fabric.raise_if_peer_failed(what)
            if time.monotonic() > deadline:
                raise ACCLError(errorCode.NOT_READY_ERROR, what)

    def _pump_waiting(self) -> bool:
        """:meth:`_pump` for blocked request waits: additionally enforces
        the peer-liveness verdict, so an async request parked on a dead
        peer retires with PEER_FAILED (Request.wait catches the raise and
        completes the request with it) instead of pumping forever."""
        progressed = self._pump()
        if self._fabric is not None:
            self._fabric.raise_if_peer_failed("request wait")
        return progressed

    def _park_continuation(self, cont, step: int) -> None:
        """Park a resumable continuation on the cooperative retry queue
        (NOT_READY re-enqueue with current_step,
        ccl_offload_control.c:2460-2478)."""
        call_id = self._next_call_id
        self._next_call_id += 1
        self._parked_calls[call_id] = cont
        self._sched.push_retry(call_id, step)
        _metrics.inc("accl_sched_events_total", labels=_L_PARK)
        _trace.instant("sched.park", cat="sched", call_id=call_id, step=step)

    def _cross_send(self, srcbuf, count, src, dst, tag, from_device,
                    run_async, comm, compress_dtype,
                    arith=None) -> Optional[Request]:
        """Send to a rank owned by another controller process.

        The payload stays staged on this process's device (jax arrays are
        immutable — holding the shard reference is a zero-copy snapshot)
        and moves as an SPMD pair-mesh program that both endpoint
        controllers enter; the coordination service carries only the
        header (multiproc.CrossProcessFabric). Eager sends complete at
        announce time under the segment credit window; rendezvous sends
        complete when the move executes — sync blocks driving the mover,
        async parks on the retry queue like a NOT_READY firmware call
        (ccl_offload_control.c:2460-2478)."""
        if not comm.rank_is_local(src):
            raise ACCLError(
                errorCode.CONFIG_ERROR,
                f"process {jax.process_index()} does not own src rank {src}")
        self._check_count(srcbuf, count, "send")
        if not from_device:
            srcbuf.sync_to_device()
        payload = srcbuf.rank_shard(src)
        if count != srcbuf.count:
            payload = payload[:, :count]
        if arith is None:
            arith = self._arith(srcbuf.dtype, compress_dtype)
        compressing = arith is not None and arith.is_compressing
        if compressing:
            from . import ops as _ops
            payload = _ops.compress(payload, arith.uncompressed,
                                    arith.compressed)
        nbytes = count * constants.dtype_size(srcbuf.dtype)
        self._check_rendezvous_size(nbytes, compressing, "cross-process send")
        sdev, ddev = comm.device(src).id, comm.device(dst).id
        fab = self._fabric

        if nbytes > self.config.max_eager_size and not compressing:
            # rendezvous: zero-copy handoff, done only when moved (fw :595-612)
            _metrics.inc("accl_sendrecv_protocol_total", labels=_L_RDV_X)
            _metrics.note_call(operation.send, nbytes, srcbuf.dtype)
            seq = fab.announce(sdev, ddev, tag, payload, "r", 0)
            if not run_async:
                with _trace.span("xsend.rendezvous", cat="fabric",
                                 src=src, dst=dst, nbytes=nbytes):
                    self._drive_until(
                        lambda: not fab.send_pending(sdev, ddev, seq),
                        f"rendezvous send {src}->{dst}: no recv accepted "
                        f"within {self.config.timeout}s")
                return self._finish(operation.send, None, payload, True,
                                    False, comm)
            req = Request(operation.send.name, outputs=None, external=True,
                          on_complete=self._queue.retire,
                          progress=self._pump_waiting, comm=comm,
                          native_registry=self._reqreg)
            self._queue.push(req)

            def cont_rdv(step: int) -> Optional[int]:
                if req.status in _TERMINAL:
                    return None
                fab.drive()
                if not fab.send_pending(sdev, ddev, seq):
                    req.fulfill(outputs=payload)
                    return None
                return step

            self._park_continuation(cont_rdv, 0)
            return req

        # eager: completes at announce, bounded by the credit window. The
        # sequence number is reserved NOW — a credit-starved send holds its
        # place in the pair stream so later sends cannot overtake it (the
        # receiver's fetch cursor stalls at the unannounced seq until the
        # announce lands: per-pair non-overtaking, like the per-pair seqn
        # ordering of dma_mover.cpp:581-610)
        nseg = fab.nsegments(count * payload.dtype.itemsize)
        seq = fab.next_seq(sdev, ddev)
        _metrics.inc("accl_sendrecv_protocol_total", labels=_L_EAGER_X)
        _metrics.note_call(operation.send, nbytes, srcbuf.dtype)
        if not run_async:
            with _trace.span("xsend.eager", cat="fabric",
                             src=src, dst=dst, nbytes=nbytes, nseg=nseg):
                try:
                    self._drive_until(
                        lambda: fab.eager_can_announce(sdev, ddev, seq,
                                                       nseg),
                        f"eager window to rank {dst} full for "
                        f"{self.config.timeout}s (no recv consuming "
                        f"segments)")
                except ACCLError:
                    # never strand the reserved seq: the pair stream must
                    # stay advanceable for the receiver after this send
                    # fails
                    fab.announce_cancel(sdev, ddev, seq)
                    raise
                fab.announce(sdev, ddev, tag, payload, "e", nseg, seq=seq)
            return self._finish(operation.send, None, payload, True, False,
                                comm)

        req = Request(operation.send.name, outputs=None, external=True,
                      on_complete=self._queue.retire, progress=self._pump_waiting,
                      comm=comm, native_registry=self._reqreg)
        self._queue.push(req)

        def cont_eager(step: int) -> Optional[int]:
            if req.status in _TERMINAL:
                # cancelled while parked: tombstone the reserved seq so the
                # receiver's fetch cursor is not stalled forever
                fab.announce_cancel(sdev, ddev, seq)
                return None
            fab.drive()
            if fab.eager_can_announce(sdev, ddev, seq, nseg):
                fab.announce(sdev, ddev, tag, payload, "e", nseg, seq=seq)
                req.fulfill(outputs=payload)
                return None
            return step

        first = cont_eager(0)
        if first is not None:
            self._park_continuation(cont_eager, first)
        return req

    def _cross_recv(self, dstbuf, count, src, dst, tag, to_device,
                    run_async, comm, compress_dtype) -> Optional[Request]:
        """Receive from a rank owned by another controller process.

        Matches announcements on (src, tag|ANY) in seqn order with
        out-of-order parking (rxbuf_seek.cpp:50-66 semantics), accepts the
        match into the global move schedule, and drives the mover until the
        payload shard lands on this process's device — written into the
        destination buffer without a host round-trip."""
        if not comm.rank_is_local(dst):
            raise ACCLError(
                errorCode.CONFIG_ERROR,
                f"process {jax.process_index()} does not own dst rank {dst}")
        self._check_count(dstbuf, count, "recv")
        _metrics.note_call(operation.recv,
                           count * constants.dtype_size(dstbuf.dtype),
                           dstbuf.dtype)
        arith = self._arith(dstbuf.dtype, compress_dtype)
        sdev, ddev = comm.device(src).id, comm.device(dst).id
        fab = self._fabric
        delivered: list = []

        def deliver(shard, header) -> None:
            c = header.get("c")
            if c is not None:
                # receiver-side correlation: the sender stamped
                # (epoch, proc, seq) into the announce header, so this
                # rank's span/flight event can NAME its sender instead
                # of guessing from timing
                _flight.record("recv_correlated", src=src, dst=dst,
                               sender_epoch=c[0], sender_proc=c[1],
                               sender_seq=c[2])
                _trace.instant("xrecv.corr", cat="fabric",
                               corr=f"{c[0]}.{c[1]}.{c[2]}")
            x = shard
            if arith is not None and arith.is_compressing:
                from . import ops as _ops
                x = _ops.decompress(x, arith.compressed, arith.uncompressed)
            # device-only store in the mover's hot path; the host mirror is
            # refreshed once by the recv finalizer when to_device is False
            dstbuf.store_rank_shard(dst, x, sync_host=False)
            delivered.append(True)

        def match_once() -> bool:
            m = fab.try_match(sdev, ddev, tag)
            if m is None:
                return False
            seq, header = m
            if header["n"] != count:
                raise ACCLError(
                    errorCode.INVALID_BUFFER_SIZE,
                    f"recv {dst}<-{src}: count {count} != message count "
                    f"{header['n']}")
            fab.accept(sdev, ddev, seq, header, deliver)
            return True

        if not run_async:
            with _trace.span("xrecv.match", cat="fabric", src=src, dst=dst):
                self._drive_until(
                    match_once,
                    f"recv {dst}<-{src}: no matching send within "
                    f"{self.config.timeout}s")
            with _trace.span("xrecv.deliver", cat="fabric",
                             src=src, dst=dst):
                self._drive_until(
                    lambda: bool(delivered),
                    f"recv {dst}<-{src}: accepted but the move never "
                    f"executed within {self.config.timeout}s")
            return self._finish(operation.recv, dstbuf, None, to_device,
                                False, comm)

        def finalizer(_req: Request) -> None:
            if not to_device:
                dstbuf.sync_from_device()

        req = Request(operation.recv.name, outputs=None, finalizer=finalizer,
                      external=True, on_complete=self._queue.retire,
                      progress=self._pump_waiting, comm=comm,
                      native_registry=self._reqreg)
        self._queue.push(req)
        matched: list = []

        def cont_recv(step: int) -> Optional[int]:
            if req.status in _TERMINAL:
                return None
            try:
                if not matched and match_once():
                    matched.append(True)
                fab.drive()
            except Exception as e:  # count mismatch etc. surface on wait()
                req.cancel(error=e)
                return None
            if delivered:
                req.fulfill(outputs=dstbuf.rank_shard(dst))
                return None
            return 1 if matched else 0

        first = cont_recv(0)
        if first is not None:
            self._park_continuation(cont_recv, first)
        return req

    def send(
        self,
        srcbuf: BufLike,
        count: int,
        src: int,
        dst: int,
        tag: int = 0,
        from_device: bool = False,
        run_async: bool = False,
        comm: Optional[Communicator] = None,
        compress_dtype: Optional[dataType] = None,
    ) -> Optional[Request]:
        """Post a send from rank ``src`` to rank ``dst`` (``ACCL::send``;
        fw send :575-651).

        Unlike MPI, the rank is explicit: the single controller issues calls
        on behalf of every rank, so ``src`` names whose shard is sent.

        Protocol split mirrors the firmware: payloads up to
        ``max_eager_size`` go **eager** — segmented into rx-buffer-sized
        chunks, each consuming a pool slot while parked, backpressured when
        the pool is exhausted (sync: NOT_READY; async: parked on the retry
        queue with ``current_step``). Larger payloads go **rendezvous** —
        one zero-copy post, no rx buffer (:595-612). ``compress_dtype``
        compresses the wire payload only (ETH_COMPRESSED semantics).
        """
        with _trace.root("accl.send", count=count):
            return self._send(srcbuf, count, src, dst, tag, from_device,
                              run_async, comm, compress_dtype)

    def _send(self, srcbuf: BufLike, count: int, src: int, dst: int,
              tag: int, from_device: bool, run_async: bool,
              comm: Optional[Communicator],
              compress_dtype: Optional[dataType]) -> Optional[Request]:
        comm = self._comm(comm)
        arith = self._arith(srcbuf.dtype, compress_dtype)
        if arith is not None and arith.quant_scale is not None:
            # BOTH two-sided delivery paths (move_at and the cross-process
            # fabric) write wire payloads with a plain cast; a scaled wire
            # would land unscaled values
            raise ACCLError(
                errorCode.COMPRESSION_NOT_SUPPORTED,
                "quantized (scaled) wire pairs are supported on the "
                "collective paths only; use a float wire dtype for send/recv")
        if comm.is_multiprocess and not (
                comm.rank_is_local(src) and comm.rank_is_local(dst)):
            return self._cross_send(srcbuf, count, src, dst, tag,
                                    from_device, run_async, comm,
                                    compress_dtype, arith)
        self._pump()
        self._check_count(srcbuf, count, "send")
        data = self._input(srcbuf, count, from_device)
        if arith is not None and arith.is_compressing:
            from . import ops as _ops
            data = _ops.compress(data, arith.uncompressed, arith.compressed)
        matcher = self.matcher(comm)
        nbytes = count * constants.dtype_size(srcbuf.dtype)
        compressing = arith is not None and arith.is_compressing
        self._check_rendezvous_size(nbytes, compressing, "send")
        if nbytes > self.config.max_eager_size and not compressing:
            # rendezvous: one zero-copy post, no rx buffer (fw :595-612;
            # compressed messages always take the eager path, like the fw)
            _metrics.inc("accl_sendrecv_protocol_total", labels=_L_RDV)
            _metrics.note_call(operation.send, nbytes, srcbuf.dtype)
            post = SendPost(src=src, dst=dst, tag=tag, data=data, count=count)
            with _trace.span("accl.match"):
                matcher.post_send(post)
            return self._finish(operation.send, None, data, True, run_async, comm)
        _metrics.inc("accl_sendrecv_protocol_total", labels=_L_EAGER)
        _metrics.note_call(operation.send, nbytes, srcbuf.dtype)
        if (not run_async
                and nbytes < self.config.latency_tier_threshold
                and nbytes <= self.config.eager_rx_buffer_size):
            # the latency-tier fast path: a sub-threshold payload is by
            # construction a single segment, so the segmentation table,
            # the capacity/slot prechecks sized for multi-segment
            # messages, and the continuation machinery are pure overhead
            # — one slot reserve + one post, dispatch timed at µs
            # resolution
            return self._eager_send_fast(matcher, data, count, src, dst,
                                         tag)
        with _trace.span("accl.match"):
            req = self._eager_send(matcher, data, count, srcbuf.dtype,
                                   src, dst, tag, run_async)
        if run_async:
            return req
        return self._finish(operation.send, None, data, True, False, comm)

    def _eager_send_fast(self, matcher, data, count: int, src: int,
                         dst: int, tag: int) -> Optional[Request]:
        """Single-segment sync eager send — the latency-tier fast path
        (``nbytes < latency_tier_threshold``, one rx-buffer segment).

        Same protocol state transitions as :meth:`_eager_send` at n=1:
        upfront capacity validation against a parked recv, one pool-slot
        reserve (NOT_READY backpressure when exhausted, counted by the
        pool), one post. Dispatch latency (fast-path entry → posted)
        lands in the µs-resolution ``accl_latency_dispatch_seconds{path=
        "eager_send"}`` histogram — the ms-scale dispatch bins cannot
        resolve a p99 for ops whose whole budget is tens of µs."""
        t0 = _metrics.tick()
        with _trace.span("accl.match"):
            cap = matcher.recv_capacity(src, dst, tag)
            if cap >= 0 and cap < count:
                raise ACCLError(
                    errorCode.INVALID_BUFFER_SIZE,
                    f"send {src}->{dst} count {count} overflows the "
                    f"pending recv's remaining capacity {cap}")
            slot = matcher.rx_pool.reserve(
                src, dst, tag, matcher.outbound_seq(src, dst), count)
            if slot < 0:
                raise ACCLError(
                    errorCode.NOT_READY_ERROR,
                    f"eager rx-buffer pool exhausted (0 free, 1 needed); "
                    f"drain pending recvs or raise "
                    f"config.eager_rx_buffer_count")
            post = SendPost(src=src, dst=dst, tag=tag, data=data,
                            count=count, rx_slot=slot)
            try:
                matcher.post_send(post)
            except Exception:
                # rejected before the seqn was consumed — give the slot
                # back
                matcher.rx_pool.release(slot)
                raise
        _metrics.note_latency_dispatch("eager_send", t0)
        return self._finish(operation.send, None, data, True, False,
                            matcher.comm)

    def send_page_batch(self, srcbuf: BufLike, counts, src: int,
                        dst: int, tag: int = 0,
                        comm: Optional[Communicator] = None):
        """Ship N page-sized payloads from ``srcbuf`` as N single-
        segment eager messages with ONE batched rx-slot reservation —
        the disaggregated KV handoff's page-send path.  ``counts`` is
        the per-page element count; page i occupies elements
        ``[sum(counts[:i]), sum(counts[:i+1]))`` of ``srcbuf`` and
        arrives as its own message (the receiver posts one recv per
        page, so pages drain — and free their slots — independently,
        instead of one monolithic message parking every segment until
        the final recv).  All-or-nothing: the batch reserves every slot
        up front (:meth:`RxBufPool.reserve_batch`) or FALLS BACK to one
        plain :meth:`send` of the whole buffer — also the path when a
        page exceeds the rx-buffer segment size — counted per outcome
        in ``accl_sendrecv_page_batch_total{outcome}``, never a silent
        behavior switch."""
        counts = [int(c) for c in counts]
        total = sum(counts)
        comm = self._comm(comm)
        if comm.is_multiprocess and not (
                comm.rank_is_local(src) and comm.rank_is_local(dst)):
            # the cross-process fabric has its own segmentation; the
            # batched reservation is a local-matcher optimization
            _metrics.inc("accl_sendrecv_page_batch_total",
                         labels=(("outcome", "fallback"),))
            return self.send(srcbuf, total, src, dst, tag=tag, comm=comm)
        self._pump()
        self._check_count(srcbuf, total, "send")
        esize = constants.dtype_size(srcbuf.dtype)
        matcher = self.matcher(comm)
        slots = None
        if counts and max(counts) * esize <= min(
                self.config.eager_rx_buffer_size,
                self.config.max_eager_size):
            slots = matcher.rx_pool.reserve_batch(
                src, dst, tag, matcher.outbound_seq(src, dst), counts)
        if slots is None:
            _metrics.inc("accl_sendrecv_page_batch_total",
                         labels=(("outcome", "fallback"),))
            return self.send(srcbuf, total, src, dst, tag=tag, comm=comm)
        _metrics.inc("accl_sendrecv_page_batch_total",
                     labels=(("outcome", "batched"),))
        _metrics.inc("accl_sendrecv_protocol_total", labels=_L_EAGER)
        _metrics.note_call(operation.send, total * esize, srcbuf.dtype)
        data = self._input(srcbuf, total, False)
        off = 0
        for i, (c, slot) in enumerate(zip(counts, slots)):
            post = SendPost(src=src, dst=dst, tag=tag,
                            data=data[:, off:off + c], count=c,
                            rx_slot=slot)
            try:
                matcher.post_send(post)
            except Exception:
                # the failed page's slot plus every unposted page's:
                # posted pages keep theirs (the engine releases on
                # delivery), the rest roll back
                for s in slots[i:]:
                    matcher.rx_pool.release(s)
                raise
            off += c
        return self._finish(operation.send, None, data, True, False,
                            comm)

    def _eager_send(self, matcher, data, count: int, dt: dataType,
                    src: int, dst: int, tag: int,
                    run_async: bool) -> Optional[Request]:
        """Post an eager message's segments: all of them or none (sync;
        returns None and the caller waits), or what fits now with the
        rest parked (async; returns the request)."""
        segs = self._segments(count, dt)
        # validate against any parked recv upfront: a mid-message overflow
        # would otherwise strand a half-posted message with shifted seqns
        cap = matcher.recv_capacity(src, dst, tag)
        if cap >= 0 and cap < count:
            raise ACCLError(
                errorCode.INVALID_BUFFER_SIZE,
                f"send {src}->{dst} count {count} overflows the pending "
                f"recv's remaining capacity {cap}")
        posted: List[SendPost] = []

        def post_segment(i: int) -> bool:
            """Reserve a pool slot then post segment i; False when the pool
            is exhausted (slot released by the engine on delivery)."""
            off, ln = segs[i]
            slot = matcher.rx_pool.reserve(
                src, dst, tag, matcher.outbound_seq(src, dst), ln)
            if slot < 0:
                return False
            post = SendPost(src=src, dst=dst, tag=tag,
                            data=data[:, off:off + ln], count=ln,
                            rx_slot=slot, eom=(i == len(segs) - 1))
            try:
                matcher.post_send(post)
            except Exception:
                # rejected before the seqn was consumed — give the slot back
                matcher.rx_pool.release(slot)
                raise
            posted.append(post)
            return True

        if not run_async:
            # all-or-nothing: never leave a half-posted message behind.
            # One free slot suffices only when every segment is GUARANTEED
            # to deliver immediately (slot turns over per segment): a
            # full-capacity recv is parked AND no earlier undelivered send
            # on the pair blocks seqn eligibility; otherwise all segments
            # may park at once.
            drained = (matcher.outbound_seq(src, dst)
                       == matcher.inbound_seq(src, dst))
            need = 1 if (cap >= count and drained) else len(segs)
            if need > matcher.rx_pool.size:
                # cannot succeed in THIS state: the message needs more slots
                # than the pool owns, so retrying without a state change
                # spins forever (large compressed sends hit this most —
                # compression forces the eager path, fw parity). Recoverable
                # once a full-capacity recv is posted and the pair drains
                # (need collapses to 1), hence still NOT_READY.
                raise ACCLError(
                    errorCode.NOT_READY_ERROR,
                    f"eager message needs {need} rx-buffer slots but the "
                    f"pool only has {matcher.rx_pool.size}; this send cannot "
                    f"proceed until a full-capacity recv is posted and the "
                    f"pair drains — or raise config.eager_rx_buffer_count/"
                    f"eager_rx_buffer_size, or (for uncompressed payloads) "
                    f"lower max_eager_size to use rendezvous")
            if matcher.rx_pool.free_slots < need:
                raise ACCLError(
                    errorCode.NOT_READY_ERROR,
                    f"eager rx-buffer pool exhausted "
                    f"({matcher.rx_pool.free_slots} free, "
                    f"{need} needed); drain pending recvs or "
                    f"raise config.eager_rx_buffer_count")
            for i in range(len(segs)):
                if not post_segment(i):
                    # unreachable by construction of the precheck; loud
                    # guard so a logic slip can never drop tail segments
                    raise ACCLError(
                        errorCode.DMA_NOT_OKAY_ERROR,
                        f"eager send {src}->{dst}: pool slot vanished at "
                        f"segment {i}/{len(segs)}")
            return None

        # async: post what fits now, park the rest with current_step
        def abort_undelivered() -> None:
            """Failure retirement (PEER_FAILED / ERROR, incl. cancel):
            posted-but-undelivered segments are aborted — removed from
            the pending store, counted CONSUMED so the pair stream never
            strands on a hole, and their eager rx-pool slots released.
            Without this every death-retired send permanently shrank the
            pool until the next epoch reset (the round-15 rx-pool leak).
            Delivered segments already returned their slots; the abort
            skips them (rx_slot == -1)."""
            for p in posted:
                if p.rx_slot >= 0:
                    matcher.abort_send(p)

        def on_done(r: Request) -> None:
            self._queue.retire(r)
            if r.status in (requestStatus.ERROR,
                            requestStatus.PEER_FAILED):
                abort_undelivered()

        req = Request(operation.send.name, outputs=data, external=True,
                      on_complete=on_done, progress=self._pump_waiting,
                      comm=matcher.comm, native_registry=self._reqreg)
        self._queue.push(req)

        def continue_from(step: int) -> Optional[int]:
            if req.status in _TERMINAL:
                return None  # cancelled/errored: do not post tail segments
            i = step
            try:
                while i < len(segs) and post_segment(i):
                    i += 1
            except Exception as e:
                req.cancel(error=e)
                return None
            req.current_step = i
            if i == len(segs):
                req.fulfill(outputs=data)
                return None
            return i

        first = continue_from(0)
        if first is not None:
            self._park_continuation(continue_from, first)
        return req

    def recv(
        self,
        dstbuf: BufLike,
        count: int,
        src: int,
        dst: int,
        tag: int = TAG_ANY,
        to_device: bool = False,
        run_async: bool = False,
        comm: Optional[Communicator] = None,
        compress_dtype: Optional[dataType] = None,
    ) -> Optional[Request]:
        """Post a recv at rank ``dst`` for a message from ``src``
        (``ACCL::recv``; fw recv :655-712).

        Mirrors the sender's protocol split: eager messages arrive as
        rx-buffer-sized segments consumed in seqn order (fw :680-711);
        rendezvous messages as one zero-copy move (the RDMA WRITE analog,
        :604-612). A sync recv that cannot match raises ``NOT_READY_ERROR``
        (the firmware's retry verdict surfaced as an exception, since a
        single controller cannot be preempted by a later send); an async
        recv parks like a rendezvous address announcement and its request
        completes on match — ``current_step`` counts delivered segments.
        """
        with _trace.root("accl.recv", count=count):
            return self._recv(dstbuf, count, src, dst, tag, to_device,
                              run_async, comm, compress_dtype)

    def _recv(self, dstbuf: BufLike, count: int, src: int, dst: int,
              tag: int, to_device: bool, run_async: bool,
              comm: Optional[Communicator],
              compress_dtype: Optional[dataType]) -> Optional[Request]:
        comm = self._comm(comm)
        arith = self._arith(dstbuf.dtype, compress_dtype)  # validate the pair
        if arith is not None and arith.quant_scale is not None:
            # mirror send(): a quantized send is always rejected, so a
            # quantized recv could never be fulfilled — fail it up front
            raise ACCLError(
                errorCode.COMPRESSION_NOT_SUPPORTED,
                "quantized (scaled) wire pairs are supported on the "
                "collective paths only; use a float wire dtype for send/recv")
        if comm.is_multiprocess and not (
                comm.rank_is_local(src) and comm.rank_is_local(dst)):
            return self._cross_recv(dstbuf, count, src, dst, tag,
                                    to_device, run_async, comm,
                                    compress_dtype)
        self._pump()
        self._check_count(dstbuf, count, "recv")
        _metrics.note_call(operation.recv,
                           count * constants.dtype_size(dstbuf.dtype),
                           dstbuf.dtype)
        matcher = self.matcher(comm)

        assembled: list = []
        pending_req: list = []
        parked_sync: list = []  # sync recv raised NOT_READY but stayed posted
        seg_off = [0]           # elements delivered so far (write cursor)
        n_delivered = [0]       # segments delivered (current_step analog)
        last_eom = [False]      # last delivered segment ended its message

        def deliver(spost: SendPost) -> None:
            """One arriving segment = one move program writing it into the
            receiver's shard at its offset (per-segment MOVE_ON_RECV +
            MOVE_STRIDE, fw :680-711): a partially-arrived message is
            progressively visible in dstbuf on device, which is what lets
            the rx-pool backpressure pipeline senders into parked recvs.
            The segment's device snapshot is dropped once written — the
            recv holds no payload while parked."""
            n_delivered[0] += 1
            last_eom[0] = spost.eom
            off, seg_off[0] = seg_off[0], seg_off[0] + spost.count
            with _trace.span("accl.launch"):
                dest = self._input(dstbuf, count, True)
                moved = self._programs.run(
                    self._key(comm, operation.recv, "move_at",
                              spost.src, spost.dst),
                    lambda: primitives.build_move_at(comm, spost.src,
                                                     spost.dst),
                    spost.data, dest, off)
                self._store(dstbuf, count, moved)
            if pending_req:
                pending_req[0].current_step = n_delivered[0]
            if seg_off[0] == count:
                assembled.append(moved)
                if pending_req:
                    pending_req[0].fulfill(outputs=moved)
                elif parked_sync and not to_device:
                    # a sync recv that parked after partial delivery has no
                    # request handle to run the finalizer — sync the host
                    # mirror here so dstbuf.host is fresh on completion
                    jax.block_until_ready(moved)
                    dstbuf.sync_from_device()

        post = RecvPost(src=src, dst=dst, tag=tag, count=count,
                        deliver=deliver)

        if not run_async:
            with _trace.span("accl.match"):
                done = matcher.post_recv(post)
                # a partially-filled recv resumes as parked senders free
                # up: each consumed segment releases a pool slot, the pump
                # lets the blocked sender post the next segment into this
                # parked recv (cooperative eager pipeline, fw :628-649)
                while not done:
                    before = post.remaining
                    self._pump()
                    done = post.remaining == 0
                    if not done and post.remaining == before:
                        break  # no progress possible
            if not done:
                if seg_off[0] > 0:
                    # segments were consumed — keep the recv parked so the
                    # delivered data is not lost; it completes (and writes
                    # dstbuf, syncing the host mirror) when the remaining
                    # segments arrive, like a NOT_READY call resuming from
                    # current_step. Do NOT re-post: this recv stays active.
                    parked_sync.append(True)
                    boundary = (" (the delivered data ends exactly at a "
                                "message boundary — count mismatch if the "
                                "sender is done)"
                                if last_eom[0] else "")
                    raise ACCLError(
                        errorCode.NOT_READY_ERROR,
                        f"recv {dst}<-{src} tag={tag}: "
                        f"{count - post.remaining}/{count} elements arrived; "
                        f"recv remains posted and resumes as segments "
                        f"arrive{boundary}")
                matcher.remove_recv(post)
                raise ACCLError(
                    errorCode.NOT_READY_ERROR,
                    f"recv {dst}<-{src} tag={tag}: no matching send posted",
                )
            return self._finish(operation.recv, dstbuf,
                                assembled[0] if assembled else None,
                                to_device, False, comm)

        # async: park; request completes when the last segment lands
        def finalizer(_req: Request) -> None:
            if not to_device:
                dstbuf.sync_from_device()

        req = Request(operation.recv.name, outputs=None, finalizer=finalizer,
                      external=True, on_complete=self._queue.retire,
                      progress=self._pump_waiting, comm=comm,
                      native_registry=self._reqreg)
        pending_req.append(req)
        try:
            self._queue.push(req)
            with _trace.span("accl.match"):
                matcher.post_recv(post)
        except Exception as e:
            req.cancel(error=e)
            raise
        return req

    def put(
        self,
        srcbuf: BufLike,
        dstbuf: BufLike,
        count: int,
        src: int,
        dst: int,
        from_device: bool = False,
        to_device: bool = False,
        run_async: bool = False,
        comm: Optional[Communicator] = None,
    ) -> Optional[Request]:
        """One-sided put: write ``src``'s shard into ``dst``'s shard of
        ``dstbuf`` with no matching recv (``ACCL::stream_put`` analog — the
        one-sided primitive, accl.hpp stream_put)."""
        t0 = _metrics.tick()
        with _trace.root("accl.put", count=count):
            comm = self._comm(comm)
            self._check_count(srcbuf, count, "put src")
            self._check_count(dstbuf, count, "put dst")
            x = self._input(srcbuf, count, from_device)
            dest = self._input(dstbuf, count, True)
            with _trace.span("accl.launch"):
                moved = self._programs.run(
                    self._key(comm, operation.put, count, dstbuf.dtype, src,
                              dst),
                    lambda: primitives.build_move(comm, src, dst),
                    x.astype(dest.dtype), dest)
                self._store(dstbuf, count, moved)
            _metrics.note_call(operation.put,
                               count * constants.dtype_size(srcbuf.dtype),
                               srcbuf.dtype, None, t0)
            return self._finish(operation.put, dstbuf, moved, to_device,
                                run_async, comm)

    # ------------------------------------------------------------------
    # collectives
    # ------------------------------------------------------------------

    def bcast(
        self,
        buf: BufLike,
        count: int,
        root: int,
        from_device: bool = False,
        to_device: bool = False,
        run_async: bool = False,
        comm: Optional[Communicator] = None,
        compress_dtype: Optional[dataType] = None,
        algorithm: Optional[Algorithm] = None,
    ) -> Optional[Request]:
        """``ACCL::bcast`` (accl.cpp; fw :798-990)."""
        t0 = _metrics.tick()
        with _trace.root("accl.bcast", count=count):
            comm = self._comm(comm)
            self._check_count(buf, count, "bcast")
            x = self._input(buf, count, from_device)
            with _trace.span("accl.plan"):
                key, build = self._spec_bcast(comm, count, buf.dtype, root,
                                              compress_dtype, algorithm)
            with _trace.span("accl.launch"):
                y = self._programs.run(key, build, x)
                self._store(buf, count, y)
            _metrics.note_call(operation.bcast,
                               count * constants.dtype_size(buf.dtype),
                               buf.dtype, key, t0)
            return self._finish(operation.bcast, buf, y, to_device,
                                run_async, comm)

    def scatter(
        self,
        sendbuf: BufLike,
        recvbuf: BufLike,
        count: int,
        root: int,
        from_device: bool = False,
        to_device: bool = False,
        run_async: bool = False,
        comm: Optional[Communicator] = None,
        compress_dtype: Optional[dataType] = None,
        algorithm: Optional[Algorithm] = None,
    ) -> Optional[Request]:
        """``ACCL::scatter``: root's ``count*world`` buffer chunked over ranks
        (fw :994-1125)."""
        t0 = _metrics.tick()
        with _trace.root("accl.scatter", count=count):
            comm = self._comm(comm)
            world = comm.world_size
            self._check_count(sendbuf, count * world, "scatter send")
            self._check_count(recvbuf, count, "scatter recv")
            x = self._input(sendbuf, count * world, from_device)
            with _trace.span("accl.plan"):
                key, build = self._spec_scatter(comm, count, sendbuf.dtype,
                                                root, compress_dtype,
                                                algorithm)
            with _trace.span("accl.launch"):
                y = self._programs.run(key, build, x).astype(
                    recvbuf.jnp_dtype)
                self._store(recvbuf, count, y)
            _metrics.note_call(
                operation.scatter,
                count * world * constants.dtype_size(sendbuf.dtype),
                sendbuf.dtype, key, t0)
            return self._finish(operation.scatter, recvbuf, y, to_device,
                                run_async, comm)

    def gather(
        self,
        sendbuf: BufLike,
        recvbuf: BufLike,
        count: int,
        root: int,
        from_device: bool = False,
        to_device: bool = False,
        run_async: bool = False,
        comm: Optional[Communicator] = None,
        compress_dtype: Optional[dataType] = None,
        algorithm: Optional[Algorithm] = None,
    ) -> Optional[Request]:
        """``ACCL::gather``: concat all sends at root (fw :1130-1296)."""
        t0 = _metrics.tick()
        with _trace.root("accl.gather", count=count):
            comm = self._comm(comm)
            world = comm.world_size
            self._check_count(sendbuf, count, "gather send")
            self._check_count(recvbuf, count * world, "gather recv")
            x = self._input(sendbuf, count, from_device)
            r = self._input(recvbuf, count * world, True)
            with _trace.span("accl.plan"):
                key, build = self._spec_gather(comm, count, sendbuf.dtype,
                                               root, compress_dtype,
                                               algorithm)
            with _trace.span("accl.launch"):
                y = self._programs.run(key, build, x, r)
                self._store(recvbuf, count * world, y)
            _metrics.note_call(operation.gather,
                               count * constants.dtype_size(sendbuf.dtype),
                               sendbuf.dtype, key, t0)
            return self._finish(operation.gather, recvbuf, y, to_device,
                                run_async, comm)

    def allgather(
        self,
        sendbuf: BufLike,
        recvbuf: BufLike,
        count: int,
        from_device: bool = False,
        to_device: bool = False,
        run_async: bool = False,
        comm: Optional[Communicator] = None,
        compress_dtype: Optional[dataType] = None,
        algorithm: Optional[Algorithm] = None,
    ) -> Optional[Request]:
        """``ACCL::allgather`` (fw :1299-1505)."""
        t0 = _metrics.tick()
        with _trace.root("accl.allgather", count=count):
            comm = self._comm(comm)
            world = comm.world_size
            self._check_count(sendbuf, count, "allgather send")
            self._check_count(recvbuf, count * world, "allgather recv")
            x = self._input(sendbuf, count, from_device)
            with _trace.span("accl.plan"):
                key, build = self._spec_allgather(comm, count, sendbuf.dtype,
                                                  compress_dtype, algorithm)
            with _trace.span("accl.launch"):
                y = self._programs.run(key, build, x).astype(
                    recvbuf.jnp_dtype)
                self._store(recvbuf, count * world, y)
            _metrics.note_call(operation.allgather,
                               count * constants.dtype_size(sendbuf.dtype),
                               sendbuf.dtype, key, t0)
            return self._finish(operation.allgather, recvbuf, y, to_device,
                                run_async, comm)

    def reduce(
        self,
        sendbuf: BufLike,
        recvbuf: BufLike,
        count: int,
        root: int,
        function: reduceFunction,
        from_device: bool = False,
        to_device: bool = False,
        run_async: bool = False,
        comm: Optional[Communicator] = None,
        compress_dtype: Optional[dataType] = None,
        algorithm: Optional[Algorithm] = None,
    ) -> Optional[Request]:
        """``ACCL::reduce`` (fw :1509-1744)."""
        t0 = _metrics.tick()
        with _trace.root("accl.reduce", count=count):
            comm = self._comm(comm)
            self._check_count(sendbuf, count, "reduce send")
            self._check_count(recvbuf, count, "reduce recv")
            x = self._input(sendbuf, count, from_device)
            r = self._input(recvbuf, count, True)
            with _trace.span("accl.plan"):
                key, build = self._spec_reduce(comm, count, sendbuf.dtype,
                                               root, function, compress_dtype,
                                               algorithm)
            with _trace.span("accl.launch"):
                y = self._programs.run(key, build, x, r)
                self._store(recvbuf, count, y)
            _metrics.note_call(operation.reduce,
                               count * constants.dtype_size(sendbuf.dtype),
                               sendbuf.dtype, key, t0)
            return self._finish(operation.reduce, recvbuf, y, to_device,
                                run_async, comm)

    def allreduce(
        self,
        sendbuf: BufLike,
        recvbuf: BufLike,
        count: int,
        function: reduceFunction,
        from_device: bool = False,
        to_device: bool = False,
        run_async: bool = False,
        comm: Optional[Communicator] = None,
        compress_dtype: Optional[dataType] = None,
        algorithm: Optional[Algorithm] = None,
    ) -> Optional[Request]:
        """``ACCL::allreduce`` (accl.cpp:796-842; fw :1855-2075) — the hot path."""
        t0 = _metrics.tick()
        with _trace.root("accl.allreduce", count=count):
            comm = self._comm(comm)
            self._check_count(sendbuf, count, "allreduce send")
            self._check_count(recvbuf, count, "allreduce recv")
            x = self._input(sendbuf, count, from_device)
            with _trace.span("accl.plan"):
                key, build = self._spec_allreduce(comm, count, sendbuf.dtype,
                                                  function, compress_dtype,
                                                  algorithm)
            with _trace.span("accl.launch"):
                y = self._programs.run(key, build, x).astype(
                    recvbuf.jnp_dtype)
                self._store(recvbuf, count, y)
            _metrics.note_call(operation.allreduce,
                               count * constants.dtype_size(sendbuf.dtype),
                               sendbuf.dtype, key, t0)
            return self._finish(operation.allreduce, recvbuf, y, to_device,
                                run_async, comm)

    def reduce_scatter(
        self,
        sendbuf: BufLike,
        recvbuf: BufLike,
        count: int,
        function: reduceFunction,
        from_device: bool = False,
        to_device: bool = False,
        run_async: bool = False,
        comm: Optional[Communicator] = None,
        compress_dtype: Optional[dataType] = None,
        algorithm: Optional[Algorithm] = None,
    ) -> Optional[Request]:
        """``ACCL::reduce_scatter``: ``count*world`` in, ``count`` out per rank
        (fw :1748-1852)."""
        t0 = _metrics.tick()
        with _trace.root("accl.reduce_scatter", count=count):
            comm = self._comm(comm)
            world = comm.world_size
            self._check_count(sendbuf, count * world, "reduce_scatter send")
            self._check_count(recvbuf, count, "reduce_scatter recv")
            x = self._input(sendbuf, count * world, from_device)
            with _trace.span("accl.plan"):
                key, build = self._spec_reduce_scatter(
                    comm, count, sendbuf.dtype, function, compress_dtype,
                    algorithm)
            with _trace.span("accl.launch"):
                y = self._programs.run(key, build, x).astype(
                    recvbuf.jnp_dtype)
                self._store(recvbuf, count, y)
            _metrics.note_call(
                operation.reduce_scatter,
                count * world * constants.dtype_size(sendbuf.dtype),
                sendbuf.dtype, key, t0)
            return self._finish(operation.reduce_scatter, recvbuf, y,
                                to_device, run_async, comm)

    def alltoall(
        self,
        sendbuf: BufLike,
        recvbuf: BufLike,
        count: int,
        from_device: bool = False,
        to_device: bool = False,
        run_async: bool = False,
        comm: Optional[Communicator] = None,
        compress_dtype: Optional[dataType] = None,
        algorithm: Optional[Algorithm] = None,
    ) -> Optional[Request]:
        """``ACCL::alltoall`` (fw :2123-2218)."""
        t0 = _metrics.tick()
        with _trace.root("accl.alltoall", count=count):
            comm = self._comm(comm)
            world = comm.world_size
            self._check_count(sendbuf, count * world, "alltoall send")
            self._check_count(recvbuf, count * world, "alltoall recv")
            x = self._input(sendbuf, count * world, from_device)
            with _trace.span("accl.plan"):
                key, build = self._spec_alltoall(comm, count, sendbuf.dtype,
                                                 compress_dtype, algorithm)
            with _trace.span("accl.launch"):
                y = self._programs.run(key, build, x).astype(
                    recvbuf.jnp_dtype)
                self._store(recvbuf, count * world, y)
            _metrics.note_call(
                operation.alltoall,
                count * world * constants.dtype_size(sendbuf.dtype),
                sendbuf.dtype, key, t0)
            return self._finish(operation.alltoall, recvbuf, y, to_device,
                                run_async, comm)

    def barrier(self, comm: Optional[Communicator] = None) -> None:
        """``ACCL::barrier`` (fw :2078-2120): flush outstanding work, then a
        zero-payload rendezvous exchange (scalar psum across the mesh).

        Multi-process: adds a host-level coordination-service barrier (the
        zero-byte notification gather/scatter analog) on top of the
        device-level psum, which every controller enters SPMD."""
        t0 = _metrics.tick()
        with _trace.root("accl.barrier"):
            comm = self._comm(comm)
            # flush only THIS communicator's traffic — a sub-communicator
            # barrier must not block on unrelated communicators (reference
            # barrier flushes per-communicator seqn state, fw :2081-2090)
            self._queue.drain(timeout=self.config.timeout, comm=comm)
            if comm.is_multiprocess:
                # host-level barrier FIRST, scoped to this communicator's
                # processes and driving the mover while it waits: a peer
                # may be blocked inside a pair move this process must
                # co-execute before it can enter the device collective
                # below. Scoping fixes the round-2 fabric's all-process
                # over-synchronization (a 2-rank sub-comm barrier no
                # longer blocks the whole job).
                procs = sorted({d.process_index for d in comm.devices})
                self._fabric.barrier(name=self._comm_tag(comm),
                                     process_ids=procs, pump=self._pump)
                shards = [
                    jax.device_put(np.ones((1,), np.int32), comm.device(r))
                    for r in comm.local_ranks
                ]
                token = jax.make_array_from_single_device_arrays(
                    (comm.world_size,), comm.sharding(), shards)
            else:
                token = jax.device_put(
                    np.ones((comm.world_size,), dtype=np.int32),
                    comm.sharding())
            with _trace.span("accl.launch"):
                jax.block_until_ready(self._programs.run(
                    self._key(comm, operation.barrier),
                    lambda: primitives.build_barrier(comm), token))
            # a barrier moves no payload; its "dispatch" histogram entry is
            # the whole synchronization (drain + host barrier + device psum)
            _metrics.note_call(operation.barrier, 0, dataType.int32, None,
                               t0)

    @staticmethod
    def _comm_tag(comm: Communicator) -> str:
        """Stable cross-process identity for a communicator: the ordered
        global device-id list (id(comm) differs per process)."""
        import hashlib
        ids = ",".join(str(d.id) for d in comm.devices)
        return hashlib.md5(ids.encode()).hexdigest()[:12]

    # ------------------------------------------------------------------
    # introspection (accl.cpp:980-1064 dump_* analogs)
    # ------------------------------------------------------------------

    def stats(self) -> dict:
        """Structured introspection snapshot — the firmware ``dump_*``
        family as ONE JSON-serializable object (round-trips through
        ``json.dumps`` by construction): resolved config, program-cache
        stats, in-flight queue depth, cooperative-scheduler state (parked
        continuations + retry-queue depths), per-communicator matcher /
        rx-pool / sequence-counter state, the cross-process fabric's
        control/data byte accounting, and the metrics delta since
        ``initialize()`` (the PERFCNT readout for this session)."""
        import json as _json

        from .parallel.synth import dcn_wire_totals as _dcn_totals
        from .parallel.synth import plan_cache_stats as _synth_stats

        progs, hits, misses = self._programs.stats()
        fresh, retry = self._sched.depths
        comms = []
        for comm in self.comms:
            m = self._matchers.get(id(comm))
            ns, nr = m.n_pending if m else (0, 0)
            pool = m.rx_pool if m else None
            if m is not None and m.is_native and comm.world_size <= 64:
                # the native engine owns the counters; enumerate pairs
                # through it (bounded: introspection stays O(P^2)-scan
                # free on big meshes — the python dicts below are then
                # simply empty, like the reference capping its dumps)
                P = comm.world_size
                out_seq = {f"{s}->{d}": v
                           for s in range(P) for d in range(P)
                           if (v := m.outbound_seq(s, d))}
                in_seq = {f"{s}->{d}": v
                          for s in range(P) for d in range(P)
                          if (v := m.inbound_seq(s, d))}
            else:
                # python engine: active pairs only — a quiet mesh dumps {}
                out_seq = {f"{s}->{d}": v for (s, d), v
                           in comm._outbound_seq.items()}
                in_seq = {f"{s}->{d}": v for (s, d), v
                          in comm._inbound_seq.items()}
            comms.append({
                "world_size": comm.world_size,
                "is_multiprocess": bool(comm.is_multiprocess),
                "pending_sends": ns,
                "pending_recvs": nr,
                "rx_pool": ({"free": pool.free_slots, "total": pool.size}
                            if pool else None),
                "outbound_seq": out_seq,
                "inbound_seq": in_seq,
            })
        fabric = None
        if self._fabric is not None:
            fabric = {
                "session": self._fabric.ns,
                "epoch": self._fabric.epoch,
                "kv_bytes": self._fabric.kv_bytes,
                "moved_bytes": self._fabric.moved_bytes,
                "staged_messages": len(self._fabric._staged),
                "pooled_messages": len(self._fabric._pool),
                "heartbeats": self._fabric._hb_count,
                "dead_peers": self._fabric.dead_peers,
                "excluded_peers": self._fabric.excluded_peers,
            }
        return {
            "schema": _metrics.SCHEMA_VERSION,
            # explicit top-level alias (r18): downstream tooling keys on
            # the unambiguous name; "schema" stays for old readers
            "schema_version": _metrics.SCHEMA_VERSION,
            "hwid": self.parse_hwid(),
            # local recovery count — the epoch baked into program/plan
            # cache keys (the fabric's epoch is under "fabric" below)
            "session_epoch": self._epoch,
            "config": _json.loads(self.config.to_json()),
            "program_cache": {"programs": progs, "hits": hits,
                              "misses": misses,
                              "evictions": self._programs.evictions,
                              "max_size": self._programs.maxsize},
            # the synth schedule-plan cache, beside the program cache it
            # feeds (module-global, reset per session by initialize())
            "sched_plan_cache": _synth_stats(),
            "dcn_wire": _dcn_totals(),
            "queue": {"inflight": len(self._queue.inflight)},
            "scheduler": {"parked_continuations": len(self._parked_calls),
                          "fresh_depth": fresh, "retry_depth": retry},
            "comms": comms,
            "fabric": fabric,
            "flight": _flight.stats(),
            "cluster": _cluster.stats(),
            "metrics": _metrics.delta(self._metrics_baseline),
        }

    def flight_dump(self, path: Optional[str] = None) -> Optional[str]:
        """Write the flight-recorder ring now (reason ``"manual"``).
        With ``path`` the file lands exactly there; otherwise under
        ``$ACCL_FLIGHT_DIR`` (None returned when neither names a
        destination — the ring stays inspectable via ``stats()``)."""
        return _flight.dump("manual", path=path)

    def cluster_stats(self) -> dict:
        """Merged cluster-wide metrics view (docs/observability.md):
        every controller's last published snapshot folded into one —
        counters summed, gauges maxed, histograms bucket-merged — with
        per-rank publish lag and explicit ``stale_ranks`` /
        ``missing_ranks`` verdicts. This controller's own snapshot is
        taken fresh (never stale by its own cadence); peers are read
        from the coordination KV where the fabric's progress loop
        publishes them. Works degraded without a fabric: the merge is
        then just this process."""
        me = jax.process_index()
        blobs: dict = {}
        if self._fabric is not None:
            procs = sorted({getattr(d, "process_index", 0)
                            for d in self.comms[0].devices})
            blobs = self._fabric.collect_obs(procs)
        blobs[me] = _cluster.payload(me)
        return _cluster.merge(blobs)

    def recalibrate(self) -> dict:
        """One online α/β recalibration pass (obs/recal): refit the
        scheduler cost registers from the accumulated dispatch-latency
        histograms and, when ``config.sched_online_recal`` is on AND
        some tier drifted beyond ``recal.DRIFT_RATIO``, write the
        fitted registers back through the config setter and bump the
        synth plan-cache recal generation so every plan re-resolves at
        the new prices. Sub-threshold or disarmed passes are advisory:
        the fit is returned, nothing changes. Outcome counted
        ``accl_recal_total{outcome}`` exactly once per call."""
        result = _recal.maybe_recalibrate(self.config)
        if result["outcome"] == "applied":
            from .parallel import synth as _synth

            self.config = self.config.replace(**result["registers"])
            gen = _synth.bump_recal_generation()
            result["recal_generation"] = gen
            _flight.record("recal_applied", generation=gen,
                           worst_drift=result.get("worst_drift"),
                           registers=dict(result["registers"]))
        return result

    def dump_state(self) -> str:
        progs, hits, misses = self._programs.stats()
        fresh, retry = self._sched.depths
        lines = [
            "ACCL-TPU state:",
            f"  {self.parse_hwid()}",
            f"  program cache: {progs} programs, {hits} hits, {misses} misses",
            f"  inflight requests: {len(self._queue.inflight)}",
            f"  scheduler: {len(self._parked_calls)} parked continuations, "
            f"queue depths fresh={fresh} retry={retry}",
        ]
        for comm in self.comms:
            lines.append(comm.dump())
            m = self._matchers.get(id(comm))
            if m is not None:
                lines.append(m.dump())
        return "\n".join(lines)

    def dump_communicator(self, comm: Optional[Communicator] = None) -> str:
        return (comm or self.comms[0]).dump()

    def dump_eager_rx_buffers(self, comm: Optional[Communicator] = None) -> str:
        """Per-slot pool table (``ACCL::dump_eager_rx_buffers``,
        accl.cpp:999-1064): status / occupancy / tag / seqn per slot."""
        return self.matcher(comm).rx_pool.dump()
