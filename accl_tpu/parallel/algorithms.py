"""Runtime algorithm selection (SURVEY.md §2.6 "selectable strategies").

The reference firmware picks flat vs binary-tree vs ring per call from
size/world thresholds held in tuning registers (``ccl_offload_control.c:
816,1533``; written at init from ``accl.cpp:1214-1224``). This module is
that selector for the TPU build: given (operation, payload bytes, world,
config) it returns the algorithm family, and dispatches to the matching
program builder.

Defaults: XLA-native single-shot programs for small/medium payloads (XLA's
own collectives are the latency-optimal "rendezvous single move" path on
ICI), explicit chunked ring for large payloads where fixed reduction order
and per-hop compression matter, hierarchical 2-D for very large payloads on
composite world sizes. Every family remains force-selectable per call —
the tuning-register analog.
"""
from __future__ import annotations

from typing import Callable, Optional

from ..arithconfig import ArithConfig
from ..communicator import Communicator
from ..config import ACCLConfig, Algorithm, TransportBackend
from ..constants import ACCLError, dataType, errorCode, operation, reduceFunction
from ..obs import metrics as _metrics
from . import flat, hierarchical, pallas_ring, primitives, ring, synth, tree

#: default payload size above which AUTO prefers the explicit ring (bytes);
#: per-session values live in ACCLConfig.ring_threshold (autotunable)
RING_THRESHOLD = 4 * 1024 * 1024
#: default payload size above which AUTO prefers hierarchical 2D on
#: composite worlds; per-session: ACCLConfig.hier_threshold
HIER_THRESHOLD = 64 * 1024 * 1024
#: default for ACCLConfig.dcn_hier_threshold — on a multi-host (DCN) mesh
#: hierarchical wins much earlier: the heavy phases stay on intra-host ICI
#: and only the n/cols shard crosses the DCN
DCN_HIER_THRESHOLD = 64 * 1024


def _hier_shape(comm: Communicator, on_dcn: bool = False):
    """2-D factorization for hierarchical collectives: host-aligned when
    the mesh spans hosts (rows = hosts, so DCN traffic is the small
    phase), most-square otherwise. On a DCN transport WITHOUT a
    host-aligned shape there is no valid auto split at all — the factor2d
    fallback would put the bandwidth-heavy "intra-host" phase on DCN
    links (the ADVICE r2 #4 trap, which applies to every AUTO engage
    point, not just the early dcn_hier_threshold branch)."""
    hs = comm.hosts_shape()
    if hs is not None:
        return hs
    if on_dcn:
        return None
    return hierarchical.factor2d(comm.world_size)

_SUPPORTED = {
    operation.bcast: {Algorithm.XLA, Algorithm.FLAT, Algorithm.TREE,
                      Algorithm.RING, Algorithm.PALLAS},
    operation.reduce: {Algorithm.XLA, Algorithm.FLAT, Algorithm.TREE,
                       Algorithm.RING, Algorithm.PALLAS},
    operation.allreduce: {Algorithm.XLA, Algorithm.FLAT, Algorithm.TREE,
                          Algorithm.RING, Algorithm.HIERARCHICAL,
                          Algorithm.PALLAS, Algorithm.MULTIAXIS,
                          Algorithm.TWOTIER},
    operation.allgather: {Algorithm.XLA, Algorithm.RING, Algorithm.PALLAS,
                          Algorithm.MULTIAXIS, Algorithm.TWOTIER},
    operation.reduce_scatter: {Algorithm.XLA, Algorithm.RING,
                               Algorithm.PALLAS, Algorithm.MULTIAXIS,
                               Algorithm.TWOTIER},
    operation.scatter: {Algorithm.XLA, Algorithm.FLAT, Algorithm.PALLAS},
    operation.gather: {Algorithm.XLA, Algorithm.FLAT, Algorithm.RING,
                       Algorithm.PALLAS},
    operation.alltoall: {Algorithm.XLA, Algorithm.FLAT, Algorithm.PALLAS},
    # the overlapped TP matmul family: one program is both the collective
    # and the matmul, so only the fused Pallas kernels and the unfused
    # XLA pair exist as families
    operation.allgather_matmul: {Algorithm.XLA, Algorithm.PALLAS},
    operation.matmul_reduce_scatter: {Algorithm.XLA, Algorithm.PALLAS},
    # the expert-parallel fused a2a pair: same two-family structure
    operation.alltoall_matmul: {Algorithm.XLA, Algorithm.PALLAS},
    operation.matmul_alltoall: {Algorithm.XLA, Algorithm.PALLAS},
}


def supported(op: operation, algo: Algorithm) -> bool:
    return algo in _SUPPORTED.get(op, {Algorithm.XLA})


#: (algorithm, op) pairs already warned about — the global-preference
#: fallback is observable exactly once per pair (ADVICE r2 #5). Scope
#: is one SESSION, not the process: ACCL.initialize() clears it via
#: :func:`reset_global_fallback_warnings`, so a fresh session (or a
#: test constructing its own ACCL) observes its own misconfiguration
#: again instead of inheriting a prior session's silence.
_warned_global_fallback: set = set()


def reset_global_fallback_warnings() -> None:
    """Session hook: forget which (algorithm, op) fallbacks were already
    warned about. Called by ``ACCL.initialize()`` — the module-global
    set would otherwise leak across sessions and test runs."""
    _warned_global_fallback.clear()


def cmatmul_wire_bytes(op: operation, nbytes: int, cfg: ACCLConfig,
                       count: Optional[int] = None) -> int:
    """Effective ICI bytes for a collective-matmul/fused-a2a payload
    under the session wire dtype (``ACCLConfig.cmatmul_wire_dtype``).

    ``nbytes`` follows the op's operand-byte convention (agmm: LHS
    shard bytes in the operand dtype; mmrs: travelling f32 accumulator
    bytes; alltoall_matmul: per-destination token-block bytes;
    matmul_alltoall: f32 y-block bytes); ``count`` (elements) resolves
    the operand width exactly —
    without it the f32 default is assumed, so callers dispatching
    NON-f32 agmm operands MUST pass count or select() will scale bytes
    the wire cannot actually compress (the kernel-module resolution
    never narrows same-width operands and is authoritative for
    device-side dispatch). Full-precision sessions (and wire dtypes at
    least as wide as the operand) return nbytes unchanged."""
    name = cfg.cmatmul_wire_dtype
    if not name:
        return nbytes
    from ..ops import collective_matmul as cm
    wdt = cm._ALL_WIRE_NAMES.get(name)
    if wdt is None:
        return nbytes
    import jax.numpy as jnp

    wisz = jnp.dtype(wdt).itemsize
    op_isz = (nbytes // count) if count else 4
    if op_isz <= wisz or op_isz <= 0:
        return nbytes   # the wire never upcasts
    return (nbytes // op_isz) * wisz


def select(
    op: operation,
    nbytes: int,
    comm: Communicator,
    cfg: ACCLConfig,
    requested: Optional[Algorithm] = None,
    count: Optional[int] = None,
) -> Algorithm:
    """Resolve the algorithm for one call — the tuning-register thresholds
    of the firmware's per-collective selection (flat vs binary tree:
    ``ccl_offload_control.c:816`` bcast, ``:1533`` reduce). Every
    resolution is counted (``accl_algorithm_selected_total``) so AUTO's
    behavior over a workload is attributable after the fact.

    For the bandwidth collectives (allreduce / allgather /
    reduce_scatter) the scalar ladder below is the LEGACY layer of a
    two-stage resolution: its decision feeds the topology-aware
    schedule synthesizer (:mod:`accl_tpu.parallel.synth`), whose cached
    α-β cost-model search may upgrade it to the multi-axis torus
    decomposition (``Algorithm.MULTIAXIS``) — sequential or
    chunk-PIPELINED (the plan's ``pipeline_chunks`` param; the per-axis
    legs of successive chunks overlap) — on meshes with a declared or
    coordinate-detected torus shape, including declared 3-axis shapes.
    On ICI such a mesh keeps XLA's own collective unless the ladder's
    plan strictly beats its price; the multi-axis shapes compete there
    only for an allreduce whose arith decompresses before arithmetic
    (``xla_gathers``: XLA's path would all-gather the whole buffer).
    On a host-aligned multi-slice DCN mesh with ``cfg.dcn_wire_dtype``
    set, the synthesizer's per-tier cost model (DCN α/β for cross-slice
    steps, ICI α/β intra-slice) may instead upgrade to the TWO-TIER
    schedule (``Algorithm.TWOTIER``: intra-slice reduce-scatter →
    compressed cross-slice exchange → intra-slice all-gather;
    ``dcn_wire_dtype="off"`` keeps every DCN resolution byte-identical
    to the ladder — the opt-in contract, docs/scheduling.md §two-tier).
    Non-default scalar registers are autotune seeds and pin the legacy
    decision; single-axis meshes with default config resolve exactly as
    the ladder alone (``cfg.sched_full_authority`` retires the ladder
    outright when set) — see ``docs/scheduling.md`` for the cost model,
    candidate space, pipelined-phase formula and override/migration
    story."""
    algo, _ = select_plan(op, nbytes, comm, cfg, requested, count)
    return algo


def select_plan(
    op: operation,
    nbytes: int,
    comm: Communicator,
    cfg: ACCLConfig,
    requested: Optional[Algorithm] = None,
    count: Optional[int] = None,
    wire_inert: bool = False,
    xla_gathers: bool = False,
):
    """:func:`select` plus the resolved :class:`synth.SchedulePlan` when
    the synthesizer owned the decision (None for explicit requests,
    world-1, and ops outside ``synth.SYNTH_OPS``) — the dispatch layer
    reads the plan's ``pipeline_chunks``/``shape2d`` params so the
    program it builds matches the schedule the plan counters claim.
    ``wire_inert`` marks a call the DCN cross-slice codec cannot
    actually compress — an ArithConfig wire already narrowing every
    hop, or a payload dtype the codec refuses (ints, bf16/f16): the
    two-tier window stays closed there (the builders stand the
    per-leg codec down, so pricing or counting it would describe an
    exchange that never runs). ``xla_gathers`` marks an allreduce whose
    arith decompresses before arithmetic: XLA's native path then
    all-gathers the whole wire buffer (``primitives.build_allreduce``),
    so the ICI window does not offer it (:func:`synth.resolve`)."""
    algo, plan = _select(op, nbytes, comm, cfg, requested, count,
                         wire_inert, xla_gathers)
    _metrics.inc("accl_algorithm_selected_total",
                 labels=(("op", op.name), ("algorithm", algo.value)))
    if plan is not None and plan.shape == "twotier":
        # per-dispatch accounting of the cross-slice leg's pre/post
        # compression bytes (accl_dcn_wire_bytes_total{op,dtype,stage})
        synth.note_dcn_wire_bytes(op, plan, nbytes, count)
    return algo, plan


def _select(
    op: operation,
    nbytes: int,
    comm: Communicator,
    cfg: ACCLConfig,
    requested: Optional[Algorithm] = None,
    count: Optional[int] = None,
    wire_inert: bool = False,
    xla_gathers: bool = False,
):
    algo = requested or cfg.algorithm
    if algo != Algorithm.AUTO:
        if supported(op, algo):
            return algo, None
        if requested is not None:
            raise ValueError(f"{algo} not supported for {op.name}")
        # a global cfg.algorithm preference that this op cannot honor falls
        # through to AUTO resolution rather than poisoning unrelated ops.
        # EVERY occurrence increments the fallback counter — the warn-once
        # set dedupes only the LOG LINE, so the telemetry tier still shows
        # how often the misconfiguration bit (ISSUE r8: the warn-once set
        # suppressed all signal after the first hit)
        _metrics.inc("accl_algorithm_fallback_total",
                     labels=(("op", op.name), ("algorithm", algo.value)))
        if (algo, op) not in _warned_global_fallback:
            _warned_global_fallback.add((algo, op))
            from ..utils.logging import get_logger
            get_logger("algorithms").warning(
                "session algorithm %s unsupported for %s; using AUTO",
                algo.name, op.name)
    world = comm.world_size
    if world == 1:
        return Algorithm.XLA, None
    legacy = _select_legacy(op, nbytes, comm, cfg, count)
    if op in synth.SYNTH_OPS:
        # second stage: the schedule synthesizer may upgrade the ladder's
        # decision to the multi-axis torus decomposition (cached per
        # (op, topology, size-bucket); legacy seeds stay binding)
        plan = synth.resolve(op, nbytes, comm, cfg, legacy, count=count,
                             wire_inert=wire_inert,
                             xla_gathers=xla_gathers)
        return plan.algorithm, plan
    return legacy, None


def _select_legacy(
    op: operation,
    nbytes: int,
    comm: Communicator,
    cfg: ACCLConfig,
    count: Optional[int] = None,
) -> Algorithm:
    """The scalar-threshold ladder — the pre-synthesis resolution,
    preserved verbatim: it remains authoritative for every op outside
    :data:`synth.SYNTH_OPS`, for single-axis meshes, and wherever a
    non-default register (an autotune seed) overrides the cost model."""
    world = comm.world_size
    on_dcn = cfg.transport == TransportBackend.DCN
    if on_dcn:
        # multi-host: long edges are expensive. Hierarchical allreduce as
        # soon as the payload justifies it (cfg.dcn_hier_threshold — set
        # by autotune when measured on the live DCN mesh); log-depth trees
        # for rooted rendezvous ops (a flat star would cross the DCN
        # world-1 times). The early engage needs a HOST-aligned 2-D shape:
        # with one device per host the factor2d fallback would put the
        # bandwidth-heavy "intra-host" phase on DCN links — a perf trap,
        # so fall through to the ICI thresholds instead (ADVICE r2 #4).
        # The silent fall-through is COUNTED (op + reason), mirroring the
        # accl_cmatmul_fallback_total discipline: a non-host-aligned mesh
        # losing the hierarchical engage is attributable, not invisible
        if op == operation.allreduce and nbytes >= cfg.dcn_hier_threshold:
            if comm.hosts_shape() is not None:
                return Algorithm.HIERARCHICAL
            _metrics.inc("accl_select_decline_total",
                         labels=(("op", op.name),
                                 ("reason", "dcn_no_host_shape")))
        if op in (operation.bcast, operation.reduce) \
                and nbytes > cfg.max_eager_size:
            return Algorithm.TREE
    if cfg.transport == TransportBackend.ICI:
        # the RDMA-over-ICI perf core is the default large-payload path on
        # real chip-to-chip links (VMEM ring below the staging threshold,
        # segmented HBM kernels above it — the builders split internally).
        # Per-op thresholds: each op's nbytes convention differs (count vs
        # per-block vs total input bytes), so one shared value would mix
        # units; autotune measures each crossover like the ring pair.
        pallas_at = {
            operation.allreduce: cfg.pallas_threshold,
            operation.allgather: cfg.ag_pallas_threshold,
            operation.reduce_scatter: cfg.rs_pallas_threshold,
            operation.bcast: cfg.bcast_pallas_threshold,
            operation.gather: cfg.gather_pallas_threshold,
            operation.scatter: cfg.scatter_pallas_threshold,
            operation.alltoall: cfg.alltoall_pallas_threshold,
            operation.reduce: cfg.reduce_pallas_threshold,
            # overlap-vs-XLA thresholds for the collective-matmul family
            # (allgather_matmul: LHS shard bytes; matmul_reduce_scatter:
            # travelling f32 accumulator bytes) — autotuned by
            # bench.autotune_collective_matmul (the per-aspect-class
            # registers live on the kernel module's session-default
            # resolution; select() reads the scalar square-class ones)
            operation.allgather_matmul: cfg.ag_matmul_threshold,
            operation.matmul_reduce_scatter: cfg.rs_matmul_threshold,
            # the fused MoE a2a pair shares ONE register: both
            # directions move the same (e_local, C, d) block per
            # exchange (dispatch: token blocks in the operand dtype;
            # combine: f32 y blocks) — autotuned by
            # bench.autotune_moe_a2a
            operation.alltoall_matmul: cfg.a2a_matmul_threshold,
            operation.matmul_alltoall: cfg.a2a_matmul_threshold,
        }.get(op)
        if op in (operation.allgather_matmul,
                  operation.matmul_reduce_scatter,
                  operation.alltoall_matmul,
                  operation.matmul_alltoall):
            # the register compares WIRE bytes: under a session wire
            # dtype (ACCLConfig.cmatmul_wire_dtype) the payload moves
            # fewer bytes than the caller's operand-byte convention, so
            # the comparison scales nbytes to effective wire bytes —
            # select() and the kernel-module resolution stay in one unit
            nbytes = cmatmul_wire_bytes(op, nbytes, cfg, count)
        if pallas_at is not None and nbytes >= pallas_at:
            return Algorithm.PALLAS
    if op == operation.allreduce and nbytes >= cfg.hier_threshold:
        if _hier_shape(comm, on_dcn) is not None:
            return Algorithm.HIERARCHICAL
        # same visibility for the generic engage point: a prime world
        # (no 2-D split) or a non-host-aligned DCN mesh declines here
        _metrics.inc("accl_select_decline_total",
                     labels=(("op", op.name),
                             ("reason", "dcn_no_host_shape" if on_dcn
                              else "no_2d_shape")))
    if op == operation.allreduce and nbytes >= cfg.ring_threshold:
        return Algorithm.RING
    if op == operation.allgather and nbytes >= cfg.ag_ring_threshold:
        return Algorithm.RING
    if op == operation.reduce_scatter and nbytes >= cfg.rs_ring_threshold:
        return Algorithm.RING
    if nbytes > cfg.max_eager_size:
        # rendezvous regime: the fw picks flat vs binary tree by world size
        # (BCAST_FLAT_TREE_MAX_RANKS, :816-869) and, for reduce, also by
        # count (REDUCE_FLAT_TREE_MAX_COUNT, :1533-1602)
        if op == operation.bcast:
            return (Algorithm.FLAT
                    if world <= cfg.bcast_flat_tree_max_ranks
                    else Algorithm.TREE)
        if op == operation.reduce:
            small = count is not None and count <= cfg.reduce_flat_tree_max_count
            return (Algorithm.FLAT
                    if world <= cfg.reduce_flat_tree_max_ranks or small
                    else Algorithm.TREE)
        if op in (operation.scatter, operation.gather, operation.alltoall):
            # fw rendezvous scatter/gather/alltoall are all flat-tree
            # families (:1011-1081, :1144-1206, :2123-2218)
            return Algorithm.FLAT
    return Algorithm.XLA


# ---------------------------------------------------------------------------
# builder dispatch
# ---------------------------------------------------------------------------

def build_bcast(comm, root: int, algo: Algorithm,
                arith: Optional[ArithConfig],
                dt: Optional[dataType] = None,
                segment_bytes: Optional[int] = None) -> Callable:
    if algo == Algorithm.PALLAS:
        if dt is None:
            raise ValueError("Algorithm.PALLAS bcast requires dt")
        from . import pallas_chunked
        return pallas_chunked.build_chunked_ring_bcast(
            comm, root, dt, segment_bytes, arith=arith)
    if algo == Algorithm.FLAT:
        return flat.build_flat_bcast(comm, root, arith)
    if algo == Algorithm.TREE:
        return tree.build_tree_bcast(comm, root, arith)
    if algo == Algorithm.RING:
        return ring.build_ring_bcast(comm, root, arith)
    return primitives.build_bcast(comm, root, arith)


def build_scatter(comm, root: int, algo: Algorithm,
                  arith: Optional[ArithConfig],
                  dt: Optional[dataType] = None,
                  segment_bytes: Optional[int] = None) -> Callable:
    if algo == Algorithm.PALLAS:
        if dt is None:
            raise ValueError("Algorithm.PALLAS scatter requires dt")
        from . import pallas_chunked
        return pallas_chunked.build_chunked_ring_scatter(
            comm, root, dt, segment_bytes, arith=arith)
    if algo == Algorithm.FLAT:
        return flat.build_flat_scatter(comm, root, arith)
    return primitives.build_scatter(comm, root, arith)


def build_gather(comm, root: int, algo: Algorithm,
                 arith: Optional[ArithConfig], fanin: int = 0,
                 dt: Optional[dataType] = None,
                 segment_bytes: Optional[int] = None) -> Callable:
    if algo == Algorithm.PALLAS:
        if dt is None:
            raise ValueError("Algorithm.PALLAS gather requires dt")
        from . import pallas_chunked
        return pallas_chunked.build_chunked_ring_gather(
            comm, root, dt, segment_bytes, arith=arith)
    if algo == Algorithm.FLAT:
        return flat.build_flat_gather(comm, root, arith, fanin)
    if algo == Algorithm.RING:
        return ring.build_ring_gather(comm, root, arith)
    return primitives.build_gather(comm, root, arith)


def build_alltoall(comm, algo: Algorithm,
                   arith: Optional[ArithConfig],
                   dt: Optional[dataType] = None,
                   segment_bytes: Optional[int] = None) -> Callable:
    if algo == Algorithm.PALLAS:
        if dt is None:
            raise ValueError("Algorithm.PALLAS alltoall requires dt")
        from . import pallas_chunked
        return pallas_chunked.build_chunked_ring_alltoall(
            comm, dt, segment_bytes, arith=arith)
    if algo == Algorithm.FLAT:
        return flat.build_flat_alltoall(comm, arith)
    return primitives.build_alltoall(comm, arith)


def build_reduce(comm, root: int, func: reduceFunction, dt: dataType,
                 algo: Algorithm, arith: Optional[ArithConfig],
                 fanin: int = 0,
                 segment_bytes: Optional[int] = None) -> Callable:
    if algo == Algorithm.PALLAS:
        from . import pallas_chunked
        return pallas_chunked.build_chunked_ring_reduce(
            comm, root, func, dt, segment_bytes, arith=arith)
    if algo == Algorithm.FLAT:
        return flat.build_flat_reduce(comm, root, func, dt, arith, fanin)
    if algo == Algorithm.TREE:
        return tree.build_tree_reduce(comm, root, func, dt, arith)
    if algo == Algorithm.RING:
        return ring.build_ring_reduce(comm, root, func, dt, arith)
    return primitives.build_reduce(comm, root, func, dt, arith)


def _multiaxis_shape(comm, mesh_shape) -> tuple:
    """The axes tuple for an explicit/resolved MULTIAXIS build — any
    rank >= 2 (a declared ``(2, 2, 2)`` dispatches a real 3-axis
    decomposition): the caller passes the synthesizer's resolved torus
    shape when it has one; a direct build without one falls back to the
    most-square 2-D split (the ``_hier_shape`` discipline for explicit
    requests) and fails loudly on prime worlds."""
    if mesh_shape is not None:
        axes = tuple(int(s) for s in mesh_shape)
        p = 1
        for s in axes:
            p *= s
        if p != comm.world_size:
            raise ValueError(
                f"mesh_shape {'x'.join(map(str, axes))} != world "
                f"{comm.world_size}")
        return axes
    shape = hierarchical.factor2d(comm.world_size)
    if shape is None:
        raise ValueError(
            "multiaxis collective needs a composite world with a 2-D "
            f"torus factorization, got world={comm.world_size}")
    return tuple(shape)


def _twotier_shape(comm, mesh_shape) -> tuple:
    """(slices, per_slice) for a two-tier build: the resolved plan's
    shape when the synthesizer picked it, else the PHYSICAL slice
    boundary (``comm.hosts_shape()``), else — for explicit requests on
    single-host rigs (the bench A/B, the emulator) — the most-square
    factorization, failing loudly on prime worlds."""
    if mesh_shape is not None:
        s = tuple(int(v) for v in mesh_shape)
        if len(s) != 2 or s[0] * s[1] != comm.world_size:
            raise ValueError(
                f"two-tier shape {s} != world {comm.world_size}")
        return s
    hs = comm.hosts_shape()
    if hs is not None:
        return tuple(hs)
    shape = hierarchical.factor2d(comm.world_size)
    if shape is None:
        raise ValueError(
            "two-tier collective needs a composite world with a "
            f"(slices, per_slice) split, got world={comm.world_size}")
    return tuple(shape)


def build_allreduce(comm, func: reduceFunction, dt: dataType, algo: Algorithm,
                    arith: Optional[ArithConfig],
                    segment_bytes: Optional[int] = None,
                    fanin: int = 0,
                    bidirectional: bool = False,
                    on_dcn: bool = False,
                    mesh_shape=None,
                    pipeline_chunks: int = 1,
                    dcn_wire_dtype=None) -> Callable:
    if algo == Algorithm.TWOTIER:
        s2 = _twotier_shape(comm, mesh_shape)
        return hierarchical.build_twotier_allreduce(
            comm, s2[0], s2[1], func, dt, arith,
            dcn_wire_dtype=dcn_wire_dtype)
    if algo == Algorithm.MULTIAXIS:
        axes = _multiaxis_shape(comm, mesh_shape)
        return synth.build_multiaxis_allreduce(
            comm, axes, func, dt, arith, pipeline_chunks=pipeline_chunks)
    if algo == Algorithm.PALLAS:
        return pallas_ring.build_pallas_ring_allreduce(
            comm, func, dt, segment_bytes, arith=arith,
            bidirectional=bidirectional)
    if algo == Algorithm.FLAT:
        return flat.build_flat_allreduce(comm, func, dt, arith, fanin)
    if algo == Algorithm.RING:
        return ring.build_ring_allreduce(comm, func, dt, arith)
    if algo == Algorithm.TREE:
        return tree.build_tree_allreduce(comm, func, dt, arith)
    if algo == Algorithm.HIERARCHICAL:
        # on_dcn: an explicit HIERARCHICAL request on a DCN mesh without a
        # host-aligned shape must fail loudly, not take the factor2d split
        # that puts the bandwidth-heavy phase on DCN links (the same trap
        # select() avoids — ADVICE r3 #1)
        rc = _hier_shape(comm, on_dcn)
        if rc is None:
            raise ValueError(
                "hierarchical allreduce needs a composite world"
                + (" with a host-aligned 2-D shape on DCN" if on_dcn else "")
                + f", got world={comm.world_size}"
            )
        return hierarchical.build_hier_allreduce(comm, rc[0], rc[1], func, dt, arith)
    return primitives.build_allreduce(comm, func, dt, arith)


def build_allgather_matmul(comm, algo: Algorithm,
                           bidirectional: bool = True,
                           wire_dtype=None) -> Callable:
    """(world, m, k) sharded LHS row shards + (world, k, n) sharded
    weight blocks -> (world, world*m, n): ``all_gather(x, rows) @ w``.
    PALLAS runs the comm/compute-overlapped ring kernel
    (ops/collective_matmul.py; resident or k-blocked streaming per the
    plan); anything else the unfused XLA pair. ``wire_dtype`` stages
    the ring payload compressed ("off" pins full precision)."""
    from ..ops import collective_matmul as cm
    if algo == Algorithm.PALLAS:
        pallas_ring._check_multiprocess(comm)

    def body(x, w):
        y = cm.all_gather_matmul_body(
            x[0], w[0], axis=primitives.AXIS,
            overlap=(algo == Algorithm.PALLAS),
            bidirectional=bidirectional, wire_dtype=wire_dtype)
        return y[None]

    return primitives._smap(comm, body, 2)


def build_matmul_reduce_scatter(comm, algo: Algorithm,
                                bidirectional: bool = True,
                                wire_dtype=None) -> Callable:
    """(world, m, k) sharded local rows + (world, k, n) sharded weight
    blocks -> (world, m/world, n): ``reduce_scatter(x @ w, rows)`` with
    the per-hop partial folded into the ring under PALLAS."""
    from ..ops import collective_matmul as cm
    if algo == Algorithm.PALLAS:
        pallas_ring._check_multiprocess(comm)

    def body(x, w):
        y = cm.matmul_reduce_scatter_body(
            x[0], w[0], axis=primitives.AXIS,
            overlap=(algo == Algorithm.PALLAS),
            bidirectional=bidirectional, wire_dtype=wire_dtype)
        return y[None]

    return primitives._smap(comm, body, 2)


def build_fsdp_matmul(comm, algo: Algorithm,
                      bidirectional: bool = True,
                      wire_dtype=None) -> Callable:
    """(world, m, k) sharded local rows + (world, n/world, k) sharded
    weight-column shards in travel layout -> (world, m, n):
    ``x @ all_gather(wt)ᵀ`` — the ZeRO/FSDP forward with the parameter
    gather folded into the matmul. PALLAS runs the agmm ring kernel on
    the TRAVELLING WEIGHT SHARD (ops/collective_matmul.py — FSDP's
    forward, no materialized full weight); anything else the unfused
    gather + matmul pair. Used by the ``zero_fsdp`` autotune/bench
    path; the training step itself composes the same kernels through
    :mod:`accl_tpu.models.zero`."""
    from ..ops import collective_matmul as cm
    if algo == Algorithm.PALLAS:
        pallas_ring._check_multiprocess(comm)

    def body(x, wt):
        yt = cm.all_gather_matmul_body(
            wt[0], x[0].T, axis=primitives.AXIS,
            overlap=(algo == Algorithm.PALLAS),
            bidirectional=bidirectional, wire_dtype=wire_dtype)
        return yt.T[None]

    return primitives._smap(comm, body, 2)


def build_pipeline_relay(comm, algo: Algorithm) -> Callable:
    """(world, n, d) forward payloads + (world, n, d) backward payloads
    -> the pair after ONE pipeline tick's relay: forward shards shift +1
    ring hop, backward shards -1 — both directions of every link at
    once.  PALLAS runs the fused double-buffered credit-semaphore kernel
    (``ops/pipeline_relay.py`` — the 1F1B activation relay); anything
    else the ``ppermute`` pair.  The standalone program form the bench
    and schedule suites exercise; the train steps compose the same op
    through :mod:`accl_tpu.models.pipeline`."""
    from ..ops import pipeline_relay as pr
    if algo == Algorithm.PALLAS:
        pallas_ring._check_multiprocess(comm)

    def body(f, b):
        fo, bo = pr.pp_relay(f[0], b[0], primitives.AXIS,
                             (primitives.AXIS,),
                             overlap=(algo == Algorithm.PALLAS))
        return fo[None], bo[None]

    from jax.sharding import PartitionSpec as P
    return primitives._smap(comm, body, 2,
                            out_specs=(P(primitives.AXIS),
                                       P(primitives.AXIS)))


def build_alltoall_matmul(comm, algo: Algorithm,
                          bidirectional: bool = True,
                          wire_dtype=None) -> Callable:
    """(world, E, C, d) per-destination token blocks + (world, e_local,
    d, h) expert in-projections -> (world, e_local, world*C, h):
    ``einsum(all_to_all(x), w)``.  PALLAS runs the comm/compute-
    overlapped flat-exchange kernel (ops/collective_alltoall.py — each
    arriving block's expert matmul hides the next exchange's wire
    time); anything else the unfused XLA pair. ``wire_dtype`` stages
    the token payload compressed ("off" pins full precision)."""
    from ..ops import collective_alltoall as ca
    if algo == Algorithm.PALLAS:
        pallas_ring._check_multiprocess(comm)

    def body(x, w):
        y = ca.alltoall_matmul_body(
            x[0], w[0], axis=primitives.AXIS,
            overlap=(algo == Algorithm.PALLAS),
            bidirectional=bidirectional, wire_dtype=wire_dtype)
        return y[None]

    return primitives._smap(comm, body, 2)


def build_matmul_alltoall(comm, algo: Algorithm,
                          bidirectional: bool = True,
                          wire_dtype=None) -> Callable:
    """(world, e_local, world*C, hd) expert activations + (world,
    e_local, hd, d) out-projections -> (world, E, C, d):
    ``all_to_all(einsum(h, w))`` with each destination's block on the
    wire while the next destination's matmul runs under PALLAS."""
    from ..ops import collective_alltoall as ca
    if algo == Algorithm.PALLAS:
        pallas_ring._check_multiprocess(comm)

    def body(h, w):
        y = ca.matmul_alltoall_body(
            h[0], w[0], axis=primitives.AXIS,
            overlap=(algo == Algorithm.PALLAS),
            bidirectional=bidirectional, wire_dtype=wire_dtype)
        return y[None]

    return primitives._smap(comm, body, 2)


def build_allgather(comm, algo: Algorithm,
                    arith: Optional[ArithConfig],
                    dt: dataType,
                    segment_bytes: Optional[int] = None,
                    bidirectional: bool = False,
                    mesh_shape=None,
                    pipeline_chunks: int = 1,
                    dcn_wire_dtype=None) -> Callable:
    if algo == Algorithm.TWOTIER:
        s2 = _twotier_shape(comm, mesh_shape)
        return hierarchical.build_twotier_allgather(
            comm, s2[0], s2[1], arith, dcn_wire_dtype=dcn_wire_dtype)
    if algo == Algorithm.MULTIAXIS:
        axes = _multiaxis_shape(comm, mesh_shape)
        return synth.build_multiaxis_allgather(
            comm, axes, arith, pipeline_chunks=pipeline_chunks)
    if algo == Algorithm.PALLAS:
        return pallas_ring.build_pallas_ring_allgather(
            comm, dt, segment_bytes, arith=arith,
            bidirectional=bidirectional)
    if algo == Algorithm.RING:
        return ring.build_ring_allgather(comm, arith)
    return primitives.build_allgather(comm, arith)


def build_reduce_scatter(comm, func: reduceFunction, dt: dataType,
                         algo: Algorithm,
                         arith: Optional[ArithConfig],
                         segment_bytes: Optional[int] = None,
                         bidirectional: bool = False,
                         mesh_shape=None,
                         pipeline_chunks: int = 1,
                         dcn_wire_dtype=None) -> Callable:
    if algo == Algorithm.TWOTIER:
        s2 = _twotier_shape(comm, mesh_shape)
        return hierarchical.build_twotier_reduce_scatter(
            comm, s2[0], s2[1], func, dt, arith,
            dcn_wire_dtype=dcn_wire_dtype)
    if algo == Algorithm.MULTIAXIS:
        axes = _multiaxis_shape(comm, mesh_shape)
        return synth.build_multiaxis_reduce_scatter(
            comm, axes, func, dt, arith, pipeline_chunks=pipeline_chunks)
    if algo == Algorithm.PALLAS:
        return pallas_ring.build_pallas_ring_reduce_scatter(
            comm, func, dt, segment_bytes, arith=arith,
            bidirectional=bidirectional)
    if algo == Algorithm.RING:
        return ring.build_ring_reduce_scatter(comm, func, dt, arith)
    return primitives.build_reduce_scatter(comm, func, dt, arith)
