"""Collective matmul: comm/compute-overlapped tensor-parallel kernels.

The textbook TP pattern serializes its two engines: the MXU runs the
local matmul, THEN the ICI runs the collective (or vice versa), so each
sits idle for the other's phase — exactly the host-launch/streaming
split the reference's datapath exists to avoid (SURVEY §2: compute fused
with collectives).  ACCL+ (arXiv 2312.11742) fuses the collective engine
into the application dataflow; Near-Optimal Wafer-Scale Reduce (arXiv
2404.15888) folds per-hop compute into the transfer schedule.  These
kernels are that idea for the TPU build: the ring schedule and the MXU
schedule are ONE Pallas program —

* :func:`all_gather_matmul` — ``Y = all_gather(x) @ w`` where ``x`` is
  the per-rank row shard of the LHS and ``w`` the local weight block
  (Megatron column-parallel forward over a sequence-sharded input).
  Each arriving ring shard is multiplied while the next hop's
  ``make_async_remote_copy`` is in flight, starting from the local
  shard (its matmul overlaps hop 0);
* :func:`matmul_reduce_scatter` — ``Y_shard = reduce_scatter(x @ w)``
  (row-parallel combine).  The travelling partial-product accumulator
  rides the ring; each hop's local partial block is computed on the
  MXU while the accumulator is in flight, then folded — the per-hop
  accumulate-in-transfer schedule of the wafer-scale reduce.

Both reuse the double-buffered send/recv VMEM staging discipline of
``parallel/pallas_chunked.py`` (two slots, credit semaphores with
grants == gates, every semaphore drains to zero) and offer
bidirectional-channel variants for P >= 4 mirroring ``_dirs(chan)``
there: the shard's row halves counter-rotate so both directions of
every ICI link carry payload (half the bytes each).

Backward passes are the SAME kernels with roles swapped (the classic
collective-matmul duality), registered as ``jax.custom_vjp``:

* d(all_gather_matmul):  dx = matmul_reduce_scatter(dy, wᵀ),
                         dw = all_gather(x)ᵀ @ dy;
* d(matmul_reduce_scatter): dx = all_gather_matmul(dy, wᵀ),
                            dw = xᵀ @ all_gather(dy).

A block-geometry policy (:func:`agmm_plan` / :func:`mmrs_plan`) sizes
the staged shard against the scoped-VMEM budget.  When the WHOLE staged
shard fits, the fully VMEM-resident kernels above run (``mode:
resident``).  When it does not, the plan no longer falls back to XLA:
it picks a ``k_block`` and the **streaming** kernels run (``mode:
stream``) — the ``pallas_chunked`` segmentation discipline applied to
the matmul operand.  The per-hop shard pipelines HBM→VMEM in k-blocks
through the same double-buffered credit-semaphore staging; only the
k-BLOCK (not the shard) must fit the scoped-VMEM budget.  When even
the minimum k-block misses — the (m, n) f32 ACCUMULATOR floor — the
plans grow an accumulator-blocking arm (the k-block idiom rotated,
gated by ``ACCLConfig.cmatmul_nblock``): the accumulator splits along
a lane-aligned block of its own dim (traveller rows for agmm, output
columns for mm×rs, traveller columns for the fused wgrad) and the body
runs the existing streaming kernel once per block over disjoint output
slices — wire-neutral, since the blocks' payloads sum to the unsplit
payload.  The unfused XLA pair remains only for kernels-unavailable
rungs, thresholds, and degenerate geometries (every fallback is
counted in ``accl_cmatmul_fallback_total`` by reason).

**Fused dgrad/wgrad** (round 9): both ``custom_vjp`` backward rules now
overlap BOTH gradients.  dx was already the dual kernel; dw — formerly
an unfused ``all_gather`` + matmul — runs :func:`gathered_wgrad_body`:
the all-gather of x (agmm) / dy (mmrs) is folded into the dw matmul's
k-sweep, each arriving ring shard contributing its ``xᵀ@dy`` partial
(a dim-0-contracting ``dot_general``, the flash-backward idiom) while
the next hop's remote DMA is in flight.

**bf16 wire staging**: shards and travelling accumulators can ride the
ICI in a narrower wire dtype while every accumulation stays f32
on-chip — the reference's ``hp_compression`` shape ("compress on the
wire, accumulate wide"), via ``ops/compression.pallas_cast`` on the
staged operand and in-kernel wire staging for the travelling mm×rs
accumulator.  Halves ICI bytes; gated by the
``ACCLConfig.cmatmul_wire_dtype`` write-through register with a
per-call ``wire_dtype`` override on every entry point.  agmm's wire
payload is the INPUT shard (rounded once — bit-exact whenever the
inputs are wire-representable); mm×rs rounds the travelling PARTIAL
SUM once per hop (tolerance-bounded; see docs/kernels.md).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import metrics as _metrics
from ..parallel import pallas_ring as _pr
from ..parallel.pallas_ring import _LANES, _sublane

AXIS = _pr.AXIS

#: scoped-VMEM budget for the overlap plan (chip limit ~16 MiB; the
#: margin covers Mosaic's own staging) — the flash policy's number
_VMEM_BUDGET = 12 << 20


def _interpret_params():
    # late-bound so tests patching pallas_ring._interpret_params (e.g. to
    # enable the race detector) cover these kernels too
    return _pr._interpret_params()


# ---------------------------------------------------------------------------
# session-level overlap switch (ACCLConfig.cmatmul_overlap write-through,
# the flash set_flash_bwd_mode shape); per-call override on the wrappers
# ---------------------------------------------------------------------------

_OVERLAP_DEFAULT = True
#: engage-at-or-above payload bytes for the SESSION-DEFAULT resolution
#: (overlap=None): agmm keys on the (m, k) LHS shard, mmrs on the
#: (m/P, n) f32 travelling accumulator — the same conventions as the
#: ``select()`` registers, which land here via the config write-through
#: (``ACCLConfig.ag_matmul_threshold`` / ``rs_matmul_threshold``, incl.
#: autotune's DISABLED sentinel). 0 until a session installs tuned
#: values: overlap-by-default, matching cmatmul_overlap=True. An
#: EXPLICIT ``overlap=True`` bypasses the thresholds (the force-
#: selectable per-call analog, like a requested Algorithm.PALLAS).
_AG_THRESHOLD = 0
_RS_THRESHOLD = 0


def set_overlap_enabled(enabled: bool) -> None:
    """Set the module-default overlap mode (``ACCLConfig.cmatmul_overlap``
    lands here at every config assignment). Per-call override: the
    wrappers' ``overlap`` argument."""
    global _OVERLAP_DEFAULT
    _OVERLAP_DEFAULT = bool(enabled)


def get_overlap_enabled() -> bool:
    return _OVERLAP_DEFAULT


def set_overlap_thresholds(ag_bytes: int, rs_bytes: int) -> None:
    """Install the session's overlap-vs-XLA size registers (config
    write-through; autotuned). Consulted only by the overlap=None
    session-default resolution — see the module attribute docs."""
    global _AG_THRESHOLD, _RS_THRESHOLD
    _AG_THRESHOLD = int(ag_bytes)
    _RS_THRESHOLD = int(rs_bytes)


def get_overlap_thresholds() -> Tuple[int, int]:
    return _AG_THRESHOLD, _RS_THRESHOLD


#: per-aspect-class overrides of the scalar registers above, keyed by
#: :func:`aspect_class` name — the autotune crossover is shape-dependent
#: (a wide (k, n) amortizes the ring differently than a tall one), so
#: ``bench.autotune_collective_matmul`` sweeps 2-3 aspect classes and
#: records each class's crossover here (config write-through:
#: ``ACCLConfig.ag_matmul_class_thresholds`` / ``rs_…``). A class with
#: no entry falls back to the scalar register.
_AG_CLASS_THRESHOLDS: dict = {}
_RS_CLASS_THRESHOLDS: dict = {}


def aspect_class(k: int, n: int) -> str:
    """Aspect-ratio class of the (k, n) weight block: ``wide`` when the
    output dim dominates (n >= 2k), ``tall`` when the contraction dim
    does (k >= 2n), else ``square``. The autotune sweep measures one
    crossover per class."""
    if n >= 2 * k:
        return "wide"
    if k >= 2 * n:
        return "tall"
    return "square"


def set_overlap_class_thresholds(ag: dict, rs: dict) -> None:
    """Install the per-aspect-class crossover registers (config
    write-through; autotuned). Keys are :func:`aspect_class` names."""
    global _AG_CLASS_THRESHOLDS, _RS_CLASS_THRESHOLDS
    _AG_CLASS_THRESHOLDS = dict(ag or {})
    _RS_CLASS_THRESHOLDS = dict(rs or {})


def get_overlap_class_thresholds() -> Tuple[dict, dict]:
    return dict(_AG_CLASS_THRESHOLDS), dict(_RS_CLASS_THRESHOLDS)


def _ag_threshold(k: int, n: int) -> int:
    return int(_AG_CLASS_THRESHOLDS.get(aspect_class(k, n), _AG_THRESHOLD))


def _rs_threshold(k: int, n: int) -> int:
    return int(_RS_CLASS_THRESHOLDS.get(aspect_class(k, n), _RS_THRESHOLD))


#: accumulator-blocking register (``ACCLConfig.cmatmul_nblock``
#: write-through): when the k-blocked streaming sweep still misses the
#: VMEM budget — the irreducible (m, n) f32 accumulator floor — the
#: plans grow a SECOND halving sweep that splits the accumulator itself
#: along a lane-aligned block of its own dim (traveller rows for agmm,
#: output columns for mm×rs, traveller columns for the fused wgrad) and
#: the bodies run the existing kernels once per block over disjoint
#: output slices (wire-neutral: the blocks' payloads sum to the unsplit
#: payload). False pins the pre-blocking behavior: accumulator-floor
#: shapes decline to the unfused pair (counted ``vmem_miss``).
_NBLOCK_DEFAULT = True


def set_nblock_enabled(enabled: bool) -> None:
    """Set the module-default accumulator-blocking mode
    (``ACCLConfig.cmatmul_nblock`` lands here at every config
    assignment). Existing-shape plan resolution is unaffected either
    way — the blocked arms run only after the resident and k-block
    sweeps both miss."""
    global _NBLOCK_DEFAULT
    _NBLOCK_DEFAULT = bool(enabled)


def get_nblock_enabled() -> bool:
    return _NBLOCK_DEFAULT


# ---------------------------------------------------------------------------
# wire staging (compress on the wire, accumulate wide)
# ---------------------------------------------------------------------------

#: session wire-dtype register (``ACCLConfig.cmatmul_wire_dtype``
#: write-through). None = wire rides the operand dtype (no compression).
_WIRE_DTYPE_DEFAULT: Optional[str] = None

_WIRE_NAMES = {
    "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
    "f16": jnp.float16, "float16": jnp.float16,
}

#: stochastic-rounding wire codecs: same wire dtype, but the INPUT-SHARD
#: cast runs ``compression.pallas_compress_stochastic`` — unbiased under
#: the repeated compress/accumulate cycles of multi-step training
#: (ROADMAP round-9 leftover). In-kernel stagings (the mm×rs travelling
#: accumulator, the a2a combine's y blocks) still round
#: deterministically: ``astype`` is the only cast available inside a
#: kernel, and those payloads are rounded once per element anyway.
_SR_WIRE_NAMES = {
    "bf16_sr": jnp.bfloat16, "bfloat16_sr": jnp.bfloat16,
}

#: every accepted wire-dtype name -> jnp dtype (deterministic + SR)
_ALL_WIRE_NAMES = {**_WIRE_NAMES, **_SR_WIRE_NAMES}


def set_wire_dtype(name) -> None:
    """Set the session wire dtype for collective-matmul staging (config
    write-through). ``None`` disables compression; per-call override:
    the ``wire_dtype`` argument on every entry point (``"off"`` forces
    full precision for one call)."""
    global _WIRE_DTYPE_DEFAULT
    if name is not None and not isinstance(name, str):
        name = jnp.dtype(name).name
    if name is not None and name not in _ALL_WIRE_NAMES:
        raise ValueError(f"unsupported cmatmul wire dtype {name!r}; "
                         f"one of {sorted(set(_ALL_WIRE_NAMES))} or None")
    _WIRE_DTYPE_DEFAULT = name


def get_wire_dtype() -> Optional[str]:
    return _WIRE_DTYPE_DEFAULT


def _resolve_wire_codec(wire_dtype, operand_dtype):
    """Resolve a per-call wire request against the session register to
    ``(jnp dtype | None, stochastic: bool)`` — None for a full-precision
    wire. ``None`` follows the session default; ``"off"``/``False``
    force full precision. The ``*_sr`` names select the stochastic-
    rounding compress lane for input-shard casts (in-kernel stagings
    always round deterministically). Never upcasts: a wire dtype at
    least as wide as the operand resolves to None (nothing to
    compress)."""
    w = _WIRE_DTYPE_DEFAULT if wire_dtype is None else wire_dtype
    if w in (None, "off", False):
        return None, False
    sr = False
    if isinstance(w, str):
        if w not in _ALL_WIRE_NAMES:
            # the per-call override is the only unvalidated input path
            # (the session register validates in set_wire_dtype) — a
            # typo must fail with the valid names, not a bare KeyError
            raise ValueError(
                f"unsupported cmatmul wire dtype {w!r}; one of "
                f"{sorted(set(_ALL_WIRE_NAMES))}, 'off', or None")
        wdt = _ALL_WIRE_NAMES[w]
        sr = w in _SR_WIRE_NAMES
    else:
        wdt = w
    if jnp.dtype(wdt).itemsize >= jnp.dtype(operand_dtype).itemsize:
        return None, False
    return wdt, sr


def _resolve_wire(wire_dtype, operand_dtype):
    """Dtype-only view of :func:`_resolve_wire_codec` (the plan/engage
    callers size staged terms and never care how the cast rounds)."""
    return _resolve_wire_codec(wire_dtype, operand_dtype)[0]


def wire_itemsize(dtype, wire_dtype=None) -> int:
    """EFFECTIVE per-element wire bytes for a collective-matmul payload
    under the given wire request (session default at None) — what the
    size thresholds must see (a bf16-staged f32 shard moves half the
    bytes, so it clears a byte register at twice the element count)."""
    wdt = _resolve_wire(wire_dtype, dtype)
    return jnp.dtype(wdt if wdt is not None else dtype).itemsize


def _wire_cast(x, wdt, stochastic: bool = False):
    """Stage an operand into the wire dtype via the hp_compression Pallas
    lane (the cast the packetizer-front lane performs in the reference);
    identity when no compression resolved. ``stochastic`` selects the
    stochastic-rounding lane (the ``bf16_sr`` codec) — unbiased under
    repeated compression, falling back to the deterministic cast on
    rungs without the TPU PRNG (compression handles the gate)."""
    if wdt is None or x.dtype == jnp.dtype(wdt):
        return x
    from . import compression
    if stochastic:
        # per-execution seed folded over the WHOLE payload's bits: a
        # constant (or degenerate — e.g. sampled padding zeros) seed
        # would replay the same PRNG stream every training step, so
        # boundary elements would round the same way each time —
        # re-introducing exactly the accumulated bias SR exists to
        # kill. The wrapping int32 sum sees every bit flip anywhere in
        # the payload (no FP absorption) and costs one fused pass next
        # to the O(n) cast itself.
        bits = lax.bitcast_convert_type(
            x.astype(jnp.float32).reshape(-1), jnp.int32)
        seed = jnp.sum(bits, dtype=jnp.int32)
        return compression.pallas_compress_stochastic(x, wdt, seed=seed)
    return compression.pallas_cast(x, wdt)


# ---------------------------------------------------------------------------
# fallback accounting: every plan/policy fallback is counted by reason
# (the round-8 telemetry sees what the warn-once log hides)
# ---------------------------------------------------------------------------

#: (op, reason) pairs already warned about — log dedup only; the counter
#: increments on EVERY fallback. Session-scoped like the algorithms
#: fallback set: ACCL.initialize() clears it via
#: :func:`reset_fallback_warnings`.
_warned_fallback: set = set()


def reset_fallback_warnings() -> None:
    """Session hook (called by ``ACCL.initialize``): forget which
    (op, reason) fallbacks were already warned about."""
    _warned_fallback.clear()


def _note_fallback(op: str, reason: str) -> None:
    """One collective-matmul fused-path fallback: bump
    ``accl_cmatmul_fallback_total{op, reason}`` (reasons: ``vmem_miss``
    — no plan geometry fits even a k-block; ``no_interpret`` — no
    backend that can execute remote DMA; ``threshold`` — the session
    size register declined) and warn once per (op, reason). Runs at
    trace/build time, so the count is per compiled program, not per
    step."""
    _metrics.inc("accl_cmatmul_fallback_total",
                 labels=(("op", op), ("reason", reason)))
    if (op, reason) not in _warned_fallback:
        _warned_fallback.add((op, reason))
        from ..utils.logging import get_logger
        get_logger("collective_matmul").warning(
            "collective matmul %s: fused kernel fallback (%s); "
            "running the unfused XLA pair", op, reason)


# ---------------------------------------------------------------------------
# ring geometry over a (possibly multi-axis) mesh
# ---------------------------------------------------------------------------

def _flat_ids(axis: str, mesh_axes: Sequence[str], P: int):
    """(my, left, right) as LOGICAL device ids over the FULL mesh.

    The remote-DMA device id is the linear index into the mesh's device
    array, so on a multi-axis mesh (the mlp's (dp, tp)) the ring axis
    index alone is not the device id — the other axes contribute the
    row offset. ``mesh_axes`` is the mesh's axis-name order; rings stay
    within a row because only the ring axis' index differs between
    neighbors."""
    pos = lax.axis_index(axis)
    p32 = jnp.int32(P)
    rpos = lax.rem(pos + jnp.int32(1), p32)
    lpos = lax.rem(pos + p32 - jnp.int32(1), p32)
    my = jnp.int32(0)
    left = jnp.int32(0)
    right = jnp.int32(0)
    for name in mesh_axes:
        size = jnp.int32(lax.axis_size(name))
        idx = lax.axis_index(name)
        my = my * size + idx
        left = left * size + (lpos if name == axis else idx)
        right = right * size + (rpos if name == axis else idx)
    return pos, my, left, right


def _dirs(chan: int, left, right, bidirectional: bool):
    """Per-channel ring orientation, mirroring pallas_chunked._dirs:
    (downstream we send to, upstream we grant credits to, index sign).
    Channel 1 rotates LEFT when bidirectional so both directions of
    every ICI link carry payload simultaneously."""
    if bidirectional and chan == 1:
        return left, right, jnp.int32(1)
    return right, left, jnp.int32(-1)


# ---------------------------------------------------------------------------
# latency-hiding all-gather x matmul
# ---------------------------------------------------------------------------

def _agmm_kernel(x_ref, w_ref, o_ref, buf, send_sem, recv_sem, cap_sem, *,
                 P: int, axis: str, mesh_axes: Tuple[str, ...],
                 bidirectional: bool):
    """x_ref: (mp, kp) own LHS shard; w_ref: (kp, n); o_ref: (P, mp, n);
    all VMEM. ``buf``: (nchan, 2, mh, kp) double-buffered recv slots.

    Transfer ``t`` (t = 0..P-2) forwards the shard received at t-1 (t=0:
    the local shard) downstream while the matmul of the newest arrival
    runs on the MXU — the hop transfer and the hop matmul overlap by
    construction. Credit discipline (grants == gates, drains to zero):
    the slot written by transfer t is granted back upstream only after
    its matmul consumed it AND the forward that read it drained.

    ``bidirectional``: the shard's row halves counter-rotate (channel 0
    top half -> right, channel 1 bottom half -> left); each output
    block's halves arrive via opposite rings, so every link carries
    half the bytes in each direction.
    """
    nchan = 2 if bidirectional else 1
    mh = x_ref.shape[0] // nchan
    pos, _, left, right = _flat_ids(axis, mesh_axes, P)
    _pr._ring_barrier(left, right)
    hops = P - 1

    def rows(chan):
        return pl.ds(chan * mh, mh)

    def _rdma(chan, src_slot, dst_slot, use_x: bool):
        dst, _, _ = _dirs(chan, left, right, bidirectional)
        src = (x_ref.at[rows(chan), :] if use_x
               else buf.at[chan, src_slot])
        return pltpu.make_async_remote_copy(
            src_ref=src,
            dst_ref=buf.at[chan, dst_slot],
            send_sem=send_sem.at[chan, dst_slot],
            recv_sem=recv_sem.at[chan, dst_slot],
            device_id=dst,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )

    # prologue: launch transfer 0 on every channel, then compute the
    # local block while the ring moves — hop 0 is already overlapped
    for chan in range(nchan):
        _rdma(chan, 0, 0, use_x=True).start()
    o_ref[pos] = jnp.dot(x_ref[...], w_ref[...],
                         preferred_element_type=o_ref.dtype)

    def hop(t, _):
        t = jnp.int32(t)
        slot = lax.rem(t, jnp.int32(2))
        nslot = lax.rem(t + 1, jnp.int32(2))

        for chan in range(nchan):
            _, upstream, sign = _dirs(chan, left, right, bidirectional)
            # block whose shard transfer t delivered here
            src_idx = lax.rem(pos + sign * (t + jnp.int32(1))
                              + jnp.int32(2 * P), jnp.int32(P))

            _rdma(chan, slot, slot, use_x=False).wait_recv()

            # forward the arrival before its matmul so transfer t+1 is
            # in flight during the MXU work of hop t
            @pl.when(t + 1 <= hops - 1)
            def _fwd(chan=chan, slot=slot, nslot=nslot):
                # credit gate: downstream must have consumed its slot
                # (t+1)%2 content (transfer t-1) before we overwrite it
                @pl.when(t + 1 >= 2)
                def _gate():
                    pltpu.semaphore_wait(cap_sem.at[chan], 1)
                _rdma(chan, slot, nslot, use_x=False).start()

            o_ref[src_idx, rows(chan)] = jnp.dot(
                buf[chan, slot], w_ref[...],
                preferred_element_type=o_ref.dtype)

            @pl.when(t + 1 <= hops - 1)
            def _drain(chan=chan, slot=slot, nslot=nslot):
                _rdma(chan, slot, nslot, use_x=False).wait_send()

            @pl.when(t == 0)
            def _drain0(chan=chan):
                # the prologue send (x_ref source) also used slot 0's
                # send semaphore; consume it exactly once
                _rdma(chan, 0, 0, use_x=True).wait_send()

            # slot t%2 consumed by matmul AND drained by the forward ->
            # grant it back for upstream's transfer t+2 (grants == gates)
            @pl.when(t + 2 <= hops - 1)
            def _grant(chan=chan, upstream=upstream):
                pltpu.semaphore_signal(
                    cap_sem.at[chan], inc=1, device_id=upstream,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
        return 0

    lax.fori_loop(0, hops, hop, 0)


def _agmm_call(x, w, *, P: int, axis: str, mesh_axes: Tuple[str, ...],
               out_dtype, bidirectional: bool):
    mp, kp = x.shape
    n = w.shape[1]
    nchan = 2 if bidirectional else 1
    return pl.pallas_call(
        functools.partial(_agmm_kernel, P=P, axis=axis,
                          mesh_axes=mesh_axes, bidirectional=bidirectional),
        out_shape=jax.ShapeDtypeStruct((P, mp, n), out_dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((nchan, 2, mp // nchan, kp), x.dtype),  # buf
            pltpu.SemaphoreType.DMA((nchan, 2)),               # send_sem
            pltpu.SemaphoreType.DMA((nchan, 2)),               # recv_sem
            pltpu.SemaphoreType.REGULAR((nchan,)),             # cap_sem
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=8),
        interpret=_interpret_params(),
    )(x, w)


# ---------------------------------------------------------------------------
# matmul x reduce-scatter
# ---------------------------------------------------------------------------

def _mmrs_kernel(x_ref, w_ref, o_ref, acc_buf, recv_buf, send_sem,
                 recv_sem, cap_sem, *rest, P: int, axis: str,
                 mesh_axes: Tuple[str, ...], bidirectional: bool,
                 wire=None):
    """x_ref: (P, cp, kp) own LHS rows grouped by output chunk; w_ref:
    (kp, n); o_ref: (cp, n) f32; all VMEM.

    Ring schedule mirrors ``pallas_chunked._chunked_rs_kernel``: the
    accumulator travels downstream; at hop ``s`` the LOCAL partial for
    chunk ``(pos + sign*(s+1)) % P`` is computed ON THE MXU while the
    accumulator's remote DMA is in flight, then folded into the
    arrival. Rank ``pos`` ends owning folded chunk ``(pos+1) % P``
    (channel 1 mirrored: ``(pos-1) % P``); the wrapper realigns.

    The seed partial (own chunk) is NOT overlapped — it gates hop 0's
    send — but every one of the P-1 hop partials is.

    ``wire`` (a jnp dtype) adds a wire staging buffer (``rest[0]``):
    the remote DMA carries the travelling accumulator compressed to the
    wire dtype; the fold decompresses and accumulates in f32 — the
    ``pallas_chunked`` per-hop wire discipline ("compress on the wire,
    accumulate wide"). ``acc_buf`` stays f32; the rdma source switches
    to the wire buffer, whose reuse ``rdma.wait_send()`` guards.
    """
    wire_buf = rest[0] if wire is not None else None
    nchan = 2 if bidirectional else 1
    cp = o_ref.shape[0]
    ch = cp // nchan
    pos, _, left, right = _flat_ids(axis, mesh_axes, P)
    _pr._ring_barrier(left, right)
    hops = P - 1

    def rows(chan):
        return pl.ds(chan * ch, ch)

    def partial(chan, idx):
        return jnp.dot(x_ref[idx, rows(chan)], w_ref[...],
                       preferred_element_type=o_ref.dtype)

    def _rdma(chan, slot):
        dst, _, _ = _dirs(chan, left, right, bidirectional)
        return pltpu.make_async_remote_copy(
            src_ref=(acc_buf if wire is None else wire_buf).at[chan],
            dst_ref=recv_buf.at[chan, slot],
            send_sem=send_sem.at[chan],
            recv_sem=recv_sem.at[chan, slot],
            device_id=dst,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )

    for chan in range(nchan):
        acc_buf[chan] = partial(chan, pos)   # seed: own chunk's partial
        if wire is not None:
            wire_buf[chan] = acc_buf[chan].astype(wire)

    def hop(s, _):
        s = jnp.int32(s)
        slot = lax.rem(s, jnp.int32(2))

        for chan in range(nchan):
            _, upstream, sign = _dirs(chan, left, right, bidirectional)
            idx = lax.rem(pos + sign * (s + jnp.int32(1))
                          + jnp.int32(2 * P), jnp.int32(P))

            # credit gate: downstream's fold of this slot's s-2 content
            @pl.when(s >= 2)
            def _gate(chan=chan):
                pltpu.semaphore_wait(cap_sem.at[chan], 1)

            rdma = _rdma(chan, slot)
            rdma.start()

            # the hop's local partial runs on the MXU while the
            # accumulator is on the wire — the overlap this kernel is for
            p = partial(chan, idx)

            rdma.wait_recv()
            # decompress at the fold: accumulation stays f32 on-chip
            folded = recv_buf[chan, slot].astype(o_ref.dtype) + p

            # recv slot consumed -> grant upstream a credit for s+2
            @pl.when(s + 2 <= hops - 1)
            def _grant(chan=chan, upstream=upstream):
                pltpu.semaphore_signal(
                    cap_sem.at[chan], inc=1, device_id=upstream,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)

            rdma.wait_send()          # send staging drained
            acc_buf[chan] = folded
            if wire is not None:
                wire_buf[chan] = folded.astype(wire)   # compress lane
        return 0

    lax.fori_loop(0, hops, hop, 0)
    for chan in range(nchan):
        o_ref[rows(chan)] = acc_buf[chan]


def _mmrs_call(x, w, *, P: int, axis: str, mesh_axes: Tuple[str, ...],
               out_dtype, bidirectional: bool, wire=None):
    _, cp, kp = x.shape
    n = w.shape[1]
    nchan = 2 if bidirectional else 1
    scratch = [
        pltpu.VMEM((nchan, cp // nchan, n), out_dtype),     # acc_buf
        pltpu.VMEM((nchan, 2, cp // nchan, n),
                   wire if wire is not None else out_dtype),  # recv_buf
        pltpu.SemaphoreType.DMA((nchan,)),                  # send_sem
        pltpu.SemaphoreType.DMA((nchan, 2)),                # recv_sem
        pltpu.SemaphoreType.REGULAR((nchan,)),              # cap_sem
    ]
    if wire is not None:
        scratch.append(pltpu.VMEM((nchan, cp // nchan, n), wire))
    return pl.pallas_call(
        functools.partial(_mmrs_kernel, P=P, axis=axis,
                          mesh_axes=mesh_axes, bidirectional=bidirectional,
                          wire=wire),
        out_shape=jax.ShapeDtypeStruct((cp, n), out_dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=9),
        interpret=_interpret_params(),
    )(x, w)


# ---------------------------------------------------------------------------
# k-blocked STREAMING all-gather x matmul: payload, weights and output
# stay in HBM; the per-hop shard pipelines through VMEM in k-blocks
# ---------------------------------------------------------------------------

def _agmm_stream_kernel(x_ref, w_ref, o_ref, bounce_ref, send_buf,
                        recv_buf, wbuf, acc, send_sem, recv_sem, cap_sem,
                        ld_sem, wld_sem, st_sem, ost_sem, *, P: int,
                        axis: str, mesh_axes: Tuple[str, ...],
                        bidirectional: bool, nkb: int):
    """x_ref: (nkb, mp, kb) own LHS shard, SEGMENT-major (the wrapper
    splits the k dim so every DMA below is a leading-index copy);
    w_ref: (nkb, kb, n); o_ref: (P, mp, n) f32 — all HBM (``pl.ANY``).
    ``bounce_ref``: (nchan, nkb, mh, kb) HBM relay scratch (an extra
    output the wrapper discards, the ``_chunked_alltoall_kernel``
    bounce idiom).

    The ``pallas_chunked`` segmentation discipline applied to the
    matmul operand: global step ``u = t*nkb + j`` moves SEGMENT j of
    transfer t (t = 0: the own shard, loaded from x_ref; t > 0: the
    relay of the previous hop's arrival, reloaded from the bounce —
    ``_chunked_gather_kernel``'s store-and-forward). Each arriving
    (mh, kb) segment is multiplied against the staged (kb, n) w block
    and accumulated into the hop's resident f32 (mh, n) accumulator;
    on the hop's last segment the block flushes to HBM. Our own send
    is always in flight during the step's MXU work, so the per-hop
    comm/compute overlap of the resident kernel survives segmentation.

    Output phases (local block = phase 0, hop t = phase t+1) alternate
    the two accumulator slots; a phase's flush is consumed exactly once
    — by phase+2's first accumulate, or the epilogue. Credit discipline
    verbatim from the resident kernels: recv slots key on step parity,
    a writer gates on the consumer having matmul'd AND flushed the
    slot's previous content, grants == gates, every semaphore drains
    to zero.
    """
    nchan = 2 if bidirectional else 1
    mh = acc.shape[2]
    pos, _, left, right = _flat_ids(axis, mesh_axes, P)
    _pr._ring_barrier(left, right)
    hops = P - 1
    U = hops * nkb          # static: total segment transfers per channel

    def rows(chan):
        return pl.ds(chan * mh, mh)

    def _rdma(chan, slot):
        dst, _, _ = _dirs(chan, left, right, bidirectional)
        return pltpu.make_async_remote_copy(
            src_ref=send_buf.at[chan, slot],
            dst_ref=recv_buf.at[chan, slot],
            send_sem=send_sem.at[chan, slot],
            recv_sem=recv_sem.at[chan, slot],
            device_id=dst,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )

    def wait_ost(chan, aslot):
        """Consume one accumulator-flush completion (descriptor
        recreated for its size — the chunked wait_store pattern)."""
        pltpu.make_async_copy(
            acc.at[chan, aslot], o_ref.at[0, rows(chan)],
            ost_sem.at[chan, aslot]).wait()

    def step(u, _):
        u = jnp.int32(u)
        t = u // jnp.int32(nkb)
        j = lax.rem(u, jnp.int32(nkb))
        slot = lax.rem(u, jnp.int32(2))
        aslot = lax.rem(t + jnp.int32(1), jnp.int32(2))
        local_phase = t == 0

        # the step's w k-block fetch overlaps the sends + the wire wait
        wld = pltpu.make_async_copy(w_ref.at[j], wbuf, wld_sem)
        wld.start()

        # ---- send side: transfer (t, j) -------------------------------
        for chan in range(nchan):
            # deferred drain: this send slot's u-2 transfer completes
            # before the reload overwrites it (chunked_scatter root)
            @pl.when(u >= 2)
            def _drain(chan=chan, slot=slot):
                _rdma(chan, slot).wait_send()

            # stage the outgoing segment: own shard at t = 0, the relay
            # of the previous hop's arrival (bounce) after
            @pl.when(local_phase)
            def _own(chan=chan, slot=slot, j=j):
                d = pltpu.make_async_copy(
                    x_ref.at[j, rows(chan)], send_buf.at[chan, slot],
                    ld_sem.at[chan])
                d.start()
                d.wait()

            @pl.when(jnp.logical_not(local_phase))
            def _relay(chan=chan, slot=slot, j=j):
                d = pltpu.make_async_copy(
                    bounce_ref.at[chan, j], send_buf.at[chan, slot],
                    ld_sem.at[chan])
                d.start()
                d.wait()

            # credit gate: downstream consumed its slot's u-2 content
            @pl.when(u >= 2)
            def _gate(chan=chan):
                pltpu.semaphore_wait(cap_sem.at[chan], 1)

            _rdma(chan, slot).start()

        wld.wait()

        # ---- compute + recv side --------------------------------------
        for chan in range(nchan):
            _, upstream, sign = _dirs(chan, left, right, bidirectional)
            src_idx = lax.rem(pos + sign * (t + jnp.int32(1))
                              + jnp.int32(2 * P), jnp.int32(P))

            # local block (phase 0): the staged own segment, same w
            # block — its matmul hides transfer 0, as in the resident
            # kernel's prologue
            @pl.when(local_phase)
            def _local(chan=chan, slot=slot, j=j):
                p = jnp.dot(send_buf[chan, slot], wbuf[...],
                            preferred_element_type=jnp.float32)
                acc[chan, 0] = jnp.where(j == 0, p, acc[chan, 0] + p)

                @pl.when(j == jnp.int32(nkb - 1))
                def _store0(chan=chan):
                    pltpu.make_async_copy(
                        acc.at[chan, 0], o_ref.at[pos, rows(chan)],
                        ost_sem.at[chan, 0]).start()

            _rdma(chan, slot).wait_recv()

            # phase t+1 reuses the slot phase t-1 flushed from: consume
            # that store exactly once before the first accumulate
            @pl.when(jnp.logical_and(j == 0, t >= 1))
            def _accgate(chan=chan, aslot=aslot):
                wait_ost(chan, aslot)

            p = jnp.dot(recv_buf[chan, slot], wbuf[...],
                        preferred_element_type=jnp.float32)
            acc[chan, aslot] = jnp.where(j == 0, p, acc[chan, aslot] + p)

            # flush the arrival for the relay at (t+1, j); the wait
            # lands the store before the reload reads it (the
            # chunked_gather store-and-forward discipline)
            @pl.when(t < hops - 1)
            def _flush(chan=chan, slot=slot, j=j):
                st = pltpu.make_async_copy(
                    recv_buf.at[chan, slot], bounce_ref.at[chan, j],
                    st_sem.at[chan])
                st.start()
                st.wait()

            # recv slot consumed (matmul + flush) -> grant upstream a
            # credit for its step u+2 (grants == gates)
            @pl.when(u + 2 <= U - 1)
            def _grant(chan=chan, upstream=upstream):
                pltpu.semaphore_signal(
                    cap_sem.at[chan], inc=1, device_id=upstream,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)

            @pl.when(j == jnp.int32(nkb - 1))
            def _store(chan=chan, aslot=aslot, src_idx=src_idx):
                pltpu.make_async_copy(
                    acc.at[chan, aslot], o_ref.at[src_idx, rows(chan)],
                    ost_sem.at[chan, aslot]).start()
        return 0

    lax.fori_loop(0, U, step, 0)

    # epilogue: the last two sends and the last two accumulator flushes
    # (phases P-2 and P-1) are still undrained — consume each exactly once
    for chan in range(nchan):
        _rdma(chan, (U - 1) % 2).wait_send()
        if U >= 2:
            _rdma(chan, (U - 2) % 2).wait_send()
        wait_ost(chan, (P - 1) % 2)
        wait_ost(chan, (P - 2) % 2)


def _agmm_stream_call(xseg, wseg, *, P: int, axis: str,
                      mesh_axes: Tuple[str, ...], bidirectional: bool,
                      nkb: int, mp: int, np_: int):
    """xseg: (nkb, mp, kb) segment-major shard; wseg: (nkb, kb, np_).
    Returns the (P, mp, np_) f32 output (the HBM bounce is discarded)."""
    kb = xseg.shape[2]
    nchan = 2 if bidirectional else 1
    mh = mp // nchan
    out = pl.pallas_call(
        functools.partial(_agmm_stream_kernel, P=P, axis=axis,
                          mesh_axes=mesh_axes, bidirectional=bidirectional,
                          nkb=nkb),
        out_shape=(jax.ShapeDtypeStruct((P, mp, np_), jnp.float32),
                   jax.ShapeDtypeStruct((nchan, nkb, mh, kb), xseg.dtype)),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)),
        scratch_shapes=[
            pltpu.VMEM((nchan, 2, mh, kb), xseg.dtype),    # send_buf
            pltpu.VMEM((nchan, 2, mh, kb), xseg.dtype),    # recv_buf
            pltpu.VMEM((kb, np_), wseg.dtype),             # wbuf
            pltpu.VMEM((nchan, 2, mh, np_), jnp.float32),  # acc
            pltpu.SemaphoreType.DMA((nchan, 2)),           # send_sem
            pltpu.SemaphoreType.DMA((nchan, 2)),           # recv_sem
            pltpu.SemaphoreType.REGULAR((nchan,)),         # cap_sem
            pltpu.SemaphoreType.DMA((nchan,)),             # ld_sem
            pltpu.SemaphoreType.DMA,                       # wld_sem
            pltpu.SemaphoreType.DMA((nchan,)),             # st_sem
            pltpu.SemaphoreType.DMA((nchan, 2)),           # ost_sem
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=10),
        interpret=_interpret_params(),
    )(xseg, wseg)
    return out[0]


# ---------------------------------------------------------------------------
# k-blocked STREAMING matmul x reduce-scatter: the per-hop partial's
# k-sweep streams from HBM while the accumulator is on the wire
# ---------------------------------------------------------------------------

def _mmrs_stream_kernel(x_ref, w_ref, o_ref, acc_buf, recv_buf, pacc,
                        xblk, wblk, send_sem, recv_sem, cap_sem,
                        xld_sem, wld_sem, *rest, P: int, axis: str,
                        mesh_axes: Tuple[str, ...], bidirectional: bool,
                        nkb: int, wire=None):
    """x_ref: (P, nkb, cp, kb) segment-major chunk grid in HBM; w_ref:
    (nkb, kb, n) in HBM; o_ref: (cp, n) f32 VMEM.

    Ring schedule is ``_mmrs_kernel``'s verbatim (same slots, credits
    and realignment contract); only the per-hop partial changes: it
    streams (ch, kb) x-blocks and (kb, n) w-blocks from HBM and
    accumulates in the f32 ``pacc`` scratch while the travelling
    accumulator's remote DMA is in flight — so the k-sweep's HBM
    traffic AND MXU work both hide under the wire time. ``wire`` adds
    the compressed staging buffer (``rest[0]``) exactly as in the
    resident kernel.
    """
    wire_buf = rest[0] if wire is not None else None
    nchan = 2 if bidirectional else 1
    cp = o_ref.shape[0]
    ch = cp // nchan
    pos, _, left, right = _flat_ids(axis, mesh_axes, P)
    _pr._ring_barrier(left, right)
    hops = P - 1

    def rows(chan):
        return pl.ds(chan * ch, ch)

    def _rdma(chan, slot):
        dst, _, _ = _dirs(chan, left, right, bidirectional)
        return pltpu.make_async_remote_copy(
            src_ref=(acc_buf if wire is None else wire_buf).at[chan],
            dst_ref=recv_buf.at[chan, slot],
            send_sem=send_sem.at[chan],
            recv_sem=recv_sem.at[chan, slot],
            device_id=dst,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )

    def ksweep(idx_of, into):
        """Streamed partial: ``into[chan] = Σ_j x[idx, j] @ w[j]``. The
        block loads are waited immediately (single-slot staging); the
        whole sweep runs while the hop's remote DMA is in flight."""
        def kstep(j, _):
            j = jnp.int32(j)
            wld = pltpu.make_async_copy(w_ref.at[j], wblk, wld_sem)
            wld.start()
            wld.wait()
            for chan in range(nchan):
                xld = pltpu.make_async_copy(
                    x_ref.at[idx_of(chan), j, rows(chan)], xblk,
                    xld_sem)
                xld.start()
                xld.wait()
                p = jnp.dot(xblk[...], wblk[...],
                            preferred_element_type=o_ref.dtype)
                into[chan] = jnp.where(j == 0, p, into[chan] + p)
            return 0

        lax.fori_loop(0, nkb, kstep, 0)

    # seed: own chunk's partial (gates hop 0's send, as in the resident)
    ksweep(lambda chan: pos, acc_buf)
    if wire is not None:
        for chan in range(nchan):
            wire_buf[chan] = acc_buf[chan].astype(wire)

    def hop(s, _):
        s = jnp.int32(s)
        slot = lax.rem(s, jnp.int32(2))

        for chan in range(nchan):
            # credit gate: downstream's fold of this slot's s-2 content
            @pl.when(s >= 2)
            def _gate(chan=chan):
                pltpu.semaphore_wait(cap_sem.at[chan], 1)

            _rdma(chan, slot).start()

        def idx_of(chan):
            _, _, sign = _dirs(chan, left, right, bidirectional)
            return lax.rem(pos + sign * (s + jnp.int32(1))
                           + jnp.int32(2 * P), jnp.int32(P))

        # the hop's partial streams + computes while the wire flies
        ksweep(idx_of, pacc)

        for chan in range(nchan):
            _, upstream, _ = _dirs(chan, left, right, bidirectional)
            _rdma(chan, slot).wait_recv()
            # decompress at the fold: accumulation stays f32 on-chip
            folded = recv_buf[chan, slot].astype(o_ref.dtype) + pacc[chan]

            @pl.when(s + 2 <= hops - 1)
            def _grant(chan=chan, upstream=upstream):
                pltpu.semaphore_signal(
                    cap_sem.at[chan], inc=1, device_id=upstream,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)

            _rdma(chan, slot).wait_send()
            acc_buf[chan] = folded
            if wire is not None:
                wire_buf[chan] = folded.astype(wire)
        return 0

    lax.fori_loop(0, hops, hop, 0)
    for chan in range(nchan):
        o_ref[rows(chan)] = acc_buf[chan]


def _mmrs_stream_call(xseg, wseg, *, P: int, axis: str,
                      mesh_axes: Tuple[str, ...], out_dtype,
                      bidirectional: bool, nkb: int, cp: int, np_: int,
                      wire=None):
    """xseg: (P, nkb, cp, kb) segment-major chunk grid; wseg:
    (nkb, kb, np_). Returns the (cp, np_) f32 folded chunk (pre-
    realignment, as the resident call)."""
    kb = xseg.shape[3]
    nchan = 2 if bidirectional else 1
    ch = cp // nchan
    scratch = [
        pltpu.VMEM((nchan, ch, np_), out_dtype),            # acc_buf
        pltpu.VMEM((nchan, 2, ch, np_),
                   wire if wire is not None else out_dtype),  # recv_buf
        pltpu.VMEM((nchan, ch, np_), out_dtype),            # pacc
        pltpu.VMEM((ch, kb), xseg.dtype),                   # xblk
        pltpu.VMEM((kb, np_), wseg.dtype),                  # wblk
        pltpu.SemaphoreType.DMA((nchan,)),                  # send_sem
        pltpu.SemaphoreType.DMA((nchan, 2)),                # recv_sem
        pltpu.SemaphoreType.REGULAR((nchan,)),              # cap_sem
        pltpu.SemaphoreType.DMA,                            # xld_sem
        pltpu.SemaphoreType.DMA,                            # wld_sem
    ]
    if wire is not None:
        scratch.append(pltpu.VMEM((nchan, ch, np_), wire))  # wire_buf
    return pl.pallas_call(
        functools.partial(_mmrs_stream_kernel, P=P, axis=axis,
                          mesh_axes=mesh_axes, bidirectional=bidirectional,
                          nkb=nkb, wire=wire),
        out_shape=jax.ShapeDtypeStruct((cp, np_), out_dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=11),
        interpret=_interpret_params(),
    )(xseg, wseg)


# ---------------------------------------------------------------------------
# fused gathered wgrad: the all-gather folded into dw's k-sweep
# ---------------------------------------------------------------------------

def _wgrad_kernel(trav_ref, loc_ref, o_ref, buf, lbuf, send_sem, recv_sem,
                  cap_sem, lld_sem, *, P: int, axis: str,
                  mesh_axes: Tuple[str, ...], bidirectional: bool,
                  travel_lhs: bool):
    """trav_ref: (msp, ctp) own shard of the GATHERED operand (VMEM);
    loc_ref: (P, msp, clp) the resident operand's blocks by source rank
    (HBM); o_ref: (ctp, clp) f32 (``travel_lhs``) / (clp, ctp) — the dw
    accumulator panel.

    ``dw = Σ_p shard_pᵀ @ loc_p`` (or the mirror): the gathered
    operand's ring IS dw's k-sweep — each arriving shard contributes
    its dim-0-contracting ``dot_general`` partial (the flash-backward
    idiom) while the next hop's transfer is in flight. Ring schedule,
    slots and credit discipline are ``_agmm_kernel``'s verbatim
    (forward-before-compute, grants == gates); the local shard's
    contribution overlaps transfer 0. Both row-half channels fold into
    the SAME panel (the contraction dim is the row dim, so halves sum).
    """
    nchan = 2 if bidirectional else 1
    msh = trav_ref.shape[0] // nchan
    pos, _, left, right = _flat_ids(axis, mesh_axes, P)
    _pr._ring_barrier(left, right)
    hops = P - 1

    def rows(chan):
        return pl.ds(chan * msh, msh)

    def _rdma(chan, src_slot, dst_slot, use_own: bool):
        dst, _, _ = _dirs(chan, left, right, bidirectional)
        src = (trav_ref.at[rows(chan), :] if use_own
               else buf.at[chan, src_slot])
        return pltpu.make_async_remote_copy(
            src_ref=src,
            dst_ref=buf.at[chan, dst_slot],
            send_sem=send_sem.at[chan, dst_slot],
            recv_sem=recv_sem.at[chan, dst_slot],
            device_id=dst,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )

    def ldloc(chan, idx):
        d = pltpu.make_async_copy(loc_ref.at[idx, rows(chan)],
                                  lbuf.at[chan], lld_sem.at[chan])
        d.start()
        d.wait()

    def contrib(chan, seg):
        loc = lbuf[chan]
        if seg.dtype != loc.dtype:
            # a narrow wire shard meets a wider local block:
            # lax.dot_general requires matching operand dtypes (unlike
            # jnp.dot), so up-convert to the common type. Matching
            # operands (e.g. bf16 x bf16 training) keep their dtype —
            # preferred_element_type=f32 already accumulates wide, and
            # an unconditional f32 upcast would forfeit the bf16 MXU
            # rate the fused path exists to win
            wide = jnp.promote_types(seg.dtype, loc.dtype)
            seg = seg.astype(wide)
            loc = loc.astype(wide)
        a, b = (seg, loc) if travel_lhs else (loc, seg)
        return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

    # prologue: launch transfer 0, then fold the LOCAL shard's
    # contribution while the ring moves — hop 0 is already overlapped
    for chan in range(nchan):
        _rdma(chan, 0, 0, use_own=True).start()
    for chan in range(nchan):
        ldloc(chan, pos)
        c = contrib(chan, trav_ref[rows(chan), :])
        if chan == 0:
            o_ref[...] = c
        else:
            o_ref[...] = o_ref[...] + c

    def hop(t, _):
        t = jnp.int32(t)
        slot = lax.rem(t, jnp.int32(2))
        nslot = lax.rem(t + 1, jnp.int32(2))

        for chan in range(nchan):
            _, upstream, sign = _dirs(chan, left, right, bidirectional)
            src_idx = lax.rem(pos + sign * (t + jnp.int32(1))
                              + jnp.int32(2 * P), jnp.int32(P))

            _rdma(chan, slot, slot, use_own=False).wait_recv()

            # forward the arrival before its matmul so transfer t+1 is
            # in flight during the MXU work of hop t
            @pl.when(t + 1 <= hops - 1)
            def _fwd(chan=chan, slot=slot, nslot=nslot):
                @pl.when(t + 1 >= 2)
                def _gate():
                    pltpu.semaphore_wait(cap_sem.at[chan], 1)
                _rdma(chan, slot, nslot, use_own=False).start()

            ldloc(chan, src_idx)
            o_ref[...] = o_ref[...] + contrib(chan, buf[chan, slot])

            @pl.when(t + 1 <= hops - 1)
            def _drain(chan=chan, slot=slot, nslot=nslot):
                _rdma(chan, slot, nslot, use_own=False).wait_send()

            @pl.when(t == 0)
            def _drain0(chan=chan):
                _rdma(chan, 0, 0, use_own=True).wait_send()

            @pl.when(t + 2 <= hops - 1)
            def _grant(chan=chan, upstream=upstream):
                pltpu.semaphore_signal(
                    cap_sem.at[chan], inc=1, device_id=upstream,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
        return 0

    lax.fori_loop(0, hops, hop, 0)


def _wgrad_call(trav, loc, *, P: int, axis: str,
                mesh_axes: Tuple[str, ...], bidirectional: bool,
                travel_lhs: bool):
    msp, ctp = trav.shape
    clp = loc.shape[2]
    nchan = 2 if bidirectional else 1
    oshape = (ctp, clp) if travel_lhs else (clp, ctp)
    return pl.pallas_call(
        functools.partial(_wgrad_kernel, P=P, axis=axis,
                          mesh_axes=mesh_axes, bidirectional=bidirectional,
                          travel_lhs=travel_lhs),
        out_shape=jax.ShapeDtypeStruct(oshape, jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((nchan, 2, msp // nchan, ctp), trav.dtype),  # buf
            pltpu.VMEM((nchan, msp // nchan, clp), loc.dtype),      # lbuf
            pltpu.SemaphoreType.DMA((nchan, 2)),                # send_sem
            pltpu.SemaphoreType.DMA((nchan, 2)),                # recv_sem
            pltpu.SemaphoreType.REGULAR((nchan,)),              # cap_sem
            pltpu.SemaphoreType.DMA((nchan,)),                  # lld_sem
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=12),
        interpret=_interpret_params(),
    )(trav, loc)


# ---------------------------------------------------------------------------
# block-geometry policy: a resident plan when the whole staged shard
# fits, a streaming plan when a k-BLOCK does, None only when even the
# minimum k-block misses (caller falls back to the unfused XLA pair)
# ---------------------------------------------------------------------------

def _pad_to(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def _shrink_block(bp: int, mult: int, fits) -> Optional[int]:
    """Largest ``mult``-aligned block (halving sweep from ``bp``)
    accepted by ``fits``; None when even the minimum (one ``mult``)
    block misses. The k-block sweep with the alignment generalized —
    the accumulator-blocking arms sweep dims whose quantum is the
    sublane-group (traveller rows) rather than always the lane."""
    b = bp
    while b > mult and not fits(b):
        b = max(mult, _pad_to(b // 2, mult))
    return b if fits(b) else None


def _shrink_kb(kp: int, fits) -> Optional[int]:
    """Largest lane-aligned k-block (halving sweep from the full padded
    k) accepted by ``fits``; None when even the 128-lane minimum
    misses."""
    return _shrink_block(kp, _LANES, fits)


def agmm_plan(m: int, k: int, n: int, P: int, dtype,
              bidirectional: bool, w_dtype=None,
              wire_dtype=None) -> Optional[dict]:
    """Geometry for the overlapped all-gather-matmul.

    ``mode: resident`` — everything VMEM-resident (the shard, the
    weight block, the (P, m, n) f32 output panel and the
    double-buffered recv slots fit together); ``mode: stream`` — the
    shard pipelines through VMEM in lane-aligned ``kb`` k-blocks
    (payload, weights and output stay in HBM; only 2 send + 2 recv
    (mh, kb) slots, one (kb, n) weight block and 2 (mh, n) f32
    accumulators per channel are resident). When even the 128-lane
    k-block misses — the m×n accumulator floor — the accumulator-
    blocking arm (``cmatmul_nblock``) splits the traveller's rows into
    ``mb``-blocks (keys ``mb``/``nmb``; the body runs the streaming
    kernel once per block). None only when the lane-floor weight block
    alone exceeds the budget — the caller falls back to the unfused
    XLA pair.

    ``wire_dtype`` sizes the staged/transferred x terms (wire staging
    halves them under bf16); ``w_dtype`` sizes the weight terms when it
    differs from the operand dtype."""
    if m < 1 or k < 1 or n < 1 or P < 1:
        return None
    xdt = jnp.dtype(wire_dtype) if wire_dtype is not None \
        else jnp.dtype(dtype)
    isz = xdt.itemsize
    wisz = jnp.dtype(w_dtype).itemsize if w_dtype is not None \
        else jnp.dtype(dtype).itemsize
    sub = _sublane(xdt)
    nchan = 2 if (bidirectional and P >= 4) else 1
    mp = _pad_to(max(m, 1), sub * nchan)
    kp = _pad_to(max(k, 1), _LANES)   # lane dim of x, sublane dim of w
    np_ = _pad_to(max(n, 1), _LANES)
    est = (mp * kp * isz            # x shard
           + kp * np_ * wisz        # w block
           + P * mp * np_ * 4       # f32 output blocks
           + 2 * mp * kp * isz)     # recv slots (nchan halves sum to mp)
    if est <= _VMEM_BUDGET:
        return {"mode": "resident", "mp": mp, "kp": kp, "np": np_,
                "nchan": nchan, "bidirectional": nchan == 2,
                "kb": kp, "nkb": 1, "vmem_bytes": est}

    def est_stream(kb):
        return (4 * mp * kb * isz      # 2 send + 2 recv slots
                + 2 * mp * np_ * 4     # double-buffered f32 accumulators
                + kb * np_ * wisz)     # staged w k-block

    kb = _shrink_kb(kp, lambda b: est_stream(b) <= _VMEM_BUDGET)
    if kb is not None:
        nkb = -(-kp // kb)
        return {"mode": "stream", "mp": mp, "kp": nkb * kb, "np": np_,
                "nchan": nchan, "bidirectional": nchan == 2,
                "kb": kb, "nkb": nkb, "vmem_bytes": est_stream(kb)}
    if not _NBLOCK_DEFAULT:
        return None

    # accumulator-floor arm (the k-block idiom rotated onto the f32
    # accumulator): even the 128-lane k-block missed because the
    # double-buffered (mp, np) accumulators dominate, so split the
    # TRAVELLER'S ROWS into sublane-aligned mb-blocks — each block runs
    # the streaming kernel over its own disjoint output rows, and the
    # blocks' wire payloads sum to the unsplit shard (wire-neutral).
    def est_block(mb, kb):
        return (4 * mb * kb * isz      # 2 send + 2 recv slots
                + 2 * mb * np_ * 4     # double-buffered f32 accumulators
                + kb * np_ * wisz)     # staged w k-block

    mb = _shrink_block(mp, sub * nchan,
                       lambda b: est_block(b, _LANES) <= _VMEM_BUDGET)
    if mb is None:
        # a (kb_min, n) w-block alone over budget: the lane floor on the
        # weight staging is irreducible by row blocking — honest decline
        return None
    kb = _shrink_kb(kp, lambda b: est_block(mb, b) <= _VMEM_BUDGET)
    nmb = -(-mp // mb)
    nkb = -(-kp // kb)
    return {"mode": "stream", "mp": nmb * mb, "kp": nkb * kb, "np": np_,
            "nchan": nchan, "bidirectional": nchan == 2,
            "kb": kb, "nkb": nkb, "mb": mb, "nmb": nmb,
            "vmem_bytes": est_block(mb, kb)}


def mmrs_plan(m: int, k: int, n: int, P: int, dtype,
              bidirectional: bool, w_dtype=None,
              wire_dtype=None) -> Optional[dict]:
    """Geometry for the overlapped matmul-reduce-scatter. ``m`` is the
    FULL local row count (must divide by P; the wrapper checks).

    ``mode: resident`` — the full chunk grid, weight block and
    travelling accumulator are VMEM-resident; ``mode: stream`` — the
    per-hop partial's k-sweep streams (cp, kb) x-blocks and (kb, n)
    w-blocks from HBM while the travelling accumulator is on the wire
    (the accumulator, recv slots, partial buffer and output chunk stay
    VMEM-resident — they are the wire payload). When even the 128-lane
    k-block misses — the accumulator floor — the accumulator-blocking
    arm (``cmatmul_nblock``) splits the travelling accumulator's
    lane-aligned columns into ``nb``-blocks (keys ``nb``/``nnb``).
    ``wire_dtype`` sizes the travelling-accumulator wire terms
    (staged/transferred as the wire dtype, folded in f32)."""
    if m < 1 or k < 1 or n < 1 or P < 1 or m % P:
        return None
    isz = jnp.dtype(dtype).itemsize
    acc_wisz = jnp.dtype(wire_dtype).itemsize if wire_dtype is not None \
        else 4
    wisz = jnp.dtype(w_dtype).itemsize if w_dtype is not None else isz
    sub = _sublane(dtype)
    nchan = 2 if (bidirectional and P >= 4) else 1
    cp = _pad_to(max(m // P, 1), sub * nchan)
    kp = _pad_to(max(k, 1), _LANES)   # lane dim of the chunk grid
    np_ = _pad_to(max(n, 1), _LANES)
    wire_extra = cp * np_ * acc_wisz if wire_dtype is not None else 0
    est = (P * cp * kp * isz        # x grouped by chunk
           + kp * np_ * wisz        # w block
           + cp * np_ * 4           # f32 output chunk
           + cp * np_ * 4           # acc
           + 2 * cp * np_ * acc_wisz  # recv slots (wire dtype)
           + wire_extra)            # wire staging buffer
    if est <= _VMEM_BUDGET:
        return {"mode": "resident", "cp": cp, "kp": kp, "np": np_,
                "nchan": nchan, "bidirectional": nchan == 2,
                "kb": kp, "nkb": 1, "vmem_bytes": est}

    def est_stream(kb):
        return (cp * np_ * 4                # f32 output chunk
                + cp * np_ * 4              # acc
                + cp * np_ * 4              # per-hop partial (pacc)
                + 2 * cp * np_ * acc_wisz   # recv slots
                + wire_extra                # wire staging buffer
                + (cp // nchan) * kb * isz  # streamed x block
                + kb * np_ * wisz)          # streamed w block

    kb = _shrink_kb(kp, lambda b: est_stream(b) <= _VMEM_BUDGET)
    if kb is not None:
        nkb = -(-kp // kb)
        return {"mode": "stream", "cp": cp, "kp": nkb * kb, "np": np_,
                "nchan": nchan, "bidirectional": nchan == 2,
                "kb": kb, "nkb": nkb, "vmem_bytes": est_stream(kb)}
    if not _NBLOCK_DEFAULT:
        return None

    # accumulator-floor arm: here the travelling accumulator IS the
    # (cp, np) payload, so split its lane-aligned COLUMNS — each
    # nb-block's accumulator rides its own ring over the same streamed
    # x grid and a w column slice, folding into disjoint output
    # columns; the blocks' wire payloads sum to the unsplit
    # accumulator (wire-neutral).
    def est_block(nb, kb):
        wx = cp * nb * acc_wisz if wire_dtype is not None else 0
        return (3 * cp * nb * 4            # out chunk + acc + pacc
                + 2 * cp * nb * acc_wisz   # recv slots
                + wx                       # wire staging buffer
                + (cp // nchan) * kb * isz  # streamed x block
                + kb * nb * wisz)          # streamed w block

    nb = _shrink_block(np_, _LANES,
                       lambda b: est_block(b, _LANES) <= _VMEM_BUDGET)
    if nb is None:
        # the (cp, nb_min) lane-floor column still misses: cp is pinned
        # by the scatter geometry (m/P), not shrinkable here
        return None
    kb = _shrink_kb(kp, lambda b: est_block(nb, b) <= _VMEM_BUDGET)
    nkb = -(-kp // kb)
    nnb = -(-np_ // nb)
    return {"mode": "stream", "cp": cp, "kp": nkb * kb, "np": nnb * nb,
            "nchan": nchan, "bidirectional": nchan == 2,
            "kb": kb, "nkb": nkb, "nb": nb, "nnb": nnb,
            "vmem_bytes": est_block(nb, kb)}


def wgrad_plan(ms: int, ct: int, cl: int, P: int, trav_dtype, loc_dtype,
               bidirectional: bool) -> Optional[dict]:
    """Geometry for the fused gathered-wgrad kernel (``dw = Σ_p
    contribution(shard_p, loc_block_p)``): the travelling shard
    (ms, ct), its double-buffered recv slots, one per-channel local
    block (ms/nchan, cl) and the f32 (ct, cl) accumulator output must
    be VMEM-resident together. When that misses, the streaming arm
    (``cmatmul_nblock``) splits the traveller's lane-aligned columns
    into ``ctb``-blocks (keys ``ctb``/``nctb``), each riding its own
    ring pass into a disjoint dw block. None -> the VJP keeps the
    unfused gathered dw (same math, no overlap)."""
    if ms < 1 or ct < 1 or cl < 1 or P < 1:
        return None
    tisz = jnp.dtype(trav_dtype).itemsize
    lisz = jnp.dtype(loc_dtype).itemsize
    # rows are the CONTRACTION dim here; pad by the stricter sublane of
    # the two operands so both slice cleanly into row halves
    sub = max(_sublane(trav_dtype), _sublane(loc_dtype))
    nchan = 2 if (bidirectional and P >= 4) else 1
    msp = _pad_to(max(ms, 1), sub * nchan)
    ctp = _pad_to(max(ct, 1), _LANES)
    clp = _pad_to(max(cl, 1), _LANES)
    est = (msp * ctp * tisz          # own travelling shard
           + 2 * msp * ctp * tisz    # recv slots (nchan halves sum)
           + msp * clp * lisz        # per-channel local blocks
           + ctp * clp * 4)          # f32 dw accumulator
    if est <= _VMEM_BUDGET:
        return {"msp": msp, "ctp": ctp, "clp": clp, "nchan": nchan,
                "bidirectional": nchan == 2, "vmem_bytes": est}
    if not _NBLOCK_DEFAULT:
        return None

    # streaming arm (the k-block idiom rotated onto the dw panel): the
    # whole travelling shard over budget, so split the traveller's
    # lane-aligned COLUMNS — each ctb-block rides its own ring pass and
    # folds into a disjoint (ctb, cl) dw row block (column block when
    # the traveller is the RHS); the per-block wires sum to the
    # unsplit gather (wire-neutral). The local blocks and the lane
    # floor on ctb are the irreducible terms — shapes where they alone
    # exceed the budget stay honest declines.
    def est_block(ctb):
        return (3 * msp * ctb * tisz   # trav block + recv slots
                + msp * clp * lisz     # per-channel local blocks
                + ctb * clp * 4)       # f32 dw block accumulator

    ctb = _shrink_block(ctp, _LANES,
                        lambda b: est_block(b) <= _VMEM_BUDGET)
    if ctb is None:
        return None
    nctb = -(-ctp // ctb)
    return {"msp": msp, "ctp": nctb * ctb, "clp": clp, "nchan": nchan,
            "bidirectional": nchan == 2, "ctb": ctb, "nctb": nctb,
            "vmem_bytes": est_block(ctb)}


# ---------------------------------------------------------------------------
# unfused XLA references (the fallback pair, and the parity oracle)
# ---------------------------------------------------------------------------

def xla_all_gather_matmul(x, w, axis: str = AXIS):
    """The sequential pair: blocking all-gather, then the matmul."""
    xg = lax.all_gather(x, axis, axis=0, tiled=True)
    return jnp.dot(xg, w, preferred_element_type=jnp.float32)


def xla_matmul_reduce_scatter(x, w, axis: str = AXIS):
    """The sequential pair: full local matmul, then a blocking
    psum_scatter over the row dimension."""
    p = jnp.dot(x, w, preferred_element_type=jnp.float32)
    return lax.psum_scatter(p, axis, scatter_dimension=0, tiled=True)


# ---------------------------------------------------------------------------
# per-rank bodies (padding + realignment around the kernels)
# ---------------------------------------------------------------------------

def _kernels_available() -> bool:
    """The ring kernels need a backend that can execute remote DMA: a
    real TPU, an AOT TPU lowering (``pallas_ring.aot_lowering``), or the
    TPU interpreter, which every supported jax ships — so always True.
    Kept as the one seam tests patch to drive the ``no_interpret``
    decline accounting."""
    return True


def _resolve(overlap: Optional[bool], nbytes: int, threshold: int) -> bool:
    """overlap=None: session default AND the payload clears the tuned
    size register; True/False: forced (the per-call tuning-register
    override). Either way the kernels must be executable here."""
    if overlap is None:
        on = _OVERLAP_DEFAULT and nbytes >= threshold
    else:
        on = bool(overlap)
    return on and _kernels_available()


def agmm_engage_reason(m: int, k: int, n: int, P: int, dtype,
                       overlap: Optional[bool] = None,
                       bidirectional: bool = True,
                       wire_dtype=None, w_dtype=None) -> Optional[str]:
    """None when :func:`all_gather_matmul` would run the FUSED kernel
    for these shapes under the given overlap mode; otherwise the
    decline reason — ``"off"`` (an explicit/session overlap-off
    request: a requested baseline, never counted as a fallback),
    ``"no_interpret"``, ``"threshold"``, or ``"vmem_miss"``. THE
    single resolution of the session registers (aspect-class aware, in
    EFFECTIVE wire bytes), kernel availability, and the VMEM plan
    (resident OR streaming) — the engage checks and the restructuring
    consumers' committed-baseline telemetry (the mlp, the layerwise
    ZeRO step) both read it, so a counted label can never drift from
    the actual decision. Pass ``w_dtype`` when the weight dtype
    differs from the operand dtype — the body plans with the REAL
    weight dtype, and a verdict computed without it can diverge from
    dispatch near the VMEM budget."""
    wdt = _resolve_wire(wire_dtype, dtype)
    nbytes = m * k * jnp.dtype(wdt if wdt is not None else dtype).itemsize
    if (overlap is not None and not overlap) or \
            (overlap is None and not _OVERLAP_DEFAULT):
        return "off"
    if not _kernels_available():
        return "no_interpret"
    if overlap is None and nbytes < _ag_threshold(k, n):
        return "threshold"
    if agmm_plan(m, k, n, P, dtype, bidirectional,
                 w_dtype=w_dtype, wire_dtype=wdt) is None:
        return "vmem_miss"
    return None


def agmm_engages(m: int, k: int, n: int, P: int, dtype,
                 overlap: Optional[bool] = None,
                 bidirectional: bool = True,
                 wire_dtype=None, w_dtype=None) -> bool:
    """True when :func:`all_gather_matmul` would run the FUSED kernel
    for these shapes — :func:`agmm_engage_reason` with the verdict
    collapsed to a bool. Lets callers that RESTRUCTURE around the
    fused kernels (the mlp's sequence-sharded datapath) fall back to
    their own baseline instead of a degraded unfused rendition of the
    restructured program."""
    return agmm_engage_reason(m, k, n, P, dtype, overlap, bidirectional,
                              wire_dtype, w_dtype) is None


def mmrs_engage_reason(m: int, k: int, n: int, P: int, dtype,
                       overlap: Optional[bool] = None,
                       bidirectional: bool = True,
                       wire_dtype=None, w_dtype=None) -> Optional[str]:
    """:func:`agmm_engage_reason`'s sibling for
    :func:`matmul_reduce_scatter` (the traveller is the f32
    accumulator, so wire bytes key off f32). Geometries the kernel
    cannot express at all (rows not divisible by world) report
    ``"vmem_miss"``'s sibling class as ``"geometry"``."""
    if P < 1 or m % P:
        return "geometry"
    wdt = _resolve_wire(wire_dtype, jnp.float32)
    nbytes = (m // P) * n * (jnp.dtype(wdt).itemsize
                             if wdt is not None else 4)
    if (overlap is not None and not overlap) or \
            (overlap is None and not _OVERLAP_DEFAULT):
        return "off"
    if not _kernels_available():
        return "no_interpret"
    if overlap is None and nbytes < _rs_threshold(k, n):
        return "threshold"
    if mmrs_plan(m, k, n, P, dtype, bidirectional,
                 w_dtype=w_dtype, wire_dtype=wdt) is None:
        return "vmem_miss"
    return None


def mmrs_engages(m: int, k: int, n: int, P: int, dtype,
                 overlap: Optional[bool] = None,
                 bidirectional: bool = True,
                 wire_dtype=None, w_dtype=None) -> bool:
    """:func:`agmm_engages`' sibling for :func:`matmul_reduce_scatter`
    (the traveller is the f32 accumulator, so wire bytes key off f32)."""
    return mmrs_engage_reason(m, k, n, P, dtype, overlap, bidirectional,
                              wire_dtype, w_dtype) is None


def wgrad_engage_reason(ms: int, ct: int, cl: int, P: int, dtype,
                        overlap: Optional[bool] = None,
                        bidirectional: bool = True,
                        wire_dtype=None, loc_dtype=None,
                        travel_lhs: bool = True) -> Optional[str]:
    """:func:`agmm_engage_reason`'s sibling for the fused gathered-wgrad
    leg of the VJPs (:func:`gathered_wgrad_body`): the travelling
    (ms, ct) shard's wire bytes against the FORWARD op's register
    (``travel_lhs`` keys the agmm vs mmrs table, as at dispatch) and
    the :func:`wgrad_plan` VMEM resolution — resident only, there is
    no streaming wgrad (the ROADMAP leftover). Restructuring consumers
    (the layerwise ZeRO step) must consult this alongside the
    forward/dual checks: a geometry whose agmm/mmrs plans fit but
    whose dw panel misses would otherwise commit to a "fused" schedule
    with its activation gradients silently unfused."""
    if P < 2:
        return "geometry"
    wdt = _resolve_wire(wire_dtype, dtype)
    nbytes = ms * ct * jnp.dtype(wdt if wdt is not None else dtype).itemsize
    if (overlap is not None and not overlap) or \
            (overlap is None and not _OVERLAP_DEFAULT):
        return "off"
    if not _kernels_available():
        return "no_interpret"
    th = _ag_threshold(ct, cl) if travel_lhs else _rs_threshold(cl, ct)
    if overlap is None and nbytes < th:
        return "threshold"
    if wgrad_plan(ms, ct, cl, P, wdt if wdt is not None else dtype,
                  loc_dtype if loc_dtype is not None else dtype,
                  bidirectional) is None:
        return "vmem_miss"
    return None


def _fallback_reason(overlap: Optional[bool], op: str) -> None:
    """Count a policy-level fallback (the plan was never consulted).
    An overlap=False REQUEST — per call or session-wide
    (``cmatmul_overlap=False``) — is a requested XLA pair, not a
    fallback; only size-register declines and impossible requests
    count (a ``threshold`` label must mean a size register actually
    declined, or the counter sends operators chasing phantom
    crossovers)."""
    if overlap is not None and not overlap:
        return
    if overlap is None and not _OVERLAP_DEFAULT:
        return
    _note_fallback(op, "no_interpret" if not _kernels_available()
                   else "threshold")


def all_gather_matmul_body(x, w, *, axis: str = AXIS,
                           mesh_axes: Optional[Tuple[str, ...]] = None,
                           overlap: Optional[bool] = None,
                           bidirectional: bool = True,
                           wire_dtype=None):
    """Per-rank body: x (m, k) row shard, w (k, n) local block ->
    (P*m, n) f32 — ``all_gather(x, rows) @ w`` with per-hop overlap.
    The plan picks the VMEM-resident kernel or the k-blocked streaming
    kernel; the unfused XLA pair remains only for kernels-unavailable
    rungs, declined thresholds and geometries whose minimum k-block
    misses the budget (each counted by reason). ``wire_dtype`` stages
    the shard on the wire in a narrower dtype (f32 accumulation
    on-chip); the fallback pair always runs full precision."""
    m, k = x.shape
    k2, n = w.shape
    if k != k2:
        raise ValueError(f"contraction mismatch: x {x.shape} vs w {w.shape}")
    P = lax.axis_size(axis)
    mesh_axes = tuple(mesh_axes) if mesh_axes else (axis,)
    if P == 1:
        return jnp.dot(x, w, preferred_element_type=jnp.float32)
    wdt, sr = _resolve_wire_codec(wire_dtype, x.dtype)
    shard_bytes = m * k * jnp.dtype(wdt if wdt is not None
                                    else x.dtype).itemsize
    plan = None
    if _resolve(overlap, shard_bytes, _ag_threshold(k, n)):
        plan = agmm_plan(m, k, n, P, x.dtype, bidirectional,
                         w_dtype=w.dtype, wire_dtype=wdt)
        if plan is None:
            _note_fallback("allgather_matmul", "vmem_miss")
    else:
        _fallback_reason(overlap, "allgather_matmul")
    if plan is None:
        return xla_all_gather_matmul(x, w, axis)
    mp, kp, np_ = plan["mp"], plan["kp"], plan["np"]
    xw = _wire_cast(x, wdt, stochastic=sr)
    xp = jnp.zeros((mp, kp), xw.dtype)
    xp = lax.dynamic_update_slice(xp, xw, (0, 0))
    wp = jnp.zeros((kp, np_), w.dtype)
    wp = lax.dynamic_update_slice(wp, w, (0, 0))
    if plan["mode"] == "resident":
        out = _agmm_call(xp, wp, P=P, axis=axis, mesh_axes=mesh_axes,
                         out_dtype=jnp.float32,
                         bidirectional=plan["bidirectional"])
    else:
        kb, nkb = plan["kb"], plan["nkb"]
        mb, nmb = plan.get("mb", mp), plan.get("nmb", 1)
        wseg = wp.reshape(nkb, kb, np_)
        blocks = []
        for i in range(nmb):
            # accumulator-floor arm: each sublane-aligned row block of
            # the traveller rides its own ring pass into a disjoint
            # output row slice (one iteration == the unblocked kernel)
            xb = xp if nmb == 1 else \
                lax.dynamic_slice_in_dim(xp, i * mb, mb, axis=0)
            # segment-major split of the contraction dim: every staged
            # DMA in the streaming kernel becomes a leading-index copy
            xseg = xb.reshape(mb, nkb, kb).transpose(1, 0, 2)
            blocks.append(_agmm_stream_call(
                xseg, wseg, P=P, axis=axis, mesh_axes=mesh_axes,
                bidirectional=plan["bidirectional"],
                nkb=nkb, mp=mb, np_=np_))
        out = blocks[0] if nmb == 1 else jnp.concatenate(blocks, axis=1)
    return out[:, :m, :n].reshape(P * m, n)


def matmul_reduce_scatter_body(x, w, *, axis: str = AXIS,
                               mesh_axes: Optional[Tuple[str, ...]] = None,
                               overlap: Optional[bool] = None,
                               bidirectional: bool = True,
                               wire_dtype=None):
    """Per-rank body: x (m, k) local rows, w (k, n) local block ->
    (m/P, n) f32 — ``reduce_scatter(x @ w, rows)`` with the per-hop
    partial computed while the accumulator is on the wire (k-blocked
    from HBM in streaming mode). ``wire_dtype`` stages the TRAVELLING
    accumulator on the wire in a narrower dtype; every fold
    decompresses and accumulates in f32 on-chip."""
    m, k = x.shape
    k2, n = w.shape
    if k != k2:
        raise ValueError(f"contraction mismatch: x {x.shape} vs w {w.shape}")
    P = lax.axis_size(axis)
    if m % P:
        raise ValueError(f"rows {m} not divisible by world {P}")
    mesh_axes = tuple(mesh_axes) if mesh_axes else (axis,)
    if P == 1:
        return jnp.dot(x, w, preferred_element_type=jnp.float32)
    wdt = _resolve_wire(wire_dtype, jnp.float32)   # the traveller is f32
    acc_bytes = (m // P) * n * (jnp.dtype(wdt).itemsize
                                if wdt is not None else 4)
    plan = None
    if _resolve(overlap, acc_bytes, _rs_threshold(k, n)):
        plan = mmrs_plan(m, k, n, P, x.dtype, bidirectional,
                         w_dtype=w.dtype, wire_dtype=wdt)
        if plan is None:
            _note_fallback("matmul_reduce_scatter", "vmem_miss")
    else:
        _fallback_reason(overlap, "matmul_reduce_scatter")
    if plan is None:
        return xla_matmul_reduce_scatter(x, w, axis)
    cp, kp, np_ = plan["cp"], plan["kp"], plan["np"]
    mc = m // P
    # group rows by output chunk with per-chunk padding so the kernel
    # indexes a uniform (P, cp, kp) grid
    grid = jnp.zeros((P, cp, kp), x.dtype)
    grid = lax.dynamic_update_slice(
        grid, x.reshape(P, mc, k), (0, 0, 0))
    wp = jnp.zeros((kp, np_), w.dtype)
    wp = lax.dynamic_update_slice(wp, w, (0, 0))
    if plan["mode"] == "resident":
        out = _mmrs_call(grid, wp, P=P, axis=axis, mesh_axes=mesh_axes,
                         out_dtype=jnp.float32,
                         bidirectional=plan["bidirectional"], wire=wdt)
    else:
        kb, nkb = plan["kb"], plan["nkb"]
        nb, nnb = plan.get("nb", np_), plan.get("nnb", 1)
        xseg = grid.reshape(P, cp, nkb, kb).transpose(0, 2, 1, 3)
        blocks = []
        for j in range(nnb):
            # accumulator-floor arm: each lane-aligned column block of
            # the travelling accumulator rides its own ring over the
            # same x grid and a w column slice (one iteration == the
            # unblocked kernel); the single realignment hop below acts
            # on the concatenated chunk
            wb = wp if nnb == 1 else \
                lax.dynamic_slice_in_dim(wp, j * nb, nb, axis=1)
            wseg = wb.reshape(nkb, kb, nb)
            blocks.append(_mmrs_stream_call(
                xseg, wseg, P=P, axis=axis, mesh_axes=mesh_axes,
                out_dtype=jnp.float32,
                bidirectional=plan["bidirectional"],
                nkb=nkb, cp=cp, np_=nb, wire=wdt))
        out = blocks[0] if nnb == 1 else jnp.concatenate(blocks, axis=1)
    fwd = [(i, (i + 1) % P) for i in range(P)]
    if plan["bidirectional"]:
        # channel 0 (top half rows) ended at chunk (pos+1), channel 1
        # (bottom half) at chunk (pos-1): realign per half, one hop in
        # each direction (the chunked-RS bidirectional realignment)
        ch = cp // 2
        bwd = [(i, (i - 1 + P) % P) for i in range(P)]
        top = lax.ppermute(out[:ch], axis, fwd)
        bot = lax.ppermute(out[ch:], axis, bwd)
        out = jnp.concatenate([top, bot], axis=0)
    else:
        # rank pos holds folded chunk (pos+1)%P; one forward hop aligns
        out = lax.ppermute(out, axis, fwd)
    return out[:mc, :n]


# ---------------------------------------------------------------------------
# fused dgrad/wgrad body: the all-gather folded into dw's k-sweep
# ---------------------------------------------------------------------------

def gathered_wgrad_body(trav, loc, *, axis: str = AXIS,
                        mesh_axes: Optional[Tuple[str, ...]] = None,
                        overlap: Optional[bool] = None,
                        bidirectional: bool = True,
                        wire_dtype=None,
                        travel_lhs: bool = True,
                        op: str = "allgather_matmul"):
    """Per-rank body for the fused wgrad: ``trav`` is this rank's
    (ms, ct) shard of the operand the backward must gather (x for
    d(ag×mm), dy for d(mm×rs)); ``loc`` is the (P*ms, cl) resident
    operand whose row blocks pair with each gathered shard.

    ``travel_lhs=True`` returns (ct, cl) = ``all_gather(trav)ᵀ @ loc``;
    False returns (cl, ct) = ``locᵀ @ all_gather(trav)``. The fused
    kernel folds the gather into the contraction sweep — each arriving
    ring shard contributes its partial while the next hop's transfer
    is in flight. Falls back to the unfused all_gather + dot_general
    (same math, no overlap) when the plan misses or the policy
    declines; fallbacks are counted under ``{op}_dw``."""
    ms, ct = trav.shape
    ml, cl = loc.shape
    P = lax.axis_size(axis)
    mesh_axes = tuple(mesh_axes) if mesh_axes else (axis,)
    if ml != P * ms:
        raise ValueError(
            f"wgrad row mismatch: loc rows {ml} != world {P} x shard {ms}")

    def _unfused(gathered):
        a, b = (gathered, loc) if travel_lhs else (loc, gathered)
        return lax.dot_general(a, b.astype(a.dtype),
                               (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

    if P == 1:
        return _unfused(trav)
    wdt, sr = _resolve_wire_codec(wire_dtype, trav.dtype)
    nbytes = ms * ct * jnp.dtype(wdt if wdt is not None
                                 else trav.dtype).itemsize
    # the travelling payload is the agmm-style shard for d(ag×mm) and
    # the dy shard for d(mm×rs): key each on its forward op's register
    th = _ag_threshold(ct, cl) if travel_lhs else _rs_threshold(cl, ct)
    plan = None
    if _resolve(overlap, nbytes, th):
        plan = wgrad_plan(ms, ct, cl, P,
                          wdt if wdt is not None else trav.dtype,
                          loc.dtype, bidirectional)
        if plan is None:
            _note_fallback(op + "_dw", "vmem_miss")
    else:
        _fallback_reason(overlap, op + "_dw")
    if plan is None:
        return _unfused(lax.all_gather(trav, axis, axis=0, tiled=True))
    msp, ctp, clp = plan["msp"], plan["ctp"], plan["clp"]
    tw = _wire_cast(trav, wdt, stochastic=sr)
    tp_ = jnp.zeros((msp, ctp), tw.dtype)
    tp_ = lax.dynamic_update_slice(tp_, tw, (0, 0))
    lp = jnp.zeros((P, msp, clp), loc.dtype)
    lp = lax.dynamic_update_slice(lp, loc.reshape(P, ms, cl), (0, 0, 0))
    ctb, nctb = plan.get("ctb", ctp), plan.get("nctb", 1)
    if nctb == 1:
        out = _wgrad_call(tp_, lp, P=P, axis=axis, mesh_axes=mesh_axes,
                          bidirectional=plan["bidirectional"],
                          travel_lhs=travel_lhs)
    else:
        # streaming arm: each lane-aligned column block of the
        # traveller rides its own ring pass into a disjoint dw row
        # (resp. column) block
        blocks = []
        for j in range(nctb):
            tb = lax.dynamic_slice_in_dim(tp_, j * ctb, ctb, axis=1)
            blocks.append(_wgrad_call(
                tb, lp, P=P, axis=axis, mesh_axes=mesh_axes,
                bidirectional=plan["bidirectional"],
                travel_lhs=travel_lhs))
        out = jnp.concatenate(blocks, axis=0 if travel_lhs else 1)
    return out[:ct, :cl] if travel_lhs else out[:cl, :ct]


# ---------------------------------------------------------------------------
# differentiable entry points (the collective-matmul duality as a VJP)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def all_gather_matmul(x, w, axis: str = AXIS,
                      mesh_axes: Optional[Tuple[str, ...]] = None,
                      overlap: Optional[bool] = None,
                      bidirectional: bool = True,
                      wire_dtype=None):
    """``all_gather(x, rows) @ w`` with per-hop comm/compute overlap.

    x: (m, k) per-rank row shard of the LHS; w: (k, n) local weight
    block (column-parallel). Returns (P*m, n) f32. ``overlap=None``
    follows the session default (``ACCLConfig.cmatmul_overlap``);
    False pins the unfused XLA pair. ``wire_dtype=None`` follows
    ``ACCLConfig.cmatmul_wire_dtype`` ("off" forces full precision).
    Differentiable: the backward runs the dual ``matmul_reduce_scatter``
    for dx AND the fused gathered wgrad for dw — both overlapped."""
    return all_gather_matmul_body(x, w, axis=axis, mesh_axes=mesh_axes,
                                  overlap=overlap,
                                  bidirectional=bidirectional,
                                  wire_dtype=wire_dtype)


def _agmm_fwd(x, w, axis, mesh_axes, overlap, bidirectional, wire_dtype):
    y = all_gather_matmul_body(x, w, axis=axis, mesh_axes=mesh_axes,
                               overlap=overlap, bidirectional=bidirectional,
                               wire_dtype=wire_dtype)
    return y, (x, w)


def _agmm_bwd(axis, mesh_axes, overlap, bidirectional, wire_dtype, res, dy):
    x, w = res
    # dX_full = psum_p(dy_p w_pᵀ); our row shard of it is exactly the
    # dual overlapped kernel
    dx = matmul_reduce_scatter_body(
        dy.astype(x.dtype), jnp.transpose(w).astype(x.dtype),
        axis=axis, mesh_axes=mesh_axes, overlap=overlap,
        bidirectional=bidirectional, wire_dtype=wire_dtype).astype(x.dtype)
    # dw = all_gather(x)ᵀ @ dy with the gather folded into the k-sweep
    dw = gathered_wgrad_body(
        x, dy.astype(x.dtype), axis=axis, mesh_axes=mesh_axes,
        overlap=overlap, bidirectional=bidirectional,
        wire_dtype=wire_dtype, travel_lhs=True,
        op="allgather_matmul").astype(w.dtype)
    return dx, dw


all_gather_matmul.defvjp(_agmm_fwd, _agmm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def matmul_reduce_scatter(x, w, axis: str = AXIS,
                          mesh_axes: Optional[Tuple[str, ...]] = None,
                          overlap: Optional[bool] = None,
                          bidirectional: bool = True,
                          wire_dtype=None):
    """``reduce_scatter(x @ w, rows)`` with per-hop comm/compute
    overlap. x: (m, k) local rows (m divisible by world); w: (k, n)
    local block (row-parallel). Returns (m/P, n) f32. Differentiable:
    dx runs the dual overlapped ``all_gather_matmul``; dw the fused
    gathered wgrad (the all-gather of dy folded into its k-sweep)."""
    return matmul_reduce_scatter_body(x, w, axis=axis, mesh_axes=mesh_axes,
                                      overlap=overlap,
                                      bidirectional=bidirectional,
                                      wire_dtype=wire_dtype)


def _mmrs_fwd(x, w, axis, mesh_axes, overlap, bidirectional, wire_dtype):
    y = matmul_reduce_scatter_body(x, w, axis=axis, mesh_axes=mesh_axes,
                                   overlap=overlap,
                                   bidirectional=bidirectional,
                                   wire_dtype=wire_dtype)
    return y, (x, w)


def _mmrs_bwd(axis, mesh_axes, overlap, bidirectional, wire_dtype, res, dy):
    x, w = res
    dx = all_gather_matmul_body(
        dy.astype(x.dtype), jnp.transpose(w).astype(x.dtype),
        axis=axis, mesh_axes=mesh_axes, overlap=overlap,
        bidirectional=bidirectional, wire_dtype=wire_dtype).astype(x.dtype)
    # dw = xᵀ @ all_gather(dy) with the gather folded into the k-sweep
    dw = gathered_wgrad_body(
        dy.astype(x.dtype), x, axis=axis, mesh_axes=mesh_axes,
        overlap=overlap, bidirectional=bidirectional,
        wire_dtype=wire_dtype, travel_lhs=False,
        op="matmul_reduce_scatter").astype(w.dtype)
    return dx, dw


matmul_reduce_scatter.defvjp(_mmrs_fwd, _mmrs_bwd)
