"""HBM-scale segmented Pallas ring collectives (the pipelined RDMA path).

The sibling :mod:`pallas_ring` kernels stage the whole payload in VMEM —
correct for latency-sized messages but structurally unable to run the
BASELINE.json sweep endpoint (1 GiB).  These kernels are the segmented
analog of the reference's streaming design: the firmware never holds a
message, it cuts it into rx-buffer-sized segments and keeps a bounded
number of moves in flight (send loop ``ccl_offload_control.c:628-649``,
segmented allreduce outer loop ``:1906-2071``).  Here:

* payload stays in HBM (``pl.ANY`` refs); only two segments per channel
  are resident in VMEM at any time;
* two independent *channels* (even/odd segments) run their rings
  concurrently — channel B's remote DMA is in flight while channel A
  folds, the ≤3-moves-in-flight analog;
* ``wait_send``/``wait_recv`` are split so the next transfer is issued
  before the previous hop's data has been consumed;
* a credit semaphore gates reuse of the two receive slots — the VMEM
  analog of the eager rx-buffer pool's backpressure, actually enforced
  (a writer blocks until the consumer has folded the slot's previous
  content), not a decorative start/wait pair.

Hazard accounting (validated by the interpret-mode race detector,
``InterpretParams(detect_races=True)``):

* recv slots alternate on the *global* step counter ``t = group*(P-1)+s``
  so the credit chain spans segment-group boundaries;
* a slot's credit is granted only after the local fold consumed it
  (reduce-scatter) or after it was both forwarded (``wait_send``) and
  flushed to HBM (all-gather);
* HBM stores are asynchronous; their semaphores are consumed exactly once
  (by the next step's slot reuse, the next group's seed, or the epilogue).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..communicator import Communicator
from ..constants import (DEFAULT_SEGMENT_SIZE, dataType, reduceFunction,
                         to_jax_dtype)
from .primitives import AXIS, _smap
from . import pallas_ring as _pr
from .pallas_ring import (_LANES, _combine, _neighbors, _pad_rows,
                          _ring_barrier, _sublane)


def _interpret_params():
    # late-bound so tests patching pallas_ring._interpret_params (e.g. to
    # enable the race detector) cover these kernels too
    return _pr._interpret_params()

#: per-segment VMEM cap — the chunked kernels keep ~10 segments resident
#: (2 channels x {acc, 2 recv slots, local, staging}), so 1 MiB segments
#: bound VMEM use to ~10 MiB of the ~16 MiB budget.
VMEM_SEGMENT_CAP = 1 << 20


def _seg_rows(segment_bytes: int, dtype) -> int:
    """Rows (of 128 lanes) per segment, honoring sublane tiling."""
    elems = max(segment_bytes // jnp.dtype(dtype).itemsize, _LANES)
    rows = max(elems // _LANES, 1)
    mult = _sublane(dtype)
    return max(-(-rows // mult) * mult, mult)


#: public alias — the ONE copy of the sublane-tiled segment-rows rule,
#: shared with the pipeline activation relay (ops/pipeline_relay.py)
seg_rows = _seg_rows


# ---------------------------------------------------------------------------
# segmented ring reduce-scatter
# ---------------------------------------------------------------------------

def _chunked_rs_kernel(x_ref, o_ref, acc_buf, recv_buf, local_buf,
                       send_sem, recv_sem, seed_sem, local_sem, store_sem,
                       cap_sem, *rest, P: int, C: int, func: reduceFunction,
                       wire=None, bidirectional: bool = False):
    """x_ref: (P, C, Sr, 128) in HBM; o_ref: (C, Sr, 128) in HBM.

    Rank ``my`` ends owning folded chunk ``(my+1) % P`` (ring schedule);
    the wrapper rolls it back.  Two channels process segments 2g / 2g+1.

    ``bidirectional=True`` mirrors channel 1 — its segments rotate LEFT
    while channel 0's rotate right, so both directions of every ICI link
    carry payload simultaneously (each direction moves half the bytes:
    the 2x ring-bandwidth ceiling a bidirectional torus link offers,
    which the reference's unidirectional Ethernet rings cannot use).
    Channel 1 then ends owning chunk ``(my-1) % P`` for its segments;
    the wrapper realigns per segment parity.

    ``wire=(wire dtype, scale)`` adds a wire staging buffer (``rest[0]``):
    the remote DMA carries the compressed segment, the fold decompresses
    it and accumulates at full precision — per-hop ETH_COMPRESSED
    semantics (hp_compression.cpp:30-144) at HBM scale. acc_buf stays in
    the compute dtype (seed source + store staging); the rdma source
    switches to the wire buffer, whose reuse rdma.wait_send() guards.
    """
    wire_buf = rest[0] if wire is not None else None
    my, left, right = _neighbors(P)
    _ring_barrier(left, right)
    hops = P - 1
    G = -(-C // 2)           # groups of two segments
    T = [G * hops, (C // 2) * hops]   # per-channel global step counts
    # per-channel ring orientation: (downstream we send to, upstream we
    # grant credits to, fold-index sign)
    def _dirs(chan):
        if bidirectional and chan == 1:
            return left, right, jnp.int32(1)
        return right, left, jnp.int32(-1)

    def seg_of(chan, g):
        return g * 2 + chan

    def wait_store(chan):
        """Consume a store completion (descriptor recreated for its size —
        the DMA-semaphore wait decrements by the copy's byte count)."""
        pltpu.make_async_copy(
            acc_buf.at[chan], o_ref.at[0], store_sem.at[chan]).wait()

    def chan_step(chan, g, s, t):
        """One hop for one channel; every async op's semaphore is consumed
        exactly once (hazard accounting in the module docstring)."""
        dst, _, sign = _dirs(chan)
        c = seg_of(chan, g)
        slot = lax.rem(t, 2)
        idx = lax.rem(my + sign * (s + jnp.int32(1)) + jnp.int32(2 * P),
                      jnp.int32(P))

        # credit gate: writing the downstream recv slot t%2 needs it to
        # have folded the slot's step t-2 content (rx-pool backpressure)
        @pl.when(t >= 2)
        def _gate():
            pltpu.semaphore_wait(cap_sem.at[chan], 1)

        rdma = pltpu.make_async_remote_copy(
            src_ref=(acc_buf if wire is None else wire_buf).at[chan],
            dst_ref=recv_buf.at[chan, slot],
            send_sem=send_sem.at[chan],
            recv_sem=recv_sem.at[chan, slot],
            device_id=dst,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()

        # overlap the RDMA with the HBM fetch of the local fold operand
        local = pltpu.make_async_copy(
            x_ref.at[idx, c], local_buf.at[chan], local_sem.at[chan])
        local.start()
        return rdma, local

    def chan_fold(chan, g, s, t, rdma, local):
        _, upstream, _ = _dirs(chan)
        c = seg_of(chan, g)
        slot = lax.rem(t, 2)
        rdma.wait_recv()
        local.wait()
        rx = (recv_buf[chan, slot] if wire is None
              else _pr._from_wire(recv_buf[chan, slot],
                                  local_buf.dtype, wire))
        folded = _combine(rx, local_buf[chan], func)

        # recv slot consumed -> grant upstream a credit for its step t+2
        @pl.when(t + 2 <= T[chan] - 1)
        def _free():
            pltpu.semaphore_signal(
                cap_sem.at[chan], inc=1, device_id=upstream,
                device_id_type=pltpu.DeviceIdType.LOGICAL)

        rdma.wait_send()          # send staging drained -> safe to overwrite
        acc_buf[chan] = folded    # store staging (and next hop's payload
                                  # when uncompressed)
        if wire is not None:
            wire_buf[chan] = _pr._to_wire(folded, wire)  # compress lane

        @pl.when(s == P - 2)
        def _flush():
            st = pltpu.make_async_copy(
                acc_buf.at[chan], o_ref.at[c], store_sem.at[chan])
            st.start()

    def group(g, _):
        def seed(chan):
            c = seg_of(chan, g)
            # previous group's final store still reads acc_buf[chan]
            @pl.when(g > 0)
            def _drain():
                wait_store(chan)
            ld = pltpu.make_async_copy(
                x_ref.at[my, c], acc_buf.at[chan], seed_sem.at[chan])
            ld.start()
            ld.wait()
            if wire is not None:
                # compress the seed for hop 0's remote DMA (the previous
                # group's last wait_send already drained wire_buf)
                wire_buf[chan] = _pr._to_wire(acc_buf[chan], wire)

        chan1 = 2 * g + 1 < C
        seed(0)

        @pl.when(chan1)
        def _seed1():
            seed(1)

        def hop(s, _):
            t = g * hops + s
            r0, l0 = chan_step(0, g, s, t)

            # channel 1's transfer is in flight while channel 0 folds
            def step1():
                return chan_step(1, g, s, t)

            @pl.when(chan1)
            def _go1():
                r1, l1 = step1()
                chan_fold(0, g, s, t, r0, l0)
                chan_fold(1, g, s, t, r1, l1)

            @pl.when(jnp.logical_not(chan1))
            def _solo():
                chan_fold(0, g, s, t, r0, l0)

            return 0

        lax.fori_loop(0, hops, hop, 0)
        return 0

    lax.fori_loop(0, G, group, 0)
    # epilogue: drain the final group's stores
    wait_store(0)
    if C > 1:
        wait_store(1)


def _chunked_rs_call(x, *, P: int, C: int, sr: int, func, dtype, wire=None,
                     bidirectional: bool = False):
    scratch = [
        pltpu.VMEM((2, sr, _LANES), dtype),          # acc_buf
        pltpu.VMEM((2, 2, sr, _LANES),
                   wire[0] if wire is not None else dtype),  # recv_buf
        pltpu.VMEM((2, sr, _LANES), dtype),          # local_buf
        pltpu.SemaphoreType.DMA((2,)),               # send_sem
        pltpu.SemaphoreType.DMA((2, 2)),             # recv_sem
        pltpu.SemaphoreType.DMA((2,)),               # seed_sem
        pltpu.SemaphoreType.DMA((2,)),               # local_sem
        pltpu.SemaphoreType.DMA((2,)),               # store_sem
        pltpu.SemaphoreType.REGULAR((2,)),           # cap_sem (per chan)
    ]
    if wire is not None:
        scratch.append(pltpu.VMEM((2, sr, _LANES), wire[0]))  # wire_buf
    return pl.pallas_call(
        functools.partial(_chunked_rs_kernel, P=P, C=C, func=func,
                          wire=wire, bidirectional=bidirectional),
        out_shape=jax.ShapeDtypeStruct((C, sr, _LANES), dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=2),
        interpret=_interpret_params(),
    )(x)


# ---------------------------------------------------------------------------
# segmented ring all-gather
# ---------------------------------------------------------------------------

def _chunked_ag_kernel(x_ref, o_ref, buf, send_sem, recv_sem, seed_sem,
                       store_sem, cap_sem, *, P: int, C: int,
                       bidirectional: bool = False):
    """x_ref: (C, Sr, 128) own block in HBM; o_ref: (P, C, Sr, 128) HBM.

    Step t: send ``buf[chan, t%2]`` downstream, receive block
    ``(my-s-1)%P`` (channel 1 mirrored: ``(my+s+1)%P``) into
    ``buf[chan, (t+1)%2]``, flush it to HBM, forward it at t+1.
    ``bidirectional=True`` rotates channel 1 LEFT so both directions of
    every link carry payload; the output is complete either way (each
    block's odd segments just arrive via the opposite ring).
    """
    my, left, right = _neighbors(P)
    _ring_barrier(left, right)
    hops = P - 1
    G = -(-C // 2)
    T = [G * hops, (C // 2) * hops]

    def _dirs(chan):
        if bidirectional and chan == 1:
            return left, right, jnp.int32(1)
        return right, left, jnp.int32(-1)

    def seg_of(chan, g):
        return g * 2 + chan

    def wait_store(chan, slot):
        """Consume a store completion on the given slot (descriptor
        recreated for its size — the wait decrements by byte count)."""
        pltpu.make_async_copy(
            buf.at[chan, slot], o_ref.at[0, 0],
            store_sem.at[chan, slot]).wait()

    def seed(chan, g):
        c = seg_of(chan, g)
        t0 = g * hops
        slot = lax.rem(t0, 2)
        # slot t0%2 last flushed by store(t0-1); consume that signal
        @pl.when(g > 0)
        def _drain():
            wait_store(chan, slot)
        ld = pltpu.make_async_copy(
            x_ref.at[c], buf.at[chan, slot], seed_sem.at[chan])
        ld.start()
        ld.wait()
        st = pltpu.make_async_copy(
            buf.at[chan, slot], o_ref.at[my, c], store_sem.at[chan, slot])
        st.start()

    def chan_send(chan, g, s, t):
        dst, _, _ = _dirs(chan)
        slot = lax.rem(t, 2)
        nslot = lax.rem(t + 1, 2)

        # credit: downstream's send(t-1) + store(t-2) must have freed nslot
        @pl.when(t >= 1)
        def _gate():
            pltpu.semaphore_wait(cap_sem.at[chan], 1)

        rdma = pltpu.make_async_remote_copy(
            src_ref=buf.at[chan, slot],
            dst_ref=buf.at[chan, nslot],
            send_sem=send_sem.at[chan],
            recv_sem=recv_sem.at[chan, nslot],
            device_id=dst,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        return rdma

    def chan_finish(chan, g, s, t, rdma):
        _, upstream, sign = _dirs(chan)
        c = seg_of(chan, g)
        slot = lax.rem(t, 2)
        nslot = lax.rem(t + 1, 2)
        src_idx = lax.rem(my + sign * (s + jnp.int32(1)) + jnp.int32(2 * P),
                          jnp.int32(P))

        rdma.wait_recv()
        st = pltpu.make_async_copy(
            buf.at[chan, nslot], o_ref.at[src_idx, c],
            store_sem.at[chan, nslot])
        st.start()

        rdma.wait_send()
        # the slot just sent was flushed by store(t-1) (or the seed store);
        # consume that signal, then release the slot to the upstream writer
        wait_store(chan, slot)

        @pl.when(t <= T[chan] - 2)
        def _release():
            pltpu.semaphore_signal(
                cap_sem.at[chan], inc=1, device_id=upstream,
                device_id_type=pltpu.DeviceIdType.LOGICAL)

    def group(g, _):
        chan1 = 2 * g + 1 < C
        seed(0, g)

        @pl.when(chan1)
        def _seed1():
            seed(1, g)

        def hop(s, _):
            t = g * hops + s
            r0 = chan_send(0, g, s, t)

            @pl.when(chan1)
            def _go1():
                r1 = chan_send(1, g, s, t)
                chan_finish(0, g, s, t, r0)
                chan_finish(1, g, s, t, r1)

            @pl.when(jnp.logical_not(chan1))
            def _solo():
                chan_finish(0, g, s, t, r0)

            return 0

        lax.fori_loop(0, hops, hop, 0)
        return 0

    lax.fori_loop(0, G, group, 0)
    # epilogue: final stores (slot (T)%2 per channel) are still in flight
    wait_store(0, T[0] % 2)
    if C > 1:
        wait_store(1, T[1] % 2)


def _chunked_ag_call(x, *, P: int, C: int, sr: int, dtype,
                     bidirectional: bool = False):
    return pl.pallas_call(
        functools.partial(_chunked_ag_kernel, P=P, C=C,
                          bidirectional=bidirectional),
        out_shape=jax.ShapeDtypeStruct((P, C, sr, _LANES), dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, 2, sr, _LANES), dtype),   # buf
            pltpu.SemaphoreType.DMA((2,)),           # send_sem
            pltpu.SemaphoreType.DMA((2, 2)),         # recv_sem
            pltpu.SemaphoreType.DMA((2,)),           # seed_sem
            pltpu.SemaphoreType.DMA((2, 2)),         # store_sem
            pltpu.SemaphoreType.REGULAR((2,)),       # cap_sem
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=3),
        interpret=_interpret_params(),
    )(x)


# ---------------------------------------------------------------------------
# segmented pipelined ring broadcast
# ---------------------------------------------------------------------------

def _chunked_bcast_kernel(x_ref, o_ref, buf, send_sem, recv_sem, seed_sem,
                          store_sem, cap_sem, *, P: int, C: int, root: int):
    """x_ref: (C, Sr, 128) root's payload in HBM; o_ref: (C, Sr, 128) HBM.

    Pipelined ring broadcast — the HBM-scale analog of the firmware's
    segmented eager bcast fanout (``ccl_offload_control.c:923-989``), but
    ring-shaped because that is the TPU-optimal topology: the root streams
    segments to its right neighbor and every rank forwards segment ``s``
    while receiving ``s+1``, so total time is ~(C + P - 2) segment times
    (≈ payload/bw for C >> P) instead of the root serializing (P-1) full
    copies like a star fanout would.

    Software pipeline over global steps ``t`` with ring position
    ``pos = (my - root) % P``: at step ``t`` a rank sends segment
    ``t - pos`` (the one it received at ``t-1``; the root loads it from
    HBM instead) and receives segment ``t - pos + 1``. The last rank
    (pos = P-1) only receives. Two VMEM slots alternate on segment
    parity; a credit semaphore gates slot reuse exactly like the other
    chunked kernels: the writer to a slot may send only after its owner
    consumed the slot's previous content (forwarded AND flushed to HBM),
    so backpressure — not luck — bounds the in-flight segments.
    """
    my, left, right = _neighbors(P)
    _ring_barrier(left, right)
    pos = lax.rem(my - jnp.int32(root) + jnp.int32(P), jnp.int32(P))
    is_root = pos == 0
    is_last = pos == P - 1

    def wait_store(slot):
        """Consume a store completion (descriptor recreated for its size —
        the DMA-semaphore wait decrements by the copy's byte count)."""
        pltpu.make_async_copy(
            buf.at[slot], o_ref.at[0], store_sem.at[slot]).wait()

    def grant(slot_seg):
        """Release the slot that held ``slot_seg`` back to the left
        writer — only when a future segment will actually reuse it
        (grants == gates, so every semaphore drains to zero)."""
        @pl.when(slot_seg <= C - 3)
        def _g():
            pltpu.semaphore_signal(
                cap_sem, inc=1, device_id=left,
                device_id_type=pltpu.DeviceIdType.LOGICAL)

    def _rdma(slot):
        return pltpu.make_async_remote_copy(
            src_ref=buf.at[slot],
            dst_ref=buf.at[slot],
            send_sem=send_sem,
            recv_sem=recv_sem.at[slot],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )

    def step(t, _):
        # the loop index arrives as int64 under x64 on the interpret rung
        s_idx = jnp.int32(t) - pos   # segment this rank sends at step t
        r_idx = s_idx + jnp.int32(1)  # segment received at step t
        send_m = jnp.logical_and(jnp.logical_and(s_idx >= 0, s_idx < C),
                                 jnp.logical_not(is_last))
        recv_m = jnp.logical_and(jnp.logical_and(r_idx >= 0, r_idx < C),
                                 jnp.logical_not(is_root))

        @pl.when(send_m)
        def _send():
            slot = lax.rem(s_idx, jnp.int32(2))

            @pl.when(is_root)
            def _load():
                # our own slot is safe: its previous send (s_idx-2) was
                # drained by wait_send two steps ago
                ld = pltpu.make_async_copy(
                    x_ref.at[s_idx], buf.at[slot], seed_sem)
                ld.start()
                ld.wait()

            # credit gate: the right neighbor must have consumed the
            # slot's previous segment (s_idx - 2) before we overwrite it
            @pl.when(s_idx >= 2)
            def _gate():
                pltpu.semaphore_wait(cap_sem, 1)

            _rdma(slot).start()

        @pl.when(recv_m)
        def _recv():
            rslot = lax.rem(r_idx, jnp.int32(2))
            _rdma(rslot).wait_recv()
            st = pltpu.make_async_copy(
                buf.at[rslot], o_ref.at[r_idx], store_sem.at[rslot])
            st.start()

            # the last rank never forwards: its slot is consumed once the
            # flush lands, so it grants from the recv side
            @pl.when(is_last)
            def _last():
                wait_store(rslot)
                grant(r_idx)

        @pl.when(send_m)
        def _finish():
            slot = lax.rem(s_idx, jnp.int32(2))
            _rdma(slot).wait_send()

            # forwarding ranks also flushed this slot's segment last step;
            # both readers are done now, so the slot goes back to the left
            @pl.when(jnp.logical_not(is_root))
            def _drain():
                wait_store(slot)
                grant(s_idx)

        return 0

    lax.fori_loop(0, C + P - 2, step, 0)


def _chunked_bcast_call(x, *, P: int, C: int, sr: int, dtype, root: int):
    return pl.pallas_call(
        functools.partial(_chunked_bcast_kernel, P=P, C=C, root=root),
        out_shape=jax.ShapeDtypeStruct((C, sr, _LANES), dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, sr, _LANES), dtype),      # buf (2 slots)
            pltpu.SemaphoreType.DMA,                 # send_sem
            pltpu.SemaphoreType.DMA((2,)),           # recv_sem
            pltpu.SemaphoreType.DMA,                 # seed_sem
            pltpu.SemaphoreType.DMA((2,)),           # store_sem
            pltpu.SemaphoreType.REGULAR,             # cap_sem
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=4),
        interpret=_interpret_params(),
    )(x)


# ---------------------------------------------------------------------------
# segmented ring-relay scatter
# ---------------------------------------------------------------------------

def _chunked_scatter_kernel(x_ref, o_ref, buf, send_sem, recv_sem, load_sem,
                            store_sem, cap_sem, *, P: int, C: int,
                            root: int):
    """x_ref: (P, C, Sr, 128) in HBM (root's full payload; scratch
    elsewhere); o_ref: (C, Sr, 128) own chunk in HBM.

    Ring-relay scatter — the segmented analog of the firmware's eager
    scatter fanout (``ccl_offload_control.c:1082-1124``), ring-shaped:
    the root streams blocks for positions 1..P-1 in that order; each rank
    keeps the FIRST C segments that arrive (its own block) and forwards
    everything after directly from the receive slot — the relay needs no
    buffering beyond the two slots because the outgoing stream is exactly
    the incoming stream minus the head block.

    With ``pos = (my - root) % P``: rank pos receives C*(P-pos) segments
    and sends C*(P-1-pos); the root sends C*(P-1) from HBM. Incoming
    segment t is block pos + t//C; at t >= C it is forwarded in the same
    step (its receiver indexes it as t - C, so the remote slot is
    (t-C)%2). Credit semaphores gate slot reuse; grants == gates.
    """
    my, left, right = _neighbors(P)
    _ring_barrier(left, right)
    pos = lax.rem(my - jnp.int32(root) + jnp.int32(P), jnp.int32(P))
    is_root = pos == 0
    Cc = jnp.int32(C)
    two = jnp.int32(2)
    n_in = jnp.where(is_root, jnp.int32(0), (jnp.int32(P) - pos) * Cc)
    n_out = (jnp.int32(P) - jnp.int32(1) - pos) * Cc

    def _rdma(src_slot, dst_slot):
        # send semaphores are PER SLOT: the root keeps two sends in
        # flight, and DMA completions are unordered — a shared counting
        # semaphore could satisfy slot A's drain with slot B's completion
        # and let the loader overwrite a slot mid-send (race-detector
        # caught exactly this)
        return pltpu.make_async_remote_copy(
            src_ref=buf.at[src_slot],
            dst_ref=buf.at[dst_slot],
            send_sem=send_sem.at[src_slot],
            recv_sem=recv_sem.at[dst_slot],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )

    def step(t, _):
        t = jnp.int32(t)
        seg = lax.rem(t, Cc)

        # ---- root: send out-segment t from HBM --------------------------
        @pl.when(jnp.logical_and(is_root, t < n_out))
        def _root_send():
            slot = lax.rem(t, two)
            blk = lax.rem(jnp.int32(root) + jnp.int32(1) + t // Cc,
                          jnp.int32(P))

            # deferred drain: consume THIS slot's t-2 send completion just
            # before overwriting it, keeping two sends in flight
            @pl.when(t >= two)
            def _drain_prev():
                _rdma(slot, slot).wait_send()

            ld = pltpu.make_async_copy(
                x_ref.at[blk, seg], buf.at[slot], load_sem)
            ld.start()
            ld.wait()

            @pl.when(t >= two)
            def _gate():
                pltpu.semaphore_wait(cap_sem, 1)

            _rdma(slot, slot).start()

        # ---- non-root: receive in-segment t, keep or forward ------------
        @pl.when(jnp.logical_and(jnp.logical_not(is_root), t < n_in))
        def _recv():
            slot = lax.rem(t, two)
            _rdma(slot, slot).wait_recv()

            @pl.when(t < Cc)
            def _keep():
                st = pltpu.make_async_copy(
                    buf.at[slot], o_ref.at[seg], store_sem)
                st.start()
                st.wait()

            @pl.when(t >= Cc)
            def _forward():
                u = t - Cc           # receiver's incoming index
                dslot = lax.rem(u, two)

                @pl.when(u >= two)
                def _gate():
                    pltpu.semaphore_wait(cap_sem, 1)

                _rdma(slot, dslot).start()
                _rdma(slot, dslot).wait_send()

            @pl.when(t + two < n_in)
            def _grant():
                pltpu.semaphore_signal(
                    cap_sem, inc=1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)

        return 0

    lax.fori_loop(0, C * (P - 1), step, 0)

    # epilogue: the root's final (up to two) sends are still undrained —
    # the last two out-segments sit in different slots
    @pl.when(is_root)
    def _epilogue():
        _rdma(0, 0).wait_send()
        if C * (P - 1) >= 2:
            _rdma(1, 1).wait_send()


def _chunked_scatter_call(x, *, P: int, C: int, sr: int, dtype, root: int):
    return pl.pallas_call(
        functools.partial(_chunked_scatter_kernel, P=P, C=C, root=root),
        out_shape=jax.ShapeDtypeStruct((C, sr, _LANES), dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, sr, _LANES), dtype),      # buf (2 slots)
            pltpu.SemaphoreType.DMA((2,)),           # send_sem (per slot)
            pltpu.SemaphoreType.DMA((2,)),           # recv_sem
            pltpu.SemaphoreType.DMA,                 # load_sem
            pltpu.SemaphoreType.DMA,                 # store_sem
            pltpu.SemaphoreType.REGULAR,             # cap_sem
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=6),
        interpret=_interpret_params(),
    )(x)


# ---------------------------------------------------------------------------
# segmented ring alltoall
# ---------------------------------------------------------------------------

def _chunked_alltoall_kernel(x_ref, o_ref, bounce, send_buf, recv_buf,
                             send_sem, recv_sem, load_sem, store_sem,
                             cap_sem, *, P: int, C: int):
    """x_ref: (P, C, Sr, 128) chunks by DESTINATION rank in HBM;
    o_ref: (P, C, Sr, 128) by SOURCE rank; bounce: (2, C, Sr, 128) HBM
    ping-pong scratch for multi-hop relays (the wrapper discards it).

    Segmented ring alltoall — beyond the reference, whose eager alltoall
    is itself unimplemented (``ccl_offload_control.c:2123-2218`` raises
    COLLECTIVE_NOT_IMPLEMENTED on the eager path). Phase ``s`` (1..P-1)
    rotates every rank's distance-``s`` chunk ``s`` hops right, one
    uniform single-hop shift of C segments at a time, store-and-forward
    through the bounce buffer. Per-link traffic is C * P(P-1)/2 segment
    times — the unidirectional-ring lower bound (every link carries a
    segment at every step of every phase).

    The step schedule is UNIFORM (no role masks): at global step
    ``g = C*s(s-1)/2 + h*C + c`` every rank sends segment c of hop h of
    phase s and receives its counterpart. One global credit chain spans
    all hops and phases: slots index by g parity, every send from g >= 2
    gates on a credit, and every recv grants one after its flush lands —
    so a fast sender cannot overwrite a neighbor's slot that still holds
    the PREVIOUS hop's tail segments (the cross-hop hazard a per-hop
    credit reset would reintroduce).
    """
    my, left, right = _neighbors(P)
    _ring_barrier(left, right)
    Cc = jnp.int32(C)
    two = jnp.int32(2)
    N = C * (P * (P - 1) // 2)  # total steps

    def _rdma(slot):
        return pltpu.make_async_remote_copy(
            src_ref=send_buf.at[slot],
            dst_ref=recv_buf.at[slot],
            send_sem=send_sem,
            recv_sem=recv_sem.at[slot],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )

    for s in range(1, P):           # phase: chunks travelling s hops
        base = C * (s * (s - 1) // 2)
        src_rank = lax.rem(my + jnp.int32(s), jnp.int32(P))
        dst_slot_rank = lax.rem(my - jnp.int32(s) + jnp.int32(P),
                                jnp.int32(P))

        def hop(h, _, s=s, base=base, src_rank=src_rank,
                dst_slot_rank=dst_slot_rank):
            # loop indices arrive as int64 under x64 on the interpret rung
            h = jnp.int32(h)
            first, last = h == 0, h == jnp.int32(s - 1)

            def step(c, _):
                c = jnp.int32(c)
                g = jnp.int32(base) + h * Cc + c
                slot = lax.rem(g, two)

                # fill the send slot (hop 0 from the input chunk, later
                # hops from the bounce written by the previous hop's recv)
                @pl.when(first)
                def _ld_x():
                    d = pltpu.make_async_copy(
                        x_ref.at[src_rank, c], send_buf.at[slot], load_sem)
                    d.start()
                    d.wait()

                @pl.when(jnp.logical_not(first))
                def _ld_bounce():
                    d = pltpu.make_async_copy(
                        bounce.at[lax.rem(h, two), c], send_buf.at[slot],
                        load_sem)
                    d.start()
                    d.wait()

                @pl.when(g >= two)
                def _gate():
                    pltpu.semaphore_wait(cap_sem, 1)

                _rdma(slot).start()

                # receive the counterpart and flush it (final hop: to its
                # output slot by source rank; else: to the bounce the
                # NEXT hop's sends will read)
                _rdma(slot).wait_recv()

                @pl.when(last)
                def _st_out():
                    st = pltpu.make_async_copy(
                        recv_buf.at[slot], o_ref.at[dst_slot_rank, c],
                        store_sem)
                    st.start()
                    st.wait()

                @pl.when(jnp.logical_not(last))
                def _st_bounce():
                    st = pltpu.make_async_copy(
                        recv_buf.at[slot],
                        bounce.at[lax.rem(h + jnp.int32(1), two), c],
                        store_sem)
                    st.start()
                    st.wait()

                @pl.when(g + two < jnp.int32(N))
                def _grant():
                    pltpu.semaphore_signal(
                        cap_sem, inc=1, device_id=left,
                        device_id_type=pltpu.DeviceIdType.LOGICAL)

                _rdma(slot).wait_send()
                return 0

            lax.fori_loop(0, C, step, 0)
            return 0

        lax.fori_loop(0, s, hop, 0)


def _chunked_alltoall_call(x, *, P: int, C: int, sr: int, dtype):
    out = pl.pallas_call(
        functools.partial(_chunked_alltoall_kernel, P=P, C=C),
        out_shape=(jax.ShapeDtypeStruct((P, C, sr, _LANES), dtype),
                   jax.ShapeDtypeStruct((2, C, sr, _LANES), dtype)),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)),
        scratch_shapes=[
            pltpu.VMEM((2, sr, _LANES), dtype),      # send_buf
            pltpu.VMEM((2, sr, _LANES), dtype),      # recv_buf
            pltpu.SemaphoreType.DMA,                 # send_sem
            pltpu.SemaphoreType.DMA((2,)),           # recv_sem
            pltpu.SemaphoreType.DMA,                 # load_sem
            pltpu.SemaphoreType.DMA,                 # store_sem
            pltpu.SemaphoreType.REGULAR,             # cap_sem
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=7),
        interpret=_interpret_params(),
    )(x)
    return out[0]  # bounce scratch discarded


# ---------------------------------------------------------------------------
# segmented ring-relay gather
# ---------------------------------------------------------------------------

def _chunked_gather_kernel(x_ref, o_ref, send_buf, recv_buf, send_sem,
                           recv_sem, load_sem, store_sem, cap_sem, *,
                           P: int, C: int, root: int):
    """x_ref: (C, Sr, 128) own block in HBM; o_ref: (P, C, Sr, 128) HBM.

    Ring-relay gather — the HBM-scale analog of the firmware's eager
    gather relay (``ccl_offload_control.c:1207-1295``): every rank sends
    its own block first, then relays the blocks arriving from upstream,
    store-and-forward through its own o_ref (the rx-buffer memory analog;
    non-root o_ref is scratch, masked off by the wrapper).

    With ``pos = (my - root) % P``, blocks flow toward the root in +1
    ring direction: rank pos sends ``pos`` blocks (own, then pos-1
    relays, FIFO) and receives ``pos - 1`` (the root: P-1). The t-th
    outgoing segment is own segment ``t`` for ``t < C``, else the segment
    received at step ``t - C`` reloaded from o_ref. Two VMEM slots per
    direction alternate on step parity; credit semaphores gate slot reuse
    (grants == gates, every semaphore drains to zero).
    """
    my, left, right = _neighbors(P)
    _ring_barrier(left, right)
    pos = lax.rem(my - jnp.int32(root) + jnp.int32(P), jnp.int32(P))
    is_root = pos == 0
    Cc = jnp.int32(C)
    n_send = pos * Cc                      # root: 0
    n_recv = jnp.where(is_root, jnp.int32((P - 1) * C), (pos - 1) * Cc)

    def blk_rank(i):
        """Global rank whose block is the i-th to arrive here (upstream
        neighbors in reverse-position order: pos-1, pos-2, ...)."""
        return lax.rem(my - jnp.int32(1) - i + jnp.int32(2 * P), jnp.int32(P))

    def _rdma(slot):
        return pltpu.make_async_remote_copy(
            src_ref=send_buf.at[slot],
            dst_ref=recv_buf.at[slot],
            send_sem=send_sem,
            recv_sem=recv_sem.at[slot],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )

    def step(t, _):
        t = jnp.int32(t)
        seg = lax.rem(t, Cc)
        slot = lax.rem(t, jnp.int32(2))
        send_m = t < n_send
        recv_m = t < n_recv

        @pl.when(send_m)
        def _send():
            # fill the send slot (safe: its step t-2 send was drained by
            # wait_send): own segment from x_ref for the first C steps,
            # then relays — the segment received at step t - C, reloaded
            # from o_ref (its store was waited before the slot was granted)
            @pl.when(t < Cc)
            def _own():
                d = pltpu.make_async_copy(
                    x_ref.at[seg], send_buf.at[slot], load_sem)
                d.start()
                d.wait()

            @pl.when(t >= Cc)
            def _relay():
                i = t // Cc - jnp.int32(1)
                d = pltpu.make_async_copy(
                    o_ref.at[blk_rank(i), seg], send_buf.at[slot], load_sem)
                d.start()
                d.wait()

            # credit gate: the right neighbor must have consumed this
            # slot's step t-2 content before we overwrite its recv slot
            @pl.when(t >= 2)
            def _gate():
                pltpu.semaphore_wait(cap_sem, 1)

            _rdma(slot).start()

        @pl.when(recv_m)
        def _recv():
            _rdma(slot).wait_recv()
            i = t // Cc
            st = pltpu.make_async_copy(
                recv_buf.at[slot], o_ref.at[blk_rank(i), seg],
                store_sem.at[slot])
            st.start()
            # the flush must land before the slot is granted back (the
            # relay reload at step t + C reads it from o_ref) — the wait
            # costs ~segment HBM-write time, well under the hop time
            st.wait()

            @pl.when(t + 2 < n_recv)
            def _grant():
                pltpu.semaphore_signal(
                    cap_sem, inc=1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)

        @pl.when(send_m)
        def _drain():
            _rdma(slot).wait_send()

        return 0

    lax.fori_loop(0, C * (P - 1), step, 0)


def _chunked_gather_call(x, *, P: int, C: int, sr: int, dtype, root: int):
    return pl.pallas_call(
        functools.partial(_chunked_gather_kernel, P=P, C=C, root=root),
        out_shape=jax.ShapeDtypeStruct((P, C, sr, _LANES), dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, sr, _LANES), dtype),      # send_buf (2 slots)
            pltpu.VMEM((2, sr, _LANES), dtype),      # recv_buf (2 slots)
            pltpu.SemaphoreType.DMA,                 # send_sem
            pltpu.SemaphoreType.DMA((2,)),           # recv_sem
            pltpu.SemaphoreType.DMA,                 # load_sem
            pltpu.SemaphoreType.DMA((2,)),           # store_sem
            pltpu.SemaphoreType.REGULAR,             # cap_sem
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=5),
        interpret=_interpret_params(),
    )(x)


# ---------------------------------------------------------------------------
# geometry + builders
# ---------------------------------------------------------------------------

def _geometry(chunk_elems: int, dtype, segment_bytes: int):
    """Segments per chunk and rows per segment for a given payload."""
    sr = _seg_rows(min(segment_bytes, VMEM_SEGMENT_CAP), dtype)
    seg_elems = sr * _LANES
    C = max(-(-chunk_elems // seg_elems), 1)
    return C, sr, seg_elems


def chunked_rs_body(x, *, P: int, func: reduceFunction, dtype,
                    segment_bytes: int, wire=None,
                    bidirectional: bool = False):
    """Per-rank shard_map body: (1, world*n) -> (1, n) (HBM-scale).
    ``wire`` compresses every remote hop (see _chunked_rs_kernel).
    ``bidirectional`` runs segment parities on counter-rotating rings;
    the final single-hop realignment then goes one hop in each
    direction (even segments came to own chunk (my+1), odd to
    (my-1))."""
    total = x.shape[-1]
    n = total // P
    if P == 1:
        # the kernel's hop loop is empty at world=1 and its epilogue would
        # wait on a store that is never issued — short-circuit
        return x[:, :n].astype(dtype).astype(x.dtype)
    C, sr, seg_elems = _geometry(n, dtype, segment_bytes)
    padded = jnp.zeros((P, C * seg_elems), dtype)
    padded = lax.dynamic_update_slice(
        padded, x.reshape(P, n).astype(dtype), (0, 0))
    chunks = padded.reshape(P, C, sr, _LANES)
    out = _chunked_rs_call(chunks, P=P, C=C, sr=sr, func=func, dtype=dtype,
                           wire=wire, bidirectional=bidirectional)
    fwd = [(i, (i + 1) % P) for i in range(P)]
    if bidirectional:
        segs = out.reshape(C, seg_elems)
        segs = segs.at[0::2].set(lax.ppermute(segs[0::2], AXIS, fwd))
        if C > 1:  # odd channel exists only with >= 2 segments
            segs = segs.at[1::2].set(lax.ppermute(
                segs[1::2], AXIS, [(i, (i - 1 + P) % P) for i in range(P)]))
        mine = segs.reshape(-1)[:n]
    else:
        mine = lax.ppermute(out.reshape(-1)[:n], AXIS, fwd)
    return mine.reshape(1, n).astype(x.dtype)


def chunked_ag_body(x, *, P: int, dtype, segment_bytes: int,
                    bidirectional: bool = False):
    """Per-rank shard_map body: (1, n) -> (1, world*n) (HBM-scale). The
    output layout is direction-independent — each block's odd segments
    just arrive via the opposite ring when ``bidirectional``."""
    n = x.shape[-1]
    if P == 1:
        return x
    C, sr, seg_elems = _geometry(n, dtype, segment_bytes)
    padded = jnp.zeros((C * seg_elems,), dtype)
    padded = lax.dynamic_update_slice(padded, x[0].astype(dtype), (0,))
    out = _chunked_ag_call(
        padded.reshape(C, sr, _LANES), P=P, C=C, sr=sr, dtype=dtype,
        bidirectional=bidirectional)
    return (out.reshape(P, C * seg_elems)[:, :n]
            .reshape(1, P * n).astype(x.dtype))


def chunked_ar_body(x, *, P: int, func: reduceFunction, dtype,
                    segment_bytes: int, wire=None, ag_wire=None,
                    bidirectional: bool = False):
    """Per-rank shard_map body: (1, n) -> (1, n); segmented ring RS + ring
    AG composition (fw ``:1888-2071`` analog). ``wire`` compresses the RS
    hops (fold at full precision); ``ag_wire`` the AG hops (pure
    transport). ``bidirectional`` runs both phases on counter-rotating
    per-parity rings; the final reorder then rolls even segments by +1
    and odd by -1 along the source-rank axis (rank r's partial holds
    chunk (r+1)'s even and chunk (r-1)'s odd segments)."""
    n = x.shape[-1]
    if P == 1:
        return x
    chunk = -(-n // P)
    C, sr, seg_elems = _geometry(chunk, dtype, segment_bytes)
    per = C * seg_elems
    chunks = _pack_chunks(x[0], P=P, chunk=chunk, C=C, sr=sr,
                          seg_elems=seg_elems, dtype=dtype)

    partial = _chunked_rs_call(chunks, P=P, C=C, sr=sr, func=func,
                               dtype=dtype, wire=wire,
                               bidirectional=bidirectional)
    if ag_wire is not None and ag_wire[0] != dtype:
        # compress once for the gather ring (no arithmetic remains)
        gathered = _chunked_ag_call(
            _pr._to_wire(partial, ag_wire), P=P, C=C, sr=sr,
            dtype=ag_wire[0], bidirectional=bidirectional)
        gathered = _pr._from_wire(gathered, dtype, ag_wire)
    else:
        gathered = _chunked_ag_call(partial, P=P, C=C, sr=sr, dtype=dtype,
                                    bidirectional=bidirectional)
    if bidirectional:
        # reorder with each segment kept in its (sr, lanes) tiling:
        # flattening segments first made the TPU compiler take ~17 s at
        # 64 MiB per rank and ~200 s at 1 GiB for the relayouts
        segs = gathered.reshape(P, C, sr, _LANES)
        segs = segs.at[:, 0::2].set(jnp.roll(segs[:, 0::2], 1, axis=0))
        if C > 1:
            segs = segs.at[:, 1::2].set(jnp.roll(segs[:, 1::2], -1, axis=0))
        blocks = segs.reshape(P, per)[:, :chunk]
        return blocks.reshape(-1)[:n].astype(x.dtype).reshape(1, n)
    # slot j holds folded chunk (j+1)%P; roll so slot c holds chunk c
    blocks = gathered.reshape(P, per)[:, :chunk]
    ordered = jnp.roll(blocks, shift=1, axis=0)
    return ordered.reshape(-1)[:n].astype(x.dtype).reshape(1, n)


def chunked_bcast_body(x, *, P: int, root: int, dtype, segment_bytes: int,
                       wire=None):
    """Per-rank shard_map body: (1, n) -> (1, n) (HBM-scale). ``wire``
    runs the whole ring in the wire dtype (pure transport — every hop
    carries compressed payload); the root's own copy stays exact."""
    n = x.shape[-1]
    if P == 1:
        return x
    kdt = wire[0] if wire is not None else dtype
    xin = (_pr._to_wire(x[0], wire) if wire is not None
           else x[0].astype(dtype))
    C, sr, seg_elems = _geometry(n, kdt, segment_bytes)
    padded = jnp.zeros((C * seg_elems,), kdt)
    padded = lax.dynamic_update_slice(padded, xin, (0,))
    out = _chunked_bcast_call(
        padded.reshape(C, sr, _LANES), P=P, C=C, sr=sr, dtype=kdt, root=root)
    flat = out.reshape(-1)[:n]
    res = (_pr._from_wire(flat, dtype, wire) if wire is not None
           else flat).astype(x.dtype)
    # the root's o_ref is never written (it is the source); keep its input
    res = jnp.where(lax.axis_index(AXIS) == root, x[0], res)
    return res.reshape(1, n)


def build_chunked_ring_bcast(comm: Communicator, root: int, dt: dataType,
                             segment_bytes: int, arith=None) -> Callable:
    """(world, n) sharded in -> (world, n) sharded out (HBM-scale):
    pipelined ring broadcast, the segmented analog of the firmware's
    eager bcast fanout (``ccl_offload_control.c:923-989``). A compressing
    ``arith`` compresses every hop (pure transport)."""
    _pr._check_multiprocess(comm)
    segment_bytes = segment_bytes or DEFAULT_SEGMENT_SIZE
    P = comm.world_size
    dtype = to_jax_dtype(dt)
    compressing = arith is not None and arith.is_compressing
    wire = ((to_jax_dtype(arith.compressed), arith.quant_scale)
            if compressing else None)

    def body(x):
        return chunked_bcast_body(x, P=P, root=root, dtype=dtype,
                                  segment_bytes=segment_bytes, wire=wire)

    return _smap(comm, body, 1)


def chunked_scatter_body(x, *, P: int, root: int, dtype,
                         segment_bytes: int, wire=None):
    """Per-rank shard_map body: (1, world*n) -> (1, n) (HBM-scale).
    ``wire`` runs every hop in the wire dtype (pure transport); the
    root's own chunk never rides the wire and stays exact."""
    total = x.shape[-1]
    n = total // P
    if P == 1:
        return x[:, :n]
    kdt = wire[0] if wire is not None else dtype
    xin = x.reshape(P, n)
    wired = (_pr._to_wire(xin, wire) if wire is not None
             else xin.astype(dtype))
    C, sr, seg_elems = _geometry(n, kdt, segment_bytes)
    per = C * seg_elems
    grid = jnp.zeros((P, per), kdt)
    grid = lax.dynamic_update_slice(grid, wired, (0, 0))
    out = _chunked_scatter_call(
        grid.reshape(P, C, sr, _LANES), P=P, C=C, sr=sr, dtype=kdt,
        root=root)
    mine = out.reshape(-1)[:n]
    mine = (_pr._from_wire(mine, dtype, wire) if wire is not None
            else mine).astype(x.dtype)
    # the root's o_ref is never written (it is the source); keep its chunk
    mine = jnp.where(lax.axis_index(AXIS) == root, xin[root], mine)
    return mine.reshape(1, n)


def build_chunked_ring_scatter(comm: Communicator, root: int, dt: dataType,
                               segment_bytes: int, arith=None) -> Callable:
    """(world, world*n) sharded in -> (world, n) sharded out (HBM-scale):
    ring-relay scatter, the segmented analog of the firmware's eager
    scatter fanout (``ccl_offload_control.c:1082-1124``). A compressing
    ``arith`` compresses every hop (pure transport)."""
    _pr._check_multiprocess(comm)
    segment_bytes = segment_bytes or DEFAULT_SEGMENT_SIZE
    P = comm.world_size
    dtype = to_jax_dtype(dt)
    compressing = arith is not None and arith.is_compressing
    wire = ((to_jax_dtype(arith.compressed), arith.quant_scale)
            if compressing else None)

    def body(x):
        return chunked_scatter_body(x, P=P, root=root, dtype=dtype,
                                    segment_bytes=segment_bytes, wire=wire)

    return _smap(comm, body, 1)


def chunked_alltoall_body(x, *, P: int, dtype, segment_bytes: int,
                          wire=None):
    """Per-rank shard_map body: (1, world*n) -> (1, world*n) (HBM-scale).
    Chunk d of the input goes to rank d; output slot s holds rank s's
    chunk for this rank. ``wire`` runs every hop in the wire dtype (pure
    transport); the rank's own chunk never rides the wire."""
    total = x.shape[-1]
    n = total // P
    if P == 1:
        return x
    kdt = wire[0] if wire is not None else dtype
    # chunks lanes-minor where they split into whole lanes: a (P, n)
    # view under the dynamic row index below costs the TPU compiler
    # time linear in n (primitives.lane_blocks)
    lanes = n % _LANES == 0
    xin = x.reshape((P, n // _LANES, _LANES) if lanes else (P, n))
    wired = (_pr._to_wire(xin, wire) if wire is not None
             else xin.astype(dtype))
    C, sr, seg_elems = _geometry(n, kdt, segment_bytes)
    per = C * seg_elems
    grid = jnp.zeros((P, C * sr, _LANES) if lanes else (P, per), kdt)
    grid = lax.dynamic_update_slice(grid, wired, (0,) * grid.ndim)
    out = _chunked_alltoall_call(
        grid.reshape(P, C, sr, _LANES), P=P, C=C, sr=sr, dtype=kdt)
    blocks = (out.reshape(P, C * sr, _LANES)[:, :n // _LANES] if lanes
              else out.reshape(P, per)[:, :n])
    blocks = (_pr._from_wire(blocks, dtype, wire) if wire is not None
              else blocks).astype(x.dtype)
    # own chunk stays local (never on the wire; o_ref[my] is unwritten)
    rank = lax.axis_index(AXIS)
    mine = lax.dynamic_index_in_dim(xin, rank, axis=0, keepdims=False)
    blocks = lax.dynamic_update_index_in_dim(
        blocks, mine.astype(x.dtype), rank, axis=0)
    return blocks.reshape(1, P * n)


def build_chunked_ring_alltoall(comm: Communicator, dt: dataType,
                                segment_bytes: int, arith=None) -> Callable:
    """(world, world*n) sharded in -> (world, world*n) sharded out
    (HBM-scale): phased ring-rotation alltoall. The reference's eager
    alltoall is unimplemented (COLLECTIVE_NOT_IMPLEMENTED) — this path
    goes beyond it. A compressing ``arith`` compresses every hop."""
    _pr._check_multiprocess(comm)
    segment_bytes = segment_bytes or DEFAULT_SEGMENT_SIZE
    P = comm.world_size
    dtype = to_jax_dtype(dt)
    compressing = arith is not None and arith.is_compressing
    wire = ((to_jax_dtype(arith.compressed), arith.quant_scale)
            if compressing else None)

    def body(x):
        return chunked_alltoall_body(x, P=P, dtype=dtype,
                                     segment_bytes=segment_bytes, wire=wire)

    return _smap(comm, body, 1)


def chunked_gather_body(x, dest, *, P: int, root: int, dtype,
                        segment_bytes: int, wire=None):
    """Per-rank shard_map body: (1, n), (1, world*n) -> (1, world*n);
    non-root outputs pass through unchanged (reference recvbuf
    semantics). ``wire`` runs every relay hop in the wire dtype; the
    root's own block stays exact."""
    n = x.shape[-1]
    rank = lax.axis_index(AXIS)
    if P == 1:
        return jnp.where(rank == root, x, dest)
    kdt = wire[0] if wire is not None else dtype
    xin = (_pr._to_wire(x[0], wire) if wire is not None
           else x[0].astype(dtype))
    C, sr, seg_elems = _geometry(n, kdt, segment_bytes)
    padded = jnp.zeros((C * seg_elems,), kdt)
    padded = lax.dynamic_update_slice(padded, xin, (0,))
    out = _chunked_gather_call(
        padded.reshape(C, sr, _LANES), P=P, C=C, sr=sr, dtype=kdt, root=root)
    flat = out.reshape(P, C * seg_elems)[:, :n]
    flat = (_pr._from_wire(flat, dtype, wire) if wire is not None
            else flat).astype(x.dtype)
    flat = flat.at[root].set(x[0])  # own block, exact (never on the wire)
    return jnp.where(rank == root, flat.reshape(1, P * n), dest)


def build_chunked_ring_gather(comm: Communicator, root: int, dt: dataType,
                              segment_bytes: int, arith=None) -> Callable:
    """(world, n), (world, world*n) sharded in -> (world, world*n) out
    (HBM-scale): ring-relay gather, the segmented analog of the
    firmware's eager gather relay (``ccl_offload_control.c:1207-1295``).
    A compressing ``arith`` compresses every hop (pure transport)."""
    _pr._check_multiprocess(comm)
    segment_bytes = segment_bytes or DEFAULT_SEGMENT_SIZE
    P = comm.world_size
    dtype = to_jax_dtype(dt)
    compressing = arith is not None and arith.is_compressing
    wire = ((to_jax_dtype(arith.compressed), arith.quant_scale)
            if compressing else None)

    def body(x, dest):
        return chunked_gather_body(x, dest, P=P, root=root, dtype=dtype,
                                   segment_bytes=segment_bytes, wire=wire)

    return _smap(comm, body, 2)


def build_chunked_ring_reduce_scatter(comm: Communicator,
                                      func: reduceFunction, dt: dataType,
                                      segment_bytes: int,
                                      arith=None,
                                      bidirectional: bool = False) -> Callable:
    """(world, world*n) sharded in -> (world, n) sharded out (HBM-scale).
    A compressing ``arith`` applies the per-hop wire lanes (see
    _chunked_rs_kernel)."""
    _pr._check_multiprocess(comm)
    P = comm.world_size
    dtype = to_jax_dtype(dt)
    kdtype, wire, pre, post = _pr._wire_policy(arith, dtype)

    def body(x):
        out = chunked_rs_body(pre(x), P=P, func=func, dtype=kdtype,
                              segment_bytes=segment_bytes, wire=wire,
                              bidirectional=bidirectional)
        return post(out, x.dtype)

    return _smap(comm, body, 1)


def build_chunked_ring_allgather(comm: Communicator, dt: dataType,
                                 segment_bytes: int,
                                 arith=None,
                                 bidirectional: bool = False) -> Callable:
    """(world, n) sharded in -> (world, world*n) sharded out (HBM-scale).
    A compressing ``arith`` runs the whole ring in the wire dtype (pure
    transport — every hop carries compressed payload)."""
    _pr._check_multiprocess(comm)
    P = comm.world_size
    dtype = to_jax_dtype(dt)
    compressing = arith is not None and arith.is_compressing
    if compressing:
        wire = (to_jax_dtype(arith.compressed), arith.quant_scale)

    def body(x):
        out_dtype = x.dtype
        if compressing:
            x = _pr._to_wire(x, wire)
            out = chunked_ag_body(x, P=P, dtype=wire[0],
                                  segment_bytes=segment_bytes,
                                  bidirectional=bidirectional)
            return _pr._from_wire(out, out_dtype, wire).astype(out_dtype)
        return chunked_ag_body(x, P=P, dtype=dtype,
                               segment_bytes=segment_bytes,
                               bidirectional=bidirectional)

    return _smap(comm, body, 1)


def _pack_chunks(vec, *, P: int, chunk: int, C: int, sr: int,
                 seg_elems: int, dtype):
    """Stride-pad a flat per-rank payload into the kernels' uniform
    (P, C, Sr, 128) chunk grid: chunk p occupies the first ``chunk``
    elements of row p's C*seg_elems stride (so segment geometry is
    identical across chunks). Shared by the allreduce and reduce
    compositions — the packing and the (my+1)%P chunk-ownership roll
    must stay in lockstep with the RS kernel's ring schedule."""
    per = C * seg_elems
    grid = jnp.zeros((P, per), dtype)
    src = jnp.zeros((P * chunk,), dtype)
    src = lax.dynamic_update_slice(src, vec.astype(dtype), (0,))
    grid = lax.dynamic_update_slice(grid, src.reshape(P, chunk), (0, 0))
    return grid.reshape(P, C, sr, _LANES)


def chunked_reduce_body(x, dest, *, P: int, root: int,
                        func: reduceFunction, dtype, segment_bytes: int,
                        wire=None, gather_wire=None):
    """Per-rank shard_map body: (1, n), (1, n) -> (1, n); segmented ring
    reduce-scatter + ring-relay gather-to-root composition (the firmware
    composes reduce from the same parts, ``ccl_offload_control.c:
    1768-1781`` reduce-then-scatter / ``:1878-1887`` reduce-then-bcast
    stance). ``wire`` compresses the RS hops (fold at full precision);
    ``gather_wire`` the relay hops (pure transport)."""
    n = x.shape[-1]
    rank = lax.axis_index(AXIS)
    if P == 1:
        return jnp.where(rank == root, x, dest)
    chunk = -(-n // P)
    C, sr, seg_elems = _geometry(chunk, dtype, segment_bytes)
    grid = _pack_chunks(x[0], P=P, chunk=chunk, C=C, sr=sr,
                        seg_elems=seg_elems, dtype=dtype)
    partial = _chunked_rs_call(grid, P=P, C=C, sr=sr, func=func,
                               dtype=dtype, wire=wire)
    # the RS output already has the gather kernel's exact (C, Sr, 128)
    # geometry — feed it straight in, no repack round trip
    if gather_wire is not None:
        gath = _chunked_gather_call(
            _pr._to_wire(partial, gather_wire), P=P, C=C, sr=sr,
            dtype=gather_wire[0], root=root)
        gath = _pr._from_wire(gath, dtype, gather_wire)
    else:
        gath = _chunked_gather_call(partial, P=P, C=C, sr=sr, dtype=dtype,
                                    root=root)
    per = C * seg_elems
    blocks = gath.reshape(P, per)[:, :chunk]  # indexed by SOURCE rank
    # the relay never transfers the root's own contribution: insert its
    # partial at full precision (it never rides the wire)
    blocks = blocks.at[root].set(partial.reshape(-1)[:chunk])
    # source rank r contributed folded chunk (r+1)%P; roll so slot c
    # holds chunk c
    ordered = jnp.roll(blocks, shift=1, axis=0).reshape(-1)[:n]
    return jnp.where(rank == root, ordered.reshape(1, n), dest)


def build_chunked_ring_reduce(comm: Communicator, root: int,
                              func: reduceFunction, dt: dataType,
                              segment_bytes: int, arith=None) -> Callable:
    """(world, n), (world, n) sharded in -> (world, n) out (HBM-scale):
    chunked RS + relay gather composition; non-root outputs pass through
    unchanged. A compressing ``arith`` compresses every hop of both
    phases."""
    _pr._check_multiprocess(comm)
    segment_bytes = segment_bytes or DEFAULT_SEGMENT_SIZE
    P = comm.world_size
    dtype = to_jax_dtype(dt)
    kdtype, wire, pre, post = _pr._wire_policy(arith, dtype)
    compressing = arith is not None and arith.is_compressing
    # same-dtype guard as the allreduce composition: when the whole
    # kernel already runs in the wire dtype (arith_is_compressed pairs),
    # compressing the gather phase again would double-apply a quantized
    # scale
    gather_wire = ((to_jax_dtype(arith.compressed), arith.quant_scale)
                   if compressing and to_jax_dtype(arith.compressed) != kdtype
                   else None)

    def body(x, dest):
        out = chunked_reduce_body(pre(x), dest, P=P, root=root, func=func,
                                  dtype=kdtype, segment_bytes=segment_bytes,
                                  wire=wire, gather_wire=gather_wire)
        return post(out, x.dtype)

    return _smap(comm, body, 2)


def build_chunked_ring_allreduce(comm: Communicator, func: reduceFunction,
                                 dt: dataType,
                                 segment_bytes: int,
                                 arith=None,
                                 bidirectional: bool = False) -> Callable:
    """Segmented ring RS + ring AG composition (fw ``:1888-2071`` analog).
    A compressing ``arith`` compresses every hop of both phases."""
    _pr._check_multiprocess(comm)
    P = comm.world_size
    dtype = to_jax_dtype(dt)
    kdtype, wire, pre, post = _pr._wire_policy(arith, dtype)
    compressing = arith is not None and arith.is_compressing
    ag_wire = ((to_jax_dtype(arith.compressed), arith.quant_scale)
               if compressing else None)

    def body(x):
        out = chunked_ar_body(pre(x), P=P, func=func, dtype=kdtype,
                              segment_bytes=segment_bytes, wire=wire,
                              ag_wire=ag_wire, bidirectional=bidirectional)
        return post(out, x.dtype)

    return _smap(comm, body, 1)
