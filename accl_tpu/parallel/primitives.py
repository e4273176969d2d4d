"""XLA-delegating collective programs (collectives v1, SURVEY.md §7 stage 3).

Every collective is a ``shard_map`` program over a global ``(world, n)``
array sharded one-shard-per-rank on the communicator's mesh axis; the body
uses XLA's native collectives (``psum``/``pmax``/``all_gather``/
``psum_scatter``/``all_to_all``/``ppermute``), which XLA lowers onto ICI
with its own fused schedules — this is the fastest path on real hardware and
plays the role of the reference's rendezvous single-move fast path. The
explicit ring/tree/flat algorithm variants live in sibling modules.

Per-operand semantics follow the reference host API (``driver/xrt/src/
accl.cpp``): e.g. ``gather`` only defines the result at the root — non-root
result shards pass through unchanged, matching "recvbuf untouched on
non-root ranks".

Wire compression (``compressionFlags.ETH_COMPRESSED``) is modeled by casting
the payload to the wire dtype before the collective and back after — the TPU
analog of compressing in front of the packetizer only
(``hp_compression.cpp``); reductions happen in the wire dtype when the arith
config says so (``ArithConfig.arith_is_compressed``).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map

from ..arithconfig import ArithConfig
from ..communicator import Communicator
from ..constants import dataType, reduceFunction, to_jax_dtype
from .. import ops

AXIS = Communicator.AXIS

#: TPU vreg lane width
LANES = 128


def lane_blocks(t, s: int):
    """``t`` in flat order, viewed as ``s`` equal blocks for a per-block
    collective or dynamic index: ``(s, rows, LANES)`` when a block splits
    into whole lanes, else ``(s, per)``. Keep payloads lanes-minor: for
    a (small, huge) 2-D operand the TPU compiler takes time linear in
    the payload (about 20 s at 256 MiB per rank, 80 s at 1 GiB), and
    none for the 3-D view."""
    per = t.size // s
    if per % LANES == 0:
        return t.reshape(s, per // LANES, LANES)
    return t.reshape(s, per)


def _smap(comm: Communicator, fn, n_in: int, out_specs=None, in_specs=None):
    if in_specs is None:
        in_specs = tuple(P(AXIS) for _ in range(n_in))
    # check_vma=False: Pallas plugin kernels inside program bodies don't carry
    # varying-mesh-axis annotations; our programs manage replication manually.
    return jax.jit(
        shard_map(
            fn,
            mesh=comm.mesh,
            in_specs=in_specs if len(in_specs) > 1 else in_specs[0],
            out_specs=out_specs if out_specs is not None else P(AXIS),
            check_vma=False,
        )
    )


def _rank():
    return lax.axis_index(AXIS)


def _wire(x, arith: Optional[ArithConfig]):
    """Cast to the wire dtype before a network hop (compress lane)."""
    if arith is None or not arith.is_compressing:
        return x
    return ops.compress(x, arith.uncompressed, arith.compressed,
                        arith.quant_scale)


def _unwire(x, arith: Optional[ArithConfig], out_dtype):
    """Cast back after the network hop (decompress lane)."""
    if arith is None or not arith.is_compressing:
        return x.astype(out_dtype)
    return ops.decompress(x, arith.compressed, arith.uncompressed,
                          arith.quant_scale).astype(out_dtype)


# --------------------------------------------------------------------------
# local primitives (no network)
# --------------------------------------------------------------------------

def build_copy(comm: Communicator) -> Callable:
    """``ACCL::copy`` (accl.cpp) — per-rank local device copy."""
    return _smap(comm, lambda x: x + 0, 1)


def build_combine(comm: Communicator, func: reduceFunction, dt: dataType,
                  use_pallas: bool = False, donate: bool = False) -> Callable:
    """``ACCL::combine`` — per-rank elementwise reduce of two operands.

    ``use_pallas`` routes through the explicit Pallas reduce_ops lane
    (standalone VMEM-tiled kernel, the plugin-architecture analog);
    otherwise the registry's fused jnp path. ``donate`` aliases the result
    onto operand 0 inside the Pallas lane so chained execution (fused
    loops, CommandList steps) updates in place — no loop-carry copy.
    """
    if use_pallas:
        from ..ops import reduce_ops

        if dt in reduce_ops.PALLAS_DTYPES:
            def body(a, b):
                return reduce_ops.pallas_combine(a, b, func, donate=donate)

            return _smap(comm, body, 2)

    def body(a, b):
        return ops.combine(a, b, func, dt)

    return _smap(comm, body, 2)


# --------------------------------------------------------------------------
# one-sided move (ppermute pair) — used by send/recv matching and put
# --------------------------------------------------------------------------

def build_move(comm: Communicator, src: int, dst: int) -> Callable:
    """Move rank ``src``'s shard into rank ``dst``'s shard of another buffer.

    The TPU analog of a single rendezvous RDMA WRITE to a remote address
    (``ccl_offload_control.c:604-612``): one ``ppermute`` with a single
    (src, dst) pair, result merged into the destination buffer's shard.
    """

    def body(x, dest):
        moved = lax.ppermute(x, AXIS, [(src, dst)])
        keep = (_rank() == dst)
        return jnp.where(keep, moved.astype(dest.dtype), dest)

    return _smap(comm, body, 2)


def build_move_at(comm: Communicator, src: int, dst: int) -> Callable:
    """Per-segment eager move: write ``src``'s segment into ``dst``'s shard
    of ``dest`` at element offset ``off``.

    The MOVE_STRIDE + MOVE_ON_RECV per-segment delivery of the firmware's
    eager recv loop (``ccl_offload_control.c:680-711``): each arriving
    segment lands in the destination buffer immediately, so a partially
    arrived message is progressively visible on device instead of being
    assembled in one move at completion. ``off`` is traced (one compiled
    program serves every offset; only distinct segment shapes retrace).
    """

    def body(seg, dest, off):
        moved = lax.ppermute(seg, AXIS, [(src, dst)])
        off = jnp.asarray(off, jnp.int32)
        upd = lax.dynamic_update_slice(
            dest, moved.astype(dest.dtype), (jnp.int32(0), off))
        keep = (_rank() == dst)
        return jnp.where(keep, upd, dest)

    return _smap(comm, body, 3, in_specs=(P(AXIS), P(AXIS), P()))


# --------------------------------------------------------------------------
# rooted collectives
# --------------------------------------------------------------------------

def build_bcast(comm: Communicator, root: int,
                arith: Optional[ArithConfig] = None) -> Callable:
    """Broadcast root's shard to all ranks (fw bcast, ccl_offload_control.c:798-990).

    Masked ``psum``: only the root contributes, so the sum *is* root's data —
    one collective, exact for floats (single non-zero term).
    """

    def body(x):
        contrib = jnp.where(_rank() == root, _wire(x, arith), jnp.zeros_like(_wire(x, arith)))
        out = lax.psum(contrib, AXIS)
        return _unwire(out, arith, x.dtype)

    return _smap(comm, body, 1)


def build_scatter(comm: Communicator, root: int,
                  arith: Optional[ArithConfig] = None) -> Callable:
    """Root's (world*count) buffer chunked across ranks (fw scatter :994-1125)."""
    world = comm.world_size

    def body(send):
        # send per-rank shape (1, world*count); only root's matters
        contrib = jnp.where(_rank() == root, _wire(send, arith),
                            jnp.zeros_like(_wire(send, arith)))
        full = lax.psum(contrib, AXIS)           # every rank: root's buffer
        chunks = full.reshape(1, world, -1)
        mine = lax.dynamic_index_in_dim(chunks, _rank(), axis=1)
        return _unwire(mine.reshape(1, -1), arith, send.dtype)

    return _smap(comm, body, 1)


def build_gather(comm: Communicator, root: int,
                 arith: Optional[ArithConfig] = None) -> Callable:
    """Concat all ranks' sends at the root; non-root result untouched
    (fw gather :1130-1296)."""

    def body(send, recv):
        g = lax.all_gather(_wire(send, arith), AXIS, axis=1, tiled=True)  # (1, world*count)
        g = _unwire(g, arith, recv.dtype)
        keep = (_rank() == root)
        return jnp.where(keep, g, recv)

    return _smap(comm, body, 2)


def build_reduce(comm: Communicator, root: int, func: reduceFunction,
                 dt: dataType, arith: Optional[ArithConfig] = None) -> Callable:
    """Elementwise reduce to the root; non-root result untouched
    (fw reduce :1509-1744)."""

    def body(send, recv):
        x = _wire(send, arith)
        if arith is not None and arith.decompress_before_arith:
            # casting pairs decompress before arithmetic (DEFAULT_ARITH_CONFIG):
            # gather wire-dtype payloads, then rank-ordered reduce at full
            # precision — matches the reference's decompress-then-accumulate.
            g = lax.all_gather(x, AXIS)                 # (world, 1, count)
            g = ops.decompress(g, arith.compressed, arith.uncompressed,
                               arith.quant_scale)
            red = ops.reduce_axis0(g, func, dt).astype(recv.dtype)
        else:
            if func == reduceFunction.SUM:
                red = lax.psum(x, AXIS)
            elif func == reduceFunction.MAX:
                red = lax.pmax(x, AXIS)
            else:
                raise ValueError(func)
            red = _unwire(red, arith, recv.dtype)
        keep = (_rank() == root)
        return jnp.where(keep, red, recv)

    return _smap(comm, body, 2)


# --------------------------------------------------------------------------
# rootless collectives
# --------------------------------------------------------------------------

def build_allgather(comm: Communicator,
                    arith: Optional[ArithConfig] = None) -> Callable:
    """fw allgather (:1299-1505)."""

    def body(send):
        g = lax.all_gather(_wire(send, arith), AXIS, axis=1, tiled=True)
        return _unwire(g, arith, send.dtype)

    return _smap(comm, body, 1)


def build_allreduce(comm: Communicator, func: reduceFunction, dt: dataType,
                    arith: Optional[ArithConfig] = None) -> Callable:
    """fw allreduce (:1855-2075) — XLA-native fast path."""

    def body(send):
        x = _wire(send, arith)
        if arith is not None and arith.decompress_before_arith:
            g = lax.all_gather(x, AXIS)
            g = ops.decompress(g, arith.compressed, arith.uncompressed,
                               arith.quant_scale)
            red = ops.reduce_axis0(g, func, dt)
            return red.astype(send.dtype)
        if func == reduceFunction.SUM:
            red = lax.psum(x, AXIS)
        elif func == reduceFunction.MAX:
            red = lax.pmax(x, AXIS)
        else:
            raise ValueError(func)
        return _unwire(red, arith, send.dtype)

    return _smap(comm, body, 1)


def build_reduce_scatter(comm: Communicator, func: reduceFunction, dt: dataType,
                         arith: Optional[ArithConfig] = None) -> Callable:
    """fw reduce_scatter (:1748-1852): in (world*count,) -> out (count,) per rank."""
    world = comm.world_size

    def body(send):
        x = _wire(send, arith)
        if func == reduceFunction.SUM and (
            arith is None or not arith.decompress_before_arith
        ):
            red = lax.psum_scatter(x, AXIS, scatter_dimension=1, tiled=True)
            return _unwire(red, arith, send.dtype)
        # general path (MAX, or decompress-before-arith): exchange chunks,
        # then rank-ordered local reduction — same dataflow as the reference's
        # ring with fused recv-reduce (:1782-1850).
        chunks = x.reshape(world, 1, -1)
        swapped = lax.all_to_all(chunks, AXIS, split_axis=0, concat_axis=0)
        if arith is not None and arith.is_compressing:
            swapped = ops.decompress(swapped, arith.compressed,
                                   arith.uncompressed, arith.quant_scale)
        red = ops.reduce_axis0(swapped, func, dt)
        return red.astype(send.dtype)

    return _smap(comm, body, 1)


def build_alltoall(comm: Communicator,
                   arith: Optional[ArithConfig] = None) -> Callable:
    """fw all-to-all (:2123-2218): chunk r of rank q lands at rank r slot q."""
    world = comm.world_size

    def body(send):
        x = _wire(send, arith).reshape(world, 1, -1)
        swapped = lax.all_to_all(x, AXIS, split_axis=0, concat_axis=0)
        out = swapped.reshape(1, -1)
        return _unwire(out, arith, send.dtype)

    return _smap(comm, body, 1)


def build_barrier(comm: Communicator) -> Callable:
    """fw barrier (:2078-2120): zero-byte notification exchange → scalar psum."""

    def body(x):
        return lax.psum(x, AXIS)

    return _smap(comm, body, 1, out_specs=P())
