"""Explicit ring collectives (collectives v2, SURVEY.md §2.6).

Re-expresses the reference firmware's ring algorithm family as ``ppermute``
step chains inside ``shard_map``:

* segmented ring reduce-scatter + ring allgather = bandwidth-optimal
  allreduce (``ccl_offload_control.c:1888-2071``),
* ring allgather with relay (``:1299-1505``),
* ring reduce-scatter with fused recv-reduce per chunk (``:1782-1850``),
* daisy-chain reduce with fused recv-reduce-send (``:1730-1743``).

Each ``ppermute`` hop is a neighbor exchange on the ring — on TPU this rides
a single ICI hop per step, the topology the reference's ring was designed
for (Ethernet ring ↔ ICI torus axis). Wire compression applies **per hop**
(compress → permute → decompress), which is the faithful analog of
``ETH_COMPRESSED`` (payload compressed on the network only,
``hp_compression.cpp``), unlike the single-shot XLA path which can only
compress end-to-end.

Reduction order is fixed by ring position — deterministic across runs, the
same guarantee the reference's fixed traversal order gives (bit-exact
reproducibility, not bit-equality with a host fold).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..arithconfig import ArithConfig
from ..communicator import Communicator
from ..constants import dataType, reduceFunction
from .. import ops
from .primitives import AXIS, LANES, _smap, lane_blocks

Array = jax.Array


def _fwd_perm(world: int):
    return [(i, (i + 1) % world) for i in range(world)]


def _hop(buf: Array, world: int, arith: Optional[ArithConfig],
         perm=None) -> Array:
    """One ring hop: compress -> ppermute (next rank unless ``perm``
    overrides the direction) -> decompress."""
    orig_dtype = buf.dtype
    if arith is not None and arith.is_compressing:
        buf = ops.compress(buf, arith.uncompressed, arith.compressed,
                           arith.quant_scale)
    moved = lax.ppermute(buf, AXIS, perm or _fwd_perm(world))
    if arith is not None and arith.is_compressing:
        moved = ops.decompress(moved, arith.compressed, arith.uncompressed,
                               arith.quant_scale)
        moved = moved.astype(orig_dtype)
    return moved


def build_ring_allreduce(
    comm: Communicator,
    func: reduceFunction,
    dt: dataType,
    arith: Optional[ArithConfig] = None,
) -> Callable:
    """Ring reduce-scatter + ring allgather (fw :1888-2071).

    2(P-1) ppermute steps moving n/P elements each — bandwidth-optimal:
    2n(P-1)/P bytes per link regardless of world size.
    """
    world = comm.world_size

    def body(x):
        n = x.shape[-1]
        # ceil, in whole lanes: chunks stay lanes-minor (lane_blocks)
        chunk = -(-n // (world * LANES)) * LANES
        padded = jnp.pad(x[0], (0, chunk * world - n))
        chunks = lane_blocks(padded, world)
        rank = lax.axis_index(AXIS)

        # phase 1: ring reduce-scatter — at step s rank r sends partial chunk
        # (r-s) and folds the received chunk (r-s-1) into its accumulator
        # (fused recv-reduce, fw fused_recv_reduce :718-751)
        def rs_step(s, ch):
            send_idx = jnp.mod(rank - s, world)
            buf = lax.dynamic_index_in_dim(ch, send_idx, axis=0, keepdims=False)
            moved = _hop(buf, world, arith)
            recv_idx = jnp.mod(rank - s - 1, world)
            cur = lax.dynamic_index_in_dim(ch, recv_idx, axis=0, keepdims=False)
            new = ops.combine(cur, moved, func, dt)
            return lax.dynamic_update_index_in_dim(ch, new, recv_idx, axis=0)

        chunks = lax.fori_loop(0, world - 1, rs_step, chunks)
        # rank r now owns fully-reduced chunk (r+1) mod P

        # phase 2: ring allgather — circulate the reduced chunks
        def ag_step(s, ch):
            send_idx = jnp.mod(rank + 1 - s, world)
            buf = lax.dynamic_index_in_dim(ch, send_idx, axis=0, keepdims=False)
            moved = _hop(buf, world, arith)
            recv_idx = jnp.mod(rank - s, world)
            return lax.dynamic_update_index_in_dim(ch, moved, recv_idx, axis=0)

        chunks = lax.fori_loop(0, world - 1, ag_step, chunks)
        return chunks.reshape(1, -1)[:, :n]

    return _smap(comm, body, 1)


def build_ring_allgather(comm: Communicator,
                         arith: Optional[ArithConfig] = None) -> Callable:
    """Ring allgather with relay (fw :1299-1505): P-1 hops, each rank
    forwards what it received last step."""
    world = comm.world_size

    def body(x):
        n = x.shape[-1]
        rank = lax.axis_index(AXIS)
        buf = lane_blocks(x[0], 1)[0]
        out = jnp.zeros((world,) + buf.shape, dtype=x.dtype)
        out = lax.dynamic_update_index_in_dim(out, buf, rank, axis=0)
        for s in range(world - 1):  # static: perm identical each step
            buf = _hop(buf, world, arith)
            src = jnp.mod(rank - s - 1, world)
            out = lax.dynamic_update_index_in_dim(out, buf, src, axis=0)
        return out.reshape(1, -1)

    return _smap(comm, body, 1)


def build_ring_reduce_scatter(
    comm: Communicator,
    func: reduceFunction,
    dt: dataType,
    arith: Optional[ArithConfig] = None,
) -> Callable:
    """Ring reduce-scatter with fused recv-reduce-forward per chunk
    (fw :1782-1850): input (world*count,) -> reduced chunk r at rank r."""
    world = comm.world_size

    def body(x):
        chunks = lane_blocks(x, world)
        rank = lax.axis_index(AXIS)

        def rs_step(s, ch):
            send_idx = jnp.mod(rank - s - 1, world)
            buf = lax.dynamic_index_in_dim(ch, send_idx, axis=0, keepdims=False)
            moved = _hop(buf, world, arith)
            recv_idx = jnp.mod(rank - s - 2, world)
            cur = lax.dynamic_index_in_dim(ch, recv_idx, axis=0, keepdims=False)
            new = ops.combine(cur, moved, func, dt)
            return lax.dynamic_update_index_in_dim(ch, new, recv_idx, axis=0)

        chunks = lax.fori_loop(0, world - 1, rs_step, chunks)
        # rank r now owns fully-reduced chunk r
        mine = lax.dynamic_index_in_dim(chunks, rank, axis=0, keepdims=False)
        return mine.reshape(1, -1)

    return _smap(comm, body, 1)


def build_ring_reduce(
    comm: Communicator,
    root: int,
    func: reduceFunction,
    dt: dataType,
    arith: Optional[ArithConfig] = None,
) -> Callable:
    """Daisy-chain reduce to the root with fused recv-reduce-send
    (fw eager reduce :1730-1743): the partial accumulates around the ring
    root+1 -> root+2 -> ... -> root. P-1 sequential full-message hops —
    latency-poor, bandwidth-simple; selectable for parity, not the default.
    """
    world = comm.world_size

    def body(send, recv):
        rank = lax.axis_index(AXIS)
        rel = jnp.mod(rank - root, world)
        acc = send[0]
        for s in range(world - 1):
            moved = _hop(acc, world, arith)
            # receiver this step: rel == s+2 (mod world); final step reaches root
            receiver_rel = (s + 2) % world
            is_receiver = rel == receiver_rel
            acc = jnp.where(is_receiver, ops.combine(moved, acc, func, dt), acc)
        out = jnp.where(rel == 0, acc.astype(recv.dtype), recv[0])
        return out[None, :]

    return _smap(comm, body, 2)


def build_ring_gather(comm: Communicator, root: int,
                      arith: Optional[ArithConfig] = None) -> Callable:
    """Ring-relay gather (fw eager gather :1207-1295): every rank sends its
    own block then relays ``distance-to-root - 1`` further blocks toward
    the root, which stores one arriving block per step. P-1 hops on
    neighbor links only — no long edges, unlike the flat star. Non-root
    outputs pass through unchanged (reference recvbuf semantics)."""
    world = comm.world_size

    def body(x, dest):
        rank = lax.axis_index(AXIS)
        n = x.shape[-1]
        out = dest.reshape(world, n)
        out = jnp.where(rank == root, out.at[root].set(x[0]), out)
        buf = x[0]
        perm = [(i, (i - 1) % world) for i in range(world)]  # toward root
        for s in range(1, world):
            buf = _hop(buf, world, arith, perm)  # relay what arrived
            src = (root + s) % world
            out = jnp.where(rank == root,
                            out.at[src].set(buf.astype(out.dtype)), out)
        return out.reshape(1, world * n)

    return _smap(comm, body, 2)


def build_ring_bcast(comm: Communicator, root: int,
                     arith: Optional[ArithConfig] = None) -> Callable:
    """Pipelined ring broadcast: root injects, every rank relays to the next
    (the eager segmented root-fanout's ring cousin; included for the
    algorithm inventory)."""
    world = comm.world_size

    def body(x):
        rank = lax.axis_index(AXIS)
        rel = jnp.mod(rank - root, world)
        buf = x[0]
        for s in range(world - 1):
            moved = _hop(buf, world, arith)
            received_now = rel == (s + 1) % world
            buf = jnp.where(received_now, moved.astype(buf.dtype), buf)
        return buf[None, :]

    return _smap(comm, body, 1)
