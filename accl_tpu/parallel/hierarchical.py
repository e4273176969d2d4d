"""Hierarchical 2-D mesh collectives (BASELINE.json config 5).

The reference selects flat vs tree vs ring by size/world thresholds; the
north star adds a **hierarchical reduce->bcast all-reduce over a 2D ICI
mesh**. On a TPU torus, a 2-D decomposition keeps every hop on a physical
ICI link of its own axis and multiplies effective bisection bandwidth:

  phase 1: reduce-scatter within each row   (payload n -> n/cols per rank)
  phase 2: all-reduce across columns        (on the n/cols shard)
  phase 3: all-gather within each row       (shard -> full payload)

Implementation: the program reshapes the communicator's 1-D (world, n)
array onto a true 2-D ``Mesh`` (``Communicator.mesh2d``, rank r at
(r // cols, r % cols), raster order — so the reshape is layout-preserving
and costs no data movement) and runs each phase as an XLA collective over
one named mesh axis. This is exactly how a multi-axis ICI torus is meant to
be driven: per-axis collectives, XLA scheduling the overlap.

The latency-oriented variant (reduce to rank 0 then broadcast, literally
"reduce->bcast") is :func:`build_hier_reduce_bcast`.

The **two-tier DCN schedule family** (``build_twotier_*``) is the
multi-slice generalization: rows are SLICES (``Communicator.
hosts_shape()`` host groups, the DCN boundary), columns the per-slice
devices on ICI. Dataflow per op:

  allreduce:       intra-slice reduce-scatter (ICI, full precision)
                   → ONE cross-slice exchange on the shard (DCN — the
                     shard gathered in the ``dcn_wire_dtype`` codec,
                     decompressed and folded at FULL precision: every
                     contribution rounds exactly once, non-sum folds
                     included)
                   → intra-slice all-gather (ICI, full precision)
  reduce_scatter:  intra-slice reduce-scatter → compressed cross-slice
                   all_to_all + full-precision fold
  allgather:       compressed cross-slice gather of the own block
                   → intra-slice all-gather

Only the shard-sized cross-slice leg ever compresses (``"off"`` keeps
it bit-exact — the pre-two-tier contract); the compressed leg rides
``ops/compression.py`` (``pallas_cast``, or the stochastic-rounding
lane for ``"bf16_sr"`` with per-leg seeds via
``compression.derive_seed``). See docs/scheduling.md §two-tier.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map

from ..arithconfig import ArithConfig
from ..communicator import Communicator
from ..constants import dataType, reduceFunction
from .. import ops
from .primitives import _unwire, _wire

ROW_AXIS = "accl_y"  # which row (changes along a column)
COL_AXIS = "accl_x"  # which column (changes along a row)

#: DCN cross-slice wire codecs (ACCLConfig.dcn_wire_dtype values
#: besides "off"); both stage bf16 on the wire — "bf16_sr" rounds
#: stochastically (TPU-only; degrades to the deterministic cast on
#: other rungs, compression handles the gate)
DCN_WIRE_DTYPES = ("off", "bf16", "bf16_sr")

#: session default for the cross-slice wire dtype (config write-through,
#: the collective_matmul.set_wire_dtype shape); per-build override via
#: the ``dcn_wire_dtype`` argument on every twotier builder
_DCN_WIRE_DEFAULT = "off"


def set_dcn_wire_dtype(name: Optional[str]) -> None:
    """Config write-through for ``ACCLConfig.dcn_wire_dtype`` — the
    session default the twotier builders resolve when no explicit
    per-build wire dtype is passed. ``None`` normalizes to "off"."""
    global _DCN_WIRE_DEFAULT
    name = name or "off"
    if name not in DCN_WIRE_DTYPES:
        raise ValueError(
            f"unsupported dcn_wire_dtype {name!r}; one of "
            f"{list(DCN_WIRE_DTYPES)}")
    _DCN_WIRE_DEFAULT = name


def get_dcn_wire_dtype() -> str:
    return _DCN_WIRE_DEFAULT


def _resolve_dcn_wire(dcn_wire_dtype: Optional[str],
                      arith: Optional[ArithConfig]) -> str:
    """The cross-slice codec for one build: the explicit argument, else
    the session register. A call-level compressing ArithConfig already
    narrows EVERY hop (the ``compressionFlags.ETH_COMPRESSED`` wire) —
    layering the DCN codec under it would double-round the exchange,
    so the per-leg wire stands down there ("off")."""
    name = dcn_wire_dtype if dcn_wire_dtype is not None \
        else _DCN_WIRE_DEFAULT
    if name not in DCN_WIRE_DTYPES:
        raise ValueError(
            f"unsupported dcn_wire_dtype {name!r}; one of "
            f"{list(DCN_WIRE_DTYPES)}")
    if arith is not None and arith.is_compressing:
        return "off"
    return name


def _dcn_compress(x, wire: str, step: int):
    """Stage a cross-slice payload into the DCN wire dtype via the
    hp_compression Pallas lanes; identity at "off" (bit-exact) and for
    operands at or below the wire width (the wire never upcasts).
    ``step`` indexes the schedule leg: the stochastic lane derives its
    seed from (payload bits, step) so two compressed legs of one
    schedule never round with the same pattern
    (``compression.derive_seed``)."""
    if wire == "off":
        return x
    from ..ops import compression
    # trace-time twin of DCN_COMPRESSIBLE: floats wider than the wire
    if x.dtype.itemsize <= jnp.dtype(jnp.bfloat16).itemsize \
            or not jnp.issubdtype(x.dtype, jnp.floating):
        return x
    if wire == "bf16_sr":
        bits = lax.bitcast_convert_type(
            x.astype(jnp.float32).reshape(-1), jnp.int32)
        seed = compression.derive_seed(jnp.sum(bits, dtype=jnp.int32),
                                       step)
        return compression.pallas_compress_stochastic(
            x, jnp.bfloat16, seed=seed)
    return compression.pallas_cast(x, jnp.bfloat16)


def _dcn_decompress(x, out_dtype):
    """Widen a cross-slice payload back before any fold — the
    decompress-before-arith discipline: a wire-dtype fold would round
    (SUM) or corrupt ordering guarantees the validator's
    decompress-fold step assumes; widening bf16 → f32 is exact."""
    return x.astype(out_dtype)


#: payload dtypes the cross-slice codec can actually narrow — anything
#: else (ints, and floats already at or below the bf16 wire width)
#: moves full precision. THE source of truth for wire inertness:
#: :func:`dcn_wire_inert` (the planner's gate) and
#: :func:`_dcn_compress`'s trace-time width check must both follow it.
DCN_COMPRESSIBLE = (dataType.float32, dataType.float64)


def dcn_wire_inert(dtype: dataType, arith: Optional[ArithConfig]) -> bool:
    """True when the DCN cross-slice codec cannot actually compress a
    call — a call-level compressing ArithConfig already narrows every
    hop (:func:`_resolve_dcn_wire` stands the codec down), or the
    payload dtype is outside :data:`DCN_COMPRESSIBLE`. The dispatch
    layer feeds this into ``select_plan(wire_inert=)`` so the two-tier
    window never prices or accounts a cast the builders would skip —
    ONE predicate beside the codec itself, so a future codec change
    cannot desynchronize planner and builder."""
    if arith is not None and arith.is_compressing:
        return True
    return dtype not in DCN_COMPRESSIBLE


def factor2d(world: int) -> Optional[Tuple[int, int]]:
    """Most-square (rows, cols) factorization, None if world is prime/1."""
    best = None
    for rows in range(2, int(world ** 0.5) + 1):
        if world % rows == 0:
            best = (rows, world // rows)
    return best


def _smap2d(comm: Communicator, rows: int, cols: int, body,
            check_vma: bool = True) -> Callable:
    """jit(reshape -> shard_map over the 2-D mesh -> reshape back).
    ``check_vma=False`` for bodies embedding Pallas plugin kernels (the
    twotier wire casts) — they carry no varying-mesh-axis annotations,
    the ``primitives._smap`` discipline."""
    mesh2 = comm.mesh2d(rows, cols, axis_names=(ROW_AXIS, COL_AXIS))
    inner = shard_map(
        body, mesh=mesh2,
        in_specs=P(ROW_AXIS, COL_AXIS, None),
        out_specs=P(ROW_AXIS, COL_AXIS, None),
        check_vma=check_vma,
    )

    @jax.jit
    def prog(x):  # x: (world, n) sharded along the 1-D communicator axis
        n = x.shape[-1]
        out = inner(x.reshape(rows, cols, n))
        return out.reshape(rows * cols, -1)

    return prog


def build_hier_allreduce(
    comm: Communicator,
    rows: int,
    cols: int,
    func: reduceFunction,
    dt: dataType,
    arith: Optional[ArithConfig] = None,
) -> Callable:
    """2D reduce-scatter / cross-axis all-reduce / all-gather (bandwidth
    variant): per-link traffic ~ n/cols on the row axis + n/cols on the
    column axis, vs ~n for a flat 1-D ring."""
    if rows * cols != comm.world_size:
        raise ValueError(f"{rows}x{cols} != world {comm.world_size}")

    decompress_arith = (arith is not None and arith.decompress_before_arith)

    def body(v):  # (1, 1, n)
        n = v.shape[-1]
        pad = (-n) % cols
        x = jnp.pad(v[0, 0], (0, pad))
        w = _wire(x, arith)
        if func == reduceFunction.SUM and decompress_arith:
            # decompress-before-arith pairs (casting/quantized wires): every
            # hop carries the wire dtype, every fold runs at full precision
            # — a wire-dtype psum would round (bf16) or wrap (int8).
            # phase 1: chunk exchange along the row, local fold
            sw = lax.all_to_all(w.reshape(cols, -1), COL_AXIS,
                                split_axis=0, concat_axis=0)   # (cols, m)
            shard = ops.reduce_axis0(
                _unwire(sw, arith, x.dtype), func, dt)         # (m,)
            # phase 2: cross-row fold of the shard
            g = lax.all_gather(_wire(shard, arith), ROW_AXIS)  # (rows, m)
            shard = ops.reduce_axis0(_unwire(g, arith, x.dtype), func, dt)
            # phase 3: row all-gather (transfer only)
            full = lax.all_gather(_wire(shard, arith), COL_AXIS, tiled=True)
            out = _unwire(full, arith, v.dtype)
        else:
            if func == reduceFunction.SUM:
                shard = lax.psum_scatter(
                    w.reshape(cols, -1), COL_AXIS, scatter_dimension=0,
                    tiled=False)
                shard = lax.psum(shard, ROW_AXIS)
                full = lax.all_gather(shard, COL_AXIS, tiled=True)
            elif func == reduceFunction.MAX:
                # max of wire values == wire of max (monotone cast): the
                # fast path is exact for MAX under any wire dtype
                full = lax.pmax(lax.pmax(w, COL_AXIS), ROW_AXIS)
            else:
                raise ValueError(func)
            out = _unwire(full, arith, v.dtype)
        return out[:n][None, None, :] if pad else out[None, None, :]

    return _smap2d(comm, rows, cols, body)


def build_hier_reduce_bcast(
    comm: Communicator,
    rows: int,
    cols: int,
    func: reduceFunction,
    dt: dataType,
    arith: Optional[ArithConfig] = None,
) -> Callable:
    """Hierarchical reduce->bcast allreduce (latency variant, the literal
    BASELINE.json "hierarchical reduce->bcast" config): reduce within rows to
    the row leader (column 0), reduce leaders across rows, broadcast back."""
    if rows * cols != comm.world_size:
        raise ValueError(f"{rows}x{cols} != world {comm.world_size}")

    decompress_arith = (arith is not None and arith.decompress_before_arith)

    def body(v):  # (1, 1, n)
        x = v[0, 0]
        w = _wire(x, arith)
        col = lax.axis_index(COL_AXIS)
        if func == reduceFunction.SUM and decompress_arith:
            # gather wire payloads per axis, fold at full precision (see
            # build_hier_allreduce); the final row gather IS the bcast
            g = lax.all_gather(w, COL_AXIS)                    # (cols, n)
            row_tot = ops.reduce_axis0(_unwire(g, arith, x.dtype), func, dt)
            g2 = lax.all_gather(_wire(row_tot, arith), ROW_AXIS)
            total = ops.reduce_axis0(_unwire(g2, arith, x.dtype), func, dt)
            return total.astype(v.dtype)[None, None, :]
        if func == reduceFunction.SUM:
            row_tot = lax.psum(w, COL_AXIS)
            # only the leader column carries the row total upward
            contrib = jnp.where(col == 0, row_tot, jnp.zeros_like(row_tot))
            tot = lax.psum(contrib, ROW_AXIS)      # global at column 0
            leader_val = jnp.where(col == 0, tot, jnp.zeros_like(tot))
            total = lax.psum(leader_val, COL_AXIS)  # bcast across the row
        elif func == reduceFunction.MAX:
            total = lax.pmax(lax.pmax(w, COL_AXIS), ROW_AXIS)
        else:
            raise ValueError(func)
        out = _unwire(total, arith, v.dtype)
        return out[None, None, :]

    return _smap2d(comm, rows, cols, body)


# ---------------------------------------------------------------------------
# two-tier DCN schedules (ISSUE 15): intra-slice legs on ICI at full
# precision, ONE cross-slice exchange over DCN in the dcn_wire_dtype
# codec — the compressed-wire shape ACCL+ ran on its slow Ethernet leg
# ---------------------------------------------------------------------------

def _check_twotier(comm: Communicator, slices: int, per_slice: int) -> None:
    if slices * per_slice != comm.world_size:
        raise ValueError(
            f"{slices}x{per_slice} != world {comm.world_size}")
    if slices < 2 or per_slice < 2:
        raise ValueError(
            f"two-tier schedules need >=2 slices of >=2 devices, got "
            f"{slices}x{per_slice}")


def build_twotier_allreduce(
    comm: Communicator,
    slices: int,
    per_slice: int,
    func: reduceFunction,
    dt: dataType,
    arith: Optional[ArithConfig] = None,
    dcn_wire_dtype: Optional[str] = None,
) -> Callable:
    """Two-tier multi-slice allreduce: intra-slice reduce-scatter over
    ``COL_AXIS`` (ICI, full precision) → the per-slice shard gathered
    across slices over ``ROW_AXIS`` (DCN) in the cross-slice wire dtype
    and folded at FULL precision after decompression (each contribution
    rounds exactly once — the SR-friendly exchange; bit-exact at
    ``"off"``) → intra-slice all-gather (ICI, full precision).

    Per-rank DCN traffic is the shard times (slices−1) wire bytes —
    at bf16 half of what the full-precision exchange moves; the
    bandwidth-heavy N-sized legs never leave the slice."""
    _check_twotier(comm, slices, per_slice)
    wire = _resolve_dcn_wire(dcn_wire_dtype, arith)
    compressing = arith is not None and arith.is_compressing
    world = slices * per_slice

    def body(v):  # (1, 1, n)
        n = v.shape[-1]
        pad = (-n) % world
        x = jnp.pad(v[0, 0], (0, pad)) if pad else v[0, 0]
        # -- leg 1 (ICI): intra-slice reduce-scatter, full precision ----
        if func == reduceFunction.SUM and not compressing:
            shard = lax.psum_scatter(
                x.reshape(per_slice, -1), COL_AXIS,
                scatter_dimension=0, tiled=False)          # (n_pad/L,)
        else:
            # general path (MAX, call-level compressing wires): chunk
            # exchange along the slice + full-precision local fold
            sw = lax.all_to_all(
                _wire(x, arith).reshape(per_slice, -1), COL_AXIS,
                split_axis=0, concat_axis=0)               # (L, m)
            shard = ops.reduce_axis0(_unwire(sw, arith, x.dtype),
                                     func, dt)             # (m,)
        # -- leg 2 (DCN): ONE cross-slice exchange on the shard --------
        # compress -> gather -> decompress -> fold at full precision
        # (the validator's decompress-fold step; "off" is bit-exact)
        if compressing:
            g = lax.all_gather(_wire(shard, arith), ROW_AXIS)
            shard = ops.reduce_axis0(_unwire(g, arith, x.dtype), func, dt)
        else:
            g = lax.all_gather(_dcn_compress(shard, wire, step=1),
                               ROW_AXIS)                   # (S, m)
            shard = ops.reduce_axis0(_dcn_decompress(g, x.dtype),
                                     func, dt)
        # -- leg 3 (ICI): intra-slice all-gather, full precision -------
        full = lax.all_gather(_wire(shard, arith), COL_AXIS, tiled=True)
        out = _unwire(full, arith, v.dtype)
        return out[:n][None, None, :] if pad else out[None, None, :]

    return _smap2d(comm, slices, per_slice, body,
                   check_vma=False)


def build_twotier_reduce_scatter(
    comm: Communicator,
    slices: int,
    per_slice: int,
    func: reduceFunction,
    dt: dataType,
    arith: Optional[ArithConfig] = None,
    dcn_wire_dtype: Optional[str] = None,
) -> Callable:
    """Two-tier reduce-scatter: intra-slice reduce-scatter over
    ``COL_AXIS`` lands rank (i, j) the partials of chunks (·, j), then
    the cross-slice ``all_to_all`` over ``ROW_AXIS`` (DCN, wire-staged)
    delivers chunk (i, j)'s per-slice partials for the full-precision
    fold — rank (i, j) ends with exactly FLAT chunk i·L+j (the 1-D
    convention every caller shares)."""
    _check_twotier(comm, slices, per_slice)
    wire = _resolve_dcn_wire(dcn_wire_dtype, arith)
    compressing = arith is not None and arith.is_compressing
    S, L = slices, per_slice
    world = S * L

    def body(v):  # (1, 1, world*count) -> (1, 1, count)
        x = v.reshape(-1)
        count = x.shape[-1] // world
        # row j of t = [chunk(0,j), ..., chunk(S-1,j)]: the intra-slice
        # scatter keeps each member its column's cross-slice stack
        t = x.reshape(S, L, count).transpose(1, 0, 2).reshape(L, -1)
        if func == reduceFunction.SUM and not compressing:
            shard = lax.psum_scatter(t, COL_AXIS, scatter_dimension=0,
                                     tiled=False)          # (S*count,)
        else:
            sw = lax.all_to_all(_wire(t, arith), COL_AXIS,
                                split_axis=0, concat_axis=0)
            shard = ops.reduce_axis0(_unwire(sw, arith, x.dtype),
                                     func, dt)
        # cross-slice leg: scatter the stack across slices (each slice
        # keeps its own chunk), decompress, fold at full precision
        if compressing:
            sw2 = lax.all_to_all(
                _wire(shard, arith).reshape(S, count), ROW_AXIS,
                split_axis=0, concat_axis=0)
            out = ops.reduce_axis0(_unwire(sw2, arith, x.dtype), func, dt)
        else:
            w2 = _dcn_compress(shard.reshape(S, count), wire, step=1)
            sw2 = lax.all_to_all(w2, ROW_AXIS,
                                 split_axis=0, concat_axis=0)  # (S, count)
            out = ops.reduce_axis0(_dcn_decompress(sw2, x.dtype),
                                   func, dt)
        return out.astype(v.dtype).reshape(1, 1, count)

    return _smap2d(comm, slices, per_slice, body,
                   check_vma=False)


def build_twotier_allgather(
    comm: Communicator,
    slices: int,
    per_slice: int,
    arith: Optional[ArithConfig] = None,
    dcn_wire_dtype: Optional[str] = None,
) -> Callable:
    """Two-tier all-gather (the reduce-scatter dual): the own block
    crosses the DCN ONCE in the wire dtype (gather over ``ROW_AXIS``),
    then the intra-slice all-gather replicates the decompressed stack
    over ICI at full precision; the transpose restores flat chunk
    order. At bf16 the DCN leg moves half the bytes of the flat ring's
    cross-slice hops — the intra-slice fan-out does the amplification
    where bandwidth is cheap."""
    _check_twotier(comm, slices, per_slice)
    wire = _resolve_dcn_wire(dcn_wire_dtype, arith)
    compressing = arith is not None and arith.is_compressing
    S, L = slices, per_slice

    def body(v):  # (1, 1, count) -> (1, 1, world*count)
        x = v.reshape(-1)
        count = x.shape[-1]
        if compressing:
            g = _unwire(lax.all_gather(_wire(x, arith), ROW_AXIS),
                        arith, x.dtype)                    # (S, count)
        else:
            g = _dcn_decompress(
                lax.all_gather(_dcn_compress(x, wire, step=0), ROW_AXIS),
                x.dtype)                                   # (S, count)
        G = lax.all_gather(_wire(g, arith), COL_AXIS)      # (L, S, count)
        G = _unwire(G, arith, v.dtype)
        out = G.transpose(1, 0, 2).reshape(-1)             # flat order
        return out.reshape(1, 1, -1)

    return _smap2d(comm, slices, per_slice, body,
                   check_vma=False)
