"""Pallas dtype-cast kernels — the ``hp_compression`` plugin.

The reference compresses f32 streams to f16 at 2:1 width in a dedicated HLS
lane in front of the packetizer (``kernels/plugins/hp_compression/
hp_compression.cpp:30-144``, TDEST 0 = compress, 1 = decompress, with
keep-mask handling for ragged tails). On TPU the wire dtype of choice is
bf16 (same exponent range as f32 — safer for gradients than f16); both
bf16 and f16 lanes are provided, plus a stochastic-rounding compress
variant for repeated-compression workloads (ragged tails are handled by
grid padding instead of keep-masks).

As with the reduction lanes, the registry's default stays the plain
``astype`` so XLA fuses the cast into the collective schedule; the Pallas
kernels are the explicit standalone lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..constants import dataType, to_jax_dtype
from .reduce_ops import _interpret  # one rule for both plugin lanes

_LANES = 128
_BLOCK_ROWS = 256

#: supported (src, dst) cast lanes
CAST_PAIRS = (
    (dataType.float32, dataType.bfloat16),
    (dataType.bfloat16, dataType.float32),
    (dataType.float32, dataType.float16),
    (dataType.float16, dataType.float32),
)


def _cast_kernel(x_ref, o_ref, *, dst):
    o_ref[:] = x_ref[:].astype(dst)


@functools.partial(jax.jit, static_argnames=("dst",))
def _pallas_cast_rowmajor(x, dst):
    """Cast over (W, rows, lanes): the leading dim rides the grid, so a
    (W, n) operand reaches the kernel with a TRAILING-dim-only split —
    flattening a (1, n) buffer (the single-chip API shape) forces XLA
    relayout copies at the kernel boundary, measured 2x on the combine
    chain (see reduce_ops._pallas_combine_rowmajor). Flat callers enter
    with W=1."""
    w, m, _ = x.shape
    spec = pl.BlockSpec((1, _BLOCK_ROWS, _LANES),
                        lambda wi, i: (wi, i, 0),
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_cast_kernel, dst=dst),
        out_shape=jax.ShapeDtypeStruct(x.shape, dst),
        grid=(w, pl.cdiv(m, _BLOCK_ROWS)),
        in_specs=[spec],
        out_specs=spec,
        interpret=_interpret(),
    )(x)


def pallas_cast(x, dst_dtype):
    """Cast via the Pallas lane, any shape (pads to the tile grid); 2D
    operands whose trailing dim divides the LANE width keep their
    leading dim as a grid axis (no flatten relayout) — a partial
    trailing row-block is masked by the grid, so the trailing dim need
    NOT reach a full (rows x lanes) tile. The collective-matmul wire
    staging path casts (m, k) shards with lane-aligned k well below the
    tile; requiring a full-tile multiple (rounds 4-8) sent exactly
    those shapes through the flatten+pad path."""
    shape = x.shape
    tile = _BLOCK_ROWS * _LANES
    if len(shape) == 2 and shape[1] >= _LANES and shape[1] % _LANES == 0:
        out = _pallas_cast_rowmajor(
            x.reshape(shape[0], -1, _LANES), dst_dtype)
        return out.reshape(shape)
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % tile
    if pad:
        flat = jnp.pad(flat, (0, pad))
    out = _pallas_cast_rowmajor(
        flat.reshape(1, -1, _LANES), dst_dtype).reshape(-1)
    if pad:
        out = out[:n]
    return out.reshape(shape)


def derive_seed(base, step: int):
    """Per-step seed for multi-leg schedules: a splitmix-style integer
    mix of ``(base seed, step index)``.

    A multi-step schedule (the two-tier DCN exchange, a pipelined
    chunk sweep) that passes the SAME seed to every compressed leg
    rounds every leg with the SAME PRNG pattern — boundary elements
    round identically on each hop, re-introducing exactly the
    correlated bias stochastic rounding exists to kill. Deriving each
    leg's seed from (base, step) decorrelates them while keeping the
    schedule deterministic for a given base. Works on Python ints and
    traced scalars alike (the twotier builders derive ``base`` from
    the payload's bits per execution, the ``_wire_cast`` discipline)."""
    h = jnp.asarray(base).astype(jnp.uint32)
    h = h ^ jnp.uint32((int(step) * 0x9E3779B9 + 0x7F4A7C15) & 0xFFFFFFFF)
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(16))
    return h.astype(jnp.int32)


def _sr_kernel(x_ref, seed_ref, o_ref, *, dst):
    # fold the grid position into the seed: one seed for the whole
    # launch would replay the SAME random pattern in every (W, row)
    # block — neighboring chunks of one payload rounding in lockstep,
    # the correlated-bias failure derive_seed exists to prevent at the
    # schedule level, reproduced at the tile level
    # (Mosaic seeds from at most two values: the grid position folds
    # into one)
    pltpu.prng_seed(seed_ref[0],
                    pl.program_id(0) * pl.num_programs(1) + pl.program_id(1))
    bits = pltpu.bitcast(pltpu.prng_random_bits(x_ref.shape), jnp.uint32)
    o_ref[:] = pltpu.stochastic_round(x_ref[:], bits, target_dtype=dst)


def _pallas_sr_rowmajor(x3, dst_dtype, seed):
    """Stochastic-round cast over (W, rows, lanes) — same grid-axis
    leading dim as :func:`_pallas_cast_rowmajor` (no flatten relayout);
    the seed (a Python int or traced scalar) rides SMEM unchanged."""
    w, m, _ = x3.shape
    spec = pl.BlockSpec((1, _BLOCK_ROWS, _LANES),
                        lambda wi, i: (wi, i, 0),
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_sr_kernel, dst=dst_dtype),
        out_shape=jax.ShapeDtypeStruct(x3.shape, dst_dtype),
        grid=(w, pl.cdiv(m, _BLOCK_ROWS)),
        in_specs=[spec, pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=spec,
    )(x3, jnp.asarray(seed, jnp.int32).reshape(1))


def pallas_compress_stochastic(x, dst_dtype, seed=0):
    """f32 -> bf16 compress with stochastic rounding: unbiased under the
    repeated compress/reduce cycles of multi-hop ring collectives (TPU-only;
    no reference analog — the FPGA lane truncates). 2D operands keep
    their leading dim as a grid axis like the deterministic lane.
    ``seed`` may be a Python int or a traced scalar — callers running
    inside a compiled step should derive it per execution (a constant
    replays the same PRNG stream every step, defeating the
    unbiasedness; see ``collective_matmul._wire_cast``), and callers
    compressing MULTIPLE legs of one schedule should decorrelate them
    via :func:`derive_seed` (each grid tile already folds its own grid
    position into the stream)."""
    if _interpret():  # stochastic_round has no interpret-mode lowering
        return x.astype(dst_dtype)
    shape = x.shape
    tile = _BLOCK_ROWS * _LANES
    if len(shape) == 2 and shape[1] >= _LANES and shape[1] % _LANES == 0:
        out = _pallas_sr_rowmajor(
            x.reshape(shape[0], -1, _LANES), dst_dtype, seed)
        return out.reshape(shape)
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % tile
    if pad:
        flat = jnp.pad(flat, (0, pad))
    out = _pallas_sr_rowmajor(
        flat.reshape(1, -1, _LANES), dst_dtype, seed).reshape(-1)
    if pad:
        out = out[:n]
    return out.reshape(shape)


def make_cast(src: dataType, dst: dataType):
    """Registry-compatible cast impl for one (src, dst) lane."""
    dst_jnp = to_jax_dtype(dst)

    def impl(x):
        return pallas_cast(x, dst_jnp)

    impl.__name__ = f"pallas_cast_{src.name}_to_{dst.name}"
    return impl
