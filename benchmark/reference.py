"""Plain reference of the collective semantics, and the check against it.

It imports nothing of ``accl_tpu`` and takes nothing that the library
made but the outputs it is checking. Inputs (the cell's global
``(n, elems)`` arrays, one row per rank and chip) are made by the
benchmark from the seed. For each output row that a call defines, the
reference moves the input rows it needs to one chip with plain device
copies (no collective), computes what the row must hold, and takes the
widest gap to what the library wrote. NaN counts as an infinite gap.

Each op's semantics is its ``want`` in ``ops/<op>.py`` (for ``n`` ranks,
input rows ``x[k]`` and count ``c``: allreduce ``out[r] = sum_k x[k]``,
reduce_scatter ``out[r] = sum_k x[k][r*c:(r+1)*c]``, allgather
``out[r] = concat_k x[k]``, ...); ``want`` imports nothing of the
library either.

The sums run over k in order, in the precision given: float32 for the
check; the control runs the same code with every input and partial sum
rounded to bfloat16.
"""
from __future__ import annotations

import json
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.ops import Op


def row_shards(arr: jax.Array) -> List[jax.Array]:
    """The ``(1, elems)`` single-chip shards of a row-sharded global
    array, in row order."""
    shards = sorted(arr.addressable_shards, key=lambda s: s.index[0].start
                    or 0)
    return [s.data for s in shards]


def _rounding(dtype):
    """Rounding of every input and partial sum to ``dtype``, computed in
    float32 with ``reduce_precision``: a bare pair of casts may be
    dropped by XLA (it allows excess precision), which would leave the
    control in float32."""
    if jnp.dtype(dtype) == jnp.float32:
        return lambda v: v
    info = jnp.finfo(dtype)
    return lambda v: jax.lax.reduce_precision(
        v, exponent_bits=info.nexp, mantissa_bits=info.nmant)


def _want(op: Op, xs: Sequence[jax.Array], r, count: int, config: dict,
          dtype) -> jax.Array:
    """What output row ``r`` must hold, from the input rows ``xs``, each
    value rounded to ``dtype``."""
    rnd = _rounding(dtype)
    return op.want([rnd(x.astype(jnp.float32)) for x in xs], r, count,
                   config, rnd)


_JITTED: Dict[tuple, object] = {}


def _jitted(kind: str, op: Op, count: int, config: dict, dtype):
    key = (kind, op.path, count, json.dumps(config, sort_keys=True),
           jnp.dtype(dtype).name)
    if key in _JITTED:
        return _JITTED[key]
    if kind == "gap":
        def fn(got, r, *xs):
            want = _want(op, xs, r, count, config, jnp.float32)
            if want.shape != got.shape:
                return jnp.float32(jnp.inf)
            d = jnp.abs(got.astype(jnp.float32) - want)
            return jnp.max(jnp.where(jnp.isnan(d), jnp.inf, d))
    else:
        def fn(r, *xs):
            return _want(op, xs, r, count, config, dtype)
    _JITTED[key] = jax.jit(fn)
    return _JITTED[key]


def row_fn(op: Op, count: int, config: dict, dtype):
    """Jitted ``(r, *xs) -> row r`` of the reference in ``dtype``."""
    return _jitted("row", op, count, config, dtype)


def _inputs_on(op: Op, n: int, config: dict, x: jax.Array, dev) -> List:
    """The input rows, those the reference reads copied to ``dev`` and
    each of the others stood in for by the first of those."""
    rows = row_shards(x)
    needed = list(op.needs(n, config))
    moved = {k: jax.device_put(rows[k], dev) for k in needed}
    return [moved.get(k, moved[needed[0]]) for k in range(n)]


def check(op: Op, count: int, n: int, config: dict, x: jax.Array,
          got: jax.Array) -> Tuple[float, int]:
    """(widest gap, elements compared) of one call's output ``got``
    against the reference computed from its input ``x``."""
    got_rows = row_shards(got)
    gap_fn = _jitted("gap", op, count, config, jnp.float32)
    worst, compared = 0.0, 0
    for r in op.rows(n, config):
        xs = _inputs_on(op, n, config, x, got_rows[r].devices().pop())
        worst = max(worst, float(gap_fn(got_rows[r], np.int32(r), *xs)))
        compared += int(got_rows[r].size)
        del xs
    return worst, compared


def lower_precision_call(op: Op, count: int, n: int, config: dict,
                         x: jax.Array, prev: jax.Array, dtype) -> jax.Array:
    """The control: the reference put in the library's place, computed in
    ``dtype``. Returns the call's whole ``(n, out_elems)`` output, with
    the rows the call does not define kept from ``prev``."""
    prev_rows = row_shards(prev)
    defined = set(op.rows(n, config))
    fn = row_fn(op, count, config, dtype)
    out = []
    for r, p in enumerate(prev_rows):
        if r not in defined:
            out.append(p)
            continue
        xs = _inputs_on(op, n, config, x, p.devices().pop())
        out.append(fn(np.int32(r), *xs))
    return jax.make_array_from_single_device_arrays(prev.shape,
                                                    prev.sharding, out)
