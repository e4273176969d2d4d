"""combine (the ``reduce_ops`` plugin), the configuration's function over
the two halves of one buffer, each a ``Buffer.slice`` view:
out[r] = x[r][:c] + x[r][c:2c]. Sum only: max is the same kernel body
under another function. A combine has no wire, so ``options`` is not
passed."""
import jax
import jax.numpy as jnp

from benchmark import ops


def in_elems(count, n):
    return 2 * count


def call(acc, src, dst, count, config, options):
    acc.combine(count, ops.reduce_function(config), src.slice(0, count),
                src.slice(count, 2 * count), dst, val1_from_device=True,
                val2_from_device=True, to_device=True)


def want(xs, r, count, config, rnd):
    # rank r's own row (r is traced: the reference is jitted over it)
    x = jax.lax.dynamic_index_in_dim(jnp.concatenate(xs, axis=0), r, axis=0)
    return rnd(x[:, :count] + x[:, count:2 * count])
