"""reduce_scatter: out[r] = sum_k x[k][r*c:(r+1)*c]."""
import jax

from benchmark import ops

NCCL_CHUNKED = True


def in_elems(count, n):
    return count * n


def call(acc, src, dst, count, config, options):
    acc.reduce_scatter(src, dst, count, ops.reduce_function(config),
                       **ops.DEV, **options)


def want(xs, r, count, config, rnd):
    return ops.sum_rows([jax.lax.dynamic_slice_in_dim(x, r * count, count,
                                                      axis=1) for x in xs],
                        rnd)
