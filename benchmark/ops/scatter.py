"""scatter: out[r] = x[root][r*c:(r+1)*c]."""
import jax

from benchmark import ops

NCCL_CHUNKED = True


def in_elems(count, n):
    return count * n


def needs(n, config):
    return [int(config["root"])]


def call(acc, src, dst, count, config, options):
    acc.scatter(src, dst, count, int(config["root"]), **ops.DEV, **options)


def want(xs, r, count, config, rnd):
    return jax.lax.dynamic_slice_in_dim(xs[int(config["root"])], r * count,
                                        count, axis=1)
