"""gather: out[root] = concat_k x[k]."""
import jax.numpy as jnp

from benchmark import ops

NCCL_CHUNKED = True


def out_elems(count, n):
    return count * n


def rows(n, config):
    return [int(config["root"])]


def call(acc, src, dst, count, config, options):
    acc.gather(src, dst, count, int(config["root"]), **ops.DEV, **options)


def want(xs, r, count, config, rnd):
    return jnp.concatenate([x[:, :count] for x in xs], axis=1)
