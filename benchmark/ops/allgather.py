"""allgather: out[r] = concat_k x[k] on every rank."""
import jax.numpy as jnp

from benchmark import ops

NCCL_CHUNKED = True


def out_elems(count, n):
    return count * n


def call(acc, src, dst, count, config, options):
    acc.allgather(src, dst, count, **ops.DEV, **options)


def want(xs, r, count, config, rnd):
    return jnp.concatenate([x[:, :count] for x in xs], axis=1)
