"""allreduce: out[r] = sum_k x[k] on every rank."""
from benchmark import ops


def call(acc, src, dst, count, config, options):
    acc.allreduce(src, dst, count, ops.reduce_function(config), **ops.DEV,
                  **options)


def want(xs, r, count, config, rnd):
    return ops.sum_rows(xs, rnd)
