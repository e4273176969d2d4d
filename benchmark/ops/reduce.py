"""reduce: out[root] = sum_k x[k]."""
from benchmark import ops


def rows(n, config):
    return [int(config["root"])]


def call(acc, src, dst, count, config, options):
    acc.reduce(src, dst, count, int(config["root"]),
               ops.reduce_function(config), **ops.DEV, **options)


def want(xs, r, count, config, rnd):
    return ops.sum_rows(xs, rnd)
