"""bcast, in place: out[r] = x[root] on every rank."""
from benchmark import ops

INPLACE = True


def needs(n, config):
    return [int(config["root"])]


def call(acc, src, dst, count, config, options):
    acc.bcast(dst, count, int(config["root"]), **ops.DEV, **options)


def want(xs, r, count, config, rnd):
    return xs[int(config["root"])]
