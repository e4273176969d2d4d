"""send then recv, ranks ``src -> dst`` of the configuration's
``sendrecv``, with its ``tag``: out[dst] = x[src]."""


def rows(n, config):
    return [int(config["sendrecv"][1])]


def needs(n, config):
    return [int(config["sendrecv"][0])]


def call(acc, src, dst, count, config, options):
    s, d = config["sendrecv"]
    tag = int(config["tag"])
    acc.send(src, count, src=s, dst=d, tag=tag, from_device=True, **options)
    acc.recv(dst, count, src=s, dst=d, tag=tag, to_device=True, **options)


def want(xs, r, count, config, rnd):
    return xs[int(config["sendrecv"][0])]
