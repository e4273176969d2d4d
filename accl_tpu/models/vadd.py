"""The vadd_put example: device-initiated compute + collective.

Port of the reference's only "application" kernel
(``kernels/plugins/vadd_put/vadd_put.cpp:20-86``): each rank reads its
input, adds a constant, and ``stream_put``s the result to the next rank on
the ring, pulling in what the previous rank produced — demonstrating a
kernel-initiated collective with no host in the loop.

Here the whole thing is one jitted ``shard_map`` program: compute (+1) and
the ring put fuse into a single XLA schedule.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from jax import shard_map

from .. import device_api as dapi
from ..communicator import Communicator


def build_vadd_put(comm: Communicator, add: float = 1.0):
    """Program: out[r] = in[(r-1) % world] + add (per-rank (1, n) shards)."""

    def kernel(x):
        y = x + add            # the "vadd" compute stage
        return dapi.put_next(y)  # stream_put to rank+1

    return jax.jit(
        shard_map(kernel, mesh=comm.mesh, in_specs=P(Communicator.AXIS),
                  out_specs=P(Communicator.AXIS), check_vma=False)
    )


def run_vadd_put(comm: Communicator, data, add: float = 1.0):
    """Convenience wrapper: device_put + run (host only supervises)."""
    x = jax.device_put(data, comm.sharding())
    return build_vadd_put(comm, add)(x)
