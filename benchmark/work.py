"""Work counts of one call: bytes, and the least time a chip could take.

Counts depend only on the op, its geometry and the number of ranks,
never on the algorithm the library chose, so a PR that switches
schedules is judged on the same work.

- ``nccl_bytes``: nccl-tests' S, the size its algbw divides by time:
  the larger of the input and output buffer per rank (the allreduce
  buffer, the allgather output, the reduce-scatter input).
- ``ici_bytes_sent``: bytes a bandwidth-optimal algorithm must send from
  each chip: 2(n-1)/n * S for allreduce, (n-1)/n * S for allgather and
  reduce-scatter (nccl-tests' busbw factors); 0 on one rank.
- ``hbm_bytes``: bytes each chip must at least read and write: its
  input buffer and its output buffer.
- ``least_time_s``: the larger of the ICI and the HBM bound.
"""
from __future__ import annotations

from benchmark.ops import Geometry

#: busbw factor of S per op, as a function of n (nccl-tests PERFORMANCE.md)
_ICI_FACTOR = {
    "allreduce": lambda n: 2.0 * (n - 1) / n,
    "allgather": lambda n: (n - 1) / n,
    "reduce_scatter": lambda n: (n - 1) / n,
}


def nccl_bytes(g: Geometry, itemsize: int) -> int:
    return max(g.in_elems, g.out_elems) * itemsize


def ici_bytes_sent(op: str, g: Geometry, n: int, itemsize: int) -> float:
    if n == 1:
        return 0.0
    if op not in _ICI_FACTOR:
        raise ValueError(f"no ICI byte count for {op!r} on {n} ranks")
    return _ICI_FACTOR[op](n) * nccl_bytes(g, itemsize)


def hbm_bytes(g: Geometry, itemsize: int) -> int:
    return (g.in_elems + g.out_elems) * itemsize


def least_time_s(op: str, g: Geometry, n: int, itemsize: int,
                 peak: dict) -> float:
    return max(ici_bytes_sent(op, g, n, itemsize) / peak["ici_bytes_per_s"],
               hbm_bytes(g, itemsize) / peak["hbm_bytes_per_s"])
