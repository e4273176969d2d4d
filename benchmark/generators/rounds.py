"""The default generator: every (op, size) pair of the mix once per
round, in an order shuffled from the seed, so every seed does the same
work in another order; one caller, back to back. Keys of the mix:

- ``ops``: library calls, each a file ``benchmark/ops/<op>.py``;
- ``size_unit``: ``"elements"`` or ``"bytes"``;
- ``size_pow2``: ``[lo, hi]``, every power of two from 2**lo to 2**hi;
- ``size_is``: what the size measures, ``"count"`` (the call's
  ``count`` argument, as Xilinx/ACCL's ``bench.cpp`` sweeps it) or
  ``"nccl"`` (nccl-tests' S: the allreduce buffer, the allgather
  output, the reduce-scatter input, per rank).
"""
from __future__ import annotations

from typing import Iterator, List, Tuple

import numpy as np

Pair = Tuple[str, int]

_KEYS = {"generator", "ops", "size_unit", "size_pow2", "size_is", "why"}


def validate(mix: dict) -> None:
    unknown = set(mix) - _KEYS
    if unknown:
        raise ValueError(f"unknown traffic keys {sorted(unknown)}")
    if mix["size_unit"] not in ("elements", "bytes"):
        raise ValueError(f"size_unit {mix['size_unit']!r}")
    if mix["size_is"] not in ("count", "nccl"):
        raise ValueError(f"size_is {mix['size_is']!r}")
    lo, hi = mix["size_pow2"]
    if not 0 <= lo <= hi:
        raise ValueError(f"size_pow2 {mix['size_pow2']}")


def sizes(mix: dict) -> List[int]:
    lo, hi = mix["size_pow2"]
    return [1 << p for p in range(lo, hi + 1)]


def pairs(mix: dict) -> List[Pair]:
    """Every (op, size) pair of the mix, in file order."""
    validate(mix)
    return [(op, s) for op in mix["ops"] for s in sizes(mix)]


def rounds(mix: dict, seed: int) -> Iterator[List[Pair]]:
    """Endless rounds, each every pair once in an order drawn from
    ``seed`` (any non-negative int, 64 bits and more included)."""
    ps = pairs(mix)
    rng = np.random.default_rng(seed)
    while True:
        yield [ps[i] for i in rng.permutation(len(ps))]
