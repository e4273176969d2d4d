"""The library calls a traffic mix can name: one file per op,
``benchmark/ops/<op>.py``, found by the name the mix gives it. A later
PR adds an op by adding its file.

Every call goes through the public ``accl_tpu.ACCL`` session, on
device-resident buffers (``from_device=True, to_device=True``),
synchronously (``run_async=False``), with ``algorithm`` left at AUTO.
Each call's buffers are sized exactly to it, as a caller who keeps one
buffer per gradient bucket or message size does.

An op file defines

- ``call(acc, src, dst, count, config, options)``: issue the call;
  ``src`` is the input buffer, ``dst`` the output (the same buffer for
  an in-place op), ``options`` the library keywords the configuration
  sets (:func:`options`);
- ``want(xs, r, count, config, rnd)``: the plain reference, what output
  row ``r`` must hold, from the input rows ``xs`` (``(1, elems)`` float32
  arrays, one per rank), with every input and partial sum rounded by
  ``rnd``; it imports nothing of ``accl_tpu``;

and, where they differ from the defaults below, ``in_elems(count, n)``
and ``out_elems(count, n)`` (elements per rank: ``count``),
``NCCL_CHUNKED`` (nccl-tests' S is a buffer of ``n`` chunks of
``count``: False), ``INPLACE`` (False), ``rows(n, config)`` (the output
rows the call defines: all) and ``needs(n, config)`` (the input rows the
reference reads: all).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

from benchmark import load_file

#: every call reads and writes device-resident buffers
DEV = dict(from_device=True, to_device=True)


@dataclass(frozen=True)
class Geometry:
    """One call: its ``count`` argument and the elements per rank of its
    input and output buffers."""
    count: int
    in_elems: int
    out_elems: int


def _count(c, n):
    return c


def _all(n, config):
    return list(range(n))


class Op:
    """An op file, with the defaults filled in."""

    def __init__(self, name: str, path: str, mod):
        self.name = name
        self.path = path
        self.call = mod.call
        self.want = mod.want
        self.in_elems = getattr(mod, "in_elems", _count)
        self.out_elems = getattr(mod, "out_elems", _count)
        self.nccl_chunked = bool(getattr(mod, "NCCL_CHUNKED", False))
        self.inplace = bool(getattr(mod, "INPLACE", False))
        self.rows = getattr(mod, "rows", _all)
        self.needs = getattr(mod, "needs", _all)


def load(name: str, root: str = None) -> Op:
    """The op ``name`` from ``<root>/benchmark/ops/<name>.py``."""
    mod = load_file(root, "ops", name)
    return Op(name, mod.__file__, mod)


def geometry(op: Op, size: int, traffic: dict, n: int,
             itemsize: int) -> Geometry:
    """Count and buffer sizes of ``op`` at ``size``: elements or bytes
    (the mix's ``size_unit``) of the call's ``count`` or of nccl-tests'
    S (``size_is``)."""
    elems = size if traffic["size_unit"] == "elements" else size // itemsize
    count = elems
    if traffic["size_is"] == "nccl" and op.nccl_chunked:
        if elems % n:
            raise ValueError(f"{op.name}: S of {elems} elements does not "
                             f"split over {n} ranks")
        count = elems // n
    return Geometry(count, op.in_elems(count, n), op.out_elems(count, n))


def options(config: dict) -> dict:
    """Library keywords the configuration sets on every call: the wire
    dtype (``compress_dtype``, a ``dataType`` name) where it names one."""
    wire = config.get("compress_dtype")
    if not wire:
        return {}
    from accl_tpu import dataType

    return {"compress_dtype": getattr(dataType, wire)}


def reduce_function(config: dict):
    from accl_tpu import reduceFunction

    return getattr(reduceFunction, config["reduce_function"].upper())


def call(acc, op: Op, src, dst, count: int, config: dict) -> None:
    """Issue one synchronous session call of ``op``."""
    op.call(acc, src, dst, count, config, options(config))


def sum_rows(xs: List, rnd):
    """``sum_k xs[k]`` in order, each partial sum rounded by ``rnd``."""
    acc = xs[0]
    for x in xs[1:]:
        acc = rnd(acc + x)
    return acc
