"""CSV sweep benchmark harness — the ``bench.cpp`` analog.

The reference sweeps 2^4..2^19 fp32 elements over every collective and
logs ``Test,Param,Cycles`` CSV rows, timing with the CCLO's device cycle
counter so host dispatch is excluded (``test/host/xrt/src/bench.cpp:25-61``,
``fixture.hpp:76-133``). This harness reproduces that matrix over the
compiled collective programs with two timing modes:

* ``block`` — per-call wall time around ``block_until_ready`` + a scalar
  readback; accurate on the CPU emulator rung where dispatch is synchronous.
* ``chain`` — dependent-op chains of two lengths with one forced readback;
  per_op = (t_long - t_short)/(k_long - k_short). This amortizes dispatch
  and readback RTT away — the PERFCNT-equivalent accounting — and is the
  right mode for real TPUs, whose dispatch is asynchronous.

Run as a module::

    python -m accl_tpu.bench --ops allreduce,bcast --min-pow 4 --max-pow 19

Each row records the measured duration plus the analytic ideal-model
efficiency (``models.ideal_duration``), mirroring
``parse_bench_results.py``.
"""
from __future__ import annotations

import csv
import dataclasses
import sys
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from ..communicator import Communicator
from ..config import ACCLConfig, Algorithm
from ..constants import dataType, dtype_size, operation, reduceFunction, to_jax_dtype
from ..obs import trace as _trace
from ..parallel import algorithms, primitives
from . import models

_pick = jax.jit(lambda v: v.ravel()[0])


#: HBM peak by device kind (bytes/s) — the anti-cheat floor's roofline.
#: Source: Google Cloud TPU documentation, per generation. A device kind
#: that is not in the table is an error, not a default.
_HBM_PEAK_BY_KIND = {
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v5p": 2765e9,
    "TPU v6 lite": 1640e9,
    "TPU v6e": 1640e9,
}


#: bf16 MXU peak by device kind (FLOP/s) — the MFU denominator. Same
#: source and unknown-kind policy as the HBM table.
_BF16_PEAK_BY_KIND = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def _peak_by_kind(table: Dict[str, float]) -> float:
    kind = getattr(jax.devices()[0], "device_kind", "")
    for k, v in sorted(table.items(), key=lambda kv: -len(kv[0])):
        if kind.startswith(k):
            return v
    raise ValueError(
        f"no peak on record for device_kind {kind!r} (known: "
        f"{', '.join(sorted(table))}); add it with its source")


def hbm_peak_bytes_per_s() -> float:
    return _peak_by_kind(_HBM_PEAK_BY_KIND)


def bf16_peak_flops() -> float:
    return _peak_by_kind(_BF16_PEAK_BY_KIND)


def _salt_scalar(dtype, i: int):
    """Per-invocation input perturbation that survives the payload dtype:
    nonzero for integers, representable (no underflow) for bf16/f16."""
    import jax.numpy as jnp
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.asarray(i % 113 + 1, dtype)
    return jnp.asarray(i * 1e-6, dtype)


@dataclasses.dataclass
class Timing:
    """One measurement with its in-run spread: ``best`` is the reported
    per-op time (least-noise estimator); median/worst + round count let a
    single artifact distinguish run-to-run noise from regression (VERDICT r2
    weak #8 — adjacent sweep sizes disagreeing 1.5x is diagnosable only
    when every row carries its own spread). ``floored``: the best round
    hit the anti-cheat physical floor — the value is a CAP, not a
    measurement, and must not be eligible for a headline peak."""
    best: float
    median: float
    worst: float
    rounds: int
    floored: bool = False


@dataclasses.dataclass
class SweepRow:
    op: str
    algorithm: str
    world: int
    count: int
    nbytes: int
    duration_ns: float       # best-of-rounds (the headline estimator)
    duration_med_ns: float   # in-run median across measurement rounds
    duration_max_ns: float   # in-run worst round
    rounds: int
    algbw_GBps: float
    efficiency: float
    # best round hit the anti-cheat physical floor: the bandwidth is a
    # CAP, not a measurement — ineligible for headline peaks
    floored: bool = False


@dataclasses.dataclass
class _Case:
    """One benchmarkable collective: program + input factory + chain adapter."""

    op: operation
    build: Callable[[], Callable]
    make_inputs: Callable[[int], tuple]
    # maps prog output back to something input-shaped so dependent chains
    # are possible (identity for in-shape == out-shape collectives)
    chain_adapt: Optional[Callable] = None
    # bytes moved per rank for algbw accounting (defaults to count*dtsize)
    payload_bytes: Optional[Callable[[int], int]] = None
    # in-place variant for the fused (loop-carry) accounting: output
    # aliases the carry operand so the chain streams with no copy
    build_fused: Optional[Callable[[], Callable]] = None
    # minimum HBM bytes per payload byte this op can generate (the
    # anti-cheat floor's multiplier): read+write = 2 for most; a combine
    # reads two operands and writes one = 3
    traffic_multiplier: float = 2.0


def _dev(comm: Communicator, arr: np.ndarray):
    return jax.device_put(arr, comm.sharding())


def _build_combine_best(comm: Communicator, func: reduceFunction,
                        dt: dataType, donate: bool = False):
    """combine through the Pallas reduce_ops lane on TPU, jnp elsewhere.
    Pallas failures surface at first trace, not at build — smoke-execute
    on tiny inputs before accepting the lane. ``donate`` builds the
    in-place chain variant (output aliases operand 0) used by the fused
    accounting, where the loop carry is dead after each step."""
    use_pallas = jax.default_backend() == "tpu"
    for pallas in ([True, False] if use_pallas else [False]):
        prog = primitives.build_combine(comm, func, dt, use_pallas=pallas,
                                        donate=donate and pallas)
        try:
            tiny = _dev(comm, np.zeros((comm.world_size, 256),
                                       np.dtype(to_jax_dtype(dt))))
            np.asarray(_pick(prog(tiny, tiny)))
            return prog
        except Exception as e:  # noqa: BLE001 - fall back, but NEVER silently
            # a broken Pallas lane must not quietly benchmark the jnp path
            # under the plugin's name (the headline bench names reduce_ops)
            print(f"WARNING: combine lane (pallas={pallas}) failed "
                  f"({type(e).__name__}: {e}); falling back", file=sys.stderr)
            continue
    return primitives.build_combine(comm, func, dt, use_pallas=False)


def _cases(comm: Communicator, dt: dataType, func: reduceFunction,
           algo: Algorithm,
           bidirectional: bool = True,
           on_dcn: bool = False) -> Dict[str, _Case]:
    world = comm.world_size
    npdt = np.dtype(to_jax_dtype(dt))

    def flat(n, fill=1.0):
        return _dev(comm, np.full((world, n), fill, npdt))

    def wide(n, fill=1.0):
        return _dev(comm, np.full((world, n * world), fill, npdt))

    import jax.numpy as jnp

    return {
        "copy": _Case(
            operation.copy,
            lambda: primitives.build_copy(comm),
            lambda n: (flat(n),)),
        "combine": _Case(
            operation.combine,
            lambda: _build_combine_best(comm, func, dt),
            lambda n: (flat(n), flat(n, 2.0)),
            build_fused=lambda: _build_combine_best(comm, func, dt,
                                                    donate=True),
            traffic_multiplier=3.0),
        "sendrecv": _Case(
            operation.send,
            lambda: primitives.build_move(comm, 0, (1 % world)),
            lambda n: (flat(n), flat(n, 0.0))),
        "bcast": _Case(
            operation.bcast,
            lambda: algorithms.build_bcast(comm, 0, algo, None, dt),
            lambda n: (flat(n),)),
        "scatter": _Case(
            operation.scatter,
            lambda: primitives.build_scatter(comm, 0),
            lambda n: (wide(n),),
            chain_adapt=lambda out: jnp.tile(out, (1, comm.world_size))),
        "gather": _Case(
            operation.gather,
            lambda: primitives.build_gather(comm, 0),
            lambda n: (flat(n), wide(n, 0.0)),
            chain_adapt=lambda out: out[:, : out.shape[1] // comm.world_size]),
        "allgather": _Case(
            operation.allgather,
            lambda: algorithms.build_allgather(
                comm, algo, None, dt, bidirectional=bidirectional),
            lambda n: (flat(n),),
            chain_adapt=lambda out: out[:, : out.shape[1] // comm.world_size]),
        "reduce": _Case(
            operation.reduce,
            lambda: algorithms.build_reduce(comm, 0, func, dt, algo, None),
            lambda n: (flat(n), flat(n, 0.0))),
        "allreduce": _Case(
            operation.allreduce,
            lambda: algorithms.build_allreduce(comm, func, dt, algo, None,
                                               bidirectional=bidirectional,
                                               on_dcn=on_dcn),
            lambda n: (flat(n, 1e-6),)),
        "reduce_scatter": _Case(
            operation.reduce_scatter,
            lambda: algorithms.build_reduce_scatter(
                comm, func, dt, algo, None, bidirectional=bidirectional),
            lambda n: (wide(n, 1e-6),),
            chain_adapt=lambda out: jnp.tile(out, (1, comm.world_size)),
            payload_bytes=lambda n: n * comm.world_size * dtype_size(dt)),
        "alltoall": _Case(
            operation.alltoall,
            lambda: primitives.build_alltoall(comm),
            lambda n: (wide(n),),
            payload_bytes=lambda n: n * comm.world_size * dtype_size(dt)),
    }


def _time_block(prog, args, reps: int) -> Timing:
    """Per-call wall time; right on synchronous backends (CPU emulator)."""
    np.asarray(_pick(jax.block_until_ready(prog(*args))))  # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(prog(*args))
        np.asarray(_pick(out))
        ts.append(time.perf_counter() - t0)
    # block mode reports the median (synchronous backend; no launch
    # floor to hunt for), with the spread carried alongside
    return Timing(best=float(np.median(ts)), median=float(np.median(ts)),
                  worst=float(np.max(ts)), rounds=reps)


def time_fused(prog, args, adapt=None, nbytes: int = 0,
               est_bw: float = 700e9, target_s: float = 1.0,
               rounds: int = 3,
               traffic_multiplier: float = 2.0) -> Timing:
    """Per-op device time with the chain INSIDE one jitted program
    (``lax.fori_loop``): one launch per measurement, so host dispatch —
    ~100 µs/launch or more — is excluded entirely.
    This is the closest analog of the reference's PERFCNT device-cycle
    accounting (``fpgadevice.cpp:241-248``), and the measurement mode the
    CommandList fusion path actually runs under.

    ``rounds`` independent (short, long) slope estimates feed the in-run
    spread: best is the latency-floor estimator (least host noise),
    median/worst expose the weather."""
    from jax import lax

    rest = args[1:]

    # every invocation perturbs the loop init with a FRESH scalar: a
    # runtime may cache repeat executions of (program, identical
    # inputs) — measured round 4: a constant-input loop returned in
    # 0.1 ms total, no launch at all. The x + s pass runs once per
    # launch, outside the loop, so it cancels out of the slope.
    _salt = iter(range(1, 1 << 30))

    def make(k: int):
        def chained(x, s):
            def body(_, v):
                out = prog(v, *rest)
                return adapt(out) if adapt is not None else out
            return lax.fori_loop(0, k, body,
                                 x + s.astype(x.dtype))
        return jax.jit(chained)

    # target ~1 s of DEVICE work in the long chain: a fixed launch cost
    # of ~100 ms (measured round 4 on a remote runtime), so a short chain
    # leaves launch/k dominating the conservative floor below
    est = max(3 * nbytes / est_bw, 2e-6)
    k_long = int(min(max(target_s / est, 64), 16384))
    k_short = max(k_long // 8, 8)
    long_f, short_f = make(k_long), make(k_short)

    def once(f) -> float:
        s = _salt_scalar(args[0].dtype, next(_salt))
        t0 = time.perf_counter()
        float(np.asarray(_pick(jax.block_until_ready(f(args[0], s)))))
        return time.perf_counter() - t0

    once(short_f)  # compile + warm
    once(long_f)
    # Anti-cheat floor: per-op device time can never beat what the HBM
    # roofline allows for this payload (``traffic_multiplier`` x payload
    # against the CHIP's peak — per-op and per-device-kind, never a
    # hardcoded 3x/v5e pair). This replaces the old t_long/k_long clamp,
    # which silently folded the ~100 ms fixed launch cost into every
    # per-op figure (round 4: the clamp under-reported an at-roofline
    # kernel by ~3x). A slope at or below the physical floor means noise
    # or runtime caching won the round — report the floor, FLAGGED.
    if jax.default_backend() == "tpu":
        phys_floor = traffic_multiplier * nbytes / hbm_peak_bytes_per_s()
    else:
        phys_floor = 0.0
    pers = []
    for _ in range(rounds):
        t_short = once(short_f)
        t_long = once(long_f)
        per = (t_long - t_short) / (k_long - k_short)
        # Off-TPU there is no roofline table; the amortized long-chain
        # rate bounds a noise-negative slope to a physically meaningful
        # value (launch cost is tiny on synchronous backends, so the
        # bound is tight rather than the old 1e-9 escape hatch that let
        # a noisy round report absurd bandwidth into sweep artifacts).
        floor = phys_floor if phys_floor > 0.0 else t_long / (k_long + 1)
        pers.append(max(per, floor, 1e-9))
    best = float(np.min(pers))
    return Timing(best=best, median=float(np.median(pers)),
                  worst=float(np.max(pers)), rounds=rounds,
                  floored=bool(best <= phys_floor * (1 + 1e-6)))


def time_chain(prog, args, adapt=None, nbytes: int = 0,
               est_bw: float = 700e9, target_s: float = 0.5,
               rounds: int = 3) -> Timing:
    """Per-op device time from two dependent chains + one forced readback
    each: slope = (t_long - t_short)/(k_long - k_short). The single shared
    implementation — the repo-root ``bench.py`` headline uses it too.
    ``rounds`` independent slope estimates carry the in-run spread."""
    # fresh-scalar perturbation per run: defeats a runtime's
    # repeat-execution cache (see time_fused)
    _salt = iter(range(1, 1 << 30))

    def run(k: int) -> None:
        x = args[0] + _salt_scalar(args[0].dtype, next(_salt))
        for _ in range(k):
            out = prog(x, *args[1:])
            x = adapt(out) if adapt is not None else out
        float(np.asarray(_pick(x)))  # forces execution of the whole chain

    est = max(3 * nbytes / est_bw, 2e-5)
    k_long = int(min(max(target_s / est, 64), 4096))
    k_short = max(k_long // 8, 8)
    run(2)  # compile + warm

    pers = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        run(k_short)
        t_short = time.perf_counter() - t0
        t0 = time.perf_counter()
        run(k_long)
        t_long = time.perf_counter() - t0
        per = (t_long - t_short) / (k_long - k_short)
        # RTT noise can swamp short sweeps; never report better than the
        # long chain's amortized rate
        pers.append(max(per, t_long / (k_long + 1) * 0.5, 1e-9))
    return Timing(best=float(np.min(pers)), median=float(np.median(pers)),
                  worst=float(np.max(pers)), rounds=rounds)


def run_sweep(
    comm: Communicator,
    ops: Sequence[str],
    dt: dataType = dataType.float32,
    func: reduceFunction = reduceFunction.SUM,
    algorithm: Algorithm = Algorithm.XLA,
    min_pow: int = 4,
    max_pow: int = 19,
    reps: int = 9,
    mode: str = "block",
    link_bw: float = 45e9,
    rtt: float = 1e-6,
    pows: Optional[Sequence[int]] = None,
    bidirectional: bool = True,
    on_dcn: bool = False,
) -> List[SweepRow]:
    """Sweep ``ops`` over 2^min_pow..2^max_pow elements (bench.cpp matrix).

    ``pows`` overrides the contiguous range with an explicit list of
    exponents (the headline bench samples a sparse sweep).
    ``bidirectional`` matches ACCLConfig.bidirectional_rings' default so
    the sweep measures the kernel the host API actually dispatches.
    ``on_dcn`` mirrors the production DCN guard: a HIERARCHICAL sweep on
    a DCN mesh without a host-aligned shape fails loudly instead of
    benchmarking the factor2d split select() refuses to take."""
    cases = _cases(comm, dt, func, algorithm, bidirectional, on_dcn)
    unknown = [o for o in ops if o not in cases]
    if unknown:
        raise ValueError(f"unknown ops {unknown}; have {sorted(cases)}")
    rows: List[SweepRow] = []
    for name in ops:
        case = cases[name]
        prog = (case.build_fused() if mode == "fused" and case.build_fused
                else case.build())
        for p in (pows if pows is not None else range(min_pow, max_pow + 1)):
            n = 2 ** p
            args = case.make_inputs(n)
            nbytes = (case.payload_bytes(n) if case.payload_bytes
                      else n * dtype_size(dt))
            with _trace.span(f"sweep.{name}", cat="bench",
                             nbytes=nbytes, mode=mode):
                if mode == "chain":
                    tm = time_chain(prog, args, case.chain_adapt, nbytes)
                elif mode == "fused":
                    tm = time_fused(prog, args, case.chain_adapt, nbytes,
                                    traffic_multiplier=case.traffic_multiplier)
                else:
                    tm = _time_block(prog, args, reps)
            eff = models.efficiency(case.op, comm.world_size, nbytes,
                                    tm.best, bw=link_bw, rtt=rtt)
            rows.append(SweepRow(
                op=name, algorithm=algorithm.name, world=comm.world_size,
                count=n, nbytes=nbytes, duration_ns=tm.best * 1e9,
                duration_med_ns=tm.median * 1e9,
                duration_max_ns=tm.worst * 1e9, rounds=tm.rounds,
                algbw_GBps=nbytes / tm.best / 1e9, efficiency=eff,
                floored=tm.floored))
    return rows


def write_csv(rows: Sequence[SweepRow], path) -> None:
    """CSV schema analog of ``fixture.hpp:81`` (Test,Param,Cycles + derived)."""
    opened = isinstance(path, (str, bytes))
    out = open(path, "w", newline="") if opened else path
    try:
        w = csv.writer(out)
        w.writerow(["op", "algorithm", "world", "count", "nbytes",
                    "duration_ns", "duration_med_ns", "duration_max_ns",
                    "rounds", "algbw_GBps", "efficiency", "floored"])
        for r in rows:
            w.writerow([r.op, r.algorithm, r.world, r.count, r.nbytes,
                        f"{r.duration_ns:.1f}", f"{r.duration_med_ns:.1f}",
                        f"{r.duration_max_ns:.1f}", r.rounds,
                        f"{r.algbw_GBps:.4f}", f"{r.efficiency:.4f}",
                        int(r.floored)])
    finally:
        if opened:
            out.close()
