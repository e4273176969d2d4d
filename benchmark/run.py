#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chips of this machine.

    python3 benchmark/run.py --workload small_msg.1chip --seed 7 \
        --seconds 10 --trace 0

Prints one JSON object as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` and, traced, ``breakdown``; then ``window``
and, last, ``checks``: each number compared with the plain reference
beside its limit (also the last lines of standard error). Exits
non-zero, with no result, where JAX finds no TPU or fewer chips than
the cell asks for, or where the library cannot be imported.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fail(msg: str) -> int:
    print(f"benchmark: {msg}", file=sys.stderr)
    return 1


def _seed(text: str) -> int:
    v = int(text)
    if v < 0:
        raise argparse.ArgumentTypeError("the seed is a whole number >= 0")
    return v


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=_seed, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # JAX's persistent compilation cache, at a fixed path inside the
    # checkout, set before anything imports jax (which reads it then);
    # the library's compile_cache.enable() takes it from here
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path.insert(0, ROOT)
    from benchmark import spec

    try:
        cell = spec.resolve_cell(args.workload, ROOT)
    except spec.SpecError as e:
        return _fail(str(e))

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _fail(f"no TPU: JAX found only {devices[0].platform} devices")
    if len(devices) < cell.chips:
        return _fail(f"{args.workload} needs {cell.chips} chips, JAX found "
                     f"{len(devices)}")
    try:
        import accl_tpu  # noqa: F401
        from accl_tpu.utils import compile_cache
    except ImportError as e:
        return _fail(f"cannot import accl_tpu ({e})")
    from benchmark import cellrun, peaks

    try:
        peaks.peaks(devices[0].device_kind)
    except ValueError as e:
        return _fail(str(e))
    compile_cache.enable(ROOT)
    # no size cap, hence no LRU bookkeeping: an entry that another
    # writer left without its access-time file then breaks nothing
    jax.config.update("jax_compilation_cache_max_size", -1)

    result = cellrun.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices[:cell.chips],
                              T_PROCESS)
    cellrun.print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
