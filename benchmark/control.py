#!/usr/bin/env python3
"""Readings for the limits of ``correct``, in one process per cell.

    python3 benchmark/control.py --workload grad_sync.4chip \
        --seeds 1,2,...,12 --control-seeds 13,14,15 --seconds 3

Opens the cell's session once, then for each seed of ``--seeds`` makes
new inputs, runs a window of ``--seconds`` on the library and compares
the kept outputs with the reference (the lower readings: sound runs),
and for each of ``--control-seeds`` the same with the control in the
library's place: the reference computed in bfloat16, the precision
below the configuration's float32 (the upper readings). One JSON line
per window; the last line gives the largest lower and the smallest
upper reading. Not part of the benchmark's own runs.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def lower_precision(dtype):
    """A ``call`` for ``cellrun.Session`` that writes what the reference
    computes in ``dtype`` into the output buffer."""
    from benchmark import reference

    def call(acc, op, src, dst, count, config):
        dst.device_store(reference.lower_precision_call(
            op, count, acc.world_size, config, src.device_view(),
            dst.device_view(), dtype))
    return call


def readings(cell, devices, seeds, control_seeds, seconds, emit=print):
    """Run the windows; return (largest lower, smallest upper)."""
    import jax.numpy as jnp

    from benchmark import cellrun, ops

    first = (seeds or control_seeds)[0]
    sess = cellrun.setup(cell, first, devices, ops.call)
    lower, upper = [], []
    for mode, group in (("program", seeds), ("bf16", control_seeds)):
        if mode == "bf16":
            sess.call_fn = lower_precision(jnp.bfloat16)
        for seed in group:
            if seed != first:
                sess.reseed(seed)
            w = cellrun.measure(sess, seed, seconds, False)
            sess.out_bufs = {}
            worst, _, checked = cellrun.check(w, sess.inputs, sess.geom,
                                              sess.op, sess.n, cell.config)
            (lower if mode == "program" else upper).append(worst)
            emit(json.dumps({"mode": mode, "seed": seed,
                             "max_abs_gap": worst, "pairs_checked": checked,
                             "calls": len(w.calls), "failed": w.failed,
                             "window_s": w.seconds}))
            first = None
    sess.close()
    return (max(lower) if lower else None, min(upper) if upper else None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax

    from accl_tpu.utils import compile_cache
    from benchmark import spec

    cell = spec.resolve_cell(args.workload, ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 1
    compile_cache.enable(ROOT)
    jax.config.update("jax_compilation_cache_max_size", -1)
    lo, up = readings(cell, devices[:cell.chips], args.seeds,
                      args.control_seeds, args.seconds)
    print(json.dumps({"lower": lo, "upper": up,
                      "seconds": time.perf_counter() - T_PROCESS}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
