"""Per-rank process launcher — the ``mpirun`` analog for the emulator rung.

The reference test ladder launches one driver process per rank with
``mpirun -np P`` against per-rank emulator processes (SURVEY.md §3.5,
``.github/workflows/build-and-test.yml``). This launcher does the same for
the TPU build's CPU emulator rung:

    python -m accl_tpu.launch -np 2 [--devices-per-proc 2] prog [args...]

``prog`` may be a Python script (run under the current interpreter) or any
executable (e.g. ``pytest``). Each child gets the ``ACCL_*`` launch
environment; :func:`accl_tpu.multiproc.ensure_initialized` (invoked on
``import accl_tpu``) connects it to process 0's coordination service, so
worker scripts need no boilerplate.

On TPU hosts the launcher refuses to run: a chip belongs to one process
at a time, so one process per host opens ``accl_tpu.ACCL()`` over all of
its chips (multi-host pods use the platform's one-process-per-host
launcher); the in-framework code paths are identical.
"""
from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys
import uuid
import time
from typing import List, Optional, Sequence


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def launch(
    nprocs: int,
    argv: Sequence[str],
    devices_per_proc: int = 1,
    timeout: Optional[float] = None,
    extra_env: Optional[dict] = None,
    platform: str = "cpu",
) -> int:
    """Spawn ``nprocs`` copies of ``argv`` with the launch environment.

    Returns the first nonzero child exit code (0 if all succeeded). On any
    child failure the remaining children are terminated, mirroring
    ``mpirun`` abort semantics.
    """
    if nprocs < 1:
        raise ValueError("need at least one process")
    if platform != "cpu":
        raise ValueError(
            f"--platform {platform} refused: the launcher starts {nprocs} "
            f"processes on THIS host, and each would try to take every "
            f"chip it can see, while a chip belongs to one process at a "
            f"time. On TPU run one process per host that opens "
            f"accl_tpu.ACCL() over all its chips (python chip_smoke.py "
            f"--chips 4); the launcher is the CPU emulator rung only")
    coord = f"127.0.0.1:{_free_port()}"
    # job-unique session nonce: cross-process keys that must never
    # collide with an earlier (possibly crashed) run on a long-lived
    # coordination service derive from this instead of shared KV
    # counters whose alignment a single crash can poison (ADVICE r4 #1)
    session = uuid.uuid4().hex
    cmd = list(argv)
    if cmd and cmd[0].endswith(".py"):
        cmd = [sys.executable] + cmd

    procs: List[subprocess.Popen] = []
    for pid in range(nprocs):
        env = dict(os.environ)
        env.update(extra_env or {})
        env["ACCL_COORDINATOR"] = coord
        env["ACCL_NUM_PROCS"] = str(nprocs)
        env["ACCL_PROC_ID"] = str(pid)
        env["ACCL_SESSION"] = session
        env["ACCL_DEVS_PER_PROC"] = str(devices_per_proc)
        # ACCL_PLATFORM beats an inherited JAX_PLATFORMS:
        # ensure_initialized pins the children to the CPU via jax.config
        env["ACCL_PLATFORM"] = platform
        # children must be able to import accl_tpu no matter where the
        # launcher was invoked from — export the package's parent directory
        pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
        procs.append(subprocess.Popen(cmd, env=env))

    # poll all children concurrently: the FIRST failure aborts the job
    # (mpirun abort semantics) — a sequential wait would sit on a blocked
    # early child while a later one is already dead
    deadline = time.monotonic() + timeout if timeout else None
    rc = 0
    try:
        remaining = set(range(nprocs))
        while remaining and rc == 0:
            for i in list(remaining):
                code = procs[i].poll()
                if code is not None:
                    remaining.discard(i)
                    if code != 0:
                        rc = code
                        break
            if deadline and time.monotonic() > deadline:
                rc = 124
            if remaining and rc == 0:
                time.sleep(0.05)
    finally:
        if rc != 0:
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
    return rc


def main(args: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m accl_tpu.launch",
        description="Launch one accl_tpu controller process per rank group.",
    )
    ap.add_argument("-np", "--nprocs", type=int, required=True,
                    help="number of processes")
    ap.add_argument("--devices-per-proc", type=int, default=1,
                    help="virtual CPU devices per process (emulator rung)")
    ap.add_argument("--timeout", type=float, default=None,
                    help="per-child wall-clock limit in seconds")
    ap.add_argument("--platform", default="cpu",
                    help="jax platform for the children: only 'cpu' (the "
                         "emulator rung); several processes per host "
                         "cannot share its chips")
    ap.add_argument("prog", nargs=argparse.REMAINDER,
                    help="program and arguments to run per process")
    ns = ap.parse_args(args)
    if not ns.prog:
        ap.error("missing program to launch")
    try:
        return launch(ns.nprocs, ns.prog,
                      devices_per_proc=ns.devices_per_proc,
                      timeout=ns.timeout, platform=ns.platform)
    except ValueError as e:
        ap.error(str(e))


if __name__ == "__main__":
    sys.exit(main())
