"""Multi-process operation (the mpirun rung of the test ladder, SURVEY.md
§3.5/§4): the launcher spawns one controller process per rank group; the
workers exercise collectives, cross-process eager/rendezvous send/recv over
the DEVICE data plane, async protocol parity, sub-communicators and
comm-scoped barriers.

Parametrized over process x devices-per-process shapes like the reference
suite parametrizes rank counts (``test/host/xrt/include/fixture.hpp:48-144``).

Reference analog: ``mpirun -np P`` against per-rank emulator processes
(``test/host/xrt/include/fixture.hpp:48-144``, ``zmq_server.cpp``).
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_launcher(args, timeout=420):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("ACCL_COORDINATOR", None)  # never nest launch environments
    # the launcher pins JAX_PLATFORMS=cpu in the children
    return subprocess.run(
        [sys.executable, "-m", "accl_tpu.launch", *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize(
    "nprocs,dpp",
    [(2, 2), (4, 1), (3, 2)],
    ids=["2x2", "4x1", "3x2"],
)
def test_worker_matrix(nprocs, dpp):
    """The full mp_worker scenario suite across launch shapes: 2x2 (the
    round-2 shape), 4x1 (one rank per controller — no in-process pairs at
    all), 3x2 (odd process count; the {0,1,W-1} sub-communicator spans the
    processes unevenly: two ranks from p0, one from p2)."""
    res = _run_launcher(
        ["-np", str(nprocs), "--devices-per-proc", str(dpp),
         os.path.join("tests", "mp_worker.py")])
    sys.stdout.write(res.stdout)
    sys.stderr.write(res.stderr)
    assert res.returncode == 0, f"launcher rc={res.returncode}"
    assert res.stdout.count("MP-OK") == nprocs


def test_protocol_parity():
    """Cross-process protocol edge cases: out-of-order tag matching,
    TAG_ANY, async send/recv request lifecycle, rendezvous sender parking,
    eager credit backpressure, count-mismatch errors."""
    res = _run_launcher(
        ["-np", "2", "--devices-per-proc", "2",
         os.path.join("tests", "mp_worker_protocol.py")])
    sys.stdout.write(res.stdout)
    sys.stderr.write(res.stderr)
    assert res.returncode == 0, f"launcher rc={res.returncode}"
    assert res.stdout.count("MP-PROTOCOL-OK") == 2


def test_launcher_propagates_failure():
    """A failing child aborts the job with a nonzero exit (mpirun abort
    semantics)."""
    res = _run_launcher(
        ["-np", "2", "--devices-per-proc", "1",
         sys.executable, "-c", "raise SystemExit(3)"], timeout=120)
    assert res.returncode != 0


def test_launcher_rejects_missing_prog():
    res = _run_launcher(["-np", "2"], timeout=60)
    assert res.returncode != 0


def test_launcher_refuses_tpu_platform():
    """Several processes on one host cannot share its chips: the launcher
    is the CPU rung only and says so before starting anything."""
    from accl_tpu import launch

    with pytest.raises(ValueError, match="one process at a time"):
        launch.launch(2, [sys.executable, "-c", "pass"], platform="tpu")
    res = _run_launcher(["-np", "2", "--platform", "tpu",
                         sys.executable, "-c", "pass"], timeout=60)
    assert res.returncode != 0
    assert "refused" in res.stderr
