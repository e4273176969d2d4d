"""algbw_GBps: nccl-tests' S summed over every call in the window, over
the window's wall time (host clock), in 1e9 bytes per second."""
from benchmark import work


def read(run):
    if not run["calls"] or run["window_s"] <= 0:
        return None
    total = sum(work.nccl_bytes(g, run["itemsize"])
                for _, g, _ in run["calls"])
    return total / run["window_s"] / 1e9
