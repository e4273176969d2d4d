"""coll_roofline: the collective programs' share of their roofline, in
%: the least time of every call in the traced window (``work.py``: the
larger of its ICI and HBM bound, by op, size and ranks, never by the
algorithm chosen) over the busy time of the busiest chip."""
from benchmark import work


def read(run):
    tr, peak = run["trace"], run["peak"]
    if tr is None or peak is None or tr.busy_max_s <= 0 or run["n"] < 2:
        return None
    least = sum(work.least_time_s(p[0], g, run["n"], run["itemsize"], peak)
                for p, g, _ in run["calls"])
    return least / tr.busy_max_s * 100.0
