"""plugin_roofline: the plugin kernel's share of its HBM roofline on one
rank, in %: the least time of every call in the traced window
(``work.py``: on one rank only the HBM bound applies, both operands read
and the result written, by op and size, never by the implementation
chosen) over the chip's busy time. Nothing on more than one rank."""
from benchmark import work


def read(run):
    tr, peak = run["trace"], run["peak"]
    if tr is None or peak is None or tr.busy_max_s <= 0 or run["n"] != 1:
        return None
    least = sum(work.least_time_s(p[0], g, 1, run["itemsize"], peak)
                for p, g, _ in run["calls"])
    return least / tr.busy_max_s * 100.0
