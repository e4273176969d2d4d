"""busy_us.<cell kind>: the busiest chip's busy time in the traced
window (the union of its op intervals) over the window's calls, in us:
the collective programs' own device time per call, where calls are too
small for a roofline share to mean anything."""


def read(run):
    tr = run["trace"]
    if tr is None or not tr.busy_s or not run["calls"]:
        return None
    return tr.busy_max_s / len(run["calls"]) * 1e6
