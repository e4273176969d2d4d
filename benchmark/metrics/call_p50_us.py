"""call_p50_us: the median, over every call completed in the window, of
its time from API entry to its output being ready on the device (host
clock), in us."""
import statistics


def read(run):
    t = [s for _, _, s in run["calls"]]
    return statistics.median(t) * 1e6 if t else None
