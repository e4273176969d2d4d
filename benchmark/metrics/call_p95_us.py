"""call_p95_us: the 95th percentile, over every call completed in the
window (never of per-chunk figures), of its time from API entry to its
output being ready on the device (host clock), in us."""
import statistics


def read(run):
    t = [s for _, _, s in run["calls"]]
    if len(t) < 20:
        return None
    return statistics.quantiles(t, n=20)[-1] * 1e6
