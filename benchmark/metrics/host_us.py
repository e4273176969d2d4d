"""host_us.<cell kind>: host time per call in the traced window, in us:
the self time of every library span (``accl_span_self_seconds{span}``)
but ``req.block``, the wait on the device, summed and divided by the
window's calls (a sendrecv call is a send and a recv)."""

PREFIX = 'accl_span_self_seconds{span="'
BLOCK = PREFIX + 'req.block"}'


def read(run):
    total, seen = 0.0, False
    for key, h in run["metrics_delta"].get("histograms", {}).items():
        if key.startswith(PREFIX) and key != BLOCK:
            total += h["sum"]
            seen = True
    if not seen or not run["calls"]:
        return None
    return total / len(run["calls"]) * 1e6
