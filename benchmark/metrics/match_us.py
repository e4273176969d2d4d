"""match_us.<cell kind>: mean self time of the library's ``accl.match``
span in the traced window, in us: send/recv matching, that is
``recv_capacity``, the rx-pool reserve and ``post_send`` /
``post_recv`` (a delivery launched inside it is a child span, not self
time). Read from ``accl_span_self_seconds{span="accl.match"}`` (sum over
count)."""

KEY = 'accl_span_self_seconds{span="accl.match"}'


def read(run):
    h = run["metrics_delta"].get("histograms", {}).get(KEY)
    if not h or not h["count"]:
        return None
    return h["sum"] / h["count"] * 1e6
