"""launch_us.<cell kind>: mean self time of the library's
``accl.launch`` span in the traced window, in us: the program-cache
lookup, the program call, ``.astype`` and ``_store``. Read from
``accl_span_self_seconds{span="accl.launch"}`` (sum over count)."""

KEY = 'accl_span_self_seconds{span="accl.launch"}'


def read(run):
    h = run["metrics_delta"].get("histograms", {}).get(KEY)
    if not h or not h["count"]:
        return None
    return h["sum"] / h["count"] * 1e6
