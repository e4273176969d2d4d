"""wait_us.<cell kind>: mean duration of the library's ``req.block``
span in the traced window, in us: the host blocked on the device
(``block_until_ready``) or on an external fulfilment. ``req.block``
opens no child span on these cells' paths, so its self time,
``accl_span_self_seconds{span="req.block"}`` (sum over count), is its
duration."""

KEY = 'accl_span_self_seconds{span="req.block"}'


def read(run):
    h = run["metrics_delta"].get("histograms", {}).get(KEY)
    if not h or not h["count"]:
        return None
    return h["sum"] / h["count"] * 1e6
