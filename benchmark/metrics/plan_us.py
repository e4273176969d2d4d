"""plan_us.<cell kind>: mean self time of the library's ``accl.plan``
span in the traced window, in us: schedule selection, that is
``_arith`` and the ``_spec_*`` call (``select_plan`` / ``synth.resolve``
and the program key). Read from
``accl_span_self_seconds{span="accl.plan"}`` (sum over count)."""

KEY = 'accl_span_self_seconds{span="accl.plan"}'


def read(run):
    h = run["metrics_delta"].get("histograms", {}).get(KEY)
    if not h or not h["count"]:
        return None
    return h["sum"] / h["count"] * 1e6
