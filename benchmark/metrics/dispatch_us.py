"""dispatch_us.<cell kind>: mean host dispatch per collective call in
the window, in us: the library's ``accl_dispatch_seconds`` (API entry
to launch) sum over count, over the window."""


def read(run):
    total, count = 0.0, 0
    for key, h in run["metrics_delta"].get("histograms", {}).items():
        if key.startswith("accl_dispatch_seconds{"):
            total += h["sum"]
            count += h["count"]
    return total / count * 1e6 if count else None
