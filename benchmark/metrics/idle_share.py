"""idle_share.<cell kind>: share of the traced window in which no op ran
on the busiest chip (the one chip, on one), in %."""


def read(run):
    tr = run["trace"]
    if tr is None or not tr.busy_s:
        return None
    return tr.idle_share(busiest=True) * 100.0
