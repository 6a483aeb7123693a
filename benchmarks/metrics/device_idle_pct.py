"""device_idle_pct (%, device trace): 1 - (union of the intervals in which
any operation runs on the device) / the traced window."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["busy_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
