"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, averaged over chips."""


def read(ctx: dict):
    t = ctx["trace"]
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
