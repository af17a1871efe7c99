"""A ratio of differences of the server's /stats counters between two
snapshots (`window_start`/`window_end`, or `trace_start`/`trace_end`). A key
may be dotted (`prefix_cache.hits`); `wall_ms` is the host-clock time between
the two snapshots."""


def read(ctx: dict, num: str, den: str, between: str = "window",
         scale: float = 1.0):
    a = ctx["stats"].get(between + "_start")
    b = ctx["stats"].get(between + "_end")
    if not a or not b:
        return None

    def diff(key: str) -> float:
        if key == "wall_ms":
            return (b["at"] - a["at"]) * 1e3
        x, y = a, b
        for part in key.split("."):
            x, y = x[part], y[part]
        return float(y) - float(x)

    d = diff(den)
    return scale * diff(num) / d if d > 0 else None
