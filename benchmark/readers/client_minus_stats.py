"""The client's median of a latency minus the server's own median of the
same: what the layers in front of the scheduler (HTTP, tokenizer, queue
hand-over, SSE write) add. The client's side is timed from the instant the
request was SENT, over every streamed request of the run (the server's
window of records cannot be cut to the measured window)."""

from metrics import percentile, ttfts_ms


def read(ctx: dict, stats: str):
    snap = ctx["stats"].get("window_end")
    mine = percentile(ttfts_ms(ctx["client"]["all_ok"], origin="sent"), 50)
    if not snap or snap.get(stats) is None or mine is None:
        return None
    return mine - float(snap[stats])
