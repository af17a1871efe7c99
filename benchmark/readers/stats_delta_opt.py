"""`stats_delta` over counters that a server may lack: the same ratio of
differences between two /stats snapshots, and None (nothing to read, the
metric is left out of the line) where a snapshot has no such key — a
program older than the counter. Only the missing key is forgiven: a key
that is there and is no number still raises."""

from readers import stats_delta


def read(ctx: dict, **args):
    try:
        return stats_delta.read(ctx, **args)
    except KeyError:
        return None
