"""Median of the dispatch records' queue_wait_ms over the window."""

import statistics


def read(ctx):
    waits = [r["queue_wait_ms"] for r in ctx.get("dispatches", ())
             if r.get("queue_wait_ms") is not None]
    return statistics.median(waits) if waits else None
