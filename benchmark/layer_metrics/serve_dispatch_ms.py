"""Median engine wall time of a dispatch (`ServeResult.latency_s`:
dispatch to fetched result). The dispatch record's latency_ms adds the
batcher's queue_wait and pack phases to it, so they are taken off again;
the record's device_ms (a host subtraction) is not read."""

import statistics


def read(ctx):
    walls = [r["latency_ms"] - r["queue_wait_ms"] - r["pack_ms"]
             for r in ctx.get("dispatches", ())
             if None not in (r.get("latency_ms"), r.get("queue_wait_ms"), r.get("pack_ms"))]
    return statistics.median(walls) if walls else None
