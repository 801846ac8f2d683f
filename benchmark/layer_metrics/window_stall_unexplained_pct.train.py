"""The part of `window_stall_pct.train` whose cause is `device_or_unknown`:
the excess lay in `host_log_fetch` and no host counter moved, so the device
took longer (a routed layer on its full row count) or the wait has a cause
the program's counters do not see. Same records, same denominator, the
window's first record left out."""


def read(ctx):
    recs = [r for r in ctx.get("records", ())
            if r.get("kind") == "train_step" and "interval_ms" in r][1:]
    wall = sum(r["interval_ms"] for r in recs)
    if not wall:
        return None
    return 100.0 * sum(r["stall_ms"] for r in recs
                       if r.get("stall_cause") == "device_or_unknown") / wall
