"""Share of the window's wall that Python's collector held the process:
the sum of `host_gc_ms` (every thread's collections, timed by the program's
one `gc.callbacks` hook) on the window's logging records over the sum of
their `interval_ms`, the window's first record left out. A pause while the
loop waits for the device costs the rate nothing; one just after the fetch
idles the chip for all of it."""


def read(ctx):
    recs = [r for r in ctx.get("records", ())
            if r.get("kind") == "train_step" and "interval_ms" in r][1:]
    wall = sum(r["interval_ms"] for r in recs)
    if not wall:
        return None
    return 100.0 * sum(r["host_gc_ms"] for r in recs) / wall
