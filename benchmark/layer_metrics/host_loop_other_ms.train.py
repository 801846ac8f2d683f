"""Host time a step that belongs to none of the fit loop's three spans:
`fit`'s own work, the caller's loop between two `fit` calls, the writer, the
memory probe and, in a traced run, opening and closing the profiler. The sum
of `interval_other_ms` on the window's logging records over the sum of their
`interval_steps`, the window's first record left out."""


def read(ctx):
    recs = [r for r in ctx.get("records", ())
            if r.get("kind") == "train_step" and "interval_ms" in r][1:]
    steps = sum(r["interval_steps"] for r in recs)
    if not steps:
        return None
    return sum(r["interval_other_ms"] for r in recs) / steps
