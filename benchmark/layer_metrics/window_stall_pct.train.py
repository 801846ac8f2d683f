"""Share of the window's wall that its logging intervals stood over what
such an interval usually takes: the sum of the `stall_ms` the program's
interval account put on the window's logging records (`spans.judge_interval`:
an interval over the median of the earlier ones of its step count by the
rule's threshold), over the sum of their `interval_ms`. The window's first record is
left out: its interval began before the window. 0 in a run that lost
nothing; nothing from a program whose records carry no interval."""


def read(ctx):
    recs = [r for r in ctx.get("records", ())
            if r.get("kind") == "train_step" and "interval_ms" in r][1:]
    wall = sum(r["interval_ms"] for r in recs)
    if not wall:
        return None
    return 100.0 * sum(r["stall_ms"] for r in recs) / wall
