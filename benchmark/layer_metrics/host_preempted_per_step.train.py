"""Times a step that the scheduler took the core from the loop's thread
while it still wanted it: the sum of `host_nivcsw` (`getrusage(RUSAGE_THREAD)`'s
involuntary context switches, read by the program once a logging boundary)
on the window's logging records over the sum of their `interval_steps`, the
window's first record left out. It stands where `host_run_delay_pct.train`
would: the chip's machine has no /proc/thread-self/schedstat, so the program
cannot read how long the thread waited for a core there, only how often."""


def read(ctx):
    recs = [r for r in ctx.get("records", ())
            if r.get("kind") == "train_step" and "interval_ms" in r][1:]
    steps = sum(r["interval_steps"] for r in recs)
    if not steps:
        return None
    return sum(r["host_nivcsw"] for r in recs) / steps
