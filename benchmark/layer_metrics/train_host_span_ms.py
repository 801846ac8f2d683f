"""Host time per step in the fit loop's own spans: host_data_next +
host_step_dispatch + host_log_fetch, from the program's span records over
the whole window."""

NAMES = ("host_data_next", "host_step_dispatch", "host_log_fetch")


def read(ctx):
    spans = [r for r in ctx.get("records", ())
             if r.get("kind") == "span" and r.get("name") in NAMES]
    if not spans or not ctx.get("steps"):
        return None
    return 1e3 * sum(r["dur_s"] for r in spans) / ctx["steps"]
