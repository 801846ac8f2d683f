"""Rows served over bucket rows dispatched, from the dispatch records."""


def read(ctx):
    d = [r for r in ctx.get("dispatches", ()) if isinstance(r.get("bucket"), int)]
    rows = sum(r["bucket"] for r in d)
    return 100.0 * sum(r["n_valid"] for r in d) / rows if rows else None
