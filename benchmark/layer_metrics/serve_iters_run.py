"""Mean early-exit iteration count of a dispatch."""


def read(ctx):
    it = [r["iters_run"] for r in ctx.get("dispatches", ())
          if r.get("iters_run") is not None]
    return sum(it) / len(it) if it else None
