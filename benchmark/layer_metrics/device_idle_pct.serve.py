"""Share of the traced seconds of steady traffic in which no operation ran
on the device."""


def read(ctx):
    t = ctx.get("trace")
    if not t or ctx.get("kind") != "serve":
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
