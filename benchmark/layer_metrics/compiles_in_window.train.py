"""Programs JAX compiled or loaded between the window's start and end
(jax.monitoring events). Must read 0: set-up warms every shape."""


def read(ctx):
    if ctx.get("kind") != "train":
        return None
    return float(ctx["compiles_in_window"])
