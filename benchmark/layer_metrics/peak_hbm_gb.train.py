"""Peak device memory on the fullest chip after the window, in GB:
peak_bytes_in_use + peak_bytes_reserved of memory_stats() (the allocator
counts a running program's scratch as reserved)."""


def read(ctx):
    if ctx.get("kind") != "train":
        return None
    return ctx["peak_bytes"] / 1e9
