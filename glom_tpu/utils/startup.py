"""Process start-up: where compiled programs are cached and which device
the process runs on.

Two rules every entry point shares. The platform is whatever the caller's
environment gives JAX (`JAX_PLATFORMS`, or JAX's own default) — nothing in
this tree chooses one. The persistent compilation cache lives where
`JAX_COMPILATION_CACHE_DIR` says, else at one fixed path inside the
checkout: the path is part of the cache key, so a directory that moves
never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache — derived from this file, never from the working
# directory, a pid, the time or tempfile.
_CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory.
    Entry points call this first, before anything compiles. With
    JAX_COMPILATION_CACHE_DIR set JAX has already read it and this sets
    nothing."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE))
    return str(_CHECKOUT_CACHE)


def device_summary() -> dict:
    """The device as JAX reports it — stamped on anything that claims to
    have run somewhere."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def require_tpu(what: str) -> dict:
    """device_summary(), or SystemExit when the default backend is not a
    TPU. Without a chip JAX prints a libtpu error and hands back the CPU;
    a path that needs the chip says so and stops."""
    dev = device_summary()
    if dev["platform"] != "tpu":
        raise SystemExit(
            f"{what} needs a TPU; jax.devices()[0].platform == "
            f"{dev['platform']!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r})"
        )
    return dev


def cpu_requested() -> bool:
    """True when the caller named the CPU as the platform to run on
    (JAX_PLATFORMS=cpu — CI's functional drives of the bench scripts).
    Neither the CPU JAX falls back to when it finds no chip nor the
    trailing host platform of "tpu,cpu" counts."""
    import jax

    return (jax.config.jax_platforms or "").split(",")[0] == "cpu"
