"""The one general generator: training images, serving images and arrival
times, all from `--seed` and the parameters of a traffic file.

The drawing and arrival code is the benchmark's copy of the program's sound
generators (`data/synthetic._draw_shapes`, `serve/workload._arrivals` and
`synth_input`), so that a later change to the program cannot move the
yardstick. Two departures, both for a steady amount of work per run:

* training images are drawn once, in set-up, as a pool of distinct batches
  that the window cycles through. The program's generator draws image by
  image on the host, several times slower than a training step on the chip
  (PERF.md section 6 has the reading), which would make the cell a
  measurement of numpy; a training job reads files. A traffic file that
  says `"data_source": "live"` gets the program's generator all the same
  (drivers/train.py), which is how that reading was taken.
* arrivals keep the exponential distribution of a Poisson process but not
  its chance: the gaps are the distribution's quantiles, put in an order
  drawn from the seed. Every seed gets the same set of gaps, so the same
  number of requests over the same time, in another order.
"""

from __future__ import annotations

import numpy as np


def _shapes(key, n: int, size: int, num_shapes: int = 5):
    """[n, 3, size, size] float32 in [-1, 1]: coloured rectangles and circles
    on a flat background (part-whole structure for the denoising loss), every
    image drawn from its own key."""
    import jax
    import jax.numpy as jnp

    def one(k):
        ks = jax.random.split(k, 1 + num_shapes)
        img = jnp.broadcast_to(
            jax.random.uniform(ks[0], (3, 1, 1), jnp.float32, -0.4, 0.4),
            (3, size, size))
        yy, xx = jnp.mgrid[0:size, 0:size]
        for i in range(num_shapes):
            kc, kk, kp, kw, kr = jax.random.split(ks[1 + i], 5)
            color = jax.random.uniform(kc, (3, 1, 1), jnp.float32, -1.0, 1.0)
            kind = jax.random.randint(kk, (), 0, 2)
            x0, y0 = jax.random.randint(kp, (2,), 0, size)
            w, h = jax.random.randint(kw, (2,), size // 8, size // 2)
            r = jax.random.randint(kr, (), size // 10, size // 3)
            rect = (xx >= x0) & (xx < x0 + w) & (yy >= y0) & (yy < y0 + h)
            circ = (xx - x0) ** 2 + (yy - y0) ** 2 < r ** 2
            img = jnp.where(jnp.where(kind == 0, rect, circ)[None], color, img)
        return jnp.clip(img, -1.0, 1.0)

    return jax.vmap(one)(jax.random.split(key, n))


def train_pool(seed: int, batch: int, size: int, n_batches: int) -> list:
    """`n_batches` distinct batches as host arrays (the trainer's feed
    uploads every step's batch, as a file reader's would); every row differs
    from every other. Drawn on the device in one jitted call."""
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) % (2**32)), 0x7261)
    imgs = jax.jit(_shapes, static_argnums=(1, 2))(key, n_batches * batch, size)
    imgs = np.asarray(imgs).reshape(n_batches, batch, 3, size, size)
    return [imgs[i] for i in range(n_batches)]


def cycle(pool: list, seed: int):
    """The pool's batches for ever: first in order (the steps `correct`
    follows read batches 0, 1, 2), then in orders drawn from the seed."""
    rng = np.random.default_rng([int(seed), 0x6F726472])
    order = list(range(len(pool)))
    while True:
        for i in order:
            yield pool[i]
        order = list(rng.permutation(len(pool)))


def serve_images(seed: int, n: int, shape) -> np.ndarray:
    """[n, c, H, W] float32 unit gaussians: stateless requests, as the
    program's `synth_input` makes them."""
    rng = np.random.default_rng([int(seed), 0x696D6773])
    return rng.standard_normal((n, *shape), dtype=np.float32)


def arrival_times(seed: int, rate_per_s: float, seconds: float) -> np.ndarray:
    """Due times in [0, seconds) of an open loop at `rate_per_s`: the
    n = rate * seconds quantiles of the exponential gap distribution,
    shuffled by the seed, rescaled so that the last request is due just
    inside the window."""
    n = max(1, int(round(rate_per_s * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate_per_s
    np.random.default_rng([int(seed), 0x61727276]).shuffle(gaps)
    t = np.cumsum(gaps)
    return t * (seconds * (n - 0.5) / n / t[-1])
