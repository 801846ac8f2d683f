"""Synthetic image datasets (this container has no dataset downloads: zero
egress, no torchvision/tfds). Procedural images with real part-whole
structure — random colored rectangles and circles on textured backgrounds —
so the denoising objective has actual signal to learn, unlike pure noise.

Deterministic given a seed; generation is numpy on the host, batches are
handed to JAX as float32 [b, c, H, W] in [-1, 1].
"""

from __future__ import annotations

from typing import Iterator, Optional

import numpy as np


def _draw_shapes(rng: np.random.Generator, size: int, num_shapes: int) -> np.ndarray:
    """One [3, size, size] image in [-1, 1]."""
    img = np.ones((3, size, size), np.float32) * rng.uniform(-0.4, 0.4, (3, 1, 1))
    yy, xx = np.mgrid[0:size, 0:size]
    for _ in range(num_shapes):
        color = rng.uniform(-1, 1, (3, 1, 1)).astype(np.float32)
        kind = rng.integers(0, 2)
        if kind == 0:  # rectangle
            x0, y0 = rng.integers(0, size, 2)
            w, h = rng.integers(size // 8, size // 2, 2)
            mask = (xx >= x0) & (xx < x0 + w) & (yy >= y0) & (yy < y0 + h)
        else:  # circle
            cx, cy = rng.integers(0, size, 2)
            r = rng.integers(size // 10, size // 3)
            mask = (xx - cx) ** 2 + (yy - cy) ** 2 < r ** 2
        img = np.where(mask[None], color, img)
    return np.clip(img, -1.0, 1.0)


def shapes_dataset(
    batch_size: int,
    image_size: int,
    *,
    seed: int = 0,
    num_shapes: int = 5,
    num_batches: Optional[int] = None,
) -> Iterator[np.ndarray]:
    """Infinite (or bounded) iterator of [b, 3, H, W] float32 batches."""
    rng = np.random.default_rng(seed)
    produced = 0
    while num_batches is None or produced < num_batches:
        batch = np.stack(
            [_draw_shapes(rng, image_size, num_shapes) for _ in range(batch_size)]
        )
        yield batch
        produced += 1


def gaussian_dataset(
    batch_size: int, image_size: int, *, seed: int = 0
) -> Iterator[np.ndarray]:
    """Pure-noise images — for smoke tests and benchmarks where content is
    irrelevant and generation speed matters."""
    rng = np.random.default_rng(seed)
    while True:
        yield rng.normal(size=(batch_size, 3, image_size, image_size)).astype(
            np.float32
        )


def token_dataset(
    batch_size: int, seq_len: int, vocab_size: int, *, seed: int = 0
) -> Iterator[np.ndarray]:
    """Infinite iterator of [b, seq_len] int32 token ids, uniform over the
    vocabulary rows held: packed sequences for the language-model objective
    (no document boundaries; content is irrelevant to speed and to the
    comparison with the reference)."""
    rng = np.random.default_rng(seed)
    while True:
        yield rng.integers(0, vocab_size, (batch_size, seq_len), dtype=np.int32)


def write_shapes_dataset(
    out_dir: str,
    num_images: int,
    image_size: int,
    *,
    seed: int = 0,
    fmt: str = "png",
    shard_size: int = 512,
) -> list:
    """Render the seeded shapes distribution to DISK — the deterministic
    on-disk dataset that backs the file-based input-pipeline record (the
    environment has no downloadable datasets; the reference README trains
    on real images from the user's own folder, ~:30-75).

    fmt='png': one 8-bit RGB PNG per image (exercises the image-decode
    loader, image_folder_dataset). fmt='npy': [shard_size, 3, H, W]
    float32 shards (npy_dataset). Returns the list of file paths written.
    """
    import os

    os.makedirs(out_dir, exist_ok=True)
    rng = np.random.default_rng(seed)
    paths = []
    if fmt == "png":
        from PIL import Image

        for i in range(num_images):
            img = _draw_shapes(rng, image_size, 5)  # [3, H, W] in [-1, 1]
            u8 = ((np.transpose(img, (1, 2, 0)) + 1.0) * 127.5).round()
            u8 = np.clip(u8, 0, 255).astype(np.uint8)
            p = os.path.join(out_dir, f"shape_{i:06d}.png")
            Image.fromarray(u8).save(p)
            paths.append(p)
        return paths
    if fmt == "npy":
        for s in range(0, num_images, shard_size):
            count = min(shard_size, num_images - s)
            shard = np.stack(
                [_draw_shapes(rng, image_size, 5) for _ in range(count)]
            )
            p = os.path.join(out_dir, f"shard_{s // shard_size:04d}.npy")
            np.save(p, shard)
            paths.append(p)
        return paths
    raise ValueError(f"fmt={fmt!r}: one of 'png', 'npy'")
