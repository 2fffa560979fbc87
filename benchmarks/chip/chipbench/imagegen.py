"""The one input generator: every traffic mix is a data file it reads.

A mix (``traffic/<mix>.json``) gives the aspect ratios to draw from, the
noise level and how many batches the pool holds.  Each image draws one
aspect ratio, fills the largest rectangle of that ratio at the top left of
the square frame with class-conditional pattern plus Gaussian noise (the
content of the program's ``image_batch``), subtracts the mean over that
rectangle, and leaves every pixel below and right of it exactly zero, as
a pipeline that pads after normalising does.  With the one ratio 1:1 the
rectangle is the whole frame.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size: the high bits are folded in,
    where ``jax.random.key`` alone would drop them."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


def content_boxes(aspect_ratios: Sequence[Sequence[int]], size: int
                  ) -> np.ndarray:
    """(n_ratios, 4) int32 rows of (top, left, height, width) of the content
    rectangle for each width:height ratio in a size x size frame."""
    rows = []
    for a, b in aspect_ratios:
        if a <= 0 or b <= 0:
            raise ValueError(f"bad aspect ratio {a}:{b}")
        if a >= b:
            w, h = size, int(round(size * b / a))
        else:
            w, h = int(round(size * a / b)), size
        rows.append((0, 0, h, w))
    return np.asarray(rows, np.int32)


def batch_fn(traffic: dict, *, batch: int, image_size: int, channels: int,
             num_classes: int):
    """``f(key) -> (images (batch, S, S, C) f32, labels (batch,) i32)``."""
    boxes = jnp.asarray(content_boxes(traffic["aspect_ratios"], image_size))
    noise_std = float(traffic["noise_std"])
    s = image_size

    def f(key):
        k_lab, k_ratio, k_noise = jax.random.split(key, 3)
        labels = jax.random.randint(k_lab, (batch,), 0, num_classes)
        box = boxes[jax.random.randint(k_ratio, (batch,), 0, boxes.shape[0])]
        top, left, h, w = (box[:, i, None, None] for i in range(4))
        yy = jnp.arange(s)[None, :, None]
        xx = jnp.arange(s)[None, None, :]
        inside = (yy >= top) & (yy < top + h) & (xx >= left) & (xx < left + w)
        inside = inside[..., None]                       # (B, S, S, 1)
        base = jax.random.normal(k_noise, (batch, s, s, channels)) * noise_std
        freq = (labels[:, None].astype(jnp.float32) + 1) / num_classes
        grid = jnp.linspace(0, 3.14159 * 4, s)
        pat = (jnp.sin(freq * grid[None, :])[:, None, :, None]
               * jnp.cos(freq * grid[None, :])[:, :, None, None])
        img = jnp.where(inside, base + pat, 0.0)
        count = (h * w)[:, 0, 0] * channels
        mean = img.sum(axis=(1, 2, 3)) / count
        img = jnp.where(inside, img - mean[:, None, None, None], 0.0)
        return img.astype(jnp.float32), labels.astype(jnp.int32)

    return f


def zero_tile_fraction(x: jax.Array, rows: int = 128, cols: int = 128
                       ) -> jax.Array:
    """Share of all-zero (rows pixels x cols channels) tiles of an NHWC
    activation viewed as the (N*H*W, C) matrix the GEMMs tile; partial
    edge tiles count like whole ones."""
    m = x.reshape(-1, x.shape[-1])
    cols = min(cols, m.shape[1])
    pr, pc = -m.shape[0] % rows, -m.shape[1] % cols
    m = jnp.pad(m != 0, ((0, pr), (0, pc)))
    t = m.reshape(m.shape[0] // rows, rows, m.shape[1] // cols, cols)
    return (~t.any(axis=(1, 3))).mean()
