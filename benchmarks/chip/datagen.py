"""Synthetic 8x8 digits for the paper-MLP cells, made from the run's seed.

A copy of the generator the system's examples use: ten glyph templates,
each sample shifted by up to one pixel, smoothed, scaled and given pixel
noise, intensities clipped to [0, 16].  The split and the iid partition
into client shards follow it too.  The benchmark makes its data here so
that its inputs do not move when the system's own helpers change.
"""
from __future__ import annotations

import numpy as np

__all__ = ["digits_task"]

_GLYPHS = (
    ("00111100", "01100110", "11000011", "11000011",
     "11000011", "11000011", "01100110", "00111100"),
    ("00011000", "00111000", "01111000", "00011000",
     "00011000", "00011000", "00011000", "01111110"),
    ("00111100", "01100110", "00000110", "00001100",
     "00011000", "00110000", "01100000", "01111110"),
    ("00111100", "01100110", "00000110", "00011100",
     "00000110", "00000110", "01100110", "00111100"),
    ("00001100", "00011100", "00110100", "01100100",
     "11111111", "00000100", "00000100", "00000100"),
    ("01111110", "01100000", "01100000", "01111100",
     "00000110", "00000110", "01100110", "00111100"),
    ("00011100", "00110000", "01100000", "01111100",
     "01100110", "01100110", "01100110", "00111100"),
    ("01111110", "00000110", "00001100", "00011000",
     "00110000", "00110000", "00110000", "00110000"),
    ("00111100", "01100110", "01100110", "00111100",
     "01100110", "01100110", "01100110", "00111100"),
    ("00111100", "01100110", "01100110", "00111110",
     "00000110", "00000110", "00001100", "00111000"),
)


def _digits(n: int, seed: int):
    rng = np.random.RandomState(seed)
    templates = np.array([[[int(c) for c in row] for row in g]
                          for g in _GLYPHS], np.float64) * 16.0
    labels = rng.randint(0, 10, size=n).astype(np.int32)
    imgs = np.empty((n, 8, 8), np.float64)
    for i, y in enumerate(labels):
        g = templates[y]
        dx, dy = rng.randint(-1, 2), rng.randint(-1, 2)
        g = np.roll(np.roll(g, dx, axis=0), dy, axis=1)
        scale = rng.uniform(0.7, 1.0)
        noise = rng.normal(0.0, 1.2, size=(8, 8))
        smooth = g + 0.25 * (np.roll(g, 1, 0) + np.roll(g, -1, 0)
                             + np.roll(g, 1, 1) + np.roll(g, -1, 1))
        imgs[i] = np.clip(scale * smooth / 2.0 + noise, 0.0, 16.0)
    return imgs.reshape(n, 64).astype(np.float32), labels


def digits_task(samples: int, test_frac: float, shards: int, seed: int):
    """→ (client shards [(x, y)], x_test, y_test, stacked x, stacked y).

    The stacked arrays hold every shard cycled to the longest one's
    length, ``(shards, n_per, 64)`` and ``(shards, n_per)``.
    """
    words = np.random.SeedSequence(seed).generate_state(3)
    x, y = _digits(samples, int(words[0]))
    perm = np.random.RandomState(int(words[1])).permutation(samples)
    n_test = int(samples * test_frac)
    te, tr = perm[:n_test], perm[n_test:]
    xtr, ytr = x[tr], y[tr]
    parts = np.array_split(
        np.random.RandomState(int(words[2])).permutation(len(ytr)), shards)
    clients = [(xtr[p], ytr[p]) for p in parts]
    n_max = max(len(p) for p in parts)
    sx = np.stack([np.resize(cx, (n_max, 64)) for cx, _ in clients])
    sy = np.stack([np.resize(cy, n_max) for _, cy in clients])
    return clients, x[te], y[te], sx, sy
