"""Seeded synthetic images, copied from the program's ``repro.data.synthetic``
(``make_covid_ct``, ``make_mura``) so that a later change there does not move
the benchmark's inputs. The program receives the arrays only."""
from __future__ import annotations

import zlib

import numpy as np

# MURA wrist counts (paper Table 2): total, positive
_MURA_WRIST = (9752, 3987)


def _lung_mask(hw: int, rng) -> np.ndarray:
    yy, xx = np.mgrid[0:hw, 0:hw].astype(np.float32) / hw
    cx1, cx2 = 0.32 + 0.03 * rng.standard_normal(), 0.68 + 0.03 * rng.standard_normal()
    cy = 0.5 + 0.02 * rng.standard_normal()
    r1 = ((xx - cx1) / 0.18) ** 2 + ((yy - cy) / 0.33) ** 2
    r2 = ((xx - cx2) / 0.18) ** 2 + ((yy - cy) / 0.33) ** 2
    return ((r1 < 1) | (r2 < 1)).astype(np.float32)


def covid_ct(n: int, hw: int, seed: int):
    """CT-like slices ``x [n, hw, hw, 1]`` in [0, 1] and labels ``y [n]``:
    positives carry ground-glass blobs inside the lungs."""
    rng = np.random.default_rng(seed)
    x = np.zeros((n, hw, hw, 1), np.float32)
    y = rng.integers(0, 2, size=n).astype(np.float32)
    yy, xx = np.mgrid[0:hw, 0:hw].astype(np.float32)
    for i in range(n):
        mask = _lung_mask(hw, rng)
        img = 0.15 + 0.05 * rng.standard_normal((hw, hw)).astype(np.float32)
        img += 0.35 * mask
        if y[i] > 0.5:
            for _ in range(rng.integers(2, 6)):
                cy, cx = rng.uniform(0.25 * hw, 0.75 * hw, size=2)
                s = rng.uniform(hw * 0.04, hw * 0.12)
                blob = np.exp(-(((yy - cy) ** 2 + (xx - cx) ** 2) / (2 * s * s)))
                img += 0.35 * blob * mask
        img += 0.04 * rng.standard_normal((hw, hw)).astype(np.float32)
        x[i, :, :, 0] = np.clip(img, 0, 1)
    return x, y


def mura_xray(n: int, hw: int, seed: int):
    """X-ray-like wrist images: a bright bone bar, and for positives a dark
    crack across it. Class balance from the paper's Table 2."""
    total, pos = _MURA_WRIST
    rng = np.random.default_rng(seed + zlib.crc32(b"wrist") % (1 << 16))
    x = np.zeros((n, hw, hw, 1), np.float32)
    y = (rng.random(n) < pos / total).astype(np.float32)
    yy, xx = np.mgrid[0:hw, 0:hw].astype(np.float32)
    for i in range(n):
        img = 0.1 + 0.03 * rng.standard_normal((hw, hw)).astype(np.float32)
        theta = rng.uniform(-0.5, 0.5)
        cx = hw / 2 + rng.uniform(-hw * 0.1, hw * 0.1)
        d = np.abs((xx - cx) + np.tan(theta) * (yy - hw / 2))
        bone = np.clip(1 - d / (hw * rng.uniform(0.06, 0.1)), 0, 1)
        img += 0.6 * bone
        if y[i] > 0.5:
            fy = rng.uniform(0.3 * hw, 0.7 * hw)
            fw = hw * rng.uniform(0.008, 0.02)
            img -= 0.5 * np.exp(-((yy - fy) ** 2) / (2 * fw * fw)) * bone
        img += 0.03 * rng.standard_normal((hw, hw)).astype(np.float32)
        x[i, :, :, 0] = np.clip(img, 0, 1)
    return x, y


DATASETS = {"covid_ct": covid_ct, "mura_xray": mura_xray}


def split_hospitals(x, y, shares, seed: int):
    """Random partition into hospital shards by share (the paper's 7:2:1
    protocol), copied from ``repro.data.split.split_clients``."""
    n = len(x)
    perm = np.random.default_rng(seed).permutation(n)
    shards, start = [], 0
    for i, s in enumerate(shares):
        size = n - start if i == len(shares) - 1 else int(round(n * s))
        idx = perm[start:start + size]
        shards.append((x[idx], y[idx]))
        start += size
    return shards
