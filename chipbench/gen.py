"""The one traffic generator: a mix file's parameters and a seed in, the
pool of batches a cell feeds out.

Every value is drawn from ``--seed``; the sizes (batch, sequence, image)
come from the mix and the configuration alone, so every seed gives the
same shapes and the same amount of work, in other values.
"""

from __future__ import annotations

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any whole seed works."""
    return np.random.default_rng([int(seed) % 2**64, stream])


def token_pool(mix: dict, vocab: int, seed: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """``mix["pool"]`` batches of (token ids, target ids), each (batch*seq,).

    Ids follow Zipf's law over the whole vocabulary (frequency of rank r
    ~ r**-zipf_exponent), so frequent tokens recur and the map can be
    learnt; each target is ``(mul * id + add) mod vocab``.
    """
    if mix["ids"] != "zipf":
        raise ValueError(f"unknown id distribution {mix['ids']!r}")
    rows = mix["batch"] * mix["seq"]
    freq = np.arange(1, vocab + 1, dtype=np.float64) ** -float(mix["zipf_exponent"])
    freq /= freq.sum()
    ids = rng(seed, 1).choice(vocab, size=(mix["pool"], rows), p=freq)
    tgt = (ids * mix["target"]["mul"] + mix["target"]["add"]) % vocab
    return [(ids[i], tgt[i]) for i in range(mix["pool"])]


def image_pool(mix: dict, image: tuple[int, int, int], classes: int,
               seed: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """``mix["pool"]`` batches of (images (batch, h, w, c) float32, labels).

    The frequency-band task: the image of class y is
    ``sin(u * (1 + y)) * cos(v * (1 + y))`` over a 4-pi grid, the same in
    every channel, plus gaussian pixel noise of std ``mix["noise"]``.
    Labels are uniform over the classes.
    """
    if mix["task"] != "frequency_band":
        raise ValueError(f"unknown image task {mix['task']!r}")
    h, w, c = image
    r = rng(seed, 2)
    labels = r.integers(0, classes, size=(mix["pool"], mix["batch"]))
    u = np.linspace(0, 3.14 * 4, w, dtype=np.float32)
    v = np.linspace(0, 3.14 * 4, h, dtype=np.float32)
    pool = []
    for y in labels:
        f = (1 + y)[:, None, None].astype(np.float32)
        base = np.sin(u[None, None, :] * f) * np.cos(v[None, :, None] * f)
        imgs = np.repeat(base[..., None], c, axis=-1)
        imgs += r.standard_normal(imgs.shape, dtype=np.float32) * np.float32(mix["noise"])
        pool.append((imgs.astype(np.float32), y))
    return pool
