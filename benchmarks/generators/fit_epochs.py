"""Training data: one shape, filled from the seed.

`generate` makes the host dataset `fit()` walks: `batches_per_epoch`
batches of `global_batch` float32 sequences and their labels. The fill
is threaded by batch (numpy's generators release the interpreter lock),
because every run of every check pays it as set-up.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np


def generate(traffic: dict, seed: int, seq_len: int, hidden: int) -> dict:
    batch = int(traffic["global_batch"])
    nb = int(traffic["batches_per_epoch"])
    scale = np.float32(traffic.get("label_scale", 1.0))
    x = np.empty((nb * batch, seq_len, hidden), np.float32)
    y = np.empty((nb * batch, seq_len, 1), np.float32)

    def fill(i):
        rng = np.random.Generator(np.random.PCG64([int(seed), i]))
        rng.standard_normal(out=x[i * batch:(i + 1) * batch], dtype=np.float32)
        rows = y[i * batch:(i + 1) * batch]
        rng.standard_normal(out=rows, dtype=np.float32)
        rows *= scale

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(fill, range(nb)))
    return {
        "x": x,
        "label": y,
        "global_batch": batch,
        "batches_per_epoch": nb,
        "tokens_per_epoch": nb * batch * seq_len,
    }
