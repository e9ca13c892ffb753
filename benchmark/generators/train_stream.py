"""Traffic of kind ``train_stream``: a seeded pool of distinct host-made
masked-LM batches, fed round-robin.  Parameters (the traffic file):
``pool_batches``, ``fetch_loss_every``; batch, sequence length and masked
positions per sequence are the configuration's ``train`` sizes."""
from __future__ import annotations

import numpy as np


def make(traffic: dict, cfg: dict, seed: int) -> list:
    """[(ids [B, s], masked positions [B, P], labels [B, P])] as int32
    host arrays; every row of every batch differs."""
    tr = cfg["train"]
    B, s, P = tr["batch"], tr["seq"], tr["masked_per_seq"]
    rng = np.random.default_rng(int(seed))
    pool = []
    for _ in range(int(traffic["pool_batches"])):
        ids = rng.integers(0, cfg["vocab_size"], (B, s), dtype=np.int32)
        pos = np.stack([np.sort(rng.choice(s, size=P, replace=False))
                        for _ in range(B)]).astype(np.int32)
        labels = np.take_along_axis(ids, pos, 1)
        pool.append((ids, pos, labels))
    return pool
