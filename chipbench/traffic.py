"""The one traffic generator. A mix is a data file, ``traffic/<name>.json``:

``{"loop": "closed", "outstanding": 768, "buckets": [256], "max_wait_ms": 20,
"pool": 4096}``
    Offline backlog: the client keeps ``outstanding`` single-image requests
    in the queue and submits more as answers come back.

``buckets`` and ``max_wait_ms`` configure the serving loop for the mix;
``pool`` is the number of distinct images requests are drawn from. Every
seed sends the same load; only the order of the images and the images
themselves change with it.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

_KEYS = {"closed": {"loop", "outstanding", "buckets", "max_wait_ms", "pool"}}


def load(path: Path) -> dict:
    """Read and check one mix."""
    mix = json.loads(Path(path).read_text())
    kind = mix.get("loop")
    if kind not in _KEYS:
        raise ValueError(f"{path}: 'loop' must be one of {sorted(_KEYS)}")
    unknown = set(mix) - _KEYS[kind]
    if unknown:
        raise ValueError(f"{path}: unknown keys {sorted(unknown)}")
    if mix["outstanding"] < 2 * max(mix["buckets"]):
        raise ValueError(f"{path}: a closed loop keeps at least two of the "
                         "largest batches outstanding")
    return mix


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), 3]))


def image_order(mix: dict, seed: int, n: int) -> np.ndarray:
    """Pool indices of the first ``n`` requests: every image once per pass
    over the pool, each pass in a fresh seeded order."""
    rng, pool = _rng(seed), mix["pool"]
    passes = -(-n // pool)
    return np.concatenate([rng.permutation(pool) for _ in range(passes)])[:n]
