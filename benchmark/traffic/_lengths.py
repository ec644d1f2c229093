"""Length distributions shared by the serving generators.

Every seed gets the same sizes in the same order (drawn from the mix's own
``shape_seed``), so that a run's work does not depend on its seed: the seed
makes the token ids (and the weights), not the schedule. A tail over a few
hundred requests is set by where the long prompts cluster; were the order
drawn from the seed, runs with different seeds would measure different
queues, not the same system.
"""

from __future__ import annotations

import numpy as np


def draw(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths from ``spec``: ``{"dist": "lognormal",
    "median", "sigma", "min", "max"}`` or ``{"dist": "uniform", "min",
    "max"}``."""
    kind = spec["dist"]
    if kind == "lognormal":
        v = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif kind == "uniform":
        v = rng.uniform(spec["min"], spec["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.floor(v), spec["min"], spec["max"]).astype(np.int64)


def request_shapes(mix: dict, n: int):
    """(prompt_lens, output_lens): the mix's fixed sequence of ``n``
    shapes; a longer sequence starts with the shorter one."""
    p = draw(mix["prompt_len"], n,
             np.random.default_rng([int(mix["shape_seed"]), 0]))
    o = draw(mix["output_len"], n,
             np.random.default_rng([int(mix["shape_seed"]), 1]))
    return p, o


def token_ids(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, (int(n),), dtype=np.int32)
