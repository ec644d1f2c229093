"""Open loop: requests arrive on a schedule whatever the system does.

Mix parameters: ``rate_per_s`` (the cell's own, fixed from a sweep),
``arrivals`` (``poisson``), ``prompt_len``, ``output_len``, ``shape_seed``. Independent users make an open loop; a request's latency is
timed from the instant it was due, so a stall is charged to every request
that waited behind it. The schedule (arrival instants and sizes) is the
mix's own and the same for every ``--seed``; the seed makes the token ids.
"""

from __future__ import annotations

import numpy as np

from benchmark.traffic._lengths import request_shapes, token_ids


class Source:
    closed = False

    def __init__(self, mix: dict, seed: int, vocab: int, num_slots: int,
                 horizon_s: float):
        del num_slots
        rate = float(mix["rate_per_s"])
        base = np.random.default_rng([int(mix["shape_seed"]), 2])
        if mix["arrivals"] != "poisson":
            raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
        # the same schedule for every seed, and a longer horizon only
        # extends it: draw generously from the mix's own stream, cut where
        # the schedule passes the horizon
        gaps = base.exponential(1.0 / rate, int(rate * horizon_s * 1.5) + 64)
        n = int(np.searchsorted(np.cumsum(gaps), horizon_s)) + 1
        self.due_s = np.cumsum(gaps[:n])
        self.prompt_len, self.output_len = request_shapes(mix, n)
        self._rng = np.random.default_rng([int(seed), 4])
        self._vocab = vocab
        self._next = 0

    def due(self, now_s: float) -> list:
        """Requests due by ``now_s``: dicts with ``due_s``, ``prompt``,
        ``max_new_tokens``."""
        out = []
        while self._next < len(self.due_s) and \
                self.due_s[self._next] <= now_s:
            i = self._next
            out.append({"due_s": float(self.due_s[i]),
                        "prompt": token_ids(self._rng, self.prompt_len[i],
                                            self._vocab),
                        "max_new_tokens": int(self.output_len[i])})
            self._next += 1
        return out

    def next_due_s(self):
        return (float(self.due_s[self._next])
                if self._next < len(self.due_s) else None)

    def finished(self, now_s: float) -> None:
        """A request finished: nothing to do, arrivals do not wait."""
