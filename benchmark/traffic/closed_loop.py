"""Closed loop: each client sends its next request when its last finished.

Mix parameters: ``clients_per_slot`` (clients = that times the engine's
slots, so the queue is never empty at 2), ``prompt_len``, ``output_len``,
``shape_seed``, ``pool`` (the size of the fixed set of shapes, cycled).
Callers that wait for a reply make a closed loop: offline batch inference.
A request is due the instant its client's previous one finished.
"""

from __future__ import annotations

import numpy as np

from benchmark.traffic._lengths import request_shapes, token_ids


class Source:
    closed = True

    def __init__(self, mix: dict, seed: int, vocab: int, num_slots: int,
                 horizon_s: float):
        del horizon_s
        self.clients = int(mix["clients_per_slot"]) * int(num_slots)
        self._shapes = request_shapes(mix, int(mix.get("pool", 512)))
        self._rng = np.random.default_rng([int(seed), 4])
        self._vocab = vocab
        self._i = 0
        self._ready = [0.0] * self.clients     # due times of waiting clients

    def _make(self, due_s: float) -> dict:
        p, o = self._shapes
        i = self._i % len(p)
        self._i += 1
        return {"due_s": due_s,
                "prompt": token_ids(self._rng, p[i], self._vocab),
                "max_new_tokens": int(o[i])}

    def due(self, now_s: float) -> list:
        out = [self._make(t) for t in self._ready if t <= now_s]
        self._ready = [t for t in self._ready if t > now_s]
        return out

    def next_due_s(self):
        return min(self._ready) if self._ready else None

    def finished(self, now_s: float) -> None:
        self._ready.append(now_s)
