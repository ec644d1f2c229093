"""Global RNG state.

Analog of the reference's ``phi::Generator`` (paddle/phi/core/generator.h) and
``paddle.seed``/``get_rng_state``. JAX RNG is functional (explicit keys), so the
eager layer keeps a splittable global generator: every eager random op splits
one subkey off the global state. Jit-traced model code should thread keys
explicitly (our nn layers take/derive keys from this generator at init time,
which happens eagerly, so initialization is reproducible under `seed`).
"""

from __future__ import annotations

import threading
from typing import List, Optional

import jax
import numpy as np

__all__ = ["Generator", "seed", "default_generator", "get_rng_state", "set_rng_state", "split_key"]


def _tracing() -> bool:
    return not jax.core.trace_ctx.is_top_level()


class Generator:
    """Splittable PRNG stream backed by a jax.random key.

    Trace-safe: inside a jit trace, keys are derived by fold_in on a host
    counter and the stored key is NEVER replaced with a traced value (a
    traced key would poison every later trace — UnexpectedTracerError).
    Inside one compiled program the derived keys are constants, so repeated
    executions reuse the same stream; compiled training steps that need
    fresh randomness per step thread a traced key via push_trace_key
    (to_static and ShardedTrainer both do).
    """

    def __init__(self, seed_: int = 0):
        self._seed = seed_
        # lazy: materializing a key initializes the jax backend, and the
        # module-level default Generator must not pin the backend at import
        # time (multi-host jax.distributed.initialize comes after import)
        self._key_ = None
        self._draws = 0
        self._lock = threading.Lock()

    @property
    def _key(self):
        if self._key_ is None:
            self._key_ = jax.random.key(self._seed)
        return self._key_

    @_key.setter
    def _key(self, k):
        self._key_ = k

    def manual_seed(self, seed_: int) -> "Generator":
        with self._lock:
            self._seed = seed_
            self._key_ = None
            self._draws = 0
        return self

    def initial_seed(self) -> int:
        return self._seed

    def split(self, num: int = 1):
        """Return `num` fresh subkeys, advancing the stream."""
        with self._lock:
            if _tracing():
                self._draws += 1
                base = jax.random.fold_in(self._key, self._draws)
                if num == 1:
                    return [base]
                return [jax.random.fold_in(base, i) for i in range(num)]
            keys = jax.random.split(self._key, num + 1)
            self._key = keys[0]
            self._draws = 0
            return list(keys[1:]) if num > 1 else [keys[1]]

    def get_state(self):
        return jax.random.key_data(self._key)

    def set_state(self, state) -> None:
        self._key = jax.random.wrap_key_data(np.asarray(state))


class _TraceKeyStack(threading.local):
    """When jit-tracing (to_static), random ops must draw from a *traced* key
    passed into the compiled function — otherwise the eager key would be baked
    in as a constant and every step would reuse the same dropout mask."""

    def __init__(self):
        self.stack: List = []


_trace_keys = _TraceKeyStack()


def push_trace_key(key) -> None:
    _trace_keys.stack.append(key)


def pop_trace_key() -> None:
    _trace_keys.stack.pop()


def in_trace() -> bool:
    return bool(_trace_keys.stack)


_default = Generator(0)


def default_generator() -> Generator:
    return _default


def seed(s: int) -> Generator:
    """paddle.seed analog: reset the global generator."""
    return _default.manual_seed(int(s))


def split_key(num: int = 1, generator: Optional[Generator] = None):
    if _trace_keys.stack:
        top = _trace_keys.stack[-1]
        keys = jax.random.split(top, num + 1)
        _trace_keys.stack[-1] = keys[0]
        return keys[1] if num == 1 else list(keys[1:])
    gen = generator or _default
    keys = gen.split(num)
    return keys[0] if num == 1 else keys


def get_rng_state():
    return _default.get_state()


def set_rng_state(state) -> None:
    _default.set_state(state)
