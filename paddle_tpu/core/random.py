"""Stateful RNG facade over jax.random.

Reference parity: paddle/phi/core/generator.h (per-device Generator with
seed/offset) and python paddle.seed / get_rng_state. Upstream-canonical,
unverified (SURVEY.md §0).

Design: one global stateful Generator holding a jax PRNG key; every random op
splits it. For TP determinism the reference keeps RNGStatesTracker with
model-parallel seeds (fleet/layers/mpu/random.py); we mirror that with named
generators derived via fold_in — the TPU-native analog of per-mesh-axis seeds.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import numpy as np


class Generator:
    def __init__(self, seed: int = 0):
        self.manual_seed(seed)

    def manual_seed(self, seed: int) -> "Generator":
        self._seed = seed
        # the key is built on first use: making one initialises a jax
        # backend, and the module-level default generator must not do
        # that at import (a chip belongs to ONE process — a launcher
        # parent that merely imports the package would take it from its
        # own children)
        self._key = None
        return self

    def _live_key(self) -> jax.Array:
        if self._key is None:
            self._key = jax.random.key(self._seed)
        return self._key

    def split(self) -> jax.Array:
        self._key, sub = jax.random.split(self._live_key())
        return sub

    def get_state(self):
        return jax.random.key_data(self._live_key())

    def set_state(self, state):
        self._key = jax.random.wrap_key_data(np.asarray(state))

    @property
    def initial_seed(self) -> int:
        return self._seed


_default_generator = Generator(0)
_named: Dict[str, Generator] = {}


def default_generator() -> Generator:
    return _default_generator


def seed(s: int) -> Generator:
    """paddle.seed — reseed the global generator (and named trackers)."""
    import zlib

    _default_generator.manual_seed(s)
    for name, g in _named.items():
        # stable per-name offset (python hash() is randomized per process)
        g.manual_seed(s ^ zlib.crc32(name.encode()))
    return _default_generator


def next_key() -> jax.Array:
    return _default_generator.split()


def get_rng_state():
    return _default_generator.get_state()


def set_rng_state(state):
    _default_generator.set_state(state)


class RNGStatesTracker:
    """fleet.meta_parallel.get_rng_state_tracker parity: named RNG streams so
    TP-replicated regions (dropout on replicated activations) share randomness
    while TP-sharded regions differ. TPU-native: fold_in the mesh-axis index."""

    def __init__(self):
        self._gens: Dict[str, Generator] = {}

    def add(self, name: str, seed_: int) -> None:
        if name in self._gens:
            raise ValueError(f"rng state {name} already exists")
        g = Generator(seed_)
        self._gens[name] = g
        _named[name] = g

    def get_states_tracker(self):
        return {k: g.get_state() for k, g in self._gens.items()}

    def set_states_tracker(self, states) -> None:
        for name, st in states.items():
            if name not in self._gens:
                self.add(name, 0)
            self._gens[name].set_state(st)

    def reset(self) -> None:
        """Drop all named streams (and their paddle.seed registrations)."""
        for name in self._gens:
            _named.pop(name, None)
        self._gens.clear()

    def rng_state(self, name: str = "model_parallel_rng"):
        import contextlib

        @contextlib.contextmanager
        def _ctx():
            global _default_generator
            if name not in self._gens:
                # decorrelate from the global stream (same rule as seed()):
                # an auto-added stream seeded with initial_seed verbatim
                # would replay the global generator's draws exactly
                import zlib
                self.add(name, _default_generator.initial_seed
                         ^ zlib.crc32(name.encode()))
            prev = _default_generator
            _default_generator = self._gens[name]
            try:
                yield
            finally:
                _default_generator = prev

        return _ctx()


_tracker: Optional[RNGStatesTracker] = None


def get_rng_state_tracker() -> RNGStatesTracker:
    global _tracker
    if _tracker is None:
        _tracker = RNGStatesTracker()
    return _tracker
