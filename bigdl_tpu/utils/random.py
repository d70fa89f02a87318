"""Reproducible random number generation.

TPU-native analog of the reference's Mersenne-Twister ``RandomGenerator``
(reference: utils/RandomGenerator.scala:23,56). Instead of a global mutable
MT19937 stream, we keep one global :class:`RandomGenerator` that owns a JAX
PRNG key and hands out fresh subkeys. Inside a traced (pure) application the
generator is *scoped*: ``push_key``/``pop_key`` bind a caller-supplied key so
the same layer code is deterministic and jit-safe (the traced key is threaded
in from the training step).
"""

from __future__ import annotations

import jax


class RandomGenerator:
    """A splittable PRNG stream with Torch-style set_seed semantics."""

    def __init__(self, seed: int = 1):
        self._seed = seed
        # made on first use: creating a key opens the device, and importing
        # the package must not (a parent that spawns device-owning workers
        # imports it too)
        self._root = None
        # Stack of externally pushed keys (used during pure/traced application).
        self._stack = []

    @property
    def _key(self):
        if self._root is None:
            self._root = jax.random.PRNGKey(self._seed)
        return self._root

    @_key.setter
    def _key(self, key):
        self._root = key

    def set_seed(self, seed: int) -> "RandomGenerator":
        self._seed = seed
        self._root = None
        return self

    def get_seed(self) -> int:
        return self._seed

    def push_key(self, key) -> None:
        """Bind an explicit key (e.g. a tracer) for the duration of a pure apply."""
        self._stack.append(key)

    def pop_key(self) -> None:
        self._stack.pop()

    @property
    def scoped(self) -> bool:
        return bool(self._stack)

    def next_key(self):
        """Return a fresh subkey, advancing whichever stream is active.

        The global (unscoped) stream is split under
        ``ensure_compile_time_eval`` so that a module called inside a raw
        ``jax.jit`` (instead of the sanctioned pure_apply/bind path, which
        pushes a scoped key) cannot poison the global key with a tracer —
        the split runs eagerly and the successor stays concrete."""
        if self._stack:
            self._stack[-1], sub = jax.random.split(self._stack[-1])
            return sub
        with jax.ensure_compile_time_eval():
            self._key, sub = jax.random.split(self._key)
        return sub

    def peek_key(self):
        """Current stream state WITHOUT advancing it. Re-binding this state
        via push_key replays the exact draw sequence that followed it (used
        by Module.backward to replay forward-time stochastic masks)."""
        return self._stack[-1] if self._stack else self._key

    # -- convenience samplers (eager use: weight init, data shuffling) -------
    def uniform(self, shape, minval=0.0, maxval=1.0, dtype="float32"):
        return jax.random.uniform(
            self.next_key(), shape, minval=minval, maxval=maxval, dtype=dtype
        )

    def normal(self, shape, mean=0.0, stdv=1.0, dtype="float32"):
        return mean + stdv * jax.random.normal(self.next_key(), shape, dtype=dtype)

    def permutation(self, n: int):
        return jax.random.permutation(self.next_key(), n)

    def bernoulli(self, shape, p):
        return jax.random.bernoulli(self.next_key(), p, shape)


#: Global generator, mirrors the reference's ``RandomGenerator.RNG`` singleton.
RNG = RandomGenerator(1)


def set_seed(seed: int) -> None:
    RNG.set_seed(seed)


def next_key():
    return RNG.next_key()
