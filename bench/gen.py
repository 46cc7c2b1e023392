"""Inputs from ``--seed``: the grid, the stencil coefficients, the receiver.

The same seed gives the same inputs, on any number of devices: the grid is
drawn on the device by one jitted call (``jax.random.uniform`` in
[-1, 1), as ``core/reference.random_grid`` draws it) straight into the
sharding it is served in, so set-up moves no grid through the host, and a
mesh cell never holds the whole grid on one chip.  Seeds of any size are
accepted (a ``SeedSequence`` folds them to a 64-bit key).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def _words(seed: int, stream: int) -> np.ndarray:
    return np.random.SeedSequence([int(seed), stream]).generate_state(
        2, np.uint32)


def key(seed: int, stream: int = 0):
    """A threefry key from ``(seed, stream)``."""
    return jax.random.wrap_key_data(jnp.asarray(_words(seed, stream)),
                                    impl="threefry2x32")


def grid(shape, dtype, seed: int, sharding=None):
    """The initial grid, uniform in [-1, 1), made on the device(s)."""
    draw = jax.jit(
        lambda k: jax.random.uniform(k, tuple(shape), jnp.dtype(dtype),
                                     minval=-1.0, maxval=1.0),
        out_shardings=sharding)
    return draw(key(seed, 0))


def coefficients(offsets, seed: int) -> dict:
    """Per-tap coefficients, drawn uniform in [0.2, 1) and scaled so that
    the taps sum to 0.5 beside a centre of 0.5: every step is an average,
    a constant grid is a fixed point, and long runs stay bounded."""
    rng = np.random.default_rng(_words(seed, 1))
    raw = rng.uniform(0.2, 1.0, len(offsets)).astype(np.float32)
    raw = (raw / (2.0 * raw.sum(dtype=np.float32))).astype(np.float32)
    return {"center": 0.5,
            "taps": [(tuple(o), float(c)) for o, c in zip(offsets, raw)]}


def receiver_rows(seed: int, extent: int, count: int):
    """Row indices read back after every call (receivers), from the seed."""
    rng = np.random.default_rng(_words(seed, 2))
    return sorted(int(r) for r in rng.choice(extent, count, replace=False))
