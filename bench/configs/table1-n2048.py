"""Plain reference for the Table-1 configuration (``table1-n2048.json``).

The window: S (n, m) with entries N(0, 1/m), drawn from a key. Its
damped solve has the general plain reference ``bench/solve_ref.py``.
Nothing of the program under test is imported.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make_window(key, n: int, m: int, dtype=jnp.float32):
    """S (n, m) with entries N(0, 1/m)."""
    return (jax.random.normal(key, (n, m), jnp.float32)
            / jnp.sqrt(m)).astype(dtype)
