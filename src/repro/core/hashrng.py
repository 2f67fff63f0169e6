"""Counter-based deterministic hash RNG.

The influence matrix Q is pseudorandom and frozen for the whole training
run (paper §1.3).  We never materialize it: every consumer (the pure-jnp
reference oracle and the Pallas TPU kernel) regenerates indices/values
from the same counter-based hash.

Implementation notes:
 - all constants are numpy scalars / Python ints so they trace as jaxpr
   *literals*, never captured consts — a hard requirement inside
   ``pl.pallas_call`` kernel bodies;
 - static (Python/numpy int) words are folded in pure Python at trace
   time, so e.g. ``hash_u32(seed, tensor_id, rows, ctr)`` costs exactly
   one traced mix over ``rows``;
 - the mixer is the murmur3 finalizer (fmix32) over a xxhash-style
   running combine — not cryptographic, but distinct
   (seed, tensor, row, counter) tuples decorrelate (tested).
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

_M32 = 0xFFFFFFFF
_C1 = 0x85EBCA6B
_C2 = 0xC2B2AE35
_K1 = 0x9E3779B9  # golden-ratio increment
_K2 = 0x165667B1
_H0 = 0x2545F491

_INV_2_24 = np.float32(1.0 / (1 << 24))
_TWO_PI = np.float32(6.283185307179586)


def _is_static(x) -> bool:
    return isinstance(x, (int, np.integer))


def fmix32(h):
    """murmur3 32-bit finalizer (full avalanche). Static or traced."""
    if _is_static(h):
        h = int(h) & _M32
        h ^= h >> 16
        h = (h * _C1) & _M32
        h ^= h >> 13
        h = (h * _C2) & _M32
        h ^= h >> 16
        return h
    h = h ^ (h >> 16)
    h = h * np.uint32(_C1)
    h = h ^ (h >> 13)
    h = h * np.uint32(_C2)
    return h ^ (h >> 16)


def _combine(h, w):
    """h' = (h ^ fmix32(w + K1)) * K2 + K1 — identical static/traced."""
    if _is_static(h) and _is_static(w):
        return ((int(h) ^ fmix32((int(w) + _K1) & _M32)) * _K2 + _K1) & _M32
    if _is_static(w):
        w = np.uint32(int(w) & _M32)
        mixed = np.uint32(fmix32(int(w + np.uint32(_K1)) & _M32))
    else:
        w = jnp.asarray(w).astype(jnp.uint32)
        mixed = fmix32(w + np.uint32(_K1))
    if _is_static(h):
        h = np.uint32(h)
    return (h ^ mixed) * np.uint32(_K2) + np.uint32(_K1)


def hash_u32(*words):
    """Combine integer words (static ints or traced arrays) into one u32.

    ``hash_u32(seed, tensor_id, row, counter)`` is the canonical call of
    the Q generator.  Static prefix words fold at trace time.
    """
    h = _H0
    for w in words:
        h = _combine(h, w)
    out = fmix32(h)
    if _is_static(out):
        return np.uint32(out)
    return out


def u32_to_uniform(u):
    """u32 -> float32 uniform in (0, 1] (never 0: safe for log).

    The 24-bit value goes through int32, which is exact and is the cast
    the TPU kernel compiler accepts (it has no uint32 -> float32)."""
    u24 = (u >> np.uint32(8)).astype(jnp.int32)
    return u24.astype(jnp.float32) * _INV_2_24 + _INV_2_24


def gaussian_from_u32(u_a, u_b):
    """Two u32 streams -> standard normal via Box-Muller (cos branch)."""
    u1 = u32_to_uniform(u_a)
    u2 = u32_to_uniform(u_b)
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    return r * jnp.cos(_TWO_PI * u2)


def bernoulli_u32(u, p):
    """u32 stream + probabilities -> {0,1} float32 Bernoulli draws."""
    return (u32_to_uniform(u) <= p).astype(jnp.float32)
