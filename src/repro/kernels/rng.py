"""Hand-rolled threefry2x32 — the single source of every walk-step draw.

The engines key each random draw by ``(base_key, walk_id, hop, round)``
(see :mod:`repro.engines.step`), which upstream jax spells as nested
``jax.random.fold_in`` + ``jax.random.uniform``.  Those call into the
``threefry2x32`` *primitive*, whose CPU/TPU lowering Mosaic cannot ingest
inside a Pallas kernel body.  This module re-derives the same bits from
scratch with plain ``jnp`` elementwise ops — adds, xors, rotates — which
lower identically under jit, vmap and shard_map.  Every function here is
**bitwise identical** to its ``jax.random`` counterpart under the
partitionable threefry layout (``jax_threefry_partitionable=True``, the
default of the installed jax; pinned by ``tests/test_rng.py``), so the
fused Pallas advance kernel, the jitted JAX impl, and the distributed
sweep all draw the very same uniforms.

Keys are carried as a raw ``uint32`` pair ``(k0, k1)`` rather than jax key
arrays: Pallas refs are flat arrays, and the pair form broadcasts — fold a
scalar key against a ``[N]`` walk-id vector and every output is ``[N]``.

Bit layout (partitionable threefry; ``T(x0, x1)`` is one cipher call):

* ``fold_in(key, d)`` is ``T(0, uint32(d))``.
* draw ``i`` of ``uniform(key, shape)`` takes the bits
  ``T(0, i).out0 ^ T(0, i).out1`` — one cipher call per draw, so
  ``uniform(key, ())`` is draw 0 and ``uniform(key, (3,))`` draws 0, 1, 2.
* bits -> float32 in [0,1): ``bitcast((bits >> 9) | 0x3F800000) - 1.0``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["threefry2x32", "fold_in", "uniform1", "uniform3", "key_halves"]

#: threefry ks-parity constant (SHA-1 of "threefish", truncated)
_PARITY = 0x1BD11BDA
#: rotation distances — groups alternate between the two quadruples
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
#: key-injection schedule after each 4-round group: (into-x0, into-x1, tweak)
_INJECT = ((1, 2, 1), (2, 0, 2), (0, 1, 3), (1, 2, 4), (2, 0, 5))


def _rotl(x, r: int):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def threefry2x32(k0, k1, x0, x1):
    """The Threefry-2x32 block cipher (20 rounds), elementwise over arrays.

    All inputs broadcast against each other as ``uint32``; returns the two
    output words ``(y0, y1)``.  Matches ``jax.random.threefry_2x32`` bit for
    bit.
    """
    k0 = jnp.asarray(k0).astype(jnp.uint32)
    k1 = jnp.asarray(k1).astype(jnp.uint32)
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(_PARITY))
    y0 = jnp.asarray(x0).astype(jnp.uint32) + ks[0]
    y1 = jnp.asarray(x1).astype(jnp.uint32) + ks[1]
    for g, (ia, ib, tweak) in enumerate(_INJECT):
        for r in _ROTATIONS[g % 2]:
            y0 = y0 + y1
            y1 = _rotl(y1, r) ^ y0
        y0 = y0 + ks[ia]
        y1 = y1 + ks[ib] + jnp.uint32(tweak)
    return y0, y1


def fold_in(k0, k1, data):
    """``jax.random.fold_in`` on a raw key pair: returns the folded pair.

    ``data`` may be any int array/scalar (non-negative values reinterpret
    bit-exactly); broadcasting against the key pair is allowed.
    """
    zero = jnp.zeros((), jnp.uint32)
    return threefry2x32(k0, k1, zero, jnp.asarray(data).astype(jnp.uint32))


def _bits_to_unit(bits):
    """uint32 random bits -> float32 in [0, 1), jax.random.uniform's map."""
    mantissa = (bits >> jnp.uint32(9)) | jnp.uint32(0x3F800000)
    return jax.lax.bitcast_convert_type(mantissa, jnp.float32) - jnp.float32(1.0)


def _draw(k0, k1, i: int):
    """Draw ``i`` of ``jax.random.uniform`` under ``key = (k0, k1)``."""
    y0, y1 = threefry2x32(k0, k1, jnp.uint32(0), jnp.uint32(i))
    return _bits_to_unit(y0 ^ y1)


def uniform1(k0, k1):
    """``jax.random.uniform(key, ())`` for every key in the pair arrays."""
    return _draw(k0, k1, 0)


def uniform3(k0, k1):
    """``jax.random.uniform(key, (3,))`` per key: returns ``(u0, u1, u2)``."""
    return _draw(k0, k1, 0), _draw(k0, k1, 1), _draw(k0, k1, 2)


def key_halves(key):
    """Split a ``jax.random.PRNGKey`` (raw or typed) into ``(k0, k1)``."""
    kd = jnp.asarray(key)
    if kd.dtype != jnp.uint32:  # new-style typed key
        kd = jax.random.key_data(key)
    return kd[..., 0], kd[..., 1]
