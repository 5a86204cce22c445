"""The benchmark's own weights and tables, made from ``--seed``.

A counter-based generator: the value of element (row, col) is a function
of the seed and its coordinates alone, so the program's table is filled on
the device in one jitted pass and the reference works out the initial
value of just the rows it touches, on the host, bit for bit the same,
without ever seeing the program's table.
"""

from __future__ import annotations

import numpy as np

_M1, _M2, _M3 = 0x9E3779B1, 0x7FEB352D, 0x846CA68B


def _mix(x, xp):
    u = xp.uint32
    x = x ^ (x >> u(16))
    x = x * u(0x85EBCA6B)
    x = x ^ (x >> u(13))
    x = x * u(0xC2B2AE35)
    return x ^ (x >> u(16))


def _seed32(seed: int, stream: int) -> int:
    s = (int(seed) * 0x9E3779B97F4A7C15 + int(stream) * 0xD1B54A32D192ED03)
    return (s ^ (s >> 32)) & 0xFFFFFFFF


def seed_key(seed: int, stream: int) -> np.uint32:
    """The 32-bit key of one stream of one seed. Passed to jitted code as
    an ARGUMENT: a seed baked in as a constant would make a new program,
    and a compile-cache miss, for every seed."""
    return np.uint32(_seed32(seed, stream))


def uniform_rows(key32, rows, dim: int, scale: float, xp=np):
    """[len(rows), dim] float32, uniform with standard deviation ``scale``;
    element (r, c) depends on (key32, r, c) only. ``xp`` is numpy or
    jax.numpy: both give the same bits."""
    u = xp.uint32
    r = rows.astype(xp.uint32)[:, None]
    c = xp.arange(dim, dtype=xp.uint32)[None, :]
    h = _mix(_mix(r * u(_M1) + xp.asarray(key32, dtype=xp.uint32), xp)
             + c * u(_M2) + u(_M3), xp)
    unit = (h >> u(8)).astype(xp.float32) * xp.float32(2.0 ** -24)
    return (unit * xp.float32(2.0) - xp.float32(1.0)) \
        * xp.float32(scale * 3.0 ** 0.5)


def fill_table(seed: int, stream: int, num_rows: int, dim: int,
               scale: float, sharding=None):
    """The whole table on the device, in its sharded layout, one jitted
    call; the same program for every seed."""
    import jax
    import jax.numpy as jnp

    def make(key32):
        return uniform_rows(key32, jnp.arange(num_rows, dtype=jnp.uint32),
                            dim, scale, xp=jnp)
    return jax.jit(make, out_shardings=sharding)(seed_key(seed, stream))


def leaf_values(key32, shape, scale: float, xp=np):
    """A dense leaf of ``shape`` with standard deviation ``scale``: the
    same generator over the flattened leaf as one column."""
    n = int(np.prod(shape))
    rows = xp.arange(n, dtype=xp.uint32)
    return uniform_rows(key32, rows, 1, scale, xp=xp).reshape(shape)
