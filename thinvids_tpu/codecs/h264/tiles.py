"""128-lane column tiles of a plane, and the constant matrices that
work on them: what the P-frame residual (jaxinter) and the motion
search's probe (jaxme) share.

A TPU lays an array's minor dimension on 128 lanes, so a plane viewed
as (H, W // 4, 4) is a relayout at 32 times the bytes, and a lane-
strided slice of it a pass of the vector unit per element (PERF.md §6,
PR 37, PR 40 and PR 42). Here a plane is taken ONCE to (T, H, 128) — its
128-lane column tiles one after another, whole (8, 128) tiles moved,
none re-laid — and every step with a 4x4 (8x8, 16x16) structure is a
constant block-diagonal or 0/1 pooling matrix on the matrix unit: along
lanes `tiles @ kron(I, core^T)`, along rows `kron(I, core) @ rows` in
groups of 16. Blocks never straddle a tile (4, 8 and 16 divide 128).
Integers ride as f32 at Precision.HIGHEST, which is exact while every
partial sum stays below 2**24. A step that only sums (the probe's 4x4
box sums, jaxme._box_sum) needs no tiles: it adds rows by row-strided
slices, which move whole 128-lane rows, and pools the lanes of
`_lane_rows` of the result.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

_LANES = 128
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32

# sum of each 4 rows, left in the first of them
_SUM4 = np.zeros((4, 4), np.float32)
_SUM4[0, :] = 1


def _block_diag(core, n: int):
    return np.kron(np.eye(n // core.shape[0], dtype=np.float32), core)


def _lane_rows(x):
    """(H, W) plane -> (H, T, 128): every row cut into its 128-lane
    pieces (W padded with zeros to a multiple of 128)."""
    H, W = x.shape
    T = -(-W // _LANES)
    if T * _LANES != W:
        x = jnp.pad(x, ((0, 0), (0, T * _LANES - W)))
    return x.reshape(H, T, _LANES)


def _to_tiles(x):
    """(H, W) plane -> (T, H, 128): its 128-lane column tiles (W padded
    with zeros to a multiple of 128)."""
    return _lane_rows(x).transpose(1, 0, 2)


def _from_tiles(x, W: int):
    T, H, _ = x.shape
    y = x.transpose(1, 0, 2).reshape(H, T * _LANES)
    return y if T * _LANES == W else y[:, :W]


def _lane_mm(x, core):
    """`core` applied to every group of core.shape[0] lanes of (T, H, 128)
    tiles; f32 out."""
    m = jnp.asarray(_block_diag(core, _LANES).T)
    return jnp.einsum("thl,lm->thm", x.astype(_F32), m, precision=_HI)


def _row_mm(x, core):
    """`core` applied to every group of core.shape[0] rows; f32 out."""
    T, H, L = x.shape
    k = 16 if H % 16 == 0 else 8
    a = jnp.asarray(_block_diag(core, k))
    return jnp.einsum("ij,bjl->bil", a,
                      x.astype(_F32).reshape(T * H // k, k, L),
                      precision=_HI).reshape(T, H, L)


def _pool(group: int):
    """(128, 128 // group) 0/1: lane l feeds column l // group."""
    return jnp.asarray((np.arange(_LANES)[:, None] // group
                        == np.arange(_LANES // group)[None, :]
                        ).astype(np.float32))


def _lane_pool(x, group: int):
    """(T, R, 128) -> (T, R, 128 // group): sums of `group` lanes, f32."""
    return jnp.einsum("trl,lm->trm", x.astype(_F32), _pool(group),
                      precision=_HI)


def _lane_spread(x, group: int):
    """(T, R, 128 // group) -> (T, R, 128): each entry over its `group`
    lanes — :func:`_lane_pool`'s transpose."""
    return jnp.einsum("trm,lm->trl", x.astype(_F32), _pool(group),
                      precision=_HI)


def _tile_maps(x, n: int):
    """(T, R, g) small per-tile maps -> (R, n): tiles side by side."""
    T, R, g = x.shape
    return x.transpose(1, 0, 2).reshape(R, T * g)[:, :n]
