"""JAX backend for the in-loop deblocking filter.

codecs/h264/deblock.py holds the single implementation of the §8.7
shifted-plane schedule, written against a tiny ops shim; this module
provides the jax.numpy shim so the SAME code traces into the jitted
encode programs (jaxinter.encode_gop_jit / encode_gop_planes / the SFE
band steps). One semantics, two backends — the numpy/JAX parity test
(tests/test_deblock.py) pins them bit-identical, which is what makes
encoder recon equal decoder output under the filter.

No `jax.jit` is defined here (the jit surface stays in the declared
modules — analysis/manifest.py); everything below is trace-time code
inside callers' programs.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from .deblock import deblock_frame
from .stages import stage


class _JaxOps:
    xp = jnp

    @staticmethod
    def scatter_cols(X, writes):
        for xs, vals in writes:
            X = X.at[:, xs].set(vals)
        return X

    @staticmethod
    def gather_cols(X, xs):
        return X[:, xs]

    @staticmethod
    def asarray(a):
        return jnp.asarray(a)


JAX_OPS = _JaxOps()


@stage("deblock")
def deblock_frame_jax(y, u, v, qp_map, *, intra: bool, nz4=None,
                      mv=None, mb_row0: int = 0,
                      total_mb_rows: int | None = None):
    """Traced deblock of one (padded) frame or band slice — see
    deblock.deblock_frame for the argument contract. Input planes keep
    their dtypes (int16 recon in, int16 out)."""
    return deblock_frame(y, u, v, qp_map, intra=intra, nz4=nz4, mv=mv,
                         mb_row0=mb_row0, total_mb_rows=total_mb_rows,
                         ops=JAX_OPS)


def nz4_from_luma_plane(z_plane, mbh: int, mbw: int):
    """(H, W) quantized luma coeff plane → (4·mbh, 4·mbw) any-nonzero
    per 4x4 block (the P-frame bS=2 input, computed on device from the
    same levels the packer ships)."""
    H, W = 16 * mbh, 16 * mbw
    b = z_plane[:H, :W].reshape(4 * mbh, 4, 4 * mbw, 4)
    return jnp.any(b != 0, axis=(1, 3))
