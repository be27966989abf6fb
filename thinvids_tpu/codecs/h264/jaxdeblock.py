"""JAX backend for the in-loop deblocking filter.

codecs/h264/deblock.py holds the single implementation of the §8.7
filter (a wavefront over macroblocks in a skewed layout), written
against a tiny ops shim; this module provides the jax.numpy shim so
the SAME code traces into the jitted encode programs
(jaxinter.encode_gop_jit / encode_gop_planes / the SFE band steps).
The loop over wavefronts runs `deblock._wavefront_step` either way:

- on the TPU as ONE Pallas kernel per frame (`_scan_kernel`): the grid
  is the wavefront index, a step's blocks are brought to VMEM by the
  pipeline, and the two blocks in flight stay in VMEM scratch from
  step to step. One device op per frame, filed under `tvt.deblock`;
- on the CPU (tests, the benchmark's mirror) as a `lax.scan`, named
  `tvt.layout` as every loop of the device program is, its body
  `tvt.deblock`.

One semantics, two backends — the parity tests (tests/test_deblock.py)
pin them to the plain reference and to libavcodec, which is what makes
encoder recon equal decoder output under the filter.

No `jax.jit` is defined here (the jit surface stays in the declared
modules — analysis/manifest.py); everything below is trace-time code
inside callers' programs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import jaxme
from .deblock import deblock_frame
from .stages import stage

#: lanes of a vector register: the kernel's blocks are whole registers
_LANES = 128


def _scan_loop(step, carry, xs):
    """The wavefronts as a `lax.scan` (the XLA mirror of the kernel)."""
    def body(carry, blocks):
        with stage("deblock"):
            return step(carry, blocks)

    with stage("layout"):
        return jax.lax.scan(body, carry, xs)[1]


def _scan_kernel(step, carry, xs, interpret: bool = False):
    """The same scan as one Pallas kernel: grid step t reads block t of
    every array of `xs`, writes block t of every output, and keeps the
    carry (zeros at t = 0, as `carry` is) in VMEM scratch."""
    steps = xs[0].shape[0]
    outs = jax.eval_shape(lambda c, b: step(c, b)[1], carry,
                          tuple(x[0] for x in xs))
    # under shard_map the outputs vary over the same mesh axes as the
    # planes they are computed from (check_vma requires it to be said)
    vma = jax.typeof(xs[0]).vma

    def kernel(*refs):
        x_refs = refs[:len(xs)]
        o_refs = refs[len(xs):len(xs) + len(outs)]
        c_refs = refs[len(xs) + len(outs):]

        @pl.when(pl.program_id(0) == 0)
        def _():
            for ref in c_refs:
                ref[...] = jnp.zeros_like(ref)

        new_carry, new_outs = step(tuple(ref[...] for ref in c_refs),
                                   tuple(ref[0] for ref in x_refs))
        for ref, value in zip(c_refs, new_carry):
            ref[...] = value
        for ref, value in zip(o_refs, new_outs):
            ref[0] = value

    def block(shape):
        return pl.BlockSpec((1,) + shape,
                            lambda t: (t,) + (0,) * len(shape))

    with stage("deblock"):
        return pl.pallas_call(
            kernel, grid=(steps,), name="tvt_deblock_wavefront",
            in_specs=[block(x.shape[1:]) for x in xs],
            out_specs=[block(o.shape) for o in outs],
            out_shape=[jax.ShapeDtypeStruct((steps,) + o.shape, o.dtype,
                                            vma=vma) for o in outs],
            scratch_shapes=[pltpu.VMEM(c.shape, c.dtype) for c in carry],
            interpret=interpret,
        )(*xs)


class _JaxOps:
    xp = jnp
    asarray = staticmethod(jnp.asarray)
    scope = staticmethod(stage)
    barrier = staticmethod(jax.lax.optimization_barrier)

    @staticmethod
    def lanes(mbh: int) -> int:
        if jaxme.use_pallas():
            return -(-mbh // _LANES) * _LANES
        return mbh

    @staticmethod
    def scan(step, carry, xs):
        if jaxme.use_pallas():
            return _scan_kernel(step, carry, xs)
        return _scan_loop(step, carry, xs)


JAX_OPS = _JaxOps()


def deblock_frame_jax(y, u, v, qp_map, *, intra: bool, nz4=None,
                      mv=None, mb_row0=0,
                      total_mb_rows: int | None = None,
                      mv_per_pel: int = 2, intra_mb=None):
    """Traced deblock of one (padded) frame or band slice — see
    deblock.deblock_frame for the argument contract. Input planes keep
    their dtypes (int16 recon in, int16 out)."""
    return deblock_frame(y, u, v, qp_map, intra=intra, nz4=nz4, mv=mv,
                         mb_row0=mb_row0, total_mb_rows=total_mb_rows,
                         mv_per_pel=mv_per_pel, intra_mb=intra_mb,
                         ops=JAX_OPS)
