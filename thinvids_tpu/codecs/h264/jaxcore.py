"""JAX/TPU implementation of the intra encode compute path.

Bit-exact port of encoder.encode_frame_arrays (tested against it): the
whole prediction→transform→quant→reconstruction loop runs as one jitted
XLA program. Structure chosen for the TPU execution model:

- macroblock ROW 0 has a left-neighbor dependency (DC/H modes) → a small
  `lax.scan` over its MBs;
- every other row uses VERTICAL prediction, which depends only on the
  reconstructed bottom edge of the row above → `lax.scan` over rows with
  all MBs of a row computed as one vectorized batch (VPU-friendly int32
  ops over (mbw, 16, 16) tiles, static shapes, no data-dependent control
  flow).

The sequential entropy pack stays on host (codecs/h264/encoder.pack_slice
or the C++ packer); this module only produces level arrays.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import jaxme, rdo
from .encoder import FrameLevels, _mode_policy, unpack_i4_modes
from .intra import I4_DC, I4_NO_TOP_RIGHT, LUMA_BLOCK_ORDER, LUMA_I4X4
from .rdo import RD_OFF
from .stages import stage
from .transform import MF_TABLE, V_TABLE, ZIGZAG_4x4, CHROMA_QP_TABLE

_MF = jnp.asarray(MF_TABLE)          # (6, 4, 4)
_V = jnp.asarray(V_TABLE)            # (6, 4, 4)
_ZZ = jnp.asarray(ZIGZAG_4x4)        # (16,)
_QPC = jnp.asarray(CHROMA_QP_TABLE)  # (52,)
_CF = jnp.asarray([[1, 1, 1, 1], [2, 1, -1, -2], [1, -1, -1, 1], [1, -2, 2, -1]],
                  dtype=jnp.int32)
_H4 = jnp.asarray([[1, 1, 1, 1], [1, 1, -1, -1], [1, -1, -1, 1], [1, -1, 1, -1]],
                  dtype=jnp.int32)
_H2 = jnp.asarray([[1, 1], [1, -1]], dtype=jnp.int32)
# raster (by*4+bx) index for each z-scan position
_ZSCAN = jnp.asarray([by * 4 + bx for (bx, by) in LUMA_BLOCK_ORDER])


def _varying_zero(x):
    """A zero int32 scalar DERIVED from `x`, not a constant.

    Under `shard_map`, values built from plain constants are unvarying
    over the mesh axes while data-derived values are varying; a
    `lax.scan` whose init carry is unvarying but whose carry output is
    varying fails the carry-type check. Deriving the zero from the
    sharded input gives inits the same varying manual axes. Do NOT
    simplify `zeros + _varying_zero(x)` to `zeros`.
    """
    return (x.reshape(-1)[0] * 0).astype(jnp.int32)


def _fwd4(x):
    return jnp.einsum("ij,...jk,lk->...il", _CF, x, _CF)


def _inv4(d):
    d0, d1, d2, d3 = d[..., 0], d[..., 1], d[..., 2], d[..., 3]
    e0, e1 = d0 + d2, d0 - d2
    e2, e3 = (d1 >> 1) - d3, d1 + (d3 >> 1)
    f = jnp.stack([e0 + e3, e1 + e2, e1 - e2, e0 - e3], axis=-1)
    g0, g1, g2, g3 = f[..., 0, :], f[..., 1, :], f[..., 2, :], f[..., 3, :]
    h0, h1 = g0 + g2, g0 - g2
    h2, h3 = (g1 >> 1) - g3, g1 + (g3 >> 1)
    return jnp.stack([h0 + h3, h1 + h2, h1 - h2, h0 - h3], axis=-2)


def _quant(w, qp, skip_dc):
    """Quantize (n, B, 4, 4) coefficient blocks. `qp` may be a scalar
    or an (n,) per-MB vector (perceptual AQ) — with a scalar the math
    reproduces the historical bits exactly."""
    qp = jnp.asarray(qp)
    if qp.ndim:
        mf = _MF[qp % 6][:, None]            # (n, 1, 4, 4)
        qbits = (15 + qp // 6)[:, None, None, None]
    else:
        mf = _MF[qp % 6]
        qbits = 15 + qp // 6
    f = (1 << qbits) // 3
    z = (jnp.abs(w) * mf + f) >> qbits
    z = jnp.where(w < 0, -z, z)
    if skip_dc:
        z = z.at[..., 0, 0].set(0)
    return z


def _dequant(z, qp):
    qp = jnp.asarray(qp)
    if qp.ndim:
        return (z * _V[qp % 6][:, None]) << (qp // 6)[:, None, None, None]
    return (z * _V[qp % 6]) << (qp // 6)


def _zigzag(b):
    return b.reshape(*b.shape[:-2], 16)[..., _ZZ]


def _inv_zigzag(seq):
    out = jnp.zeros_like(seq)
    out = out.at[..., _ZZ].set(seq)
    return out.reshape(*seq.shape[:-1], 4, 4)


def _dc_dims(qp, ndim: int):
    """(qbits, mf00, vls, qp_b) broadcastable over an (n, ...) DC array
    when `qp` is an (n,) vector, plain scalars otherwise."""
    qp = jnp.asarray(qp)
    if qp.ndim:
        shape = (qp.shape[0],) + (1,) * (ndim - 1)
        return ((15 + qp // 6).reshape(shape),
                _MF[qp % 6, 0, 0].reshape(shape),
                (_V[qp % 6, 0, 0] * 16).reshape(shape),
                qp.reshape(shape))
    return 15 + qp // 6, _MF[qp % 6, 0, 0], _V[qp % 6, 0, 0] * 16, qp


def _luma_dc_quant(wd, qp):
    qbits, mf00, _, _ = _dc_dims(qp, wd.ndim)
    f = (1 << qbits) // 3
    z = (jnp.abs(wd) * mf00 + 2 * f) >> (qbits + 1)
    return jnp.where(wd < 0, -z, z)


def _luma_dc_dequant(z, qp):
    f = jnp.einsum("ij,...jk,lk->...il", _H4, z, _H4)
    _, _, ls, qp_b = _dc_dims(qp, f.ndim)
    hi = (f * ls) << jnp.maximum(qp_b // 6 - 6, 0)
    shift = jnp.maximum(6 - qp_b // 6, 1)
    lo = (f * ls + (1 << (shift - 1))) >> shift
    return jnp.where(qp_b >= 36, hi, lo)


def _chroma_dc_quant(wd, qp):
    qbits, mf00, _, _ = _dc_dims(qp, wd.ndim)
    f = (1 << qbits) // 3
    z = (jnp.abs(wd) * mf00 + 2 * f) >> (qbits + 1)
    return jnp.where(wd < 0, -z, z)


def _chroma_dc_dequant(z, qp):
    f = jnp.einsum("ij,...jk,lk->...il", _H2, z, _H2)
    _, _, ls, qp_b = _dc_dims(qp, f.ndim)
    return ((f * ls) << (qp_b // 6)) >> 5


def _luma_mb_batch(src, pred, qp):
    """src/pred: (n, 16, 16) int32 → (dc_lev (n,16), ac_lev (n,16,15),
    recon (n,16,16))."""
    n = src.shape[0]
    resid = src - pred
    blocks = resid.reshape(n, 4, 4, 4, 4).transpose(0, 1, 3, 2, 4).reshape(n, 16, 4, 4)
    w = _fwd4(blocks)
    dc = w[..., 0, 0].reshape(n, 4, 4)                      # [by, bx]
    wd = jnp.einsum("ij,njk,lk->nil", _H4, dc, _H4) // 2
    dc_lev = _zigzag(_luma_dc_quant(wd, qp))
    z = _quant(w, qp, skip_dc=True)
    ac_lev = _zigzag(z)[:, _ZSCAN, 1:]
    # closed-loop recon from the signaled levels
    dcr = _luma_dc_dequant(_inv_zigzag(dc_lev), qp)         # (n, 4, 4)
    d = _dequant(z, qp)
    d = d.at[..., 0, 0].set(dcr.reshape(n, 16))
    r = (_inv4(d) + 32) >> 6
    predb = pred.reshape(n, 4, 4, 4, 4).transpose(0, 1, 3, 2, 4).reshape(n, 16, 4, 4)
    rec = jnp.clip(predb + r, 0, 255)
    rec = rec.reshape(n, 4, 4, 4, 4).transpose(0, 1, 3, 2, 4).reshape(n, 16, 16)
    return dc_lev, ac_lev, rec


def _chroma_mb_batch(src, pred, qpc):
    """src/pred: (n, 8, 8) int32 → (dc_lev (n,4), ac_lev (n,4,15), recon)."""
    n = src.shape[0]
    resid = src - pred
    blocks = resid.reshape(n, 2, 4, 2, 4).transpose(0, 1, 3, 2, 4).reshape(n, 4, 4, 4)
    w = _fwd4(blocks)
    dc = w[..., 0, 0].reshape(n, 2, 2)
    wd = jnp.einsum("ij,njk,lk->nil", _H2, dc, _H2)
    dc_lev = _chroma_dc_quant(wd, qpc).reshape(n, 4)
    z = _quant(w, qpc, skip_dc=True)
    ac_lev = _zigzag(z)[..., 1:]
    dcr = _chroma_dc_dequant(dc_lev.reshape(n, 2, 2), qpc)
    d = _dequant(z, qpc)
    d = d.at[..., 0, 0].set(dcr.reshape(n, 4))
    r = (_inv4(d) + 32) >> 6
    predb = pred.reshape(n, 2, 4, 2, 4).transpose(0, 1, 3, 2, 4).reshape(n, 4, 4, 4)
    rec = jnp.clip(predb + r, 0, 255)
    rec = rec.reshape(n, 2, 2, 4, 4).transpose(0, 1, 3, 2, 4).reshape(n, 8, 8)
    return dc_lev, ac_lev, rec


@functools.partial(jax.jit, static_argnames=("mbw", "mbh", "rd"))
def _encode_intra(y, u, v, qp, *, mbw: int, mbh: int, rd=RD_OFF):
    """Jitted intra compute: level arrays only (recon DCE'd away)."""
    return _intra_core(y, u, v, qp, mbw=mbw, mbh=mbh, rd=rd)[:4]


def _satd16(resid):
    """(n, 16, 16) int32 residual → (n,) SATD (sum |4x4 Hadamard| / 2;
    the intra mode-decision cost — rdo.satd16_np is the numpy twin)."""
    n = resid.shape[0]
    b = resid.reshape(n, 4, 4, 4, 4).transpose(0, 1, 3, 2, 4)
    t = jnp.einsum("ij,nbcjk,lk->nbcil", _H4, b, _H4)
    return jnp.abs(t).sum(axis=(1, 2, 3, 4)) // 2


def _satd8(resid):
    """(n, 8, 8) int32 residual → (n,) SATD."""
    n = resid.shape[0]
    b = resid.reshape(n, 2, 4, 2, 4).transpose(0, 1, 3, 2, 4)
    t = jnp.einsum("ij,nbcjk,lk->nbcil", _H4, b, _H4)
    return jnp.abs(t).sum(axis=(1, 2, 3, 4)) // 2


def _mb_activity(y32, mbw: int, mbh: int):
    """(nmb,) int32 integer luma activity — the device twin of
    rdo.mb_activity_np (uint32 throughout; exact)."""
    mb = y32[:16 * mbh, :16 * mbw].astype(jnp.uint32)
    mb = mb.reshape(mbh, 16, mbw, 16).transpose(0, 2, 1, 3)
    mb = mb.reshape(mbh * mbw, 256)
    s = mb.sum(axis=1)
    s2 = (mb * mb).sum(axis=1)
    v = 256 * s2 - s * s
    act = jnp.zeros(mbh * mbw, jnp.int32)
    for k in range(1, rdo.AQ_ACT_BITS + 1):
        act = act + (v >= jnp.uint32((1 << k) - 1)).astype(jnp.int32)
    return act


def _aq_qp_map(y32, qp, aq_q: int, mbw: int, mbh: int):
    """(nmb,) per-MB QP for one intra frame under perceptual AQ —
    integer mirror of rdo.aq_offsets_from_activity."""
    act = _mb_activity(y32, mbw, mbh)
    nmb = mbw * mbh
    total = act.sum()
    num = aq_q * (act * nmb - total)
    den = rdo.AQ_QUANT * nmb
    delta = (2 * num + den) // (2 * den)
    delta = jnp.clip(delta, -rdo.AQ_MAX_DELTA, rdo.AQ_MAX_DELTA)
    return jnp.clip(qp + delta, 0, 51).astype(jnp.int32)


def _greedy_allowed(desired):
    """Vectorized greedy left-to-right selection: allowed[c] =
    desired[c] & !allowed[c-1]. Within each run of consecutive desired
    MBs the sequential recurrence alternates starting True at the run
    head, so allowed = desired & (even offset from the run start) —
    cummax of the run-start indices replaces the scan."""
    n = desired.shape[0]
    idx = jnp.arange(n)
    prev = jnp.concatenate([jnp.zeros(1, jnp.bool_), desired[:-1]])
    run_start = desired & ~prev
    start_idx = jax.lax.cummax(jnp.where(run_start, idx, -1))
    return desired & (((idx - start_idx) % 2) == 0)


#: large finite cost for unavailable candidates (strict-< selection
#: keeps the earlier candidate on ties, so this never wins)
_COST_INF = jnp.int32(1 << 29)


def _pick3(c0, m0, c1, m1, c2, m2):
    """Strict-< argmin over three (cost, mode) pairs, earlier wins."""
    best, mode = c0, jnp.full_like(c0, m0)
    take = c1 < best
    best = jnp.where(take, c1, best)
    mode = jnp.where(take, m1, mode)
    take = c2 < best
    best = jnp.where(take, c2, best)
    mode = jnp.where(take, m2, mode)
    return best, mode


def _chroma_dc_pred_row(ts4, ls4, avail_left, avail_top):
    """(n, 8, 8) chroma DC predictions per §8.3.4 quadrant rules from
    per-MB quarter sums ts4 (n, 2) [top halves] and ls4 (n, 2) [left
    halves]; avail_* are (n,) bools. Matches intra.predict_chroma8's
    availability fallbacks for every (left, top) combination that
    occurs in a slice (at least one of them available)."""
    n = ts4.shape[0]
    t0, t1 = ts4[:, 0], ts4[:, 1]
    l0, l1 = ls4[:, 0], ls4[:, 1]
    both = avail_left & avail_top
    # quadrant (0,0): t0+l0 both; else the available one
    q00 = jnp.where(both, (t0 + l0 + 4) >> 3,
                    jnp.where(avail_top, (t0 + 2) >> 2, (l0 + 2) >> 2))
    # (1,0): prefers its own top quarter
    q10 = jnp.where(avail_top, (t1 + 2) >> 2, (l0 + 2) >> 2)
    # (0,1): prefers its own left quarter
    q01 = jnp.where(avail_left, (l1 + 2) >> 2, (t0 + 2) >> 2)
    # (1,1): both -> t1+l1; else the available one
    q11 = jnp.where(both, (t1 + l1 + 4) >> 3,
                    jnp.where(avail_top, (t1 + 2) >> 2, (l1 + 2) >> 2))
    top = jnp.concatenate([
        jnp.broadcast_to(q00[:, None, None], (n, 4, 4)),
        jnp.broadcast_to(q10[:, None, None], (n, 4, 4))], axis=2)
    bot = jnp.concatenate([
        jnp.broadcast_to(q01[:, None, None], (n, 4, 4)),
        jnp.broadcast_to(q11[:, None, None], (n, 4, 4))], axis=2)
    return jnp.concatenate([top, bot], axis=1)


def _intra_core(y, u, v, qp, *, mbw: int, mbh: int, rd=RD_OFF):
    """Intra compute for one (padded) frame: the Intra16x16 picture
    (:func:`_intra16_core`, whose ten arrays these are) and, with
    ``rd.intra4x4``, its luma coded again with each macroblock
    Intra16x16 or Intra4x4 (:func:`_intra4x4_luma`): the luma levels,
    reconstruction, modes and QP deltas are then that stage's, an
    eleventh array holds the blocks' modes, and chroma stays as the
    first stage coded it."""
    out = _intra16_core(y, u, v, qp, mbw=mbw, mbh=mbh, rd=rd)
    if rd.intra4x4:
        out = _intra4x4_luma(y, qp, out, mbw=mbw, mbh=mbh, rd=rd)
    return out


@stage("intra")
def _intra16_core(y, u, v, qp, *, mbw: int, mbh: int, rd=RD_OFF):
    """Intra16x16 compute for one (padded) frame.

    Returns (luma_dc, luma_ac, chroma_dc, chroma_ac, recon_y, recon_u,
    recon_v, luma_mode, chroma_mode, qp_delta): the historical seven
    arrays plus the per-MB mode/QP side channel — with `rd` off the
    modes are exactly encoder._mode_policy's raster and qp_delta is
    all-zero (and the level/recon arrays are bit-identical to the
    historical program).

    With ``rd.mode_decision`` the fixed V/H/DC raster becomes a per-MB
    SATD decision; rows stay data-parallel via a two-stage schedule:
    every MB of a row first encodes VERTICAL (its prediction needs only
    the carried row above), then MBs whose H/DC candidate (predicted
    from the LEFT neighbor's vertical-mode recon) beats V by SATD are
    switched — greedily constrained so a switched MB's left neighbor
    always kept V, which makes the left-recon assumption exact. Row 0
    (slice-local: no row above) decides H vs DC inside its existing
    left-to-right scan, where the true recon is available — no
    constraint needed. With ``rd.aq_q`` the quantizer runs on a per-MB
    QP map (qp + variance-AQ offsets, _aq_qp_map).
    """
    qp = qp.astype(jnp.int32)
    y = y.astype(jnp.int32)
    u = u.astype(jnp.int32)
    v = v.astype(jnp.int32)
    zero = _varying_zero(y)        # see _varying_zero: shard_map carries
    qpc = _QPC[jnp.clip(qp, 0, 51)]
    if rd.aq_q > 0:
        qp_mb = _aq_qp_map(y, qp, rd.aq_q, mbw, mbh) + zero   # (nmb,)
        qp_rows = qp_mb.reshape(mbh, mbw)
        qpc_rows = _QPC[jnp.clip(qp_mb, 0, 51)].reshape(mbh, mbw)
        qp_delta = (qp_mb - qp).astype(jnp.int32)
    else:
        # flat QP: the scans below fall back to the SCALAR quantizer
        # arguments (the per-row vectors are dead and DCE'd), so the
        # compiled default program is the historical one.
        qp_rows = jnp.broadcast_to(qp, (mbh, mbw))
        qpc_rows = jnp.broadcast_to(qpc, (mbh, mbw))
        qp_delta = jnp.zeros(mbw * mbh, jnp.int32) + zero

    # --- row 0: sequential over MBs (left-only dependencies) ---
    y_row0 = y[:16].reshape(16, mbw, 16).transpose(1, 0, 2)      # (mbw,16,16)
    u_row0 = u[:8].reshape(8, mbw, 8).transpose(1, 0, 2)
    v_row0 = v[:8].reshape(8, mbw, 8).transpose(1, 0, 2)

    def row0_step(carry, x):
        ly, lu, lv, idx = carry
        sy, su, sv, qp1, qpc1 = x
        pred_h_y = jnp.tile(ly[:, None], (1, 16))
        pred_h_u = jnp.tile(lu[:, None], (1, 8))
        pred_h_v = jnp.tile(lv[:, None], (1, 8))
        if rd.mode_decision:
            # candidates: H vs DC (left-only), decided by SATD; MB 0
            # keeps DC-128 (no neighbors).
            dc_y = jnp.full((16, 16), (ly.sum() + 8) >> 4, jnp.int32)
            c_h = _satd16((sy - pred_h_y)[None])[0]
            c_dc = _satd16((sy - dc_y)[None])[0]
            lsum_u = jnp.stack([lu[:4].sum(), lu[4:].sum()])
            lsum_v = jnp.stack([lv[:4].sum(), lv[4:].sum()])
            dc_u = _chroma_dc_pred_row(
                jnp.zeros((1, 2), jnp.int32), lsum_u[None],
                jnp.ones(1, bool), jnp.zeros(1, bool))[0]
            dc_v = _chroma_dc_pred_row(
                jnp.zeros((1, 2), jnp.int32), lsum_v[None],
                jnp.ones(1, bool), jnp.zeros(1, bool))[0]
            cc_h = (_satd8((su - pred_h_u)[None])
                    + _satd8((sv - pred_h_v)[None]))[0]
            cc_dc = (_satd8((su - dc_u)[None])
                     + _satd8((sv - dc_v)[None]))[0]
            dc128_y = jnp.full((16, 16), 128, jnp.int32)
            dc128_c = jnp.full((8, 8), 128, jnp.int32)
            take_dc = c_dc < c_h
            pred_y = jnp.where(idx == 0, dc128_y,
                               jnp.where(take_dc, dc_y, pred_h_y))
            ymode = jnp.where(idx == 0, 2, jnp.where(take_dc, 2, 1))
            take_cdc = cc_dc < cc_h
            pred_u = jnp.where(idx == 0, dc128_c,
                               jnp.where(take_cdc, dc_u, pred_h_u))
            pred_v = jnp.where(idx == 0, dc128_c,
                               jnp.where(take_cdc, dc_v, pred_h_v))
            cmode = jnp.where(idx == 0, 0, jnp.where(take_cdc, 0, 1))
        else:
            pred_y = jnp.where(idx == 0,
                               jnp.full((16, 16), 128, jnp.int32),
                               pred_h_y)
            pred_u = jnp.where(idx == 0, jnp.full((8, 8), 128, jnp.int32),
                               pred_h_u)
            pred_v = jnp.where(idx == 0, jnp.full((8, 8), 128, jnp.int32),
                               pred_h_v)
            ymode = jnp.where(idx == 0, 2, 1)     # DC then horizontal
            cmode = jnp.where(idx == 0, 0, 1)
        qp_mb1 = qp1 if rd.aq_q else qp
        qpc_mb1 = qpc1 if rd.aq_q else qpc
        ydc, yac, yrec = _luma_mb_batch(sy[None], pred_y[None], qp_mb1)
        udc, uac, urec = _chroma_mb_batch(su[None], pred_u[None], qpc_mb1)
        vdc, vac, vrec = _chroma_mb_batch(sv[None], pred_v[None], qpc_mb1)
        carry = (yrec[0, :, -1], urec[0, :, -1], vrec[0, :, -1], idx + 1)
        return carry, (ydc[0], yac[0], udc[0], uac[0], vdc[0], vac[0],
                       yrec[0], urec[0], vrec[0], ymode, cmode)

    init = (jnp.zeros(16, jnp.int32) + zero, jnp.zeros(8, jnp.int32) + zero,
            jnp.zeros(8, jnp.int32) + zero, zero)
    _, row0_out = jax.lax.scan(
        row0_step, init,
        (y_row0, u_row0, v_row0, qp_rows[0], qpc_rows[0]))
    (r0_ydc, r0_yac, r0_udc, r0_uac, r0_vdc, r0_vac,
     r0_yrec, r0_urec, r0_vrec, r0_ymode, r0_cmode) = row0_out
    bottom_y = r0_yrec[:, -1, :].reshape(-1)                     # (W,)
    bottom_u = r0_urec[:, -1, :].reshape(-1)
    bottom_v = r0_vrec[:, -1, :].reshape(-1)

    if mbh > 1:
        # --- rows 1..mbh-1: scan over rows, vectorized across MBs ---
        y_rows = y[16:].reshape(mbh - 1, 16, mbw, 16).transpose(0, 2, 1, 3)
        u_rows = u[8:].reshape(mbh - 1, 8, mbw, 8).transpose(0, 2, 1, 3)
        v_rows = v[8:].reshape(mbh - 1, 8, mbw, 8).transpose(0, 2, 1, 3)

        def row_step(carry, x):
            by, bu, bv = carry
            sy, su, sv, qp_r, qpc_r = x                          # (mbw,...)
            pred_vy = jnp.broadcast_to(by.reshape(mbw, 1, 16),
                                       (mbw, 16, 16))
            pred_vu = jnp.broadcast_to(bu.reshape(mbw, 1, 8), (mbw, 8, 8))
            pred_vv = jnp.broadcast_to(bv.reshape(mbw, 1, 8), (mbw, 8, 8))
            qp_v = qp_r if rd.aq_q else qp
            qpc_v = qpc_r if rd.aq_q else qpc
            if not rd.mode_decision:
                ydc, yac, yrec = _luma_mb_batch(sy, pred_vy, qp_v)
                udc, uac, urec = _chroma_mb_batch(su, pred_vu, qpc_v)
                vdc, vac, vrec = _chroma_mb_batch(sv, pred_vv, qpc_v)
                ymode = jnp.zeros(mbw, jnp.int32) + zero
                cmode = jnp.full(mbw, 2, jnp.int32) + zero
                carry = (yrec[:, -1, :].reshape(-1),
                         urec[:, -1, :].reshape(-1),
                         vrec[:, -1, :].reshape(-1))
                return carry, (ydc, yac, udc, uac, vdc, vac,
                               yrec, urec, vrec, ymode, cmode)

            # stage 1: vertical encode of the whole row (candidate
            # recon for the neighbors' H/DC predictions)
            _, _, yrecv = _luma_mb_batch(sy, pred_vy, qp_v)
            _, _, urecv = _chroma_mb_batch(su, pred_vu, qpc_v)
            _, _, vrecv = _chroma_mb_batch(sv, pred_vv, qpc_v)

            # stage 2: candidate costs. Left columns come from the
            # LEFT neighbor's stage-1 (vertical) recon — exact for
            # every switched MB because the greedy constraint keeps
            # its left neighbor vertical.
            lcol_y = jnp.concatenate(
                [jnp.zeros((1, 16), jnp.int32), yrecv[:-1, :, -1]])
            lcol_u = jnp.concatenate(
                [jnp.zeros((1, 8), jnp.int32), urecv[:-1, :, -1]])
            lcol_v = jnp.concatenate(
                [jnp.zeros((1, 8), jnp.int32), vrecv[:-1, :, -1]])
            has_left = (jnp.arange(mbw) > 0)
            pred_hy = jnp.broadcast_to(lcol_y[:, :, None], (mbw, 16, 16))
            pred_hu = jnp.broadcast_to(lcol_u[:, :, None], (mbw, 8, 8))
            pred_hv = jnp.broadcast_to(lcol_v[:, :, None], (mbw, 8, 8))
            tsum_y = by.reshape(mbw, 16).sum(axis=1)
            lsum_y = lcol_y.sum(axis=1)
            dc_y = jnp.where(has_left,
                             (tsum_y + lsum_y + 16) >> 5,
                             (tsum_y + 8) >> 4)
            pred_dcy = jnp.broadcast_to(dc_y[:, None, None], (mbw, 16, 16))
            ts_u = bu.reshape(mbw, 2, 4).sum(axis=2)     # (mbw, 2)
            ts_v = bv.reshape(mbw, 2, 4).sum(axis=2)
            ls_u = lcol_u.reshape(mbw, 2, 4).sum(axis=2)
            ls_v = lcol_v.reshape(mbw, 2, 4).sum(axis=2)
            avail_top = jnp.ones(mbw, bool)
            pred_dcu = _chroma_dc_pred_row(ts_u, ls_u, has_left, avail_top)
            pred_dcv = _chroma_dc_pred_row(ts_v, ls_v, has_left, avail_top)

            c_v = _satd16(sy - pred_vy)
            c_h = jnp.where(has_left, _satd16(sy - pred_hy), _COST_INF)
            c_dc = _satd16(sy - pred_dcy)
            cc_v = _satd8(su - pred_vu) + _satd8(sv - pred_vv)
            cc_h = jnp.where(has_left,
                             _satd8(su - pred_hu) + _satd8(sv - pred_hv),
                             _COST_INF)
            cc_dc = _satd8(su - pred_dcu) + _satd8(sv - pred_dcv)

            best_y, ymode_alt = _pick3(c_v, 0, c_h, 1, c_dc, 2)
            best_c, cmode_alt = _pick3(cc_v, 2, cc_h, 1, cc_dc, 0)
            desired = (best_y + best_c) < (c_v + cc_v)
            allowed = _greedy_allowed(desired)

            ymode = jnp.where(allowed, ymode_alt, 0)
            cmode = jnp.where(allowed, cmode_alt, 2)
            pred_y = jnp.where((ymode == 0)[:, None, None], pred_vy,
                               jnp.where((ymode == 1)[:, None, None],
                                         pred_hy, pred_dcy))
            pred_u = jnp.where((cmode == 2)[:, None, None], pred_vu,
                               jnp.where((cmode == 1)[:, None, None],
                                         pred_hu, pred_dcu))
            pred_v = jnp.where((cmode == 2)[:, None, None], pred_vv,
                               jnp.where((cmode == 1)[:, None, None],
                                         pred_hv, pred_dcv))

            ydc, yac, yrec = _luma_mb_batch(sy, pred_y, qp_v)
            udc, uac, urec = _chroma_mb_batch(su, pred_u, qpc_v)
            vdc, vac, vrec = _chroma_mb_batch(sv, pred_v, qpc_v)
            carry = (yrec[:, -1, :].reshape(-1),
                     urec[:, -1, :].reshape(-1),
                     vrec[:, -1, :].reshape(-1))
            return carry, (ydc, yac, udc, uac, vdc, vac,
                           yrec, urec, vrec, ymode, cmode)

        _, rows_out = jax.lax.scan(
            row_step, (bottom_y, bottom_u, bottom_v),
            (y_rows, u_rows, v_rows, qp_rows[1:], qpc_rows[1:]))
        (ydc_r, yac_r, udc_r, uac_r, vdc_r, vac_r,
         yrec_r, urec_r, vrec_r, ymode_r, cmode_r) = rows_out
        luma_dc = jnp.concatenate([r0_ydc[None], ydc_r]).reshape(-1, 16)
        luma_ac = jnp.concatenate([r0_yac[None], yac_r]).reshape(-1, 16, 15)
        u_dc = jnp.concatenate([r0_udc[None], udc_r]).reshape(-1, 4)
        u_ac = jnp.concatenate([r0_uac[None], uac_r]).reshape(-1, 4, 15)
        v_dc = jnp.concatenate([r0_vdc[None], vdc_r]).reshape(-1, 4)
        v_ac = jnp.concatenate([r0_vac[None], vac_r]).reshape(-1, 4, 15)
        yrec_all = jnp.concatenate([r0_yrec[None], yrec_r])  # (mbh,mbw,16,16)
        urec_all = jnp.concatenate([r0_urec[None], urec_r])
        vrec_all = jnp.concatenate([r0_vrec[None], vrec_r])
        luma_mode = jnp.concatenate([r0_ymode[None], ymode_r]).reshape(-1)
        chroma_mode = jnp.concatenate([r0_cmode[None], cmode_r]).reshape(-1)
    else:
        luma_dc, luma_ac = r0_ydc, r0_yac
        u_dc, u_ac, v_dc, v_ac = r0_udc, r0_uac, r0_vdc, r0_vac
        yrec_all = r0_yrec[None]
        urec_all = r0_urec[None]
        vrec_all = r0_vrec[None]
        luma_mode = r0_ymode.reshape(-1)
        chroma_mode = r0_cmode.reshape(-1)

    chroma_dc = jnp.stack([u_dc, v_dc], axis=1)                  # (nmb,2,4)
    chroma_ac = jnp.stack([u_ac, v_ac], axis=1)                  # (nmb,2,4,15)
    recon_y = yrec_all.transpose(0, 2, 1, 3).reshape(16 * mbh, 16 * mbw)
    recon_u = urec_all.transpose(0, 2, 1, 3).reshape(8 * mbh, 8 * mbw)
    recon_v = vrec_all.transpose(0, 2, 1, 3).reshape(8 * mbh, 8 * mbw)
    return (luma_dc, luma_ac, chroma_dc, chroma_ac,
            recon_y, recon_u, recon_v,
            luma_mode.astype(jnp.int32), chroma_mode.astype(jnp.int32),
            qp_delta)


# ---------------------------------------------------------------------------
# rd.intra4x4: Intra4x4 macroblocks in an IDR picture (§8.3.1)
#
# An Intra4x4 block is predicted from the RECONSTRUCTION of the blocks
# to its left, above, above left and above right, so a macroblock's
# sixteen blocks are coded one after another, and a macroblock after
# its left, upper, upper left and upper right neighbours. The schedule
# is a wavefront over macroblocks: front t holds the macroblocks with
# mx + 2 my = t, one per macroblock row, and every array of a step
# keeps the row `my` on its LAST axis (the lanes), the sample positions
# on the axes before it. mbw + 2 (mbh - 1) steps a picture (254 at
# 1080p), each of them a macroblock's sixteen blocks in ten unrolled
# rounds (two blocks that need nothing of each other share a round).
# The numpy twin (encoder._intra4x4_luma_np) walks the same macroblocks
# in raster order.
# ---------------------------------------------------------------------------

#: z-scan index of the block at (bx, by)
_BLK_AT = {xy: i for i, xy in enumerate(LUMA_BLOCK_ORDER)}


def _split4(x, ax: int):
    return [jax.lax.index_in_dim(x, i, ax, keepdims=False) for i in range(4)]


def _fwd1(x, ax: int):
    """The core transform's rows (_CF) along axis `ax`, as adds."""
    x0, x1, x2, x3 = _split4(x, ax)
    a0, a1, a2, a3 = x0 + x3, x1 + x2, x1 - x2, x0 - x3
    return jnp.stack([a0 + a1, 2 * a3 + a2, a0 - a1, a3 - 2 * a2], axis=ax)


def _had1(x, ax: int):
    """The Hadamard rows (_H4) along axis `ax`."""
    x0, x1, x2, x3 = _split4(x, ax)
    a0, a1, a2, a3 = x0 + x3, x1 + x2, x1 - x2, x0 - x3
    return jnp.stack([a0 + a1, a3 + a2, a0 - a1, a3 - a2], axis=ax)


def _inv1(x, ax: int):
    """One pass of the inverse transform (_inv4's) along axis `ax`."""
    d0, d1, d2, d3 = _split4(x, ax)
    e0, e1 = d0 + d2, d0 - d2
    e2, e3 = (d1 >> 1) - d3, d1 + (d3 >> 1)
    return jnp.stack([e0 + e3, e1 + e2, e1 - e2, e0 - e3], axis=ax)


def _row_above(x):
    """A front's array as the macroblock row BELOW sees it: lane my
    holds what lane my - 1 held, zeros entering at row 0."""
    return jnp.pad(x[..., :-1], [(0, 0)] * (x.ndim - 1) + [(1, 0)])


def _quant_lanes(w, mf, qbits, r: int, c: int):
    """_quant with the macroblock on the last axis: `w` has the
    coefficient's row on axis `r` and column on axis `c`, `mf` is
    (4, 4, n), `qbits` (n,)."""
    shape = [1] * w.ndim
    shape[r], shape[c], shape[-1] = 4, 4, w.shape[-1]
    f = (1 << qbits) // 3
    z = (jnp.abs(w) * mf.reshape(shape) + f) >> qbits
    return jnp.where(w < 0, -z, z)


def _i4_weights() -> np.ndarray:
    """§8.3.1.2's nine predictions as ONE matrix: row (mode, y, x)
    holds the weights, summing to 4, of the thirteen neighbour samples
    and the DC value [t0..t7 | l0..l3 | m | dc] whose weighted sum,
    plus 2, shifted right by 2, is the predicted sample — (a + 2b + c +
    2) >> 2 as it stands, (a + b + 1) >> 1 as (2a + 2b + 2) >> 2 and a
    plain copy (V, H, DC, horizontal-up's last samples) as (4a + 2) >>
    2, the same values. Mode-major over Table 8-2's modes; DC's value
    depends on what is available and is computed beside the matrix."""
    T, L, M, DC = list(range(8)), list(range(8, 12)), 12, 13

    def vec(*terms):
        w = np.zeros(14, np.float32)
        for k, a in terms:
            w[k] += a
        return w

    f3 = lambda a, b, c: vec((a, 1), (b, 2), (c, 1))
    f2 = lambda a, b: vec((a, 2), (b, 2))
    one = lambda a: vec((a, 4))
    # up the left column, through the corner (4), along the top row
    e = [L[3], L[2], L[1], L[0], M, T[0], T[1], T[2], T[3]]
    f3e = lambda k: f3(e[k - 1], e[k], e[k + 1])
    f2e = lambda k: f2(e[k], e[k + 1])

    def ddl(y, x):
        if x == 3 and y == 3:
            return vec((T[6], 1), (T[7], 3))
        return f3(T[x + y], T[x + y + 1], T[x + y + 2])

    def vr(y, x):
        z = 2 * x - y
        if z >= 0:
            k = 4 + x - (y >> 1)
            return f3e(k) if z % 2 else f2e(k)
        return f3e(4) if z == -1 else f3e(5 - y)

    def hd(y, x):
        z = 2 * y - x
        if z >= 0:
            k = 4 - (y - (x >> 1))
            return f3e(k) if z % 2 else f2e(k - 1)
        return f3e(4) if z == -1 else f3e(3 + x)

    def vl(y, x):
        k = x + (y >> 1)
        return f3(T[k], T[k + 1], T[k + 2]) if y % 2 else f2(T[k], T[k + 1])

    def hu(y, x):
        z, k = x + 2 * y, y + (x >> 1)
        if z > 5:
            return one(L[3])
        if z == 5:
            return vec((L[2], 1), (L[3], 3))
        return f3(L[k], L[k + 1], L[k + 2]) if z % 2 else f2(L[k], L[k + 1])

    modes = (lambda y, x: one(T[x]), lambda y, x: one(L[y]),
             lambda y, x: one(DC), ddl,
             lambda y, x: f3e(4 + x - y), vr, hd, vl, hu)
    return np.stack([at(y, x) for at in modes
                     for y in range(4) for x in range(4)])


_I4_WEIGHTS = _i4_weights()                  # (9 * 16, 14)


def _i4_predictions(t, l, m, has_top, has_left):
    """§8.3.1.2's nine predictions of one block for every macroblock
    of a front: `t` (8, n) the samples above and above right, `l` (4,
    n) those to the left, `m` (n,) the corner → (9, 4, 4, n),
    mode-major, [row, column]: one product with `_I4_WEIGHTS` — exact
    in f32 at Precision.HIGHEST, every sum being under 2**10. Values
    of a mode whose samples are missing are never chosen (the caller's
    `allowed`)."""
    st, sl = t[:4].sum(axis=0), l.sum(axis=0)
    dc = jnp.where(has_top & has_left, (st + sl + 4) >> 3,
                   jnp.where(has_left, (sl + 2) >> 2,
                             jnp.where(has_top, (st + 2) >> 2, 128)))
    nb = jnp.concatenate([t, l, m[None], dc[None]]).astype(jnp.float32)
    preds = (jax.lax.dot(jnp.asarray(_I4_WEIGHTS), nb,
                         precision=jax.lax.Precision.HIGHEST)
             .astype(jnp.int32) + 2) >> 2
    return preds.reshape(9, 4, 4, m.shape[0])


def _i4_code_block(src, pred, mf, vq, qbits, qshift):
    """One Intra4x4 block of every macroblock of a front, (4, 4, n):
    the plain 4x4 transform on all sixteen coefficients → (levels (4,
    4, n) [row, column], reconstruction (4, 4, n))."""
    w = _fwd1(_fwd1(src - pred, 0), 1)
    z = _quant_lanes(w, mf, qbits, 0, 1)
    d = (z * vq) << qshift
    r = (_inv1(_inv1(d, 1), 0) + 32) >> 6
    return z, jnp.clip(pred + r, 0, 255)


def _i16_code_mb(src, pred, mf, vq, qbits, qp):
    """_luma_mb_batch with the macroblock on the last axis: src / pred
    (16, 16, n) → (DC levels (4, 4, n) [by, bx], the blocks' levels
    (4, 4, 4, 4, n) [by, bx, row, column] (the DC position is not
    read), recon (16, 16, n))."""
    n = src.shape[-1]
    qshift = qp // 6
    x = (src - pred).reshape(4, 4, 4, 4, n)             # [by, r, bx, c]
    w = _fwd1(_fwd1(x, 1), 3)
    wd = _had1(_had1(w[:, 0, :, 0], 0), 1) // 2         # [by, bx]
    f = (1 << qbits) // 3
    zd = (jnp.abs(wd) * mf[0, 0] + 2 * f) >> (qbits + 1)
    zd = jnp.where(wd < 0, -zd, zd)
    z = _quant_lanes(w, mf, qbits, 1, 3)
    # closed-loop recon from the signaled levels (_luma_dc_dequant)
    fd = _had1(_had1(zd, 0), 1) * (vq[0, 0] * 16)
    shift = jnp.maximum(6 - qshift, 1)
    dcr = jnp.where(qp >= 36, fd << jnp.maximum(qshift - 6, 0),
                    (fd + (1 << (shift - 1))) >> shift)
    d = (z * vq[None, :, None, :, :]) << qshift
    d = d.at[:, 0, :, 0].set(dcr)
    r = (_inv1(_inv1(d, 3), 1) + 32) >> 6
    rec = jnp.clip(pred.reshape(4, 4, 4, 4, n) + r, 0, 255)
    return zd, z.transpose(0, 2, 1, 3, 4), rec.reshape(16, 16, n)


def _sath16(resid):
    """Sum of |4x4 Hadamard| over (..., 16, 16, n) residuals → (..., n),
    NOT halved (twice _satd16's scale: rdo.sath4_np summed)."""
    lead = resid.shape[:-3]
    k = len(lead)
    x = resid.reshape(*lead, 4, 4, 4, 4, resid.shape[-1])
    h = _had1(_had1(x, k + 1), k + 3)
    return jnp.abs(h).sum(axis=(k, k + 1, k + 2, k + 3))


@stage("intra4x4")
def _intra4x4_luma(y, qp, core, *, mbw: int, mbh: int, rd):
    """rd.intra4x4: an IDR picture's luma coded again, each macroblock
    Intra16x16 or Intra4x4, whichever costs less; `core` is
    _intra16_core's result, whose chroma (and the QP map's AQ
    offsets) stay. Returns the ten arrays with the luma levels,
    reconstruction, `luma_mode` (4 = Intra4x4, intra.LUMA_I4X4) and
    `qp_delta` replaced, and an eleventh: the blocks' Intra4x4PredMode,
    (nmb, 16) in z-scan order (DC where the macroblock is Intra16x16).

    Per macroblock, from the neighbours' FINAL reconstruction (closed
    loop, sample for sample what a decoder holds):
    - the Intra16x16 candidate: V, H or DC by sum |Hadamard| of the
      residual, strict-< in that order (rd.mode_decision; else the
      raster policy's mode);
    - the Intra4x4 candidate: each block in decoding order through the
      modes its neighbours allow, cost = sum |Hadamard| + 2 lambda *
      (1 bit where the mode is §8.3.1.1's predicted one, else 4),
      strict-< from mode 0 up, then coded, so the next block predicts
      from its reconstruction;
    - Intra4x4 where its summed cost + 2 lambda * rdo.I4X4_BITS is
      less than the Intra16x16 candidate's (ties stay Intra16x16).
    A macroblock that ends Intra4x4 with no level at all codes no
    mb_qp_delta: its QP is its predecessor's (§7.4.5), and `qp_delta`
    (the side channel's and the filter's QP map) says so."""
    (_, _, chroma_dc, chroma_ac, _, recon_u, recon_v, _, chroma_mode,
     qp_delta) = core
    n, T = mbh, mbw + 2 * (mbh - 1)
    zero = _varying_zero(y)
    qp = qp.astype(jnp.int32)
    qp_mb = jnp.clip(qp + qp_delta, 0, 51).reshape(mbh, mbw)

    # the picture's macroblocks by front: [t, ..., my] holds macroblock
    # (t - 2 my, my), whatever where that column is outside the picture
    t_idx, my_idx = np.meshgrid(np.arange(T), np.arange(n), indexing="ij")
    mx_idx = t_idx - 2 * my_idx
    inside = (mx_idx >= 0) & (mx_idx < mbw)
    mx_clip = np.clip(mx_idx, 0, mbw - 1)

    def by_front(a):
        """(mbh, mbw, ...) → (T, ..., n)."""
        return jnp.moveaxis(a[my_idx, mx_clip], 1, -1)

    src = by_front(y.astype(jnp.int32).reshape(mbh, 16, mbw, 16)
                   .transpose(0, 2, 1, 3))
    qp_f = by_front(qp_mb)
    mf_f, vq_f = by_front(_MF[qp_mb % 6]), by_front(_V[qp_mb % 6])
    lam2_f = by_front(
        2 * jnp.asarray(rdo.P_INTRA_LAMBDA, jnp.int32)[qp_mb])
    policy = _mode_policy(mbw, mbh)[0].reshape(mbh, mbw)[my_idx, mx_clip]
    has_top = jnp.asarray(np.arange(n) > 0)
    steps = (src, qp_f, mf_f, vq_f, lam2_f, jnp.asarray(policy),
             jnp.asarray(mx_idx > 0), jnp.asarray(mx_idx + 1 < mbw))

    def front(carry, xs):
        left_col, bot1, bot2, corner3, left_modes, mbot1, mbot2 = carry
        sy, qp_v, mf, vq, lam2, pol, has_left, has_right = xs
        qbits, qshift = 15 + qp_v // 6, qp_v // 6
        top_row = _row_above(bot2)                  # (16, n)
        tr_row = _row_above(bot1)[:4]
        corner = _row_above(corner3)
        top_modes = _row_above(mbot2)
        has_tr_mb = has_top & has_right

        # --- the Intra16x16 candidate ---
        pred_v = jnp.broadcast_to(top_row[None], (16, 16, n))
        pred_h = jnp.broadcast_to(left_col[:, None], (16, 16, n))
        st, sl = top_row.sum(axis=0), left_col.sum(axis=0)
        dc = jnp.where(has_top & has_left, (st + sl + 16) >> 5,
                       jnp.where(has_left, (sl + 8) >> 4,
                                 jnp.where(has_top, (st + 8) >> 4, 128)))
        pred_dc = jnp.broadcast_to(dc, (16, 16, n))
        if rd.mode_decision:
            c = _sath16(sy[None] - jnp.stack([pred_v, pred_h, pred_dc]))
            c16, mode16 = _pick3(jnp.where(has_top, c[0], _COST_INF), 0,
                                 jnp.where(has_left, c[1], _COST_INF), 1,
                                 c[2], 2)
        else:
            mode16 = pol
        pred16 = jnp.where(mode16 == 0, pred_v,
                           jnp.where(mode16 == 1, pred_h, pred_dc))
        if not rd.mode_decision:
            c16 = _sath16(sy - pred16)
        dc16, z16, rec16 = _i16_code_mb(sy, pred16, mf, vq, qbits, qp_v)

        # --- the Intra4x4 candidate: the blocks in decoding order, two
        # at a time where neither needs the other (bx + 2 by equal: ten
        # rounds for sixteen blocks), side by side on the lanes ---
        rec, modes, levs = {}, {}, {}
        c4 = lam2 * rdo.I4X4_BITS
        always = jnp.ones(n, bool)

        def neighbours(bx, by):
            """(above + above right (8, n), left (4, n), corner, has
            above, has left, the upper and the left block's mode)."""
            if by:
                above = rec[bx, by - 1][3]
                b_top, mode_b = always, modes[bx, by - 1]
                above_right = (rec[bx + 1, by - 1][3]
                               if _BLK_AT[bx, by] not in I4_NO_TOP_RIGHT
                               else None)
            else:
                above = top_row[4 * bx:4 * bx + 4]
                b_top, mode_b = has_top, top_modes[bx]
                above_right = (top_row[4 * bx + 4:4 * bx + 8] if bx < 3
                               else jnp.where(has_tr_mb, tr_row, above[3]))
            if above_right is None:
                above_right = jnp.broadcast_to(above[3], (4, n))
            if bx:
                beside = rec[bx - 1, by][:, 3]
                b_left, mode_a = always, modes[bx - 1, by]
            else:
                beside = left_col[4 * by:4 * by + 4]
                b_left, mode_a = has_left, left_modes[by]
            if bx and by:
                m = rec[bx - 1, by - 1][3, 3]
            elif bx:
                m = top_row[4 * bx - 1]
            elif by:
                m = left_col[4 * by - 1]
            else:
                m = corner
            return (jnp.concatenate([above, above_right]), beside, m,
                    b_top, b_left, mode_a, mode_b,
                    sy[4 * by:4 * by + 4, 4 * bx:4 * bx + 4])

        for round_ in range(10):
            blocks = [(bx, by) for bx, by in LUMA_BLOCK_ORDER
                      if bx + 2 * by == round_]
            k = len(blocks)
            wide = lambda x: jnp.concatenate([x] * k, axis=-1)
            t, beside, m, b_top, b_left, mode_a, mode_b, bsrc = (
                jnp.concatenate(xs, axis=-1)
                for xs in zip(*(neighbours(*xy) for xy in blocks)))
            preds = _i4_predictions(t, beside, m, b_top, b_left)
            sat = jnp.abs(_had1(_had1(bsrc[None] - preds, 1), 2)) \
                .sum(axis=(1, 2))                            # (9, k n)
            both = b_top & b_left
            pm = jnp.where(both, jnp.minimum(mode_a, mode_b), I4_DC)
            allowed = jnp.stack([b_top, b_left, wide(always), b_top, both,
                                 both, both, b_top, b_left])
            nine = jnp.arange(9)[:, None]
            cost = jnp.where(
                allowed,
                sat + wide(lam2) * jnp.where(pm == nine,
                                             *rdo.I4X4_MODE_BITS),
                _COST_INF)
            # the first of the cheapest: strict-< from mode 0 up
            mode = jnp.argmin(cost, axis=0).astype(jnp.int32)
            best = cost.min(axis=0)
            pred = jnp.where(mode == nine[..., None, None], preds,
                             0).sum(axis=0)
            lev, recon = _i4_code_block(bsrc, pred, wide(mf), wide(vq),
                                        wide(qbits), wide(qshift))
            for i, xy in enumerate(blocks):
                mine = slice(i * n, (i + 1) * n)
                c4 = c4 + best[mine]
                modes[xy] = mode[mine]
                levs[xy], rec[xy] = lev[..., mine], recon[..., mine]

        tiles = lambda of: jnp.stack(
            [jnp.stack([of[bx, by] for bx in range(4)]) for by in range(4)])
        rec4 = tiles(rec).transpose(0, 2, 1, 3, 4).reshape(16, 16, n)
        lev4, modes4 = tiles(levs), tiles(modes)     # [by, bx, ...]

        # --- the kind ---
        i4 = c4 < c16
        recf = jnp.where(i4, rec4, rec16)
        modes_f = jnp.where(i4, modes4, I4_DC)
        out = (dc16, jnp.where(i4, lev4, z16), recf,
               jnp.where(i4, LUMA_I4X4, mode16), modes_f)
        carry = (recf[:, 15], recf[15], bot1, bot2[15], modes_f[:, 3],
                 modes_f[3], mbot1)
        return carry, out

    z16, z4 = jnp.zeros((16, n), jnp.int32) + zero, \
        jnp.full((4, n), I4_DC, jnp.int32) + zero
    _, (dc_f, lev_f, rec_f, mode_f, modes_f) = jax.lax.scan(
        front, (z16, z16, z16, z16[0], z4, z4, z4), steps)

    def by_mb(a):
        """(T, ..., n) → (nmb, ...)."""
        a = jnp.moveaxis(a, -1, 1)[
            np.arange(mbw)[None] + 2 * np.arange(n)[:, None],
            np.arange(n)[:, None]]
        return a.reshape(mbh * mbw, *a.shape[2:])

    # blocks to the z-scan, levels to the zig-zag, once a picture
    luma_mode = by_mb(mode_f)
    i4_modes = by_mb(modes_f).reshape(-1, 16)[:, _ZSCAN]
    lev = _zigzag(by_mb(lev_f).reshape(-1, 16, 4, 4)[:, _ZSCAN])
    luma_ac = lev[..., 1:]
    luma_dc = jnp.where((luma_mode == LUMA_I4X4)[:, None], lev[..., 0],
                        _zigzag(by_mb(dc_f)))
    recon_y = by_mb(rec_f).reshape(mbh, mbw, 16, 16) \
        .transpose(0, 2, 1, 3).reshape(16 * mbh, 16 * mbw)
    # a macroblock that codes no delta takes its predecessor's QP
    held = ((luma_mode == LUMA_I4X4)
            & ~jnp.any(luma_dc != 0, axis=1)
            & ~jnp.any(luma_ac != 0, axis=(1, 2))
            & ~jnp.any(chroma_dc != 0, axis=(1, 2))
            & ~jnp.any(chroma_ac != 0, axis=(1, 2, 3)))
    last = jax.lax.cummax(jnp.where(held, -1, jnp.arange(mbh * mbw)))
    qp_delta = jnp.where(last < 0, 0, qp_delta[jnp.maximum(last, 0)])
    return (luma_dc, luma_ac, chroma_dc, chroma_ac, recon_y, recon_u,
            recon_v, luma_mode, chroma_mode, qp_delta, i4_modes)


def _mode_tail(luma_mode, chroma_mode, qp_delta, i4_modes=None):
    """The per-MB side channel appended to intra transfer vectors when
    rd.ships_modes: [mode16 | dqp16], mode16 = luma | chroma << 4, and
    with rd.intra4x4 (`i4_modes`, _intra_core's eleventh array) the
    blocks' modes after them, four 4-bit modes to an int16 word, four
    words a macroblock (encoder.unpack_i4_modes is the inverse)."""
    parts = [(luma_mode | (chroma_mode << 4)).astype(jnp.int16),
             qp_delta.astype(jnp.int16)]
    if i4_modes is not None:
        m = i4_modes.reshape(-1, 4)
        w = m[:, 0] | (m[:, 1] << 4) | (m[:, 2] << 8) | (m[:, 3] << 12)
        # the top nibble reaches the sign bit: wrap by hand, exactly
        parts.append((w - ((w >> 15) << 16)).astype(jnp.int16))
    return jnp.concatenate(parts)


@functools.partial(jax.jit, static_argnames=("mbw", "mbh", "dtype", "rd"))
def _encode_intra_packed(y, u, v, qp, *, mbw: int, mbh: int, dtype,
                         rd=RD_OFF):
    """Dense fallback: intra compute + device-side concat of all level
    arrays into ONE flat `dtype` buffer (int16 covers the full CAVLC
    level range at 2x fewer device→host bytes than raw int32). The
    common path is the sparse transfer (`_encode_intra_sparse`). With
    rd.ships_modes the per-MB [mode16 | dqp16] side channel rides at
    the tail (see intra_flat_len)."""
    out = _intra_core(y, u, v, qp, mbw=mbw, mbh=mbh, rd=rd)
    luma_dc, luma_ac, chroma_dc, chroma_ac = out[:4]
    with stage("layout"):
        parts = [luma_dc.reshape(-1), luma_ac.reshape(-1),
                 chroma_dc.reshape(-1), chroma_ac.reshape(-1)]
        flat = jnp.concatenate(parts).astype(dtype)
        if rd.ships_modes:
            flat = jnp.concatenate([flat,
                                    _mode_tail(*out[7:]).astype(dtype)])
    return flat


def intra_flat_len(nmb: int, rd=RD_OFF) -> int:
    """Length of one frame's flat intra transfer vector."""
    return nmb * (384 + rd.intra_tail_mb)


_I8_MAX = 127

# Sparse level-transfer budget: nonzero density above 1/div falls back
# to a dense fetch. Typical density at qp 27 is ~10-15 % for all-intra
# frames; the dense fallback keeps correctness for busy content. (The
# GOP path uses the block-granular budget _BLOCK_BUDGET_DIV below.)
_SPARSE_BUDGET_DIV = 4
# Escape side-channel size: levels with |v| > 127 are rare at practical
# QPs; they ride as (position, value) int32 pairs so vals stay int8.
_SPARSE_ESCAPES = 4096
_BIT_WEIGHTS = jnp.asarray([128, 64, 32, 16, 8, 4, 2, 1], jnp.uint8)


@stage("pack")
def _sparse_pack(flat, budget_div: int = _SPARSE_BUDGET_DIV):
    """Compact a flat int32 level vector on device.

    Returns (nnz, n_esc, bitmap, vals, esc_pos, esc_val):
    - bitmap: 1 bit/coeff nonzero mask (big-endian within bytes, matching
      np.unpackbits), L/8 bytes;
    - vals: the nonzero levels in scan order, clipped to int8, in a fixed
      L//_SPARSE_BUDGET_DIV buffer;
    - esc_pos/esc_val: flat positions + true values of levels exceeding
      int8 (|v| > 127), in a fixed _SPARSE_ESCAPES buffer.
    ~10x fewer device→host bytes than raw int32 at typical densities.
    The caller must fall back to a dense fetch iff nnz > budget or
    n_esc > _SPARSE_ESCAPES.
    """
    L = flat.shape[0]
    budget = L // budget_div
    mask = flat != 0
    nnz = jnp.sum(mask.astype(jnp.int32))
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
    idx = jnp.where(mask, pos, budget)
    clipped = jnp.clip(flat, -_I8_MAX, _I8_MAX).astype(jnp.int8)
    vals = jnp.zeros(budget + 1, jnp.int8).at[idx].set(
        clipped, mode="drop")[:budget]
    bitmap = jnp.sum(
        _pad8(mask).reshape(-1, 8).astype(jnp.uint8) * _BIT_WEIGHTS, axis=-1
    ).astype(jnp.uint8)
    esc_mask = jnp.abs(flat) > _I8_MAX
    n_esc = jnp.sum(esc_mask.astype(jnp.int32))
    epos = jnp.cumsum(esc_mask.astype(jnp.int32)) - 1
    eidx = jnp.where(esc_mask, epos, _SPARSE_ESCAPES)
    esc_pos = jnp.zeros(_SPARSE_ESCAPES + 1, jnp.int32).at[eidx].set(
        jnp.arange(L, dtype=jnp.int32), mode="drop")[:_SPARSE_ESCAPES]
    esc_val = jnp.zeros(_SPARSE_ESCAPES + 1, jnp.int32).at[eidx].set(
        flat, mode="drop")[:_SPARSE_ESCAPES]
    return nnz, n_esc, bitmap, vals, esc_pos, esc_val


_BLOCK = 16
# Block-sparse budget: tolerated fraction of 16-coeff blocks with any
# nonzero coefficient is 1/_BLOCK_BUDGET_DIV; beyond that the caller
# falls back to the dense fetch. P-frame residual blocks are sparse
# (~10-15 % nonzero at qp 27) but the GOP's intra frame is NOT — most
# intra blocks carry at least a DC level — so the budget must absorb
# intra_blocks + sparse P blocks (measured ~300K of 1.57M for an
# 8-frame 1080p GOP).
_BLOCK_BUDGET_DIV = 4


def _slide_left(x, by: int):
    """`x` moved left by `by` along its last axis, zeros entering."""
    return jnp.pad(x[..., by:], [(0, 0)] * (x.ndim - 1) + [(0, by)])


def _compact_left(shift, *streams, move=_slide_left):
    """Order-preserving stream compaction with static addressing,
    along the last axis.

    `shift[i]` is how far element i moves left: its position minus its
    rank among the kept elements, and 0 in a slot that holds nothing
    (whose `streams` entries must be 0 too). Round k moves every
    element whose shift has bit k set left by 2**k: a select, a static
    slice and an OR — no gather, no scatter, no sort. Ranks rise with
    position, so taken from bit 0 upwards no two elements ever meet:
    after ceil(log2(n)) rounds element i sits at i - shift[i] and every
    other slot holds 0. Returns the moved (shift, *streams); a moved
    shift still says how far its element came, so position + shift is
    where it started. A stream may have leading axes `shift` lacks
    (they move alike). `move(x, by)` is the left move; a rotation does
    as well as `_slide_left`, since no element moves past slot 0."""
    n = shift.shape[-1]
    for k in range(max(n - 1, 0).bit_length()):
        by = 1 << k
        leaves = shift & by != 0
        movers = [jnp.where(leaves, x, 0) for x in (shift, *streams)]
        # no mover lands on a stayer, so OR merges the two
        shift, *streams = ((x ^ m) | move(m, by)
                           for x, m in zip((shift, *streams), movers))
    return (shift, *streams)


# Tier 1 of the two-tier pack appends CHUNKS of this many consecutive
# blocks (a power of two, whole 128-lane registers): each chunk's
# blocks with a level are moved to its front by `_compact_left` and
# the chunk is stored whole where the last one's live blocks ended —
# one dynamic address a chunk (1,531 a 1080p GOP of 32 frames), where
# the row gather this replaced had one per budget slot (1.57 M, 29 ns
# each on the v5e: 1.27-1.84 ms of every 1080p frame, and 5-50 % more
# or less of it wherever the program's buffers happened to lie,
# PERF.md §6 PR 47). 12 rounds a chunk at 4,096; the kernel alone on
# a 1080p GOP (v5e, PR 47): 8.2 / 4.7 / 3.0 / 3.1 ms at 1,024 / 2,048 /
# 4,096 / 8,192.
_APPEND_CHUNK = 4096
#: a block as the append moves it: 8 int32 words of two levels each
_BLOCK_WORDS = _BLOCK // 2


def _append_loop(offs, shift, words, budget: int):
    """Tier 1's append as XLA ops (the CPU mirror of
    `_append_kernel`): every chunk compacted at once, then stored
    whole at its offset, first to last, so that each store overwrites
    the dead tail of the one before. Past the budget the stores land
    behind it. On the v5e this form read 0.76 ms a 1080p frame in the
    served programs for the kernel's 0.09 (PERF.md §6 PR 47), which is
    why the chip does not run it."""
    chunk = _APPEND_CHUNK
    chunks = words.shape[1] // chunk
    base = jnp.arange(chunks, dtype=jnp.int32) * chunk - offs[:-1]
    _, moved = _compact_left(
        jnp.maximum(shift.reshape(chunks, chunk) - base[:, None], 0),
        words.reshape(_BLOCK_WORDS, chunks, chunk))

    def store(c, out):
        return jax.lax.dynamic_update_slice(
            out, moved[:, c], (0, jnp.minimum(offs[c], budget)))

    # the init derived from data: see _varying_zero
    return jax.lax.fori_loop(
        0, chunks, store,
        jnp.zeros((_BLOCK_WORDS, budget + chunk), jnp.int32)
        + _varying_zero(words))


def _append_kernel(offs, shift, words, budget: int,
                   interpret: bool = False):
    """Tier 1's append as one Pallas kernel (custom call
    `tvt_pack_append`): grid step c reads chunk c — its words and
    their shifts, one (8, chunk) register row each per 128 blocks —,
    compacts it in VMEM, turns it to where the live blocks so far end
    (`offs[c]`, prefetched) and ORs it into the output block that
    offset lies in, which stays in VMEM until the offset leaves it.
    What of a chunk passes the block's end waits in `carry` and opens
    the next block; one more grid step than chunks flushes the last
    carry. The levels are read once and the kept blocks written once.
    Output blocks no offset reached are never written (the caller's
    `live` mask zeroes them), and past `cap` nothing is: the leading
    blocks are the ones kept."""
    chunk = _APPEND_CHUNK
    chunks = words.shape[1] // chunk
    blocks_out = budget // chunk + 2
    cap = (blocks_out - 1) * chunk

    def block_of(c, offs_ref):
        return jnp.minimum(offs_ref[c], cap) // chunk

    def kernel(offs_ref, shift_ref, words_ref, out_ref, carry_ref):
        c = pl.program_id(0)
        off = offs_ref[c]

        @pl.when(c == 0)
        def _():
            carry_ref[...] = jnp.zeros_like(carry_ref)

        @pl.when((c == 0) | (block_of(c, offs_ref)
                             != block_of(jnp.maximum(c - 1, 0), offs_ref)))
        def _():
            out_ref[...] = carry_ref[...]

        @pl.when((c < chunks) & (off < cap))
        def _():
            local = jnp.maximum(shift_ref[...] - (c * chunk - off), 0)
            _, moved = _compact_left(
                jnp.broadcast_to(local, words_ref.shape), words_ref[...],
                move=lambda x, by: pltpu.roll(x, chunk - by, 1))
            fill = off % chunk
            # turned a bit of `fill` at a time, by static rotations as
            # the rounds are (`pltpu.roll` by a traced count compiles,
            # but no chip has run it for this repo: PERF.md §7)
            for k in range((chunk - 1).bit_length()):
                moved = jnp.where((fill >> k) & 1 == 1,
                                  pltpu.roll(moved, 1 << k, 1), moved)
            here = jax.lax.broadcasted_iota(
                jnp.int32, moved.shape, 1) >= fill
            out_ref[...] |= jnp.where(here, moved, 0)
            carry_ref[...] = jnp.where(here, 0, moved)

    def chunk_spec(rows):
        return pl.BlockSpec(
            (rows, chunk), lambda c, _: (0, jnp.minimum(c, chunks - 1)))

    return pl.pallas_call(
        kernel, name="tvt_pack_append", interpret=interpret,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(chunks + 1,),
            in_specs=[chunk_spec(1), chunk_spec(_BLOCK_WORDS)],
            out_specs=pl.BlockSpec(
                (_BLOCK_WORDS, chunk), lambda c, o: (0, block_of(c, o))),
            scratch_shapes=[pltpu.VMEM((_BLOCK_WORDS, chunk), jnp.int32)]),
        # under shard_map the output varies over the mesh axes the
        # levels do (check_vma requires it to be said)
        out_shape=jax.ShapeDtypeStruct(
            (_BLOCK_WORDS, blocks_out * chunk), jnp.int32,
            vma=jax.typeof(words).vma),
    )(offs, shift[None, :], words)


@jax.named_scope("append")
def _append_blocks(blocks, bmask, pos, budget: int):
    """Tier 1: the first `budget` columns of `blocks` ((16, NB) int16,
    NB a multiple of `_APPEND_CHUNK`) that `bmask` marks, in order, as
    (budget, 16). `pos` is `bmask`'s running count less one. Slots
    past the marked count hold anything."""
    chunks = blocks.shape[1] // _APPEND_CHUNK
    offs = jnp.concatenate([
        jnp.zeros(1, jnp.int32),
        pos.reshape(chunks, _APPEND_CHUNK)[:, -1] + 1])
    # how far a marked block lies from its rank; a chunk takes its own
    # part of that off (`_compact_left` needs shifts inside the chunk)
    shift = jnp.where(
        bmask, jnp.arange(bmask.shape[0], dtype=jnp.int32) - pos, 0)
    words = (blocks[:_BLOCK_WORDS].astype(jnp.int32) & 0xFFFF) \
        | (blocks[_BLOCK_WORDS:].astype(jnp.int32) << 16)
    append = _append_kernel if jaxme.use_pallas() else _append_loop
    kept = append(offs, shift, words, budget)[:, :budget]
    return jnp.concatenate(
        [(kept << 16) >> 16, kept >> 16]).astype(jnp.int16).T


# Value-stream budget for the two-tier pack: elementwise nonzero density
# beyond 1/div falls back dense. It is NOT the budget that goes first.
# On a pan with white grain that is new on every frame (tools/pan
# make_frames' `grain`; QP 27, GOP 16, 320x192, counted on a GOP's
# whole levels, PR 30) the blocks with a level / the non-zero values
# are 7.8 % / 0.95 % of a GOP's sparse remainder at sigma 0, 20.1 % /
# 2.05 % at sigma 3, 26.9 % / 2.73 % at 3.5, 34.6 % / 3.65 % at 4 and
# 48.8 % / 6.26 % at 5: the 25 % block budget (_BLOCK_BUDGET_DIV)
# overflows at sigma ~3.4, the 4.17 % value budget not before ~4.2, so
# ordinarily grainy footage at CQP 27 leaves the sparse transfer
# through the BLOCK budget (tests/test_grain.py holds the table).
# Either budget sizes a device buffer only: the compact transfer
# fetches the used prefix (85 kB per 1080p frame,
# `d2h_bytes_per_frame`, PERF_LEDGER PR 24).
_VAL_BUDGET_DIV = 24


@stage("pack")
def _block_sparse_pack2(flat, budget_div: int = _BLOCK_BUDGET_DIV,
                        val_div: int = _VAL_BUDGET_DIV):
    """Two-tier device compaction: the 16-coeff blocks with a level,
    in order (tier 1: an append of chunks, `_append_blocks`) + within-
    block value compaction (tier 2).

    The device→host transfer is what this pack shrinks (its rate on a
    directly attached chip is not measured); tier 1 alone ships 16
    int8 per nonzero block but
    only ~2.5 of those are nonzero at qp 27, so tier 2 ships a 16-bit
    occupancy mask per block + just the nonzero values: ~2.6 MB/GOP vs
    ~6.6 MB (1080p, F=8).

    Returns (nblk, nval, n_esc, bitmap, bmask16, vals):
    - bitmap: 1 bit per block (any-nonzero), ceil(L/16)/8 bytes;
    - bmask16: per kept block, a uint16 lane-occupancy mask
      (bit k = coeff k nonzero), fixed (NB//budget_div,) buffer;
    - vals: the nonzero coeffs in (block, lane) order, int8-clipped,
      fixed (L//val_div,) buffer;
    - n_esc: COUNT of coeffs exceeding int8. There is no escape
      side-channel: levels beyond ±127 are rare at practical QPs, and
      a (position, value) stream would need a full-size cumsum plus
      two more full-size scatters. Any escape (n_esc > 0) falls back
      to the dense fetch for the whole wave.
    Caller falls back to a dense fetch iff nblk/nval/n_esc exceed their
    budgets (`block_sparse2_fits`).

    Both compactions (nonzero blocks inside a chunk, nonzero values →
    `vals`) go through `_compact_left`, and a chunk is placed by ONE
    dynamic offset. What the device made of the other forms, per 1080p
    frame: as scatters (`zeros.at[pos].set(..., mode="drop")`, a sort
    of the (position, value) pairs, then one update at a time) 5.52 +
    1.57 ms for the values and 1.17 ms for the block list, of a
    10.25 ms pack (PERF_LEDGER PR 24, `hd-backlog`); tier 1 as a row
    gather (`jnp.take(blocks, blist)`, one dynamic address a budget
    slot, until ISSUE 47) 1.27-1.84 ms, which moved by up to half with
    the placement of the program's buffers (tests/test_compact.py
    keeps that form as the oracle). Same bytes out, overflow
    included: past a budget the leading blocks / values are the ones
    kept.
    """
    L = flat.shape[0]
    NB = -(-L // _BLOCK)
    # zero blocks fill the last chunk of tier 1's append
    pad = -(-NB // _APPEND_CHUNK) * _APPEND_CHUNK * _BLOCK - L
    flat = flat.astype(jnp.int16)       # CAVLC levels fit int16
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros(pad, flat.dtype)])
    budget = NB // budget_div
    vbudget = L // val_div
    # a block is a column, its 16 levels on sublanes; held as such, or
    # the compiler turns the levels once for the mask and once, as
    # int32 at 8 times the bytes, for the append
    blocks = jax.lax.optimization_barrier(flat.reshape(-1, _BLOCK).T)
    bmask = jnp.any(blocks != 0, axis=0)
    nblk = jnp.sum(bmask.astype(jnp.int32))
    pos = jnp.cumsum(bmask.astype(jnp.int32)) - 1
    slot = jnp.arange(budget, dtype=jnp.int32)
    live = slot < nblk
    gathered = _append_blocks(blocks, bmask, pos, budget)  # (budget, 16)
    gathered = jnp.where(live[:, None], gathered, 0)
    bmask = bmask[:NB]
    bitmap = jnp.sum(
        _pad8(bmask).reshape(-1, 8).astype(jnp.uint8) * _BIT_WEIGHTS,
        axis=-1).astype(jnp.uint8)

    emask = gathered != 0                                # (budget, 16)
    lanes = jnp.asarray([1 << k for k in range(_BLOCK)], jnp.int32)
    bmask16 = jnp.sum(emask.astype(jnp.int32) * lanes,
                      axis=1).astype(jnp.uint16)
    counts = jnp.sum(emask.astype(jnp.int32), axis=1)    # (budget,)
    offs = jnp.cumsum(counts) - counts
    within = jnp.cumsum(emask.astype(jnp.int32), axis=1) - 1
    nval = jnp.sum(counts)
    at = jnp.arange(budget * _BLOCK, dtype=jnp.int32).reshape(emask.shape)
    shift = jnp.where(emask, at - (offs[:, None] + within), 0)
    clipped = jnp.clip(gathered, -_I8_MAX, _I8_MAX).astype(jnp.int8)
    _, vals = _compact_left(shift.reshape(-1), clipped.reshape(-1))
    # fit the budget: cut, or fill where it passes the blocks' 16 each
    vals = jnp.pad(vals, (0, max(vbudget - vals.shape[0], 0)))[:vbudget]
    n_esc = jnp.sum((jnp.abs(gathered) > _I8_MAX).astype(jnp.int32))
    return (nblk, nval, n_esc, bitmap, bmask16, vals)


def block_sparse2_budgets(L: int, budget_div: int = _BLOCK_BUDGET_DIV,
                          val_div: int = _VAL_BUDGET_DIV) -> tuple[int, int]:
    """(blocks, values) a flat level vector of length `L` may fill
    before `_block_sparse_pack2`'s buffers overflow."""
    return (-(-L // _BLOCK)) // budget_div, L // val_div


def block_sparse2_fits(nblk: int, nval: int, n_esc: int, L: int,
                       budget_div: int = _BLOCK_BUDGET_DIV,
                       val_div: int = _VAL_BUDGET_DIV) -> bool:
    blocks, values = block_sparse2_budgets(L, budget_div, val_div)
    return (int(nblk) <= blocks and int(nval) <= values
            and int(n_esc) == 0)


def _block_sparse_unpack2(nblk: int, nval: int, bitmap: np.ndarray,
                          bmask16: np.ndarray, vals: np.ndarray,
                          L: int) -> np.ndarray:
    """Host inverse of _block_sparse_pack2 → flat int16 levels (the
    single numpy implementation lives in the jax-free layout module)."""
    from .layout import block_sparse_unpack2_host

    return block_sparse_unpack2_host(nblk, nval, bitmap, bmask16, vals, L)


@stage("compact")
def _compact_stream(nblk, nval, bitmap, bmask16, vals):
    """Device-side stream compaction (tier 3 of the transfer pack):
    concatenate the two-tier sparse streams into ONE dense uint8
    payload per GOP, so the bulk fetch moves a single compact byte
    array instead of three budget-padded int arrays.

    Layout (layout.split_compact is the host parser):

        [ bitmap (nb8 bytes) | bmask16 as little-endian byte pairs,
          first nblk live entries | vals, first nval entries ]

    The vals section lands RIGHT AFTER the live bmask16 entries via a
    dynamic_update_slice at offset nb8 + 2*nblk, so the used prefix —
    ``used = nb8 + 2*nblk + nval`` bytes, returned alongside — is
    contiguous: the host fetches ``payload[:, :used_max]`` (quantized,
    parallel/dispatch) and the padding tail never crosses the link.
    There is no escape section: levels beyond ±127 have no side-channel
    in _block_sparse_pack2 (n_esc > 0 forces the wave-wide dense
    fallback before any payload is read).

    Returns (used int32, payload uint8[nb8 + 2*budget + vbudget]).
    """
    nb8 = bitmap.shape[0]
    budget = bmask16.shape[0]
    lo = (bmask16 & jnp.uint16(0xFF)).astype(jnp.uint8)
    hi = (bmask16 >> 8).astype(jnp.uint8)
    mb = jnp.stack([lo, hi], axis=1).reshape(-1)         # (2*budget,)
    vals_u8 = jax.lax.bitcast_convert_type(vals, jnp.uint8)
    payload = jnp.concatenate(
        [bitmap, mb, jnp.zeros(vals.shape[0], jnp.uint8)])
    # Live bmask16 entries occupy [nb8, nb8 + 2*nblk); the dead tail of
    # `mb` beyond that is all-zero (pack2 zeroes dead gathered rows), so
    # overwriting it with the vals stream loses nothing.
    payload = jax.lax.dynamic_update_slice(
        payload, vals_u8, ((nb8 + 2 * nblk).astype(jnp.int32),))
    used = (nb8 + 2 * nblk + nval).astype(jnp.int32)
    return used, payload


def _pad8(mask):
    L = mask.shape[0]
    pad = (-L) % 8
    if pad:
        mask = jnp.concatenate([mask, jnp.zeros(pad, mask.dtype)])
    return mask


def sparse_fits(nnz: int, n_esc: int, L: int,
                budget_div: int = _SPARSE_BUDGET_DIV) -> bool:
    return (int(nnz) <= L // budget_div
            and int(n_esc) <= _SPARSE_ESCAPES)


def _sparse_unpack(nnz: int, n_esc: int, bitmap: np.ndarray,
                   vals: np.ndarray, esc_pos: np.ndarray,
                   esc_val: np.ndarray, L: int) -> np.ndarray:
    mask = np.unpackbits(bitmap)[:L].astype(bool)
    out = np.zeros(L, np.int32)
    out[mask] = vals[:nnz].astype(np.int32)
    if n_esc:
        out[esc_pos[:n_esc]] = esc_val[:n_esc]
    return out


@functools.partial(jax.jit, static_argnames=("mbw", "mbh", "rd"))
def _encode_intra_sparse(y, u, v, qp, *, mbw: int, mbh: int, rd=RD_OFF):
    out = _intra_core(y, u, v, qp, mbw=mbw, mbh=mbh, rd=rd)
    luma_dc, luma_ac, chroma_dc, chroma_ac = out[:4]
    with stage("layout"):
        parts = [luma_dc.reshape(-1), luma_ac.reshape(-1),
                 chroma_dc.reshape(-1), chroma_ac.reshape(-1)]
        if rd.ships_modes:
            parts.append(_mode_tail(*out[7:]).astype(jnp.int32))
        flat = jnp.concatenate(parts)
    return _sparse_pack(flat)


def _unpack_levels(flat: np.ndarray, mbw: int, mbh: int,
                   rd=RD_OFF) -> FrameLevels:
    nmb = mbw * mbh
    sizes = (nmb * 16, nmb * 16 * 15, nmb * 2 * 4, nmb * 2 * 4 * 15)
    offs = np.cumsum((0,) + sizes)
    # keep the transfer dtype: int16 feeds the zero-copy native entry
    # (cavlc_pack_islice16), int32 the original one — no widening here
    flat = np.asarray(flat)
    i4_modes = None
    if rd.ships_modes:
        mode16 = np.asarray(flat[offs[4]:offs[4] + nmb], np.int32)
        luma_mode = mode16 & 15
        chroma_mode = mode16 >> 4
        qp_delta = np.asarray(flat[offs[4] + nmb:offs[4] + 2 * nmb],
                              np.int32)
        if rd.intra4x4:
            i4_modes = unpack_i4_modes(
                np.asarray(flat[offs[4] + 2 * nmb:offs[4] + 6 * nmb])
                .astype(np.int16))
    else:
        luma_mode, chroma_mode = _mode_policy(mbw, mbh)
        qp_delta = None
    return FrameLevels(
        luma_mode=luma_mode,
        chroma_mode=chroma_mode,
        luma_dc=flat[offs[0]:offs[1]].reshape(nmb, 16),
        luma_ac=flat[offs[1]:offs[2]].reshape(nmb, 16, 15),
        chroma_dc=flat[offs[2]:offs[3]].reshape(nmb, 2, 4),
        chroma_ac=flat[offs[3]:offs[4]].reshape(nmb, 2, 4, 15),
        qp_delta=qp_delta,
        i4_modes=i4_modes,
    )


def encode_intra_jax(y: np.ndarray, u: np.ndarray, v: np.ndarray,
                     qp: int, rd=RD_OFF) -> FrameLevels:
    """Run the jitted intra compute and return host-side FrameLevels."""
    mbh, mbw = y.shape[0] // 16, y.shape[1] // 16
    yd, ud, vd = jnp.asarray(y), jnp.asarray(u), jnp.asarray(v)
    qpd = jnp.asarray(qp)
    L = intra_flat_len(mbw * mbh, rd)
    nnz, n_esc, bitmap, vals, esc_pos, esc_val = jax.device_get(
        _encode_intra_sparse(yd, ud, vd, qpd, mbw=mbw, mbh=mbh, rd=rd))
    if sparse_fits(nnz, n_esc, L):
        return _unpack_levels(
            _sparse_unpack(int(nnz), int(n_esc), bitmap, vals,
                           esc_pos, esc_val, L), mbw, mbh, rd)
    # Rare (very dense content): recompute (cheap) and fetch wide.
    flat16 = _encode_intra_packed(yd, ud, vd, qpd, mbw=mbw, mbh=mbh,
                                  dtype=jnp.int16, rd=rd)
    return _unpack_levels(np.asarray(flat16), mbw, mbh, rd)


def build_intra_encoder(y_shape: tuple[int, int], qp: int, rd=RD_OFF):
    """Encoder-facing factory: returns fn(y, u, v) -> FrameLevels."""
    def fn(y, u, v):
        return encode_intra_jax(y, u, v, qp, rd)
    return fn
