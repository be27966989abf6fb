"""The P-frame residual on 128-lane tiles (ISSUE 40) against the spec.

`jaxinter._residual_p` computes every 4x4-structured step — the core
transforms, the chroma DC Hadamard, the per-block and per-MB
reductions — as constant block-diagonal matrices over (T, H, 128)
tiles of the planes, integers carried as f32. Exactness is this file's
burden: the tile transforms and the whole stage are held, bit for bit,
to the numpy functions of `codecs/h264/transform.py` (the semantic
ground truth of the codec's math), on random and worst-case planes, at
every QP, on widths that are no multiple of 128 and on band shapes
whose chroma height is no multiple of 16. The bytes of a whole encode
are held elsewhere (test_inter, test_parallel, test_sfe: the in-repo
decoder and libavcodec).
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from thinvids_tpu.codecs.h264 import inter, jaxinter, rdo
from thinvids_tpu.codecs.h264 import transform as tf
from thinvids_tpu.codecs.h264.jaxcore import _QPC
from thinvids_tpu.codecs.h264.rdo import RD_OFF, RdConfig

#: luma shapes (H, W): one tile and less, a width over one tile that is
#: no multiple of 128 (the ladder's 864 / 432 / 320 in small), a chroma
#: plane of 5 x 16 luma rows (40 rows: the matrices go in groups of 8),
#: a band of 3 MB rows over several tiles
SHAPES = [(16, 64), (32, 128), (48, 272), (80, 144), (48, 656)]


def _extreme_residual(h, w, kind, seed=0):
    """Residual planes in [-255, 255] that drive the transform's sums
    to their bounds."""
    rng = np.random.default_rng(seed)
    if kind == "random":
        return rng.integers(-255, 256, (h, w))
    if kind == "plus":
        return np.full((h, w), 255)
    if kind == "checker":
        yy, xx = np.mgrid[0:h, 0:w]
        return np.where((yy + xx) % 2 == 0, 255, -255)
    # "signs": every 4x4 block takes the sign pattern of one basis
    # function CF[i] x CF[j], so that coefficient reaches +-(sum |CF|)^2
    # * 255 (9180 for i = j = 1)
    i, j = rng.integers(0, 4, (2, h // 4, w // 4))
    signs = np.sign(tf.CF[i][..., :, None] * tf.CF[j][..., None, :])
    flip = rng.choice([-1, 1], (h // 4, w // 4, 1, 1))
    return tf.plane_from_blocks(255 * signs * flip)


@functools.cache
def _fwd(shape):
    return jax.jit(lambda x: jaxinter._from_tiles(
        jaxinter._fwd4_tiles(jaxinter._to_tiles(x)), shape[1]))


@functools.cache
def _inv(shape):
    return jax.jit(lambda d: jaxinter._from_tiles(
        jaxinter._inv4_tiles(jaxinter._to_tiles(d)), shape[1]))


@pytest.mark.parametrize("kind", ["random", "plus", "checker", "signs"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_forward_transform_on_tiles_is_the_spec_forward(shape, kind):
    r = _extreme_residual(*shape, kind)
    want = tf.plane_from_blocks(tf.forward_4x4(tf.blocks_from_plane(r)))
    got = np.asarray(_fwd(shape)(jnp.asarray(r, jnp.int32)))
    assert got.dtype == np.int32 and np.array_equal(got, want)
    if kind == "signs":
        assert np.abs(want).max() == 9180       # the bound of the header


@pytest.mark.parametrize("qp", range(52))
def test_inverse_transform_on_tiles_is_the_spec_inverse_at_every_qp(qp):
    """The largest coefficients the quantizer can hand the inverse at
    this QP (worst-case residuals, forward, quantized, dequantized),
    with their >> 1 roundings, on a shape that is no multiple of 128."""
    shape = (48, 272)
    w = tf.forward_4x4(tf.blocks_from_plane(
        _extreme_residual(*shape, "signs", seed=qp)))
    d = tf.dequant_4x4(tf.quant_4x4(w, qp, intra=False), qp)
    d = d + np.random.default_rng(qp).integers(-1, 2, d.shape)   # odd ones
    want = tf.plane_from_blocks(tf.inverse_4x4(d))
    got = np.asarray(_inv(shape)(
        jnp.asarray(tf.plane_from_blocks(d), jnp.int32)))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_inverse_transform_is_exact_at_the_stated_bound(shape):
    """|d| < 2**18 with signs that add up: the bound the header of
    jaxinter's tile section states for f32 to stay exact."""
    rng = np.random.default_rng(18)
    d = rng.choice([-(2 ** 18 - 1), 2 ** 18 - 1, 2 ** 18 - 2, 12345],
                   shape)
    want = tf.plane_from_blocks(tf.inverse_4x4(tf.blocks_from_plane(d)))
    got = np.asarray(_inv(shape)(jnp.asarray(d, jnp.int32)))
    assert np.array_equal(got, want)
    assert np.abs(want).max() > 2 ** 21


# -- the whole stage against a numpy residual coder ---------------------

def ref_residual(cy, cu, cv, py, pu, pv, qp, pskip):
    """The P-frame residual in numpy, 4x4 block by 4x4 block, from the
    spec functions of codecs/h264/transform.py alone."""
    qpc = tf.chroma_qp(qp)
    mbh, mbw = cy.shape[0] // 16, cy.shape[1] // 16

    def blocks(plane):
        return tf.blocks_from_plane(plane.astype(np.int32))

    zy = tf.quant_4x4(tf.forward_4x4(blocks(cy) - blocks(py)), qp,
                      intra=False)                       # (H/4, W/4, 4, 4)

    def chroma_levels(c, p):
        w = tf.forward_4x4(blocks(c) - blocks(p))
        dc = w[..., 0, 0].reshape(mbh, 2, mbw, 2).transpose(0, 2, 1, 3)
        zdc = tf.chroma_dc_quant(tf.chroma_dc_forward(dc), qpc, intra=False)
        return zdc, tf.quant_4x4(w, qpc, intra=False, skip_dc=True)

    (udc, uac), (vdc, vac) = chroma_levels(cu, pu), chroma_levels(cv, pv)
    if pskip:
        def per_mb(z, k):       # (mbh*k, mbw*k, 4, 4) -> (mbh, mbw, k*k*16)
            return np.abs(z).reshape(mbh, k, mbw, k, 16).transpose(
                0, 2, 1, 3, 4).reshape(mbh, mbw, -1)
        every = np.concatenate(
            [per_mb(zy, 4), per_mb(uac, 2), per_mb(vac, 2),
             np.abs(udc).reshape(mbh, mbw, 4),
             np.abs(vdc).reshape(mbh, mbw, 4)], axis=-1)
        drop = (every.sum(-1) <= rdo.PSKIP_SUM) & (every.max(-1) <= 1)

        def over(k):
            return np.repeat(np.repeat(drop, k, 0), k, 1)[..., None, None]
        zy = np.where(over(4), 0, zy)
        uac, vac = np.where(over(2), 0, uac), np.where(over(2), 0, vac)
        udc, vdc = np.where(over(1), 0, udc), np.where(over(1), 0, vdc)

    def recon(pred, d):
        r = (tf.inverse_4x4(d) + 32) >> 6
        return np.clip(tf.plane_from_blocks(r) + pred, 0, 255)

    def chroma_recon(pred, zdc, zac):
        d = tf.dequant_4x4(zac, qpc)
        dcr = tf.chroma_dc_dequant(zdc, qpc)             # (mbh, mbw, 2, 2)
        d[..., 0, 0] = dcr.transpose(0, 2, 1, 3).reshape(2 * mbh, 2 * mbw)
        return recon(pred, d)

    return dict(
        luma=tf.plane_from_blocks(zy), u_dc=udc.reshape(-1, 4),
        v_dc=vdc.reshape(-1, 4), u_ac=tf.plane_from_blocks(uac),
        v_ac=tf.plane_from_blocks(vac),
        recon_y=recon(py, tf.dequant_4x4(zy, qp)),
        recon_u=chroma_recon(pu, udc, uac),
        recon_v=chroma_recon(pv, vdc, vac),
        nz4=(zy != 0).any(axis=(2, 3)))


def _frame(shape, kind, seed):
    """(cy, cu, cv, py, pu, pv): current and predicted planes, 0..255."""
    rng = np.random.default_rng(seed)

    def pair(h, w):
        if kind == "random":
            return rng.integers(0, 256, (h, w)), rng.integers(0, 256, (h, w))
        if kind == "near":      # a good prediction: most MBs can skip
            p = rng.integers(0, 256, (h, w))
            noisy = rng.random((h // 8, w // 8)) < 0.3
            e = rng.integers(-3, 4, (h, w)) * np.kron(
                noisy, np.ones((8, 8), int))
            return np.clip(p + e, 0, 255), p
        r = _extreme_residual(h, w, kind, seed)          # all +-255
        return np.where(r > 0, 255, 0), np.where(r > 0, 0, 255)

    H, W = shape
    (cy, py), (cu, pu), (cv, pv) = (
        pair(H, W), pair(H // 2, W // 2), pair(H // 2, W // 2))
    return cy, cu, cv, py, pu, pv


@functools.cache
def _stage(shape, rd, blocked=False):
    H, W = shape

    def run(planes, qp):
        return jaxinter._residual_p(
            *planes, qp, _QPC[jnp.clip(qp, 0, 51)], mbw=W // 16,
            mbh=H // 16, blocked=blocked, rd=rd)
    return jax.jit(run)


def _run_stage(shape, planes, qp, rd, blocked=False):
    out = _stage(shape, rd, blocked)(
        tuple(jnp.asarray(p, jnp.int16) for p in planes), jnp.int32(qp))
    ll, cdc, cac, ry, ru, rv, nz4 = (np.asarray(o) for o in out)
    if blocked:
        return ll, cdc, cac
    assert ll.dtype == cdc.dtype == cac.dtype == ry.dtype == np.int16
    return dict(luma=ll, u_dc=cdc[0], v_dc=cdc[1], u_ac=cac[0], v_ac=cac[1],
                recon_y=ry, recon_u=ru, recon_v=rv, nz4=nz4)


def _assert_same(got, want):
    for name, value in want.items():
        assert got[name].shape == value.shape, name
        assert np.array_equal(got[name], value), name


PSKIP = RdConfig(pskip=True, deblock=True)


@pytest.mark.parametrize("qp", range(52))
def test_stage_is_the_spec_on_worst_case_planes_at_every_qp(qp):
    """All residuals +-255 in the sign patterns that drive each
    coefficient to its bound: the largest levels, DC levels and
    dequantized values a QP can produce, through the whole stage."""
    shape = (32, 144)
    planes = _frame(shape, "signs", qp)
    _assert_same(_run_stage(shape, planes, qp, RD_OFF),
                 ref_residual(*planes, qp, pskip=False))


@pytest.mark.parametrize("pskip", [False, True], ids=["lib", "pskip"])
@pytest.mark.parametrize("kind", ["random", "near", "checker"])
@pytest.mark.parametrize("qp", [0, 17, 25, 27, 38, 51])
def test_stage_is_the_spec_on_content(qp, kind, pskip):
    shape = (48, 272)
    planes = _frame(shape, kind, 100 + qp)
    want = ref_residual(*planes, qp, pskip)
    _assert_same(_run_stage(shape, planes, qp, PSKIP if pskip else RD_OFF),
                 want)
    if kind == "near" and pskip and qp >= 25:
        assert not want["luma"].any(axis=1).all(), "no MB row was dropped"


@pytest.mark.parametrize("pskip", [False, True], ids=["lib", "pskip"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_stage_is_the_spec_on_every_shape(shape, pskip):
    """Widths that are no multiple of 128 (padded inside the stage),
    a chroma height that is no multiple of 16, a band's shape."""
    planes = _frame(shape, "near", sum(shape))
    _assert_same(_run_stage(shape, planes, 27, PSKIP if pskip else RD_OFF),
                 ref_residual(*planes, 27, pskip))


@pytest.mark.parametrize("shape", [(32, 144), (48, 272)],
                         ids=lambda s: f"{s[0]}x{s[1]}")
def test_blocked_levels_are_the_planes_in_the_packers_layout(shape):
    """The conformance path (`blocked=True`) re-lays the finished level
    planes and nothing else: its arrays equal the host's
    `inter.blocked_from_planes` of the plane outputs."""
    planes = _frame(shape, "random", 5)
    plane = _run_stage(shape, planes, 30, RD_OFF)
    ll, cdc, cac = _run_stage(shape, planes, 30, RD_OFF, blocked=True)
    mbh, mbw = shape[0] // 16, shape[1] // 16
    want_ll, want_cac = inter.blocked_from_planes(
        plane["luma"], plane["u_ac"], plane["v_ac"], mbw, mbh)
    assert np.array_equal(ll, want_ll) and np.array_equal(cac, want_cac)
    assert np.array_equal(cdc, np.stack([plane["u_dc"], plane["v_dc"]], 1))
