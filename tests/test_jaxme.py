"""Pallas ME kernel vs the XLA reference implementation.

`me_search_xla` is the executable spec (it backs the CPU conformance
tests against the libavcodec oracle); this file checks that the
PRODUCTION Pallas kernel — run in the Pallas interpreter on CPU —
computes the identical (mv, pred) on content engineered so neighboring
macroblocks pick DIFFERENT candidates. That non-uniformity matters: a
per-MB -> per-lane mask-expansion bug (pltpu.repeat is a tile repeat,
not an element repeat) was invisible on uniform-motion content because
every MB of a lane tile took the same candidate.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from thinvids_tpu.codecs.h264 import jaxme


def _mixed_motion_frames(w, h, seed=0):
    """(cur, ref_y, ref_u, ref_v) where different MBs have different
    true motion: the left half pans (+3, +3), the right half (-2, +1),
    with texture + noise so SADs are distinctive."""
    rng = np.random.default_rng(seed)
    pad = 8
    scene = rng.integers(0, 255, (h + 2 * pad, w + 2 * pad)).astype(np.uint8)
    ref = scene[pad:pad + h, pad:pad + w]
    cur = np.empty_like(ref)
    cur[:, :w // 2] = scene[pad + 3:pad + 3 + h, pad + 3:pad + 3 + w // 2]
    cur[:, w // 2:] = scene[pad - 2:pad - 2 + h,
                            pad + w // 2 + 1:pad + w + 1]
    ref_u = rng.integers(0, 255, (h // 2, w // 2)).astype(np.uint8)
    ref_v = rng.integers(0, 255, (h // 2, w // 2)).astype(np.uint8)
    return cur, ref, ref_u, ref_v


def _kernel_and_spec(planes, centers, lam, what):
    """Run the kernel (Pallas interpreter) and the XLA spec on (cur,
    ref_y, ref_u, ref_v) uint8 planes, require identical (mv, pred_y,
    pred_u, pred_v), and return the spec's mv as (n, 2)."""
    cy, ry, ru, rv = (jnp.asarray(p, jnp.int16) for p in planes)
    out_k = jax.device_get(jaxme.me_search_pallas(
        cy, ry, ru, rv, centers, lam, interpret=True))
    out_x = jax.device_get(jaxme.me_search_xla(
        cy, ry, ru, rv, centers, lam))
    for name, a, b in zip(["mv", "pred_y", "pred_u", "pred_v"],
                          out_k, out_x):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"{what}: {name}")
    return np.asarray(out_x[0]).reshape(-1, 2)


# (192, 128) pads to H4=128 → RG=2 grid bands: the multi-band row-block
# index maps (2*r+k) in _me_pallas and the band-relative row bases in
# the kernel only execute with >= 2 bands (ADVICE round 5: both original
# shapes collapsed to a single band, leaving a 1080p-sized blind spot).
@pytest.mark.parametrize("w,h", [(128, 64), (320, 32), (192, 128)])
def test_pallas_kernel_matches_xla_reference(w, h):
    planes = _mixed_motion_frames(w, h)
    centers = jaxme.centers_from(jnp.asarray(planes[0], jnp.int16),
                                 jnp.asarray(planes[1], jnp.int16),
                                 jnp.asarray([2, -3], jnp.int32))
    lam = jnp.asarray(jaxme.LAMBDA_H)[27]
    mv = _kernel_and_spec(planes, centers, lam,
                          "pallas kernel diverges from XLA reference")
    # sanity: the engineered content really did split MB decisions
    assert len({tuple(v) for v in mv}) > 1


def _tie_frames(w, h, kind):
    """Content on which MANY candidates have the same SAD, so (with
    lam 0) only the order of OFFSET_TABLE decides the winner: `flat`
    ties every candidate of every macroblock; `periodic` (period 2 in
    x, 4 in y) ties whole families of them, different ones per
    macroblock column because of the vertical bars of another period
    in the right half."""
    yy, xx = np.mgrid[0:h, 0:w]
    if kind == "flat":
        ref = np.full((h, w), 97, np.uint8)
        cur = ref.copy()
    else:
        ref = (60 + 80 * (xx % 2) + 40 * ((yy // 2) % 2)).astype(np.uint8)
        ref[:, w // 2:] = (50 + 90 * ((xx[:, w // 2:] // 2) % 2)
                           ).astype(np.uint8)
        cur = np.roll(ref, (2, 2), axis=(0, 1))
    cu = (40 + 3 * ((xx[:h // 2, :w // 2] // 2) % 4)
          + 5 * (yy[:h // 2, :w // 2] % 3)).astype(np.uint8)
    cv = (200 - cu).astype(np.uint8)
    return cur, ref, cu, cv


# The kernel block-sums a whole ROW of candidates in one matmul and
# then walks the row; the spec walks the table one candidate at a
# time. With lam 0 on tied content the first best of the table must
# win in both — mv AND all three predictions (the chroma planes are
# textured, so a winner out of order shows there even where luma is
# flat). 320 x 128: 2 bands x 2 chunks.
@pytest.mark.parametrize("kind", ["flat", "periodic"])
def test_pallas_kernel_breaks_ties_in_table_order(kind):
    w, h = 320, 128
    centers = jnp.asarray([[4, -2], [-2, 6], [0, 0]], jnp.int32)
    mv = _kernel_and_spec(_tie_frames(w, h, kind), centers,
                          jnp.asarray(0, jnp.int32),
                          f"tie broken out of table order ({kind})")
    if kind == "flat":
        # every candidate ties everywhere: the table's first entry wins
        _, wy0, wx0 = jaxme.OFFSET_TABLE[0]
        assert {tuple(v) for v in mv} == {(2 * 4 + wy0, 2 * -2 + wx0)}
    else:
        assert len({tuple(v) for v in mv}) > 1


# ---------------------------------------------------------------------------
# the kernel's shape: one block-sum matmul per ROW of candidates
# ---------------------------------------------------------------------------

def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (list, tuple)) else [v]):
            j = getattr(sub, "jaxpr", sub)
            if hasattr(j, "eqns"):
                yield j


def _kernel_matmuls(jaxpr, trips=1):
    """[(times run per grid step, left-side rows)] of every matmul in
    the kernel body, loops multiplied out."""
    out = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        assert name != "while", "a loop without a static trip count"
        if name == "dot_general":
            out.append((trips, eqn.invars[0].aval.shape[0]))
        inner = trips * eqn.params["length"] if name == "scan" else trips
        for sub in _sub_jaxprs(eqn):
            out += _kernel_matmuls(sub, inner)
    return out


def test_kernel_runs_one_matmul_per_row_of_candidates():
    """The mechanism's "how often" is static: per grid step the MXU is
    handed the constant selector once per row of candidates (39 rows),
    not once per candidate (227), and never for fewer than 128 rows."""
    H, W = 128, 320
    _mbh, _mbw, H4, _RG, WcK, _nch, W2K, _WcuK, W2cK = jaxme._geom(H, W)
    sd = jax.ShapeDtypeStruct
    closed = jax.make_jaxpr(functools.partial(
        jaxme._me_pallas, H=H, W=W, interpret=False))(
        sd((1, 8), jnp.int32), sd((H4, WcK), jnp.int16),
        sd((3, H4 + 128, W2K), jnp.int16),
        sd((3, H4 // 2 + 64, W2cK), jnp.int16),
        sd((3, H4 // 2 + 64, W2cK), jnp.int16),
        sd((256, 384), jnp.bfloat16))

    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in _sub_jaxprs(eqn):
                yield from find(sub)

    calls = list(find(closed.jaxpr))
    assert len(calls) == 1, "one kernel, one pallas_call"
    matmuls = _kernel_matmuls(calls[0].params["jaxpr"])

    classes = (jaxme.CENTER_CLASSES, jaxme.CENTER_B_CLASSES,
               jaxme.ZERO_CLASSES)
    rows = sum(len(wys) for cl in classes for (_p, wys, _wxs) in cl)
    cands = len(jaxme.OFFSET_TABLE)
    assert (rows, cands) == (39, 227)
    assert sum(t for t, _m in matmuls) == rows
    assert 4 * rows < cands
    # every candidate is in exactly one matmul, 64 rows of it each
    assert sum(t * m for t, m in matmuls) == 64 * cands
    assert min(m for _t, m in matmuls) >= 128


# ---------------------------------------------------------------------------
# the production (non-interpret) kernel, lowered for TPU from the CPU
#
# On CPU `use_pallas()` is False, so every other test takes the XLA
# mirror and the kernel only ever runs in the interpreter, outside
# shard_map. These trace the path a chip takes — `use_pallas` forced
# on — and cross-lower it for TPU, so a jax upgrade that breaks the
# kernel (or its legality under shard_map's check_vma) fails here
# instead of on the device.
# ---------------------------------------------------------------------------

@pytest.fixture
def force_pallas(monkeypatch):
    # traces are cached per (function, shapes): a trace another test
    # took through the XLA mirror must not be handed back here, nor
    # this one's to a later test
    jax.clear_caches()
    monkeypatch.setattr(jaxme, "use_pallas", lambda: True)
    yield
    jax.clear_caches()


def _tpu_text(jitted, *args, **kwargs) -> str:
    return jitted.trace(*args, **kwargs).lower(
        lowering_platforms=("tpu",)).as_text()


def test_kernel_lowers_for_tpu_at_1080p(force_pallas):
    H, W = 1088, 1920
    plane = jax.ShapeDtypeStruct((H, W), jnp.int16)
    chroma = jax.ShapeDtypeStruct((H // 2, W // 2), jnp.int16)
    text = _tpu_text(
        jax.jit(jaxme.me_search), plane, plane, chroma, chroma,
        jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32))
    assert "tpu_custom_call" in text


def test_kernel_lowers_inside_gop_wave_shard_map(force_pallas):
    from jax.sharding import Mesh

    from thinvids_tpu.parallel import dispatch

    mesh = Mesh(np.array(jax.devices()[:8]), ("gop",))
    G, F, H, W = 8, 2, 64, 256
    text = _tpu_text(
        dispatch._encode_wave_gop,
        jax.ShapeDtypeStruct((G, F, H, W), jnp.uint8),
        jax.ShapeDtypeStruct((G, F, H // 2, W // 2), jnp.uint8),
        jax.ShapeDtypeStruct((G, F, H // 2, W // 2), jnp.uint8),
        jax.ShapeDtypeStruct((G,), jnp.int32),
        mbw=W // 16, mbh=H // 16, mesh=mesh, compact=True)
    assert "tpu_custom_call" in text


def test_kernel_lowers_inside_sfe_p_step_shard_map(force_pallas):
    from jax.sharding import Mesh

    from thinvids_tpu.parallel import dispatch

    n = 8
    mesh = Mesh(np.array(jax.devices()[:n]), ("band",))
    mbh_band, W = 2, 256
    H = n * mbh_band * 16
    y = jax.ShapeDtypeStruct((H, W), jnp.uint8)
    c = jax.ShapeDtypeStruct((H // 2, W // 2), jnp.uint8)
    ry = jax.ShapeDtypeStruct((H, W), jnp.int16)
    rc = jax.ShapeDtypeStruct((H // 2, W // 2), jnp.int16)
    text = _tpu_text(
        dispatch._sfe_p_step, y, c, c, ry, rc, rc,
        jax.ShapeDtypeStruct((n, 2), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((n, 1), jnp.int32),
        mbw=W // 16, mbh_band=mbh_band, mesh=mesh, halo_rows=32,
        num_bands=n)
    assert "tpu_custom_call" in text


def test_use_pallas_is_not_silent(monkeypatch):
    """cpu -> the XLA mirror, tpu -> the kernel, anything else raises
    (the mirror is not a fallback for an unknown platform); the choice
    is readable afterwards (`/metrics_snapshot` -> motion_search)."""
    assert jaxme.use_pallas() is False
    assert jaxme.motion_search() == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        jaxme.use_pallas()
