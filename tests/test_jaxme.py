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


# (192, 128) pads to H4=128 → RG=2 grid bands: the multi-band row-block
# index maps (2*r+k) in _me_pallas and the band-relative row bases in
# the kernel only execute with >= 2 bands (ADVICE round 5: both original
# shapes collapsed to a single band, leaving a 1080p-sized blind spot).
@pytest.mark.parametrize("w,h", [(128, 64), (320, 32), (192, 128)])
def test_pallas_kernel_matches_xla_reference(w, h):
    cur, ref, ref_u, ref_v = _mixed_motion_frames(w, h)
    cy = jnp.asarray(cur, jnp.int16)
    ry = jnp.asarray(ref, jnp.int16)
    ru = jnp.asarray(ref_u, jnp.int16)
    rv = jnp.asarray(ref_v, jnp.int16)
    pmv = jnp.asarray([2, -3], jnp.int32)
    qp = jnp.asarray(27, jnp.int32)

    centers = jaxme.centers_from(cy, ry, pmv)
    lam = jnp.asarray(jaxme.LAMBDA_H)[27]

    out_k = jax.device_get(jaxme.me_search_pallas(
        cy, ry, ru, rv, centers, lam, interpret=True))
    out_x = jax.device_get(jaxme.me_search_xla(
        cy, ry, ru, rv, centers, lam))

    names = ["mv", "pred_y", "pred_u", "pred_v"]
    for name, a, b in zip(names, out_k, out_x):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"pallas kernel diverges from XLA reference: {name}")

    # sanity: the engineered content really did split MB decisions
    mv = np.asarray(out_x[0]).reshape(-1, 2)
    assert len({tuple(v) for v in mv}) > 1


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
