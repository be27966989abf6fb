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
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from thinvids_tpu.codecs.h264 import jaxme
from thinvids_tpu.codecs.h264.rdo import MV_PER_PEL


def _frames_over_noise(w, h, seed, cut):
    """(cur, ref_y, ref_u, ref_v) over a noise scene: ref is the scene,
    `cut(scene, pad)` the current frame taken from it (pad samples of
    scene lie round the picture); texture + noise, so SADs are
    distinctive."""
    rng = np.random.default_rng(seed)
    pad = 8
    scene = rng.integers(0, 255, (h + 2 * pad, w + 2 * pad)).astype(np.uint8)
    ref = scene[pad:pad + h, pad:pad + w]
    cur = cut(scene, pad)
    ref_u = rng.integers(0, 255, (h // 2, w // 2)).astype(np.uint8)
    ref_v = rng.integers(0, 255, (h // 2, w // 2)).astype(np.uint8)
    return cur, ref, ref_u, ref_v


def _mixed_motion_frames(w, h, seed=0):
    """Different MBs have different true motion: the left half pans
    (+3, +3), the right half (-2, +1)."""
    def cut(scene, pad):
        return np.concatenate([
            scene[pad + 3:pad + 3 + h, pad + 3:pad + 3 + w // 2],
            scene[pad - 2:pad - 2 + h, pad + w // 2 + 1:pad + w + 1]],
            axis=1)
    return _frames_over_noise(w, h, seed, cut)


SUBPELS = ("half", "quarter")


def _kernel_and_spec(planes, centers, lam, what, subpel="half"):
    """Run the kernel (Pallas interpreter) and the XLA spec on (cur,
    ref_y, ref_u, ref_v) uint8 planes, require identical (mv, pred_y,
    pred_u, pred_v), and return the spec's mv as (n, 2)."""
    cy, ry, ru, rv = (jnp.asarray(p, jnp.int16) for p in planes)
    out_k = jax.device_get(jaxme.me_search_pallas(
        cy, ry, ru, rv, centers, lam, interpret=True, subpel=subpel))
    out_x = jax.device_get(jaxme.me_search_xla(
        cy, ry, ru, rv, centers, lam, subpel=subpel))
    for name, a, b in zip(["mv", "pred_y", "pred_u", "pred_v"],
                          out_k, out_x):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"{what}: {name}")
    return np.asarray(out_x[0]).reshape(-1, 2)


# (192, 128) pads to H4=128 → RG=2 grid bands: the multi-band row-block
# index maps (2*r+k) in _me_pallas and the band-relative row bases in
# the kernel only execute with >= 2 bands (ADVICE round 5: both original
# shapes collapsed to a single band, leaving a 1080p-sized blind spot).
@pytest.mark.parametrize("subpel", SUBPELS)
@pytest.mark.parametrize("w,h", [(128, 64), (320, 32), (192, 128)])
def test_pallas_kernel_matches_xla_reference(w, h, subpel):
    planes = _mixed_motion_frames(w, h)
    per_pel = MV_PER_PEL[subpel]
    # the median centre lands on (2, -2) pel under either unit
    centers = jaxme.centers_from(jnp.asarray(planes[0], jnp.int16),
                                 jnp.asarray(planes[1], jnp.int16),
                                 jnp.asarray([2, -3], jnp.int32)
                                 * (per_pel // 2), per_pel)
    lam = jnp.asarray(jaxme._LAMBDAS[subpel])[27]
    mv = _kernel_and_spec(planes, centers, lam,
                          "pallas kernel diverges from XLA reference",
                          subpel)
    # sanity: the engineered content really did split MB decisions
    assert len({tuple(v) for v in mv}) > 1


def _blurred_motion_frames(w, h, seed=1):
    """As `_mixed_motion_frames`, the current frame a 2x2 box blur of
    the moved scene: its best match lies BETWEEN integer samples, so
    the quarter rows win macroblocks (the sharp frames never pick
    one)."""
    cur, ref, ru, rv = _mixed_motion_frames(w, h, seed)
    c = cur.astype(np.int32)
    c = (c + np.roll(c, 1, 0) + np.roll(c, 1, 1)
         + np.roll(c, (1, 1), (0, 1)) + 2) >> 2
    return c.astype(np.uint8), ref, ru, rv


@pytest.mark.parametrize("w,h", [(128, 64), (192, 128)])
def test_quarter_rows_win_and_kernel_matches(w, h):
    planes = _blurred_motion_frames(w, h)
    centers = jnp.asarray([[4, 4], [2, 2], [0, 0]], jnp.int32)
    mv = _kernel_and_spec(planes, centers, jnp.asarray(2, jnp.int32),
                          "quarter rows", "quarter")
    assert (mv & 1).any(axis=1).mean() > 0.25


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
@pytest.mark.parametrize("subpel", SUBPELS)
@pytest.mark.parametrize("kind", ["flat", "periodic"])
def test_pallas_kernel_breaks_ties_in_table_order(kind, subpel):
    w, h = 320, 128
    centers = jnp.asarray([[4, -2], [-2, 6], [0, 0]], jnp.int32)
    mv = _kernel_and_spec(_tie_frames(w, h, kind), centers,
                          jnp.asarray(0, jnp.int32),
                          f"tie broken out of table order ({kind})", subpel)
    if kind == "flat":
        # every candidate ties everywhere: the table's first entry wins
        _, wy0, wx0 = jaxme.offset_table(subpel)[0]
        per_pel = MV_PER_PEL[subpel]
        assert {tuple(v) for v in mv} == {(per_pel * 4 + wy0,
                                           per_pel * -2 + wx0)}
    else:
        assert len({tuple(v) for v in mv}) > 1


def _per_mb_motion_frames(w, h, dy, seed=3):
    """Every macroblock column c moves by its OWN whole-pixel vector
    (dy, (c % 9) - 4): the sixteen macroblocks of a 256-lane chunk all
    find their match in ONE row of candidates (the integer class's row
    wy = 2 dy, nine candidates) and nine different candidates of it."""
    def cut(scene, pad):
        return np.concatenate([
            scene[pad + dy:pad + dy + h,
                  pad + 16 * c + c % 9 - 4:pad + 16 * c + c % 9 + 12]
            for c in range(w // 16)], axis=1)
    return _frames_over_noise(w, h, seed, cut)


# The running best is per MACROBLOCK and a row's `take` masks are
# widened to luma and chroma lanes together, once per row of candidates:
# a mask that lands on a neighbour's lanes (or a chroma mask on the
# wrong eight) shows here, where every macroblock of a chunk takes
# another candidate of the same row, and nowhere on uniform motion.
# 512 x 64: two chunks; the second one's columns start another cycle.
@pytest.mark.parametrize("subpel", SUBPELS)
@pytest.mark.parametrize("dy", [1, -2])
def test_neighbours_take_different_winners_in_one_row(dy, subpel):
    w, h = 512, 64
    per_pel = MV_PER_PEL[subpel]
    mv = _kernel_and_spec(_per_mb_motion_frames(w, h, dy),
                          jnp.zeros((3, 2), jnp.int32),
                          jnp.asarray(jaxme._LAMBDAS[subpel])[27],
                          "mask expansion, lane for lane", subpel)
    mv = mv.reshape(h // 16, w // 16, 2)
    want_x = per_pel * (np.arange(w // 16) % 9 - 4)
    # interior columns: a column at the picture's edge may match the
    # clamped reference as well somewhere else
    assert (mv[:, 1:-1, 0] == per_pel * dy).all()
    assert (mv[:, 1:-1, 1] == want_x[None, 1:-1]).all()
    for chunk in (mv[0, :16, 1], mv[0, 16:, 1]):
        assert len(set(chunk.tolist())) >= 8


def test_quarter_table_is_the_half_table_and_more():
    """The quarter table holds the half table's candidates in quarter
    units and, beside them: the fine half-sample classes round the
    temporal-median centre (as the probe's has them) and, after each of
    the median and the zero centre's own, every offset with an odd
    quarter component inside +-1 pixel, each once."""
    table = jaxme.offset_table("quarter")
    half = [(c, 2 * wy, 2 * wx) for (c, wy, wx) in jaxme.OFFSET_TABLE]
    even = [t for t in table if not (t[1] | t[2]) & 1]
    assert len(even) == len(set(even)) and set(half) < set(even)
    assert [t for t in table if t in set(half)] == half     # its order
    # what the median's centre gains on the half grid is what the
    # probe's has there
    assert sorted((0,) + t[1:] for t in set(even) - set(half)) \
        == sorted(t for t in half if t[0] == 0
                  and (1, ) + t[1:] not in set(half))
    odd = [t for t in table if (t[1] | t[2]) & 1]
    assert len(odd) == len(set(odd)) == 56 + 56
    for ci in (1, 2):
        assert {(qy, qx) for (c, qy, qx) in odd if c == ci} == {
            (qy, qx) for qy in range(-4, 5)
            for qx in range(-4, 5) if (qy | qx) & 1}
        last_even = max(i for i, t in enumerate(table)
                        if t[0] == ci and not (t[1] | t[2]) & 1)
        assert all(i > last_even for i, t in enumerate(table)
                   if t[0] == ci and (t[1] | t[2]) & 1)
    assert len(table) == 379 and len(jaxme.OFFSET_TABLE) == 227
    with pytest.raises(ValueError, match="subpel"):
        jaxme.offset_table("eighth")


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
    """[(times run per grid step, left side's shape, right side's
    shape)] of every matmul in the kernel body, loops multiplied out."""
    out = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        assert name != "while", "a loop without a static trip count"
        if name == "dot_general":
            out.append((trips, eqn.invars[0].aval.shape,
                        eqn.invars[1].aval.shape))
        inner = trips * eqn.params["length"] if name == "scan" else trips
        for sub in _sub_jaxprs(eqn):
            out += _kernel_matmuls(sub, inner)
    return out


def _kernel_loop_carries(jaxpr):
    """[avals carried from step to step] of every loop in the kernel
    body."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            nc = eqn.params["num_consts"]
            out.append([v.aval for v in
                        eqn.invars[nc:nc + eqn.params["num_carry"]]])
        for sub in _sub_jaxprs(eqn):
            out += _kernel_loop_carries(sub)
    return out


@functools.lru_cache(maxsize=None)
def _kernel_jaxpr(subpel):
    """The body of the one `pallas_call` `_me_pallas` makes (320 x 128:
    2 bands x 2 chunks)."""
    H, W = 128, 320
    _mbh, _mbw, H4, _RG, WcK, _nch, W2K, _WcuK, W2cK = jaxme._geom(H, W)
    sd = jax.ShapeDtypeStruct
    closed = jax.make_jaxpr(functools.partial(
        jaxme._me_pallas, H=H, W=W, interpret=False, subpel=subpel))(
        sd((1, 8), jnp.int32), sd((H4, WcK), jnp.int16),
        sd((3, H4 + 128, W2K), jnp.int16),
        sd((3, H4 // 2 + 64, W2cK), jnp.int16),
        sd((3, H4 // 2 + 64, W2cK), jnp.int16),
        sd(jaxme._ss_np().shape, jnp.bfloat16),
        sd(jaxme._ex_np().shape, jnp.bfloat16))

    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in _sub_jaxprs(eqn):
                yield from find(sub)

    calls = list(find(closed.jaxpr))
    assert len(calls) == 1, "one kernel, one pallas_call"
    return calls[0].params["jaxpr"]


@pytest.mark.parametrize("subpel", SUBPELS)
def test_kernel_runs_one_matmul_per_row_of_candidates(subpel):
    """The mechanism's "how often" is static. The selector the kernel is
    handed is (256, 128): one column a macroblock of the chunk, not one
    a lane. Per grid step the MXU gets it once per row of candidates
    (39 rows; 68 under "quarter"), not once per candidate (227; 379),
    never for fewer than 128 rows, and each candidate's 64 rows pass it
    exactly once. The row's `take` masks are widened to lanes by one
    more matmul a row, against the (128, 384) expander — counted apart
    — and nothing the MXU writes is wider than its 384 columns."""
    assert jaxme._ss_np().shape == (256, 128)
    assert jaxme._ex_np().shape == (128, 384)
    matmuls = _kernel_matmuls(_kernel_jaxpr(subpel))
    select = [(t, lhs[0]) for t, lhs, rhs in matmuls if rhs == (256, 128)]
    expand = [(t, lhs[0]) for t, lhs, rhs in matmuls if rhs == (128, 384)]
    assert len(select) + len(expand) == len(matmuls)
    assert max(rhs[1] for _t, _lhs, rhs in matmuls) <= 384

    rows = sum(len(wys) for (cl, _q) in jaxme.CENTERS[subpel]
               for (_p, wys, _wxs) in cl) \
        + sum(len(qys) for (_cl, qrows) in jaxme.CENTERS[subpel]
              for (_yf, qys, _qxs) in qrows or ())
    cands = len(jaxme.offset_table(subpel))
    assert (rows, cands) == {"half": (39, 227),
                             "quarter": (68, 379)}[subpel]
    assert sum(t for t, _m in select) == rows
    assert 4 * rows < cands
    # every candidate is in exactly one matmul, 64 rows of it each
    assert sum(t * m for t, m in select) == 64 * cands
    assert min(m for _t, m in select) >= 128
    # the expander: once a row of candidates; a candidate's mask goes on
    # 8 sublanes deep for each of its 4 macroblock rows
    assert sum(t for t, _m in expand) == rows
    assert sum(t * m for t, m in expand) == 32 * cands


@pytest.mark.parametrize("subpel", SUBPELS)
def test_kernel_keeps_one_running_best(subpel):
    """Luma and chroma follow ONE running best per macroblock: what a
    class's loop carries beside the planes and the three predictions is
    one (4, 128) f32 cost and its two (4, 128) int32 vector components
    — no second cost over chroma lanes (the (4, 128) f32 `bestcc` the
    kernel had until PR 50), none per luma lane ((4, 256))."""
    loops = _kernel_loop_carries(_kernel_jaxpr(subpel))
    assert len(loops) == sum(len(cl) for (cl, _q) in jaxme.CENTERS[subpel])
    for carries in loops:
        small = [(a.shape, str(a.dtype)) for a in carries
                 if len(a.shape) == 2 and a.shape[0] == 4]
        assert sorted(small) == [((4, 128), "float32"),
                                 ((4, 128), "int32"), ((4, 128), "int32")]


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


def _rd(subpel):
    from thinvids_tpu.codecs.h264.rdo import RdConfig

    return RdConfig(subpel=subpel)


@pytest.mark.parametrize("subpel", SUBPELS)
def test_kernel_lowers_for_tpu_at_1080p(force_pallas, subpel):
    H, W = 1088, 1920
    plane = jax.ShapeDtypeStruct((H, W), jnp.int16)
    chroma = jax.ShapeDtypeStruct((H // 2, W // 2), jnp.int16)
    text = _tpu_text(
        jax.jit(functools.partial(jaxme.me_search, subpel=subpel)),
        plane, plane, chroma, chroma,
        jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("subpel", SUBPELS)
def test_kernel_lowers_inside_gop_wave_shard_map(force_pallas, subpel):
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
        mbw=W // 16, mbh=H // 16, mesh=mesh, rd=_rd(subpel))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("subpel", SUBPELS)
def test_kernel_lowers_inside_sfe_p_step_shard_map(force_pallas, subpel):
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
        num_bands=n, rd=_rd(subpel))
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


# ---------------------------------------------------------------------------
# §8.4.2.2.1 / §8.4.2.2.2, position by position
#
# The plain functions below are written from the standard's equations
# (8-241..8-261, Figure 8-4, Table 8-12; 8-266), share nothing with
# jaxme or the decoder, and work on one edge-padded array at a time.
# ---------------------------------------------------------------------------

_PAD = 12


def _at(R, h, w, dy, dx):
    return R[_PAD + dy:_PAD + dy + h, _PAD + dx:_PAD + dx + w]


def _tap(a, b, c, d, e, f):
    return a - 5 * b + 20 * c + 20 * d - 5 * e + f


def _clip255(x):
    return np.clip(x, 0, 255)


def _plain_luma_positions(ref):
    """{letter: (h, w) plane} of Figure 8-4's samples for every integer
    sample G of `ref` (coordinates clamped to the picture)."""
    h, w = ref.shape
    R = np.pad(ref.astype(np.int64), _PAD, mode="edge")
    G = lambda dy=0, dx=0: _at(R, h, w, dy, dx)
    b1 = lambda dy=0, dx=0: _tap(*(G(dy, dx + k) for k in range(-2, 4)))
    h1 = lambda dy=0, dx=0: _tap(*(G(dy + k, dx) for k in range(-2, 4)))
    b_ = lambda dy=0, dx=0: _clip255((b1(dy, dx) + 16) >> 5)
    h_ = lambda dy=0, dx=0: _clip255((h1(dy, dx) + 16) >> 5)
    j = _clip255((_tap(*(b1(k, 0) for k in range(-2, 4))) + 512) >> 10)
    mean = lambda p, q: (p + q + 1) >> 1
    b, hh, m, s = b_(), h_(), h_(0, 1), b_(1, 0)
    return {
        "G": G(), "b": b, "h": hh, "j": j,
        "a": mean(G(), b), "c": mean(G(0, 1), b),
        "d": mean(G(), hh), "n": mean(G(1, 0), hh),
        "f": mean(b, j), "i": mean(hh, j), "k": mean(j, m),
        "q": mean(j, s),
        "e": mean(b, hh), "g": mean(b, m), "p": mean(hh, s),
        "r": mean(m, s),
    }


#: Table 8-12: the sample at (xFrac, yFrac)
_LETTER = {(0, 0): "G", (1, 0): "a", (2, 0): "b", (3, 0): "c",
           (0, 1): "d", (1, 1): "e", (2, 1): "f", (3, 1): "g",
           (0, 2): "h", (1, 2): "i", (2, 2): "j", (3, 2): "k",
           (0, 3): "n", (1, 3): "p", (2, 3): "q", (3, 3): "r"}


def _plain_chroma(ref, qy, qx):
    """Equation 8-266 at eighth fractions (qx & 7, qy & 7)."""
    h, w = ref.shape
    R = np.pad(ref.astype(np.int64), _PAD, mode="edge")
    oy, ox, ey, ex = qy >> 3, qx >> 3, qy & 7, qx & 7
    A, B = _at(R, h, w, oy, ox), _at(R, h, w, oy, ox + 1)
    C, D = _at(R, h, w, oy + 1, ox), _at(R, h, w, oy + 1, ox + 1)
    return ((8 - ex) * (8 - ey) * A + ex * (8 - ey) * B
            + (8 - ex) * ey * C + ex * ey * D + 32) >> 6


@pytest.fixture(scope="module")
def seeded_planes():
    rng = np.random.default_rng(41)
    w, h = 96, 64
    return (rng.integers(0, 256, (h, w)).astype(np.uint8),
            rng.integers(0, 256, (h // 2, w // 2)).astype(np.uint8),
            rng.integers(0, 256, (h // 2, w // 2)).astype(np.uint8))


@pytest.mark.parametrize("letter", [v for v in _LETTER.values() if v != "G"])
def test_each_position_is_the_standards(letter, seeded_planes):
    """A current frame that IS the reference at quarter vector (qy, qx)
    — built by the plain functions — is found at exactly that vector
    with zero residual: luma by §8.4.2.2.1's position, chroma by
    §8.4.2.2.2 at the vector's eighth fractions; every whole-pixel
    part the +-1 pixel window holds, so chroma sees all eight
    fractions; and the in-repo decoder forms the same samples."""
    from thinvids_tpu.codecs.h264 import decoder

    ref, ru, rv = seeded_planes
    h, w = ref.shape
    xf, yf = next(k for k, v in _LETTER.items() if v == letter)
    wide = _plain_luma_positions(np.pad(ref, _PAD, mode="edge"))[letter]
    centers = jnp.zeros((3, 2), jnp.int32)
    search = jax.jit(functools.partial(jaxme.me_search_xla,
                                       subpel="quarter"))
    frame = decoder._RefFrame(types.SimpleNamespace(y=ref, u=ru, v=rv))
    for qy in [q for q in range(-4, 5) if q & 3 == yf]:
        for qx in [q for q in range(-4, 5) if q & 3 == xf]:
            # positions of a padded picture, read (qy >> 2, qx >> 2) on:
            # the padding makes the clamp at the picture's edge
            cur = _at(wide, h, w, qy >> 2, qx >> 2)
            mv, py, pu, pv = jax.device_get(search(
                jnp.asarray(cur, jnp.int16), jnp.asarray(ref, jnp.int16),
                jnp.asarray(ru, jnp.int16), jnp.asarray(rv, jnp.int16),
                centers, jnp.asarray(0, jnp.int32)))
            assert {tuple(v) for v in mv.reshape(-1, 2)} == {(qy, qx)}
            assert np.array_equal(py, cur)
            assert np.array_equal(pu, _plain_chroma(ru, qy, qx))
            assert np.array_equal(pv, _plain_chroma(rv, qy, qx))
            # the decoder's own position function, an interior block
            assert np.array_equal(frame.luma_pred(1, 2, (qy, qx)),
                                  cur[16:32, 32:48])
            du, dv = frame.chroma_pred(1, 2, (qy, qx))
            assert np.array_equal(du, pu[8:16, 16:24])
            assert np.array_equal(dv, pv[8:16, 16:24])
