"""In-loop deblocking filter (§8.7, in the order §8.7 prescribes).

Pins: the threshold tables; the fast filter (codecs/h264/deblock.py, a
wavefront over macroblocks — under numpy and under JAX) against the
plain raster-order reference (tools/deblock_plain.py) on random
fields; the slice-local filtering of a split-frame band (idc 2); the
in-repo decoder on a two-slice picture; and the libavcodec oracle:
its decode of an encoded GOP equals the encoder's own reconstruction.

Every comparison here asks for max |diff| == 0. The codec is integer
arithmetic end to end, and the filter feeds the next frame's
reference: one sample off in frame 1 is a different prediction in
frame 2, so "close" is not a weaker form of "equal" but a drifting
stream.
"""

import numpy as np
import pytest

from thinvids_tpu.codecs.h264.deblock import (ALPHA_TABLE, BETA_TABLE,
                                              TC0_TABLE, deblock_frame)
from thinvids_tpu.tools.deblock_plain import deblock_picture_plain


def _rand_frame(mbh, mbw, seed=0, smooth=False):
    """`smooth`: flat 4x4 blocks with a little noise — every edge is a
    blocking artefact of the size the filter acts on, so chains of
    edges that read each other's output occur all over the picture."""
    rng = np.random.default_rng(seed)
    if smooth:
        base = rng.integers(90, 120, (4 * mbh, 4 * mbw))
        y = (np.repeat(np.repeat(base, 4, 0), 4, 1)
             + rng.integers(-2, 3, (16 * mbh, 16 * mbw))).astype(np.uint8)
    else:
        y = rng.integers(0, 256, (16 * mbh, 16 * mbw), np.uint8)
    u = y[::2, ::2].copy()
    v = 255 - u
    return y, u, v


def _rand_meta(mbh, mbw, seed, intra):
    rng = np.random.default_rng(seed + 100)
    qp = rng.integers(20, 48, (mbh, mbw))
    if intra:
        return qp, {}
    return qp, dict(nz4=rng.random((4 * mbh, 4 * mbw)) < 0.4,
                    mv=rng.integers(-3, 4, (mbh, mbw, 2)))


def _fast(backend):
    if backend == "numpy":
        return deblock_frame
    from thinvids_tpu.codecs.h264.jaxdeblock import deblock_frame_jax

    return deblock_frame_jax


def _assert_planes_equal(got, want):
    for name, g, w in zip("yuv", got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"plane {name}")


class TestTables:
    def test_shapes_and_anchors(self):
        assert ALPHA_TABLE.shape == (52,)
        assert BETA_TABLE.shape == (52,)
        assert TC0_TABLE.shape == (3, 52)
        # spec anchor points (Table 8-16 / 8-17)
        assert ALPHA_TABLE[26] == 15 and ALPHA_TABLE[51] == 255
        assert BETA_TABLE[26] == 6 and BETA_TABLE[51] == 18
        assert ALPHA_TABLE[15] == 0 and BETA_TABLE[15] == 0
        assert TC0_TABLE[2, 51] == 25 and TC0_TABLE[0, 51] == 13
        assert (TC0_TABLE[:, :17] == 0).all()
        # monotone non-decreasing in qp, and bS3 >= bS2 >= bS1
        for t in (ALPHA_TABLE, BETA_TABLE, *TC0_TABLE):
            assert (np.diff(t) >= 0).all()
        assert (TC0_TABLE[2] >= TC0_TABLE[1]).all()
        assert (TC0_TABLE[1] >= TC0_TABLE[0]).all()


class TestPlainParity:
    """The wavefront against the per-macroblock raster loop."""

    @pytest.mark.parametrize("backend", ["numpy", "jax"])
    @pytest.mark.parametrize("intra", [True, False])
    @pytest.mark.parametrize("mbh,mbw,smooth", [
        (5, 7, True), (3, 2, True), (1, 4, True), (6, 1, True),
        (4, 3, False)])
    def test_random_fields(self, backend, intra, mbh, mbw, smooth):
        seed = 16 * mbh + mbw
        y, u, v = _rand_frame(mbh, mbw, seed, smooth)
        qp, kw = _rand_meta(mbh, mbw, seed, intra)
        want = deblock_picture_plain(y, u, v, qp, intra=intra, **kw)
        if smooth:
            assert (want[0] != y).sum() > y.size // 8    # it filtered
        got = _fast(backend)(y, u, v, qp, intra=intra, **kw)
        assert got[0].dtype == y.dtype
        _assert_planes_equal(got, want)

    @pytest.mark.parametrize("backend", ["numpy", "jax"])
    @pytest.mark.parametrize("intra", [True, False])
    def test_rows_past_the_picture_are_left_alone(self, backend, intra):
        """A picture of 40 rows (2.5 macroblock rows) is coded as 3;
        a band grid may pad further. Macroblock rows at or past
        `total_mb_rows` are not in the picture: nothing of theirs is
        filtered, and the last real row is filtered as the picture's
        last."""
        mbh, mbw, real = 5, 3, 3
        y, u, v = _rand_frame(mbh, mbw, 9, smooth=True)
        qp, kw = _rand_meta(mbh, mbw, 9, intra)
        cut = {k: a[:(4 if k == "nz4" else 1) * real] for k, a in kw.items()}
        want = deblock_picture_plain(y[:16 * real], u[:8 * real],
                                     v[:8 * real], qp[:real], intra=intra,
                                     **cut)
        got = _fast(backend)(y, u, v, qp, intra=intra, mb_row0=0,
                             total_mb_rows=real, **kw)
        for g, w, src, k in zip(got, want, (y, u, v), (16, 8, 8)):
            g = np.asarray(g)
            np.testing.assert_array_equal(g[:k * real], w)
            np.testing.assert_array_equal(g[k * real:], src[k * real:])

    def test_filters_blocky_content(self):
        mbh, mbw = 3, 3
        y, u, v = _rand_frame(mbh, mbw, 1, smooth=True)
        qp = np.full((mbh, mbw), 30)
        y2, u2, v2 = deblock_frame(y, u, v, qp, intra=True)
        assert (y2 != y).sum() > y.size // 4     # blocking edges filtered
        assert (u2 != u).any()

    def test_low_qp_disables_filter(self):
        # indexA < 16 -> alpha/beta 0 -> nothing may change
        mbh, mbw = 2, 2
        y, u, v = _rand_frame(mbh, mbw, 2, smooth=True)
        qp = np.full((mbh, mbw), 10)
        y2, u2, v2 = deblock_frame(y, u, v, qp, intra=True)
        np.testing.assert_array_equal(y2, y)
        np.testing.assert_array_equal(u2, u)


class TestKernel:
    """On the TPU the loop over wavefronts is one Pallas kernel
    (jaxdeblock._scan_kernel) round the same `_wavefront_step`."""

    @pytest.fixture
    def kernel_ops(self, monkeypatch):
        """The JAX shim as it is on the chip — whole 128-lane registers
        and the kernel — with the kernel in the Pallas interpreter."""
        from thinvids_tpu.codecs.h264 import jaxdeblock

        ops = type(jaxdeblock.JAX_OPS)
        monkeypatch.setattr(ops, "lanes", staticmethod(
            lambda mbh: -(-mbh // 128) * 128))
        monkeypatch.setattr(ops, "scan", staticmethod(
            lambda step, carry, xs: jaxdeblock._scan_kernel(
                step, carry, xs, interpret=True)))

    @pytest.mark.parametrize("intra", [True, False])
    def test_interpreted_kernel_equals_plain(self, kernel_ops, intra):
        mbh, mbw = 4, 5
        y, u, v = _rand_frame(mbh, mbw, 21, smooth=True)
        qp, kw = _rand_meta(mbh, mbw, 21, intra)
        want = deblock_picture_plain(y, u, v, qp, intra=intra, **kw)
        got = _fast("jax")(y, u, v, qp, intra=intra, **kw)
        _assert_planes_equal(got, want)

    def test_kernel_compiles_for_the_chip_at_1080p(self, kernel_ops,
                                                   monkeypatch):
        """The TPU's compiler, for a chip that is described and not
        attached (no chip time): the kernel at the serving width fits
        its tiling and its fast memory. Nothing runs."""
        import os

        import jax
        import jax.numpy as jnp
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        from thinvids_tpu.codecs.h264 import jaxdeblock

        monkeypatch.setitem(os.environ, "TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as exc:          # no TPU compiler in this image
            pytest.skip(f"no v5e topology can be described here: {exc}")
        ops = type(jaxdeblock.JAX_OPS)
        monkeypatch.setattr(ops, "scan", staticmethod(
            jaxdeblock._scan_kernel))
        chip = SingleDeviceSharding(topo.devices[0])
        mbh, mbw = 68, 120

        def arg(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

        lowered = jax.jit(
            lambda y, u, v, qp, nz, mv: jaxdeblock.deblock_frame_jax(
                y, u, v, qp, intra=False, nz4=nz, mv=mv)).lower(
            arg((16 * mbh, 16 * mbw), jnp.int16),
            arg((8 * mbh, 8 * mbw), jnp.int16),
            arg((8 * mbh, 8 * mbw), jnp.int16),
            arg((mbh, mbw), jnp.int32),
            arg((4 * mbh, 4 * mbw), jnp.bool_),
            arg((mbh, mbw, 2), jnp.int32))
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            text = lowered.compile().as_text()
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
        assert "tpu_custom_call" in text
        assert "tvt.deblock/tvt_deblock_wavefront" in text


@pytest.mark.parametrize("L", [32 * 8160 * 384, 8160 * (32 * 384 + 2)])
def test_levels_rewording_compiles_for_the_chip_at_1080p(L, monkeypatch):
    """ISSUE 37, kept in this file because one test file may describe
    the TPU topology (one process holds libtpu): the program that
    re-words a wave's int16 levels as int32 words compiles for a
    described v5e at the served 1080p GOP shapes (the library's, and
    the serving set's with its mode tail, which does not fill its last
    row), wants four copies of the levels in HBM beside them and no
    more, and EVERY instruction of it that does work is filed under
    `tvt.pack` by the TPU's compiler too — which `dev_unscoped_pct`
    rests on: in other forms of the same arithmetic the compiler's own
    relayout of the one-row array roots a fusion and takes the name
    away (PERF.md §6 PR 37; the served process's compile still differs
    from this one by a relayout of the result, and its profile shows
    the fusion before that without a name: §7). The plain form
    (`lax.bitcast_convert_type` of (L / 2, 2) pairs) is REFUSED there:
    the minor dimension of 2 is laid out on 128 lanes, 25.7 GB."""
    import os
    import re

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from thinvids_tpu.parallel import dispatch

    monkeypatch.setitem(os.environ, "TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:          # no TPU compiler in this image
        pytest.skip(f"no v5e topology can be described here: {exc}")
    levels = jax.ShapeDtypeStruct(
        (1, L), jnp.int16, sharding=SingleDeviceSharding(topo.devices[0]))
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = dispatch._levels_as_words.lower(levels).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    memory = compiled.memory_analysis()
    rows = -(-L // dispatch._WORD_ROW)
    assert memory.output_size_in_bytes == rows * dispatch._WORD_ROW * 2
    assert memory.temp_size_in_bytes <= 4.1 * 2 * L
    text = compiled.as_text()
    working = []
    for line in text[text.index("ENTRY"):].splitlines():
        found = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(", line)
        if found and found.group(1) not in (
                "constant", "parameter", "bitcast", "copy-start",
                "copy-done"):
            working.append(line)
    assert len(working) >= 3
    for line in working:
        assert 'op_name="jit(_levels_as_words)/tvt.pack/' in line, line


@pytest.mark.parametrize("shape", [(1088, 1920), (2176, 3840), (544, 3840),
                                   (480, 864)])
def test_probe_box_sums_compile_for_the_chip_without_a_relayout(
        shape, monkeypatch):
    """ISSUE 42, kept in this file because one test file may describe
    the TPU topology: the global-motion probe's 4x4 box sums compile
    for a described v5e at the served plane shapes (1080p, 2160p, a
    2160p band, a ladder rung whose width is no multiple of 128) with
    temporaries of a few planes at most. The view they replaced,
    `x.reshape(H // 4, 4, W // 4, 4).sum((1, 3))`, is laid out with
    its minor dimension of 4 on 128 lanes: 267 MB of temporaries for
    one 4 MB 1080p plane, 1.47 ms of every frame (PERF.md §5)."""
    import os

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from thinvids_tpu.codecs.h264 import jaxme

    monkeypatch.setitem(os.environ, "TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:          # no TPU compiler in this image
        pytest.skip(f"no v5e topology can be described here: {exc}")
    plane = jax.ShapeDtypeStruct(
        shape, jnp.int16, sharding=SingleDeviceSharding(topo.devices[0]))
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(lambda x: jaxme._box_sum(x, 4)).lower(
            plane).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    memory = compiled.memory_analysis()
    H, W = shape
    assert memory.output_size_in_bytes >= (H // 4) * (W // 4) * 4
    assert memory.temp_size_in_bytes <= 8 * 2 * H * W


@pytest.mark.parametrize("subpel", ["half", "quarter"])
def test_me_kernel_compiles_for_the_chip_at_1080p(subpel, monkeypatch):
    """ISSUE 50, kept in this file because one test file may describe
    the TPU topology: the motion-search kernel compiles for a described
    v5e at the served 1080p shape under both tables — Mosaic takes the
    (256, 128) selector, the row's `take` masks stacked 32 sublanes a
    candidate and their (128, 384) expander, which the interpreter
    (tests/test_jaxme.py) runs but cannot refuse. Nothing runs."""
    import functools
    import os

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from thinvids_tpu.codecs.h264 import jaxme

    monkeypatch.setitem(os.environ, "TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:          # no TPU compiler in this image
        pytest.skip(f"no v5e topology can be described here: {exc}")
    chip = SingleDeviceSharding(topo.devices[0])
    H, W = 1088, 1920
    _mbh, _mbw, H4, RG, WcK, nch, W2K, _WcuK, W2cK = jaxme._geom(H, W)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    lowered = jax.jit(functools.partial(
        jaxme._me_pallas, H=H, W=W, interpret=False, subpel=subpel)).lower(
        arg((1, 8), jnp.int32), arg((H4, WcK), jnp.int16),
        arg((3, H4 + 128, W2K), jnp.int16),
        arg((3, H4 // 2 + 64, W2cK), jnp.int16),
        arg((3, H4 // 2 + 64, W2cK), jnp.int16),
        arg(jaxme._ss_np().shape, jnp.bfloat16),
        arg(jaxme._ex_np().shape, jnp.bfloat16))
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert "tpu_custom_call" in compiled.as_text()
    # one vector value a macroblock: 16 live lanes of the 128 a tile has
    # (the three prediction planes beside them, and the tuple's table)
    out = compiled.memory_analysis().output_size_in_bytes
    assert 0 <= out - (RG * nch * 8 * 128 * 4 + 3 * H4 * WcK) < 4096


class TestBandSplit:
    """A split-frame band is a slice with disable_deblocking_filter_idc
    2: it filters its own rows, and no edge between two bands."""

    @pytest.mark.parametrize("intra", [True, False])
    def test_bands_filter_as_slices(self, intra):
        mbh, mbw = 6, 4
        y, u, v = _rand_frame(mbh, mbw, 3, smooth=True)
        qp, kw = _rand_meta(mbh, mbw, 3, intra)
        splits = [(0, 2), (2, 5), (5, 6)]
        slice_of_row = [i for i, (a, b) in enumerate(splits)
                        for _ in range(a, b)]
        want = deblock_picture_plain(y, u, v, qp, intra=intra,
                                     slice_of_mb_row=slice_of_row, **kw)
        whole = deblock_picture_plain(y, u, v, qp, intra=intra, **kw)
        assert (want[0] != whole[0]).any()      # the boundary matters

        def band(lo, hi):
            cut = {k: a[(4 if k == "nz4" else 1) * lo:
                        (4 if k == "nz4" else 1) * hi]
                   for k, a in kw.items()}
            return deblock_frame(
                y[16 * lo:16 * hi], u[8 * lo:8 * hi], v[8 * lo:8 * hi],
                qp[lo:hi], intra=intra, mb_row0=lo, total_mb_rows=mbh,
                **cut)

        bands = [band(a, b) for a, b in splits]
        for pi in range(3):
            np.testing.assert_array_equal(
                np.concatenate([b[pi] for b in bands]), want[pi])

    def test_edges_masks_equal_slices(self):
        """The decoder's form of the same: one plane, per-macroblock
        masks of the edges that exist (here: idc 2, slices of whole
        and of broken macroblock rows)."""
        mbh, mbw = 4, 5
        y, u, v = _rand_frame(mbh, mbw, 5, smooth=True)
        qp, kw = _rand_meta(mbh, mbw, 5, False)
        on = np.ones((mbh, mbw), bool)
        left = on.copy()
        left[:, 0] = False
        top = on.copy()
        top[0] = False
        top[2] = False                          # a slice starts at row 2
        want = deblock_picture_plain(y, u, v, qp, intra=False,
                                     slice_of_mb_row=[0, 0, 1, 1], **kw)
        got = deblock_frame(y, u, v, qp, intra=False,
                            edges=(on, left, top), **kw)
        _assert_planes_equal(got, want)


RD_SERVING = dict(mode_decision=True, pskip=True, deblock=True, aq_q=4)


class TestOracleParity:
    def test_recon_equals_libavcodec_over_a_gop(self):
        """The serving operating point (QP 25, mode decision, P_Skip,
        in-loop filter, AQ 1.0) over a whole 32-frame GOP at 192x160:
        libavcodec's decode of the stream equals the encoder's own
        reconstruction in every sample of Y, U and V of every frame.
        31 P frames chain through the filtered reference, so any
        deviation from §8.7's order would have grown into view (the
        six-pass filter this replaced was 9 off after 5 frames)."""
        from thinvids_tpu.tools import oracle

        if not oracle.oracle_available():
            pytest.skip("libavcodec oracle not available")
        from thinvids_tpu.codecs.h264.decoder import decode_annexb
        from thinvids_tpu.codecs.h264.encoder import encode_gop
        from thinvids_tpu.codecs.h264.rdo import RdConfig
        from thinvids_tpu.core.types import VideoMeta
        from thinvids_tpu.tools.pan import make_frames

        w, h, n = 192, 160, 32
        frames = make_frames(n, w, h)
        meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1,
                         num_frames=n)
        stream, recons = encode_gop(frames, meta, qp=25,
                                    return_recon=True,
                                    rd=RdConfig(**RD_SERVING))
        decoded = oracle.decode_h264(stream)
        assert len(decoded) == n
        ours = decode_annexb(stream).frames
        for i, planes in enumerate(decoded):
            for name, got, rec, k in zip("yuv", planes, recons, (1, 2, 2)):
                want = np.asarray(rec)[i][:h // k, :w // k]
                diff = np.abs(got.astype(np.int32) - want)
                assert diff.max() == 0, f"frame {i} {name}: {diff.max()}"
            # and the in-repo decoder, which runs the same filter
            np.testing.assert_array_equal(ours[i].y, planes[0])
            np.testing.assert_array_equal(ours[i].u, planes[1])
            np.testing.assert_array_equal(ours[i].v, planes[2])
