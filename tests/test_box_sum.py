"""The global-motion probe's 4x4 box sums in the planes' tiling (ISSUE 42).

`jaxme._box_sum` feeds the probe whose centre feeds the search, so one
different sum can move bits: the helper is held to numpy integer for
integer at the shapes the program runs (frames, 2160p bands, the band
farm's injected halo rows, the ladder's widths that are no multiple of
128), and the probes built on it — `coarse_probe`, its banded form on
a CPU mesh, and the band farm's split `banded_probe_cost` +
`probe_center_from_cost` — to the centres and cost vectors the tree
before the change computed (constants below).
"""

import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from thinvids_tpu.codecs.h264 import jaxme

#: (H, W): a 1080p frame, a 2160p strip, a 2160p band of a 4-band
#: split, the 16 injected halo rows at both widths, a ladder rung whole
#: and the other rungs' widths, one tile, half a tile
SHAPES = [(1088, 1920), (64, 3840), (544, 3840), (16, 1920), (16, 3840),
          (480, 864), (48, 640), (48, 432), (48, 320), (32, 128), (16, 64)]
CONTENTS = ["zeros", "all255", "random255", "int16_extremes", "corners"]


def _plane(shape, content, seed=0):
    rng = np.random.default_rng(seed)
    if content == "zeros":
        return np.zeros(shape, np.int16)
    if content == "all255":
        return np.full(shape, 255, np.int16)
    if content == "random255":
        return rng.integers(0, 256, shape).astype(np.int16)
    if content == "int16_extremes":
        # whole blocks of +32767 (the sum's bound, 16 * 32767) among
        # mixed ones
        x = rng.choice(np.array([-32767, 32767], np.int16), shape)
        x[:4, :8] = 32767
        x[-4:, -8:] = -32767
        return x
    # "corners": a lone 255 in one corner of every 4x4 block, the
    # corner walking with the block — a sum that borrowed from a
    # neighbouring block would read 0 or 510
    yy, xx = np.mgrid[0:shape[0], 0:shape[1]]
    corner = (yy // 4 + xx // 4) % 4
    at = ((yy % 4 == 3 * (corner // 2)) & (xx % 4 == 3 * (corner % 2)))
    return np.where(at, 255, 0).astype(np.int16)


_box = jax.jit(lambda x: jaxme._box_sum(x, 4))


@pytest.mark.parametrize("content", CONTENTS)
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_box_sum_equals_numpy(shape, content):
    x = _plane(shape, content)
    H, W = shape
    want = x.astype(np.int64).reshape(H // 4, 4, W // 4, 4).sum((1, 3))
    got = np.asarray(_box(jnp.asarray(x)))
    assert got.dtype == np.int32 and got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    if content == "corners":
        assert (got == 255).all()


# ---------------------------------------------------------------------------
# the probes: the parent's centres and cost vectors
# ---------------------------------------------------------------------------

H, W = 128, 320           # 4 bands of 2 MB rows; W no multiple of 128


def _frames(kind):
    """(cur, ref) luma, uint8 -> int16: `panned` moves whole by (8, -12)
    (the probe's grid holds it), `mixed` moves its halves apart (the
    probe must weigh them), `tied` has period 8 both ways, so whole
    families of windows cost the same and the first minimum decides."""
    rng = np.random.default_rng(7)
    pad = 24
    scene = rng.integers(0, 256, (H + 2 * pad, W + 2 * pad))
    ref = scene[pad:pad + H, pad:pad + W]
    if kind == "panned":
        cur = scene[pad + 8:pad + 8 + H, pad - 12:pad - 12 + W]
    elif kind == "mixed":
        cur = np.concatenate([
            scene[pad + 9:pad + 9 + H // 2, pad + 5:pad + 5 + W],
            scene[pad + H // 2 - 7:pad + H - 7, pad - 3:pad - 3 + W]])
    else:
        yy, xx = np.mgrid[0:H, 0:W]
        ref = 60 + 80 * ((xx // 4) % 2) + 40 * ((yy // 4) % 2)
        cur = np.roll(ref, (4, 4), axis=(0, 1))
    return (jnp.asarray(cur, jnp.int16), jnp.asarray(ref, jnp.int16))


#: kind -> (centre, cost.min(), cost.sum(), crc32 of the int32 cost
#: vector): what the tree before ISSUE 42 (`x.reshape(H // 4, 4, W // 4,
#: 4).sum((1, 3))`) computed, full frame and banded alike
PARENT = {
    "panned": ((8, -12), 81296, 67398319, 2800064996),
    "mixed": ((8, 4), 706886, 68299018, 1394172166),
    # two windows cost 90880, (-4, 4) and (4, -4): the first wins
    "tied": ((-4, 4), 90880, 180224000, 3645044316),
}


def _pin(cost):
    cost = np.asarray(cost, np.int32)
    return (int(cost.min()), int(cost.sum()), zlib.crc32(cost.tobytes()))


def _banded(cur, ref, bands):
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:bands]), ("band",))
    real = jnp.full((bands, 1), H // bands, jnp.int32)

    def per_band(c, r, real_b):
        cost = jaxme.banded_probe_cost(c, r, real_b[0, 0], "band", bands)
        centre = jaxme.banded_coarse_probe(c, r, real_b[0, 0], "band",
                                           bands)
        return cost[None], centre[None]

    f = shard_map(per_band, mesh=mesh, in_specs=(P("band"),) * 3,
                  out_specs=(P("band"),) * 2)
    return jax.device_get(jax.jit(f)(cur, ref, real))


@pytest.mark.parametrize("kind", sorted(PARENT))
def test_coarse_probe_returns_the_parents_centre(kind):
    cur, ref = _frames(kind)
    centre = np.asarray(jax.jit(jaxme.coarse_probe)(cur, ref))
    assert tuple(centre) == PARENT[kind][0]
    # the one-band form of the banded probe is the same probe
    cost = jax.jit(lambda c, r: jaxme.banded_probe_cost(
        c, r, jnp.int32(H), None, 1))(cur, ref)
    assert _pin(cost) == PARENT[kind][1:]
    assert tuple(jaxme.probe_center_from_cost(cost)) == PARENT[kind][0]


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs the 4 CPU devices conftest provides")
@pytest.mark.parametrize("bands", [2, 4])
@pytest.mark.parametrize("kind", sorted(PARENT))
def test_banded_probe_returns_the_parents_centre_and_cost(kind, bands):
    costs, centres = _banded(*_frames(kind), bands)
    for cost, centre in zip(costs, centres):        # the same on every band
        assert _pin(cost) == PARENT[kind][1:]
        assert tuple(centre) == PARENT[kind][0]


@pytest.mark.parametrize("kind", sorted(PARENT))
def test_band_farm_partial_costs_add_up_to_the_parents(kind):
    """Two hosts, one band each: the neighbour's 16 reference rows are
    injected as `top_ext` / `bot_ext`, their box sums stand in for the
    halo cells, and the hosts' partial costs add up to the full-frame
    cost vector (`probe_center_from_cost` argmins it on the host)."""
    cur, ref = _frames(kind)
    half = H // 2
    step = jax.jit(lambda c, r, top, bot, et, eb: jaxme.banded_probe_cost(
        c, r, jnp.int32(half), None, 1, top_ext=top, bot_ext=bot,
        edge_top=et, edge_bot=eb))
    upper = step(cur[:half], ref[:half], ref[:16], ref[half:half + 16],
                 True, False)
    lower = step(cur[half:], ref[half:], ref[half - 16:half], ref[-16:],
                 False, True)
    cost = np.asarray(upper) + np.asarray(lower)
    assert _pin(cost) == PARENT[kind][1:]
    assert tuple(jaxme.probe_center_from_cost(cost)) == PARENT[kind][0]
