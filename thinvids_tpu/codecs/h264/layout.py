"""Transfer-layout contract of the sharded GOP encode — host side.

jaxinter.encode_gop_planes emits ONE flat int16 vector per GOP (intra
blocked levels followed by P coefficient planes); this module owns the
per-MB sizes of that layout, the zero-copy host inverses (flat transfer
segments → per-slice views), and the COMPACT payload format the device
compaction stage (jaxcore._compact_stream) ships over the device→host
link.

Deliberately jax-free: tools/fuzz_native.py and the native packer's
tests import it without a backend, and the numpy implementations double
as the no-compiler parity references for the native entries.

Compact payload format (all offsets in bytes, NB = ceil(L / 16) sparse
blocks, nb8 = ceil(NB / 8)):

    [ bitmap      nb8 bytes   1 bit per 16-coeff block (big-endian
                              within bytes, np.unpackbits order)
    | bmask16     2 * nblk    per live block, a little-endian uint16
                              lane-occupancy mask (bit k = coeff k != 0)
    | vals        nval        the nonzero coeffs in (block, lane)
                              order, int8 ]

`used = nb8 + 2 * nblk + nval` bytes carry the whole stream; everything
after is transfer padding (the device buffer is budget-sized, the host
fetches a quantized slice). nblk/nval ride as separate tiny count
arrays, fetched with the device-wait barrier.

The payload unpacks whole (unpack_compact_*: a split-frame band) or in
RANGES of the level vector (a GOP: each slice thunk unpacks the levels
it packs, rest_spans): one validating pass files an index — for every
INDEX_STRIDE-th block the live blocks and the values before it
(index_compact_*) — and a range starts from the entry at or before its
first block (unpack_compact_range_*).
"""

from __future__ import annotations

import math

import numpy as np

# Per-MB flat sizes. Intra: luma DC 16 + luma AC 240 + chroma DC 8 +
# chroma AC 120. P plane layout: luma coeff plane 256 + u/v hadamard DC
# 4+4 + u/v AC planes 64+64 (MVs ride separately as int8); with
# rd.p_intra one more, the macroblock's kind word (rdo.pmode_word).
_P_FLAT_MB = 256 + 4 + 4 + 64 + 64        # = 392


def p_flat_mb(p_intra: bool = False) -> int:
    """Levels a P macroblock takes in the flat transfer layout."""
    return _P_FLAT_MB + (1 if p_intra else 0)
_INTRA_FLAT_MB = 384

#: 16-coeff granularity of the block-sparse transfer tiers
SPARSE_BLOCK = 16

#: blocks between two entries of a compact payload's index (a multiple
#: of 64: the native pass counts the bitmap a 64-bit word at a time).
#: A range's unpack walks at most this many blocks to its start; a
#: 1080p GOP's index has 1,558 entries.
INDEX_STRIDE = 4096


def index_entries(L: int) -> int:
    """Entries in the index of a compact payload of `L` levels."""
    NB = -(-L // SPARSE_BLOCK)
    return -(-NB // INDEX_STRIDE)


# ---- compact payload parsing ----------------------------------------------

def split_compact(payload: np.ndarray, nblk: int, nval: int, L: int):
    """Parse one compact payload (>= `used` uint8 bytes) into its
    (bitmap, bmask16, vals) streams. Views where alignment allows; the
    bmask16 lane masks are re-assembled from byte pairs (the payload
    gives them no alignment guarantee — nb8 may be odd)."""
    NB = -(-L // SPARSE_BLOCK)
    nb8 = (NB + 7) // 8
    if L <= 0 or nblk < 0 or nval < 0:
        # fuzz-found (tools/fuzz_native.py): negative slice counts
        # must reject like the native parser, not quietly shrink the
        # streams into a zero decode
        raise ValueError("compact stream counts out of range")
    need = nb8 + 2 * int(nblk) + int(nval)
    payload = np.asarray(payload, np.uint8).reshape(-1)
    if payload.shape[0] < need:
        raise ValueError(
            f"compact payload truncated: {payload.shape[0]} bytes < "
            f"{need} needed for nblk={nblk} nval={nval}")
    bitmap = payload[:nb8]
    mb = payload[nb8:nb8 + 2 * int(nblk)].astype(np.uint16)
    bmask16 = (mb[0::2] | (mb[1::2] << 8)).astype(np.uint16)
    vals = payload[nb8 + 2 * int(nblk):need].view(np.int8)
    return bitmap, bmask16, vals


def _lane_bits(masks: np.ndarray) -> np.ndarray:
    """uint16 lane masks → (n, 16) booleans, lane k = bit k."""
    return ((masks.astype(np.uint32)[:, None]
             >> np.arange(SPARSE_BLOCK, dtype=np.uint32)) & 1).astype(bool)


def _checked_streams(nblk: int, nval: int, bitmap: np.ndarray,
                     bmask16: np.ndarray, vals: np.ndarray, L: int):
    """The validation every numpy unpack shares: (bm, lane_bits) — the
    (NB,) live-block and (nblk, 16) live-lane booleans — of streams
    that agree with their counts. Rejects count/stream disagreement
    like the native index pass: corrupt counts must fail loudly, not
    decode as silent zeros."""
    NB = -(-L // SPARSE_BLOCK)
    if L <= 0 or nblk < 0 or nval < 0:
        raise ValueError("sparse stream counts out of range")
    if nblk > np.asarray(bmask16).reshape(-1).shape[0] \
            or nval > np.asarray(vals).reshape(-1).shape[0]:
        raise ValueError("sparse stream counts exceed buffer sizes")
    nb8 = (NB + 7) // 8
    bitmap = np.asarray(bitmap, np.uint8).reshape(-1)
    if bitmap.shape[0] < nb8:
        # fuzz-found: a truncated bitmap must reject like the native
        # wrapper's size validation, not decode short
        raise ValueError("sparse bitmap truncated")
    bits = np.unpackbits(bitmap[:nb8])
    if bits[NB:].any():
        # pack never sets the byte-padding bits past NB; a set one is
        # a corrupt bitmap (the native pass rejects it too —
        # fuzz-found asymmetry, tools/fuzz_native.py)
        raise ValueError("sparse bitmap padding bits set")
    bm = bits[:NB].astype(bool)
    lane_bits = _lane_bits(np.asarray(bmask16)[:nblk])  # (nblk, 16)
    # Explicit count agreement, like the native pass's totals: numpy's
    # size-1 broadcasting otherwise lets a corrupt nval=1 stream
    # silently replicate one value across every live lane
    # (fuzz-found, tools/fuzz_native.py)
    if int(bm.sum()) != int(nblk):
        raise ValueError("sparse bitmap disagrees with nblk")
    if int(lane_bits.sum()) != int(nval):
        raise ValueError("sparse lane masks disagree with nval")
    return bm, lane_bits


def block_sparse_unpack2_host(nblk: int, nval: int, bitmap: np.ndarray,
                              bmask16: np.ndarray, vals: np.ndarray,
                              L: int) -> np.ndarray:
    """Numpy inverse of jaxcore._block_sparse_pack2 → flat int16 levels
    (the native scatter's parity reference; jaxcore re-exports it)."""
    bm, lane_bits = _checked_streams(nblk, nval, bitmap, bmask16, vals, L)
    stream = np.asarray(vals)[:nval].astype(np.int16)
    rows = np.zeros((nblk, SPARSE_BLOCK), np.int16)
    rows[lane_bits] = stream        # row-major = (block, lane) order
    out = np.zeros((bm.shape[0], SPARSE_BLOCK), np.int16)
    out[bm] = rows
    return out.reshape(-1)[:L]


def unpack_compact_host(payload: np.ndarray, nblk: int, nval: int,
                        L: int) -> np.ndarray:
    """Compact payload → flat int16 levels (numpy fallback for the
    native cavlc_unpack_compact; identical output — tested)."""
    bitmap, bmask16, vals = split_compact(payload, nblk, nval, L)
    return block_sparse_unpack2_host(int(nblk), int(nval), bitmap,
                                     bmask16, vals, L)


def unpack_compact_auto(payload: np.ndarray, nblk: int, nval: int,
                        L: int) -> np.ndarray:
    """Two-tier compact unpack: the native single-pass parse+scatter
    when a compiler exists, :func:`unpack_compact_host` otherwise
    (identical output — tested)."""
    from ... import native as native_mod

    if native_mod.available():
        return native_mod.unpack_compact(nblk, nval, payload, L)
    return unpack_compact_host(payload, nblk, nval, L)


def index_compact_host(payload: np.ndarray, nblk: int, nval: int,
                       L: int) -> np.ndarray:
    """Numpy twin of native.index_compact: validate one compact payload
    as :func:`unpack_compact_host` does (same errors) and return its
    index, (index_entries(L), 2) int64: entry j = (live blocks, values)
    before block j * INDEX_STRIDE."""
    bitmap, bmask16, vals = split_compact(payload, nblk, nval, L)
    bm, lane_bits = _checked_streams(int(nblk), int(nval), bitmap,
                                     bmask16, vals, L)
    at = np.arange(index_entries(L)) * INDEX_STRIDE
    blocks_before = np.concatenate([[0], np.cumsum(bm)])[at]
    values_before = np.concatenate([[0], np.cumsum(lane_bits.sum(1))])
    return np.stack([blocks_before, values_before[blocks_before]],
                    axis=1).astype(np.int64)


def unpack_compact_range_host(payload: np.ndarray, nblk: int, nval: int,
                              L: int, index: np.ndarray, l0: int, l1: int,
                              out: np.ndarray) -> None:
    """Numpy twin of native.unpack_compact_range: levels [l0, l1) of
    the vector into `out` (l1 - l0 int16, zeroed here), read from the
    index entry at or before block l0 // 16 on. A block the range's
    edge cuts gives this side its own lanes."""
    bitmap, bmask16, vals = split_compact(payload, nblk, nval, L)
    if not 0 <= l0 <= l1 <= L:
        raise ValueError(f"level range [{l0}, {l1}) outside [0, {L})")
    out[:] = 0
    if l0 == l1:
        return
    j = (l0 // SPARSE_BLOCK) // INDEX_STRIDE
    b_from, b_to = j * INDEX_STRIDE, -(-l1 // SPARSE_BLOCK)
    bi, vi = (int(x) for x in index[j])
    bits = np.unpackbits(bitmap[b_from // 8:-(-b_to // 8)])
    live = b_from + np.flatnonzero(bits[:b_to - b_from])
    lane_bits = _lane_bits(bmask16[bi:bi + live.shape[0]])
    at = (live[:, None] * SPARSE_BLOCK + np.arange(SPARSE_BLOCK))[lane_bits]
    stream = vals[vi:vi + at.shape[0]].astype(np.int16)
    mine = (at >= l0) & (at < l1)
    out[at[mine] - l0] = stream[mine]


# ---- zero-copy unflatten (flat transfer segments → slice views) ------------

def unflatten_intra(seg: np.ndarray, nmb: int):
    """Flat intra segment (nmb * 384, layout il_dc|il_ac|ic_dc|ic_ac) →
    blocked VIEWS. The int16 views feed cavlc_pack_islice16 directly —
    an astype(int32) chain here would allocate ~4 copies of the intra
    levels per GOP on the critical path."""
    o = nmb * 16
    il_dc = seg[:o].reshape(nmb, 16)
    il_ac = seg[o:o + nmb * 240].reshape(nmb, 16, 15)
    o += nmb * 240
    ic_dc = seg[o:o + nmb * 8].reshape(nmb, 2, 4)
    o += nmb * 8
    ic_ac = seg[o:o + nmb * 120].reshape(nmb, 2, 4, 15)
    return il_dc, il_ac, ic_dc, ic_ac


def unflatten_p_planes(seg: np.ndarray, mv8: np.ndarray, num_frames: int,
                       mbw: int, mbh: int, p_intra: bool = False):
    """Flat P segment → plane VIEWS (the plane->blocked scan happens
    inside the native packer, cavlc_pack_pslice_plane, so no relayout
    pass runs on the host). With `p_intra` the segment ends in the
    frames' kind channel, `pmode` (F-1, nmb), a seventh view."""
    nmb = mbw * mbh
    H, W = mbh * 16, mbw * 16
    hw2 = (H // 2) * (W // 2)
    F1 = num_frames - 1
    o = 0
    lp = seg[o:o + F1 * H * W].reshape(F1, H, W)
    o += F1 * H * W
    udc = seg[o:o + F1 * nmb * 4].reshape(F1, nmb, 4)
    o += F1 * nmb * 4
    vdc = seg[o:o + F1 * nmb * 4].reshape(F1, nmb, 4)
    o += F1 * nmb * 4
    uac = seg[o:o + F1 * hw2].reshape(F1, H // 2, W // 2)
    o += F1 * hw2
    vac = seg[o:o + F1 * hw2].reshape(F1, H // 2, W // 2)
    o += F1 * hw2
    if p_intra:
        return (np.asarray(mv8), lp, udc, vdc, uac, vac,
                seg[o:o + F1 * nmb].reshape(F1, nmb))
    return (np.asarray(mv8), lp, udc, vdc, uac, vac)


def intra_tail_mb(ships_modes: bool, intra4x4: bool = False) -> int:
    """int16 words a macroblock of the IDR's side channel at the end
    of the level vector (rdo.RdConfig.intra_tail_mb): [mode16 | dqp16]
    when modes ship, then the sixteen 4-bit Intra4x4 block modes in
    four words (encoder.unpack_i4_modes) under `intra4x4`."""
    if not ships_modes:
        return 0
    return 6 if intra4x4 else 2


def _split_tail(tail: np.ndarray, nmb: int) -> tuple:
    """The side channel's (mode16, dqp16[, block-mode words (nmb, 4)])."""
    return (tail[:nmb], tail[nmb:2 * nmb]) + (
        (tail[2 * nmb:].reshape(nmb, 4),) if tail.shape[0] > 2 * nmb else ())


def unflatten_gop(flat: np.ndarray, mv8: np.ndarray, num_frames: int,
                  mbw: int, mbh: int, ships_modes: bool = False,
                  p_intra: bool = False, intra4x4: bool = False):
    """Host inverse of jaxinter.encode_gop_planes: split the flat int16
    vector into (intra blocked arrays, P plane views). EVERY array is a
    zero-copy view into `flat`. With `ships_modes` the vector ends in
    the per-MB intra [mode16 | dqp16] side channel (and `intra4x4`'s
    block modes), appended to the returned intra tuple; `p_intra` as
    unflatten_p_planes'."""
    nmb = mbw * mbh
    flat = np.asarray(flat)
    o = nmb * _INTRA_FLAT_MB
    intra = unflatten_intra(flat[:o], nmb)
    p_end = flat.shape[0] - nmb * intra_tail_mb(ships_modes, intra4x4)
    planes = unflatten_p_planes(flat[o:p_end], mv8, num_frames, mbw, mbh,
                                p_intra)
    if ships_modes:
        intra = intra + _split_tail(flat[p_end:], nmb)
    return intra, planes


def split_dense_dc(dense: np.ndarray, nmb: int, ships_modes: bool = False):
    """The dense transfer segment [il_dc | ic_dc (| mode16 | dqp16 (|
    block modes))] → (il_dc, ic_dc) views, and the side channel's
    tuple or ()."""
    ndc = nmb * 16
    dense = np.asarray(dense)
    il_dc = dense[:ndc].reshape(nmb, 16)
    ic_dc = dense[ndc:ndc + nmb * 8].reshape(nmb, 2, 4)
    if not ships_modes:
        return il_dc, ic_dc, ()
    return il_dc, ic_dc, _split_tail(dense[ndc + nmb * 8:], nmb)


def unflatten_gop_parts(dense: np.ndarray, rest: np.ndarray,
                        mv8: np.ndarray, num_frames: int,
                        mbw: int, mbh: int, ships_modes: bool = False,
                        p_intra: bool = False):
    """Sparse-path unflatten straight from the two transfer segments —
    dense = [il_dc | ic_dc] (the hadamard DC prefix, _per_gop_sparse;
    with `ships_modes` also the side channel's tail, appended to the
    returned intra tuple), rest = [il_ac | ic_ac | P planes] — without
    first concatenating them back into the full flat layout (which
    copied ~25 MB per 1080p GOP). Views only."""
    nmb = mbw * mbh
    nlac = nmb * 240
    rest = np.asarray(rest)
    il_dc, ic_dc, modes = split_dense_dc(dense, nmb, ships_modes)
    il_ac = rest[:nlac].reshape(nmb, 16, 15)
    o = nlac + nmb * 120
    ic_ac = rest[nlac:o].reshape(nmb, 2, 4, 15)
    planes = unflatten_p_planes(rest[o:], mv8, num_frames, mbw, mbh,
                                p_intra)
    return (il_dc, il_ac, ic_dc, ic_ac) + modes, planes


def rest_spans(num_frames: int, mbw: int, mbh: int, p_intra: bool = False):
    """Where each slice's levels lie in the sparse remainder `rest` =
    [il_ac | ic_ac | P planes], for unpacking it slice by slice: the
    IDR's spans, then one list per P frame, each span an (offset,
    length, shape) triple in the order :func:`unflatten_gop_parts` hands the
    views — il_ac, ic_ac; lp, udc, vdc, uac, vac[, pmode]. The P
    segment is component-major (:func:`unflatten_p_planes`), so a P
    frame is five or six separate runs of the vector. The offsets
    need not be multiples of 16."""
    nmb = mbw * mbh
    H, W = mbh * 16, mbw * 16
    F1 = num_frames - 1
    intra = [(0, nmb * 240, (nmb, 16, 15)),
             (nmb * 240, nmb * 120, (nmb, 2, 4, 15))]
    shapes = [(H, W), (nmb, 4), (nmb, 4), (H // 2, W // 2),
              (H // 2, W // 2)] + ([(nmb,)] if p_intra else [])
    frames = [[] for _ in range(F1)]
    o = nmb * 360
    for shape in shapes:
        n = math.prod(shape)
        for i in range(F1):
            frames[i].append((o + i * n, n, shape))
        o += F1 * n
    return intra, frames
