"""Rate-distortion operating point of the encoder core.

One frozen, hashable config rides as a STATIC argument through every
jitted encode program (jaxcore/jaxinter/parallel.dispatch) and through
the numpy reference paths, so a feature toggle is a compile-time
specialization, never a traced branch:

- ``mode_decision``: per-MB intra mode decision — SATD (4x4 Hadamard)
  cost over the candidate I16x16/chroma predictors instead of the
  fixed V/H/DC raster policy (encoder._mode_policy stays the
  feature-off layout AND the fallback).
- ``pskip``: P_Skip bias — inter MBs whose quantized residual is
  near-zero (sum |level| <= pskip_sum, max |level| <= 1) drop the
  residual entirely, so the entropy packer's §8.4.1.1 skip inference
  turns them into mb_skip_run entries and the recon stays closed-loop
  (pure prediction — exactly what a decoder reconstructs for a
  skipped MB).
- ``deblock``: §8.7 in-loop deblocking applied to the recon carried
  between frames (and signaled in the slice headers), in §8.7's own
  order: codecs/h264/deblock.py, a wavefront over macroblocks.
- ``aq_strength``: perceptual (variance/JND-style) per-MB QP
  modulation on INTRA frames: flat MBs (where quantization error is
  most visible) encode finer, busy MBs (where texture masks it)
  coarser, around the same average QP. P frames keep the slice QP
  (their mb_qp_delta would be unsignalable on skipped/uncoded MBs).
- ``subpel``: motion-vector precision, "half" or "quarter". With
  "quarter" the search also scores §8.4.2.2.1's quarter positions
  round the temporal-median and the zero centre (jaxme.CENTERS) and
  every vector between the search, the filter's bS test, the
  packers and the P_Skip inference is in QUARTER-sample units
  (`mv_per_pel` 4); with "half" in half-sample units (2).
- ``p_intra``: intra macroblocks in P pictures (§7.3.5 mb_type 5..30
  in a P slice). Every P macroblock is coded inter, as without the
  setting, or Intra16x16 (V, H or DC, predicted from the CURRENT
  picture's unfiltered reconstruction, §8.3.3), whichever costs less
  (jaxinter._p_intra); the filter takes its bS per edge from the
  per-macroblock map, the packers code the macroblock's kind, and a
  neighbour's vector prediction sees refIdx -1 there.
- ``intra4x4``: Intra4x4 macroblocks in IDR pictures (§7.3.5 I_NxN,
  §8.3.1). Every macroblock of an IDR picture is coded Intra16x16,
  with the candidates it has without the setting, or as sixteen 4x4
  blocks each predicted from its own reconstructed neighbours in one
  of §8.3.1.2's nine directions, whichever costs less
  (jaxcore._intra4x4_luma); the level arrays carry the kind as
  `luma_mode` 4 (intra.LUMA_I4X4) and the block modes beside them,
  the packers code mb_type 0. Chroma is the Intra16x16 path's.

This module is deliberately jax-free: the host packers import it
without initializing a device backend.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from ...core.config import SUBPELS, subpel_of

#: AQ quantization of the strength knob: configs are static jit args,
#: so the continuous setting is snapped to 1/AQ_QUANT steps to bound
#: the number of distinct compiled programs.
AQ_QUANT = 4
#: units of a motion vector per integer sample, by `subpel`
MV_PER_PEL = dict(zip(SUBPELS, (2, 4)))
#: AQ per-MB offset clamp (QP steps either side of the frame QP).
AQ_MAX_DELTA = 6
#: P_Skip bias: an inter MB whose quantized levels sum to <= this (in
#: absolute value, all planes) with every |level| <= 1 drops its
#: residual. 2 keeps the bias to MBs whose coded cost would exceed the
#: distortion it buys back (on the pan clip of tools/pan.py, CPU run:
#: bits fall with no PSNR loss at 2; 4+ starts to visibly smear grain).
PSKIP_SUM = 2
#: `p_intra`'s handicap of the Intra16x16 candidate, in bits: what
#: such a macroblock of a P slice pays before its first AC coefficient
#: (mb_type ue(6..30), intra_chroma_pred_mode, mb_qp_delta, the luma DC
#: block) and the margin a SATD / SAD cost needs for being blind to
#: how much dearer an intra residual codes than an inter one of the
#: same cost. The decision compares `intra cost + lambda *
#: P_INTRA_BITS` with `inter cost + lambda * (the vector's bits)`, on
#: one scale (SATD with mode_decision, else SAD), lambda =
#: P_INTRA_LAMBDA[qp]. Chosen in PR 45's step 0 on tools/crossing.py
#: at 1080p, QP 25, the serving tools on (PERF.md §6): a P picture's
#: bits fall by 5.6 % at 24 (26 % of its macroblocks intra, PSNR-Y
#: +0.55 dB), 6.4 % at 96, 7.3 % at 128 (16 %, +0.36 dB), 7.8 % at
#: 160 (10 %), 7.0 % at 400 (4 %, +0.09 dB).
P_INTRA_BITS = 128
#: passes in which `p_intra` codes the macroblocks that want intra:
#: pass k those of class (x + y) mod P_INTRA_PASSES == k, each
#: predicted from neighbours the pass before made final; where wishes
#: touch, one macroblock in P_INTRA_PASSES stays inter
#: (jaxinter._p_intra). Each pass is one plane-parallel Intra16x16
#: residual of the picture.
P_INTRA_PASSES = 4
#: lambda of a SATD / SAD cost at each QP: round(2 ** ((qp - 12) / 6)),
#: at least 1 (the square root of the SSD lambda 0.85 * 2 ** ((qp - 12)
#: / 3), as x264's table is)
P_INTRA_LAMBDA = tuple(max(1, int(round(2.0 ** ((q - 12) / 6.0))))
                       for q in range(52))
#: `intra4x4`'s rate terms, in bits, on the scale of P_INTRA_LAMBDA: a
#: block whose mode is the predicted one (§8.3.1.1) pays
#: prev_intra4x4_pred_mode_flag alone, any other the flag and
#: rem_intra4x4_pred_mode
I4X4_MODE_BITS = (1, 4)
#: ... and the handicap of the Intra4x4 kind against Intra16x16: the
#: decision takes Intra4x4 where `sum over blocks (SATD + lambda *
#: mode bits) + lambda * I4X4_BITS` is LESS than the Intra16x16
#: candidate's SATD (ties stay Intra16x16). Chosen in PR 49's step 0
#: on tools/screen.py at 1080p, QP 25, the serving tools on (PERF.md
#: §6 has the sweep).
I4X4_BITS = 16
#: the per-macroblock word of a P picture's kind channel (`pmode`, the
#: transfer layouts' and the packers'): 0 = inter; an intra macroblock
#: holds 1 | Intra16x16 luma mode << 1 | intra chroma mode << 3
P_INTRA_FLAG = 1


@dataclasses.dataclass(frozen=True)
class RdConfig:
    """Static RD feature set of one encode. Hashable (a jit static)."""

    mode_decision: bool = False
    pskip: bool = False
    deblock: bool = False
    #: aq strength in 1/AQ_QUANT QP units (0 = off); use from_settings
    #: or aq_from_strength to build from the float knob
    aq_q: int = 0
    #: motion-vector precision, one of SUBPELS
    subpel: str = "half"
    #: intra macroblocks in P pictures (the per-MB inter / Intra16x16
    #: decision of jaxinter._p_intra)
    p_intra: bool = False
    #: Intra4x4 macroblocks in IDR pictures (the per-MB Intra16x16 /
    #: Intra4x4 decision of jaxcore._intra4x4_luma)
    intra4x4: bool = False

    def __post_init__(self) -> None:
        if self.subpel not in SUBPELS:
            raise ValueError(
                f"subpel must be one of {SUBPELS}, not {self.subpel!r}")

    @property
    def mv_per_pel(self) -> int:
        """Units of this encode's motion vectors per integer sample;
        mvd is coded in quarter samples, 4 // mv_per_pel to a unit."""
        return MV_PER_PEL[self.subpel]

    @property
    def aq_strength(self) -> float:
        return self.aq_q / AQ_QUANT

    @property
    def aq(self) -> bool:
        return self.aq_q > 0

    @property
    def ships_modes(self) -> bool:
        """True when the transfer layouts carry a per-MB intra mode
        (+ qp-delta) side channel (see layout.intra_tail_mb), which
        with `intra4x4` also holds the blocks' modes."""
        return self.mode_decision or self.aq_q > 0 or self.intra4x4

    @property
    def intra_tail_mb(self) -> int:
        """int16 words a macroblock in the IDR's side channel
        (layout.intra_tail_mb, the host's side of the same rule)."""
        from .layout import intra_tail_mb

        return intra_tail_mb(self.ships_modes, self.intra4x4)


#: the feature-off config: every existing path's behavior, bit for bit
RD_OFF = RdConfig()


def aq_from_strength(strength: float) -> int:
    """Quantize the float aq_strength knob to the static aq_q field."""
    return max(0, min(3 * AQ_QUANT,
                      int(round(float(strength) * AQ_QUANT))))


def rd_from_settings(settings) -> RdConfig:
    """Build the static RD config from a Settings snapshot (the six
    knobs registered in core/config.DEFAULT_SETTINGS)."""
    from ...core.config import as_bool, as_float

    return RdConfig(
        mode_decision=as_bool(settings.get("mode_decision", False), False),
        pskip=as_bool(settings.get("pskip", False), False),
        deblock=as_bool(settings.get("deblock", False), False),
        aq_q=aq_from_strength(as_float(settings.get("aq_strength", 0.0),
                                       0.0)),
        subpel=subpel_of(settings),
        p_intra=as_bool(settings.get("p_intra", False), False),
        intra4x4=as_bool(settings.get("intra4x4", False), False),
    )


# ---------------------------------------------------------------------------
# SATD (4x4 Hadamard) — the intra mode-decision cost, numpy reference.
# jaxcore implements the same transform on device; both must agree
# exactly (integer math only).
# ---------------------------------------------------------------------------

_H4 = np.array([[1, 1, 1, 1], [1, 1, -1, -1],
                [1, -1, -1, 1], [1, -1, 1, -1]], np.int32)


def satd16_np(resid: np.ndarray) -> int:
    """Sum of |Hadamard4x4| over a (16, 16) int32 residual block,
    divided by 2 (the standard SATD normalization — integer exact
    because the Hadamard doubles parity)."""
    total = 0
    r = resid.astype(np.int64)
    for by in range(4):
        for bx in range(4):
            b = r[4 * by:4 * by + 4, 4 * bx:4 * bx + 4]
            t = _H4 @ b @ _H4
            total += int(np.abs(t).sum())
    return total // 2


def sath4_np(resid: np.ndarray) -> int:
    """Sum of |Hadamard4x4| of one (4, 4) residual, NOT halved: the
    Intra4x4 decision sums it over a macroblock's blocks and compares
    with twice the Intra16x16 SATD, so no block rounds on its own."""
    return int(np.abs(_H4 @ resid.astype(np.int64) @ _H4).sum())


def satd8_np(resid: np.ndarray) -> int:
    """SATD of an (8, 8) chroma residual (four 4x4 Hadamards)."""
    total = 0
    r = resid.astype(np.int64)
    for by in range(2):
        for bx in range(2):
            b = r[4 * by:4 * by + 4, 4 * bx:4 * bx + 4]
            t = _H4 @ b @ _H4
            total += int(np.abs(t).sum())
    return total // 2


# ---------------------------------------------------------------------------
# perceptual AQ map — per-MB intra QP offsets from luma activity.
# ---------------------------------------------------------------------------

#: activity ceiling: 256·Σx² − (Σx)² <= 256·255²·256 < 2^32 for a
#: 16x16 uint8 block — 32 power-of-two thresholds cover every ilog2
#: value, and the whole computation fits uint32 (the jax mirror runs
#: without x64).
AQ_ACT_BITS = 32


def mb_activity_np(y: np.ndarray, mbw: int, mbh: int) -> np.ndarray:
    """(nmb,) int32 integer activity per MB: floor(log2(1 + V)) where
    V = 256·Σx² − (Σx)² (= 256² · variance of the MB's luma). ALL
    integer math — the jax mirror (jaxcore._mb_activity) must agree
    bit for bit, which float32 log2/variance cannot guarantee at
    rounding boundaries. floor(log2(1+v)) = |{k in 1..32 : v >= 2^k-1}|
    (the 2^k−1 form keeps every threshold inside uint32)."""
    y64 = y[:16 * mbh, :16 * mbw].astype(np.int64)
    mb = y64.reshape(mbh, 16, mbw, 16).transpose(0, 2, 1, 3)
    mb = mb.reshape(mbh * mbw, 256)
    s = mb.sum(axis=1)
    s2 = (mb * mb).sum(axis=1)
    v = 256 * s2 - s * s                       # >= 0, < 2^32
    act = np.zeros(mbh * mbw, np.int64)
    for k in range(1, AQ_ACT_BITS + 1):
        act += v >= ((1 << k) - 1)
    return act.astype(np.int32)


def aq_offsets_from_activity(act: np.ndarray, aq_q: int) -> np.ndarray:
    """(nmb,) int32 per-MB QP offsets from the integer activity map:
    round(strength · (act − mean(act))) via pure integer arithmetic
    (floor-division rounding, identical in numpy and XLA), clamped to
    ±AQ_MAX_DELTA — the x264-style variance-AQ shape: busy MBs
    (texture masks quantization error) move UP in QP, flat MBs down,
    ~zero-mean over the frame so the frame QP stays the rate operating
    point."""
    act = np.asarray(act, np.int64)
    nmb = act.shape[0]
    if aq_q <= 0 or nmb == 0:
        return np.zeros(nmb, np.int32)
    total = act.sum()
    num = aq_q * (act * nmb - total)           # strength·diff · (Q·nmb)
    den = AQ_QUANT * nmb
    delta = (2 * num + den) // (2 * den)       # floor-based round
    return np.clip(delta, -AQ_MAX_DELTA, AQ_MAX_DELTA).astype(np.int32)


def aq_offsets_np(y: np.ndarray, aq_q: int, mbw: int, mbh: int
                  ) -> np.ndarray:
    """(nmb,) int32 per-MB QP offsets for one INTRA frame."""
    return aq_offsets_from_activity(mb_activity_np(y, mbw, mbh), aq_q)


def clamp_qp_map(base_qp, offsets) -> np.ndarray:
    """Per-MB QP = base + offset, clamped to the legal H.264 range."""
    return np.clip(np.asarray(base_qp) + np.asarray(offsets), 0, 51
                   ).astype(np.int32)


def pmode_word(luma_mode, chroma_mode):
    """The `pmode` word of an intra macroblock (arrays or ints)."""
    return P_INTRA_FLAG | (luma_mode << 1) | (chroma_mode << 3)


def pmode_fields(pmode):
    """(is_intra, luma_mode, chroma_mode) of `pmode` words."""
    pmode = np.asarray(pmode, np.int32)
    return (pmode & P_INTRA_FLAG) != 0, (pmode >> 1) & 3, (pmode >> 3) & 3
