"""Names of the device program's stages: `jax.named_scope("tvt.<stage>")`.

A scope adds one component to the `op_name` of every op traced inside
it, and nothing else: the compiled program differs in metadata only. A
profiled job's trace (`TVT_PROFILE_DIR`, per-job `profile_dir`) then
files each device op under its stage in Perfetto / XProf, and
`benchmark/tvtbench/scope_reduce.py` sums device time per stage.

Each stage is entered at the one place its function is defined, so
every step program that reaches it (`parallel/dispatch._encode_gop_single`,
`_encode_wave_gop`, `_sfe_intra_step`, `_sfe_p_step`, the split-frame
steps' dense and farm twins, the XLA mirror) inherits the name. Stages never enclose one
another. The one scope that may enclose a stage is `layout`: it names
the loops over GOPs and P frames, whose bodies hold the stages; an op's
stage is the LAST `tvt.*` component of its path.

jax's persistent compile cache keys on the module with this debug
info stripped, so an executable cached by a tree without the scopes is
loaded as it is, without the names (PERF.md §7).
"""

from __future__ import annotations

import jax

PREFIX = "tvt."

STAGES = (
    "intra",        # _intra_core and what the IDR paths add round it
    "intra4x4",     # rd.intra4x4: the IDR's luma again, each macroblock
                    # Intra16x16 or Intra4x4 (jaxcore._intra4x4_luma)
    "me_prep",      # search centers, padding, center stacks
    "me_search",    # the motion-search kernel (or its XLA mirror)
    "me_median",    # the frame's median MV (next frame's center)
    "residual",     # P-frame transform, quant, recon
    "p_intra",      # rd.p_intra: the inter / Intra16x16 decision of a
                    # P macroblock and the chosen ones' residual
    "deblock",      # in-loop filter (rd.deblock)
    "pack",         # sparse packs of the level vector
    "compact",      # fold of the sparse streams into one payload
    "halo",         # SFE band halo exchange and recon fix-up
    "layout",       # casts, flattening, the GOP and P-frame loops
)


def stage(name: str):
    """`with stage("pack"):` or `@stage("pack")` on a function."""
    if name not in STAGES:
        raise ValueError(f"no stage named {name!r} (have {STAGES})")
    return jax.named_scope(PREFIX + name)
