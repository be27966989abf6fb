"""The stage names of the device program (codecs/h264/stages.py).

Every served step program is compiled here on the CPU at a tiny shape
and the `op_name` of each instruction of the compiled module — what a
profile of the program files the op under — is checked: the stages the
program runs all occur, a stage never encloses a stage, and nearly
every instruction that does work carries one. Values are not tested
here: the parity tests against the numpy reference encoder hold the
bytes (test_inter, test_jaxcore, test_parallel, test_sfe).
"""

import collections
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from thinvids_tpu.codecs.h264.rdo import RdConfig
from thinvids_tpu.codecs.h264.stages import PREFIX, STAGES, stage
from thinvids_tpu.parallel import dispatch

GOP = {"intra", "me_prep", "me_search", "me_median", "residual", "layout"}
SPARSE = {"pack", "compact"}
SFE_P = {"me_prep", "me_search", "me_median", "residual", "halo", "layout"}
SFE_I = {"intra", "halo", "layout"}
RD_ON = RdConfig(mode_decision=True, pskip=True, deblock=True)
#: the serving point as a daemon's settings give it (aq_strength 1.0)
RD_SERVING = RdConfig(mode_decision=True, pskip=True, deblock=True, aq_q=4)
#: ... with quarter-sample vectors (`serving-1080p-camera`)
RD_QUARTER = RdConfig(mode_decision=True, pskip=True, deblock=True, aq_q=4,
                      subpel="quarter")

#: instructions that do no work of their own
NO_WORK = {"constant", "parameter", "tuple", "get-tuple-element", "bitcast"}
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _unscoped_by_design(path: str) -> bool:
    """The exceptions to "every working instruction has a stage":

    - no path at all: what the compiler made itself (copies, layout
      broadcasts, the pieces it splits a cumsum or a sort into), and
      the bodies jax lowers out of line and names relative to nothing
      (`reduce_window_sum` of a cumsum, the `lt`/`select_n` of an
      argmax's reducer);
    - the boundary of a `shard_map`: the per-device view of an input
      or output, before any stage is entered.
    """
    return (not path.startswith("jit(")
            or re.fullmatch(r"jit\(\w+\)/shard_map(/broadcast\.\d+)?", path)
            is not None)


def _shapes(*specs):
    return [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in specs]


def _gop_args():
    G, F, H, W = 2, 3, 32, 64
    c = (G, F, H // 2, W // 2)
    return (_shapes(((G, F, H, W), jnp.uint8), (c, jnp.uint8),
                    (c, jnp.uint8), ((G,), jnp.int32)),
            dict(mbw=W // 16, mbh=H // 16))


def _gop_mesh():
    return Mesh(np.array(jax.devices()[:2]), ("gop",))


def _sfe(p_frame: bool):
    n, mbh_band, W = 2, 2, 64
    H = n * mbh_band * 16
    y, c = ((H, W), jnp.uint8), ((H // 2, W // 2), jnp.uint8)
    tail = [((), jnp.int32), ((n, 1), jnp.int32)]
    kwargs = dict(mbw=W // 16, mbh_band=mbh_band,
                  mesh=Mesh(np.array(jax.devices()[:n]), ("band",)),
                  total_mb_rows=n * mbh_band)
    if not p_frame:
        return _shapes(y, c, c, *tail), kwargs
    ry, rc = ((H, W), jnp.int16), ((H // 2, W // 2), jnp.int16)
    return (_shapes(y, c, c, ry, rc, rc, ((n, 2), jnp.int32), *tail),
            dict(kwargs, halo_rows=16, num_bands=n))


def _lower_gop(program, cuts=False, how="lower", **more):
    """`cuts`: the program of a plan made on scene cuts, which takes
    each GOP's real frame count beside its QP (ISSUE 34). `how`:
    "lower" for the module, "trace" for the jaxpr."""
    args, kwargs = _gop_args()
    return getattr(program, how)(*args, *args[3:] * cuts, **kwargs, **more)


def _lower_sfe(program, p_frame, how="lower", **more):
    args, kwargs = _sfe(p_frame)
    return getattr(program, how)(*args, **kwargs, **more)


CASES = {
    "gop_single": (
        lambda: _lower_gop(dispatch._encode_gop_single),
        GOP | SPARSE),
    "wave_gop": (
        lambda: _lower_gop(dispatch._encode_wave_gop, mesh=_gop_mesh()),
        GOP | SPARSE),
    "sfe_intra": (
        lambda: _lower_sfe(dispatch._sfe_intra_step, False),
        SFE_I | SPARSE),
    "sfe_intra_dense": (
        lambda: _lower_sfe(dispatch._sfe_intra_step_dense, False),
        SFE_I),
    "sfe_p": (
        lambda: _lower_sfe(dispatch._sfe_p_step, True),
        SFE_P | SPARSE),
    "sfe_p_dense": (
        lambda: _lower_sfe(dispatch._sfe_p_step_dense, True),
        SFE_P),
    # the RD features: the in-loop filter is a stage of its own (in a
    # band it filters the band's own rows: no halo exchange round it)
    "gop_single_rd": (
        lambda: _lower_gop(dispatch._encode_gop_single, rd=RD_ON),
        GOP | SPARSE | {"deblock"}),
    "sfe_p_rd": (
        lambda: _lower_sfe(dispatch._sfe_p_step, True, rd=RD_ON),
        SFE_P | SPARSE | {"deblock"}),
    # the P-frame loop with a traced bound: `tvt.layout` still names
    # the `while`, the stages inside it keep their names
    "gop_single_cuts": (
        lambda: _lower_gop(dispatch._encode_gop_single, cuts=True),
        GOP | SPARSE),
    "wave_gop_cuts": (
        lambda: _lower_gop(dispatch._encode_wave_gop, cuts=True,
                           mesh=_gop_mesh()),
        GOP | SPARSE),
    "gop_single_rd_cuts": (
        lambda: _lower_gop(dispatch._encode_gop_single, cuts=True, rd=RD_ON),
        GOP | SPARSE | {"deblock"}),
    # ISSUE 39, the executable of `serving-1080p-edited`: the bounded
    # loop with all four serving tools inside, on one device and a mesh
    "gop_single_serving_cuts": (
        lambda: _lower_gop(dispatch._encode_gop_single, cuts=True,
                           rd=RD_SERVING),
        GOP | SPARSE | {"deblock"}),
    "wave_gop_serving_cuts": (
        lambda: _lower_gop(dispatch._encode_wave_gop, cuts=True,
                           mesh=_gop_mesh(), rd=RD_SERVING),
        GOP | SPARSE | {"deblock"}),
    # ISSUE 41, the executables of `serving-1080p-camera`: the quarter
    # rows run inside the search's stage in the scan form, the bounded
    # form and the split-frame P step
    "gop_single_serving_quarter": (
        lambda: _lower_gop(dispatch._encode_gop_single, rd=RD_QUARTER),
        GOP | SPARSE | {"deblock"}),
    "gop_single_serving_quarter_cuts": (
        lambda: _lower_gop(dispatch._encode_gop_single, cuts=True,
                           rd=RD_QUARTER),
        GOP | SPARSE | {"deblock"}),
    "sfe_p_quarter": (
        lambda: _lower_sfe(dispatch._sfe_p_step, True,
                           rd=RdConfig(subpel="quarter")),
        SFE_P | SPARSE),
}


def _working_paths(text: str) -> list[str]:
    """`op_name` ("" where there is none) of every instruction of a
    compiled module that does work."""
    paths = []
    for line in text.splitlines():
        found = INSTRUCTION.match(line)
        if found and found.group(1) not in NO_WORK:
            name = OP_NAME.search(line)
            paths.append(name.group(1) if name else "")
    return paths


@functools.cache
def _compiled_paths(case: str) -> tuple[str, ...]:
    """One compile per program, whichever tests read it."""
    lower = PACK_ALONE[case] if case in PACK_ALONE else CASES[case][0]
    return tuple(_working_paths(lower().compile().as_text()))


@pytest.mark.parametrize("case", sorted(CASES))
def test_step_program_ops_are_filed_under_their_stage(case):
    want = CASES[case][1]
    paths = _compiled_paths(case)
    assert len(paths) > 500, "the compiled module was not read"
    seen, unscoped = set(), []
    for path in paths:
        parts = path.split("/")
        at = [i for i, part in enumerate(parts) if part.startswith(PREFIX)]
        if not at:
            unscoped.append(path)
            continue
        seen.add(parts[at[-1]][len(PREFIX):])
        # (ii) a stage never encloses a stage. Only `layout` may stand
        # before another scope, and only as the name of a loop (GOPs,
        # P frames) whose body holds it.
        for outer, inner in zip(at, at[1:]):
            assert parts[outer] == PREFIX + "layout" \
                and "while" in parts[outer + 1:inner], path
    # (i) each stage the program runs occurs, and no name outside the set
    assert seen <= set(STAGES)
    assert want <= seen, f"stages missing: {sorted(want - seen)}"
    assert not seen & (set(STAGES) - want - {"layout"}), \
        f"unexpected stages: {sorted(seen - want)}"
    # (iii) what carries no stage is an exception named above, and rare
    strays = sorted({p for p in unscoped if not _unscoped_by_design(p)})
    assert not strays, f"ops outside every stage: {strays[:10]}"
    assert len(unscoped) <= 0.10 * len(paths), \
        f"{len(unscoped)} of {len(paths)} working instructions unscoped"


#: the GOP programs whose last output is the GOP's whole int16 levels,
#: left on the device for the dense fallback (ISSUE 31)
LEVELS_OUT = {
    "gop_single": (dispatch._encode_gop_single, dict),
    "wave_gop": (dispatch._encode_wave_gop,
                 lambda: dict(mesh=_gop_mesh())),
    "gop_single_rd": (dispatch._encode_gop_single, lambda: dict(rd=RD_ON)),
}


@pytest.mark.parametrize("case", sorted(LEVELS_OUT))
def test_what_the_levels_output_adds_is_filed_under_a_stage(case):
    """The same program compiled without its last output is what ran
    before ISSUE 31: the compiler drops what only that output needs.
    Every working instruction the output adds carries a `tvt.*` stage
    (the concatenate of `encode_gop_planes` and the GOP loop's
    stacking: `tvt.layout`), so no op the SOURCE adds can raise
    `dev_unscoped_pct`. What the TPU compiler makes of a one-GOP loop's
    stacking (a zero-fill and a copy with no path at all, PERF.md §6
    PR 31) is not visible to this CPU compile."""
    program, more = LEVELS_OUT[case]
    args, kwargs = _gop_args()
    inner = program.__wrapped__

    def without_levels(*arrays):
        return inner(*arrays, **kwargs, **more())[:-1]

    without_levels.__name__ = inner.__name__    # the same `jit(...)` path
    parent = collections.Counter(_working_paths(
        jax.jit(without_levels).lower(*args).compile().as_text()))
    added = collections.Counter(_compiled_paths(case)) - parent
    assert added and not parent - collections.Counter(_compiled_paths(case))
    stages = {part for path in added for part in path.split("/")
              if part.startswith(PREFIX)}
    assert PREFIX + "layout" in stages
    unscoped = sorted(path for path in added if PREFIX not in path)
    assert not unscoped, unscoped


@pytest.mark.parametrize("shape,mesh", [((1, 2 * 1152), False),
                                        ((2, 3, 770), False),
                                        ((2, 2 * 1152), True)])
def test_the_rewording_of_the_levels_is_filed_under_pack(shape, mesh):
    """ISSUE 37: a wave that left the sparse budgets runs one more
    program, `_levels_as_words` (its int16 levels as int32 words for
    the link). Every instruction of it that does work carries
    `tvt.pack` and no other stage, so the stages still sum to busy and
    `dev_unscoped_pct` gets nothing — in the GOP form (G, L), the
    all-intra form (G, F, L) and sharded over a `gop` mesh."""
    from jax.sharding import NamedSharding, PartitionSpec

    sharding = (NamedSharding(_gop_mesh(), PartitionSpec("gop"))
                if mesh else None)
    levels = jax.ShapeDtypeStruct(shape, jnp.int16, sharding=sharding)
    paths = _working_paths(
        dispatch._levels_as_words.lower(levels).compile().as_text())
    assert len(paths) >= 3, "the compiled module was not read"
    for path in paths:
        scopes = [part for part in path.split("/")
                  if part.startswith(PREFIX)]
        assert scopes == [PREFIX + "pack"], path
        assert path.startswith("jit(_levels_as_words)/"), path


def _lower_pack2(budget_div, val_div):
    from thinvids_tpu.codecs.h264 import jaxcore

    return jax.jit(lambda flat: jaxcore._block_sparse_pack2(
        flat, budget_div, val_div)).lower(
            jax.ShapeDtypeStruct((16 * 64 + 3,), jnp.int32))


#: the pack alone, with the wave path's divisors and the unit budgets
#: of `_sfe_pack_band`, then every step program that packs
#: (`_per_gop_sparse` under the GOP programs, `_sfe_pack_band` under
#: the split-frame steps)
PACK_ALONE = {"pack2": lambda: _lower_pack2(4, 24),
              "pack2_unit": lambda: _lower_pack2(1, 1)}
PACKING = sorted(PACK_ALONE) + sorted(
    case for case, (_lower, want) in CASES.items() if SPARSE <= want)


@pytest.mark.parametrize("case", PACKING)
def test_no_scatter_under_the_pack_stage(case):
    """ISSUE 25: the pack compacts with static addressing. A scatter
    under `tvt.pack` (XLA's TPU lowering: a sort plus one update at a
    time, 7 ns per candidate) must not come back unnoticed."""
    packing = {path for path in _compiled_paths(case)
               if PREFIX + "pack" in path}
    assert len(packing) > 20, "the pack stage was not read"
    scatters = sorted(path for path in packing if "/scatter" in path)
    assert not scatters, scatters


#: every program form that runs the P-frame residual: the GOP program
#: as a `scan` and bounded, at the library and the serving point, and
#: the split-frame P step
RESIDUAL = {
    "gop_single": lambda: _lower_gop(
        dispatch._encode_gop_single, how="trace"),
    "gop_single_cuts": lambda: _lower_gop(
        dispatch._encode_gop_single, cuts=True, how="trace"),
    "gop_single_serving": lambda: _lower_gop(
        dispatch._encode_gop_single, how="trace", rd=RD_SERVING),
    "gop_single_serving_cuts": lambda: _lower_gop(
        dispatch._encode_gop_single, cuts=True, how="trace",
        rd=RD_SERVING),
    "sfe_p": lambda: _lower_sfe(dispatch._sfe_p_step, True, how="trace"),
    "sfe_p_rd": lambda: _lower_sfe(dispatch._sfe_p_step, True, how="trace",
                                   rd=RD_ON),
}


def _equations(jaxpr, outer=()):
    """(path of named scopes, equation) of a jaxpr and of every jaxpr
    nested in it (`jit`, `scan`, `while`, `shard_map`, ...): an inner
    equation's scopes follow those of the equation that holds it, as
    they do in the lowered module's `op_name`."""
    for eqn in jaxpr.eqns:
        path = outer + tuple(
            part for part in str(eqn.source_info.name_stack).split("/")
            if part)
        yield path, eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner, path)


def _tiling_breaches(case, stage_name):
    """(equations seen, arrays with a minor dimension under 8, slices
    with a lane stride or gathers of part-rows) under one stage of a
    traced program. Only per-MB maps (at most 16 entries a
    macroblock) may be narrower than 8."""
    traced = RESIDUAL[case]()
    args, kwargs = _sfe(True) if case.startswith("sfe") else _gop_args()
    shape = args[0].shape
    mb = 16 * (kwargs["mbw"] * shape[-2] // 16)          # 16 per MB
    seen, narrow, strided = 0, [], []
    for path, eqn in _equations(traced.jaxpr.jaxpr):
        scopes = [part for part in path if part.startswith(PREFIX)]
        if not scopes or scopes[-1] != PREFIX + stage_name:
            continue
        seen += 1
        name = eqn.primitive.name
        for var in (*eqn.invars, *eqn.outvars):
            aval = var.aval
            if getattr(aval, "ndim", 0) and aval.shape[-1] < 8 \
                    and aval.size > mb:
                narrow.append((name, aval.shape))
        operand = eqn.invars[0].aval if eqn.invars else None
        if name == "slice" and (eqn.params["strides"] or (1,))[-1] != 1:
            strided.append((name, operand.shape, eqn.params["strides"]))
        if name == "gather" and operand.size > mb \
                and eqn.params["slice_sizes"][-1] != operand.shape[-1]:
            strided.append((name, operand.shape,
                            eqn.params["slice_sizes"]))
    return seen, narrow, strided


@pytest.mark.parametrize("case", sorted(RESIDUAL))
def test_the_residual_keeps_the_planes_tiling(case):
    """ISSUE 40: under `tvt.residual` no array made from a plane has a
    minor dimension under 8 (a TPU lays the minor dimension on 128
    lanes: the (H, W // 4, 4) views of the old butterflies were a
    relayout at 32 times the bytes, 9.5 of a 1080p frame's 18.9 ms),
    nothing is sliced with a lane stride, and a gather moves whole
    rows. Only per-MB maps (at most 16 entries a macroblock: `nz4`, the
    chroma DC levels) and the (4, 4) quant tables are smaller than
    that, so the copies cannot come back unseen."""
    seen, narrow, strided = _tiling_breaches(case, "residual")
    assert seen > 300, "the residual stage was not read"
    assert not narrow, sorted(set(narrow))[:10]
    assert not strided, strided[:10]


@pytest.mark.parametrize("case", sorted(RESIDUAL))
def test_the_probe_keeps_the_planes_tiling(case):
    """ISSUE 42: the same rule under `tvt.me_prep`. The global-motion
    probe's 4x4 box sums viewed the current and the reference plane as
    (H // 4, 4, W // 4, 4) — 1.47 of a 1080p frame's 9.8 ms in two
    `reshape` and two `reduce_sum`; rows are now added by row-strided
    slices and lanes pooled by a 0/1 matrix on 128-lane pieces, in the
    GOP programs' `coarse_probe` and in the split-frame step's
    `banded_probe_cost` alike."""
    seen, narrow, strided = _tiling_breaches(case, "me_prep")
    assert seen > 100, "the ME prep stage was not read"
    assert not narrow, sorted(set(narrow))[:10]
    assert not strided, strided[:10]


@pytest.mark.parametrize("case", ["gop_single", "gop_single_serving"])
def test_the_pack_appends_chunks_and_gathers_nothing(case):
    """ISSUE 47: tier 1 of the two-tier pack stores whole chunks of
    blocks at one dynamic offset each (`jaxcore._append_blocks`, filed
    as `tvt.pack/append`). No `gather` sits under `tvt.pack` — the row
    gather it replaced fetched 1.57 M rows of 32 bytes a 1080p GOP at
    29 ns each, and its time moved 5-50 % with the placement of the
    program's buffers — and tier 1 makes no array with a minor
    dimension under 8: a block is a column of the (16, NB) levels and
    of the (8, NB) words the append moves."""
    packing, tier1, gathers, narrow = 0, 0, [], []
    for path, eqn in _equations(RESIDUAL[case]().jaxpr.jaxpr):
        scopes = [part for part in path if part.startswith(PREFIX)]
        if not scopes or scopes[-1] != PREFIX + "pack":
            continue
        packing += 1
        if eqn.primitive.name == "gather":
            gathers.append(eqn.invars[0].aval.shape)
        if "append" not in path:
            continue
        tier1 += 1
        for var in (*eqn.invars, *eqn.outvars):
            aval = var.aval
            if getattr(aval, "ndim", 0) and aval.shape[-1] < 8 \
                    and aval.size > 16:
                narrow.append((eqn.primitive.name, aval.shape))
    assert packing > 200 and tier1 > 100, "the pack stage was not read"
    assert not gathers, gathers
    assert not narrow, sorted(set(narrow))[:10]


DEBLOCKING = sorted(case for case, (_lower, want) in CASES.items()
                    if "deblock" in want)


@pytest.mark.parametrize("case", DEBLOCKING)
def test_deblock_stage_is_a_named_loop_without_gather_or_scatter(case):
    """ISSUE 26: the in-loop filter addresses samples by static slices
    of reshaped planes and walks the wavefronts in one loop. What it
    adds is filed under `tvt.deblock`; the loop itself is named
    through `tvt.layout` (the one scope that may enclose a stage); and
    no `gather` / `scatter` sits under the stage — the six-pass form
    it replaced indexed whole sample planes by index arrays (PR 25
    measured such addressing at 1 GB/s on the chip). The threshold
    tables are read by compares (`deblock._lut`), so the count is 0,
    per-block grids included."""
    def stage_of(path):
        scopes = [part for part in path.split("/")
                  if part.startswith(PREFIX)]
        return scopes[-1] if scopes else None

    filter_ops = [path for path in _compiled_paths(case)
                  if stage_of(path) == PREFIX + "deblock"]
    assert len(filter_ops) > 100, "the deblock stage was not read"
    in_loop = [path for path in filter_ops if re.search(
        r"tvt\.layout/while/body/(?:closed_call/)?tvt\.deblock/", path)]
    assert len(in_loop) > 50, "the wavefront loop is not named tvt.layout"
    indexed = sorted(path for path in filter_ops
                     if re.search(r"/(gather|scatter)", path))
    assert not indexed, indexed


@pytest.mark.parametrize("case", ["gop_single_serving_cuts",
                                  "wave_gop_serving_cuts"])
def test_the_serving_tools_keep_their_stage_inside_the_bounded_loop(case):
    """ISSUE 39: the P-frame loop of a cut-aligned GOP is a `while`
    with a traced bound, named `tvt.layout` like the loop over GOPs
    round it; with the serving tools on, the filter runs inside both
    and is still filed under `tvt.deblock` (its own wavefront loop, on
    the CPU mirror, one `tvt.layout` deeper), and nothing that works
    inside a loop is left without a stage: each op there has exactly
    one, the last `tvt.*` component of its path."""
    loop = PREFIX + "layout/while/body/"
    paths = _compiled_paths(case)
    in_p_loop = [path for path in paths if re.search(
        rf"{re.escape(loop)}(?:closed_call/)?{re.escape(loop)}", path)]
    assert len(in_p_loop) > 1000, "the P-frame loop was not read"
    stages_there = collections.Counter(
        [part for part in path.split("/") if part.startswith(PREFIX)][-1]
        for path in in_p_loop)
    assert stages_there[PREFIX + "deblock"] > 100
    assert {PREFIX + name for name in
            ("me_prep", "me_search", "me_median", "residual")} \
        <= set(stages_there)
    # the IDR and the sparse pack stay outside the P-frame loop
    assert not {PREFIX + "intra", PREFIX + "pack"} & set(stages_there)
    bare = sorted({path for path in paths
                   if "while" in path and PREFIX not in path})
    assert not bare, bare[:10]


def test_stage_names_are_a_closed_set():
    with pytest.raises(ValueError, match="no stage named"):
        stage("mux")
    text = jax.jit(stage("pack")(lambda x: x + 1)).lower(
        jnp.zeros(4, jnp.int32)).as_text(debug_info=True)
    assert '"jit(<lambda>)/tvt.pack/add"' in text
