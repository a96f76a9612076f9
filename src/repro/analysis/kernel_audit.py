"""Static memory-safety verifier for the Pallas filter-chain kernels.

The kernels in ``kernels/filter_chain/`` hand-write grid/BlockSpec index
arithmetic; nothing checked it statically. This pass captures every
``pallas_call`` launch geometry (grid, BlockSpecs, operand shapes) by
intercepting the launch — the kernel body never runs — and proves, for a
sweep of supported (rows, cols, capacity, tile) shapes:

  kernel-oob-access        error    a BlockSpec index map demands a block
                                    outside the (tile-padded) array for
                                    some grid point
  kernel-misaligned-tile   error    a VMEM block whose lane (last) dim is
                                    neither a multiple of 128 nor the
                                    array's full lane extent — Mosaic
                                    retiles it with a layout change on
                                    every access
  kernel-misaligned-sublane warning a VMEM block sublane dim that is not
                                    1, a multiple of 8, or the full
                                    sublane extent
  kernel-vmem-pressure     error    double-buffered per-grid-step working
                                    set exceeds the ~16 MiB VMEM budget
  kernel-model-drift       error    captured per-grid-step HBM bytes
                                    disagree with ``benchmarks/roofline.py
                                    ::filter_ingest_model``'s per-launch
                                    charges (the two models are the same
                                    contract, single-sourced in spirit —
                                    they must not contradict)
  kernel-constant-drift    error    module tiling constants broke their
                                    invariants (DEFAULT_TILE % 128,
                                    STAT_TILE == skip_tier.SKIP_TILE, ...)
  kernel-interpret-only    warning  a construct that runs under
                                    ``interpret=True`` but will not lower
                                    to Mosaic as written: a ref indexed
                                    at a dynamic lane offset (``pl.ds``
                                    with a traced start in the minormost
                                    index slot) that is not declared
                                    128-aligned by ``pl.multiple_of``

Blocks in SMEM and outputs left in HBM (``memory_space=pl.ANY``, written
by the kernel's own DMAs) are outside the VMEM geometry checks; the
compile rehearsals in ``tests/test_tpu_compile.py`` cover what Mosaic
itself refuses. ``capture_launches`` sweeps the real entry points (chain
with/without fused compaction, skip-tier decisions on/off, the zone-map
stats pre-pass); ``audit_launches`` runs the geometry checks on any list
of ``Launch`` records, which is what the seeded-defect tests drive
directly.
"""

from __future__ import annotations

import ast
import dataclasses
import importlib
import importlib.util
import itertools
import math
from pathlib import Path

import numpy as np

from repro.analysis.diagnostics import Diagnostic

#: per-core VMEM budget the working-set bound checks against (bytes)
VMEM_BUDGET = 16 * 2 ** 20
#: pipeline double-buffering factor applied to the block working set
DOUBLE_BUFFER = 2

#: default (rows_padded, n_rows_actual, capacity, tile) shape sweep —
#: ragged actual row counts, minimum/large capacities, a non-default tile
DEFAULT_SHAPES = (
    (2048, 2048, 128, 2048),
    (4096, 3100, 1024, 2048),
    (8192, 8192, 8192, 2048),
    (4096, 4000, 512, 512),
)


# ------------------------------------------------------------ capture layer
@dataclasses.dataclass
class BlockInfo:
    """One BlockSpec, reduced to what the geometry checks need."""

    block_shape: tuple | None        # None: whole array; squeezed dims → 1
    index_map: object                # callable grid→block indices, or None
    memory_space: str                # "smem" | "vmem" | "any" (left in HBM)


@dataclasses.dataclass
class Launch:
    """One captured ``pallas_call`` launch geometry."""

    name: str
    grid: tuple
    in_specs: list                   # list[BlockInfo], aligned with in_shapes
    out_specs: list
    in_shapes: list                  # list[(shape tuple, dtype str)]
    out_shapes: list
    ctx: dict = dataclasses.field(default_factory=dict)


def _space_of(spec) -> str:
    space = str(getattr(spec, "memory_space", "")).lower()
    return space if space in ("smem", "any") else "vmem"


def _info_of(spec) -> BlockInfo:
    shape = getattr(spec, "block_shape", None)
    return BlockInfo(None if shape is None
                     else tuple(1 if d is None else d for d in shape),
                     getattr(spec, "index_map", None), _space_of(spec))


def _as_list(v):
    return list(v) if isinstance(v, (list, tuple)) else [v]


class _Recorder:
    """Context manager replacing ``pl.pallas_call`` with a geometry tap.

    The fake launch records (grid, specs, operand/result shapes) and
    returns zeros of the declared out_shape — the kernel body never
    executes, so capture is O(shapes), not O(rows).
    """

    def __init__(self):
        self.launches: list[Launch] = []
        self._real = None

    def __enter__(self):
        from jax.experimental import pallas as pl
        self._real = pl.pallas_call
        launches = self.launches

        def fake_pallas_call(kernel, *, grid=None, in_specs=None,
                             out_specs=None, out_shape=None, name=None,
                             **_kw):
            single = not isinstance(out_shape, (list, tuple))

            def runner(*args):
                import jax.numpy as jnp
                launches.append(Launch(
                    name=name or getattr(kernel, "__name__", "<kernel>"),
                    grid=(grid,) if isinstance(grid, int) else tuple(grid),
                    in_specs=[_info_of(s) for s in _as_list(in_specs)],
                    out_specs=[_info_of(s) for s in _as_list(out_specs)],
                    in_shapes=[(tuple(a.shape), str(a.dtype))
                               for a in args],
                    out_shapes=[(tuple(o.shape), str(o.dtype))
                                for o in _as_list(out_shape)],
                ))
                outs = [jnp.zeros(o.shape, o.dtype)
                        for o in _as_list(out_shape)]
                return outs[0] if single else outs

            return runner

        pl.pallas_call = fake_pallas_call
        return self

    def __exit__(self, *exc):
        from jax.experimental import pallas as pl
        pl.pallas_call = self._real
        return False


def capture_launches(shapes=DEFAULT_SHAPES) -> list[Launch]:
    """Drive every kernel entry point across ``shapes`` under the tap.

    ``shapes``: (rows_padded, n_rows_actual, capacity, tile) tuples.
    Returns one ``Launch`` per ``pallas_call``, annotated with the launch
    context (tile, capacity, actual rows) the audit checks need.
    """
    import jax.numpy as jnp

    from repro.core import predicates as pred_lib

    # the jitted `filter_chain` re-export shadows the module name in the
    # package namespace; import the module itself explicitly
    fc = importlib.import_module("repro.kernels.filter_chain.filter_chain")
    specs = pred_lib.pack(pred_lib.paper_filters_4("fig1"))
    n_cols = int(np.max(np.asarray(specs.column))) + 1
    n_preds = int(specs.column.shape[0])
    perm = jnp.arange(n_preds, dtype=jnp.int32)

    out: list[Launch] = []
    for rows_p, n_rows, cap, tile in shapes:
        if rows_p % tile or tile % fc.STAT_TILE:
            raise ValueError(f"bad sweep shape {(rows_p, n_rows, cap, tile)}")
        cols = jnp.zeros((n_cols, rows_p), jnp.float32)
        meta = jnp.asarray([n_rows, 100, 0, 0], jnp.int32)
        n_sub = rows_p // fc.STAT_TILE
        decisions = (jnp.zeros((n_sub,), jnp.int32),
                     jnp.zeros((n_sub,), jnp.int32))
        ctx = {"tile": tile, "rows_padded": rows_p, "n_rows": n_rows,
               "capacity": cap, "n_cols": n_cols}
        with _Recorder() as rec:
            fc.filter_chain_pallas(cols, specs, perm, meta, tile=tile)
            fc.filter_chain_pallas(cols, specs, perm, meta, tile=tile,
                                   compact=True, capacity=cap)
            fc.filter_chain_pallas(cols, specs, perm, meta, tile=tile,
                                   compact=True, capacity=cap,
                                   skip_decisions=decisions)
            fc.tile_stats_pallas(cols, tile=tile)
        for launch in rec.launches:
            launch.ctx = dict(ctx)
        out.extend(rec.launches)
    return out


# ---------------------------------------------------------- geometry checks
def _dtype_bytes(dtype: str) -> int:
    return np.dtype(dtype).itemsize


def _block_bytes(block, dtype) -> int:
    return int(np.prod(block)) * _dtype_bytes(dtype)


def _check_spec(launch: Launch, kind: str, i: int, spec: BlockInfo,
                arr_shape: tuple, dtype: str) -> list[Diagnostic]:
    diags: list[Diagnostic] = []
    loc = f"kernel:{launch.name}:{kind}[{i}]"
    if spec.memory_space != "vmem" or spec.block_shape is None:
        return diags
    block = spec.block_shape
    if len(block) != len(arr_shape):
        diags.append(Diagnostic(
            "kernel-oob-access", "error", loc,
            f"block rank {len(block)} != array rank {len(arr_shape)} "
            f"({block} vs {arr_shape})", "fix the BlockSpec shape"))
        return diags

    # ---- in-bounds: every grid point's block index must address an
    # existing (tile-padded) block in every dimension
    n_blocks = [max(1, math.ceil(a / b)) for a, b in zip(arr_shape, block)]
    if spec.index_map is not None:
        for point in itertools.product(*(range(g) for g in launch.grid)):
            idx = spec.index_map(*point)
            idx = (idx,) if not isinstance(idx, tuple) else idx
            for d, (bi, nb) in enumerate(zip(idx, n_blocks)):
                if not 0 <= int(bi) < nb:
                    diags.append(Diagnostic(
                        "kernel-oob-access", "error", loc,
                        f"grid point {point}: index map demands block "
                        f"{tuple(int(x) for x in idx)} but dim {d} has "
                        f"only {nb} block(s) of {block[d]} over extent "
                        f"{arr_shape[d]} — rows past the array would be "
                        "read/written",
                        "fix the index map (block indices, not element "
                        "offsets) or the grid size"))
                    break
            else:
                continue
            break                     # one finding per spec is enough

    # ---- lane / sublane alignment (f32 native tile is (8, 128))
    lane = block[-1]
    if lane % 128 and lane != arr_shape[-1]:
        diags.append(Diagnostic(
            "kernel-misaligned-tile", "error", loc,
            f"lane (last) block dim {lane} is neither a multiple of 128 "
            f"nor the full array extent {arr_shape[-1]} — Mosaic retiles "
            "this block with a layout change on every access",
            "pad the block to 128 lanes or restructure so the minormost "
            "dim is fully covered (see the stats-kernel layout)"))
    if len(block) >= 2:
        sub = block[-2]
        if sub not in (1, arr_shape[-2]) and sub % 8:
            diags.append(Diagnostic(
                "kernel-misaligned-sublane", "warning", loc,
                f"sublane block dim {sub} is not 1, a multiple of 8, or "
                f"the full extent {arr_shape[-2]}",
                "round the sublane dim to the 8-row f32 granule"))
    return diags


def _vmem_working_set(launch: Launch) -> int:
    total = 0
    for spec, (shape, dtype) in zip(
            launch.in_specs + launch.out_specs,
            launch.in_shapes + launch.out_shapes):
        if spec.memory_space != "vmem":
            continue
        block = spec.block_shape if spec.block_shape is not None else shape
        total += _block_bytes(block, dtype)
    return DOUBLE_BUFFER * total


def audit_launches(launches) -> list[Diagnostic]:
    """Geometry checks over captured (or hand-built) ``Launch`` records."""
    diags: list[Diagnostic] = []
    for launch in launches:
        for kind, specs, shapes in (("in", launch.in_specs,
                                     launch.in_shapes),
                                    ("out", launch.out_specs,
                                     launch.out_shapes)):
            for i, (spec, (shape, dtype)) in enumerate(zip(specs, shapes)):
                diags += _check_spec(launch, kind, i, spec, shape, dtype)

        ws = _vmem_working_set(launch)
        if ws > VMEM_BUDGET:
            diags.append(Diagnostic(
                "kernel-vmem-pressure", "error", f"kernel:{launch.name}",
                f"double-buffered per-grid-step working set {ws} B "
                f"exceeds the {VMEM_BUDGET} B VMEM budget",
                "shrink the tile or split the launch"))
    return diags


# --------------------------------------------------- roofline byte contract
def _load_roofline():
    """``benchmarks.roofline`` — by import when the repo root is on the
    path, by file location otherwise (installed-package runs)."""
    try:
        return importlib.import_module("benchmarks.roofline")
    except ImportError:
        pass
    from repro.core import plan as _plan
    root = Path(_plan.__file__).resolve().parents[3]
    cand = root / "benchmarks" / "roofline.py"
    if not cand.is_file():
        return None
    spec = importlib.util.spec_from_file_location("_kernel_audit_roofline",
                                                  cand)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _streamed_bytes(launch: Launch) -> int:
    """Per-grid-step bytes a launch streams through VMEM blocks.

    For a chain launch: the column tile in + the int8 mask out. The
    monitor counters live in SMEM (a few hundred bytes against megabyte
    tiles), and the fused compaction's survivors leave by the kernel's
    own DMA to an HBM output — neither is BlockSpec geometry.
    """
    return sum(_block_bytes(spec.block_shape, dtype)
               for spec, (_, dtype) in zip(
                   launch.in_specs + launch.out_specs,
                   launch.in_shapes + launch.out_shapes)
               if spec.memory_space == "vmem")


def crosscheck_roofline(launches) -> list[Diagnostic]:
    """The captured launch geometry and the analytic byte model must agree.

    Each launch family has a closed-form prediction the geometry must
    match byte-for-byte: every chain launch, fused or not, streams
    chain-only = C·T·B + T (the fused survivors are the model's DMA
    term); the stats pre-pass = the summary write half of
    ``bytes_summary``.
    """
    roofline = _load_roofline()
    if roofline is None:
        return [Diagnostic(
            "kernel-model-drift", "warning", "kernel:roofline",
            "benchmarks/roofline.py not found — byte-model cross-check "
            "skipped", "run from a checkout with benchmarks/ present")]
    diags: list[Diagnostic] = []

    def drift(name, what, geom, model):
        diags.append(Diagnostic(
            "kernel-model-drift", "error", f"kernel:{name}",
            f"{what}: captured geometry moves {geom} B/grid-step but "
            f"filter_ingest_model charges {model:.0f} B — the kernel and "
            "the roofline model contradict",
            "change BOTH the kernel and "
            "benchmarks/roofline.py::filter_ingest_model together; they "
            "are one contract"))

    for launch in launches:
        if not launch.ctx:
            continue
        tile, n_cols = launch.ctx["tile"], launch.ctx["n_cols"]
        model = roofline.filter_ingest_model(n_cols=n_cols, tile=tile,
                                             pass_rate=1.0)
        if launch.name.startswith("adaptive_filter_chain"):
            geom = _streamed_bytes(launch)
            if geom != model["bytes_chain_only"]:
                drift(launch.name, "chain launch (streamed blocks)", geom,
                      model["bytes_chain_only"])
        elif "tile_stats" in launch.name:
            geom = sum(_block_bytes(s.block_shape, d)
                       for s, (_, d) in zip(launch.out_specs,
                                            launch.out_shapes))
            want = model["bytes_summary"] / 2        # the write half
            if geom != want:
                drift(launch.name, "zone-map summary write", geom, want)
    return diags


# ------------------------------------------------ interpret-only AST screen
def _is_static(node: ast.AST) -> bool:
    return isinstance(node, ast.Constant) or (
        isinstance(node, ast.UnaryOp) and _is_static(node.operand))


def _is_pl_call(node: ast.AST, attr: str) -> bool:
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == attr
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == "pl")


def scan_interpret_only(source_path: Path | None = None) -> list[Diagnostic]:
    """Flag refs indexed at an unaligned dynamic lane offset: a subscript
    (load, store, or a DMA's ``.at[...]`` window) whose minormost index
    is ``pl.ds`` over a traced start.

    Interpret mode executes them as plain array indexing; Mosaic needs
    to prove a dynamic lane offset is a multiple of 128, so a start
    wrapped in ``pl.multiple_of(...)`` passes, as does a static one. A
    dynamic SUBLANE slice (e.g. the chain's column select) lowers fine
    and is not flagged.
    """
    if source_path is None:
        fc = importlib.import_module(
            "repro.kernels.filter_chain.filter_chain")
        source_path = Path(fc.__file__)
    tree = ast.parse(source_path.read_text(), filename=str(source_path))
    diags: list[Diagnostic] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Subscript):
            continue
        idx = node.slice
        last = idx.elts[-1] if isinstance(idx, ast.Tuple) else idx
        if not (_is_pl_call(last, "ds") and last.args):
            continue
        start = last.args[0]
        if _is_static(start) or _is_pl_call(start, "multiple_of"):
            continue
        diags.append(Diagnostic(
            "kernel-interpret-only", "warning",
            f"{source_path.name}:{node.lineno}",
            "ref indexed at a DYNAMIC lane offset (pl.ds over a traced "
            "start in the minormost slot) that is not declared aligned — "
            "runs under interpret=True, will not lower to Mosaic as "
            "written",
            "align the offset to 128 lanes and say so with "
            "pl.multiple_of, or roll the vector instead (see the fused "
            "compaction's stitch)"))
    return diags


# ---------------------------------------------------------- module constants
def check_constants() -> list[Diagnostic]:
    from repro.core import skip_tier as skip_tier_lib
    from repro.core.adaptive_filter import CAPACITY_QUANTUM

    fc = importlib.import_module("repro.kernels.filter_chain.filter_chain")
    diags = []
    loc = "kernel:constants"
    if fc.DEFAULT_TILE % 128:
        diags.append(Diagnostic(
            "kernel-constant-drift", "error", loc,
            f"DEFAULT_TILE {fc.DEFAULT_TILE} is not a multiple of the "
            "128-lane VPU width", "restore the 128 alignment"))
    if fc.STAT_TILE != skip_tier_lib.SKIP_TILE:
        diags.append(Diagnostic(
            "kernel-constant-drift", "error", loc,
            f"STAT_TILE {fc.STAT_TILE} != skip_tier.SKIP_TILE "
            f"{skip_tier_lib.SKIP_TILE}: the zone-map granularity forked",
            "single-source the granule"))
    if CAPACITY_QUANTUM % 128:
        diags.append(Diagnostic(
            "kernel-constant-drift", "error", loc,
            f"CAPACITY_QUANTUM {CAPACITY_QUANTUM} is not 128-lane "
            "aligned: auto-capacity widths would misalign every packed "
            "buffer", "quantize capacities to 128s"))
    return diags


# ------------------------------------------------------------------- driver
def audit_kernels(shapes=DEFAULT_SHAPES, *, model_check: bool = True
                  ) -> list[Diagnostic]:
    """The full kernel audit: capture + geometry + constants + AST screen
    + roofline byte-model cross-check."""
    launches = capture_launches(shapes)
    diags = audit_launches(launches)
    diags += check_constants()
    diags += scan_interpret_only()
    if model_check:
        diags += crosscheck_roofline(launches)
    return diags
