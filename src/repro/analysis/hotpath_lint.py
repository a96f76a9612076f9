"""Hot-path sync lint: AST pass banning host round-trips in traced code.

Functions reachable from the jitted step must never force a device→host
sync — one stray ``.item()`` or ``np.asarray`` inside the traced call
graph serializes the dispatch pipeline (or worse, fails under
``shard_map``). This pass walks ``core/``, ``kernels/``, and
``parallel/``, indexes every function, builds a name-based call graph
from the jitted-step roots, and flags inside that reachable set:

  * ``.item()`` / ``.block_until_ready()`` on anything
  * ``jax.device_get`` / ``jax.block_until_ready``
  * ``np.asarray`` / ``np.array`` (numpy forces the transfer; only the
    base names ``np``/``numpy`` count — ``jnp.asarray`` stays on device)
  * ``int(...)`` / ``float(...)`` over an expression that reads data
    (an attribute or subscript other than ``.shape``/``.ndim``/
    ``.dtype``/``.size`` — casting a traced value concretizes it;
    casting static python ints is fine)
  * ``enable_x64`` / ``jax_enable_x64`` anywhere in the reachable set
    (flipping x64 recompiles the world and breaks the u32-limb contract)

A few syncs are SANCTIONED by design and allowlisted with their reasons:
the skip tier's ambiguous-tile count (sizes a static gather width), the
deferred-exchange counter's re-anchor on a state the session did not
issue (the steady step counts boundaries on the host and reads nothing),
and the guarded runtime's probes. The allowlist is qualname-keyed; adding
an entry is a reviewed diff, not a comment.

The call graph is deliberately over-approximate (any call to a name
``foo`` may reach ANY indexed function named ``foo``, attribute calls
match on the terminal name) — for a ban-list, false reachability only
makes the lint stricter, and the explicit module EXCLUDES keep the host
engines (whose whole job is host work) out of the graph.
"""

from __future__ import annotations

import ast
import dataclasses
from pathlib import Path

from repro.analysis.diagnostics import Diagnostic

#: packages scanned, relative to the repro package root
SCAN_DIRS = ("core", "kernels", "parallel", "serving")

#: host-side modules excluded from the graph: their job IS host work
EXCLUDES = (
    "core/engine/numpy_engine.py",   # host engine (row-exact wall time)
    "core/np_exec.py",               # legacy host executor
    "core/executor_sim.py",          # host simulator
    "kernels/filter_chain/ref.py",   # numpy reference kernel
)

#: jitted-step entry points: every function the session jits, plus the
#: shard_mapped bodies (matched by qualified name against the index)
ROOTS = (
    "FilterSession.step",
    "AdaptiveFilter.step",
    "AdaptiveFilter._step_compact",
    "AdaptiveFilter._step_skip",
    "AdaptiveFilter._step_skip_compact",
    "AdaptiveFilter.exchange_update",
    "ShardedAdaptiveFilter.sharded_step",
    "ShardedAdaptiveFilter.sharded_step_compact",
    "ShardedAdaptiveFilter._sharded_exchange",
    # the serving admission step: queue/host glue must not leak syncs
    # into the gate's drive path (the one sanctioned readback is
    # AdmissionServer._decide, allowlisted below)
    "AdmissionServer._gate_batch",
)

#: qualname → why this host sync is sanctioned. Everything else that
#: syncs inside the reachable set is a finding.
ALLOWLIST: dict[str, str] = {
    "AdaptiveFilter.skip_amb_cap":
        "THE skip-tier sync: the ambiguous-tile count sizes a static "
        "(quantized) gather width — one int per step, by design",
    "AdaptiveFilter.observe_for_capacity":
        "epoch-boundary auto-capacity retune; reads accumulated stats "
        "only when an epoch just closed, never in the steady step",
    "FilterSession.step":
        "the DRIVER: orchestrates jit calls from the host, so its own "
        "body may sync between them (extracted helpers are audited "
        "individually; the traced functions it calls are the real roots)",
    "FilterSession._observe_skip_arm":
        "skip_tier='auto' tuner observation: block_until_ready gives "
        "honest per-arm wall clock — both arms pay the same sync",
    "FilterSession._sync_rows_into_epoch":
        "deferred-boundary self-heal: one sync when step is handed a "
        "state the session did not issue (a retry, a restored or "
        "foreign state) — never in the steady step",
    "FilterSession.validate_state":
        "THE guarded-runtime integrity probe: every state invariant "
        "fused into one jitted boolean — one sync per validation "
        "boundary (never per step), driven by runtime.guard",
    "host_pred_rows":
        "trace-time constant: np.asarray reads the closed-over static "
        "PredicateSpecs tuple, never a traced array",
    "_group_matrix":
        "trace-time constant: one-hot of the static CNF groups tuple",
    "cnf_order":
        "trace-time constant: np.asarray reads the static CNF groups "
        "tuple (the ranks sorted around it stay traced xp arrays)",
    "eq_round":
        "trace-time constant: quantizes a static python threshold to its "
        "f32 packing — the arg is never a traced array",
    "bloom_key":
        "trace-time constant: Bloom bit index of a static threshold",
    "AdmissionServer._decide":
        "THE serving dequeue→decision sync: answering rejects and "
        "quarantined batches immediately with a reason code requires "
        "concretizing the gate mask on the host — one readback per "
        "micro-batch, by design",
}

_FORBIDDEN_METHODS = ("item", "block_until_ready")
_NP_NAMES = ("np", "numpy")
_SHAPE_ATTRS = ("shape", "ndim", "dtype", "size")


# ----------------------------------------------------------------- indexing
@dataclasses.dataclass
class _Fn:
    qualname: str          # "Class.method" or "function"
    name: str              # terminal name
    path: Path
    rel: str               # path relative to package root
    node: ast.AST          # FunctionDef / AsyncFunctionDef
    calls: set = dataclasses.field(default_factory=set)


def _index_functions(py_path: Path, rel: str) -> list[_Fn]:
    tree = ast.parse(py_path.read_text(), filename=str(py_path))
    fns: list[_Fn] = []

    def visit(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{child.name}" if prefix else child.name
                fns.append(_Fn(qual, child.name, py_path, rel, child))
                # nested defs (shard_map locals, closures) belong to their
                # parent: violations inside them surface under the parent's
                # qualname, and their callees extend the parent's edge set
            elif isinstance(child, ast.ClassDef):
                visit(child, f"{child.name}.")

    visit(tree, "")
    for fn in fns:
        for sub in ast.walk(fn.node):
            if isinstance(sub, ast.Call):
                callee = _callee_name(sub.func)
                if callee:
                    fn.calls.add(callee)
    return fns


def _callee_name(func: ast.AST) -> str | None:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _reachable(fns: list[_Fn], roots=ROOTS) -> set[str]:
    """Qualnames reachable from the roots through same-name call edges."""
    by_name: dict[str, list[_Fn]] = {}
    by_qual: dict[str, _Fn] = {}
    for fn in fns:
        by_name.setdefault(fn.name, []).append(fn)
        by_qual[fn.qualname] = fn
    seen: set[str] = set()
    frontier = [by_qual[r] for r in roots if r in by_qual]
    while frontier:
        fn = frontier.pop()
        if fn.qualname in seen:
            continue
        seen.add(fn.qualname)
        for callee in fn.calls:
            for cand in by_name.get(callee, ()):
                if cand.qualname not in seen:
                    frontier.append(cand)
    return seen


# -------------------------------------------------------------- the checker
def _reads_data(node: ast.AST) -> bool:
    """True when an int()/float() argument can hold a traced value: it
    dereferences an attribute or subscript that is not a static shape
    query. ``int(x.shape[1])`` is static; ``int(info.n_ambiguous)`` syncs.
    """
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute):
            if sub.attr in _SHAPE_ATTRS:
                return False      # x.shape / arr.ndim: static under trace
        if isinstance(sub, ast.Subscript):
            base = sub.value
            if isinstance(base, ast.Attribute) and base.attr in _SHAPE_ATTRS:
                continue          # x.shape[1]
            return True
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr not in _SHAPE_ATTRS:
            return True
    return False


def _violations_in(fn: _Fn) -> list[tuple[int, str, str]]:
    """(line, code, message) triples for one function body."""
    out = []
    for node in ast.walk(fn.node):
        if isinstance(node, ast.Call):
            callee = node.func
            if isinstance(callee, ast.Attribute):
                if callee.attr in _FORBIDDEN_METHODS:
                    out.append((node.lineno, "hotpath-host-sync",
                                f".{callee.attr}() forces a device→host "
                                "sync"))
                elif callee.attr in ("asarray", "array") and isinstance(
                        callee.value, ast.Name) \
                        and callee.value.id in _NP_NAMES:
                    out.append((node.lineno, "hotpath-host-sync",
                                f"np.{callee.attr}() copies the operand "
                                "to the host"))
                elif callee.attr in ("device_get", "block_until_ready") \
                        and isinstance(callee.value, ast.Name) \
                        and callee.value.id == "jax":
                    out.append((node.lineno, "hotpath-host-sync",
                                f"jax.{callee.attr}() is an explicit "
                                "host sync"))
            elif isinstance(callee, ast.Name):
                if callee.id in ("int", "float") and node.args and \
                        _reads_data(node.args[0]):
                    out.append((node.lineno, "hotpath-host-sync",
                                f"{callee.id}() over a data-bearing "
                                "expression concretizes a traced value"))
        name = None
        if isinstance(node, ast.Attribute):
            name = node.attr
        elif isinstance(node, ast.Name):
            name = node.id
        elif isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and node.value == "jax_enable_x64":
            name = node.value       # jax.config.update("jax_enable_x64", .)
        if name and "enable_x64" in name:
            out.append((node.lineno, "hotpath-enable-x64",
                        "enable_x64 inside the jitted call graph flips "
                        "global precision and recompiles everything"))
    return out


def lint_hotpath(package_root: str | Path | None = None,
                 roots=ROOTS, allowlist: dict | None = None
                 ) -> list[Diagnostic]:
    """Run the hot-path sync lint over an installed ``repro`` tree.

    ``package_root``: directory containing ``core/``/``kernels/``/
    ``parallel/`` (default: the imported ``repro`` package — tests point
    it at a mutated temp copy to prove detection). Findings are error
    severity: a new sync in the hot path is a broken contract, not style.
    """
    if package_root is None:
        # repro is a namespace package (no __init__.py): locate it from a
        # concrete submodule instead of repro.__file__ (which is None)
        from repro.core import plan as _plan
        package_root = Path(_plan.__file__).parent.parent
    package_root = Path(package_root)
    allow = ALLOWLIST if allowlist is None else allowlist

    fns: list[_Fn] = []
    for sub in SCAN_DIRS:
        base = package_root / sub
        if not base.is_dir():
            continue
        for py in sorted(base.rglob("*.py")):
            rel = py.relative_to(package_root).as_posix()
            if rel in EXCLUDES:
                continue
            fns.extend(_index_functions(py, rel))

    reachable = _reachable(fns, roots)
    diags: list[Diagnostic] = []
    # a stale allowlist entry is an ERROR, not noise: it means a sanctioned
    # sync was renamed/removed and its exemption now silently dangles —
    # the next function to take the name inherits a free pass nobody
    # reviewed. Keys must resolve by qualname or bare name in the index.
    known_quals = {fn.qualname for fn in fns}
    known_names = {fn.name for fn in fns}
    for key in sorted(allow):
        if key not in known_quals and key not in known_names:
            diags.append(Diagnostic(
                "hotpath-stale-allowlist", "error", f"allowlist:{key}",
                f"ALLOWLIST entry {key!r} matches no indexed function "
                "(qualname or bare name) under "
                f"{'/'.join(SCAN_DIRS)} — the sanctioned sync it "
                "described was renamed or removed",
                "delete the entry, or re-key it to the function's current "
                "qualname"))
    for fn in fns:
        if fn.qualname not in reachable:
            continue
        if fn.qualname in allow or fn.name in allow:
            continue
        for line, code, msg in _violations_in(fn):
            diags.append(Diagnostic(
                code, "error", f"{fn.rel}:{line}",
                f"{msg} (in {fn.qualname}, reachable from the jitted "
                "step)",
                "hoist the host work into the session driver between jit "
                "calls, or — if this sync is genuinely sanctioned — add "
                "the qualname to hotpath_lint.ALLOWLIST with its reason"))
    return diags
