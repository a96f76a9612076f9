"""IR-tier static analysis: seeded defects + clean-repo pins.

Every detector class ships with a test that INJECTS its defect and
asserts detection — a verifier nobody has seen fire is a comment, not a
check. The kernel defects are hand-built ``Launch`` records (the audit's
geometry checks are pure functions of the record); the jaxpr defects are
tiny traced closures; the fingerprint defects are simulated drifted
declarations via the ``runtime_only=`` override. The clean-repo pins
lock the shipped tree's expected findings exactly (none), so any new
finding is a visible diff here before it is a CI failure.
"""

import json

import numpy as np
import pytest

from repro.analysis import diagnostics as diag_lib
from repro.analysis import hotpath_lint, jaxpr_lint, kernel_audit, plan_matrix
from repro.analysis.kernel_audit import BlockInfo, Launch

jax = pytest.importorskip("jax")
jnp = jax.numpy


def codes(diags):
    return [d.code for d in diags]


# ===================================================== kernel seeded defects
def _launch(name="seeded_kernel", grid=(2,), in_specs=(), out_specs=(),
            in_shapes=(), out_shapes=(), ctx=None):
    return Launch(name=name, grid=grid, in_specs=list(in_specs),
                  out_specs=list(out_specs), in_shapes=list(in_shapes),
                  out_shapes=list(out_shapes), ctx=ctx or {})


def test_kernel_audit_catches_off_by_one_index_map():
    # 4096 rows in 2048-blocks = 2 blocks; map i -> i+1 walks off the end
    bad = _launch(
        grid=(2,),
        in_specs=[BlockInfo((4, 2048), lambda i: (0, i + 1), "vmem")],
        in_shapes=[((4, 4096), "float32")])
    found = kernel_audit.audit_launches([bad])
    assert "kernel-oob-access" in codes(found)
    assert all(d.severity == "error" for d in found)

    good = _launch(
        grid=(2,),
        in_specs=[BlockInfo((4, 2048), lambda i: (0, i), "vmem")],
        in_shapes=[((4, 4096), "float32")])
    assert kernel_audit.audit_launches([good]) == []


def test_kernel_audit_catches_misaligned_lane_tile():
    # lane block dim 100: not a multiple of 128, not the full extent
    bad = _launch(
        grid=(1,),
        in_specs=[BlockInfo((8, 100), lambda i: (0, 0), "vmem")],
        in_shapes=[((64, 4096), "float32")])
    found = kernel_audit.audit_launches([bad])
    assert codes(found) == ["kernel-misaligned-tile"]
    assert found[0].severity == "error"


def test_kernel_audit_warns_misaligned_sublane():
    # sublane 12: not 1, not the full 64, not a multiple of 8
    bad = _launch(
        grid=(1,),
        in_specs=[BlockInfo((12, 128), lambda i: (0, 0), "vmem")],
        in_shapes=[((64, 4096), "float32")])
    found = kernel_audit.audit_launches([bad])
    assert codes(found) == ["kernel-misaligned-sublane"]
    assert found[0].severity == "warning"


def test_kernel_audit_catches_vmem_blowout():
    # 8 x 4096 x 2048 f32 = 256 MiB block, x2 double-buffer >> 16 MiB
    bad = _launch(
        grid=(1,),
        in_specs=[BlockInfo((8, 4096, 2048), lambda i: (0, 0, 0), "vmem")],
        in_shapes=[((8, 4096, 2048), "float32")])
    found = kernel_audit.audit_launches([bad])
    assert "kernel-vmem-pressure" in codes(found)


def test_kernel_audit_flags_unaligned_dynamic_lane_offset(tmp_path):
    # a ref window at a traced lane offset Mosaic cannot prove aligned —
    # as a store, a load and a DMA window; aligned/static/sublane forms pass
    src = tmp_path / "seeded_kernel.py"
    src.write_text(
        "def k(x_ref, o_ref, hbm, n):\n"
        "    o_ref[:, pl.ds(n, 128)] = x_ref[...]\n"
        "    y = x_ref[0:1, pl.ds(n + 1, 128)]\n"
        "    c = hbm.at[:, pl.ds(n * 3, 256)]\n"
        "    o_ref[:, pl.ds(pl.multiple_of(n * 128, 128), 128)] = y\n"
        "    o_ref[:, pl.ds(0, 128)] = y\n"
        "    o_ref[pl.ds(n, 1), :] = y\n")
    found = kernel_audit.scan_interpret_only(src)
    assert codes(found) == ["kernel-interpret-only"] * 3
    assert sorted(d.location for d in found) == [
        "seeded_kernel.py:2", "seeded_kernel.py:3", "seeded_kernel.py:4"]
    assert all(d.severity == "warning" for d in found)


def test_kernel_audit_clean_on_shipped_kernels():
    """The shipped Pallas launches prove in-bounds + aligned + in-budget
    across the ragged shape sweep, and every dynamic lane offset in the
    kernels is declared aligned: no finding at all."""
    found = kernel_audit.audit_kernels()
    assert found == []


def test_kernel_audit_model_drift_detected(monkeypatch):
    """Skewing the roofline model must trip the byte-contract check."""
    launches = kernel_audit.capture_launches(
        shapes=((2048, 2048, 128, 2048),))
    real = kernel_audit._load_roofline().filter_ingest_model

    class _Skewed:
        @staticmethod
        def filter_ingest_model(**kw):
            m = real(**kw)
            m["bytes_chain_only"] += 512     # model now over-charges
            return m

    monkeypatch.setattr(kernel_audit, "_load_roofline", lambda: _Skewed)
    found = kernel_audit.crosscheck_roofline(launches)
    assert "kernel-model-drift" in codes(found)


def test_kernel_geometry_matches_roofline_exactly():
    launches = kernel_audit.capture_launches()
    assert kernel_audit.crosscheck_roofline(launches) == []


# ====================================================== jaxpr seeded defects
def test_jaxpr_lint_catches_f64():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64) * 2.0)(jnp.ones((4,)))
        found = jaxpr_lint.lint_jaxpr(closed, name="seeded_f64")
    assert "jaxpr-f64" in codes(found)
    assert all(d.severity == "error"
               for d in found if d.code == "jaxpr-f64")


def test_jaxpr_lint_catches_scalar_capture():
    captured = jnp.float32(3.0)          # 0-d device constant in closure
    closed = jax.make_jaxpr(lambda x: x * captured)(jnp.ones((4,)))
    found = jaxpr_lint.lint_jaxpr(closed, name="seeded_capture")
    assert "jaxpr-scalar-capture" in codes(found)

    # python scalars inline as literals — NOT flagged
    closed = jax.make_jaxpr(lambda x: x * 3.0)(jnp.ones((4,)))
    found = jaxpr_lint.lint_jaxpr(closed, name="literal")
    assert "jaxpr-scalar-capture" not in codes(found)


def test_jaxpr_lint_catches_dead_code():
    def f(x):
        _ = jnp.sin(x) + 1.0             # computed, thrown away
        return x * 2.0

    closed = jax.make_jaxpr(f)(jnp.ones((4,)))
    found = jaxpr_lint.lint_jaxpr(closed, name="seeded_dead")
    assert "jaxpr-dead-code" in codes(found)


def test_jaxpr_lint_catches_degenerate_broadcast():
    # current jax elides no-op broadcasts at staging, so seed the rule
    # with a hand-built record shaped like a jaxpr (the lint reads only
    # primitive.name / params / invars / outvars / effects)
    from types import SimpleNamespace as NS

    aval = NS(shape=(4,), dtype=jnp.float32, ndim=1)
    var_in, var_out = NS(aval=aval), NS(aval=aval)
    eqn = NS(primitive=NS(name="broadcast_in_dim"), params={},
             invars=[var_in], outvars=[var_out], effects=frozenset())
    jaxpr = NS(eqns=[eqn], invars=[var_in], outvars=[var_out])
    closed = NS(jaxpr=jaxpr, consts=[])
    found = jaxpr_lint.lint_jaxpr(closed, name="seeded_bcast")
    assert "jaxpr-degenerate-broadcast" in codes(found)


def test_jaxpr_lint_catches_host_callback():
    def f(x):
        jax.debug.callback(lambda v: None, x)
        return x * 2.0

    closed = jax.make_jaxpr(f)(jnp.ones((4,)))
    found = jaxpr_lint.lint_jaxpr(closed, name="seeded_cb")
    assert "jaxpr-host-callback" in codes(found)


def test_session_jaxprs_clean_on_shipped_plans():
    """Every traced session callable of the representative plan families
    lints clean (info-level donation advisories only)."""
    from repro.core.plan import FilterPlan, TokenizeSpec
    from repro.core.predicates import paper_filters_4

    preds = paper_filters_4("fig1")
    for plan in (FilterPlan(predicates=preds),
                 FilterPlan(predicates=preds, compact=True,
                            tokenize=TokenizeSpec(32000),
                            skip_tier="zonemap")):
        found = jaxpr_lint.lint_plan_jaxprs(plan, rows_per_shard=256)
        assert [d for d in found if d.severity != "info"] == []


def test_make_jaxprs_covers_every_jitted_entry():
    from repro.core.plan import FilterPlan, TokenizeSpec
    from repro.core.predicates import paper_filters_4
    from repro.core.session import build_session

    preds = paper_filters_4("fig1")
    batch = np.random.default_rng(0).uniform(
        -64, 64, (4, 512)).astype(np.float32)

    plan = FilterPlan(predicates=preds, compact=True,
                      tokenize=TokenizeSpec(32000), skip_tier="zonemap")
    traced = build_session(plan).make_jaxprs(batch)
    assert {"step", "exchange", "compact", "tokenize", "validate_state",
            "skip_compact"} <= set(traced)

    plain = FilterPlan(predicates=preds, skip_tier="zonemap")
    traced = build_session(plain).make_jaxprs(batch)
    assert {"step", "exchange", "validate_state", "skip_step"} \
        <= set(traced)


def test_tokenizer_trace_then_execute_is_safe():
    """Tracing the tokenizer FIRST must not poison its cached closure for
    later real execution (the functools.cache tracer-leak class the
    scalar-capture rule exists for)."""
    from repro.data import tokenizer

    packed = jnp.ones((1, 2, 128), jnp.float32)
    counts = jnp.asarray([5], jnp.int32)
    closed = jax.make_jaxpr(
        lambda p, c: tokenizer.tokens_from_padded(p, c, 1000))(packed, counts)
    found = jaxpr_lint.lint_jaxpr(closed, name="tokenize")
    assert "jaxpr-scalar-capture" not in codes(found)
    toks, n = tokenizer.tokens_from_padded(packed, counts, 1000)
    assert int(n) == 5 * 8               # executes fine after the trace


# ================================================= deterministic diagnostics
def _sample_diags():
    return [
        diag_lib.Diagnostic("z-code", "warning", "b.py:2", "msg", "hint"),
        diag_lib.Diagnostic("a-code", "error", "b.py:2", "msg", ""),
        diag_lib.Diagnostic("a-code", "error", "a.py:1", "msg", ""),
        diag_lib.Diagnostic("a-code", "error", "b.py:2", "msg", ""),  # dup
        diag_lib.Diagnostic("m-code", "info", "plan:x", "n", ""),
    ]


def test_canonical_is_order_invariant_and_deduped():
    diags = _sample_diags()
    fwd = diag_lib.canonical(diags)
    rev = diag_lib.canonical(list(reversed(diags)))
    assert fwd == rev
    assert len(fwd) == 4                 # exact duplicate removed
    assert json.dumps(diag_lib.to_json(fwd)) \
        == json.dumps(diag_lib.to_json(rev))     # byte-reproducible


def test_canonical_sorts_by_location_then_code():
    out = diag_lib.canonical(_sample_diags())
    assert [(d.location, d.code) for d in out] == [
        ("a.py:1", "a-code"), ("b.py:2", "a-code"), ("b.py:2", "z-code"),
        ("plan:x", "m-code")]


# ----------------------------------------------------------------- SARIF
def test_sarif_export_shape():
    sarif = diag_lib.to_sarif(diag_lib.canonical(_sample_diags()))
    assert sarif["version"] == "2.1.0"
    run = sarif["runs"][0]
    assert [r["id"] for r in run["tool"]["driver"]["rules"]] \
        == ["a-code", "m-code", "z-code"]
    levels = {r["ruleId"]: r["level"] for r in run["results"]}
    assert levels == {"a-code": "error", "z-code": "warning",
                      "m-code": "note"}
    by_rule = {r["ruleId"]: r for r in run["results"]}
    # file:line findings carry a physicalLocation; semantic ones logical
    assert "physicalLocation" in by_rule["a-code"]["locations"][0]
    assert by_rule["m-code"]["locations"][0]["logicalLocations"][0][
        "fullyQualifiedName"] == "plan:x"
    # fix hints ride along in the message text
    assert "hint: hint" in by_rule["z-code"]["message"]["text"]


# ======================================================== stale allowlist
def test_stale_allowlist_entry_is_an_error():
    allow = dict(hotpath_lint.ALLOWLIST)
    allow["AdaptiveFilter.renamed_long_ago"] = "a dangling exemption"
    found = hotpath_lint.lint_hotpath(allowlist=allow)
    stale = [d for d in found if d.code == "hotpath-stale-allowlist"]
    assert len(stale) == 1
    assert stale[0].severity == "error"
    assert "renamed_long_ago" in stale[0].message


def test_shipped_allowlist_has_no_stale_entries():
    found = hotpath_lint.lint_hotpath()
    assert [d for d in found
            if d.code == "hotpath-stale-allowlist"] == []


# ==================================================== fingerprint coverage
def test_fingerprint_coverage_clean_on_shipped_plan():
    assert plan_matrix.fingerprint_coverage() == []


def test_fingerprint_coverage_catches_conflict():
    # declare a HASHED field (scope) runtime-only: declaration vs. hash
    from repro.core.plan import FINGERPRINT_RUNTIME_ONLY
    drifted = FINGERPRINT_RUNTIME_ONLY | {"scope"}
    found = plan_matrix.fingerprint_coverage(runtime_only=drifted)
    assert codes(found) == ["plan-fingerprint-conflict"]
    assert "scope" in found[0].message


def test_fingerprint_coverage_catches_uncovered():
    # drop an unhashed field (engine) from the declaration: now uncovered
    from repro.core.plan import FINGERPRINT_RUNTIME_ONLY
    drifted = FINGERPRINT_RUNTIME_ONLY - {"engine"}
    found = plan_matrix.fingerprint_coverage(runtime_only=drifted)
    assert codes(found) == ["plan-fingerprint-uncovered"]
    assert "engine" in found[0].message


# =========================================================== plan matrix
def test_plan_enumeration_and_identity_dedupe():
    named = plan_matrix.enumerate_plans()
    assert len(named) > 100              # the space is genuinely large
    deduped = plan_matrix.dedupe_plans(named)
    assert 0 < len(deduped) < len(named)
    # identity really is a dedupe key: re-keying loses nothing
    assert len({key for _, _, key in deduped}) == len(deduped)


def test_budget_selection_covers_every_axis_value():
    deduped = plan_matrix.dedupe_plans(plan_matrix.enumerate_plans())
    selected, skipped = plan_matrix.select_within_budget(deduped, 12)
    assert len(selected) == 12
    assert len(selected) + len(skipped) == len(deduped)
    covered = set().union(*(set(key) for _, _, key in selected))
    everything = set().union(*(set(key) for _, _, key in deduped))
    assert covered == everything         # no axis value left unaudited


# ================================================================== CLI
def test_cli_kernels_flag_clean(capsys):
    from repro.analysis.__main__ import main

    assert main(["--kernels"]) == 0
    out = capsys.readouterr().out
    assert "0 error(s)" in out


def test_cli_sarif_output(tmp_path, capsys):
    from repro.analysis.__main__ import main

    sarif_path = tmp_path / "out.sarif"
    assert main(["--hotpath", "--json", "--sarif", str(sarif_path)]) == 0
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert payload == []                 # clean repo
    sarif = json.loads(sarif_path.read_text())
    assert sarif["version"] == "2.1.0"
    assert sarif["runs"][0]["results"] == []


def test_cli_json_is_byte_reproducible(capsys):
    from repro.analysis.__main__ import main

    def run():
        assert main(["--kernels", "--json"]) == 0
        return capsys.readouterr().out

    assert run() == run()
