"""Compiled-plan auditor: statically checks the HLO a FilterPlan lowers to.

The repo's hardest-won invariants lived as ad-hoc subprocess greps:
PER_SHARD / deferred-exchange steps are collective-free, nothing inside
``session.step`` calls back to the host, the u32-limb tokenizer never
materializes an f64, and the skip tier's quantized gather keeps the jit
cache bounded across ragged batches. This module is those pins as a
reusable pass: ``audit_plan`` compiles a session for the plan, lowers the
jitted step / exchange / tokenize callables, and audits the HLO text —
the same contract surface the ROADMAP's serving / bandit / multi-tenant
directions need to validate many plans against one engine (Strider-style,
arXiv 1705.05688).

Expectations are derived FROM the plan, so the auditor is one call per
plan, not one grep per mode:

  scope            per_shard / per_batch     step must be collective-free
                   centralized + eager       step must carry the collective
                                             (num_shards > 1 meshes only)
                   centralized + deferred*   step collective-free; the
                                             boundary-exchange module must
                                             carry the one collective
  any              step must be free of host callbacks / infeed / outfeed
  tokenize set     step + tokenizer modules must never contain an f64 op
  skip_tier on     distinct step traces across ragged ambiguous-tile
                   counts must stay within the 16-tile quantization bound

Diagnostic codes: ``hlo-step-collective``, ``hlo-missing-collective``,
``hlo-host-callback``, ``hlo-f64-in-tokenize``, ``hlo-unbounded-traces``
(all error severity — each is a broken compile contract).
"""

from __future__ import annotations

import re

import numpy as np

from repro.analysis.diagnostics import Diagnostic

#: collective HLO op kinds (shared with the roofline analyzer —
#: ``launch.hlo_analysis._COLLECTIVES`` is the same tuple; re-declared here
#: so importing the auditor never drags the launch layer in)
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                  "collective-permute")

#: host-transfer markers inside compiled HLO: python callbacks lower to
#: custom-calls whose target names a callback trampoline; infeed/outfeed
#: are the raw host-transfer ops
_CALLBACK_RE = re.compile(r"custom-call.*callback", re.IGNORECASE)
_HOST_OPS = ("infeed", "outfeed", "send(", "send-done", "recv(", "recv-done")


# ------------------------------------------------------------- text queries
def collectives_in(text: str) -> list[str]:
    """Collective op kinds present in an HLO module (sorted, deduped)."""
    found = {kind for kind in COLLECTIVE_OPS
             for line in text.splitlines()
             if re.search(rf"\b{kind}(-start)?\(", line)}
    return sorted(found)


def host_callbacks_in(text: str) -> list[str]:
    """Lines evidencing a host round-trip inside a compiled module."""
    hits = []
    for line in text.splitlines():
        s = line.strip()
        if _CALLBACK_RE.search(s):
            hits.append(s[:160])
        elif any(f" {op}" in s or s.startswith(op) for op in _HOST_OPS):
            if "custom-call" in s or s.split("=")[-1].strip().startswith(
                    ("infeed", "outfeed", "send", "recv")):
                hits.append(s[:160])
    return hits


def has_f64(text: str) -> bool:
    return "f64[" in text or " f64 " in text


# -------------------------------------------------------------- the auditor
def _synth_batch(plan, rows_per_shard: int, shards: int) -> np.ndarray:
    """Deterministic f32[C, S·R] batch shaped for the plan's chain."""
    n_cols = max(p.column for p in plan.predicates) + 1
    rng = np.random.default_rng(7)
    return rng.uniform(-64.0, 64.0,
                       (n_cols, rows_per_shard * shards)).astype(np.float32)


def _expectations(plan, num_shards: int):
    """(step_must_be_collective_free, collective_expected_somewhere)."""
    deferred = plan.exchange != "eager"
    step_free = plan.scope != "centralized" or deferred
    # on a 1-shard mesh the partitioner elides the psum — only demand the
    # collective's PRESENCE when there is an actual mesh to merge across
    expect_present = plan.scope == "centralized" and num_shards > 1 \
        and plan.adaptive
    return step_free, expect_present


def audit_plan(plan, mesh=None, *, rows_per_shard: int = 512,
               ragged_batches: int = 6) -> list[Diagnostic]:
    """Compile ``plan`` and statically audit every module it executes.

    ``mesh``: optional ``jax.sharding.Mesh`` (as ``build_session``); the
    collective presence/absence checks are strongest on a >1-device mesh
    (CI runs this under ``XLA_FLAGS=--xla_force_host_platform_device_count=4``).
    Returns error-severity diagnostics only — a clean plan audits to [].
    """
    from repro.core.session import build_session

    session = build_session(plan, mesh=mesh)
    diags: list[Diagnostic] = []
    shards = session.num_shards
    batch = _synth_batch(plan, rows_per_shard, shards)
    state = session.init_state()

    step_free, expect_present = _expectations(plan, shards)
    step_text = session.compiled_step_texts(state, batch)["step"]
    diags += audit_step_text(step_text, plan, num_shards=shards)

    # the boundary exchange / retune module: deferred CENTRALIZED must show
    # its one collective HERE (and only here)
    if plan.scope == "centralized" and plan.exchange != "eager" \
            and expect_present:
        ex_text = session.compiled_exchange_text(state)
        if not collectives_in(ex_text):
            diags.append(Diagnostic(
                "hlo-missing-collective", "error", "plan:exchange-hlo",
                f"deferred exchange on a {shards}-shard mesh compiled "
                "without any collective — shard statistics are never "
                "merged and every shard re-ranks on local evidence only",
                "the exchange_update psum was dropped; check its "
                "axis names under shard_map"))

    if plan.tokenize is not None:
        diags += _audit_tokenizer(plan, rows_per_shard, shards)

    if plan.skip_tier not in ("off", None) and not session.sharded:
        diags += _audit_trace_count(session, batch,
                                    ragged_batches=ragged_batches)
    return diags


def audit_step_text(step_text: str, plan, *, num_shards: int,
                    location: str = "plan:step-hlo") -> list[Diagnostic]:
    """Audit one compiled per-step module against the plan's contract."""
    diags: list[Diagnostic] = []
    step_free, expect_present = _expectations(plan, num_shards)
    colls = collectives_in(step_text)
    if step_free and colls:
        why = "PER_SHARD/PER_BATCH scopes never exchange statistics" \
            if plan.scope != "centralized" else \
            f"exchange={plan.exchange!r} defers the merge to the " \
            "boundary module"
        diags.append(Diagnostic(
            "hlo-step-collective", "error", location,
            f"per-step HLO for scope={plan.scope!r} "
            f"exchange={plan.exchange!r} contains collectives "
            f"{colls} — {why}, so the step module must compile "
            "collective-free",
            "a cross-shard reduce leaked into the step trace; move it "
            "into the boundary exchange or drop it"))
    if not step_free and expect_present and not colls:
        diags.append(Diagnostic(
            "hlo-missing-collective", "error", location,
            f"eager CENTRALIZED step on a {num_shards}-shard mesh "
            "compiled without any collective — monitor counters are "
            "never globally merged",
            "the per-step reduce_stats psum was dropped"))
    hits = host_callbacks_in(step_text)
    if hits:
        diags.append(Diagnostic(
            "hlo-host-callback", "error", location,
            f"compiled step round-trips to the host ({len(hits)} "
            f"site(s); first: {hits[0]!r}) — the hot step must stay on "
            "device end to end",
            "remove the callback/infeed from the traced step; host work "
            "belongs in the session driver between jit calls"))
    if plan.tokenize is not None and has_f64(step_text):
        diags.append(Diagnostic(
            "hlo-f64-in-tokenize", "error", location,
            "f64 op in a tokenize-plan step module: the u32-limb "
            "tokenizer contract is that no f64 value ever exists in the "
            "traced program (TPUs have no u64/f64 fast path)",
            "something upcast to float64 — check for python-float "
            "promotion or an enable_x64 leak"))
    return diags


def _audit_tokenizer(plan, rows_per_shard: int, shards: int
                     ) -> list[Diagnostic]:
    """Lower the u32-limb tokenize jit for this plan and ban f64 ops."""
    import jax.numpy as jnp

    from repro.data import tokenizer

    ts = plan.tokenize
    n_cols = max(p.column for p in plan.predicates) + 1
    tok = tokenizer._jit_tokens_from_padded()
    packed = jnp.zeros((max(shards, 1), n_cols, rows_per_shard), jnp.float32)
    counts = jnp.zeros((max(shards, 1),), jnp.int32)
    text = tok.lower(packed, counts, vocab_size=ts.vocab_size,
                     tokens_per_row=ts.tokens_per_row).compile().as_text()
    if has_f64(text):
        return [Diagnostic(
            "hlo-f64-in-tokenize", "error", "plan:tokenize-hlo",
            "f64 op in the compiled u32-limb tokenizer module — the "
            "f32→f64 widening must stay integer bit surgery "
            "(data/tokenizer._limb_ops), never a real float64 convert",
            "check that no enable_x64 context wraps the trace and that "
            "the limb ops were not edited to use jnp.float64")]
    return []


def _audit_trace_count(session, batch: np.ndarray, *, ragged_batches: int
                       ) -> list[Diagnostic]:
    """Drive ragged ambiguous-tile widths; the jit cache must stay within
    the 16-tile gather quantization bound.

    The skip tier's one host sync sizes a static gather width, quantized
    by ``skip_tier.quantize_amb_cap`` to multiples of 16 tiles precisely
    so distinct trace count is O(n_tiles/16), not O(n_tiles). An edit
    that drops the quantization still passes every correctness test —
    only the trace count betrays it.
    """
    from repro.core import skip_tier as skip_tier_lib

    f = session.filter
    n_rows = batch.shape[1]
    n_tiles = -(-n_rows // skip_tier_lib.SKIP_TILE)
    bound = len({skip_tier_lib.quantize_amb_cap(k, n_tiles)
                 for k in range(n_tiles + 1)})
    rng = np.random.default_rng(11)
    state = session.init_state()
    for i in range(ragged_batches):
        # vary how many tiles the zone maps can resolve: mix fully-provable
        # constant tiles with straddling ones in a different ratio per batch
        cols = np.asarray(batch).copy()
        n_flat = (i * n_tiles) // max(ragged_batches - 1, 1)
        flat_rows = n_flat * skip_tier_lib.SKIP_TILE
        cols[:, :flat_rows] = 1e9          # provably fails any bounded chain
        cols[:, flat_rows:] = rng.uniform(
            -64.0, 64.0, cols[:, flat_rows:].shape).astype(np.float32)
        state, _ = session.step(state, cols)
    jit_fns = [("skip", f._jit_step_skip),
               ("skip-compact", f._jit_step_skip_compact)]
    diags = []
    for name, fn in jit_fns:
        if fn is None:
            continue
        n_traces = fn._cache_size()
        if n_traces > bound:
            diags.append(Diagnostic(
                "hlo-unbounded-traces", "error", f"plan:{name}-jit-cache",
                f"{n_traces} distinct traces of the {name} step after "
                f"{ragged_batches} ragged batches over {n_tiles} tiles — "
                f"the 16-tile quantization contract bounds it at {bound}",
                "skip_amb_cap stopped quantizing the gather width "
                "(skip_tier.quantize_amb_cap) — every distinct ambiguous "
                "count now compiles its own module"))
    return diags
