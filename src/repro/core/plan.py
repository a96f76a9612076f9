"""FilterPlan: the one declarative description of an adaptive-filter stage.

The paper's thesis is that adaptive reordering should be a property of the
execution engine, not something the user wires by hand. A ``FilterPlan``
is therefore the *whole* user-facing configuration surface — chain, engine,
scope, shard count, compaction, exchange cadence, and device tokenization —
validated once, here, with every cross-field rule in one place. Compiling a
plan (``repro.core.session.build_session``) yields a ``FilterSession`` with
exactly one ``step`` entry point; nothing downstream re-checks combinations.

Valid field combinations (the single source of truth):

  engine      any registered engine name ("jnp", "pallas", "numpy", ...).
              Host (non-traceable) engines stream via
              ``AdaptiveFilter.process_stream``; a session's jitted step
              falls back to the jnp reference engine for them.
  cost_mode   "static" works everywhere; "measured" (host wall clocks)
              needs the numpy engine.
  scope       "per_shard" | "centralized" | "per_batch" (paper §2.2).
  shards      1 = single executor; > 1 runs the step under ``shard_map``
              over a data mesh axis (needs a traceable engine and that
              many visible devices).
  compact     device-side survivor compaction (padded [.., C, cap] gather
              + count). Needs a traceable engine — host engines already
              emit compacted rows.
  capacity    only with ``compact``: None (batch width, lossless), an
              int >= 1 (fixed width; overflow is counted + warned), or
              "auto" (tracks the monitor lane's pass-rate × ``slack``,
              re-quantized to 128s at epoch boundaries).
  slack       >= 1.0; headroom factor for "auto" capacity.
  exchange    "eager" | "deferred" | "deferred-async"; anything but
              "eager" requires scope="centralized" (other scopes never
              exchange statistics).
  tokenize    TokenizeSpec(vocab_size, tokens_per_row) to hash+pack the
              survivors on device; requires ``compact`` (it consumes the
              padded buffers) and vocab_size < 2**24 (u32-limb modulo).
  skip_tier   "off" | "zonemap" | "zonemap+bloom" | "auto": the
              tile-statistics skip tier (``core.skip_tier``) — 128-row
              zone maps (+ Bloom bits for equality predicates) resolve
              whole tiles before the row-level chain. Needs shards == 1
              (the jnp path sizes a gather from a per-step host sync,
              which cannot drive static shapes under shard_map); "auto"
              needs a traceable engine — the session's online tuner
              drives it by measured us_per_row. Survivors, tokens, and
              ordering statistics are bit-identical with the tier on or
              off; only speed changes.
  predicates  a chain with AND-terms (``Predicate.term``) or OP_IN atoms
              needs skip_tier="off" (the zone-map and Bloom proofs cover
              flat and CNF chains of the other ops only); one with
              AND-terms also needs shards == 1 (the elastic reshard
              re-derives CNF orders only).

Two plans are checkpoint-compatible iff their *fingerprints* match: the
fingerprint hashes the semantic identity of the adaptive state (predicate
chain, ordering config, scope, adaptivity, cost mode) and deliberately
excludes execution details (engine, shard count, compaction, exchange,
tokenize) — so a checkpoint moves freely across engines and shard counts
(elastic reshard) but refuses to load into a session whose ordering math
would disagree with the one that wrote it.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import warnings
from typing import Sequence

from repro.core import engine as engine_lib
from repro.core.engine import get_engine
from repro.core.ordering import OrderingConfig
from repro.core.predicates import OP_IN, Predicate
from repro.core.scope import EXCHANGE_MODES, scope_from_str
from repro.core.skip_tier import SKIP_TIER_MODES

#: vocab ceiling of the u32-limb device tokenizer's byte-fold modulo —
#: THE definition (``repro.data.tokenizer`` imports it lazily; it lives
#: here because the plan layer must validate it without importing jax)
MAX_DEVICE_VOCAB = 1 << 24

#: FilterPlan fields EXCLUDED from ``fingerprint()`` by design: execution
#: details a checkpoint is portable across (engine swap, elastic reshard,
#: compaction/tokenize wiring, skip-tier speed knobs). Every plan field
#: must be either hashed by ``fingerprint()`` or listed here —
#: ``repro.analysis.plan_matrix.fingerprint_coverage`` enforces the
#: partition behaviorally, so a new field cannot silently break
#: checkpoint-restore compatibility. Extending this set is a reviewed
#: diff, exactly like the hotpath allowlist.
FINGERPRINT_RUNTIME_ONLY = frozenset({
    "engine", "shards", "axis_name", "compact", "capacity", "slack",
    "exchange", "tokenize", "skip_tier",
})


# ------------------------------------------------------------- deprecation
_WARNED: set[str] = set()


def warn_deprecated(key: str, message: str) -> None:
    """Emit ``message`` as a DeprecationWarning once per process per key.

    Messages carry a ``repro:`` prefix so CI can promote exactly THIS
    package's deprecations to errors (``-W "error:repro:DeprecationWarning"``
    matches on the message prefix) without flaking on third-party
    DeprecationWarnings from jax/numpy releases.
    """
    if key in _WARNED:
        return
    _WARNED.add(key)
    warnings.warn(f"repro: {message}", DeprecationWarning, stacklevel=3)


# ------------------------------------------------------ cross-field rules
def chain_shape(predicates: Sequence[Predicate]) -> dict:
    """The chain facts ``validate_combo`` checks: AND-terms, OP_IN atoms."""
    return {"terms": any(p.term is not None for p in predicates),
            "in_atoms": any(p.op == OP_IN for p in predicates)}


def validate_combo(*, scope: str, cost_mode: str, backend: str,
                   compact_output: bool, compact_capacity,
                   compact_slack: float, exchange: str, shards: int = 1,
                   device_tokenize: bool = False,
                   skip_tier: str = "off", terms: bool = False,
                   in_atoms: bool = False) -> None:
    """THE cross-field validation for every engine × scope × compaction ×
    exchange × tokenize combination.

    ``AdaptiveFilterConfig``, ``ShardedAdaptiveFilter``, the pipelines, and
    ``FilterPlan`` all funnel through here, so the rules cannot drift.

    Every violated rule is reported — ONE aggregated ``ValueError`` listing
    all of them, each enumerating the valid choices for its field — so a
    plan with three bad fields costs one round trip, not three. Rules that
    depend on a field that already failed (e.g. engine-capability checks
    when the backend name is unknown) are skipped rather than reported as
    spurious extra failures.
    """
    problems: list[str] = []
    try:
        scope_from_str(scope)
        scope_ok = True
    except ValueError as e:
        problems.append(str(e))
        scope_ok = False
    if cost_mode not in ("static", "measured"):
        problems.append(f"bad cost_mode {cost_mode}; pick from "
                        "('static', 'measured')")
    backend_ok = backend in engine_lib.available_engines()
    if not backend_ok:
        problems.append(f"bad backend {backend}; registered engines: "
                        f"{engine_lib.available_engines()}")
    if cost_mode == "measured" and backend != "numpy":
        problems.append(
            "measured cost mode needs the host (numpy) backend; "
            f"cost_mode='static' works on every engine, got {backend!r}")
    if shards < 1:
        problems.append(f"shards must be >= 1, got {shards}")
    traceable = backend_ok and get_engine(backend).traceable
    if backend_ok and shards > 1 and not traceable:
        problems.append(
            f"backend {backend!r} is a host engine; the sharded "
            "filter needs a traceable engine (jnp / pallas)")
    if backend_ok and compact_output and not traceable:
        problems.append(
            "compact_output is the device-side gather; the host "
            f"engine {backend!r} already emits compacted rows "
            "(boolean-index short-circuit) — drop the flag")
    if compact_capacity is not None:
        if not compact_output:
            problems.append("compact_capacity needs compact_output=True")
        if isinstance(compact_capacity, str):
            if compact_capacity != "auto":
                problems.append(
                    f"compact_capacity {compact_capacity!r}: pass "
                    "an int, None (batch width), or 'auto'")
        elif compact_capacity < 1:
            problems.append(
                f"compact_capacity must be >= 1, got {compact_capacity!r} "
                "(or None for batch width, or 'auto')")
    if compact_slack < 1.0:
        problems.append(f"compact_slack must be >= 1.0 (headroom factor), "
                        f"got {compact_slack!r}")
    if exchange not in EXCHANGE_MODES:
        problems.append(
            f"bad exchange {exchange!r}; pick from {EXCHANGE_MODES}")
    elif exchange != "eager" and scope_ok and scope != "centralized":
        problems.append(
            "deferred exchange only changes the CENTRALIZED scope's "
            f"collective cadence; scope {scope!r} never exchanges "
            "— drop the flag")
    if device_tokenize and not compact_output:
        problems.append("device_tokenize consumes the padded compacted "
                        "buffers — it needs compact_output=True")
    if skip_tier not in SKIP_TIER_MODES:
        problems.append(
            f"bad skip_tier {skip_tier!r}; pick from {SKIP_TIER_MODES}")
    elif skip_tier != "off":
        if shards > 1:
            problems.append(
                "skip_tier needs shards == 1: the jnp skip path sizes its "
                "ambiguous-tile gather from a per-step host sync, which "
                "cannot drive static shapes under shard_map — run the "
                "tier per-executor or drop it")
        if backend_ok and not getattr(get_engine(backend), "supports_skip",
                                      False):
            problems.append(
                f"backend {backend!r} does not implement the skip tier; "
                "pick an engine with tile-statistics support (jnp / "
                "pallas / numpy) or skip_tier='off'")
        if backend_ok and skip_tier == "auto" and not traceable:
            problems.append(
                "skip_tier='auto' is driven by the session's online "
                "us_per_row tuner, which needs a traceable engine — pick "
                "'zonemap'/'zonemap+bloom' explicitly for host engines")
        if terms or in_atoms:
            problems.append(
                f"skip_tier {skip_tier!r} cannot prove tiles of a chain "
                "with AND-terms or OP_IN atoms (its zone-map and Bloom "
                "proofs cover the other ops in flat and CNF chains) — use "
                "skip_tier='off'")
    if terms and shards > 1:
        problems.append(
            f"shards={shards}: a chain with AND-terms runs on one shard "
            "(the elastic reshard re-derives CNF orders only) — use "
            "shards=1")
    if problems:
        if len(problems) == 1:
            raise ValueError(problems[0])
        raise ValueError(
            f"{len(problems)} invalid plan field combinations:\n  - "
            + "\n  - ".join(problems))


# ----------------------------------------------------------------- the plan
@dataclasses.dataclass(frozen=True)
class TokenizeSpec:
    """On-device tokenize/pack stage appended to the compacted survivors."""

    vocab_size: int
    tokens_per_row: int = 8

    def __post_init__(self) -> None:
        if not 1 <= self.vocab_size < MAX_DEVICE_VOCAB:
            raise ValueError(
                f"tokenize vocab_size must be in [1, {MAX_DEVICE_VOCAB}) "
                f"(u32-limb modulo), got {self.vocab_size}")
        if self.tokens_per_row < 1:
            raise ValueError("tokens_per_row must be >= 1")


@dataclasses.dataclass(frozen=True)
class FilterPlan:
    """Declarative adaptive-filter stage; see the module docstring for the
    full table of valid field combinations (this class IS the single source
    of truth — everything else delegates its validation here).

    Compile with ``repro.core.session.build_session(plan, mesh=None)``.
    """

    predicates: Sequence[Predicate]      # the chain (CNF via Predicate.group)
    ordering: OrderingConfig = OrderingConfig()
    engine: str = "jnp"
    scope: str = "per_shard"
    shards: int = 1
    axis_name: str = "data"
    adaptive: bool = True
    cost_mode: str = "static"
    compact: bool = False
    capacity: int | str | None = None    # None | int | "auto"
    slack: float = 1.5
    exchange: str = "eager"
    tokenize: TokenizeSpec | None = None
    skip_tier: str = "off"               # off | zonemap | zonemap+bloom | auto

    def __post_init__(self) -> None:
        object.__setattr__(self, "predicates", tuple(self.predicates))
        if not self.predicates:
            raise ValueError("need at least one predicate")
        validate_combo(scope=self.scope, cost_mode=self.cost_mode,
                       backend=self.engine, compact_output=self.compact,
                       compact_capacity=self.capacity,
                       compact_slack=self.slack, exchange=self.exchange,
                       shards=self.shards,
                       device_tokenize=self.tokenize is not None,
                       skip_tier=self.skip_tier,
                       **chain_shape(self.predicates))

    # ------------------------------------------------------------ identity
    def fingerprint(self) -> str:
        """Semantic identity of the adaptive state this plan produces.

        Covers the chain, the ordering config, scope, adaptivity, and cost
        mode; excludes engine / shards / compaction / exchange / tokenize /
        skip_tier (execution details a checkpoint is portable across —
        shard count explicitly so, that is what elastic reshard is; the
        skip tier never changes survivors or statistics, only speed).
        """
        # a term label is appended only where one is set, so chains
        # without terms keep the fingerprints they always had (an IN set
        # is in t1/t2)
        payload = {
            "predicates": [
                (p.name, p.column, p.op, p.t1, p.t2, p.rounds,
                 p.static_cost, None if p.group is None else str(p.group))
                + (() if p.term is None else (str(p.term),))
                for p in self.predicates],
            "ordering": dataclasses.asdict(self.ordering),
            "scope": self.scope,
            "adaptive": self.adaptive,
            "cost_mode": self.cost_mode,
        }
        blob = json.dumps(payload, sort_keys=True).encode()
        return hashlib.sha256(blob).hexdigest()[:16]
