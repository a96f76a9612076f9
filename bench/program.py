"""The one place the benchmark reaches into the program: it turns a
configuration file into ``build_session(FilterPlan(...))``."""

from __future__ import annotations

PLAN_KEYS = ("engine", "scope", "shards", "exchange", "compact", "capacity",
             "skip_tier")


def session(config: dict):
    from repro.core import FilterPlan, build_session
    from repro.core import predicates as P
    from repro.core.ordering import OrderingConfig

    ops = {"gt": P.OP_GT, "lt": P.OP_LT, "between": P.OP_BETWEEN,
           "hashmix": P.OP_HASHMIX}
    chain = [P.Predicate(p["name"], column=p["column"], op=ops[p["op"]],
                         t1=p["t1"], t2=p.get("t2", 0.0),
                         rounds=p.get("rounds", 0),
                         static_cost=p.get("static_cost", 1.0))
             for p in config["predicates"]]
    plan = {k: v for k, v in config["plan"].items() if k in PLAN_KEYS}
    unknown = set(config["plan"]) - set(PLAN_KEYS)
    if unknown:
        raise ValueError(f"unknown plan keys {sorted(unknown)}")
    return build_session(FilterPlan(
        predicates=chain, ordering=OrderingConfig(**config["ordering"]),
        **plan))
