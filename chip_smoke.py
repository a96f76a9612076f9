#!/usr/bin/env python3
"""Smoke run of the adaptive filter on a TPU: the quickest proof that the
main path still starts on the chip. It is a smoke run, not a benchmark —
the times it prints include compilation and per-step host syncs.

  python chip_smoke.py             # one chip: ingest phases + admission server
  python chip_smoke.py --chips 4   # four chips: the sharded scopes only

One chip. Ingest drives ``build_session(FilterPlan(...)).step`` with the
paper chain (``configs/paper_filters.py``) over 8 drifting batches of
2^22 rows × 3 f32 columns (``data/stream.gen_batch``) for:

  pallas            compaction off
  pallas-compact    fused in-kernel compaction, capacity = batch rows
  jnp-compact       the XLA reference path, capacity = batch rows
  pallas-skip       zone-map skip tier on the clustered layout, against
  pallas-clustered  the same clustered stream with the tier off

and checks that every phase on one stream adopts the same per-batch
permutation and epoch, that the permutation changed, that the first and
last batch's masks equal the numpy engine's, that packed survivors equal
the mask-selected rows, and that each pallas step's compiled HLO holds its
kernels as ``tpu_custom_call`` (compiled, not interpreted). The serve phase
runs ``repro.launch.serve.main`` in this process (guardrail chain, 16,384
requests, batch 256, three traffic phases, guarded session) and checks
parity with its synchronous reference and that the guard never retried,
degraded or rolled back.

Four chips (``--chips 4``). The same ingest stream through 4-shard
pallas sessions with ``scope="centralized", exchange="deferred"`` and
``scope="per_shard"``, against a 1-shard session: centralized-deferred
must adopt the 1-shard perm trajectory, and every mask must be equal.

One process, which starts no other. No exception is swallowed: the
script exits non-zero, printing no result line, when JAX finds no TPU, a
check fails or anything raises. Its last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ROWS = 1 << 22          # rows per ingest batch (48 MiB of f32 columns)
BATCHES = 8
SEED = 0


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ------------------------------------------------------------------ ingest
def make_stream(layout: str, rows: int, batches: int) -> list[np.ndarray]:
    from repro.configs.paper_filters import DEFAULT
    from repro.data.stream import gen_batch

    return [gen_batch(SEED, b, b * rows, rows, drift=DEFAULT.drift,
                      layout=layout) for b in range(batches)]


def paper_plan(**kw):
    from repro.configs.paper_filters import DEFAULT, filter_chain
    from repro.core import FilterPlan

    return FilterPlan(predicates=filter_chain("flat"),
                      ordering=DEFAULT.ordering, **kw)


def numpy_mask(plan, batch: np.ndarray) -> np.ndarray:
    """The host oracle's mask (a CNF mask does not depend on the order)."""
    from repro.core import MonitorSpec, get_engine, pack

    specs = pack(plan.predicates)
    res = get_engine("numpy").run_chain(
        batch, specs, np.arange(specs.n, dtype=np.int32),
        MonitorSpec(plan.ordering.collect_rate, 0))
    return np.asarray(res.mask, bool)


def run_phase(name: str, plan, stream, oracle: dict, *, mesh=None,
              kernels: tuple = ()) -> dict:
    """Drive one plan over the stream; check masks, survivors and HLO."""
    import jax

    from repro.core import build_session

    session = build_session(plan, mesh=mesh)
    state = session.init_state()
    trajectory = [np.asarray(state.perm).tolist()]
    epochs = []
    t0 = time.perf_counter()
    t_first = None
    for b, batch in enumerate(stream):
        if b == 1:
            texts = session.compiled_step_texts(state, batch)
            t_hlo = time.perf_counter()
        state, res = session.step(state, batch)
        trajectory.append(np.asarray(res.metrics.perm).tolist())
        epochs.append(int(np.max(np.asarray(res.metrics.epoch))))
        if b in oracle:
            mask = res.mask_np
            check(np.array_equal(mask, oracle[b]),
                  f"{name}: batch {b} mask differs from the numpy engine "
                  f"({int(mask.sum())} vs {int(oracle[b].sum())} survivors)")
            if plan.compact:
                check(res.n_dropped == 0,
                      f"{name}: batch {b} dropped {res.n_dropped} survivors")
                check(np.array_equal(res.survivors(), batch[:, mask]),
                      f"{name}: batch {b} packed survivors differ from the "
                      "mask-selected rows")
        if b == 0:
            jax.block_until_ready(state)
            t_first = time.perf_counter() - t0
    jax.block_until_ready(state)
    steady = time.perf_counter() - t_hlo
    rows = sum(int(b.shape[1]) for b in stream[1:])
    for kernel in kernels:
        check(any(kernel in t and "tpu_custom_call" in t
                  for t in texts.values()),
              f"{name}: compiled step has no tpu_custom_call for {kernel}")
    log(f"{name}: first step (compile) {t_first:.3f}s, then "
        f"{rows / steady:.4g} rows/s over {len(stream) - 1} steps "
        f"[smoke timing, not a benchmark]; epochs {epochs}")
    return {"perms": trajectory, "epochs": epochs}


def same_trajectory(a: dict, b: dict, what: str) -> None:
    check(a["perms"] == b["perms"] and a["epochs"] == b["epochs"],
          f"{what}: perm/epoch trajectories differ\n{a}\n{b}")


def ingest_one_chip(rows: int = ROWS, batches: int = BATCHES) -> None:
    t0 = time.perf_counter()
    iid = make_stream("iid", rows, batches)
    clustered = make_stream("clustered", rows, batches)
    plan = paper_plan()
    last = batches - 1
    oracle_iid = {b: numpy_mask(plan, iid[b]) for b in (0, last)}
    oracle_cl = {b: numpy_mask(plan, clustered[b]) for b in (0, last)}
    log(f"streams + numpy oracle: {time.perf_counter() - t0:.1f}s "
        f"({batches} x {rows} rows x 3 f32 columns per layout)")

    chain = ("adaptive_filter_chain",)
    pallas = run_phase("pallas", paper_plan(engine="pallas"), iid,
                       oracle_iid, kernels=chain)
    check(len({tuple(p) for p in pallas["perms"]}) > 1,
          "the permutation never changed over the drifting stream")
    fused = run_phase("pallas-compact",
                      paper_plan(engine="pallas", compact=True,
                                 capacity=rows), iid, oracle_iid,
                      kernels=chain)
    jnp_c = run_phase("jnp-compact",
                      paper_plan(engine="jnp", compact=True, capacity=rows),
                      iid, oracle_iid)
    same_trajectory(pallas, fused, "pallas vs pallas-compact")
    same_trajectory(pallas, jnp_c, "pallas vs jnp-compact")
    skip = run_phase("pallas-skip",
                     paper_plan(engine="pallas", skip_tier="zonemap"),
                     clustered, oracle_cl,
                     kernels=("adaptive_filter_tile_stats",
                              "adaptive_filter_chain_skip"))
    off = run_phase("pallas-clustered", paper_plan(engine="pallas"),
                    clustered, oracle_cl, kernels=chain)
    same_trajectory(skip, off, "skip tier vs tier off (clustered)")


# ------------------------------------------------------------------- serve
def serve_one_chip(out_dir: Path) -> None:
    from repro.launch import serve

    bench = out_dir / "serve.json"
    t0 = time.perf_counter()
    rc = serve.main(["--requests", "16384", "--batch", "256", "--guarded",
                     "--bench-out", str(bench),
                     "--state-out", str(out_dir / "serve_state.json")])
    dt = time.perf_counter() - t0
    check(rc == 0, f"serve exited {rc}")
    report = json.loads(bench.read_text())
    check(report["parity"]["checked"] and report["parity"]["ok"],
          f"serve parity failed: {report['parity']}")
    guard = report["guard"]
    for key in ("retries", "n_degrades", "rollbacks"):
        check(guard[key] == 0, f"serve guard {key} = {guard[key]}: a "
              "recovery rung hid a failure")
    check(report["config"]["phases_seen"] == [0, 1, 2],
          f"traffic phases {report['config']['phases_seen']}")
    log(f"serve: {report['decided']}/{report['requests']} decided, "
        f"{report['requests_per_sec']:.6g} req/s, p99 admission "
        f"{report['admission_latency_ms']['p99']:.6g} ms, wall {dt:.3f}s "
        "[smoke timing, not a benchmark]")


# ------------------------------------------------------------- four chips
def sharded_four_chips(rows: int = ROWS, batches: int = BATCHES) -> None:
    import jax

    from repro.core.sharded import data_mesh

    iid = make_stream("iid", rows, batches)
    plan = paper_plan(engine="pallas")
    oracle = {b: numpy_mask(plan, iid[b]) for b in (0, batches - 1)}
    mesh = data_mesh(4, "data")
    check(mesh.devices.size == 4 and len(jax.devices()) >= 4,
          "the four-chip path needs four devices")
    one = run_phase("1-shard", plan, iid, oracle,
                    kernels=("adaptive_filter_chain",))
    central = run_phase(
        "4-shard centralized-deferred",
        paper_plan(engine="pallas", shards=4, scope="centralized",
                   exchange="deferred"), iid, oracle, mesh=mesh,
        kernels=("adaptive_filter_chain",))
    run_phase("4-shard per_shard",
              paper_plan(engine="pallas", shards=4, scope="per_shard"),
              iid, oracle, mesh=mesh, kernels=("adaptive_filter_chain",))
    shard_perms = central["perms"]             # [batch][shard][P]
    check(all(row == p[0] for p in shard_perms for row in p),
          "centralized-deferred shards hold different permutations")
    check(one["perms"] == [p[0] for p in shard_perms]
          and one["epochs"] == central["epochs"],
          f"centralized-deferred did not adopt the 1-shard trajectory\n"
          f"{one}\n{central}")


# -------------------------------------------------------------------- main
def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: ingest + serve phases; 4: the sharded scopes")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"[chip_smoke] FAIL: no src/repro beside {Path(__file__).name}"
              ": run it from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] FAIL: no TPU found (JAX platform "
              f"{dev.platform!r}); this smoke run needs the chip",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"[chip_smoke] FAIL: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache

    log(f"device {dev.device_kind} x{len(devices)}; compile cache "
        f"{enable_compile_cache()}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            sharded_four_chips()
        else:
            ingest_one_chip()
            with tempfile.TemporaryDirectory() as out:
                serve_one_chip(Path(out))
    except SmokeFailure as e:
        print(f"[chip_smoke] FAIL: {e}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
