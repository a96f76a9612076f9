#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``: sizes, the
predicate chain, the plan, the driver and the data generator it runs) and
a traffic mix (``bench/traffic/<mix>.json``: parameters that driver
reads). Metrics are small readers, ``bench/metrics/<name>.py``, each with
``read(run) -> float | None``. All three are found by their names, so a
new cell, configuration, mix or metric is new files plus new entries in
``BENCHMARK.json``.

Each run makes its data from ``--seed``, builds the session, warms every
shape the window uses (set-up), measures for ``--seconds``, then compares
what the window produced with the plain reference (``bench/reference.py``)
and prints one JSON line last. ``--trace 1`` traces a window of at most
``TRACE_SECONDS`` and reports the per-layer metrics instead of the
end-to-end ones. Off a TPU, or with fewer chips than the cell asks for, it
exits with code 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
TRACE_SECONDS = 4.0


class Refused(Exception):
    """The run cannot measure here (no chip, unknown device, bad cell)."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_cell(root: Path, workload: str) -> tuple[dict, dict, dict, dict]:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, traffic


def metrics_for(bench: dict, cell: dict, trace: bool) -> list[dict]:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell["name"] in m["workloads"]]


def read_metric(name: str, run):
    mod = importlib.import_module(f"bench.metrics.{name}")
    return mod.read(run)


class Run:
    """What the metric readers see of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def device_info(n_devices: int) -> dict:
    import jax

    devs = jax.devices()[:n_devices]
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in devs if d.memory_stats()]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": int(max(peaks)) if peaks else None}


def compile_counter():
    """Counts compilations and persistent-cache loads from now on."""
    import jax

    box = [0]

    def compiled(event, duration_secs, **_):
        if "backend_compile" in event:
            box[0] += 1

    def loaded(event, **_):
        if event.endswith("/cache_hits"):
            box[0] += 1

    jax.monitoring.register_event_duration_secs_listener(compiled)
    jax.monitoring.register_event_listener(loaded)
    return box


class GcClock:
    """Python's garbage-collector pauses from ``start`` on: the host path
    stalls while it runs."""

    def __init__(self):
        import gc

        self.pauses, self.t0 = [], None
        gc.callbacks.append(self)

    def __call__(self, phase, info):
        if phase == "start":
            self.t0 = time.perf_counter()
        elif self.t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self.t0))

    def close(self):
        import gc

        gc.callbacks.remove(self)


def run_cell(args, root: Path = ROOT, require_chip: bool = True,
             config_override: dict | None = None,
             session_factory=None) -> dict:
    bench, cell, config, traffic = load_cell(root, args.workload)
    if config_override:
        config = {**config, **config_override}
    if not (root / "src" / "repro").is_dir():
        raise Refused(f"no src/repro under {root}: run from a checkout")
    for p in (str(root), str(root / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    devices = jax.devices()
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    if require_chip:
        if devices[0].platform != "tpu":
            raise Refused(f"no TPU found (JAX platform "
                          f"{devices[0].platform!r})")
        if len(devices) < cell["chips"]:
            raise Refused(f"cell {cell['name']} needs {cell['chips']} chips,"
                          f" JAX sees {len(devices)}")
        if devices[0].device_kind not in peaks:
            raise Refused(f"device kind {devices[0].device_kind!r} is not in"
                          " bench/peaks.json")
    peak = peaks.get(devices[0].device_kind)
    from repro.compile_cache import enable_compile_cache

    log(f"device {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{enable_compile_cache()}")

    driver_mod = importlib.import_module(f"bench.drivers.{config['driver']}")
    kw = {} if session_factory is None else {
        "session_factory": session_factory}
    t0 = time.perf_counter()
    driver = driver_mod.Driver(config, traffic, args.seed, **kw)
    t1 = time.perf_counter()
    log(driver.describe())
    driver.warm()
    log(f"set-up: start {t0 - T_START:.3f} s, data and session "
        f"{t1 - t0:.3f} s, warm-up {time.perf_counter() - t1:.3f} s")
    compiles = compile_counter()
    seconds = min(args.seconds, TRACE_SECONDS) if args.trace \
        else float(args.seconds)
    tmp = tempfile.TemporaryDirectory(prefix="bench-trace-") \
        if args.trace else None
    if tmp is not None:
        from bench import trace_reduce

        trace_reduce.start(tmp.name)
    setup_s = time.perf_counter() - T_START
    from bench.drivers.common import annotate

    gc_clock = GcClock()
    with annotate("bench.window"):
        win = driver.window(seconds)
    gc_clock.close()
    trace = None
    if tmp is not None:
        trace = trace_reduce.stop_and_reduce(tmp.name, cell["chips"])
        tmp.cleanup()
    n_compiles = compiles[0]
    dev = device_info(cell["chips"])
    log(f"compiles inside the window: {n_compiles}")
    log(f"peak device memory: {dev['memory_peak_bytes']} bytes")
    lat = sorted(win.latencies_s)
    log(f"window: {win.seconds:.3f} s, {win.steps} steps, "
        f"{win.attempted} {win.latency_kind} started, {len(lat)} done; "
        f"latency median {lat[len(lat) // 2] if lat else None} s, "
        f"longest {lat[-5:]} s")
    gen2 = [t for g, t in gc_clock.pauses if g == 2]
    log(f"garbage collection in the window: {len(gc_clock.pauses)} pauses, "
        f"{sum(t for _, t in gc_clock.pauses):.6f} s in all, {len(gen2)} "
        f"full, longest {max((t for _, t in gc_clock.pauses), default=0):.6f}"
        " s")
    counts = driver.layer_counts(win)
    run = Run(cell=cell, config=config, traffic=traffic, setup_s=setup_s,
              window=win, counts=counts, trace=trace, peak=peak)
    metrics = {}
    for m in metrics_for(bench, cell, args.trace):
        value = read_metric(m["name"], run)
        if value is None:
            if not args.trace:
                raise RuntimeError(f"end-to-end metric {m['name']} read "
                                   "nothing")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = driver.check()
    limits = config["limits"]
    correct = all(checks[k] <= limits[k] for k in limits) \
        and set(checks) == set(limits)
    out = {"correct": correct, "attempted": win.attempted,
           "failed": 0 if correct else win.attempted,
           "metrics": metrics, "device": dev}
    if trace is not None:
        out["device"]["busy_s"] = trace.busy_s
        out["device"]["window_s"] = trace.window_s
        out["breakdown"] = trace.breakdown
    out["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                     for k in sorted(checks)}
    for k in sorted(checks):
        log(f"check {k} = {checks[k]} (limit {limits.get(k)})")
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args)
    except Refused as e:
        log(f"FAIL: {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
