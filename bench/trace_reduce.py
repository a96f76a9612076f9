"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer readers
and the ``breakdown`` need, with nothing but JAX's own reader.

Device time is read from the ``XLA Ops`` line of each ``/device:TPU:<n>``
plane; the ``Async XLA Ops`` line holds copies that wait in the
background and is not device work. An op's name is the HLO instruction's
name without its ``.<n>`` suffix, so a Pallas kernel reads as its
``name=`` (``adaptive_filter_chain``, ``adaptive_filter_chain_skip``,
``adaptive_filter_tile_stats``). Host spans are the benchmark's own
``bench.*`` annotations on the host plane's ``python`` line; the window is
the ``bench.window`` span. Host→device transfers are the runtime's host
events: ``XlaLinearize`` (the host puts the batch into the device layout)
up to the matching ``tpu::System::TransferToDevice=>IssueEvent=>Done``.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import re

_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*\s*=")
LINEARIZE = "XlaLinearize"
H2D_DONE = "tpu::System::TransferToDevice=>IssueEvent=>Done"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
TOP = 10


def op_name(event_name: str) -> str:
    m = _NAME.match(event_name)
    return m.group(1) if m else event_name.split("(")[0].strip()


def union_length(intervals) -> float:
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def merged(intervals) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Summary:
    """A traced window, in seconds. Per-device figures are means over the
    devices the cell uses."""

    window_s: float
    busy_s: float
    op_s: dict                 # op name → device seconds
    h2d_s: float | None        # union of host→device transfer spans
    h2d_count: int
    gaps: list                 # [(seconds, host span name)] idle gaps
    breakdown: dict

    def kernel_s(self, prefix: str) -> float | None:
        hits = [v for k, v in self.op_s.items() if k.startswith(prefix)]
        return sum(hits) if hits else None

    def collective_s(self) -> float | None:
        hits = [v for k, v in self.op_s.items()
                if any(k.startswith(c) for c in COLLECTIVES)]
        return sum(hits) if hits else None


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce(path: str, n_devices: int) -> Summary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host_spans, lin, done = [], [], []
    devices = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            idx = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[idx] = [(e.start_ns, e.start_ns + e.duration_ns,
                                     op_name(e.name)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = (e.start_ns, e.start_ns + e.duration_ns)
                    if e.name.startswith("bench."):
                        host_spans.append((*span, e.name))
                    elif e.name == LINEARIZE:
                        lin.append(span)
                    elif e.name == H2D_DONE:
                        done.append(span)
    windows = [s for s in host_spans if s[2] == "bench.window"]
    if not windows:
        seen = collections.Counter(n for _, _, n in host_spans)
        raise ValueError(f"{path}: no bench.window span; bench spans seen: "
                         f"{dict(seen)}; planes: "
                         f"{[p.name for p in data.planes]}")
    lo, hi = windows[0][0], windows[0][1]
    used = sorted(devices)[:n_devices]
    if not used:
        raise ValueError(f"{path}: no TPU device plane")
    spans = [s for s in host_spans if s[2] != "bench.window"]
    op_s: dict = collections.Counter()
    busy, gaps = 0.0, collections.Counter()
    all_gaps = []
    for d in used:
        ops = [(*_clip(s, e, lo, hi), n) for s, e, n in devices[d]]
        ops = [(s, e, n) for s, e, n in ops if e > s]
        for s, e, n in ops:
            op_s[n] += (e - s) / 1e9 / len(used)
        runs = merged((s, e) for s, e, _ in ops)
        busy += sum(e - s for s, e in runs) / 1e9 / len(used)
        edges = [lo] + [x for r in runs for x in r] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            best, name = 0, "other"
            for s, e, n in spans:
                ov = min(e, g1) - max(s, g0)
                if ov > best:
                    best, name = ov, n
            gaps[name] += (g1 - g0) / 1e9 / len(used)
            all_gaps.append(((g1 - g0) / 1e9, name))
    h2d = None
    if lin and done:
        pairs = list(zip(sorted(lin), sorted(done))) \
            if len(lin) == len(done) else [*lin, *done]
        h2d = union_length(
            _clip(a[0], b[1], lo, hi) for a, b in
            (p if isinstance(p[0], tuple) else (p, p) for p in pairs)) / 1e9
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]
    return Summary(
        window_s=(hi - lo) / 1e9, busy_s=busy, op_s=dict(op_s), h2d_s=h2d,
        h2d_count=min(len(lin), len(done)), gaps=all_gaps,
        breakdown={"device_ops": [[k, v] for k, v in top_ops],
                   "idle_gaps": [[k, v] for k, v in top_gaps]})


def start(directory: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=opts)


def stop_and_reduce(directory: str, n_devices: int) -> Summary:
    import jax

    jax.profiler.stop_trace()
    paths = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one trace under {directory}: {paths}")
    return reduce(paths[0], n_devices)
