"""Fault tolerance: checkpoint/restart driver, failure injection, stragglers.

At 1000+ nodes the mean time between node failures is shorter than a long
run, so the driver treats failure as the normal case:

  * every state element needed to resume — params, optimizer, data-pipeline
    cursor AND the adaptive filter's OrderState (the paper's ranks) — lives
    in one atomic checkpoint; restart resumes BIT-IDENTICALLY (asserted by
    tests/test_fault_tolerance.py);
  * ``FailureInjector`` kills steps deterministically for tests/chaos runs;
  * ``StragglerMonitor`` implements the data-plane mitigation the paper's
    per-executor scope enables: each shard's filter keeps local ranks, so a
    slow/failed shard's *unprocessed batches* can be reassigned to healthy
    shards without transferring any adaptive state (round-robin reassignment
    over the counter-based stream — any shard can generate any batch);
  * elastic rescale: checkpoints are host-local numpy + a manifest, so a
    restore can target a different device count (re-shard on load).
The data-plane extensions (the guarded-runtime counterpart of the
step-kill injector):

  * ``DataFaultInjector`` — seeded NaN/Inf batch poisoning + all-pass
    column storms, pure in ``(seed, batch_index)`` so rollback REPLAY
    re-applies identical faults;
  * ``corrupt_state`` / ``corrupt_blob`` — one-defect OrderState and
    bit-flipped checkpoint factories for validator/integrity tests;
  * ``GracefulShutdown`` — SIGINT/SIGTERM → polled flag, so drivers flush
    a final checkpoint and print the resume command instead of dying
    mid-epoch.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Iterable

import jax
import numpy as np

from repro.checkpoint import CheckpointManager


class FailureInjector:
    """Deterministically raises at the given step numbers (chaos testing)."""

    def __init__(self, fail_at_steps: Iterable[int] = ()):
        self.fail_at = set(fail_at_steps)
        self.failures = 0

    def maybe_fail(self, step: int):
        if step in self.fail_at:
            self.fail_at.discard(step)
            self.failures += 1
            raise RuntimeError(f"injected node failure at step {step}")


# ========================================================= data-plane faults
class DataFaultInjector:
    """Seeded, REPLAY-DETERMINISTIC data-plane fault schedule.

    Transforms batch contents as a pure function of ``(seed, batch_index,
    cols)`` — never of call count — so the guarded runtime's rollback
    replay (``GuardedSession.run_log_stream``) re-applies identical faults
    to re-generated batches. Fault kinds:

      * ``poison_at``: NaN/Inf-poison a seeded fraction of the batch's
        cells (half NaN, half +Inf) — the admission check must quarantine;
      * ``storm_at``: replace the batch with ``storm_row`` tiled across
        every row — an adversarial column storm in which (by the caller's
        construction of ``storm_row``) every row passes the chain,
        overflowing any bounded ``compact_capacity``.

    Use directly as the ``batch_hook`` of ``run_log_stream``.
    """

    def __init__(self, *, poison_at: Iterable[int] = (),
                 storm_at: Iterable[int] = (), storm_row=None,
                 poison_frac: float = 0.01, seed: int = 0):
        self.poison_at = frozenset(poison_at)
        self.storm_at = frozenset(storm_at)
        if self.storm_at and storm_row is None:
            raise ValueError("storm_at needs storm_row (a [C] feature "
                             "vector every predicate passes)")
        self.storm_row = None if storm_row is None \
            else np.asarray(storm_row, np.float32)
        self.poison_frac = poison_frac
        self.seed = seed

    def __call__(self, batch_index: int, cols: np.ndarray) -> np.ndarray:
        if batch_index in self.storm_at:
            return np.tile(self.storm_row[:, None],
                           (1, cols.shape[1])).astype(np.float32)
        if batch_index in self.poison_at:
            rng = np.random.Generator(
                np.random.Philox(key=[self.seed, batch_index]))
            out = np.array(cols, np.float32)
            flat = out.reshape(-1)
            n = max(1, int(flat.size * self.poison_frac))
            idx = rng.choice(flat.size, size=n, replace=False)
            flat[idx[: n // 2 + 1]] = np.nan
            flat[idx[n // 2 + 1:]] = np.inf
            return out
        return cols


#: defect classes ``corrupt_state`` injects — each one is a distinct
#: violated invariant the fused validator must detect
STATE_CORRUPTIONS = ("nan_stat", "inf_stat", "bad_perm", "bad_group_perm",
                     "count_overflow", "negative_rows")


def corrupt_state(state, kind: str, seed: int = 0):
    """Return a copy of an ``OrderState`` with ONE injected defect.

    Simulates in-memory/device state rot for validator property tests and
    the chaos soak. ``kind`` is one of ``STATE_CORRUPTIONS``.
    """
    from repro.data.pipeline import fstate_from_arrays, fstate_to_arrays

    a = {k: np.array(v) for k, v in fstate_to_arrays(state).items()}
    rng = np.random.Generator(np.random.Philox(key=[seed, 0]))
    if kind == "nan_stat":
        flat = a["stats.num_cut"].reshape(-1)
        flat[rng.integers(flat.size)] = np.nan
    elif kind == "inf_stat":
        flat = a["stats.cost_acc"].reshape(-1)
        flat[rng.integers(flat.size)] = np.inf
    elif kind == "bad_perm":
        a["perm"][..., 0] = a["perm"][..., 1]     # duplicate entry
    elif kind == "bad_group_perm":
        a["group_perm"][..., 0] = a["group_perm"].shape[-1] + 3
    elif kind == "count_overflow":
        a["stats.num_cut"][..., 0] = a["stats.n_monitored"] + 1000.0
    elif kind == "negative_rows":
        a["rows_into_epoch"][...] = -5
    else:
        raise ValueError(f"unknown corruption kind {kind!r}; pick from "
                         f"{STATE_CORRUPTIONS}")
    return fstate_from_arrays(a)


def corrupt_blob(blob: dict, *, seed: int = 0, n_flips: int = 1) -> dict:
    """Bit-flip a checkpoint blob (deep copy; the original is untouched).

    Flips ``n_flips`` seeded bits in one of the envelope's state arrays —
    the storage-rot model the crc32 integrity field exists to catch.
    """
    import copy

    out = copy.deepcopy(blob)
    arrays = out["arrays"] if isinstance(out, dict) and "arrays" in out \
        else out
    rng = np.random.Generator(np.random.Philox(key=[seed, 1]))
    key = sorted(arrays)[int(rng.integers(len(arrays)))]
    v = np.array(np.asarray(arrays[key]))
    raw = v.reshape(-1).view(np.uint8)
    for _ in range(n_flips):
        raw[int(rng.integers(raw.size))] ^= np.uint8(
            1 << int(rng.integers(8)))
    arrays[key] = v
    return out


# ========================================================= graceful shutdown
class GracefulShutdown:
    """Context manager turning SIGINT/SIGTERM into a polled flag.

    First signal: set ``requested`` — the driver finishes the current
    step, flushes a final checkpoint, and prints the resume command.
    Second signal: raise ``KeyboardInterrupt`` (the operator insists).
    Handlers are restored on exit; must be entered from the main thread.
    """

    def __init__(self, signals: Iterable[int] | None = None):
        import signal as _signal

        self._signals = tuple(signals) if signals is not None \
            else (_signal.SIGINT, _signal.SIGTERM)
        self.requested = False
        self._old: dict = {}

    def _handler(self, signum, frame):
        if self.requested:
            raise KeyboardInterrupt
        self.requested = True

    def __enter__(self) -> "GracefulShutdown":
        import signal as _signal

        for s in self._signals:
            self._old[s] = _signal.signal(s, self._handler)
        return self

    def __exit__(self, *exc) -> bool:
        import signal as _signal

        for s, h in self._old.items():
            _signal.signal(s, h)
        self._old.clear()
        return False


@dataclasses.dataclass
class StragglerMonitor:
    """Tracks per-shard step latencies; flags shards slower than
    ``threshold`` × median and proposes batch reassignment."""

    n_shards: int
    threshold: float = 2.0
    window: int = 16

    def __post_init__(self):
        self._lat = [list() for _ in range(self.n_shards)]

    def record(self, shard: int, seconds: float):
        buf = self._lat[shard]
        buf.append(seconds)
        if len(buf) > self.window:
            buf.pop(0)

    def stragglers(self) -> list[int]:
        med = np.median([np.mean(l) for l in self._lat if l] or [0.0])
        if med <= 0:
            return []
        return [i for i, l in enumerate(self._lat)
                if l and np.mean(l) > self.threshold * med]

    def reassign(self, shard_batches: dict[int, list[int]]) -> dict[int, list[int]]:
        """Move the tail of each straggler's queue to the fastest shards.
        Batches are counter-based (stream.gen_batch) so any shard can
        produce any batch — no data movement, just index reassignment."""
        slow = set(self.stragglers())
        if not slow:
            return shard_batches
        fast = [i for i in shard_batches if i not in slow]
        if not fast:
            return shard_batches
        out = {k: list(v) for k, v in shard_batches.items()}
        for s in slow:
            tail = out[s][len(out[s]) // 2:]
            out[s] = out[s][:len(out[s]) // 2]
            for j, b in enumerate(tail):
                out[fast[j % len(fast)]].append(b)
        return out


class TrainDriver:
    """Restartable training loop: run() can be killed at any step (or by the
    injector) and called again; it resumes from the newest checkpoint."""

    def __init__(self, *, step_fn: Callable, pipeline, params, opt_state,
                 ckpt_dir: str, ckpt_every: int = 50,
                 injector: FailureInjector | None = None,
                 async_ckpt: bool = False):
        self.step_fn = step_fn
        self.pipeline = pipeline
        self.params = params
        self.opt_state = opt_state
        self.manager = CheckpointManager(ckpt_dir)
        self.ckpt_every = ckpt_every
        self.injector = injector or FailureInjector()
        self.async_ckpt = async_ckpt
        self.step = 0
        self.history: list[float] = []

    # ---------------------------------------------------------------- state
    def _tree(self):
        return {"params": self.params, "opt": self.opt_state}

    def save(self):
        self.manager.save(
            self.step, self._tree(),
            extra={"step": self.step,
                   "pipeline": _pipeline_state_to_json(self.pipeline)},
            blocking=not self.async_ckpt)

    def try_restore(self) -> bool:
        from repro.checkpoint.ckpt import latest_step
        if latest_step(self.manager.directory) is None:
            return False
        tree, extra, step = self.manager.restore(self._tree())
        self.params, self.opt_state = tree["params"], tree["opt"]
        self.step = extra["step"]
        _pipeline_state_from_json(self.pipeline, extra["pipeline"])
        return True

    # ----------------------------------------------------------------- run
    def run(self, n_steps: int, stop: "GracefulShutdown | None" = None
            ) -> bool:
        """Returns True if target reached, False if a failure interrupted.

        ``stop``: optional ``GracefulShutdown`` (or anything with a
        ``requested`` flag) polled between steps — a pending shutdown
        flushes a final checkpoint and returns False instead of dying
        mid-epoch (the caller prints the resume command).
        """
        it = iter(self.pipeline)
        try:
            while self.step < n_steps:
                if stop is not None and getattr(stop, "requested", False):
                    self.manager.wait()
                    self.save()
                    return False
                batch = next(it, None)
                if batch is None:
                    return True  # stream exhausted
                self.injector.maybe_fail(self.step)
                self.params, self.opt_state, metrics = self.step_fn(
                    self.params, self.opt_state, batch)
                self.history.append(float(metrics["loss"]))
                self.step += 1
                if self.step % self.ckpt_every == 0:
                    self.save()
        except RuntimeError:
            self.manager.wait()
            return False
        self.manager.wait()
        self.save()
        return True


def _pipeline_state_to_json(pipeline) -> dict:
    """Handles both ``Pipeline`` (one cursor) and ``ShardedPipeline`` (one
    cursor per shard). The filter state is the session's versioned blob:
    scalar metadata (version, fingerprint, shard + accumulator layout)
    rides under ``filter_meta`` so restores are guarded and elastic."""
    st = pipeline.state()
    arrays = st.filter_state["arrays"]
    out = {
        "filter_meta": {k: v for k, v in st.filter_state.items()
                        if k != "arrays"},
        "filter_state": {k: v.tolist() for k, v in arrays.items()},
        "filter_dtypes": {k: str(v.dtype) for k, v in arrays.items()},
        "buffer": st.buffer.tolist(),
        "batches_emitted": st.batches_emitted,
        "rows_in": st.rows_in,
        "rows_pass": st.rows_pass,
    }
    if hasattr(st, "stream_cursors"):
        out["stream_cursors"] = [int(c) for c in st.stream_cursors]
    else:
        out["stream_cursor"] = st.stream_cursor
    return out


def _pipeline_state_from_json(pipeline, d: dict):
    from repro.data.pipeline import PipelineState, ShardedPipelineState
    arrays = {k: np.asarray(v, dtype=d["filter_dtypes"][k])
              for k, v in d["filter_state"].items()}
    # pre-session checkpoints have no envelope — their raw arrays load as
    # v1 blobs; versioned ones reassemble the v2 envelope (fingerprint
    # checked, elastic reshard applied on layout change)
    fs = dict(d["filter_meta"], arrays=arrays) if "filter_meta" in d \
        else arrays
    common = dict(filter_state=fs,
                  buffer=np.asarray(d["buffer"], np.int32),
                  batches_emitted=d["batches_emitted"], rows_in=d["rows_in"],
                  rows_pass=d["rows_pass"])
    if "stream_cursors" in d:
        pipeline.restore(ShardedPipelineState(
            stream_cursors=list(d["stream_cursors"]), **common))
    else:
        pipeline.restore(PipelineState(
            stream_cursor=d["stream_cursor"], **common))
