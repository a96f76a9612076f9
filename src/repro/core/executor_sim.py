"""Spark-fidelity executor simulation: tasks, shared state, lock, deferral.

``core.ordering`` is the *functional* port of the paper's mechanism; this
module reproduces the paper's §2.2 concurrency semantics exactly, for the
fidelity benchmarks and tests:

  * one "executor" = a process-wide state object (permutation + adj ranks),
    the analogue of the static JVM fields;
  * N "task" threads each process partitions (numpy column batches) pulled
    from a shared queue, reading the current permutation WITHOUT a lock
    (like a JVM read of a static array reference);
  * each task accumulates its own (numCut, cost) metrics;
  * when a task observes the epoch boundary it tries the executor lock:
    the winner folds its metrics into the global ranks and re-sorts; losers
    DEFER — they keep their collected metrics and retry at the next epoch
    (verbatim the paper's "non-permitted updates are deferred to the next
    epoch keeping the collected metrics").
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Sequence

import numpy as np

from repro.core import np_exec
from repro.core import stats as stats_lib
from repro.core.ordering import OrderingConfig
from repro.core.predicates import Predicate


class _ExecutorState:
    """The 'static JVM fields' of one executor."""

    def __init__(self, n_preds: int, cfg: OrderingConfig):
        self.cfg = cfg
        self.perm = np.arange(n_preds)
        self.adj_rank = np.zeros(n_preds, np.float64)
        self.rows_seen = 0
        self.epoch = 0
        self.next_boundary = cfg.calculate_rate
        self.lock = threading.Lock()
        self.deferred_updates = 0
        self.perm_history: list[list[int]] = []

    def try_epoch_update(self, num_cut, cost_acc, n_monitored) -> bool:
        """Winner updates ranks; losers defer (returns False, keep metrics).

        The rank math itself is the shared ``core.stats`` implementation run
        on the numpy namespace — this class only reproduces the paper's
        lock/defer concurrency semantics around it.
        """
        if not self.lock.acquire(blocking=False):
            self.deferred_updates += 1
            return False
        try:
            if n_monitored <= 0:
                return True  # consumed, nothing learned
            st = stats_lib.FilterStats(
                num_cut=np.asarray(num_cut, np.float64),
                cost_acc=np.asarray(cost_acc, np.float64),
                n_monitored=float(n_monitored))
            rank = stats_lib.ranks(st, xp=np)
            self.adj_rank = stats_lib.momentum_update(
                self.adj_rank, rank, self.cfg.momentum,
                first_epoch=self.epoch == 0, xp=np)
            self.perm = stats_lib.order_from_ranks(self.adj_rank, xp=np)
            self.perm_history.append([int(i) for i in self.perm])
            self.epoch += 1
            return True
        finally:
            self.lock.release()


@dataclasses.dataclass
class SimResult:
    total_work_units: float
    wall_seconds: float
    rows_processed: int
    rows_passed: int
    epochs: int
    deferred_updates: int
    final_perm: list[int]
    perm_history: list[list[int]]


def run_executor(predicates: Sequence[Predicate],
                 partitions: Sequence[np.ndarray],
                 cfg: OrderingConfig = OrderingConfig(),
                 n_tasks: int = 4,
                 adaptive: bool = True,
                 cost_mode: str = "measured") -> SimResult:
    """Process ``partitions`` with ``n_tasks`` concurrent task threads."""
    n_preds = len(predicates)
    state = _ExecutorState(n_preds, cfg)
    work_q: queue.Queue = queue.Queue()
    for part in partitions:
        work_q.put(part)

    totals = {"work": 0.0, "rows": 0, "passed": 0}
    totals_lock = threading.Lock()

    def task_loop():
        # task-local metric accumulators (survive across partitions, as the
        # paper's tasks... are short-lived; here one thread runs many tasks,
        # each partition plays the role of one task's data slice)
        num_cut = np.zeros(n_preds, np.float64)
        cost_acc = np.zeros(n_preds, np.float64)
        n_mon = 0.0
        sample_phase = 0
        while True:
            try:
                part = work_q.get_nowait()
            except queue.Empty:
                return
            perm = state.perm if adaptive else np.arange(n_preds)
            mask, work, _, _ = np_exec.run_chain_np(part, predicates, perm,
                                                    count_tiles=False)
            if adaptive:
                cut, _gcut, m, secs, _ = np_exec.run_monitor_np(
                    part, predicates, cfg.collect_rate, sample_phase)
                num_cut += cut
                if cost_mode == "measured":
                    cost_acc += secs
                else:
                    cost_acc += np.array(
                        [p.static_cost for p in predicates]) * m
                n_mon += m
            sample_phase = (sample_phase + part.shape[1]) % cfg.collect_rate
            with totals_lock:
                totals["work"] += work
                totals["rows"] += part.shape[1]
                totals["passed"] += int(mask.sum())
                state.rows_seen += part.shape[1]
                crossed = state.rows_seen >= state.next_boundary
                if crossed:
                    state.next_boundary += cfg.calculate_rate
            if adaptive and crossed:
                if state.try_epoch_update(num_cut, cost_acc, n_mon):
                    num_cut[:] = 0.0
                    cost_acc[:] = 0.0
                    n_mon = 0.0
                # else: deferred — metrics kept, retried next boundary

    t0 = time.perf_counter()
    threads = [threading.Thread(target=task_loop) for _ in range(n_tasks)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0

    return SimResult(
        total_work_units=totals["work"],
        wall_seconds=wall,
        rows_processed=totals["rows"],
        rows_passed=totals["passed"],
        epochs=state.epoch,
        deferred_updates=state.deferred_updates,
        final_perm=[int(i) for i in state.perm],
        perm_history=state.perm_history,
    )
