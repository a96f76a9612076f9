"""What the drivers share: the window's bookkeeping and the host spans."""

from __future__ import annotations

import dataclasses
import importlib
import random

from bench import reference as ref


def annotate(name: str):
    """A host span in the profiler's trace (free when nothing traces)."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


def data_module(name: str):
    return importlib.import_module(f"bench.data.{name}")


@dataclasses.dataclass
class Window:
    """What one measured window produced."""

    seconds: float = 0.0        # its length on the host clock
    rows_done: int = 0          # rows whose results were complete inside it
    latencies_s: list = dataclasses.field(default_factory=list)
    latency_kind: str = ""      # "batch" | "query"
    steps: int = 0              # session.step calls submitted inside it
    attempted: int = 0          # batches or queries started inside it
    first_step: int = 0         # global index of its first step


class Reservoir:
    """A uniform sample, drawn from the seed, of up to ``k`` items from a
    stream of unknown length (Algorithm R)."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = random.Random(seed)
        self.n = 0
        self.items: list = []

    def offer(self, item) -> None:
        self.n += 1
        if len(self.items) < self.k:
            self.items.append(item)
            return
        j = self.rng.randrange(self.n)
        if j < self.k:
            self.items[j] = item


def ordering_checks(config: dict, local_rows: int, shards: int,
                    steps: list, cuts_of) -> dict:
    """Replay the paper's ordering over every step the session took and
    count the steps whose adopted order or epoch disagrees.

    ``steps``: host records with ``perm`` (the order the step ran) and
    ``epoch`` (epochs completed after it); ``cuts_of(k, start, stride)``
    gives step k's monitor counts summed over executors.
    """
    order = ref.Ordering(config["predicates"], config["ordering"])
    misordered = epoch_wrong = 0
    for k, rec in enumerate(steps):
        if ref.misordered(rec["perm"], order.adj):
            misordered += 1
        cuts, n_mon = cuts_of(k, order.sample_start(), order.collect)
        order.fold(cuts, n_mon, local_rows)
        epochs = set(int(e) for e in rec["epoch"].reshape(-1))
        if epochs != {order.epoch}:
            epoch_wrong += 1
    return {"perm_misordered": misordered, "epoch_wrong": epoch_wrong}


