"""CPU tests of the benchmark: ``python -m pytest bench/tests``.

Four virtual CPU devices stand in for the four-chip cell's mesh; the
Pallas kernels run in interpret mode. Set before JAX is imported.
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: each configuration cut to a size the CPU runs in a second, with epochs
#: short enough that every step closes one
SMALL = {
    "paper-log-stream": {
        "batch_rows": 4096, "ring_blocks": 2,
        "ordering": {"collect_rate": 64, "calculate_rate": 4096,
                     "momentum": 0.3}},
    "paper-log-4exec": {
        "batch_rows": 2048, "ring_blocks": 8,
        "ordering": {"collect_rate": 64, "calculate_rate": 2048,
                     "momentum": 0.3}},
    "tpch-q6-lineitem": {
        "partition_rows": 8192, "partitions": 6,
        "plan": {"engine": "pallas", "compact": True, "capacity": 8192,
                 "skip_tier": "zonemap"},
        "ordering": {"collect_rate": 64, "calculate_rate": 8192,
                     "momentum": 0.3}},
}
CELLS = {"paper-log.drift": "paper-log-stream",
         "tpch-q6.shipdate-sorted": "tpch-q6-lineitem",
         "tpch-q6.orderkey-order": "tpch-q6-lineitem",
         "paper-log-4exec.drift": "paper-log-4exec"}


def run_small(cell: str, session_factory=None, seed: int = 2 ** 33 + 17,
              seconds: float = 0.5) -> dict:
    """One run of ``cell`` on the CPU at its small size."""
    import argparse

    from bench import run

    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=0)
    return run.run_cell(args, require_chip=False,
                        config_override=SMALL[CELLS[cell]],
                        session_factory=session_factory)
