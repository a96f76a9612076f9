"""The paper's synthetic log stream (arXiv:1905.01349 §5), made on the host.

A copy of the arithmetic of ``repro.data.stream.gen_batch``, kept here so
that a change to the program cannot change the benchmark's data:

  column 0  date      ~ N(500, 100)
  column 1  int       ~ N(50, 15)
  column 2  str_hash  ~ U[0, 2^20)

Each block of rows is drawn from its own Philox stream keyed by
``(seed, block_index)``, so a block depends on nothing but those two
numbers. Drift ("regime") moves the column means by ``amplitude`` standard
deviations, with the sign set by the block's middle row. Rows stay in
generator order (``gen_batch``'s ``layout="iid"``).
"""

from __future__ import annotations

import math

import numpy as np

DATE = (500.0, 100.0)
INT = (50.0, 15.0)
STR_MOD = 1048576.0


def drift_shift(drift: dict, row_mid: float) -> tuple[float, float, float]:
    """Mean shifts of (date in std, int in std, str_hash as a fraction)."""
    kind = drift.get("kind", "none")
    if kind == "none":
        return 0.0, 0.0, 0.0
    phase = row_mid / drift["period_rows"]
    amp = drift["amplitude"]
    if kind == "sine":
        return (amp * math.sin(2 * math.pi * phase),
                -amp * math.sin(2 * math.pi * phase + 2.0),
                0.25 * math.sin(2 * math.pi * phase + 4.0))
    if kind == "regime":
        sign = 1.0 if int(phase) % 2 == 0 else -1.0
        return amp * sign, -amp * sign, 0.2 * sign
    raise ValueError(f"unknown drift kind {kind!r}")


def block(seed: int, index: int, rows: int, drift: dict) -> np.ndarray:
    """Rows [index·rows, (index+1)·rows) of the stream as f32[3, rows]."""
    rng = np.random.Generator(np.random.Philox(key=[seed, index]))
    d_shift, i_shift, s_shift = drift_shift(drift, index * rows + rows / 2)
    date = rng.normal(DATE[0] + d_shift * DATE[1], DATE[1], rows)
    intc = rng.normal(INT[0] + i_shift * INT[1], INT[1], rows)
    strh = (rng.integers(0, int(STR_MOD), rows).astype(np.float64)
            + s_shift * STR_MOD) % STR_MOD
    return np.stack([date, intc, strh]).astype(np.float32)


def host_ring(seed: int, config: dict, traffic: dict) -> list[np.ndarray]:
    """The replayed ring of step inputs, each f32[3, shards·batch_rows].

    ``config["ring_blocks"]`` distinct blocks of ``batch_rows`` rows; a
    step of an S-shard plan takes S consecutive blocks side by side, shard
    i owning block i.
    """
    rows = config["batch_rows"]
    shards = config["plan"].get("shards", 1)
    n_blocks = config["ring_blocks"]
    if n_blocks % shards:
        raise ValueError(f"ring_blocks {n_blocks} is not a multiple of "
                         f"{shards} shards")
    blocks = [block(seed, b, rows, traffic["drift"]) for b in range(n_blocks)]
    return [np.concatenate(blocks[s:s + shards], axis=1)
            for s in range(0, n_blocks, shards)]
