"""TPC-H ``lineitem`` columns for Q6, made on the device from the seed.

Columns, in this order, with dbgen's distributions (TPC-H v3, §4.2.3):

  0  l_shipdate       o_orderdate + U{1..121} days, o_orderdate uniform on
                      [1992-01-01, 1998-08-02]; day numbers since 1970-01-01
  1  l_discount       U{0..10} hundredths
  2  l_quantity       U{1..50}
  3  l_extendedprice  l_quantity × p_retailprice(p_partkey), in cents, with
                      p_partkey U{1..SF·200,000} and
                      p_retailprice = 90000 + (partkey/10 mod 20001)
                                      + 100·(partkey mod 1000) cents

Every value is a whole number below 2^24, so f32 holds it exactly.

Two physical orders of the same distributions (``traffic["order"]``):

  orderkey  dbgen's own: each order's 1..7 lines side by side, sharing one
            orderdate, orders one after another, so ship dates scatter
            at the scale of a 128-row tile;
  shipdate  the table sorted by ship date, as a date-clustered warehouse
            table holds it. The day histogram is drawn on the host from the
            seed (a multinomial over the ship-date distribution) and each
            row's day is read off its cumulative counts; the other columns
            are independent of the date in dbgen, so they are drawn per
            row as in the other order.

Each partition is one call of one compiled program, keyed by
``(seed, partition)``, so set-up compiles it once.
"""

from __future__ import annotations

import numpy as np

ORDER_FIRST = 8035        # 1992-01-01
ORDER_LAST = 10440        # 1998-08-02 (ENDDATE - 151 days)
SHIP_LAG = (1, 121)
LINES_PER_ORDER = (1, 7)


def _key(seed: int):
    import jax

    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def ship_day_probs() -> tuple[int, np.ndarray]:
    """First ship day and P(ship day = first + i): uniform orderdate
    convolved with the uniform 1..121-day lag."""
    n_order = ORDER_LAST - ORDER_FIRST + 1
    n_lag = SHIP_LAG[1] - SHIP_LAG[0] + 1
    p = np.convolve(np.ones(n_order), np.ones(n_lag)) / (n_order * n_lag)
    return ORDER_FIRST + SHIP_LAG[0], p


def ship_day_ends(seed: int, total_rows: int) -> tuple[int, np.ndarray]:
    """Cumulative row counts per ship day of a shipdate-sorted table."""
    first, p = ship_day_probs()
    rng = np.random.Generator(np.random.Philox(key=[seed, 0x51D]))
    counts = rng.multinomial(total_rows, p)
    return first, np.cumsum(counts).astype(np.int64)


def _partition_fn(rows: int, order: str, max_partkey: int):
    import jax
    import jax.numpy as jnp

    def other_columns(key, shape):
        k_disc, k_qty, k_part = jax.random.split(key, 3)
        disc = jax.random.randint(k_disc, shape, 0, 11)
        qty = jax.random.randint(k_qty, shape, 1, 51)
        pk = jax.random.randint(k_part, shape, 1, max_partkey + 1)
        cents = 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)
        return disc, qty, qty * cents

    def make(key, part, ends, first_day):
        key = jax.random.fold_in(key, part)
        k_rows, k_date = jax.random.split(key)
        disc, qty, price = other_columns(k_rows, (rows,))
        if order == "shipdate":
            # day of row j = ship days whose cumulative count is <= the
            # row's global number: count the day ends before and inside
            local_end = ends - part * rows
            before = jnp.sum(local_end < 0)
            inside = jnp.where((local_end >= 0) & (local_end < rows),
                               local_end, rows)
            step = jnp.zeros(rows, jnp.int32).at[inside].add(1, mode="drop")
            ship = first_day + before + jnp.cumsum(step)
        else:
            k_lines, k_odate, k_lag = jax.random.split(k_date, 3)
            lines = jax.random.randint(k_lines, (rows,), LINES_PER_ORDER[0],
                                       LINES_PER_ORDER[1] + 1)
            start = jnp.cumsum(lines) - lines
            first = jnp.zeros(rows, jnp.int32).at[start].add(1, mode="drop")
            row_order = jnp.cumsum(first) - 1
            odate = jax.random.randint(k_odate, (rows,), ORDER_FIRST,
                                       ORDER_LAST + 1)
            lag = jax.random.randint(k_lag, (rows,), SHIP_LAG[0],
                                     SHIP_LAG[1] + 1)
            ship = odate[row_order] + lag
        return jnp.stack([ship, disc, qty, price]).astype(jnp.float32)

    return jax.jit(make)


def device_table(seed: int, config: dict, traffic: dict) -> list:
    """The cached table: one f32[4, partition_rows] device array per
    partition."""
    import jax.numpy as jnp

    rows = config["partition_rows"]
    n_parts = config["partitions"]
    order = traffic["order"]
    if order not in ("shipdate", "orderkey"):
        raise ValueError(f"unknown lineitem order {order!r}")
    first_day, ends = ship_day_ends(seed, rows * n_parts)
    if ends[-1] >= 2 ** 31:
        raise ValueError("table too large for int32 row numbers")
    make = _partition_fn(rows, order, config["scale_factor"] * 200_000)
    key = _key(seed)
    ends_d = jnp.asarray(ends.astype(np.int32))
    return [make(key, p, ends_d, first_day) for p in range(n_parts)]
