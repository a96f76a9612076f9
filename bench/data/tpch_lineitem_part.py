"""TPC-H ``lineitem ⋈ part`` for Q19, pre-joined on ``p_partkey =
l_partkey`` and made on the device from the seed.

Columns, in this order, with dbgen's distributions (TPC-H v3, §4.2.3);
the last three are the part's, drawn once per ``p_partkey`` from the seed,
so every line of one part carries the same values, as the join gives:

  0  l_quantity       U{1..50}
  1  l_extendedprice  l_quantity × p_retailprice(l_partkey), in cents, with
                      l_partkey U{1..SF·200,000} (as ``tpch_lineitem``)
  2  l_discount       U{0..10} hundredths
  3  l_shipinstruct   code of 4 instructions, uniform
  4  l_shipmode       code of 7 modes, uniform
  5  p_brand          Brand#MN, M and N each U{1..5}: code 5·(M-1) + (N-1)
  6  p_container      5 sizes × 8 kinds, uniform: code 8·size + kind
  7  p_size           U{1..50}

The dictionaries are the configuration's ``dictionaries``. Every value is
a whole number below 2^24, so f32 holds it exactly. No Q19 column depends
on the order date, so dbgen's orderkey order (an order's 1..7 lines side
by side) is rows independent of one another here.

The part attributes are one table per attribute over every partkey, made
once per run; each partition is one call of one compiled program keyed by
``(seed, partition)`` that draws the lines and gathers their part's
attributes.
"""

from __future__ import annotations

from bench.data.tpch_lineitem import _key

N_INSTRUCTS = 4
N_MODES = 7
N_BRANDS = 25
N_CONTAINERS = 40
PART_STREAM = 0x9A47      # fold-in tag of the part tables' key


def part_tables(seed: int, max_partkey: int):
    """(brand, container, size) i32[max_partkey + 1] device tables, index
    = p_partkey (entry 0 unused)."""
    import jax

    k_brand, k_cont, k_size = jax.random.split(
        jax.random.fold_in(_key(seed), PART_STREAM), 3)
    shape = (max_partkey + 1,)
    return (jax.random.randint(k_brand, shape, 0, N_BRANDS),
            jax.random.randint(k_cont, shape, 0, N_CONTAINERS),
            jax.random.randint(k_size, shape, 1, 51))


def _partition_fn(rows: int, max_partkey: int):
    import jax
    import jax.numpy as jnp

    def make(key, part, brand, container, size):
        """(f32[8, rows] partition, i32[rows] l_partkey)."""
        k_qty, k_disc, k_inst, k_mode, k_part = jax.random.split(
            jax.random.fold_in(key, part), 5)
        qty = jax.random.randint(k_qty, (rows,), 1, 51)
        disc = jax.random.randint(k_disc, (rows,), 0, 11)
        inst = jax.random.randint(k_inst, (rows,), 0, N_INSTRUCTS)
        mode = jax.random.randint(k_mode, (rows,), 0, N_MODES)
        pk = jax.random.randint(k_part, (rows,), 1, max_partkey + 1)
        cents = 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)
        cols = jnp.stack([qty, qty * cents, disc, inst, mode, brand[pk],
                          container[pk], size[pk]]).astype(jnp.float32)
        return cols, pk

    return jax.jit(make)


def partitions(seed: int, config: dict, parts):
    """Yields (partition, l_partkey) for each partition number in
    ``parts``, one at a time."""
    max_partkey = config["scale_factor"] * 200_000
    tables = part_tables(seed, max_partkey)
    make = _partition_fn(config["partition_rows"], max_partkey)
    key = _key(seed)
    for p in parts:
        yield make(key, p, *tables)


def device_table(seed: int, config: dict, traffic: dict) -> list:
    """The cached table: one f32[8, partition_rows] device array per
    partition."""
    if traffic["order"] != "orderkey":
        raise ValueError(f"lineitem ⋈ part comes in orderkey order only, "
                         f"not {traffic['order']!r}")
    return [cols for cols, _ in partitions(seed, config,
                                           range(config["partitions"]))]
