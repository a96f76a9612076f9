"""Compile rehearsals: the Pallas filter kernels compiled for a described
TPU v5e at deployment widths, with no chip attached.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
misaligned blocks, scalar stores to VMEM, bool vectors through
``cond``/``select``, dynamic lane offsets, VMEM overuse. Each test here
lowers one launch of the main path for one v5e chip — 2^22 rows, the
paper chain's 3 columns, the guardrail chain's 4 and TPC-H Q19's tree of
AND-terms and IN atoms over 8 — and asserts the
compiled program holds the kernel as a ``tpu_custom_call``. The topology
is described inside the module fixture (never at import), and every
compile runs in the test's own process.
"""

import importlib

import pytest
from conftest import q19_filters

jax = pytest.importorskip("jax")
jnp = jax.numpy

ROWS = 1 << 22


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from a persistent
    # cache without the chip; keep such compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _kernels():
    # the package re-exports the jitted ``filter_chain`` under the module's
    # name; import the kernel module itself
    return importlib.import_module("repro.kernels.filter_chain.filter_chain")


def _chain(name):
    from repro.core import pack
    from repro.core.predicates import paper_filters_4
    from repro.serving.traffic import guardrail_chain

    if name == "q19":
        return pack(q19_filters())
    return pack(paper_filters_4("fig1") if name == "paper"
                else guardrail_chain())


# (chain, columns, fused compaction capacity or None, skip decisions)
LAUNCHES = {
    "paper": ("paper", 3, None, False),
    "guardrail-cnf": ("guardrail", 4, None, False),
    "paper-skip": ("paper", 3, None, True),
    "paper-compact": ("paper", 3, ROWS, False),
    "guardrail-compact-skip": ("guardrail", 4, ROWS // 4, True),
    "q19-tree-compact": ("q19", 8, ROWS, False),
}


@pytest.mark.parametrize("launch", sorted(LAUNCHES))
def test_chain_kernel_compiles_for_v5e(one_chip, launch):
    chain, n_cols, capacity, skip = LAUNCHES[launch]
    fc = _kernels()
    specs = _chain(chain)
    n_preds = int(specs.column.shape[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [sds((n_cols, ROWS), jnp.float32), sds((n_preds,), jnp.int32),
            sds((4,), jnp.int32)]
    if skip:
        args += [sds((ROWS // fc.STAT_TILE,), jnp.int32)] * 2

    def launch_fn(cols, perm, meta, *decisions):
        return fc.filter_chain_pallas(
            cols, specs, perm, meta, interpret=False,
            compact=capacity is not None, capacity=capacity or 0,
            skip_decisions=tuple(decisions) or None)

    text = jax.jit(launch_fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert ("adaptive_filter_chain_skip" if skip
            else "adaptive_filter_chain") in text


def test_stats_kernel_compiles_for_v5e(one_chip):
    fc = _kernels()
    cols = jax.ShapeDtypeStruct((3, ROWS), jnp.float32, sharding=one_chip)
    text = jax.jit(lambda c: fc.tile_stats_pallas(c, interpret=False)) \
        .lower(cols).compile().as_text()
    assert "tpu_custom_call" in text
    assert "adaptive_filter_tile_stats" in text
