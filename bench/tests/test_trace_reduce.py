"""The trace reduction on a small trace recorded on one TPU v5 lite: the
stream driver at 2^16 rows per step inside a ``bench.window`` span."""

import pytest
from conftest import ROOT

from bench import trace_reduce

TRACE = ROOT / "bench" / "tests" / "data" / "stream_small.xplane.pb.gz"


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    import gzip

    path = tmp_path_factory.mktemp("trace") / "stream_small.xplane.pb"
    path.write_bytes(gzip.decompress(TRACE.read_bytes()))
    return trace_reduce.reduce(str(path), 1)


def test_window_and_busy(summary):
    assert 0.0 < summary.busy_s < summary.window_s
    assert summary.window_s == pytest.approx(
        summary.busy_s + sum(g for g, _ in summary.gaps), rel=1e-6)


def test_kernels_by_stable_name(summary):
    chain = summary.kernel_s("adaptive_filter_chain")
    assert chain is not None and chain > 0
    assert summary.kernel_s("adaptive_filter_tile_stats") is None
    assert summary.collective_s() is None
    names = [n for n, _ in summary.breakdown["device_ops"]]
    assert "adaptive_filter_chain" in names
    assert len(names) <= trace_reduce.TOP


def test_transfers_and_gaps(summary):
    assert summary.h2d_count > 0 and summary.h2d_s > 0
    gaps = dict(summary.breakdown["idle_gaps"])
    assert set(gaps) <= {"bench.submit", "bench.readback", "other"}
    assert sum(gaps.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-6)


def test_op_names():
    assert trace_reduce.op_name(
        "%adaptive_filter_chain_skip.1 = (s8[2048]) custom-call(...)") \
        == "adaptive_filter_chain_skip"
    assert trace_reduce.op_name("%all-reduce.12 = f32[4] all-reduce(x)") \
        == "all-reduce"
    assert trace_reduce.op_name("%fusion.3.1 = f32[4] fusion(x)") == "fusion"
    assert trace_reduce.op_name("jit_step(123)") == "jit_step"


def test_union_length():
    assert trace_reduce.union_length([(0, 2), (1, 3), (5, 6)]) == 4
