"""The comparison that decides ``correct``: sound runs pass it, and each
fault a cell can have, planted under the timed path, fails it."""

import json
import subprocess
import sys

import numpy as np
import pytest
from conftest import CELLS, ROOT, run_small

from bench import program
from bench.control import ControlSession


def wrapped(fault):
    """A session factory whose ``step`` is the program's, broken by
    ``fault(real_step, state, batch) -> (state, result)``."""
    def factory(config):
        session = program.session(config)
        real = session.step
        session.step = lambda state, batch: fault(real, state, batch)
        return session
    return factory


def state_unchanged(real, state, batch):
    _, res = real(state, batch)
    return state, res


def half_left_out(real, state, batch):
    import jax.numpy as jnp

    cols = jnp.asarray(batch)
    half = cols.shape[1] // 2
    return real(state, cols.at[:, half:].set(jnp.nan))


def answer_altered(real, state, batch):
    state, res = real(state, batch)
    if res.packed is not None:
        return state, res._replace(packed=res.packed.at[-1, 0].add(1.0))
    return state, res._replace(mask=res.mask.at[0].set(~res.mask[0]))


def exchange_left_out(config):
    session = program.session(config)
    session.filter.maybe_exchange = lambda state: state
    return session


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(cell):
    out = run_small(cell)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert all(v["value"] == 0 for v in out["checks"].values())


FAULTS = [(c, f) for c in sorted(CELLS)
          for f in (state_unchanged, half_left_out, answer_altered)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_fault_is_not_correct(cell, fault):
    out = run_small(cell, session_factory=wrapped(fault))
    assert not out["correct"], out["checks"]


def test_exchange_left_out_is_not_correct():
    out = run_small("paper-log-4exec.drift",
                    session_factory=exchange_left_out)
    assert not out["correct"], out["checks"]
    assert out["checks"]["epoch_wrong"]["value"] > 0


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_bfloat16_control_is_not_correct(cell):
    out = run_small(cell, session_factory=ControlSession)
    assert not out["correct"], out["checks"]


def test_off_the_chip_exits_nonzero_without_a_result(capsys):
    from bench import run

    rc = run.main(["--workload", "paper-log.drift", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper-log.drift",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_every_cell_names_files_that_exist():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in bench["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    assert np.all([w["chips"] in (1, 4) for w in bench["workloads"]])
