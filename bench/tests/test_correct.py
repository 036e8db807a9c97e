"""The comparison that decides ``correct``, shown to fail, on the CPU.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Each cell runs at a size a test run can hold, through the harness's own
set-up, window and check (``bench/run.py``), with the look for a chip
skipped. Three things are shown per cell, against the limits in the
configuration's file:

* the program's readings pass;
* the control, the plain reference with its products at three bf16 passes
  in the program's place (``bench/control.py``), fails: the three passes
  are written out in bfloat16 (``bench.reference.gram.tn``), so a CPU
  computes them as the chip's ``Precision.HIGH`` does;
* the timed path with an answer altered where it is produced (one entry of
  one answer off by 1%) comes out not correct;
* in a least-squares cell, the timed path with its ridge left out comes out
  not correct.

The other faults the contract names (a training step that returns its
state, half of a batch left out of a mean, the exchange between chips left
out) cannot occur in these cells: none trains or spans chips.
"""

import time

import jax
import pytest

from bench import cells, control, run

SIZES = {
    "gram-f32.single_32768x8192": {"shape": [1024, 1536]},
    "gram-f32.blocks_480x1024": {"shape": [4, 256, 256]},
    "lstsq-f32.tall_65536x4096": {"shape": [2048, 512], "ridge": 2.048},
}
SEED = 2**33 + 12345


def _small(name):
    spec = cells.resolve(name)
    spec.traffic = dict(spec.traffic, **SIZES[name])
    return spec


def _limit(spec, name):
    limit = spec.config["limits"][name]
    assert limit is not None, f"no limit set for {name}"
    return limit


CELLS = list(SIZES)


@pytest.mark.parametrize("name", CELLS)
def test_program_passes_and_control_fails(name):
    spec = _small(name)
    prog = control.readings(spec, SEED, 0.3, "program")
    ctrl = control.readings(spec, SEED, 0.3, "control")
    assert "lost" not in prog
    for key, value in prog.items():
        limit = _limit(spec, key)
        assert value <= limit, (key, value, limit)
        assert ctrl[key] > limit, (key, ctrl[key], limit)


def _alter(x):
    """One entry of the answer off by 1%, where it is produced."""
    return x.at[(0,) * x.ndim].multiply(1.01)


@pytest.mark.parametrize("name", CELLS)
def test_an_altered_answer_is_not_correct(name, monkeypatch):
    spec = _small(name)
    program = spec.entry.program

    def broken(plan, config, traffic):
        fn = program(plan, config, traffic)
        return jax.jit(lambda *ops: jax.tree_util.tree_map(_alter, fn(*ops)))

    monkeypatch.setattr(spec.entry, "program", broken)
    _assert_not_correct(spec)


@pytest.mark.parametrize("name", [n for n in CELLS if "ridge" in SIZES[n]])
def test_a_dropped_ridge_is_not_correct(name, monkeypatch):
    """The program solves without its ridge; the reference keeps it."""
    spec = _small(name)
    program = spec.entry.program
    monkeypatch.setattr(spec.entry, "program", lambda plan, config, traffic:
                        program(plan, config, dict(traffic, ridge=0.0)))
    _assert_not_correct(spec)


def _assert_not_correct(spec):
    out = run.run_cell(spec, SEED, 0.3, False, devices=jax.devices(),
                       start=time.time())
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("name", CELLS)
def test_a_sound_run_is_correct(name):
    spec = _small(name)
    out = run.run_cell(spec, SEED + 1, 0.3, False, devices=jax.devices(),
                       start=time.time())
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in spec.end_to_end}
    assert all(v["value"] > 0 for v in out["metrics"].values())
