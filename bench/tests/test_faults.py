"""The harness's correctness check, driven end to end at a tiny size with
everything but the look for a chip: a sound program comes out correct;
each fault a training cell can have, planted under the timed path, and
the control (the reference in bfloat16 put in the program's place) come
out not correct against the stand-ins' limits (``tiny.TINY_LIMITS``)."""
import time

import jax
import jax.numpy as jnp
import pytest
from lib import harness

import tiny

CELLS = ["smollm360m.silo", "femnist_cnn.xdev", "smollm360m.xdev_int8"]


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_cache", lambda: "off")


def _run(name, plant=None, seed=5):
    cell = tiny.tiny_cell(name)
    return harness.run_cell(cell, seed, 0.2, False,
                            t_start=time.monotonic(), require_tpu=False,
                            plant=plant, log=lambda m: None)


def _unchanged(fn):
    """A step that returns its state unchanged (the metrics still come
    from a real step on a copy)."""
    def step(state, *args):
        _, metrics = fn(jax.tree.map(jnp.copy, state), *args)
        return state, metrics
    return step


def _half_cohort(fn):
    """Half of the round's clients left out, the mean over the rest."""
    def step(state, cohort_batch, meta_batch, weights, rng):
        c = max(weights.shape[0] // 2, 1)
        return fn(state, jax.tree.map(lambda x: x[:c], cohort_batch),
                  meta_batch, weights[:c], rng)
    return step


@pytest.mark.parametrize("name", CELLS)
def test_sound_program_is_correct(name):
    res = _run(name)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_unset_limit_is_not_correct(name):
    """A number with no limit has nothing to hold it: the run fails."""
    cell = tiny.tiny_cell(name)
    cell.traffic = {**cell.traffic, "limits": {
        **cell.traffic["limits"], "grad_gap": None}}
    res = harness.run_cell(cell, 5, 0.2, False, t_start=time.monotonic(),
                           require_tpu=False, log=lambda m: None)
    assert res["checks"]["grad_gap"]["limit"] is None
    assert not res["correct"]


def _fails(numbers, limits):
    return any(numbers[k] > limits[k] for k in harness.CHECKS)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [_unchanged, _half_cohort],
                         ids=["state_unchanged", "half_cohort"])
def test_planted_fault_is_not_correct(name, fault):
    res = _run(name, plant=harness.Plant(wrap_round=fault))
    numbers = {k: c["value"] for k, c in res["checks"].items()}
    limits = {k: c["limit"] for k, c in res["checks"].items()}
    assert _fails(numbers, limits), (numbers, limits)
    assert not res["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    """The control is read the way a run reads the program: compared with
    the float32 reference by the cell's numbers and limits."""
    cell = tiny.tiny_cell(name)
    seed = 5
    arrays, parts, meta = cell.builder.make_data(cell.cfg, cell.traffic,
                                                 seed)
    data = harness.make_recording_data(arrays, parts, meta, seed,
                                       harness.CHECK_ROUNDS)
    import repro.core  # noqa: F401
    trainer, _, init, key, abstract = harness.build_program(cell, seed)
    prog = harness.check_rounds(cell, trainer, data, init, key, abstract)
    rounds = [data.fed_rounds[r] for r in range(harness.CHECK_ROUNDS)]
    ref = harness.reference_readings(cell, init(key), rounds)
    ctl = harness.reference_readings(cell, init(key), rounds,
                                     dtype=jnp.bfloat16, precision="default")
    numbers = harness.compare(ctl, ref)
    sound = harness.compare(prog, ref)
    assert _fails(numbers, cell.traffic["limits"]), (numbers, sound)
    assert not _fails(sound, cell.traffic["limits"]), sound


def test_round_program_text_is_read_back():
    """A traced run reads the compiled text of the round program it drove,
    lowered from the state and inputs of its second round."""
    cell = tiny.tiny_cell("smollm360m.silo")
    import repro.core  # noqa: F401
    arrays, parts, meta = cell.builder.make_data(cell.cfg, cell.traffic, 5)
    data = harness.make_recording_data(arrays, parts, meta, 5,
                                       harness.CHECK_ROUNDS)
    trainer, _, init, key, abstract = harness.build_program(cell, 5)
    programs = harness.capture_programs(trainer)
    harness.check_rounds(cell, trainer, data, init, key, abstract)
    assert list(programs) == [1]
    assert "ENTRY" in harness.program_text(programs)
