"""The control of ``correct`` at a size a test run can hold: the reference
put in the program's place and computed in float8, the nearest precision
below the bfloat16 the configurations state, has to come out as not
correct.  The readings that set the real cells' limits were taken on the
chip at the cells' own sizes (PERF.md section 2); the tiny cells carry
limits set the same way from CPU readings."""

from __future__ import annotations

import pytest

from helpers import tiny

TRAIN, SERVE = "train-gpt2m-1k", "serve-gpt2l-chat"


@pytest.fixture(scope="module")
def train_run():
    import jax

    from benchmarks.drivers import train

    _, cell, config = tiny("tiny-train", TRAIN)
    run = train.Run(cell, config, seed=1, seconds=1.0,
                    devices=jax.devices()[:1], note=lambda msg: None)
    run.build()
    return run


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_training_is_not_correct(train_run, seed):
    from benchmarks.drivers.train import compare

    run, limits = train_run, train_run.cell["limits"]
    run.seed_state(seed)
    ref = run.reference()
    control = compare(run.reference(mode="fp8"), ref)
    assert any(control[name] > limits[name] for name in limits), control
    # and the sound program is: bfloat16 compute against the reference
    got = run.first_chunk()
    run.trainer.state = None
    program = compare(got, ref)
    assert all(program[name] <= limits[name] for name in limits), program


@pytest.mark.parametrize("fault, number", [
    ("half_batch", "moment_norm_gap"),
    ("state_unchanged", "update_norm_gap"),
    ("first_batch_again", "update_norm_gap"),   # xs[0] fed to every step
    ("stale_weights", "moment_norm_gap")])      # a cast hoisted out of the scan
def test_planted_faults_read_far_over_the_limits(train_run, fault, number):
    from benchmarks.drivers.train import compare

    run, limits = train_run, train_run.cell["limits"]
    run.seed_state(4)
    reading = compare(run.reference(fault=fault), run.reference())[number]
    assert reading > 3 * limits[number]


@pytest.mark.parametrize("seed", [5, 8, 11])
def test_float8_serving_is_not_correct(seed):
    import jax

    from benchmarks.drivers import serve

    _, cell, config = tiny("tiny-serve", SERVE)
    run = serve.Run(cell, config, seed=seed, seconds=3.0,
                    devices=jax.devices()[:1], note=lambda msg: None)
    run.setup()
    obs = run.window()
    sample = run.sample()
    limit = cell["limits"]["token_logit_gap"]
    assert obs["failed"] == 0 and len(sample) == 6
    # the longest finished request is in the sample
    assert max(len(r["prompt"]) + len(t) for r, t in run.finished) == \
        len(sample[0][0]["prompt"]) + len(sample[0][1])
    run.free()
    assert run.gaps(sample)["token_logit_gap"] <= limit
    assert run.gaps(sample, mode="fp8")["token_logit_gap"] > limit
