"""The harness has to say ``correct: false`` when the timed path is broken
underneath it. Each case drives a whole run at the rehearsal size (the look
for a chip skipped) with one fault planted in the program's objects."""

import jax
import jax.numpy as jnp
import pytest

from benchmarks import run

CELLS = ["gpt2-124m.silo4", "femnist-cnn.c200", "femnist-cnn.c10"]


def state_unchanged(api):
    real = api.round_fn

    def broken(global_vars, *args, **kw):
        keep = jax.tree_util.tree_map(jnp.copy, global_vars)
        _, metrics = real(global_vars, *args, **kw)
        return keep, metrics

    for attr in ("supports_may_pad", "variant_for"):
        if hasattr(real, attr):
            setattr(broken, attr, getattr(real, attr))
    api.round_fn = broken


def half_batch_left_out(api):
    real = api._place_batch

    def broken(batch, rng):
        x, y, mask, ns, keys = real(batch, rng)
        return x, y, mask.at[..., mask.shape[-1] // 2:].set(0.0), ns, keys

    api._place_batch = broken


def answer_altered(api):
    real = api.eval_fn

    def broken(variables, *batches):
        out = dict(real(variables, *batches))
        out["loss_sum"] = out["loss_sum"] * 1.05
        return out

    api.eval_fn = broken


def measure(cell, sabotage=None):
    return run.measure(
        ["--workload", cell, "--seed", "2147483659", "--seconds", "1", "--rehearse"],
        sabotage=sabotage,
    )


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct(cell):
    out = measure(cell)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["compiles_in_window"] == 0


@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out, answer_altered])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_is_not_correct(cell, fault):
    out = measure(cell, sabotage=fault)
    assert out["correct"] is False, out["compared"]
