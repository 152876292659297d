"""A whole run on the CPU at the -smoke sizes, past the look for a chip:
sound, it comes out correct; with a served token altered where the
decode step produces it, it comes out not correct."""
import time

import jax
import pytest

from benchmarks.onchip import driver
from benchmarks.onchip.tests.smoke import smoke_cell


def _run(cell, hook=None, seed=2**31 + 11):
    return driver.run_cell(cell, seed, 1.5, 0, t_start=time.perf_counter(),
                           devices=jax.devices(), engine_hook=hook)


@pytest.mark.parametrize("name", ["smollm360m.chat", "mingru360m.longdoc"])
def test_sound_run_is_correct(name):
    cell = smoke_cell(name)
    r = _run(cell)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert r["checks"]["max_logit_gap"]["value"] < 1e-3
    assert r["compiles_in_window"] == 0


@pytest.mark.parametrize("name", ["smollm360m.chat", "mingru360m.longdoc"])
def test_altered_token_is_not_correct(name):
    cell = smoke_cell(name)
    vocab = cell.config["sizes"]["vocab"]

    def alter(eng):
        step = eng.sm.step

        def bad(*a, **k):
            out, state = step(*a, **k)
            return (out + 1) % vocab, state
        eng.sm.step = bad

    r = _run(cell, alter)
    assert not r["correct"]
    assert r["checks"]["max_logit_gap"]["value"] > \
        r["checks"]["max_logit_gap"]["limit"]
