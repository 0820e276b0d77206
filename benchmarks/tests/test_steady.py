"""The dense cells' rate comes from the median time between completions, so
that a stall in a few steps cannot move it (the driver's first check read a
10% spread in one set of ``seq2k-b4`` runs whose steps repeat to 0.005%);
what the median leaves out is a per-layer metric of its own."""

import numpy as np
import pytest

from benchmarks import harness

STEP = 0.4232


def completions(stalls=(), jitter=0.0, n=70, seed=0):
    """Completion times of ``n`` steps of ``STEP`` seconds as the host sees
    them: ``stalls`` maps a step to seconds the device waited before it,
    ``jitter`` is how late the host may notice a completion."""
    gaps = np.full(n, STEP)
    for k, extra in dict(stalls).items():
        gaps[k] += extra
    late = np.random.RandomState(seed).uniform(0, jitter, size=n + 1)
    return np.concatenate([[0.0], np.cumsum(gaps)]) + late


@pytest.fixture(scope="module")
def lm_train():
    return harness.load_module((harness.HERE,), "runners", "lm_train")


@pytest.mark.parametrize("stalls", [{}, {7: 2.1}, {3: 0.4, 20: 0.9, 41: 0.8},
                                    {k: 0.2 for k in range(10, 25)}],
                         ids=["quiet", "one-stall", "three-stalls",
                              "fifteen-slow-steps"])
def test_a_stall_moves_the_mean_and_not_the_median(lm_train, stalls):
    s = lm_train.step_seconds(completions(stalls))
    assert s["median"] == pytest.approx(STEP, rel=1e-9)
    assert s["mean"] == pytest.approx(STEP + sum(stalls.values()) / 70)
    assert s["max"] == pytest.approx(STEP + max(stalls.values(), default=0))


def test_a_late_look_at_the_clock_does_not_move_the_median(lm_train):
    s = lm_train.step_seconds(completions(jitter=2e-3, seed=3))
    assert s["median"] == pytest.approx(STEP, rel=2e-3)
    assert s["q25"] <= s["median"] <= s["q75"] <= s["max"]


def test_every_slower_step_moves_the_median(lm_train):
    """A real loss is every step's, and shows in full."""
    slow = lm_train.step_seconds(completions({k: 0.01 * STEP
                                              for k in range(70)}))
    assert slow["median"] == pytest.approx(1.01 * STEP)


def test_the_readers_take_the_median_and_show_what_it_leaves_out(lm_train):
    readers = harness.layer_readers((harness.HERE,))
    s = lm_train.step_seconds(completions({7: 2.1}))
    facts = {"runner": "lm_train", "chips": 1, "flops_per_step": 5e13,
             "step_s": s["median"], "step_s_mean": s["mean"]}
    reading = harness.Reading(facts=facts, trace=None, compiles_in_window=0,
                              peaks={"bf16_flops_per_s": 197e12})
    assert readers["model.mfu_pct"].read(reading) == pytest.approx(
        100 * 5e13 / STEP / 197e12)
    assert readers["host.stall_share"].read(reading) == pytest.approx(
        100 * (1 - STEP / (STEP + 2.1 / 70)))
    quiet = harness.Reading(facts=dict(facts, step_s_mean=s["median"]),
                            trace=None, peaks={}, compiles_in_window=0)
    assert readers["host.stall_share"].read(quiet) == 0.0
    assert readers["model.mfu_pct"].read(quiet) is None      # no peaks: off-chip
    assert readers["host.stall_share"].read(harness.Reading(
        facts={}, trace=None, peaks={}, compiles_in_window=0)) is None
