"""LR schedule tests (role of reference tests/unit/test_lr_schedulers.py:527)."""

import numpy as np
import pytest

from deepspeed_tpu.runtime.lr_schedules import (LRRangeTest, OneCycle,
                                                WarmupDecayLR, WarmupLR,
                                                get_lr_schedule)


def test_warmup_lr():
    s = WarmupLR(warmup_min_lr=0.0, warmup_max_lr=0.01, warmup_num_steps=10)
    assert float(s.lr_at(0)) == 0.0
    np.testing.assert_allclose(float(s.lr_at(5)), 0.005)
    np.testing.assert_allclose(float(s.lr_at(10)), 0.01)
    np.testing.assert_allclose(float(s.lr_at(100)), 0.01)


def test_warmup_decay_lr():
    s = WarmupDecayLR(total_num_steps=100, warmup_min_lr=0.0,
                      warmup_max_lr=0.01, warmup_num_steps=10)
    np.testing.assert_allclose(float(s.lr_at(5)), 0.005)
    np.testing.assert_allclose(float(s.lr_at(10)), 0.01)
    np.testing.assert_allclose(float(s.lr_at(55)), 0.005)
    np.testing.assert_allclose(float(s.lr_at(100)), 0.0, atol=1e-9)


def test_lr_range_test():
    s = LRRangeTest(lr_range_test_min_lr=1e-4, lr_range_test_step_size=10,
                    lr_range_test_step_rate=1.0)
    np.testing.assert_allclose(float(s.lr_at(0)), 1e-4)
    np.testing.assert_allclose(float(s.lr_at(10)), 2e-4)
    s2 = LRRangeTest(lr_range_test_min_lr=1e-4, lr_range_test_step_size=10,
                     lr_range_test_step_rate=1.0, lr_range_test_staircase=True)
    np.testing.assert_allclose(float(s2.lr_at(9)), 1e-4)
    np.testing.assert_allclose(float(s2.lr_at(10)), 2e-4)


def test_one_cycle():
    s = OneCycle(cycle_min_lr=0.001, cycle_max_lr=0.01,
                 cycle_first_step_size=10)
    np.testing.assert_allclose(float(s.lr_at(0)), 0.001)
    np.testing.assert_allclose(float(s.lr_at(10)), 0.01)
    np.testing.assert_allclose(float(s.lr_at(20)), 0.001)
    # decay phase
    s2 = OneCycle(cycle_min_lr=0.001, cycle_max_lr=0.01,
                  cycle_first_step_size=10, decay_lr_rate=0.1,
                  decay_step_size=5)
    assert float(s2.lr_at(30)) < 0.001


def test_one_cycle_momentum():
    s = OneCycle(cycle_min_lr=0.001, cycle_max_lr=0.01,
                 cycle_first_step_size=10, cycle_momentum=True,
                 cycle_min_mom=0.8, cycle_max_mom=0.9)
    np.testing.assert_allclose(float(s.mom_at(0)), 0.9)
    np.testing.assert_allclose(float(s.mom_at(10)), 0.8)
    np.testing.assert_allclose(float(s.mom_at(20)), 0.9)


def test_get_lr_schedule_dispatch():
    s = get_lr_schedule("WarmupLR", {"warmup_max_lr": 0.1})
    assert isinstance(s, WarmupLR)
    with pytest.raises(ValueError):
        get_lr_schedule("Nope", {})


def test_step_protocol_and_state_dict():
    s = WarmupLR(warmup_max_lr=0.01, warmup_num_steps=10)
    for _ in range(5):
        s.step()
    assert s.last_batch_iteration == 4
    sd = s.state_dict()
    s2 = WarmupLR(warmup_max_lr=0.01, warmup_num_steps=10)
    s2.load_state_dict(sd)
    assert s2.last_batch_iteration == 4


def test_step_reads_nothing_from_the_device(monkeypatch):
    """``step()`` runs once an optimizer step on the engine's hot path: it
    counts, and the rate is evaluated when someone asks for it."""
    s = WarmupLR(warmup_max_lr=0.01, warmup_num_steps=10)
    calls = []
    lr_at = s.lr_at
    monkeypatch.setattr(s, "lr_at", lambda step: calls.append(1) or lr_at(step))
    for _ in range(5):
        s.step()
    assert not calls
    assert s.get_last_lr() == [pytest.approx(0.004)]
    assert s.get_last_lr() == s.get_lr() and len(calls) == 3
