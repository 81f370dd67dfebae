"""A grad program whose launch stalls on the host twice in a row makes
``forward`` wait for the loss before it launches the next one
(``DeepSpeedEngine._note_grad_launch``); an engine whose launches do not
stall never waits, and the trajectory is the same either way."""

import time

import jax
import pytest

from tests.unit.test_engine import make_engine, train_steps


def _slow_grad_launches(engine, monkeypatch, seconds):
    launch = engine._launch

    def slow(fn, *args, **kwargs):
        if fn is engine._grad_fn and fn in engine._launched:
            time.sleep(seconds)
        return launch(fn, *args, **kwargs)

    monkeypatch.setattr(engine, "_launch", slow)


@pytest.mark.parametrize("stall, waits", [(0.0, False), (0.05, True)])
def test_two_stalled_launches_make_forward_wait_for_the_loss(
        monkeypatch, stall, waits):
    engine = make_engine()
    _slow_grad_launches(engine, monkeypatch, stall)
    awaited = []
    block = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: (awaited.append(x), block(x))[1])
    losses = train_steps(engine, n=5)
    assert engine._await_loss_before_launch is waits
    # the compiling launch is not counted, the next two are: from the
    # fourth forward on the last loss is awaited before the launch
    assert len(awaited) == (2 if waits else 0)
    assert losses == train_steps(make_engine(), n=5)


def test_one_stalled_launch_between_quick_ones_changes_nothing(monkeypatch):
    engine = make_engine()
    for seconds in (0.0, 0.05, 0.0, 0.05, 0.0):
        engine._note_grad_launch(True, seconds)
    engine._note_grad_launch(False, 9.0)     # a compiling launch
    assert not engine._await_loss_before_launch
