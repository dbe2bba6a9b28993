"""The learning-rate schedule is one formula with two evaluations: `jnp`
inside the optimizer (traced into the step program) and numpy for the
driver's log line, which must not dispatch a device program (a read of
it would wait for the steps in flight). They agree to float32 rounding.
"""

import jax
import numpy as np
import pytest

from moco_tpu.utils.config import OptimConfig
from moco_tpu.utils.schedules import make_lr_schedule

SPE = 7  # steps per epoch
EPOCHS = 20
MILESTONES = (12, 16)
WARMUP = 2

# the steps where the formula changes branch or value
STEPS = {
    "step0": 0,
    "last_warmup_step": WARMUP * SPE - 1,
    "first_step_after_warmup": WARMUP * SPE,
    "before_epoch_boundary": 5 * SPE - 1,
    "after_epoch_boundary": 5 * SPE,
    "before_milestone_1": MILESTONES[0] * SPE - 1,
    "at_milestone_1": MILESTONES[0] * SPE,
    "before_milestone_2": MILESTONES[1] * SPE - 1,
    "at_milestone_2": MILESTONES[1] * SPE,
    "last_step": EPOCHS * SPE - 1,
}


def _cfg(cos: bool, warmup: int) -> OptimConfig:
    return OptimConfig(lr=0.03, epochs=EPOCHS, cos=cos, schedule=MILESTONES, warmup_epochs=warmup)


@pytest.mark.parametrize("where", list(STEPS))
@pytest.mark.parametrize("warmup", [0, WARMUP], ids=["no_warmup", "warmup"])
@pytest.mark.parametrize("cos", [True, False], ids=["cosine", "milestones"])
def test_host_lr_equals_device_lr(cos, warmup, where):
    cfg, step = _cfg(cos, warmup), STEPS[where]
    on_device = float(make_lr_schedule(cfg, SPE)(step))
    host = make_lr_schedule(cfg, SPE, xp=np)
    # the host evaluation moves nothing to the device: it passes where
    # every host-to-device transfer, explicit ones too, is refused
    with jax.transfer_guard_host_to_device("disallow_explicit"):
        value = host(step)
    assert not isinstance(value, jax.Array)
    assert float(value) == pytest.approx(on_device, rel=1e-6)


@pytest.mark.parametrize("cos", [True, False], ids=["cosine", "milestones"])
def test_host_lr_follows_the_recipe(cos):
    """Per-epoch granularity, warm-up ramp, and the decay itself, on the
    host evaluation alone (the reference's `adjust_learning_rate`)."""
    host = make_lr_schedule(_cfg(cos, WARMUP), SPE, xp=np)
    # linear ramp over the warm-up steps, reaching the base rate's neighbourhood
    assert float(host(0)) == pytest.approx(0.03 / (WARMUP * SPE))
    assert float(host(WARMUP * SPE - 1)) == pytest.approx(0.03)
    # constant inside an epoch, lower (or equal, between milestones) in the next
    assert float(host(5 * SPE)) == float(host(6 * SPE - 1))
    assert float(host(6 * SPE)) <= float(host(5 * SPE))
    if cos:
        assert float(host(10 * SPE)) == pytest.approx(0.03 * 0.5, rel=1e-6)
    else:
        assert float(host(MILESTONES[0] * SPE - 1)) == pytest.approx(0.03)
        assert float(host(MILESTONES[0] * SPE)) == pytest.approx(0.003)
        assert float(host(MILESTONES[1] * SPE)) == pytest.approx(0.0003)
