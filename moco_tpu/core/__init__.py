from moco_tpu.core.ema import ema_update
from moco_tpu.core.moco import (
    MoCoEncoder,
    MocoState,
    Zero23TrainStep,
    ZeroGathered,
    build_encoder,
    build_predictor,
    create_state,
    full_param_shapes,
    make_train_step,
    place_state,
    reshard_state,
    sample_input,
    state_specs,
    zero_stage23,
)
from moco_tpu.core.queue import check_queue_divisibility, enqueue, init_queue

__all__ = [
    "ema_update",
    "MoCoEncoder",
    "MocoState",
    "Zero23TrainStep",
    "ZeroGathered",
    "build_encoder",
    "build_predictor",
    "create_state",
    "full_param_shapes",
    "make_train_step",
    "place_state",
    "reshard_state",
    "sample_input",
    "state_specs",
    "zero_stage23",
    "check_queue_divisibility",
    "enqueue",
    "init_queue",
]
