"""The device side's names: every part of the train step, of the expert
dispatch and of the augmentation program runs under its `moco.` scope
(`obs.trace.STEP_SCOPES`). The compiled programs' `op_name` metadata is
what a device trace shows for each op (`benchmarks/readers/scope.py`), so
that is where the scopes are looked for: each name a path reaches is
there, the backward pass's under `transpose(`, and no `moco.` scope
outside the list is. Small configurations of the benchmark's own (their
CPU rehearsal sizes), compiled for the CPU; nothing runs."""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from moco_tpu.obs.trace import STEP_SCOPES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OP_NAME = re.compile(r'op_name="([^"]*)"')
SCOPE = re.compile(r"moco\.[A-Za-z_]+(?:\.[A-Za-z_]+)*")

STEP_PARTS = {
    "moco.ema", "moco.key_encoder", "moco.query_encoder", "moco.contrastive_loss",
    "moco.optimizer", "moco.health",
}
AUGMENT_PARTS = {
    "moco.augment.crop", "moco.augment.colour", "moco.augment.blur",
    "moco.augment.flip_normalize",
}


def rehearsal_config(name: str, **overrides):
    """The benchmark configuration `name` at its CPU rehearsal size, with
    dotted `overrides` on top (`data.image_size` above 64 keeps the blur,
    which the recipe leaves out of small images)."""
    from benchmarks.harness.common import _replace_dotted, build_train_config

    with open(os.path.join(REPO, "benchmarks", "configs", f"{name}.json")) as f:
        cfg_file = json.load(f)
    config = build_train_config(cfg_file, {}, seed=0, workdir="", rehearse=True)
    config = dataclasses.replace(
        config, parallel=dataclasses.replace(config.parallel, num_data=1)
    )
    for key, value in overrides.items():
        config = _replace_dotted(config, key, value)
    return config


def compiled_programs(config) -> dict:
    """{"step": HLO text[, "augment": HLO text]}: the train step as
    `train.py` builds it and, for image input, the input pipeline's
    two-view augmentation program, lowered from abstract shapes and
    compiled for this process's backend."""
    from moco_tpu.core import (
        build_encoder, build_predictor, create_state, make_train_step, sample_input,
    )
    from moco_tpu.data.datasets import SyntheticDataset
    from moco_tpu.data.pipeline import TwoCropPipeline
    from moco_tpu.parallel import create_mesh
    from moco_tpu.utils.schedules import build_optimizer

    mesh = create_mesh(num_data=1, num_model=1)
    encoder, predictor = build_encoder(config.moco, num_data=1), build_predictor(config.moco, 1)
    tx = build_optimizer(config.optim, steps_per_epoch=100)
    state = jax.eval_shape(
        lambda r: create_state(r, config, encoder, tx, sample_input(config), predictor=predictor),
        jax.random.PRNGKey(0),
    )
    step = make_train_step(config, encoder, tx, mesh, predictor=predictor, total_steps=100)
    b = config.data.global_batch
    if config.data.input == "tokens":
        view = {"ids": jax.ShapeDtypeStruct((b, config.data.seq_len), jnp.int32),
                "lengths": jax.ShapeDtypeStruct((b,), jnp.int32)}
    else:
        s = config.data.image_size
        view = jax.ShapeDtypeStruct((b, s, s, 3), jnp.float32)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    out = {"step": step.lower(state, {"im_q": view, "im_k": view}, key).compile().as_text()}
    if config.data.input != "tokens":
        s = config.data.image_size
        pipeline = TwoCropPipeline(config.data, mesh, dataset=SyntheticDataset(b, s))
        raw = jax.ShapeDtypeStruct((b, s + 16, s + 16, 3), jnp.uint8)
        out["augment"] = pipeline._augment.lower(key, raw).compile().as_text()
    return out


def scopes(text: str) -> tuple[set, set]:
    """(every `moco.` scope in the program's op_name paths, those that
    stand inside a `transpose(`: the backward pass)."""
    every, backward = set(), set()
    for path in OP_NAME.findall(text):
        found = SCOPE.findall(path)
        every.update(found)
        if "transpose(" in path:
            backward.update(found)
    return every, backward


CASES = {
    # ResNet, the v2 step: queue, Shuffle-BN path, health gauges; the v2 recipe with its blur
    "resnet_v2": ("r50_v2", {"data.image_size": 72}, STEP_PARTS | {"moco.enqueue"},
                  AUGMENT_PARTS),
    # ViT, the v3 step: predictor, symmetric loss, AdamW, EMA on the cosine ramp
    "vit_v3": ("vit_b16_v3", {"data.image_size": 72}, STEP_PARTS, AUGMENT_PARTS),
    # a decoder stack with expert layers, 2 of 8 experts held: the dispatch's two-rung
    # ladder (`_laddered`, whose backward runs in a custom rule) under remat
    "joyai_tokens": ("joyai_flash_ep16",
                     {"moco.remat": True, "moco.expert_share": [2, 2], "data.seq_len": 512},
                     STEP_PARTS | {"moco.enqueue", "moco.moe_dispatch", "moco.expert_ffn"}, set()),
}


@pytest.fixture(scope="module", params=sorted(CASES))
def programs(request):
    name, overrides, step_parts, augment_parts = CASES[request.param]
    return compiled_programs(rehearsal_config(name, **overrides)), step_parts, augment_parts


def test_every_part_is_named_in_the_compiled_programs(programs):
    texts, step_parts, augment_parts = programs
    step, step_backward = scopes(texts["step"])
    assert step_parts <= step, sorted(step_parts - step)
    # the backward pass of what is differentiated carries its scope inside `transpose(`
    differentiated = {"moco.query_encoder", "moco.contrastive_loss"}
    if "moco.moe_dispatch" in step_parts:
        differentiated |= {"moco.moe_dispatch", "moco.expert_ffn"}
        # the ladder's backward rule runs in the branch the step's count takes
        ladder = [p for p in OP_NAME.findall(texts["step"]) if "transpose(" in p and "/cond/" in p]
        assert ladder and all(SCOPE.findall(p)[-1] in differentiated for p in ladder)
    assert differentiated <= step_backward, sorted(differentiated - step_backward)
    # the optimizer, EMA, key forward and gauges are not differentiated
    assert not step_backward & {"moco.ema", "moco.key_encoder", "moco.health", "moco.optimizer"}
    if augment_parts:
        augment, _ = scopes(texts["augment"])
        assert augment == augment_parts, sorted(augment ^ augment_parts)
    else:
        assert "augment" not in texts


def test_no_moco_scope_outside_the_list(programs):
    texts, _, _ = programs
    for text in texts.values():
        every, _ = scopes(text)
        assert every <= set(STEP_SCOPES), sorted(every - set(STEP_SCOPES))


def test_every_moco_scope_in_the_tree_is_listed():
    """The source's `named_scope("moco.…")` literals are the list, no more
    and no fewer: the list is what the reader, its tests and PERF.md name."""
    found = set()
    for root, _, files in os.walk(os.path.join(REPO, "moco_tpu")):
        for fname in files:
            if fname.endswith(".py"):
                with open(os.path.join(root, fname)) as f:
                    found.update(re.findall(r'named_scope\(\s*"(moco\.[^"]*)"', f.read()))
    assert found == set(STEP_SCOPES), sorted(found ^ set(STEP_SCOPES))
