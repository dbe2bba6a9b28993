"""The encoders that read token rows: every decoder-stack family, by arch."""

from __future__ import annotations

from moco_tpu.models.joyai import _JOYAI_CONFIGS, create_joyai
from moco_tpu.models.phi4flash import _PHI4FLASH_CONFIGS, create_phi4flash
from moco_tpu.models.smallthinker import _SMALLTHINKER_CONFIGS, create_smallthinker

_CREATE = {
    **dict.fromkeys(_JOYAI_CONFIGS, create_joyai),
    **dict.fromkeys(_SMALLTHINKER_CONFIGS, create_smallthinker),
    **dict.fromkeys(_PHI4FLASH_CONFIGS, create_phi4flash),
}


def is_token_arch(arch: str) -> bool:
    return arch in _CREATE


def create_token_encoder(arch: str, **cut):
    """The family's backbone at its cut of a deployment
    (`models/decoder.py::create_stack`'s keywords)."""
    return _CREATE[arch](arch, **cut)
