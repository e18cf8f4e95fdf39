"""zamba2-7b cut to one chip: published layers 0-11 (one whole period of the
pattern: 12 Mamba layers, sites 6 and 11, so each shared block at one site),
every width as published. It stands for the first stage of an 81-layer
replica whose other 69 layers are further pipeline stages."""
import dataclasses

from repro.configs import zamba2_7b
from repro.models.config import ModelConfig


def config() -> ModelConfig:
    full = zamba2_7b.config()
    return dataclasses.replace(
        full, name="zamba2-7b-l12", num_layers=12, hybrid_layer_ids=(6, 11),
        notes=full.notes + ("cut to published layers 0-11: depth and sites only",),
    )


def smoke_config() -> ModelConfig:
    return zamba2_7b.smoke_config()
