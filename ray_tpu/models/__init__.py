"""Model families, TPU-first.

Pure-functional JAX models (param pytrees + logical sharding axes — no
framework lock-in), scan-over-layers for O(1) compile scaling, bfloat16
matmuls on the MXU, sharding expressed by logical axis names resolved
against the 6-axis mesh of ``ray_tpu.parallel.mesh``.

Coverage mirrors BASELINE.md target configs: Llama-3 family (flagship),
GPT-2, MLP (Fashion-MNIST baseline), ViT (ImageNet streaming).
"""

from ray_tpu.models.gpt2 import GPT2Config, GPT2Model
from ray_tpu.models.jamba import JambaConfig, JambaModel
from ray_tpu.models.lfm2 import Lfm2Config, Lfm2Model
from ray_tpu.models.llama import LlamaConfig, LlamaModel
from ray_tpu.models.mla import MLAConfig, MLAModel
from ray_tpu.models.mlp import MLPConfig, MLPModel
from ray_tpu.models.moe import MoEConfig, MoEModel
from ray_tpu.models.nemotron_h import NemotronHConfig, NemotronHModel
from ray_tpu.models.vit import ViTConfig, ViTModel

__all__ = ["LlamaConfig", "LlamaModel", "MLPConfig", "MLPModel",
           "GPT2Config", "GPT2Model", "ViTConfig", "ViTModel",
           "MoEConfig", "MoEModel", "MLAConfig", "MLAModel", "NemotronHConfig",
           "NemotronHModel", "JambaConfig", "JambaModel", "Lfm2Config",
           "Lfm2Model", "model_for"]

_MODEL_OF = {LlamaConfig: LlamaModel, MoEConfig: MoEModel,
             MLAConfig: MLAModel, NemotronHConfig: NemotronHModel,
             JambaConfig: JambaModel, Lfm2Config: Lfm2Model,
             GPT2Config: GPT2Model, MLPConfig: MLPModel,
             ViTConfig: ViTModel}


def model_for(cfg, **kwargs):
    """The model a configuration describes: its class follows from the
    config's class (``kwargs``, e.g. ``mesh=``, go to its constructor)."""
    try:
        return _MODEL_OF[type(cfg)](cfg, **kwargs)
    except KeyError:
        raise TypeError(
            f"no model for a {type(cfg).__name__}; known: "
            + ", ".join(c.__name__ for c in _MODEL_OF)) from None
