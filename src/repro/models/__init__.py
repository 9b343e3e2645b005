"""Model substrate: configs, layers, and the unified LM assembly."""
from .config import (SHAPES, SHAPES_BY_NAME, MLAConfig, ModelConfig,
                     MoEConfig, ShapeSpec, SSMConfig, XLSTMConfig,
                     YaRNConfig, applicable_shapes)
from .model import (TrainBatch, decode_step, forward, init_cache,
                    init_params, loss_fn, prefill)
from .moe import get_mesh, set_mesh

__all__ = [
    "SHAPES", "SHAPES_BY_NAME", "MLAConfig", "ModelConfig", "MoEConfig",
    "ShapeSpec", "SSMConfig", "XLSTMConfig", "YaRNConfig", "applicable_shapes",
    "TrainBatch", "decode_step", "forward", "init_cache", "init_params",
    "loss_fn", "prefill", "get_mesh", "set_mesh",
]
