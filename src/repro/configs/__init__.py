from repro.configs.base import (ARCH_IDS, SHAPES, MLAConfig, ModelConfig,
                                MoEConfig, SSMConfig, ShapeConfig, get_config,
                                get_smoke_config)

__all__ = ["ARCH_IDS", "SHAPES", "MLAConfig", "ModelConfig", "MoEConfig",
           "SSMConfig", "ShapeConfig", "get_config", "get_smoke_config"]
