"""Architecture configs (one module per assigned architecture) + registry."""
from repro.configs.base import (  # noqa: F401
    ModelConfig, register, get_config, list_configs, reduced_config,
)
# Import for registration side-effects.
from repro.configs import (  # noqa: F401
    starcoder2_15b, qwen3_0p6b, qwen2p5_3b, phi3_mini_3p8b, phi3p5_moe_42b,
    grok1_314b, internvl2_1b, seamless_m4t_large_v2, recurrentgemma_9b,
    mamba2_130m, granite_4p0_h_small,
)
from repro.configs.shapes import SHAPES, input_specs, shape_for  # noqa: F401
