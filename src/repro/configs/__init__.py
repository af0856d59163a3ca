"""Architecture registry — importing this package registers all configs."""
from repro.configs.base import (
    INPUT_SHAPES,
    ArchConfig,
    InputShape,
    PipelineConfig,
    get_config,
    list_archs,
)

# registration side-effects
from repro.configs import (  # noqa: F401
    dbrx_132b,
    deepseek_coder_33b,
    deepseek_v2_236b,
    deepseek_v2_lite,
    glm4_9b,
    mamba2_370m,
    minicpm3_4b,
    paac_cnn,
    pixtral_12b,
    qwen2_7b,
    seamless_m4t_large_v2,
    zamba2_7b,
)

ASSIGNED_ARCHS = [
    "minicpm3-4b",
    "glm4-9b",
    "deepseek-v2-236b",
    "seamless-m4t-large-v2",
    "deepseek-coder-33b",
    "dbrx-132b",
    "qwen2-7b",
    "zamba2-7b",
    "pixtral-12b",
    "mamba2-370m",
]

__all__ = [
    "ArchConfig",
    "InputShape",
    "PipelineConfig",
    "INPUT_SHAPES",
    "get_config",
    "list_archs",
    "ASSIGNED_ARCHS",
]
