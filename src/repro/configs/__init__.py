"""Architecture configs. Importing this package registers every assigned
architecture in ``ARCHITECTURES``."""
from repro.configs.base import (  # noqa: F401
    ARCHITECTURES,
    ATTN,
    GLOBAL,
    INPUT_SHAPES,
    MAMBA,
    AdversaryConfig,
    AggConfig,
    AvailabilityConfig,
    CompressionConfig,
    FedConfig,
    GPOConfig,
    HierarchyConfig,
    InputShape,
    ModelConfig,
    PrivacyConfig,
    ServeConfig,
    TrainConfig,
    config_dict,
    get_arch,
    override,
    smoke_variant,
)

# registration side effects
from repro.configs import (  # noqa: F401,E402
    gemma2_27b,
    gemma3_27b,
    granite_4_0_h_small,
    granite_moe_3b_a800m,
    grok_1_314b,
    llava_next_34b,
    mamba2_780m,
    qwen2_0_5b,
    qwen3_32b,
    whisper_small,
    zamba2_1_2b,
)

ALL_ARCHS = tuple(ARCHITECTURES.names())
