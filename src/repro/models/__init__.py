from repro.models.config import (  # noqa: F401
    AttentionConfig, EncoderConfig, Mamba2Config, MLAConfig, ModelConfig,
    MoEConfig, RWKV6Config)
