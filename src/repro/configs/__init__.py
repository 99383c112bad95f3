"""Assigned architecture configs.  Importing this package registers all archs."""
from repro.configs import (  # noqa: F401
    command_r_plus_104b,
    h2o_danube_3_4b,
    mistral_nemo_12b,
    olmo_1b,
    jamba_1_5_large_398b,
    rwkv6_7b,
    qwen3_moe_235b_a22b,
    moonlight_16b_a3b,
    whisper_base,
    internvl2_26b,
)
from repro.configs.base import (  # noqa: F401
    ArchConfig, ShapeConfig, SHAPES, all_archs, get, live_shapes, smoke,
)
