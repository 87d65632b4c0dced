"""Architecture registry: --arch <id> resolves here."""
from .base import (
    Config,
    GNNConfig,
    LMConfig,
    MLASpec,
    MoESpec,
    RecsysConfig,
    ShapeCell,
    YarnSpec,
    get_arch,
    get_config,
    get_shapes,
    input_specs,
    list_archs,
    register,
)

register("deepseek-7b", "deepseek_7b")
register("gemma3-4b", "gemma3_4b")
register("tinyllama-1.1b", "tinyllama_1_1b")
register("qwen2-moe-a2.7b", "qwen2_moe_a2_7b")
register("deepseek-v2-236b", "deepseek_v2_236b")
register("gatedgcn", "gatedgcn")
register("bst", "bst")
register("dcn-v2", "dcn_v2")
register("fm", "fm")
register("sasrec", "sasrec")

__all__ = [
    "Config",
    "GNNConfig",
    "LMConfig",
    "MLASpec",
    "MoESpec",
    "RecsysConfig",
    "ShapeCell",
    "YarnSpec",
    "get_arch",
    "get_config",
    "get_shapes",
    "input_specs",
    "list_archs",
    "register",
]
