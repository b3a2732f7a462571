from .attention import dot_product_attention, rotary_embedding
from .bert import Bert
from .config import TransformerConfig, get_config, list_models, param_count, register_config
from .exaone_moe import ExaoneMoe
from .generation import generate
from .gpt2 import GPT2
from .jamba import Jamba
from .llama import Llama
from .mellum import Mellum
from .moe import MoEBlock
from .t5 import T5


_ARCHS = {"llama": Llama, "bert": Bert, "gpt2": GPT2, "t5": T5, "exaone_moe": ExaoneMoe, "mellum": Mellum, "jamba": Jamba}


def build_model(name: str):
    """Registry name → model instance (e.g. "llama-7b", "bert-base")."""
    config = get_config(name)
    if config.arch not in _ARCHS:
        raise ValueError(f"Unknown arch {config.arch!r}; available: {sorted(_ARCHS)}")
    return _ARCHS[config.arch](config)
