"""``model_type: mistral``: a rotary grouped-query decoder with a gated SiLU
MLP and no sliding window, run through the program's ``models/llama.py``.
Thin glue over ``lib/weights.py``, ``lib/work.py`` and
``lib/reference_llama.py`` (the int8 control is the reference's)."""

from __future__ import annotations

import numpy as np

from benchmark.lib import configs, reference_llama, weights, work

logits_at = reference_llama.logits_at
forward_flops = work.llama_forward_flops


def widths(cfg: dict) -> dict:
    return {k: cfg[k] for k in ("hidden_size", "intermediate_size", "head_dim")}


def build(cfg: dict):
    from accelerate_tpu.models import Llama
    from accelerate_tpu.models.config import TransformerConfig

    return Llama(TransformerConfig(**configs.transformer_fields(cfg)))


def params(cfg: dict, seed: int, dtype) -> dict:
    return weights.llama_params(cfg, seed, dtype)


def decode_attention_bytes(cfg: dict, contexts) -> int:
    """Every layer reads the whole of every live context: the bytes follow
    from the lengths' sum alone."""
    return work.decode_attention_bytes(cfg, int(np.sum(contexts)))
