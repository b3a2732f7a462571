"""``model_type: bert``: the encoder with pooler and classification head,
fine-tuned through the program's ``models/bert.py``. Thin glue over
``lib/weights.py``, ``lib/work.py``, ``lib/traffic.py`` and
``lib/reference_bert.py`` (the fp8 control is the reference's)."""

from __future__ import annotations

import numpy as np

from benchmark.lib import configs, reference_bert, traffic, weights, work

first_steps = reference_bert.first_steps


def widths(cfg: dict) -> dict:
    return {k: cfg[k] for k in ("hidden_size", "intermediate_size")}


def build(cfg: dict):
    from accelerate_tpu.models import Bert
    from accelerate_tpu.models.config import TransformerConfig

    return Bert(TransformerConfig(**configs.transformer_fields(cfg)))


def params(cfg: dict, seed: int, dtype) -> dict:
    """float32 masters, as the source initialises them, and no other type."""
    if np.dtype(dtype) != np.float32:
        raise ValueError(f"bert's weights from the seed are float32 masters, not {dtype}")
    return weights.bert_params(cfg, seed)


def loss_fn(model):
    from accelerate_tpu.models import Bert

    return Bert.loss_fn(model)


def batches(mix: dict, cfg: dict, seed: int) -> list[dict]:
    return traffic.classification_batches(
        mix, cfg["vocab_size"], cfg["type_vocab_size"], cfg["assumed"]["num_labels"], seed
    )


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return work.bert_train_flops_per_token(cfg, seq_len, cfg["assumed"]["num_labels"])
