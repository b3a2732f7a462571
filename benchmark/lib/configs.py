"""Files found by name alone (data files, readers, family files), and from a
configuration's file (the source's own ``config.json`` keys) to the arguments
of the program's ``TransformerConfig``. The mapping is the benchmark's own,
so the program's ``config_from_hf_json`` may change."""

from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(kind: str, name: str) -> dict:
    """``benchmark/<kind>/<name>.json``: files are found by name alone."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module of its own. A name may hold
    dots and dashes, so the file is loaded by its path; one that is not there
    is an error that names it."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"benchmark/{kind}/{name}.py is missing (looked for {path})")
    module_spec = importlib.util.spec_from_file_location(f"benchmark.{kind}." + re.sub(r"[.\-]", "_", name), path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module


def model_config(name: str, rehearse: bool = False) -> dict:
    cfg = load_json("configs", name)
    if rehearse:
        cfg = {**cfg, **cfg["rehearse"]}
    return cfg


def family(cfg: dict):
    """``benchmark/families/<model_type>.py``: whatever the benchmark does per
    model family, found by the source's own ``model_type`` (what a family's
    file gives is listed in ``benchmark/families/__init__.py``)."""
    return load_module("families", cfg["model_type"])


def transformer_fields(cfg: dict) -> dict:
    """Keyword arguments for ``accelerate_tpu.models.config.TransformerConfig``."""
    kind = cfg["model_type"]
    if kind == "bert":
        return dict(
            arch="bert", vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"], num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"], max_seq_len=cfg["max_position_embeddings"],
            type_vocab_size=cfg["type_vocab_size"], norm_eps=cfg["layer_norm_eps"],
            num_labels=cfg["assumed"]["num_labels"], dropout_rate=cfg["hidden_dropout_prob"],
        )
    if kind == "mistral":
        if cfg.get("sliding_window") is not None:
            raise ValueError("the program's llama path has no sliding window")
        return dict(
            arch="llama", vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"], num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], max_seq_len=cfg["max_position_embeddings"],
            rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
            tie_embeddings=cfg["tie_word_embeddings"],
        )
    raise ValueError(f"no mapping for model_type {kind!r}")
