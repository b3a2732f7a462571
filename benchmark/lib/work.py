"""Operations and bytes that the algorithms need, computed from shapes.

The keys are those of the configurations' files (the sources' own
``config.json`` keys). Recomputed operations are never counted."""

from __future__ import annotations


def _head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def bert_param_count(cfg: dict, num_labels: int = 2) -> int:
    """Every parameter of a BERT encoder with pooler and classification head,
    embeddings included."""
    h, i, layers = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    embeddings = (cfg["vocab_size"] + cfg["max_position_embeddings"] + cfg["type_vocab_size"]) * h + 2 * h
    layer = 4 * (h * h + h) + 2 * h + (h * i + i) + (i * h + h) + 2 * h
    return embeddings + layers * layer + (h * h + h) + (h * num_labels + num_labels)


def bert_train_flops_per_token(cfg: dict, seq_len: int, num_labels: int = 2) -> float:
    """6·N for the forward and backward matmuls (N counts the embeddings too,
    the usual convention, a slight overcount) plus 12·L·H·S for the attention
    scores and context."""
    dense = 6.0 * bert_param_count(cfg, num_labels)
    attention = 12.0 * cfg["num_hidden_layers"] * cfg["hidden_size"] * seq_len
    return dense + attention


def llama_layer_params(cfg: dict) -> int:
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    d, nh, nkv = _head_dim(cfg), cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * nh * d + 2 * h * nkv * d + nh * d * h + 3 * h * i + 2 * h


def llama_param_count(cfg: dict) -> int:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    head = 0 if cfg.get("tie_word_embeddings") else h * v
    return v * h + cfg["num_hidden_layers"] * llama_layer_params(cfg) + h + head


def llama_matmul_params(cfg: dict) -> int:
    """Parameters that a token is multiplied with: the layers' projections and
    the output head. The embedding is a lookup and the norms are not matmuls."""
    h = cfg["hidden_size"]
    return cfg["num_hidden_layers"] * (llama_layer_params(cfg) - 2 * h) + h * cfg["vocab_size"]


def llama_forward_flops(cfg: dict, context_before: int, new_tokens: int) -> float:
    """Forward operations to process ``new_tokens`` tokens that follow
    ``context_before`` cached ones, causally: 2 per matmul parameter and token,
    plus 4·heads·head_dim per (token, attended position) and layer."""
    attended = new_tokens * context_before + new_tokens * (new_tokens + 1) // 2
    attention = 4.0 * cfg["num_attention_heads"] * _head_dim(cfg) * cfg["num_hidden_layers"] * attended
    return 2.0 * llama_matmul_params(cfg) * new_tokens + attention


def llama_request_flops(cfg: dict, prompt_len: int, output_len: int) -> float:
    """Forward operations of one served request: every prompt token and every
    output token but the last goes through the model once."""
    return llama_forward_flops(cfg, 0, prompt_len + output_len - 1)


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes of K and V that one cached token holds over all layers."""
    return 2 * cfg["num_key_value_heads"] * _head_dim(cfg) * bytes_per_value * cfg["num_hidden_layers"]


def decode_attention_bytes(cfg: dict, context_lengths_sum: int, bytes_per_value: int = 2) -> int:
    """Bytes of K and V that decode attention must read for tokens decoded at
    the given live context lengths (their sum), whatever kernel does it."""
    return kv_bytes_per_token(cfg, bytes_per_value) * context_lengths_sum
