"""``model_type: exaone_moe``: window and full attention layers in one stack,
a leading dense MLP, then sparse layers of a shared expert beside routed
experts chosen by sigmoid scores, of which this chip holds a share
(``cfg["held"]``; ``lib/exaone_moe.py``). Run through the program's
``models/exaone_moe.py``. Glue over ``lib/exaone_moe.py`` (weights from the
seed, operations and bytes) and ``lib/reference_exaone_moe.py`` (the plain
reference, with its int8 control)."""

from __future__ import annotations

from benchmark.lib import exaone_moe as family
from benchmark.lib import reference_exaone_moe

logits_at = reference_exaone_moe.logits_at
forward_flops = family.forward_flops
decode_attention_bytes = family.decode_attention_bytes
grouped_expert_work = family.grouped_expert_work  # for expert_mlp_roofline.serve
params = family.params
WIDTHS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim", "num_experts_per_tok", "sliding_window",
    "num_shared_experts",
)


def widths(cfg: dict) -> dict:
    return {**{k: cfg[k] for k in WIDTHS}, "router_experts": family.router_experts(cfg)}


def build(cfg: dict):
    from accelerate_tpu.models.config import TransformerConfig
    from accelerate_tpu.models.exaone_moe import ExaoneMoe

    layers = cfg["num_hidden_layers"]
    return ExaoneMoe(TransformerConfig(
        arch="exaone_moe", vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"], num_layers=layers,
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        max_seq_len=cfg["max_position_embeddings"], rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"], tie_embeddings=cfg["tie_word_embeddings"],
        layer_types=tuple(cfg["layer_types"][:layers]), mlp_layer_types=tuple(cfg["mlp_layer_types"][:layers]),
        sliding_window=cfg["sliding_window"], moe_intermediate_size=cfg["moe_intermediate_size"],
        num_shared_experts=cfg["num_shared_experts"], routed_scaling_factor=cfg["routed_scaling_factor"],
        num_experts=family.router_experts(cfg), moe_top_k=cfg["num_experts_per_tok"],
        experts_held=(family.first_expert(cfg), cfg["num_experts"]),
    ))


def counters(engine) -> dict:
    """The engine's always-on counters of the routed experts (decode steps and
    prefill programs apart) and of the two kinds of cached layer
    (``telemetry/serving.py``)."""
    stats = engine.stats
    by_expert = stats.moe_tokens_by_held_expert
    return {
        "assignments": stats.moe_assignments, "assignments_held": stats.moe_assignments_held,
        "experts_hit": stats.moe_experts_hit,
        "prefill_assignments_held": stats.moe_prefill_assignments_held, "prefill_experts_hit": stats.moe_prefill_experts_hit,
        "attended_window_tokens": stats.attended_window_tokens, "attended_full_tokens": stats.attended_full_tokens,
        **{f"tokens_by_held_expert.{e}": int(n) for e, n in enumerate([] if by_expert is None else by_expert)},
    }
