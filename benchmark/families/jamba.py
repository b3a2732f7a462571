"""``model_type: jamba``: state-space (Mamba-1) layers beside a few attention
layers with no positional term, one KV head, every layer followed by a dense
gated-SiLU MLP, the head tied to the embedding. Run through the program's
``models/jamba.py``. Glue over ``lib/jamba.py`` (weights from the seed,
operations and bytes, the state's gaps) and ``lib/reference_jamba.py`` (the
plain reference, with its int8 control). Served through
``drivers/serve_state.py``, which asks for ``lane_state``, ``state_after`` and
``state_gaps`` beside what ``drivers/serve.py`` asks."""

from __future__ import annotations

from benchmark.lib import jamba as family
from benchmark.lib import reference_jamba

logits_at = reference_jamba.logits_at
state_after = reference_jamba.state_after  # for drivers/serve_state.py, with the two below
state_gaps = family.state_gaps
forward_flops = family.forward_flops
decode_attention_bytes = family.decode_attention_bytes
ssm_scan_bytes = family.ssm_scan_bytes  # for ssm_scan_roofline.serve
params = family.params
WIDTHS = ("hidden_size", "intermediate_size", "mamba_d_state", "mamba_d_conv", "mamba_dt_rank", "mamba_expand", "num_experts_per_tok")


def widths(cfg: dict) -> dict:
    return {**{k: cfg[k] for k in WIDTHS}, "head_dim": family.head_dim(cfg)}


_BUILT: dict = {}


def build(cfg: dict):
    """The program's model for ``cfg``, the SAME object however often it is
    asked for: the engine keeps its compiled programs on the model, so the
    engine of ``drivers/serve_state.py``'s probe runs the very programs the
    window's engine compiled, and traces none anew."""
    from accelerate_tpu.models.config import TransformerConfig, mamba_layer_types
    from accelerate_tpu.models.jamba import Jamba

    layers = cfg["num_hidden_layers"]
    fields = dict(
        arch="jamba", vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"], num_layers=layers,
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"], head_dim=family.head_dim(cfg),
        max_seq_len=cfg["max_position_embeddings"], norm_eps=cfg["rms_norm_eps"], tie_embeddings=cfg["tie_word_embeddings"],
        num_experts=cfg["num_experts"], layer_types=mamba_layer_types(layers, cfg["attn_layer_period"], cfg["attn_layer_offset"]),
        mamba_d_state=cfg["mamba_d_state"], mamba_d_conv=cfg["mamba_d_conv"], mamba_expand=cfg["mamba_expand"],
        mamba_dt_rank=cfg["mamba_dt_rank"], mamba_conv_bias=cfg["mamba_conv_bias"],
    )
    key = tuple(sorted(fields.items()))
    if key not in _BUILT:
        _BUILT[key] = Jamba(TransformerConfig(**fields))
    return _BUILT[key]


def lane_state(engine, slot: int):
    """The recurrent layers' state that lane ``slot`` of the engine holds, [Lm, N, C], as it is kept."""
    return engine.cache.extras.ssm[slot]


def counters(engine) -> dict:
    """The engine's always-on counters of the recurrent layers' scan
    (``telemetry/serving.py``), cut where the programs are dispatched; nought
    where the program keeps none."""
    stats = engine.stats
    names = ("ssm_decode_tokens", "ssm_prefill_tokens", "ssm_prefill_programs", "ssm_state_resets")
    return {name: getattr(stats, name, 0) for name in names}
