"""Model configurations and the built-in registry.

The model zoo is pure JAX: parameters are pytrees of jnp arrays, models are
(init, apply) function pairs. This keeps abstract init (`jax.eval_shape`),
partition-rule matching (by pytree path), and checkpoint IO trivial — no
module-system indirection between the framework and XLA.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Optional


@dataclass(frozen=True)
class TransformerConfig:
    """One config for both decoder (llama-style) and encoder (bert-style) stacks."""

    arch: str = "llama"  # "llama" | "bert" | "gpt2" | "t5" | "exaone_moe" | "mellum" | "jamba"
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: Optional[int] = None  # grouped-query attention; None = num_heads
    head_dim: Optional[int] = None  # None = hidden_size // num_heads
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # encoder-only extras
    type_vocab_size: int = 2
    num_labels: int = 2
    dropout_rate: float = 0.0
    # mixture-of-experts (decoder): num_experts > 1 swaps the gated MLP for a
    # top-k routed expert MLP sharded over the `expert` mesh axis
    num_experts: int = 1
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # per-layer pattern (archs "exaone_moe" and "mellum", models/exaone_moe.py,
    # models/mellum.py): the kind of
    # attention ("sliding_attention" | "full_attention") and of MLP ("dense" |
    # "sparse") of every layer, the window of the sliding layers, the routed
    # experts' width beside the dense layers' ``intermediate_size``, and the
    # share of the ``num_experts`` routed experts that this chip holds as
    # (first expert, count); None = all of them. ``num_experts`` stays the
    # router's width and ``moe_top_k`` the experts a token
    layer_types: tuple = ()
    mlp_layer_types: tuple = ()
    sliding_window: Optional[int] = None
    moe_intermediate_size: Optional[int] = None
    num_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    experts_held: Optional[tuple] = None
    # rotary parameters by layer kind (arch "mellum"): ((kind, ((key, value),
    # ...)), ...) with the source's ``rope_parameters`` keys (``rope_type``
    # "default" | "yarn", ``rope_theta``, and YaRN's ``factor``,
    # ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    # ``attention_factor``); a kind not named takes plain rotary at
    # ``rope_theta``. Pairs, not a dict: a config is hashed (``rope_by_kind``
    # builds it from the source's nested dict; ``rope_of`` reads it)
    rope_parameters: tuple = ()
    # state-space layers beside attention layers (arch "jamba", models/jamba.py):
    # ``layer_types`` names every layer "mamba" or "attention"
    # (``mamba_layer_types`` builds it from the source's period and offset),
    # and a Mamba-1 mixer has ``mamba_expand * hidden_size`` channels, each
    # with ``mamba_d_state`` states, a causal depthwise convolution over
    # ``mamba_d_conv`` tokens and a step size projected through ``mamba_dt_rank``
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None  # None = ceil(hidden_size / 16)
    mamba_conv_bias: bool = True
    # encoder-decoder (t5) extras: relative-position bias bucketing and the
    # decoder's BOS (t5 starts generation from the pad token)
    rel_buckets: int = 32
    rel_max_distance: int = 128
    decoder_start_token_id: int = 0

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def dim_per_head(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def mamba_rank(self) -> int:
        return self.mamba_dt_rank or -(-self.hidden_size // 16)

    def rope_of(self, kind: str) -> dict:
        """The rotary parameters of a layer kind, as the source's keys."""
        return dict(dict(self.rope_parameters).get(kind, (("rope_type", "default"), ("rope_theta", self.rope_theta))))

    def replace(self, **kwargs) -> "TransformerConfig":
        return replace(self, **kwargs)


def rope_by_kind(rope_parameters: dict) -> tuple:
    """``TransformerConfig.rope_parameters`` from a source config's
    ``rope_parameters`` nested by layer kind."""
    return tuple((kind, tuple(sorted(of_kind.items()))) for kind, of_kind in sorted(rope_parameters.items()))


def mamba_layer_types(num_layers: int, attn_layer_period: int, attn_layer_offset: int) -> tuple:
    """``TransformerConfig.layer_types`` of a stack whose layer ``i`` attends
    iff ``i % attn_layer_period == attn_layer_offset`` and is a Mamba mixer
    otherwise (the source's two keys)."""
    return tuple("attention" if i % attn_layer_period == attn_layer_offset else "mamba" for i in range(num_layers))


_REGISTRY: dict[str, TransformerConfig] = {
    # llama family (decoder)
    "llama-tiny": TransformerConfig(
        arch="llama", vocab_size=1024, hidden_size=128, intermediate_size=352,
        num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=256,
    ),
    "llama-125m": TransformerConfig(
        arch="llama", vocab_size=32000, hidden_size=768, intermediate_size=2048,
        num_layers=12, num_heads=12, max_seq_len=2048,
    ),
    "llama-1b": TransformerConfig(
        arch="llama", vocab_size=32000, hidden_size=2048, intermediate_size=5504,
        num_layers=22, num_heads=16, max_seq_len=2048,
    ),
    "llama-7b": TransformerConfig(
        arch="llama", vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_layers=32, num_heads=32, max_seq_len=4096,
    ),
    "llama-13b": TransformerConfig(
        arch="llama", vocab_size=32000, hidden_size=5120, intermediate_size=13824,
        num_layers=40, num_heads=40, max_seq_len=4096,
    ),
    "llama-70b": TransformerConfig(
        arch="llama", vocab_size=32000, hidden_size=8192, intermediate_size=28672,
        num_layers=80, num_heads=64, num_kv_heads=8, max_seq_len=4096,
    ),
    # moe variant of the decoder family (expert-parallel MLP)
    "llama-moe-tiny": TransformerConfig(
        arch="llama", vocab_size=1024, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=256,
        num_experts=4, moe_top_k=2, moe_capacity_factor=2.0,
    ),
    # state-space layers beside attention layers (models/jamba.py): two Mamba
    # layers either side of an attention layer, one KV head, tied head
    "jamba-tiny": TransformerConfig(
        arch="jamba", vocab_size=256, hidden_size=32, intermediate_size=64, num_layers=6,
        num_heads=4, num_kv_heads=1, head_dim=8, max_seq_len=256, norm_eps=1e-6, tie_embeddings=True,
        layer_types=mamba_layer_types(6, 4, 1), mamba_d_state=4, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=4,
    ),
    # gpt2 family (decoder, learned positions + LayerNorm + tied embeddings) —
    # the reference's big-model benchmark lineage (GPT-J/NeoX, README.md:31-34)
    "gpt2-tiny": TransformerConfig(
        arch="gpt2", vocab_size=1024, hidden_size=128, intermediate_size=512,
        num_layers=2, num_heads=4, max_seq_len=256, tie_embeddings=True,
    ),
    "gpt2-124m": TransformerConfig(
        arch="gpt2", vocab_size=50257, hidden_size=768, intermediate_size=3072,
        num_layers=12, num_heads=12, max_seq_len=1024, tie_embeddings=True,
    ),
    "gpt2-355m": TransformerConfig(
        arch="gpt2", vocab_size=50257, hidden_size=1024, intermediate_size=4096,
        num_layers=24, num_heads=16, max_seq_len=1024, tie_embeddings=True,
    ),
    "gpt2-774m": TransformerConfig(
        arch="gpt2", vocab_size=50257, hidden_size=1280, intermediate_size=5120,
        num_layers=36, num_heads=20, max_seq_len=1024, tie_embeddings=True,
    ),
    "gpt2-1.5b": TransformerConfig(
        arch="gpt2", vocab_size=50257, hidden_size=1600, intermediate_size=6400,
        num_layers=48, num_heads=25, max_seq_len=1024, tie_embeddings=True,
    ),
    # t5 family (encoder-decoder) — reference examples/inference/t5.py and the
    # T0pp-11B row of benchmarks/README.md:35. num_layers counts layers PER
    # stack (encoder and decoder are symmetric); v1.0 geometry (ReLU FF, tied
    # embeddings with d_model^-0.5 logit scaling).
    "t5-tiny": TransformerConfig(
        arch="t5", vocab_size=1024, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=4, head_dim=32, max_seq_len=256,
        tie_embeddings=True, rel_buckets=8, rel_max_distance=32,
    ),
    "t5-small": TransformerConfig(
        arch="t5", vocab_size=32128, hidden_size=512, intermediate_size=2048,
        num_layers=6, num_heads=8, head_dim=64, max_seq_len=512, tie_embeddings=True,
    ),
    "t5-base": TransformerConfig(
        arch="t5", vocab_size=32128, hidden_size=768, intermediate_size=3072,
        num_layers=12, num_heads=12, head_dim=64, max_seq_len=512, tie_embeddings=True,
    ),
    "t5-large": TransformerConfig(
        arch="t5", vocab_size=32128, hidden_size=1024, intermediate_size=4096,
        num_layers=24, num_heads=16, head_dim=64, max_seq_len=512, tie_embeddings=True,
    ),
    "t5-3b": TransformerConfig(
        arch="t5", vocab_size=32128, hidden_size=1024, intermediate_size=16384,
        num_layers=24, num_heads=32, head_dim=128, max_seq_len=512, tie_embeddings=True,
    ),
    "t5-11b": TransformerConfig(
        arch="t5", vocab_size=32128, hidden_size=1024, intermediate_size=65536,
        num_layers=24, num_heads=128, head_dim=128, max_seq_len=512, tie_embeddings=True,
    ),
    # bert family (encoder) — nlp_example parity (BERT-base MRPC)
    "bert-tiny": TransformerConfig(
        arch="bert", vocab_size=1024, hidden_size=128, intermediate_size=512,
        num_layers=2, num_heads=2, max_seq_len=128,
    ),
    "bert-base": TransformerConfig(
        arch="bert", vocab_size=30522, hidden_size=768, intermediate_size=3072,
        num_layers=12, num_heads=12, max_seq_len=512, norm_eps=1e-12,
    ),
    "bert-large": TransformerConfig(
        arch="bert", vocab_size=30522, hidden_size=1024, intermediate_size=4096,
        num_layers=24, num_heads=16, max_seq_len=512, norm_eps=1e-12,
    ),
}


def config_from_hf_json(source) -> TransformerConfig:
    """Map a HF ``config.json`` (dict, file path, or directory containing
    one) to a :class:`TransformerConfig` — no weights needed.

    Parity: reference commands/estimate.py:215-299 builds a meta-device model
    for any Hub repo from its config alone; this is the offline analogue for
    the four zoo families (llama/mistral, gpt2, bert, t5).
    """
    import json
    import os

    if isinstance(source, str):
        path = source
        if os.path.isdir(path):
            path = os.path.join(path, "config.json")
        with open(path) as f:
            cfg = json.load(f)
    else:
        cfg = dict(source)

    mt = cfg.get("model_type", "")
    arch = {"llama": "llama", "mistral": "llama", "gpt2": "gpt2", "bert": "bert", "t5": "t5"}.get(mt)
    if arch is None:
        raise ValueError(
            f"Unsupported model_type {mt!r} in config.json — supported: "
            "llama, mistral, gpt2, bert, t5"
        )
    if arch == "llama":
        return TransformerConfig(
            arch="llama",
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg.get("num_key_value_heads"),
            head_dim=cfg.get("head_dim"),
            max_seq_len=cfg.get("max_position_embeddings", 2048),
            rope_theta=cfg.get("rope_theta", 10000.0),
            norm_eps=cfg.get("rms_norm_eps", 1e-5),
            tie_embeddings=cfg.get("tie_word_embeddings", False),
        )
    if arch == "gpt2":
        h = cfg["n_embd"]
        return TransformerConfig(
            arch="gpt2",
            vocab_size=cfg["vocab_size"],
            hidden_size=h,
            intermediate_size=cfg.get("n_inner") or 4 * h,
            num_layers=cfg["n_layer"],
            num_heads=cfg["n_head"],
            max_seq_len=cfg.get("n_positions", 1024),
            norm_eps=cfg.get("layer_norm_epsilon", 1e-5),
            tie_embeddings=True,
        )
    if arch == "bert":
        return TransformerConfig(
            arch="bert",
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            max_seq_len=cfg.get("max_position_embeddings", 512),
            type_vocab_size=cfg.get("type_vocab_size", 2),
            norm_eps=cfg.get("layer_norm_eps", 1e-12),
        )
    # t5: symmetric stacks only (num_layers counts layers PER stack)
    dec = cfg.get("num_decoder_layers", cfg["num_layers"])
    if dec != cfg["num_layers"]:
        raise ValueError(
            f"asymmetric t5 stacks (encoder {cfg['num_layers']}, decoder {dec}) "
            "are not supported"
        )
    return TransformerConfig(
        arch="t5",
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["d_model"],
        intermediate_size=cfg["d_ff"],
        num_layers=cfg["num_layers"],
        num_heads=cfg["num_heads"],
        head_dim=cfg.get("d_kv", 64),
        max_seq_len=cfg.get("n_positions", 512),
        norm_eps=cfg.get("layer_norm_epsilon", 1e-6),
        tie_embeddings=cfg.get("tie_word_embeddings", True),
        rel_buckets=cfg.get("relative_attention_num_buckets", 32),
        rel_max_distance=cfg.get("relative_attention_max_distance", 128),
        decoder_start_token_id=cfg.get("decoder_start_token_id", 0),
    )


def get_config(name: str) -> TransformerConfig:
    if name not in _REGISTRY:
        raise KeyError(f"Unknown model {name!r}; available: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def register_config(name: str, config: TransformerConfig) -> None:
    _REGISTRY[name] = config


def list_models() -> list[str]:
    return sorted(_REGISTRY)


def train_flops_per_token(config: TransformerConfig, seq_len: int | None = None) -> float:
    """Training FLOPs per token: the standard 6·N dense estimate (fwd + bwd)
    plus 12·L·H·S for the self-attention score/context matmuls, which the
    parameter count does not see. Shared by MFU derivation in telemetry and
    the benchmark suite so the two can never disagree."""
    seq = seq_len if seq_len is not None else config.max_seq_len
    dense = 6.0 * param_count(config)
    attention = 12.0 * config.num_layers * config.hidden_size * seq
    return dense + attention


def train_flops_per_step(config: TransformerConfig, batch_size: int, seq_len: int) -> float:
    """Training FLOPs for one optimizer step over ``batch_size`` sequences."""
    return batch_size * seq_len * train_flops_per_token(config, seq_len)


def param_count(config: TransformerConfig) -> int:
    """Exact parameter count without materializing anything."""
    h, i, v = config.hidden_size, config.intermediate_size, config.vocab_size
    d, nh, nkv = config.dim_per_head, config.num_heads, config.kv_heads
    if config.arch == "llama":
        if config.num_experts > 1:
            mlp = h * config.num_experts + config.num_experts * 2 * h * i  # router + experts
        else:
            mlp = 3 * h * i  # gate, up, down
        per_layer = (
            h * (nh * d)          # q
            + 2 * h * (nkv * d)   # k, v
            + (nh * d) * h        # o
            + mlp
            + 2 * h               # two rmsnorms
        )
        total = v * h + config.num_layers * per_layer + h  # embed + layers + final norm
        if not config.tie_embeddings:
            total += h * v  # lm head
        return total
    if config.arch == "jamba":
        c, n, r = config.mamba_d_inner, config.mamba_d_state, config.mamba_rank
        mixer = (
            h * 2 * c                                        # in: input and gate
            + config.mamba_d_conv * c + (c if config.mamba_conv_bias else 0)
            + c * (r + 2 * n) + r + 2 * n                    # x projection and its three small norms
            + r * c + c                                      # dt projection with bias
            + c * n + c                                      # A_log, D
            + c * h                                          # out
        )
        attention = 2 * h * nh * d + 2 * h * nkv * d
        mamba = sum(kind == "mamba" for kind in config.layer_types)
        total = v * h + mamba * mixer + (config.num_layers - mamba) * attention + config.num_layers * (3 * h * i + 2 * h) + h
        return total if config.tie_embeddings else total + h * v
    if config.arch == "gpt2":
        embed = v * h + config.max_seq_len * h  # token + learned positions (tied head)
        per_layer = (
            h * 3 * h + 3 * h     # fused qkv with bias
            + h * h + h           # o with bias
            + h * i + i           # mlp up
            + i * h + h           # mlp down
            + 4 * h               # two layernorms (scale+bias)
        )
        return embed + config.num_layers * per_layer + 2 * h  # + final layernorm
    if config.arch == "t5":
        inner = nh * d
        attn = 4 * h * inner  # q, k, v (h→inner) + o (inner→h): equal byte counts
        ff = 2 * h * i
        enc_layer = attn + ff + 2 * h  # two rmsnorms
        dec_layer = 2 * attn + ff + 3 * h  # self + cross attention, three norms
        rel = 2 * config.rel_buckets * nh  # one table per stack
        return (
            v * h  # shared embedding (tied head)
            + config.num_layers * (enc_layer + dec_layer)
            + rel
            + 2 * h  # encoder + decoder final norms
        )
    if config.arch == "bert":
        embed = v * h + config.max_seq_len * h + config.type_vocab_size * h + 2 * h
        per_layer = (
            4 * (h * h + h)       # q,k,v,o with bias
            + h * i + i           # mlp up
            + i * h + h           # mlp down
            + 4 * h               # two layernorms (scale+bias)
        )
        pooler = h * h + h
        classifier = h * config.num_labels + config.num_labels
        return embed + config.num_layers * per_layer + pooler + classifier
    raise ValueError(f"unknown arch {config.arch}")
