"""The serving steps' share of the chip's peak: the forward operations the
slice's work needs (2 per matmul parameter and token, plus attention from the
requests' own lengths; the prompts of the requests submitted in the slice, the
tokens decoded in it at their live context lengths) per second, over chips
times the bf16 peak. Padding to prefill buckets is not counted: it is not
needed work."""

from benchmark.lib import work


def read(reading):
    window = reading["window"]
    if reading["peaks"] is None or not window.get("decode_tokens"):
        return None
    cfg = reading["config"]
    heads_dim = cfg["num_attention_heads"] * cfg["head_dim"] * cfg["num_hidden_layers"]
    decode = 2.0 * work.llama_matmul_params(cfg) * window["decode_tokens"] + 4.0 * heads_dim * (
        window["decode_context_sum"] + window["decode_tokens"]
    )
    per_second = (window["prefill_flops"] + decode) / window["elapsed_s"]
    return 100.0 * per_second / (reading["cell"]["chips"] * reading["peaks"]["bf16_flops_per_s"])
