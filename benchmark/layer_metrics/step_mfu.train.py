"""The whole training step's share of the chip's peak: tokens per second of
the traced slice times the operations a token needs (the family's
``train_flops_per_token``: forward and backward, no recomputation counted)
over chips times the bf16 peak."""


def read(reading):
    if reading["peaks"] is None:
        return None
    window = reading["window"]
    flops = reading["family"].train_flops_per_token(reading["config"], window["seq_len"])
    return 100.0 * window["tokens_per_s"] * flops / (reading["cell"]["chips"] * reading["peaks"]["bf16_flops_per_s"])
