"""The canonical training loop: BERT sequence classification on an MRPC-like
paraphrase task, TPU-native.

Parity with the reference's flagship example (examples/nlp_example.py:1): the
user keeps the loop, ``Accelerator`` makes it run unchanged on one chip, a
TPU slice, or a virtual CPU mesh — sharding, precision, and collectives all
come from ``prepare()`` + ``backward()`` + ``gather_for_metrics()``.

Run from the repo root (``PYTHONPATH=.`` stands in for an install). On the
machine with the chip, alone — one process uses the chip:
    PYTHONPATH=. python examples/nlp_example.py --mixed_precision bf16
On the 8-device virtual CPU mesh:
    PYTHONPATH=. XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/nlp_example.py --num_epochs 2
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np
import optax

import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from example_utils import PairClassificationDataset, accuracy_f1, train_eval_split

from accelerate_tpu import Accelerator
from accelerate_tpu.models import Bert
from accelerate_tpu.utils import set_seed

EVAL_BATCH_SIZE = 16


def get_dataloaders(accelerator: Accelerator, batch_size: int, max_len: int, vocab_size: int):
    """Train/eval loaders over the bundled dataset (deterministic split)."""
    dataset = PairClassificationDataset(vocab_size=vocab_size, max_len=max_len)
    train_set, eval_set = train_eval_split(dataset)
    train_loader = accelerator.prepare_data_loader(
        train_set, batch_size=batch_size, shuffle=True, seed=42
    )
    eval_loader = accelerator.prepare_data_loader(
        eval_set, batch_size=EVAL_BATCH_SIZE, shuffle=False
    )
    return train_loader, eval_loader


def training_function(config: dict, args: argparse.Namespace) -> dict:
    accelerator = Accelerator(mixed_precision=args.mixed_precision)
    set_seed(int(config["seed"]))

    model = Bert("bert-tiny")
    cfg = model.config
    train_loader, eval_loader = get_dataloaders(
        accelerator, int(config["batch_size"]), max_len=64, vocab_size=cfg.vocab_size
    )

    steps_per_epoch = len(train_loader)
    warmup_steps = max(1, steps_per_epoch // 2)
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=config["lr"],
        warmup_steps=warmup_steps,
        decay_steps=max(steps_per_epoch * int(config["num_epochs"]), warmup_steps + 1),
    )
    model, optimizer, scheduler = accelerator.prepare(
        model, optax.adamw(schedule), lambda c: schedule(c)
    )
    loss_fn = Bert.loss_fn(accelerator.unwrap_model(model))

    eval_metric: dict = {}
    for epoch in range(int(config["num_epochs"])):
        train_loader.set_epoch(epoch)
        for batch in train_loader:
            with accelerator.accumulate(model):
                accelerator.backward(loss_fn, batch)
                optimizer.step()
                scheduler.step()
                optimizer.zero_grad()

        predictions, references = [], []
        for batch in eval_loader:
            logits = model.apply(
                model.params, batch["input_ids"], batch["attention_mask"], batch["token_type_ids"]
            )
            preds = jnp.argmax(logits, axis=-1)
            preds, refs = accelerator.gather_for_metrics((preds, batch["labels"]))
            predictions.append(np.asarray(preds))
            references.append(np.asarray(refs))
        eval_metric = accuracy_f1(np.concatenate(predictions), np.concatenate(references))
        accelerator.print(f"epoch {epoch}: {eval_metric}")

    accelerator.end_training()
    return eval_metric


def parse_args(argv=None) -> argparse.Namespace:
    parser = argparse.ArgumentParser(description="Canonical training-loop example.")
    parser.add_argument(
        "--mixed_precision", type=str, default=None, choices=["no", "fp16", "bf16", "fp8"],
        help="Compute precision policy (params stay fp32).",
    )
    parser.add_argument("--num_epochs", type=int, default=3)
    parser.add_argument("--batch_size", type=int, default=16)
    parser.add_argument("--lr", type=float, default=1e-3)
    return parser.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    config = {"lr": args.lr, "num_epochs": args.num_epochs, "seed": 42, "batch_size": args.batch_size}
    training_function(config, args)


if __name__ == "__main__":
    main()
