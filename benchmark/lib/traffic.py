"""The one general traffic generator. A mix is a data file of parameters
(``benchmark/traffic/<mix>.json``); everything is drawn from the seed, and the
program receives only the generated inputs.

Every seed gets the same set of sizes in another order: lengths are the
quantiles of the mix's distributions, not draws from them, so two seeds offer
the same work and differ only in its order and in the token ids."""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def lognormal_quantiles(spec: dict, count: int) -> np.ndarray:
    """``count`` lengths at the mid-quantiles of a log-normal with the given
    median and sigma, clipped to [min, max]."""
    grid = (np.arange(count) + 0.5) / count
    z = np.array([NormalDist().inv_cdf(float(q)) for q in grid])
    lengths = np.rint(np.exp(math.log(spec["median"]) + spec["sigma"] * z))
    return np.clip(lengths, spec["min"], spec["max"]).astype(np.int64)


def request_pool(mix: dict) -> list[tuple[int, int]]:
    """The mix's fixed multiset of (prompt length, output length): prompt
    quantiles paired with output quantiles shuffled by the mix's own
    ``pairing_seed``, so the two lengths are independent and no run seed
    changes the set."""
    count = mix["pool"]
    prompts = lognormal_quantiles(mix["prompt_len"], count)
    outputs = lognormal_quantiles(mix["output_len"], count)
    outputs = np.random.default_rng(mix["pairing_seed"]).permutation(outputs)
    return [(int(p), int(o)) for p, o in zip(prompts, outputs)]


class ClientStreams:
    """Per-client request streams of a closed loop. The pool is shuffled by
    the seed and dealt round-robin, client ``c`` taking items ``c, c + n, …``
    and starting over when the pool is spent. Prompt tokens are independent
    draws from ``[1, vocab)`` on the client's own generator, so no two
    prompts share a prefix beyond chance."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        pool = request_pool(mix)
        order = np.random.default_rng([seed, 0]).permutation(len(pool))
        self.pool = [pool[i] for i in order]
        self.clients = mix["clients"]
        self.vocab = vocab
        self._rngs = [np.random.default_rng([seed, 1, c]) for c in range(self.clients)]
        self._sent = [0] * self.clients

    def next(self, client: int) -> tuple[np.ndarray, int]:
        """(prompt ids, output length) of the client's next request."""
        index = (client + self._sent[client] * self.clients) % len(self.pool)
        self._sent[client] += 1
        prompt_len, output_len = self.pool[index]
        prompt = self._rngs[client].integers(1, self.vocab, (prompt_len,)).astype(np.int32)
        return prompt, output_len


def classification_batches(mix: dict, vocab: int, type_vocab: int, labels: int, seed: int) -> list[dict]:
    """``batch_pool`` batches of sentence-pair rows padded to ``seq_len``:
    every row differs, real lengths are spread evenly over
    [min_real_len, seq_len] (the same set for every seed, in another order),
    the second segment carries token type 1, padding is id 0 and masked."""
    rng = np.random.default_rng([seed, 2])
    b, s, pool = mix["batch_size"], mix["seq_len"], mix["batch_pool"]
    lengths = np.rint(np.linspace(mix["min_real_len"], s, b * pool)).astype(np.int64)
    lengths = rng.permutation(lengths).reshape(pool, b)
    position = np.arange(s)[None, :]
    batches = []
    for real in lengths:
        mask = position < real[:, None]
        split = (real * rng.uniform(0.3, 0.7, b)).astype(np.int64)[:, None]
        ids = rng.integers(1, vocab, (b, s))
        batches.append({
            "input_ids": np.where(mask, ids, 0).astype(np.int32),
            "attention_mask": mask.astype(np.int32),
            "token_type_ids": np.where(mask & (position >= split), min(1, type_vocab - 1), 0).astype(np.int32),
            "labels": rng.integers(0, labels, (b,)).astype(np.int32),
        })
    return batches
