"""Whatever the benchmark does per model family. A configuration's file names
its family by the source's own ``model_type``; ``lib/configs.py:family`` finds
``families/<model_type>.py`` by that name alone, and the drivers, ``run.py``,
the readers under ``layer_metrics/`` and the contract test ask it for
everything that differs between families. They name no model.

Every family's file gives:

- ``widths(cfg) -> dict``: the published widths as the source's keys hold
  them, ``hidden_size`` among them (the contract test holds it to >= 1024);
- ``build(cfg)``: the program's model object, on the program's normal path;
- ``params(cfg, seed, dtype)``: the program's tree of weights from the seed,
  made on the device in one jitted call.

A family that is served (``drivers/serve.py``) gives besides:

- ``logits_at(cfg, seed, ids, positions, dtype, control=False)``: the plain
  reference's logits [B, n, V] at ``positions`` [B, n] of one full forward
  pass over ``ids`` [B, T], from its own weights of the seed; ``control``
  computes one precision below the one the configuration states;
- ``forward_flops(cfg, context_before, new_tokens) -> float``: the forward
  operations ``new_tokens`` tokens need after ``context_before`` cached ones;
- ``decode_attention_bytes(cfg, contexts) -> int``: the bytes of cached state
  that decode attention must read for tokens decoded at the live context
  lengths ``contexts`` (one entry a decoded token: what was cached before it).

A family that is trained (``drivers/train.py``) gives besides:

- ``loss_fn(model)``: the program's loss for ``compiled_step``;
- ``batches(mix, cfg, seed) -> list[dict]``: the mix's pool of host batches;
- ``first_steps(cfg, seed, batches, optimizer, row_block, control=False)``:
  the plain reference's first steps over ``batches``: ``{"losses",
  "grad_norms", "change_norms"}``, the norms by leaf under the names of
  ``params``' tree; ``control`` as above;
- ``train_flops_per_token(cfg, seq_len) -> float``: forward and backward
  operations a token needs, recomputation not counted.

Optional: ``counters(program) -> dict[str, number]``, always-on counters of
the program (``program`` is the ``ServingEngine`` in a serving cell, the
``Accelerator`` in a training cell) for the family's own per-layer metrics.
The driver reads them as the window opens and as it closes, and the
differences are ``reading["window"]["family"]``. Spans need no hook: the
readers read the program's ring (``lib/program_spans.py``)."""
