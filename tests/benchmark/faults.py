"""Faults planted underneath the benchmark's timed path, to see ``correct``
come out false. Used by ``test_benchmark.py`` at rehearsal size and, by hand,
on the chip at the cells' own sizes:

    python3 tests/benchmark/faults.py <fault> -- --workload <cell> --seed 1 --seconds 1

Each fault patches the program (never the benchmark) and then runs
``benchmark/run.py``'s ``main`` unchanged."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def state_unchanged():
    """A training step that returns its loss and leaves parameters and
    optimizer state as they were."""
    from accelerate_tpu import Accelerator

    original = Accelerator.compiled_step

    def compiled_step(self, loss_fn, model=None, **kwargs):
        step = original(self, loss_fn, model=model, **{**kwargs, "donate": False})
        prepared = model if model is not None else self._models[-1]
        optimizer = next(opt for opt in self._optimizers if opt._box is prepared.box)

        def frozen(batch):
            params, opt_state = prepared.params, optimizer.opt_state
            loss = step(batch)
            prepared.params, optimizer.opt_state = params, opt_state
            return loss

        return frozen

    Accelerator.compiled_step = compiled_step


def half_batch():
    """Half of the batch left out, the mean taken over the rest."""
    from accelerate_tpu.models import Bert

    original = Bert.loss_fn

    def loss_fn(model):
        fn = original(model)
        return lambda params, batch: fn(params, {k: v[: v.shape[0] // 2] for k, v in batch.items()})

    Bert.loss_fn = staticmethod(loss_fn)


def altered_token():
    """One slot's decoded token altered where it is produced, every fourth
    decode step. ``ALTER_EVERY_SLOT=1`` alters every slot's instead (for a
    reading on the chip, where a sample of eight requests rarely holds slot 0)."""
    import jax.numpy as jnp

    from accelerate_tpu.serving.engine import ServingEngine

    original = ServingEngine._paged_decode_program
    calls = {"n": 0}

    def program(self):
        decode = original(self)
        vocab = self.model.config.vocab_size

        def altered(*args):
            nxt, *rest = decode(*args)
            calls["n"] += 1
            if calls["n"] % 4 == 0:
                if os.environ.get("ALTER_EVERY_SLOT"):
                    nxt = (nxt + 1) % vocab
                else:
                    nxt = nxt.at[0].set((nxt[0] + 1) % vocab)
            return (nxt, *rest)

        return altered

    ServingEngine._paged_decode_program = program


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch, "altered_token": altered_token}


if __name__ == "__main__":
    fault, dash, *argv = sys.argv[1:]
    if fault not in FAULTS or dash != "--":
        sys.exit(f"usage: faults.py <{'|'.join(FAULTS)}> -- <run.py arguments>")
    sys.path.insert(0, ROOT)
    FAULTS[fault]()
    from benchmark import run

    sys.exit(run.main(argv))
