"""Multi-process correctness payload: run with 2+ REAL processes rendezvousing
through jax.distributed (the path single-process virtual-mesh tests cannot
cover): global-array assembly from process-local data, cross-process object
broadcast, loader sharding, and training parity across hosts.

Launched per process by tests/test_multiprocess.py via
``accelerate-tpu launch --num_processes N --process_id i
--coordinator_address 127.0.0.1:PORT`` with per-process virtual CPU devices —
the CPU stand-in for a multi-host TPU pod (SURVEY §4's three-tier scheme).
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np


def main():
    # CI harness: force the CPU backend with N virtual host devices
    force_cpu = os.environ.get("ACCELERATE_TEST_FORCE_CPU_DEVICES")
    if force_cpu:
        import jax

        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={int(force_cpu)}"
        ).strip()
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", int(force_cpu))

    import optax

    import jax
    import jax.numpy as jnp

    from accelerate_tpu import Accelerator, PartialState, ops, set_seed
    from accelerate_tpu.ops.operations import broadcast_object_list

    state = PartialState()
    expected_procs = int(os.environ["ACCELERATE_NUM_PROCESSES"])
    assert state.num_processes == expected_procs, (state.num_processes, expected_procs)
    assert jax.process_count() == expected_procs
    assert state.num_devices == jax.device_count()
    assert state.num_devices > jax.local_device_count()  # genuinely multi-host

    # cross-process object broadcast: every process must see rank 0's payload
    payload = [{"token": "rank0-secret", "pid": state.process_index}] if state.is_main_process else [None]
    received = broadcast_object_list(payload)
    assert received[0]["token"] == "rank0-secret", received

    # global-array assembly from process-local shards + gather round trip
    local_rows = 4
    local = np.full((local_rows, 2), state.process_index, np.float32)
    global_batch = ops.send_to_device({"x": local})
    gathered = ops.gather(global_batch)
    assert gathered["x"].shape[0] == local_rows * state.num_processes
    seen_ranks = sorted(set(np.asarray(gathered["x"])[:, 0].astype(int).tolist()))
    assert seen_ranks == list(range(state.num_processes)), seen_ranks

    # training parity: every process runs the same loop; replicated params
    # must be identical across hosts afterwards
    set_seed(0)
    accelerator = Accelerator()

    class Lin:
        def init(self, rng):
            del rng
            return {"a": jnp.zeros(()), "b": jnp.zeros(())}

        apply = staticmethod(lambda p, x: p["a"] * x + p["b"])

    def loss_fn(params, batch):
        return jnp.mean((Lin.apply(params, batch["x"]) - batch["y"]) ** 2)

    rng = np.random.default_rng(7)
    xs = rng.normal(size=(64,)).astype(np.float32)

    class DS:
        def __len__(self):
            return 64

        def __getitem__(self, i):
            return {"x": xs[i], "y": 2 * xs[i] + 1}

    model, opt, loader = accelerator.prepare(Lin(), optax.sgd(0.1), DS())
    for epoch in range(3):
        loader.set_epoch(epoch)
        for batch in loader:
            accelerator.backward(loss_fn, batch)
            opt.step()
            opt.zero_grad()
    a = float(jax.device_get(model.params["a"]))
    b = float(jax.device_get(model.params["b"]))
    assert np.isfinite(a) and np.isfinite(b)
    # gather each host's view of the (replicated) params — must agree exactly
    views = ops.gather_object([{"a": a, "b": b}])
    assert all(v == views[0] for v in views), views
    assert abs(a - 2.0) < 0.5 and abs(b - 1.0) < 0.5, (a, b)

    # DataLoaderDispatcher: process 0 owns the stream; every process must see
    # its exact slice, in order, across the uneven tail
    def stream():
        for i in range(22):  # not a multiple of the global batch
            yield {"x": np.float32(i)}

    dispatcher = accelerator.prepare_data_loader(stream(), batch_size=4, dispatch_batches=True)
    rows = []
    for batch in dispatcher:
        rows.append(np.asarray(ops.gather(batch["x"])))
    flat = np.concatenate([r.ravel() for r in rows])
    # broadcast ORDER is part of the contract: rank 0 reads the stream and
    # every process must see its exact slice of each batch in stream order —
    # the gathered reconstruction is the original sequence, not a permutation
    assert flat[:20].astype(int).tolist() == list(range(20)), flat[:20]
    # the uneven tail is padded by wrap-around; real rows all appear
    assert set(range(22)) <= set(flat.astype(int).tolist()), sorted(set(flat.astype(int)))

    # gather_for_metrics drops the duplicated tail exactly
    n = state.num_processes * 8 + 3

    class DS2:
        def __len__(self):
            return n

        def __getitem__(self, i):
            return {"x": np.float32(i)}

    loader2 = accelerator.prepare_data_loader(DS2(), batch_size=8)
    seen = []
    for batch in loader2:
        seen.append(np.asarray(accelerator.gather_for_metrics(batch["x"])))
    flat2 = np.concatenate(seen)
    assert len(flat2) == n, (len(flat2), n)
    assert set(flat2.astype(int).tolist()) == set(range(n))

    # checkpoint round trip across ranks: save_state writes on rank 0 only,
    # every rank loads rank 0's directory (shared filesystem on one host)
    import shutil
    import tempfile

    d = broadcast_object_list([tempfile.mkdtemp() if state.is_main_process else None])[0]
    try:
        ckpt = os.path.join(d, "ckpt")
        accelerator.save_state(ckpt)
        saved_a = float(jax.device_get(model.params["a"]))
        # perturb, then restore
        model.params = jax.tree.map(lambda p: p + 1.0, model.params)
        accelerator.load_state(ckpt)
        restored_a = float(jax.device_get(model.params["a"]))
        assert abs(restored_a - saved_a) < 1e-6, (saved_a, restored_a)
        views = ops.gather_object([restored_a])
        assert all(v == views[0] for v in views), views
    finally:
        state.wait_for_everyone()
        if state.is_main_process:
            shutil.rmtree(d, ignore_errors=True)

    # sharded checkpoint across REAL processes: every process writes only its
    # own chunk files; the union reassembles the global tensors regardless of
    # the mesh that wrote them (cross-topology resume, reference FSDP
    # SHARDED_STATE_DICT utils/fsdp_utils.py:85-96). Single-process virtual
    # meshes can't catch a rank writing (or reading) another rank's chunks.
    from jax.sharding import NamedSharding, PartitionSpec as P

    from accelerate_tpu.checkpointing import (
        load_model_weights_sharded,
        save_model_weights_sharded,
    )

    full = np.arange(16 * 8, dtype=np.float32).reshape(16, 8)
    sharding = NamedSharding(state.mesh, P("data"))
    sharded_param = jax.make_array_from_callback(full.shape, sharding, lambda idx: full[idx])
    d2 = broadcast_object_list([tempfile.mkdtemp() if state.is_main_process else None])[0]
    try:
        save_model_weights_sharded({"w": sharded_param}, d2)
        # each process wrote exactly one shard file + index
        shard_files = sorted(
            f for f in os.listdir(d2)
            if ".shard" in f and f.endswith((".npz", ".safetensors"))
        )
        assert len(shard_files) == state.num_processes, sorted(os.listdir(d2))
        # reassembly reads the UNION of all ranks' files → the full tensor,
        # loadable under any other mesh layout
        loaded = load_model_weights_sharded(d2)
        np.testing.assert_array_equal(loaded["w"], full)
        # re-shard under a DIFFERENT topology (column split instead of rows)
        resharding = NamedSharding(state.mesh, P(None, "data"))
        relaid = jax.make_array_from_callback(
            loaded["w"].shape, resharding, lambda idx: loaded["w"][idx]
        )
        local_cols = [np.asarray(s.data) for s in relaid.addressable_shards]
        assert all(c.shape == (16, 1) for c in local_cols), [c.shape for c in local_cols]
    finally:
        state.wait_for_everyone()
        if state.is_main_process:
            shutil.rmtree(d2, ignore_errors=True)

    # telemetry aggregation across REAL processes: per-host metric values
    # must come back as fleet min/max/mean on EVERY host (the collective the
    # hub's flush rides), and the flush itself must emit exactly one jsonl
    # record — from the main process only.
    agg = state.aggregate_metrics({"per_host": float(state.process_index), "same": 7.0})
    n = state.num_processes
    assert agg["per_host"] == {"min": 0.0, "max": float(n - 1), "mean": (n - 1) / 2}, agg
    assert agg["same"]["min"] == agg["same"]["max"] == 7.0, agg

    d3 = broadcast_object_list([tempfile.mkdtemp() if state.is_main_process else None])[0]
    try:
        from accelerate_tpu.telemetry import Telemetry, TelemetryConfig

        telemetry = Telemetry(
            accelerator=accelerator, config=TelemetryConfig(sample_every=2, dir=d3)
        )
        for _ in range(4):
            loss = accelerator.backward(loss_fn, {"x": jnp.ones((4,)), "y": jnp.ones((4,))})
            telemetry.step(loss)
        record = telemetry.flush()  # collective: every host calls it
        assert record["aggregate"]["steps"]["min"] == 4.0, record["aggregate"]["steps"]
        telemetry.finish()
        state.wait_for_everyone()
        if state.is_main_process:
            sink = os.path.join(d3, "telemetry.jsonl")
            lines = [json.loads(l) for l in open(sink)]
            assert lines and lines[0]["metrics"]["steps"] == 4, lines
    finally:
        state.wait_for_everyone()
        if state.is_main_process:
            shutil.rmtree(d3, ignore_errors=True)

    state.wait_for_everyone()
    state.print(json.dumps({"multiprocess_ok": True, "processes": state.num_processes, "devices": state.num_devices}))


if __name__ == "__main__":
    main()
