"""Program analyzer: self-gate + seeded regressions.

The self-gate is the acceptance invariant: the analyzer runs over the repo's
OWN compiled step (bert-tiny) and serving decode programs and must report
zero ERROR findings — donation intact, no fp64 leaks, no warm-loop hazards.
The seeded-regression tests prove the gate has teeth: a deliberately broken
donation, an injected ``.item()`` host sync, and a shape-bucket recompile
must each be caught.

All tier-1-fast on the CPU mesh: donation markers, collective inventories,
and jit-cache events are backend-independent properties of the programs.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from accelerate_tpu import Accelerator
from accelerate_tpu.analysis import (
    CATALOG,
    AnalysisReport,
    Finding,
    HazardSanitizer,
    audit_lowered,
    collective_inventory,
    donation_drop_warning,
    explain_recompile,
    lint_paths,
    lint_source,
    signature_of,
)
from accelerate_tpu.models import Bert, Llama
from accelerate_tpu.serving import ServingEngine
from accelerate_tpu.telemetry import TelemetryConfig

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bert_batch(model, batch_size=8, seq_len=16, sharding=None, seed=0):
    rng = np.random.default_rng(seed)
    batch = {
        "input_ids": jnp.asarray(
            rng.integers(0, model.config.vocab_size, (batch_size, seq_len)), jnp.int32
        ),
        "attention_mask": jnp.ones((batch_size, seq_len), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, 2, (batch_size,)), jnp.int32),
    }
    if sharding is not None:
        batch = {k: jax.device_put(v, sharding) for k, v in batch.items()}
    return batch


# -- the self-gate (acceptance criterion) ------------------------------------


def test_self_gate_compiled_step_zero_errors(tmp_path):
    """The repo's own fused step program must audit clean: every donated
    buffer aliased, no fp64, no oversized constants — and the report must
    land as a {"kind": "analysis"} record in telemetry.jsonl."""
    accelerator = Accelerator(telemetry_config=TelemetryConfig(dir=str(tmp_path)))
    model = Bert("bert-tiny")
    accelerator.prepare_model(model)
    accelerator.prepare_optimizer(optax.adamw(1e-4))
    batch = _bert_batch(model, sharding=accelerator.state.data_sharding())

    report = accelerator.analyze(Bert.loss_fn(model), batch)
    assert report.errors == [], report.render()
    donation = report.inventory["donation"]
    assert donation["declared"] > 0
    assert donation["aliased"] == donation["declared"]
    # the data-parallel grad sync is visible as a diffable collective inventory
    collectives = report.inventory["collectives"]
    assert collectives.get("all_reduce", {}).get("count", 0) >= 1
    assert collectives["all_reduce"]["bytes"] > 0
    # executable-level confirmation: XLA kept the aliases
    assert donation.get("executable_alias_entries", 0) == donation["declared"]
    assert donation.get("alias_bytes", 0) > 0
    accelerator.telemetry.finish()
    records = [
        json.loads(line) for line in open(tmp_path / "telemetry.jsonl", encoding="utf-8")
    ]
    analysis = [r for r in records if r["kind"] == "analysis"]
    assert analysis and analysis[0]["analysis"]["counts"]["error"] == 0


def test_self_gate_serving_decode_zero_errors():
    model = Llama("llama-tiny")
    engine = ServingEngine(model, model.init(jax.random.key(0)), num_slots=2, max_len=32)
    report = engine.analyze(write_record=False)
    assert report.errors == [], report.render()
    # on CPU donation is off by backend string — the audit says so explicitly
    assert any(f.code == "DONATION_DISABLED" for f in report.findings)
    # prefill programs audited too (lowered-only)
    assert any(k.startswith("prefill_") for k in report.inventory)


# -- seeded regressions (the gate has teeth) ----------------------------------


def test_seeded_broken_donation_is_caught():
    """Donate a buffer that cannot alias any output: the analyzer must name
    it. This is exactly the silent failure mode donate_argnums has today."""

    def broken(params, batch):
        return batch.sum() + params.sum()  # params donated, only scalars out

    lowered = jax.jit(broken, donate_argnums=(0,)).lower(
        jnp.ones((64, 64)), jnp.ones((4,))
    )
    report = audit_lowered(lowered, label="seeded_broken")
    assert [f.code for f in report.errors] == ["DONATION_DROPPED"]
    assert report.inventory["donation"]["aliased"] < report.inventory["donation"]["declared"]


def test_executable_level_donation_drop_reaches_report():
    """Donation can survive lowering (jax.buffer_donor) and still be dropped
    by XLA (sharding/layout mismatch). audit_lowered must surface the
    executable-level drop as an ERROR, not just the summary."""

    class FakeExecutable:
        def as_text(self):
            # zero alias entries kept, though lowering kept the donations
            return "HloModule jit_f, input_output_alias={ }, entry_computation_layout=..."

        def memory_analysis(self):
            raise NotImplementedError

        @property
        def input_shardings(self):
            raise NotImplementedError

    def f(p, b):
        return p * 2 + b.sum(), p + 1.0

    lowered = jax.jit(f, donate_argnums=(0,)).lower(jnp.ones((16, 16)), jnp.ones((4,)))
    report = audit_lowered(lowered, compiled=FakeExecutable(), label="exec_drop")
    assert [f_.code for f_ in report.errors] == ["DONATION_DROPPED"]
    assert "executable aliased only 0" in report.errors[0].message
    assert report.inventory["donation"]["aliased"] == 0


def test_seeded_host_sync_is_caught():
    step = jax.jit(lambda x: x * 2.0)
    step(jnp.ones((8,)))  # warm
    with HazardSanitizer(label="test-window") as sanitizer:
        out = step(jnp.ones((8,)))
        _ = float(out.sum())  # the injected hidden sync
    findings = [f for f in sanitizer.report.findings if f.code == "HOST_SYNC"]
    assert findings, sanitizer.report.render()
    assert findings[0].severity == "error"
    # the call site points at THIS file, not jax internals
    assert "test_analysis.py" in (findings[0].path or "")


def test_seeded_recompile_is_caught_and_explained():
    step = jax.jit(lambda x: x * 3.0)
    step(jnp.ones((8,)))  # warm at bucket A
    with HazardSanitizer(label="test-window") as sanitizer:
        watched = sanitizer.watch(step, label="step")
        watched(jnp.ones((8,)))
        watched(jnp.ones((16,)))  # bucket change: forced retrace
    report = sanitizer.report
    recompiles = [f for f in report.findings if f.code == "WARM_RECOMPILE"]
    assert recompiles, report.render()
    # explain_recompile names the exact leaf and the shape transition
    assert sanitizer.recompile_explanations
    summary = sanitizer.recompile_explanations[0]["summary"]
    assert "(8,)" in summary and "(16,)" in summary


def test_sanitizer_catches_cache_miss_with_key():
    from accelerate_tpu.utils.jit_cache import dot_keyed_jit

    class Owner:
        pass

    owner = Owner()
    dot_keyed_jit(owner, "_cache", ("warm",), lambda: 1)
    with HazardSanitizer(label="window") as sanitizer:
        dot_keyed_jit(owner, "_cache", ("warm",), lambda: 1)  # hit: fine
        dot_keyed_jit(owner, "_cache", ("cold", 512), lambda: 2)  # miss
    misses = [f for f in sanitizer.report.findings if f.code == "CACHE_MISS"]
    assert len(misses) == 1
    assert misses[0].data["misses"] == 1
    assert "cold" in str(misses[0].data["recent_miss_keys"])


# -- program audit units -------------------------------------------------------


def test_fp64_leak_detection():
    with jax.enable_x64(True):
        lowered = jax.jit(lambda a: a * 2.0).lower(jnp.ones((4,), jnp.float64))
        report = audit_lowered(lowered, compile=False, label="x64", expect_donation=False)
        assert [f.code for f in report.errors] == ["FP64_LEAK"]
        relaxed = audit_lowered(
            lowered, compile=False, label="x64", expect_donation=False, allow_fp64=True
        )
        assert relaxed.errors == []


def test_large_baked_constant_detection():
    table = jnp.asarray(np.random.default_rng(0).normal(size=(512, 1024)), jnp.float32)

    def closes_over(x):
        return x @ table  # 2 MiB constant baked into the program

    lowered = jax.jit(closes_over).lower(jnp.ones((4, 512)))
    report = audit_lowered(lowered, compile=False, label="const", expect_donation=False)
    large = [f for f in report.findings if f.code == "LARGE_CONSTANT"]
    assert large and large[0].data["largest_bytes"] >= 2 * (1 << 20)


def test_replication_audit_severity_follows_intent():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()), ("data",))
    replicated = NamedSharding(mesh, PartitionSpec())
    big = jax.device_put(jnp.ones((512, 1024)), replicated)  # 2 MiB, replicated
    lowered = jax.jit(lambda p: p * 2.0).lower(big)
    compiled = lowered.compile()
    info = audit_lowered(
        lowered, compiled=compiled, label="repl", expect_donation=False, sharded_intent=False
    )
    assert [f.code for f in info.findings] == ["REPLICATED_PARAM_INFO"]
    assert info.errors == []
    hard = audit_lowered(
        lowered, compiled=compiled, label="repl", expect_donation=False, sharded_intent=True
    )
    assert [f.code for f in hard.errors] == ["REPLICATED_PARAM"]


def test_collective_inventory_parses_both_ir_forms():
    hlo = "\n".join(
        [
            "  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %x), replica_groups={}",
            "  %ag = bf16[8,256]{1,0} all-gather(bf16[1,256]{1,0} %y), dimensions={0}",
        ]
    )
    inv = collective_inventory(hlo)
    assert inv["all_reduce"] == {"count": 1, "bytes": 4096}
    assert inv["all_gather"] == {"count": 1, "bytes": 8 * 256 * 2}
    shlo = '%4 = "stablehlo.reduce_scatter"(%3) : (tensor<64xf32>) -> tensor<8xf32>'
    assert collective_inventory(shlo)["reduce_scatter"] == {"count": 1, "bytes": 32}


def test_collective_inventory_quantized_dtypes():
    """The int8 serving path's collectives (s8/u8 in post-SPMD HLO, i8/ui8
    in StableHLO) and sub-byte s4 must size correctly — a parser that only
    knows float classes silently drops them from the inventory, and from
    every contract built on it."""
    hlo = "\n".join(
        [
            "  %ag = s8[8,256]{1,0} all-gather(s8[1,256]{1,0} %q), dimensions={0}",
            "  %ar = u8[1024]{0} all-reduce(u8[1024]{0} %x), replica_groups={}",
            "  %p = s4[4096]{0} all-gather(s4[512]{0} %w), dimensions={0}",
        ]
    )
    inv = collective_inventory(hlo)
    assert inv["all_gather"] == {"count": 2, "bytes": 8 * 256 + 4096 // 2}
    assert inv["all_reduce"] == {"count": 1, "bytes": 1024}
    shlo = '%2 = "stablehlo.all_gather"(%1) : (tensor<1x64xi8>) -> tensor<8x64xi8>'
    assert collective_inventory(shlo)["all_gather"] == {"count": 1, "bytes": 512}


def test_collective_inventory_async_start_forms():
    """The overlap work's async spellings must inventory like their sync
    forms: every `-start(` opcode counts once (the done is a different
    opcode), sized from the tuple RESULT — first-type sizing would price an
    all-gather at its (smaller) operand shape, and reduce-scatter/all-to-all
    starts used to vanish entirely."""
    hlo = "\n".join(
        [
            "  %ag = (f32[1024]{0}, f32[8192]{0}) all-gather-start(f32[1024]{0} %p), dimensions={0}",
            "  %agd = f32[8192]{0} all-gather-done(f32[8192]{0} %ag)",
            "  %rs = (f32[8192]{0}, f32[1024]{0}) reduce-scatter-start(f32[8192]{0} %q), dimensions={0}",
            "  %rsd = f32[1024]{0} reduce-scatter-done(f32[1024]{0} %rs)",
            "  %aa = (f32[2048]{0}, f32[2048]{0}) all-to-all-start(f32[2048]{0} %r)",
        ]
    )
    inv = collective_inventory(hlo)
    assert inv["all_gather"] == {"count": 1, "bytes": 8192 * 4}
    # reduce-scatter's tuple is (operand, result): max = the 8192 operand —
    # a deliberate over- not under-estimate; the schedule pass prices the
    # matched done exactly
    assert inv["reduce_scatter"] == {"count": 1, "bytes": 8192 * 4}
    assert inv["all_to_all"] == {"count": 1, "bytes": 2048 * 4}


def test_large_baked_constant_quantized_dtypes():
    """A >=1MiB int8 table baked into a program (the int8 serving path's
    dequant scales/tables) must trip LARGE_CONSTANT like a float one."""
    from accelerate_tpu.analysis import constant_audit

    hlo = "  %c = s8[2097152]{0} constant({...})"
    findings = constant_audit(hlo, label="int8_const")
    assert [f.code for f in findings] == ["LARGE_CONSTANT"]
    assert findings[0].data["largest_bytes"] == 2 << 20
    shlo = "  %c = stablehlo.constant dense_resource<w> : tensor<1048576x2xi8>"
    findings = constant_audit(shlo, label="int8_const")
    assert [f.code for f in findings] == ["LARGE_CONSTANT"]
    assert findings[0].data["largest_bytes"] == 2 << 20
    # sub-byte packing: 4M s4 elements are 2 MiB, not 4
    sub = "  %c = s4[4194304]{0} constant({...})"
    findings = constant_audit(sub, label="int4_const")
    assert findings and findings[0].data["largest_bytes"] == 2 << 20


def test_schedule_pass_classifies_overlap():
    """Async pair with independent compute between start and done =
    overlapped; async pair whose done is right behind the start (or a plain
    sync collective) = serialized, its bytes on the critical path."""
    from accelerate_tpu.analysis import collective_schedule

    hlo = "\n".join(
        [
            "ENTRY %main {",
            "  %p = f32[1024]{0} parameter(0)",
            "  %q = f32[1024]{0} parameter(1)",
            "  %ag = f32[8192]{0} all-gather-start(f32[1024]{0} %p), dimensions={0}",
            "  %ind = f32[1024]{0} multiply(f32[1024]{0} %q, f32[1024]{0} %q)",
            "  %agd = f32[8192]{0} all-gather-done(f32[8192]{0} %ag)",
            "  %ar = f32[1024]{0} all-reduce-start(f32[1024]{0} %ind), to_apply=%add",
            "  %ard = f32[1024]{0} all-reduce-done(f32[1024]{0} %ar)",
            "  %sync = f32[512]{0} all-reduce(f32[512]{0} %q), to_apply=%add",
            "}",
        ]
    )
    s = collective_schedule(hlo)
    assert s["total_count"] == 3 and s["async_count"] == 2
    assert s["overlapped_count"] == 1  # the all-gather hid behind %ind
    assert s["serialized_count"] == 2  # back-to-back all-reduce + the sync op
    assert s["overlapped_comm_bytes"] == 8192 * 4
    assert s["serialized_comm_bytes"] == 1024 * 4 + 512 * 4
    per = s["per_kind"]
    assert per["all_gather"]["overlapped_count"] == 1
    assert per["all_reduce"]["serialized_bytes"] == 1024 * 4 + 512 * 4


def test_schedule_pass_dependent_compute_is_not_overlap():
    """Compute that CONSUMES the start's value (directly or transitively)
    hides no latency — it must not count as overlap; nor do data-movement
    ops like copy/reshape sitting between start and done."""
    from accelerate_tpu.analysis import collective_schedule

    hlo = "\n".join(
        [
            "ENTRY %main {",
            "  %p = f32[1024]{0} parameter(0)",
            "  %ag = f32[8192]{0} all-gather-start(f32[1024]{0} %p), dimensions={0}",
            "  %use = f32[8192]{0} multiply(f32[8192]{0} %ag, f32[8192]{0} %ag)",
            "  %chain = f32[8192]{0} add(f32[8192]{0} %use, f32[8192]{0} %use)",
            "  %mv = f32[8192]{0} copy(f32[8192]{0} %p)",
            "  %agd = f32[8192]{0} all-gather-done(f32[8192]{0} %ag)",
            "}",
        ]
    )
    s = collective_schedule(hlo)
    assert s["overlapped_count"] == 0 and s["serialized_count"] == 1


def test_schedule_pass_unmatched_done_is_serialized():
    """An async start whose done the pass cannot pair (async-wrapped in a
    different computation) must classify conservatively as SERIALIZED — the
    walk saw the rest of the computation, not the start→done window, so
    crediting 'overlap' would silently shrink the serialized-comm baseline."""
    from accelerate_tpu.analysis import collective_schedule

    hlo = "\n".join(
        [
            "ENTRY %main {",
            "  %p = f32[1024]{0} parameter(0)",
            "  %q = f32[1024]{0} parameter(1)",
            "  %ag = f32[8192]{0} all-gather-start(f32[1024]{0} %p), dimensions={0}",
            "  %ind = f32[1024]{0} multiply(f32[1024]{0} %q, f32[1024]{0} %q)",
            "}",
        ]
    )
    s = collective_schedule(hlo)
    assert s["total_count"] == 1
    assert s["overlapped_count"] == 0 and s["serialized_count"] == 1
    assert s["serialized_comm_bytes"] == 8192 * 4  # sized from the start

    # real XLA starts are tuple-typed (operand, result): the size must come
    # from the LARGEST type in the result tuple, not the first (the input)
    tup = "\n".join(
        [
            "ENTRY %main {",
            "  %p = f32[1024]{0} parameter(0)",
            "  %ag = (f32[1024]{0}, f32[8192]{0}) all-gather-start(f32[1024]{0} %p), dimensions={0}",
            "}",
        ]
    )
    s = collective_schedule(tup)
    assert s["serialized_count"] == 1
    assert s["serialized_comm_bytes"] == 8192 * 4


def test_explain_recompile_names_the_leaf():
    a = signature_of(({"ids": jnp.ones((4, 8), jnp.int32), "n": 3},))
    b = signature_of(({"ids": jnp.ones((4, 12), jnp.int32), "n": 3},))
    diff = explain_recompile(a, b)
    assert list(diff["changed"]) == ["0/ids"]
    assert "(4, 8)" in diff["summary"] and "(4, 12)" in diff["summary"]
    same = explain_recompile(a, a)
    assert "identical" in same["summary"]
    static = explain_recompile(
        signature_of(({"n": 3},)), signature_of(({"n": 4},))
    )
    assert "static:3" in str(static["changed"])


def test_explain_recompile_names_weak_type_flip():
    """A Python-scalar-born array (weak dtype) and an explicit one share
    shape AND dtype but are different trace keys — the signature must carry
    the weak-type bit so the diff names the culprit leaf instead of
    reporting "identical abstract signatures"."""
    weak = jnp.asarray(1.0)  # Python float: weak f32
    strong = jnp.float32(1.0) * jnp.ones(())  # committed f32
    assert weak.aval.weak_type and not strong.aval.weak_type
    a = signature_of(({"lr": weak},))
    b = signature_of(({"lr": strong},))
    assert a["0/lr"].endswith("/weak") and not b["0/lr"].endswith("/weak")
    diff = explain_recompile(a, b)
    assert list(diff["changed"]) == ["0/lr"]
    assert "weak" in diff["summary"]
    assert "identical" not in diff["summary"]


def test_donation_drop_warning_branches():
    assert donation_drop_warning(0, 0, "tpu") is None
    assert donation_drop_warning(4, 4, "tpu") is None
    dropped = donation_drop_warning(4, 1, "tpu")
    assert dropped["event"] == "donation_dropped"
    assert "1/4" in dropped["message"]


# -- eager-path donation (optimizer.py) ---------------------------------------


def test_optimizer_verify_donation():
    class Linear:
        def init(self, rng):
            return {"w": jnp.ones((32, 32)), "b": jnp.zeros((32,))}

        def apply(self, params, x):
            return x @ params["w"] + params["b"]

    accelerator = Accelerator()
    model = accelerator.prepare_model(Linear())
    optimizer = accelerator.prepare_optimizer(optax.adam(1e-3))
    report = optimizer.verify_donation()
    assert report.errors == [], report.render()
    donation = report.inventory["donation"]
    assert donation["declared"] > 0
    assert donation["aliased"] == donation["declared"]


# -- serving donation consult (engine satellite) ------------------------------


class _TelemetryStub:
    """Just enough hub for the engine: a compile tracker + record capture."""

    def __init__(self):
        from accelerate_tpu.telemetry import CompileTracker

        self.compiles = CompileTracker().start()
        self.records = []

    def write_record(self, kind, payload):
        self.records.append({"kind": kind, **payload})
        return self.records[-1]


def test_engine_consults_donation_after_first_compile():
    model = Llama("llama-tiny")
    telemetry = _TelemetryStub()
    engine = ServingEngine(
        model, model.init(jax.random.key(0)), num_slots=2, max_len=32, telemetry=telemetry
    )
    engine._donate = False  # CPU default: consult is a no-op
    engine.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=2)
    engine.run()
    assert engine._donation_checked
    assert not [r for r in telemetry.records if r["kind"] == "analysis"]

    # donation requested (the TPU/GPU path, verifiable on CPU too): the
    # engine must consult the audit once and record the verdict
    engine2 = ServingEngine(
        model, model.init(jax.random.key(1)), num_slots=2, max_len=32, telemetry=telemetry
    )
    engine2._donate = True
    engine2.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=2)
    engine2.run()
    verdicts = [r for r in telemetry.records if r["kind"] == "analysis"]
    assert verdicts and verdicts[0]["event"] == "donation_verified"
    assert verdicts[0]["declared"] == verdicts[0]["aliased"] > 0


# -- telemetry: steady-state recompile record with signature diff -------------


def test_compile_record_carries_signature_diff(tmp_path):
    accelerator = Accelerator(
        telemetry_config=TelemetryConfig(dir=str(tmp_path), sample_every=2)
    )
    model = Bert("bert-tiny")
    accelerator.prepare_model(model)
    accelerator.prepare_optimizer(optax.adamw(1e-4))
    step = accelerator.compiled_step(Bert.loss_fn(model))
    telemetry = accelerator.telemetry
    batch_a = _bert_batch(model, seq_len=16)
    for _ in range(3):
        telemetry.step(step(batch_a))
    batch_b = _bert_batch(model, seq_len=24)  # steady-state shape change
    telemetry.step(step(batch_b))
    telemetry.finish()
    records = [
        json.loads(line) for line in open(tmp_path / "telemetry.jsonl", encoding="utf-8")
    ]
    compiles = [r for r in records if r["kind"] == "compile"]
    assert compiles, [r["kind"] for r in records]
    explain = compiles[-1]["explain"]
    changed = " ".join(explain["changed"])
    assert "input_ids" in changed
    assert "(8, 16)" in explain["summary"] and "(8, 24)" in explain["summary"]


# -- source lint ---------------------------------------------------------------

_HAZARD_SOURCE = '''
import time, random
import numpy as np
import jax

@jax.jit
def step(params, batch):
    t = time.time()
    r = random.random()
    u = np.random.uniform()
    v = batch.sum().item()
    w = np.asarray(batch)
    if params > 0:
        pass
    while batch:
        break
    print(w)
    results.append(w)
    global counter
    return params

def loss(params, batch):
    return float(batch)

grad = jax.value_and_grad(loss)
'''


def test_lint_catches_every_hazard_class():
    findings = lint_source(_HAZARD_SOURCE, "hazards.py")
    codes = {f.code for f in findings}
    assert {
        "HOST_TIME", "HOST_RANDOM", "LINT_HOST_SYNC", "TRACED_BRANCH",
        "TRACE_PRINT", "CAPTURED_MUTATION_CALL", "CAPTURED_MUTATION", "HOST_CAST",
    } <= codes
    # both the decorated fn and the one passed to value_and_grad are scoped
    assert any("hazards.py:23" in (f.path or "") for f in findings)


def test_lint_jax_random_is_not_host_random():
    source = '''
import jax
from jax import random

@jax.jit
def step(params, key):
    noise = random.normal(key, params.shape)   # the keyed idiom IS the fix
    return params + noise
'''
    assert lint_source(source, "keyed.py") == []
    aliased = source.replace("from jax import random", "from jax import random as jrandom").replace(
        "random.normal", "jrandom.normal"
    )
    assert lint_source(aliased, "keyed2.py") == []
    # numpy's random module stays flagged
    source_np = source.replace("from jax import random", "from numpy import random")
    assert [f.code for f in lint_source(source_np, "np.py")] == ["HOST_RANDOM"]


def test_lint_parse_error_has_its_own_code():
    findings = lint_source("def broken(:\n", "bad.py")
    assert [f.code for f in findings] == ["PARSE_ERROR"]
    assert findings[0].severity == "warning"
    assert "could not parse" in findings[0].message


def test_sanitizer_records_h2d_guard_trip():
    step = jax.jit(lambda x: x + 1.0)
    step(jnp.ones((4,)))  # warm (device-committed input)
    with pytest.raises(Exception, match="host-to-device"):
        with HazardSanitizer(label="h2d", transfer_guard="disallow") as sanitizer:
            step(np.ones((4,), np.float32))  # implicit per-call H2D upload
    trips = [f for f in sanitizer.report.findings if f.code == "H2D_TRANSFER"]
    assert trips and "test_analysis.py" in trips[0].path


def test_lint_safe_patterns_not_flagged():
    source = '''
import jax
import jax.numpy as jnp

@jax.jit
def step(params, batch, mask):
    if mask is None:                 # static structure check
        mask = jnp.ones_like(batch)
    if batch.ndim > 2:               # shapes are trace-time constants
        batch = batch.reshape(batch.shape[0], -1)
    acc = []
    acc.append(batch)                # locally bound: not captured state
    updates, state = tx.update(batch, params)   # consumed result: functional
    return updates

def helper(x):
    import time
    return time.time()               # NOT traced: no finding
'''
    assert lint_source(source, "clean.py") == []


def test_lint_pragma_waivers():
    source = '''
import time
import jax

@jax.jit
def line_waived(params):
    return time.time()  # accel-lint: disable=HOST_TIME

@jax.jit
def fn_waived(params):  # accel-lint: disable=all
    t = time.time()
    return params.sum().item()

@jax.jit
def not_waived(params):
    return time.time()
'''
    findings = lint_source(source, "waived.py")
    assert len(findings) == 1
    assert "waived.py:16" in findings[0].path


def test_lint_detects_all_traced_entry_forms():
    source = '''
import jax
from functools import partial
import time

@partial(jax.jit, static_argnums=(1,))
def decorated(x, n):
    return time.time()

def by_call(x):
    return time.time()

jitted = jax.jit(by_call)

def scanned(carry, x):
    return carry, time.time()

jax.lax.scan(scanned, 0, None)

factory = jax.jit(donate_argnums=(0,))(lambda x: time.time())
'''
    findings = lint_source(source, "forms.py")
    assert len([f for f in findings if f.code == "HOST_TIME"]) == 4


def test_repo_lint_gate_zero_unwaived_findings():
    """Satellite gate: the repo's own code and examples stay lint-clean —
    any new finding must be fixed or explicitly waived with a pragma. Every
    waiver must NAME its code (no blanket ``disable=all``), and — enforced
    by the LINT_WAIVER_UNUSED audit inside lint_paths itself — every waiver
    must still be suppressing something."""
    from accelerate_tpu.analysis.lint import PRAGMA_RE, iter_python_files

    lint_targets = [
        os.path.join(REPO_ROOT, "accelerate_tpu"),
        os.path.join(REPO_ROOT, "examples"),
        os.path.join(REPO_ROOT, "bench.py"),
    ]
    report = lint_paths(lint_targets)
    assert report.findings == [], report.render()
    assert report.inventory["files_scanned"] > 50

    for path in iter_python_files(lint_targets):
        with open(path, encoding="utf-8") as f:
            for lineno, line in enumerate(f, start=1):
                m = PRAGMA_RE.search(line)
                if m:
                    codes = {c.strip().upper() for c in m.group(1).split(",")}
                    assert "ALL" not in codes, f"{path}:{lineno} blanket waiver"


# -- findings / report / catalog ----------------------------------------------


def test_finding_defaults_from_catalog():
    finding = Finding("HOST_SYNC", "msg")
    assert finding.severity == "error"
    assert finding.fix_hint
    report = AnalysisReport(findings=[finding, Finding("CACHE_MISS", "m2")])
    assert report.has_errors and len(report.warnings) == 1
    assert report.counts()["error"] == 1
    assert report.to_dict()["findings"][0]["code"] == "HOST_SYNC"  # severity-sorted


def test_docs_catalog_in_sync():
    """docs/analysis.md documents every finding ID (single source: CATALOG)."""
    doc = open(os.path.join(REPO_ROOT, "docs", "analysis.md"), encoding="utf-8").read()
    for code in CATALOG:
        assert code in doc, f"finding {code} missing from docs/analysis.md"


# -- CLI ----------------------------------------------------------------------


def test_cli_analyze_lint_exit_codes(tmp_path, capsys):
    from accelerate_tpu.commands.cli import main

    bad = tmp_path / "bad.py"
    bad.write_text(
        "import jax, time\n@jax.jit\ndef f(x):\n    return time.time()\n"
    )
    assert main(["analyze", str(bad)]) == 1
    out = capsys.readouterr().out
    assert "HOST_TIME" in out
    good = tmp_path / "good.py"
    good.write_text("import jax\n@jax.jit\ndef f(x):\n    return x * 2\n")
    assert main(["analyze", str(good)]) == 0
    capsys.readouterr()
    assert main(["analyze", str(good), "--json"]) == 0
    assert json.loads(capsys.readouterr().out)[0]["counts"]["error"] == 0
