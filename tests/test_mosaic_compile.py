"""Mosaic accepts the kernels of the main serving path at real widths: compiled
ahead of time for a DESCRIBED v5e (no chip attached, nothing runs), so what the
chip's compiler would refuse fails here first. Interpret mode cannot show this:
it runs any shape and any ref indexing.

Every program is built and compiled in a child process (``spawn``, one a module
and worker) that describes the topology and alone loads the TPU's library: the
test process, an xdist worker among others, never maps it. A case sends the
child a module-level function that builds its program, and its arguments, and
gets back what it asserts on: the program's text and byte counts, or the
message of what refused it. A compile that runs past ``_LIMIT_S`` kills the
child and fails that case only, and where no v5e can be described every case
skips, loudly."""

import multiprocessing
import os
import re
import traceback
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

_LIMIT_S = 180  # a case's compile, or the child's start


class _Compiled:
    """What a case reads of a program compiled in the child: its text and its
    memory analysis's byte counts."""

    def __init__(self, compiled):
        self.text = compiled.as_text()
        memory = compiled.memory_analysis()
        self.memory = types.SimpleNamespace(**{k: getattr(memory, k) for k in dir(memory) if k.endswith("_in_bytes")})

    def as_text(self):
        return self.text

    def memory_analysis(self):
        return self.memory


class _Refused(Exception):
    """What refused a program in the child (Mosaic's message, most often)."""


def _child(conn):
    """Describe a v5e, then compile what the parent sends: ``(build, args,
    kwargs)`` → ``build(sharding, *args, **kwargs)``, its program as
    :class:`_Compiled` (first of a tuple, where ``build`` returns more)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    os.environ["ACCELERATE_PALLAS_INTERPRET"] = "0"  # build the real kernels off-TPU
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        conn.send(("skip", f"no v5e:2x2 topology can be described here: {e}"))
        return
    chip = SingleDeviceSharding(topo.devices[0])
    conn.send(("ready", None))
    while True:
        build, args, kwargs = conn.recv()
        try:
            built = build(chip, *args, **kwargs)
            built = (_Compiled(built[0]), *built[1:]) if isinstance(built, tuple) else _Compiled(built)
            conn.send(("compiled", built))
        except Exception as e:  # the case reads the message; the traceback goes to the captured stderr
            traceback.print_exc()
            conn.send(("refused", str(e)))


class _DescribedChip:
    """The test process's end of the child: ``compile(build, *args, **kwargs)``
    returns what ``build`` made in the child within ``_LIMIT_S``, or raises
    :class:`_Refused` with the message of what refused it."""

    def __init__(self):
        self.process = None

    def start(self):
        spawn = multiprocessing.get_context("spawn")
        self.conn, end = spawn.Pipe()
        self.process = spawn.Process(target=_child, args=(end,), daemon=True)
        self.process.start()
        end.close()
        kind, message = self._receive("describing a v5e")
        if kind == "skip":
            self.close()
            pytest.skip(message)

    def compile(self, build, *args, **kwargs):
        if self.process is None:
            self.start()
        self.conn.send((build, args, kwargs))
        kind, built = self._receive(build.__name__)
        if kind == "refused":
            raise _Refused(built)
        return built

    def _receive(self, what):
        if not self.conn.poll(_LIMIT_S):
            self.close()
            pytest.fail(f"{what} ran past {_LIMIT_S} s in the child, which is killed")
        try:
            return self.conn.recv()
        except EOFError:
            self.process.join(10)
            code = self.process.exitcode
            self.close()
            pytest.fail(f"the child died during {what} (exit code {code})")

    def close(self):
        if self.process is not None:
            self.process.kill()
            self.process.join(10)
            self.conn.close()
            self.process = None


@pytest.fixture(scope="module")
def one_chip():
    chip = _DescribedChip()
    chip.start()
    yield chip
    chip.close()


def test_the_test_process_never_maps_the_tpu_library(one_chip):
    """A case's program is built and compiled in the child: after one, this
    process (an xdist worker beside others) maps no ``libtpu``."""
    text = one_chip.compile(_compile_paged_decode, 4, 2, 64, 16, 1, 8, 128, 8).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    with open("/proc/self/maps") as maps:
        mapped = [line.split()[-1] for line in maps if "libtpu" in line]
    assert not mapped, f"the test process maps {sorted(set(mapped))}"


def _compile_paged_decode(chip, slots, layers, pages, ps, kv, nh, d, pps, dtype=jnp.bfloat16):
    """The engine's slot ``vmap`` of the paged kernel over one stacked pool
    ``[layers, pages, ps, kv, d]``, compiled for the described chip."""
    from accelerate_tpu.ops.paged_attention import paged_decode_attention

    def shape(dims, dtype=dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    def attend(q, kn, vn, tables, lengths, pool_k, pool_v, layer):
        one = lambda q, kn, vn, row, n: paged_decode_attention(q, kn, vn, pool_k, pool_v, row, n, layer)
        return jax.vmap(one)(q, kn, vn, tables, lengths)

    pool = shape((layers, pages, ps, kv, d))
    return jax.jit(attend).lower(
        shape((slots, 1, 1, nh, d)), shape((slots, 1, 1, kv, d)), shape((slots, 1, 1, kv, d)),
        shape((slots, pps), jnp.int32), shape((slots,), jnp.int32), pool, pool, shape((), jnp.int32),
    ).compile()


def _pool_is_viewed_where_it_lies(compiled, layers, pages, page_rows, d):
    """One custom call; the kernel's operand ``[L, P, ps*KV, D]`` is a bitcast
    of the stacked pool, K and V (a page ``[ps, KV, D]`` is, byte for byte, the
    matrix ``[ps*KV, D]``); nothing copies or relays the pool out; no scratch in
    HBM."""
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert len(re.findall(rf"= bf16\[{layers},{pages},{page_rows},{d}\]\S* bitcast\(", text)) == 2
    assert not re.search(rf"= bf16\[({layers},)?{pages},[\d,]+{d}\]\S* (copy|fusion)\(", text), "the pool, or a layer of it, is copied or relaid out"
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_paged_attention_addresses_the_stacked_pool_under_mosaic(one_chip):
    """The serving cell's geometry (mistral-7b: 32 slots, 16 layers of a
    2561-page pool, 8 KV heads of 128, R = 32 query rows against pages of 128
    rows): the page DMA's two-index source ``pool.at[layer, page]`` on the
    4-D view of the HBM ref lowers, both MXU products lower, the slot vmap
    lands in ONE custom call, and nothing around it writes a layer of the
    pool."""
    slots, layers, pages, ps, kv, nh, d, pps = 32, 16, 2561, 16, 8, 32, 128, 80
    compiled = one_chip.compile(_compile_paged_decode, slots, layers, pages, ps, kv, nh, d, pps)
    _pool_is_viewed_where_it_lies(compiled, layers, pages, ps * kv, d)


def _compile_held_experts(chip, tokens, per_slot=False):
    """k-exaone's 16 held experts of 6144 x 2048 under the router's top 8 of
    128: ``tokens`` tokens at once (a prefill chunk), or one a slot under the
    engine's vmap (a decode step)."""
    from accelerate_tpu.models.moe import dropless_experts

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    hidden, width, held, experts = 6144, 2048, 16, 128
    weights = (
        shape((hidden, experts)), shape((experts,), jnp.float32),
        shape((held, hidden, width)), shape((held, hidden, width)), shape((held, width, hidden)),
    )
    routed = lambda x, router, bias, gate, up, down: dropless_experts(x, router, bias, gate, up, down, top_k=8, scaling=2.5, first=0)
    if per_slot:
        return jax.jit(jax.vmap(routed, in_axes=(0, None, None, None, None, None))).lower(shape((tokens, 1, hidden)), *weights).compile()
    return jax.jit(routed).lower(shape((tokens, hidden)), *weights).compile()


def test_the_two_kind_cells_kernels_compile_at_its_geometry(one_chip):
    """``k-exaone.serve-mixed``: 128 slots on ONE full layer's pool of 20,481
    pages, eight query heads a KV head (mistral has four: R = 64 query rows),
    and the held experts' grouped products (XLA's own Mosaic grouped product, a
    chunk of 256 rows at a time) for a prefill chunk's many tokens and for a
    decode step's few."""
    from accelerate_tpu.models.moe import CHUNK_ROWS

    slots, pages, ps, kv, nh, d, pps = 128, 20481, 16, 8, 64, 128, 160
    compiled = one_chip.compile(_compile_paged_decode, slots, 1, pages, ps, kv, nh, d, pps)
    _pool_is_viewed_where_it_lies(compiled, 1, pages, ps * kv, d)

    # a prefill chunk's 512 tokens: 4,096 assignments sorted, a chunk of rows at a time through the grouped products, in a loop
    text = one_chip.compile(_compile_held_experts, 512).as_text()
    assert len(re.findall(rf"= bf16\[{CHUNK_ROWS},\d+\]\S* custom-call\(", text)) == 3
    assert text.count("ragged-dot-metadata = ") == 1 and " while(" in text
    # a decode step's 128 tokens, one a slot under the engine's vmap: ONE batch (the batching rule) of 1,024
    # assignments through the same three products, not 128 batched ones
    text = one_chip.compile(_compile_held_experts, slots, per_slot=True).as_text()
    assert len(re.findall(rf"= bf16\[{CHUNK_ROWS},\d+\]\S* custom-call\(", text)) == 3
    assert text.count("ragged-dot-metadata = ") == 1 and " while(" in text


def test_four_kv_heads_are_pages_of_64_rows_and_the_pool_is_viewed_not_copied(one_chip, monkeypatch):
    """``mellum2.serve-code``: 64 slots on two full layers' pool of 51,201
    pages at 4 KV heads of 128: a page ``[16, 4, 128]`` is the matrix ``[64,
    128]``, no shared-tile geometry, no padded head. Mosaic takes it, the view
    of the stacked pool is a bitcast (no copy of 1.7 GB a launch), and the one
    launch needs no scratch in HBM."""
    monkeypatch.setenv("ACCELERATE_PALLAS_INTERPRET", "0")  # the gates answer for the chip
    from accelerate_tpu.ops.paged_attention import paged_kernel_fallback_reason

    slots, layers, pages, ps, kv, nh, d, pps = 64, 2, 51201, 16, 4, 32, 128, 800
    assert paged_kernel_fallback_reason((pages, ps, kv, d), nh, kv) is None
    compiled = one_chip.compile(_compile_paged_decode, slots, layers, pages, ps, kv, nh, d, pps)
    _pool_is_viewed_where_it_lies(compiled, layers, pages, ps * kv, d)


@pytest.mark.parametrize("kv,ps,group", [
    (1, 16, 8), (1, 8, 1), (2, 4, 8), (3, 16, 2), (6, 4, 1), (12, 2, 1), (8, 1, 4), (16, 16, 2),  # page rows in eights: 16, 8, 8, 48, 24, 24, 8, 256
    (3, 4, 2), (2, 2, 8), (4, 1, 8), (6, 2, 1),  # 12, 4, 4, 12 rows a page
])
def test_the_gate_says_what_mosaic_takes(kv, ps, group, one_chip, monkeypatch):
    """``paged_kernel_fallback_reason`` against the compiler itself, on both
    sides of its one geometric rule (a page's ``ps * KV`` rows in eights, a
    bf16 pool): where it names no reason the kernel compiles, one KV head
    attending a one-token window among them, and where it names one Mosaic
    refuses the page DMA."""
    monkeypatch.setenv("ACCELERATE_PALLAS_INTERPRET", "0")
    from accelerate_tpu.ops.paged_attention import paged_kernel_fallback_reason

    reason = paged_kernel_fallback_reason((64, ps, kv, 128), kv * group, kv)
    if reason is None:
        text = one_chip.compile(_compile_paged_decode, 4, 2, 64, ps, kv, kv * group, 128, 8).as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1
    else:
        with pytest.raises(Exception, match="(?i)mosaic|tiled|memref|pallas"):
            one_chip.compile(_compile_paged_decode, 4, 2, 64, ps, kv, kv * group, 128, 8)


def _compile_mellum2_experts(chip, tokens, per_slot=False):
    """mellum2's 64 experts of 2304 x 896, all held, under a softmax top 8:
    ``tokens`` tokens at once (a prefill span), or one a slot under the
    engine's vmap (a decode step)."""
    from accelerate_tpu.models.moe import dropless_experts

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    hidden, width, experts = 2304, 896, 64
    weights = (shape((hidden, experts)), shape((experts, hidden, width)), shape((experts, hidden, width)), shape((experts, width, hidden)))
    routed = lambda x, router, gate, up, down: dropless_experts(x, router, None, gate, up, down, top_k=8, scoring="softmax_topk")
    if per_slot:
        return jax.jit(jax.vmap(routed, in_axes=(0, None, None, None, None))).lower(shape((tokens, 1, hidden)), *weights).compile()
    return jax.jit(routed).lower(shape((tokens, hidden)), *weights).compile()


def test_mellum2s_experts_compile_as_the_grouped_kernel_named_for_the_trace(one_chip):
    """64 experts of 2304 x 896 all held: a decode step's 512 assignments and a
    prefill span's 8,192 each go through three launches of the Pallas grouped
    matmul (one expert's whole matrix a tile), in ONE chunk, and the custom
    calls carry the scope's name, ``moe.experts``, which the benchmark's
    ``expert_mlp_roofline.serve`` reads the trace by."""
    for tokens, per_slot in ((1024, False), (64, True)):
        text = one_chip.compile(_compile_mellum2_experts, tokens, per_slot).as_text()
        assert len(re.findall(r"%moe\.experts[.\d]* = bf16\[\d+,\d+\]\S* custom-call\(", text)) == 3, tokens
        assert "ragged-dot" not in text


def test_one_kv_head_under_twenty_query_rows_is_a_page_of_16_rows_viewed_not_copied(one_chip, monkeypatch):
    """``jamba2.serve-reasoning``: 256 slots on two attention layers' pool of
    67,585 pages at ONE KV head of 128 under 20 query heads (R = 20 query rows,
    not a multiple of 8): a page ``[16, 1, 128]`` is the matrix ``[16, 128]``.
    The gate names no reason, Mosaic takes it, the view of the stacked pool is a
    bitcast, and the one launch needs no scratch in HBM."""
    monkeypatch.setenv("ACCELERATE_PALLAS_INTERPRET", "0")
    from accelerate_tpu.ops.paged_attention import paged_kernel_fallback_reason

    slots, layers, pages, ps, kv, nh, d, pps = 256, 2, 67585, 16, 1, 20, 128, 264
    assert paged_kernel_fallback_reason((pages, ps, kv, d), nh, kv) is None
    compiled = one_chip.compile(_compile_paged_decode, slots, layers, pages, ps, kv, nh, d, pps)
    _pool_is_viewed_where_it_lies(compiled, layers, pages, ps * kv, d)


def _compile_ssm_scan(chip, lanes, tokens, layers=26, states=16, channels=5120, run=13):
    """``ops/ssm_scan.py`` as the model calls it: a run of layers scanned, each
    advancing its layer of the state by ``tokens`` tokens; ``lanes`` lanes
    under the engine's slot vmap (None: one lane alone, a prefill chunk)."""
    from accelerate_tpu.ops.ssm_scan import ssm_scan

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    def lane(state, dt, du, b, c, a):
        def body(state, layer):
            state, y = ssm_scan(state, layer, layer == 0, dt, du, b, c, a)
            return state, y.sum()

        return jax.lax.scan(body, state, 7 + jnp.arange(run, dtype=jnp.int32))

    lead = () if lanes is None else (lanes,)
    fn = lane if lanes is None else jax.vmap(lane, in_axes=(0, 0, 0, 0, 0, None))
    return jax.jit(fn, donate_argnums=0).lower(
        shape((*lead, layers, states, channels)), shape((*lead, tokens, channels)), shape((*lead, tokens, channels)),
        shape((*lead, tokens, states)), shape((*lead, tokens, states)), shape((states, channels)),
    ).compile()


@pytest.mark.parametrize("lanes,tokens", [(256, 1), (None, 512), (None, 32)], ids=["decode_step_256_lanes", "prefill_chunk_512", "smallest_bucket"])
def test_the_selective_scan_advances_the_stacked_state_where_it_lies(lanes, tokens, one_chip, monkeypatch):
    """The state-space cell's two launches: a decode step (256 lanes, one token
    each: the slot vmap lands in ONE custom call with the lanes on the grid)
    and a prefill chunk (one lane, 512 tokens in chunks of 128 with the state
    resident). The stacked state ``[lanes, 26, 16, 5120]`` float32, 2.2 GB at
    256 lanes, is the call's operand AND its result (aliased), inside the
    layer scan's loop: nothing copies it, a layer of it or a lane of it, and
    the program needs no scratch in HBM beyond the tokens' own operands."""
    monkeypatch.setenv("ACCELERATE_PALLAS_INTERPRET", "0")
    from accelerate_tpu.ops.ssm_scan import ssm_kernel_fallback_reason

    state = (26, 16, 5120) if lanes is None else (lanes, 26, 16, 5120)
    assert ssm_kernel_fallback_reason(state) is None
    compiled = one_chip.compile(_compile_ssm_scan, lanes, tokens)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "%ssm_scan" in text
    dims = ",".join(map(str, state))
    assert not re.search(rf"= f32\[(1,)?{dims}\]\S* (copy|fusion)\(", text), "the stacked state is copied, not aliased"
    assert re.search(r"output_to_operand_aliasing=\{\{0\}: \(\d+, \{\}\)", text), "the call's state result does not alias its state operand"
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 4 * int(np.prod(state)) and memory.temp_size_in_bytes < 32 << 20


# (16, 55808) and (64, 19968): the second channels in 128s past the rule's line at 16 and at 64 states, in tiles of
# 512; the first (55680 and 19840) tile only by 128, and Mosaic takes 45 to 65 s to refuse their unrolled tiles
@pytest.mark.parametrize("states,channels", [(16, 5120), (4, 256), (12, 1000), (16, 32768), (16, 55808), (64, 19968)])
def test_the_scans_gate_says_what_mosaic_takes(states, channels, one_chip, monkeypatch):
    """``ssm_kernel_fallback_reason`` against the compiler itself, on both
    sides of its one rule (a lane's state, in and out, beside eight tokens'
    operands in VMEM): where it names no reason the kernel compiles, a decode
    step and a prefill chunk whose token chunk is cut to fit, states and
    channels that fill no tile among them; where it names one Mosaic refuses."""
    monkeypatch.setenv("ACCELERATE_PALLAS_INTERPRET", "0")
    from accelerate_tpu.ops.ssm_scan import ssm_kernel_fallback_reason

    for lanes, tokens in ((4, 1), (None, 128)):
        if ssm_kernel_fallback_reason((2, states, channels)) is None:
            text = one_chip.compile(_compile_ssm_scan, lanes, tokens, layers=2, states=states, channels=channels, run=1).as_text()
            assert text.count('custom_call_target="tpu_custom_call"') == 1
        else:
            with pytest.raises(Exception, match="(?i)vmem|memory|mosaic"):
                one_chip.compile(_compile_ssm_scan, lanes, tokens, layers=2, states=states, channels=channels, run=1)


def _compile_window_layers_then_ring_write(chip, slots, layers, kv, r, nh, d=128, dtype=jnp.bfloat16):
    """What a decode step does with the window layers' rings, one array
    ``[slots, kv, r, d]`` a layer: every lane attends its own ring of every
    layer (the engine's slot ``vmap`` of ``window_attention``), then the
    step's new entries go into the donated rings through ``ring_write``."""
    from accelerate_tpu.models.exaone_moe import window_attention
    from accelerate_tpu.ops.ring_write import ring_write

    def shape(dims, dtype=dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    def step(q, k, v, wk, wv, lengths, active):
        lane = lambda q, k, v, rk, rv, n: window_attention(q[None], k[None], v[None], rk[None], rv[None], n, r)[0]
        attended = [jax.vmap(lane)(q[w], k[w], v[w], wk[w], wv[w], lengths) for w in range(layers)]
        written = ring_write((*wk, *wv), [x[:, 0] for x in (*k, *v)], lengths, active)
        return attended, written[:layers], written[layers:]

    rings = tuple(shape((slots, kv, r, d)) for _ in range(layers))
    new = tuple(shape((slots, 1, kv, d)) for _ in range(layers))
    return jax.jit(step, donate_argnums=(3, 4)).lower(
        tuple(shape((slots, 1, nh, d)) for _ in range(layers)), new, new, rings, rings, shape((slots,), jnp.int32), shape((slots,), jnp.bool_),
    ).compile()


@pytest.mark.parametrize("slots,layers,kv,r,nh", [(64, 6, 4, 1024, 32), (128, 3, 8, 128, 64)], ids=["mellum2_serve_code", "k_exaone_serve_mixed"])
def test_a_decode_steps_ring_entries_go_in_where_they_lie(slots, layers, kv, r, nh, one_chip, monkeypatch):
    """The two cells with window layers (``mellum2.serve-code``: 64 lanes, 6
    window layers, 4 KV heads, rings of 1024; ``k-exaone.serve-mixed``: 128, 3,
    8, 128): ``window_attention`` reads the rings under XLA, ``ring_write``
    puts the step's entries into all ``2 * layers`` of them in ONE custom call
    whose ring operands are its results (aliased, donated), and nothing in the
    program makes a ring anew: no select over one, no copy or slice of one, no
    update slice into one. The asynchronous copies that bring parts of a ring
    into VMEM ahead of the products that read it (``slice-start``,
    ``copy-start``) are XLA's prefetch, and write no ring in HBM."""
    monkeypatch.setenv("ACCELERATE_PALLAS_INTERPRET", "0")
    from accelerate_tpu.ops.ring_write import ring_write_fallback_reason

    assert ring_write_fallback_reason((slots, kv, r, 128), jnp.bfloat16) is None
    compiled = one_chip.compile(_compile_window_layers_then_ring_write, slots, layers, kv, r, nh)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "%ring_write" in text
    ring = rf"bf16\[{slots},{kv},{r},128\]"
    made = re.findall(rf"= {ring}\{{[^}}]*\}} (select|copy|dynamic-update-slice)\(", text)
    assert not made, f"a ring is made anew by {sorted(set(made))}"
    # a fusion with a ring for its result only views one (a bitcast that the product reading it has fused), and no
    # fused computation ends in an op that would write one
    fused = re.findall(rf"= {ring}\{{[^}}]*\}} fusion\(.*", text)
    assert all("calls=%bitcast_fusion" in line for line in fused), fused
    assert not re.search(rf"ROOT \S+ = {ring}\S* (select|copy|slice|dynamic-slice|dynamic-update-slice|concatenate)\(", text)
    # every ring result of the call aliases the ring operand behind the two prefetched scalars
    for j in range(2 * layers):
        assert f"{{{j}}}: ({2 + j}, {{}})" in text
    # and the program's scratch in HBM (the layers' scores and weights, float32) holds nothing of a ring's size
    memory, ring_bytes = compiled.memory_analysis(), slots * kv * r * 128 * 2
    assert memory.alias_size_in_bytes >= 2 * layers * ring_bytes and memory.temp_size_in_bytes < ring_bytes // 2


def _mistral_widths(layers=2):
    """``mistral-7b-v0.3``'s widths (hidden 4096, 32 query and 8 KV heads of
    128, MLP 14,336, vocabulary 32,768) at ``layers`` layers, its parameters
    as shapes in bf16 on the described chip."""
    from accelerate_tpu.models import Llama
    from accelerate_tpu.models.config import TransformerConfig

    cfg = TransformerConfig(
        arch="llama", vocab_size=32768, hidden_size=4096, intermediate_size=14336, num_layers=layers, num_heads=32,
        num_kv_heads=8, head_dim=128, max_seq_len=32768, rope_theta=1e6, norm_eps=1e-5, tie_embeddings=False,
    )
    return Llama(cfg)


def _as_shapes(tree, chip, dtype=None):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, dtype or x.dtype, sharding=chip), tree)


def _compile_serving_program(chip, program):
    """The engine's ``program`` (``decode`` or ``prefill_512``) at mistral's
    widths, two layers, and the decode attention the engine chose."""
    from accelerate_tpu.serving import ServingEngine

    model = _mistral_widths()
    params = _as_shapes(jax.eval_shape(model._init, jax.random.key(0)), chip, jnp.bfloat16)
    engine = ServingEngine(model, params, num_slots=32, max_len=640, buckets=(32, 512), num_pages=64, use_kernels=True, dtype=jnp.bfloat16)
    engine._donate = True  # as on the chip
    if program == "decode":
        fn, args = engine._paged_decode_program(), engine._decode_arguments(engine._sampling_keys(0))
    else:
        fn, args = engine._paged_prefill_program(512), (np.zeros((1, 512), np.int32), *engine._prefill_arguments(engine.cache.tables[0].copy(), 0, 512, 0))
    return fn.lower(params, *_as_shapes(args, chip)).compile(), engine.kernel_summary()["decode_attention"]


# an op whose result is one layer of a stacked projection weight, wq [1, 4096, 4096] or wk / wv [1, 4096, 1024]
_PROJECTION_WEIGHT = r"bf16\[1,4096,(?:4096|1024)\]\S*"


@pytest.mark.parametrize("program", ["decode", "prefill_512"])
def test_the_serving_programs_read_the_projection_weights_where_they_lie(program, one_chip):
    """The engine's paged decode program (32 lanes, the paged kernel) and its
    512-token prefill program at mistral's widths, two layers: the q/k/v
    products are held in the layout the weights give them
    (``models/llama.py:decoder_layer``), so no program slices a layer's
    ``wq``, ``wk`` or ``wv`` into VMEM and transposes it there. The layouts
    that rotary and attention want fall on the activations."""
    compiled, decode_attention = one_chip.compile(_compile_serving_program, program)
    assert decode_attention == "pallas"
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == (1 if program == "decode" else 0)
    relaid = [name for name, op in re.findall(rf"%(\S+) = {_PROJECTION_WEIGHT} (copy|copy-start|fusion)\(", text)
              if op != "fusion" or "dynamic-slice" in name]
    assert not relaid, f"a projection weight is sliced out or relaid by {relaid}"

