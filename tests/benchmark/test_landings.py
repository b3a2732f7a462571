"""The landing intervals (``benchmark/lib/landings.py``): which steps they keep,
their arithmetic on hand-made rings, the anchor on the trace's clock, the idle
note's attribution, and a traced rehearsal of a serving cell."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.lib import landings, program_spans, trace  # noqa: E402

NEW = ["decode_program_ms_p50.serve", "prefill_device_share.serve", "ready_at_fetch_share.serve"]
MS = 1_000_000
# where each landing ends (ms of the ring's clock): steps 1-10 land programs 0-9, so step k's interval is
# ENDS[k + 1] - ENDS[k]: 12 a plain step, 18 and 15 for steps 2 and 5, which prefill; step 10 has no next
ENDS = [996, 1009, 1021, 1039, 1051, 1063, 1078, 1090, 1102, 1114, 1126]
PREFILLS = {2: [{"request": 7, "span": 64, "tokens": 40, "position": 0}],
            5: [{"request": 8, "span": 32, "tokens": 20, "position": 0}, {"request": 9, "span": 32, "tokens": 9, "position": 0}]}
WINDOW = (50 * MS, 200 * MS)  # the trace's clock: the window opens where step 1 starts


def engine_step(number, in_flight=1, fetches=None, decode=True):
    """Step ``number`` as the ring holds it, in the form of ``test_program_spans.py:hand_made_slice``: admit,
    prefill (its programs), decode dispatch, one fetch a ``(program, ids)`` of ``fetches`` (by default the program
    before its own, ``ready`` 0), deliver. Span ids ``100 × number`` on; it starts 0.6 ms after the last landing."""
    sid, start, end = 100 * number, ENDS[number - 1] + 0.6, ENDS[number]
    at = lambda ms: int(round(ms * MS))  # noqa: E731
    spans = [(sid + 1, sid, "engine.admit", at(start), at(start + 0.1), {"admitted": 0})]
    spans += [(sid + 10 + i, sid + 2, "engine.prefill_dispatch", at(start + 0.2), at(start + 0.3), ids)
              for i, ids in enumerate(PREFILLS.get(number, []))]
    spans.append((sid + 2, sid, "engine.prefill", at(start + 0.1), at(start + 0.5), {"programs": len(PREFILLS.get(number, []))}))
    if decode:
        spans.append((sid + 3, sid, "engine.decode_dispatch", at(start + 0.5), at(start + 1.5), {"in_flight": in_flight}))
    fetches = [(number - 1, {"program": number - 1, "ready": 0})] if fetches is None else fetches
    for i, (_, ids) in enumerate(fetches):  # a second fetch lands 0.1 ms after the first
        spans.append((sid + 4 + i, sid, "engine.fetch", at(start + 1.5 + 0.1 * i), at(end + 0.1 * i), ids))
    tail = end + 0.1 * max(len(fetches) - 1, 0)
    spans.append((sid + 6, sid, "engine.deliver", at(tail), at(tail + 0.3), {"retired": 0}))
    spans.append((sid, 0, "engine.step", at(start), at(tail + 0.4), {"step": number, "tokens": 3}))
    return spans


def ring(**changed):
    """Steps 1-10; ``changed`` maps ``"step<k>"`` to the arguments of that step's :func:`engine_step`."""
    return [s for k in range(1, 11) for s in engine_step(k, **changed.get(f"step{k}", {}))]


def root_fetch(program, start, end):
    """A landing outside ``step()`` (``_land`` with no books): a root ``engine.fetch``."""
    return [(9000 + program, 0, "engine.fetch", int(start * MS), int(end * MS), {"program": program, "ready": 0})]


def ready(program, value):
    return [(program, {"program": program, "ready": value})]


PLAIN, P2, P5 = [], [40], [20, 9]  # the prompt tokens of each kept interval's prefill programs
CASES = {
    # name: (ring, kept intervals' lengths, their prefill programs' tokens, decode_program_ms, prefill share, ready share)
    "steady": (ring(), [12, 18, 12, 12, 15, 12, 12, 12, 12], [PLAIN, P2, PLAIN, PLAIN, P5, *[PLAIN] * 4], 12.0, 100 * 9 / 117, 0.0),
    # step 3 found program 2 landed: step 2's interval ends there and step 3's starts there, both dropped
    "ready_at_either_end": (
        ring(step3={"fetches": ready(2, 1)}), [12, 12, 15, 12, 12, 12, 12], [PLAIN, PLAIN, P5, *[PLAIN] * 4], 12.0, 100 * 3 / 87, 10.0,
    ),
    # the host came late three times: three prefill-free intervals are under the five the median needs
    "mostly_ready": (
        ring(step3={"fetches": ready(2, 1)}, step5={"fetches": ready(4, 1)}, step7={"fetches": ready(6, 1)}),
        [12, 12, 12], [PLAIN] * 3, None, None, 30.0,
    ),
    "in_flight_0": (
        ring(step4={"in_flight": 0}), [12, 18, 12, 15, 12, 12, 12, 12], [PLAIN, P2, PLAIN, P5, *[PLAIN] * 4], 12.0, 100 * 9 / 105, 0.0,
    ),
    # program 3 landed outside step() (a drain): step 3's interval ends at a root, and nothing was queued behind
    # it; step 4 then dispatches with nothing in flight and fetches nothing
    "root_fetch": (
        ring(step4={"in_flight": 0, "fetches": []}) + root_fetch(3, 1040, 1050),
        [12, 18, 15, 12, 12, 12, 12], [PLAIN, P2, P5, *[PLAIN] * 4], 12.0, 100 * 9 / 93, 0.0,
    ),
    # step 4 lands program 3 and its own (a quarantine): two fetches, dropped; step 3's interval ends at the first
    "two_fetches": (
        ring(step4={"fetches": ready(3, 0) + ready(4, 0)}), [12, 18, 12, 15, 12, 12, 12, 12],
        [PLAIN, P2, PLAIN, P5, *[PLAIN] * 4], 12.0, 100 * 9 / 105, 0.0,
    ),
    # step 4 dispatched no decode program (every lane still prefilling, or a speculative step) but landed program 3
    "no_decode": (
        ring(step4={"decode": False}), [12, 18, 12, 15, 12, 12, 12, 12], [PLAIN, P2, PLAIN, P5, *[PLAIN] * 4], 12.0, 100 * 9 / 105, 0.0,
    ),
    # a program from before `engine.fetch` said what landed: no interval, no number
    "parent": ([s if s[2] != "engine.fetch" else (*s[:5], {}) for s in ring()], None, None, None, None, None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_intervals_keep_a_step_between_two_waited_landings_and_the_metrics_read_them(case):
    spans, lengths, programs, decode, share, ready_share = CASES[case]
    found = landings.landings(spans)
    if lengths is None:
        assert found is None
    else:
        assert [k["ms"] for k in found["kept"]] == pytest.approx(lengths)
        assert [[ids["tokens"] for ids in k["prefill"]] for k in found["kept"]] == programs
    assert landings.decode_program_ms(found) == (None if decode is None else pytest.approx(decode))
    assert landings.prefill_share(found) == (None if share is None else pytest.approx(share))
    assert landings.ready_share(found) == (None if ready_share is None else pytest.approx(ready_share))


def test_the_ring_lies_on_the_trace_clock_and_the_idle_note_names_the_span_under_each_gap():
    spans, (start_ns, end_ns) = ring(), WINDOW
    offset = landings.offset_ns(spans, start_ns, end_ns)
    assert offset == start_ns - int(round(996.6 * MS))  # step 1's start, on the window's
    assert landings.offset_ns(spans, start_ns, 170 * MS) is None  # the ring, so laid, would end after the window
    assert landings.offset_ns([s for s in spans if s[2] != "engine.step"], start_ns, end_ns) is None
    on_trace = lambda ring_ms: ring_ms * MS + offset  # noqa: E731

    def device_busy_but(*holes):
        bounds = [start_ns, *(on_trace(t) for hole in holes for t in hole), end_ns]
        return [trace.Event("fusion", lo, hi - lo, "/device:TPU:0", "XLA Ops") for lo, hi in zip(bounds[::2], bounds[1::2])]

    # 0.2 ms idle in step 3's deliver, 1 ms in step 5's fetch, 0.1 ms between steps 6 and 7 (the caller's time)
    idle = landings.idle_by_span(device_busy_but((1039.05, 1039.25), (1055.0, 1056.0), (1078.45, 1078.55)),
                                 spans, offset, start_ns, end_ns)
    assert list(idle) == ["engine.fetch", "engine.deliver", landings.BETWEEN]
    assert list(idle.values()) == pytest.approx([1e-3, 2e-4, 1e-4])
    # a gap across a fetch's end and its deliver's start goes to the span that covered more of it
    assert landings.idle_by_span(device_busy_but((1050.7, 1051.1)), spans, offset, start_ns, end_ns) == pytest.approx(
        {"engine.fetch": 4e-4})
    assert landings.idle_by_span(device_busy_but((1050.9, 1051.2)), spans, offset, start_ns, end_ns) == pytest.approx(
        {"engine.deliver": 3e-4})


@pytest.fixture
def fresh_slice():
    def forget():
        program_spans.slice_spans.cache_clear()
        program_spans.slice_steps.cache_clear()
        landings._of_slice.cache_clear()

    forget()
    yield
    forget()


@pytest.mark.parametrize("program", ["with_ready", "from_before_ready", "keeps_no_spans"])
def test_the_readers_read_the_ring_once_and_say_nothing_of_a_program_that_says_nothing(program, monkeypatch, capsys, fresh_slice):
    """The driver lays these files over the parent's checkout too: its ring has no ``ready``, and older programs
    keep no ring at all."""
    from accelerate_tpu.telemetry import profiler

    spans = ring() if program == "with_ready" else CASES["parent"][0]
    if program == "keeps_no_spans":
        monkeypatch.delattr(profiler, "recorded")
    else:
        monkeypatch.setattr(profiler, "recorded", lambda: [profiler.Span(*s) for s in spans])
    reading = {"window": {}, "trace": {"start_ns": WINDOW[0], "end_ns": WINDOW[1], "ops": []}}
    values = {name: run.read_layer_metric(name, reading) for name in NEW}
    if program == "with_ready":
        assert values == {NEW[0]: 12.0, NEW[1]: pytest.approx(100 * 9 / 117), NEW[2]: 0.0}
    else:
        assert values == dict.fromkeys(NEW)
    notes = [line for line in capsys.readouterr().err.splitlines() if line.startswith("note: device idle by program span")]
    assert len(notes) == 1
    assert ("9 kept landing intervals, 7 of them without prefill" in notes[0]) == (program == "with_ready")


IN_PROCESS = """
import json, sys
sys.path.insert(0, {root!r})
from benchmark import run
code = run.main(sys.argv[1:])
from benchmark.lib import landings, program_spans, trace
spans = program_spans.slice_spans()
worst = None
if "--keep-trace" in sys.argv:
    events = trace.read_events(".bench_trace/" + sys.argv[2])
    start_ns, end_ns = trace.span_window(events, "bench.window")
    # the anchor's one error: the harness's statements between the window's start and the first step's
    lead = min(e.start_ns for e in events if e.plane == trace.HOST_PLANE and e.name == "engine.step") - start_ns
    offset = landings.offset_ns(spans, start_ns, end_ns) + lead
    worst = 0.0
    for name in sorted({{s[2] for s in spans}}):
        copies = sorted((e for e in events if e.plane == trace.HOST_PLANE and e.name == name), key=lambda e: e.start_ns)
        mine = sorted((s for s in spans if s[2] == name), key=lambda s: s[3])
        assert len(copies) == len(mine), name
        for s, e in zip(mine, copies):
            worst = max(worst, abs(s[3] + offset - e.start_ns), abs(s[4] + offset - e.start_ns - e.duration_ns))
    worst = [lead, worst]
print("anchored: " + json.dumps(worst), file=sys.stderr)
sys.exit(code)
"""


@pytest.mark.parametrize("traced", [1, 0])
def test_a_traced_rehearsal_reads_the_landings_and_lays_the_ring_on_the_trace(traced, tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    os.symlink(os.path.join(ROOT, "accelerate_tpu"), tmp_path / "accelerate_tpu")
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "PYTHONPATH")}
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false", OMP_NUM_THREADS="1",
               XLA_FLAGS="--xla_cpu_multi_thread_eigen=false")
    keep = ["--keep-trace"] if traced else []
    done = subprocess.run(
        [sys.executable, "-c", IN_PROCESS.format(root=str(tmp_path)), "--workload", "mistral-7b.serve-chat",
         "--seed", str(2**31 + 19), "--seconds", "1", "--rehearse", "--trace", str(traced), *keep],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    notes = [line for line in done.stderr.splitlines() if line.startswith("note: device idle by program span")]
    if not traced:
        assert not set(NEW) & set(result["metrics"]) and notes == []
        return
    values = {name: result["metrics"].get(name, {}).get("value") for name in NEW}
    # the CPU lands most programs before the host comes for them, how many depends on the machine's load
    assert 0 <= values["ready_at_fetch_share.serve"] <= 100
    kept, plain = (int(w) for w in notes[0].split("; ")[-1].replace(" of them without prefill", "").split(" kept landing intervals, "))
    if plain >= landings.MIN_PLAIN:  # what this rehearsal met, said by the note: read both
        assert values["decode_program_ms_p50.serve"] > 0 and values["prefill_device_share.serve"] is not None
    else:
        assert values["decode_program_ms_p50.serve"] is None and values["prefill_device_share.serve"] is None
    assert len(notes) == 1 and "no device operations in the trace" in notes[0] and kept >= plain
    # the ring, anchored at the window's start, lies within half a ms of its own copies in the kept trace, past the
    # anchor's one error: the time from the window's start to the first step's, which a loaded CPU can stretch
    lead, worst = json.loads(next(line for line in done.stderr.splitlines() if line.startswith("anchored: "))[10:])
    assert 0 <= lead and 0 <= worst < 0.5 * MS
    assert os.path.isdir(tmp_path / ".bench_trace" / "mistral-7b.serve-chat")
