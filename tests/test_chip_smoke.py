"""chip_smoke.py off the chip: it must refuse to report anything without a
TPU, its named CPU rehearsal must run the real phases green with
interpret-mode kernels, and one failed phase must fail the run. (Every
phase at rehearsal size: ``python chip_smoke.py --cpu-rehearsal``, ~40 s —
too long for this suite's budget, which pays for the two serving phases; see
.claude/skills/verify/SKILL.md.)"""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _result_and_summary(text: str) -> tuple[dict, dict]:
    """The contract's last line (exactly ``ok`` and ``device``) and the
    ``[summary]`` line before it."""
    *_, summary_line, last = text.strip().splitlines()
    result = json.loads(last)
    assert set(result) == {"ok", "device"}
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert summary_line.startswith("[summary] ")
    return result, json.loads(summary_line.removeprefix("[summary] "))


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "{" not in proc.stdout  # no JSON result, not even a failed one


@pytest.mark.parametrize(
    "name, kernels",
    [
        ("serve_int8", {"quant_matmul": "interpreted", "paged_attention": "interpreted"}),
        # its op-level check is the one caller of the paged kernel outside the
        # engine and the tests: a change of the kernel's signature shows here
        ("serve", {"paged_attention": "interpreted"}),
    ],
)
def test_cpu_rehearsal_runs_green_with_interpreted_kernels(capsys, name, kernels):
    rc = chip_smoke.main(["--cpu-rehearsal", "--phases", name])
    result, summary = _result_and_summary(capsys.readouterr().out)
    assert rc == 0, summary
    assert result["ok"] is True and summary["rehearsal"]
    assert result["device"]["platform"] == "cpu"  # never mistaken for a chip result
    assert isinstance(result["device"]["kind"], str) and isinstance(result["device"]["count"], int)
    phase = summary["phases"][name]
    assert phase["kernels"] == kernels
    assert phase["steady_state_compiles"] == 0
    assert phase["tokens_equal"] == phase["tokens"]  # fp32 at tiny size: no ties
    assert summary["skipped"]["mesh"] == "not selected"


def test_a_failing_phase_fails_the_run(capsys, monkeypatch):
    def broken(run):
        raise RuntimeError("kernel fell back")

    monkeypatch.setitem(chip_smoke.PHASES, "train", broken)
    rc = chip_smoke.main(["--cpu-rehearsal", "--phases", "train"])
    result, summary = _result_and_summary(capsys.readouterr().out)
    assert rc != 0
    assert result["ok"] is False and summary["failed"] == ["train"]
    assert "kernel fell back" in summary["phases"]["train"]["error"]
