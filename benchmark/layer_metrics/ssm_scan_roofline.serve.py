"""The selective-scan kernel's share of its roofline. The recurrence of a
state-space layer is bound by bytes where it advances many lanes by one token
(a decode step reads and writes every active lane's whole state, float32, once
a layer), so the least time is the bytes the recurrence itself must move over
the HBM peak: the family's ``ssm_scan_bytes``, from the mathematics and not
from the kernel (a (layer, lane)'s state once each way a launch, a token's
``c``, ``delta``, ``B``, ``C`` in and ``y`` out), counted by the engine's
always-on counters over the slice (``ssm_decode_tokens``, ``ssm_prefill_tokens``,
``ssm_prefill_programs``: decode launches and prefill launches alike). It is
divided by the device time of the trace's operations named ``ssm_scan``. A
prefill launch advances one lane by a whole chunk and is bound by the VPU's and
EUP's arithmetic, for which the chip publishes no peak: such launches read low
against bytes, and a slice with more prefill in it reads lower. Nothing to read
where there are no peaks, no such operation ran, or the program keeps no such
counters."""

from benchmark.lib import trace

KERNEL = "ssm_scan"


def read(reading):
    counted, reduced = reading["window"].get("family") or {}, reading["trace"]
    work = getattr(reading["family"], "ssm_scan_bytes", None)
    tokens = counted.get("ssm_decode_tokens", 0) + counted.get("ssm_prefill_tokens", 0)
    if reading["peaks"] is None or work is None or not tokens:
        return None
    seconds, count = trace.seconds_of(reduced["ops"], KERNEL, reduced["start_ns"], reduced["end_ns"])
    if not count or seconds <= 0:
        return None
    moved = work(reading["config"], counted["ssm_decode_tokens"], counted["ssm_prefill_tokens"], counted["ssm_prefill_programs"])
    return 100.0 * moved / reading["peaks"]["hbm_bytes_per_s"] / seconds
