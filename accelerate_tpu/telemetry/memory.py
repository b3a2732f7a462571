"""Per-device HBM watermarks + host RSS.

Polled on the step-timer's sampling cadence (never per step): each sample
reads ``device.memory_stats()`` via the environment helpers and folds it into
run-lifetime watermarks. Two peak notions are kept deliberately distinct:

- ``peak_bytes_in_use``: the allocator's OWN high watermark — catches spikes
  between polls (transient fragmentation, donation double-buffering).
- ``observed_high_bytes``: the max of the *sampled* live bytes — what the
  steady state actually holds, immune to one-off init spikes.

CPU runs expose no device stats; the host RSS
watermark is reported instead so telemetry.jsonl always carries a real memory
signal on every backend.
"""

from __future__ import annotations

from typing import Any, Optional

from ..utils.environment import get_device_memory_info, get_host_memory_info


def state_bytes_per_chip(tree: Any) -> int:
    """Bytes of a state pytree ONE chip holds: the per-device addressable
    shard, not the logical array. Under the ZeRO sharded update the optimizer
    state is 1/N of the replicated layout — this is the accounting that makes
    the saving a telemetry/bench number (``zero_opt_state_bytes_per_chip``)
    instead of a claim; on replicated state it degrades to the full size."""
    import jax

    total = 0
    for leaf in jax.tree.leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            # shards of THIS process's first device: one chip's residency
            device = shards[0].device
            total += sum(s.data.nbytes for s in shards if s.device == device)
        elif hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
    return total


class MemoryMonitor:
    def __init__(self):
        self.samples = 0
        self._per_device: list[dict] = []  # watermarks, index-aligned with local devices
        self._host: dict = {}

    def sample(self) -> None:
        self.samples += 1
        infos = get_device_memory_info()
        for i, info in enumerate(infos):
            if i >= len(self._per_device):
                self._per_device.append(
                    {
                        "bytes_limit": info["bytes_limit"],
                        "live_bytes": info["bytes_in_use"],
                        "observed_high_bytes": info["bytes_in_use"],
                        "peak_bytes_in_use": info["peak_bytes_in_use"],
                    }
                )
                continue
            mark = self._per_device[i]
            mark["bytes_limit"] = info["bytes_limit"]
            mark["live_bytes"] = info["bytes_in_use"]
            mark["observed_high_bytes"] = max(mark["observed_high_bytes"], info["bytes_in_use"])
            mark["peak_bytes_in_use"] = max(mark["peak_bytes_in_use"], info["peak_bytes_in_use"])
        host = get_host_memory_info()
        if host:
            prev_peak = self._host.get("peak_rss_bytes", 0)
            self._host = {**host, "peak_rss_bytes": max(host["peak_rss_bytes"], prev_peak)}

    @property
    def hbm_high_watermark_bytes(self) -> Optional[int]:
        if not self._per_device:
            return None
        return max(d["peak_bytes_in_use"] for d in self._per_device)

    def snapshot(self) -> dict:
        out: dict = {"samples": self.samples}
        if self._per_device:
            out["devices"] = [dict(d) for d in self._per_device]
            out["hbm_high_watermark_bytes"] = self.hbm_high_watermark_bytes
            out["hbm_limit_bytes"] = max(d["bytes_limit"] for d in self._per_device)
        if self._host:
            out["host_rss_bytes"] = self._host.get("rss_bytes")
            out["host_peak_rss_bytes"] = self._host.get("peak_rss_bytes")
        return out
