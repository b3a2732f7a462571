"""Big-model inference: abstract init → device map → streamed execution.

Parity: reference big_modeling.py + hooks.py (§2.5 of SURVEY):
- init_empty_weights (big_modeling.py:56) → ``jax.eval_shape`` abstract init:
  zero bytes allocated, exact shapes/dtypes.
- infer_auto_device_map + dispatch_model (305) + AlignDevicesHook (hooks.py:
  212) → ``dispatch_model`` here returns a ``StreamedModel`` that keeps
  resident components on the TPU and streams cpu/disk layers through HBM with
  an async double buffer. No forward-patching: streaming is explicit in the
  run loop, and the per-layer compute is ONE jit program reused by every
  layer (static shapes — the XLA analogue of the hook's device juggling).
- cpu_offload / disk_offload (169/249) → thin wrappers over dispatch_model.
- load_checkpoint_and_dispatch (498) → same pipeline from a weights file.

Transfer design: each offloaded layer is *packed into one contiguous host
buffer* at dispatch time, so streaming a layer is a single DMA (the reference
moves every tensor separately through AlignDevicesHook — hooks.py:328-358);
unpacking into the nine weight views happens on-device inside the jitted
layer program, where slicing is HBM-bandwidth cheap. Layers stream and
execute in GROUPS (one jit program per group) to amortize per-program
dispatch latency; the group size is derived from ``stream_window_bytes``.

Memory invariant (benchmarks/README.md:44-46): device HBM holds the resident
components + at most two streamed layer *groups* (double buffer) — bounded by
``stream_window_bytes`` (default ``DEFAULT_STREAM_WINDOW_BYTES``); host RAM
holds only the offloaded components (memmap-backed when from disk).
"""

from __future__ import annotations

import os
from typing import Any, Mapping, Optional, Union

import numpy as np

import jax
import jax.numpy as jnp

from .logging import get_logger
from .models.config import TransformerConfig
from .models.llama import Llama
from .utils.modeling import _iter_flat as _flat_items, check_device_map, infer_auto_device_map
from .utils.offload import load_offloaded_weight, offload_weight, save_offload_index

logger = get_logger(__name__)

# default HBM budget for the double-buffered streamed-layer window
DEFAULT_STREAM_WINDOW_BYTES = 512 << 20

# kept for llama HF-name mapping stability; the packer itself is generic
LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate", "w_up", "w_down")


def init_empty_weights(model) -> Any:
    """Abstract parameters: shapes/dtypes with zero allocation.

    The reference monkey-patches nn.Module registration onto the meta device
    (big_modeling.py:121-166); functional init makes this a one-liner.
    """
    return jax.eval_shape(model.init, jax.random.key(0))


init_on_device = init_empty_weights  # parity alias


def _np_dtype(dtype) -> np.dtype:
    """numpy dtype for a jnp scalar type WITHOUT a device round trip.

    ``np.asarray(jnp.zeros((), dtype))`` would run a device op and fetch it
    just to name a dtype.
    """
    return np.dtype(dtype)


def _device_put_packed(buf):
    """One DMA per buffer; quantized layers are (int8 data, fp sidecar) pairs."""
    if isinstance(buf, tuple):
        return tuple(jax.device_put(jnp.asarray(part)) for part in buf)
    return jax.device_put(jnp.asarray(buf))


def _bytes_view(buf) -> list[np.ndarray]:
    """Raw little-endian byte views of a packed host buffer (no copy for
    plain buffers; quantized (q, f) pairs yield two views)."""
    parts = buf if isinstance(buf, tuple) else (buf,)
    return [np.asarray(part).view(np.uint8).ravel() for part in parts]


def _bitcast_u8(u8: jax.Array, dtype) -> jax.Array:
    """Reinterpret a device uint8 buffer as ``dtype`` (on-device, free at
    HBM bandwidth — the XLA analogue of np.view)."""
    itemsize = _np_dtype(dtype).itemsize
    if itemsize == 1:
        return jax.lax.bitcast_convert_type(u8, dtype)
    return jax.lax.bitcast_convert_type(u8.reshape(-1, itemsize), dtype)


def _unflatten(flat: dict[str, Any]) -> dict:
    out: dict = {}
    for key, value in flat.items():
        node = out
        parts = key.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = value
    return out


class LayerPacker:
    """Fixed layout of one transformer layer in a single contiguous buffer.

    Works on ANY stacked-layers pytree (leaves shaped [L, ...]): the layout
    is derived from the tree itself, not from a model family (reference
    hooks.py:212 works on arbitrary modules — this is the analogue). Ordering
    is the sorted flattened key order, identical on pack and unpack.
    """

    def __init__(self, stacked_layers: Any, dtype):
        self.dtype = dtype
        self.shapes: dict[str, tuple] = {
            key: tuple(leaf.shape[1:]) for key, leaf in _flat_items(stacked_layers)
        }
        self.offsets: dict[str, tuple[int, int]] = {}
        offset = 0
        for key, shape in self.shapes.items():
            size = int(np.prod(shape)) if shape else 1
            self.offsets[key] = (offset, size)
            offset += size
        self.total = offset

    @classmethod
    def for_config(cls, cfg: TransformerConfig, dtype) -> "LayerPacker":
        """Layout from a llama config without materializing params (bench)."""
        h, i = cfg.hidden_size, cfg.intermediate_size
        nh, nkv, d = cfg.num_heads, cfg.kv_heads, cfg.dim_per_head
        shapes = {
            "attn_norm": (1, h), "mlp_norm": (1, h),
            "wq": (1, h, nh * d), "wk": (1, h, nkv * d), "wv": (1, h, nkv * d),
            "wo": (1, nh * d, h), "w_gate": (1, h, i), "w_up": (1, h, i), "w_down": (1, i, h),
        }
        return cls({k: np.empty(s, np.int8) for k, s in shapes.items()}, dtype)

    def pack(self, layer: Mapping[str, Any]) -> np.ndarray:
        np_dtype = _np_dtype(self.dtype)
        buf = np.empty((self.total,), np_dtype)
        flat = dict(_flat_items(layer))
        for key, (offset, size) in self.offsets.items():
            buf[offset : offset + size] = np.asarray(flat[key], np_dtype).ravel()
        return buf

    def unpack(self, buf: jax.Array) -> dict[str, jax.Array]:
        """On-device view extraction (static slices; used inside jit)."""
        out = {}
        for key, (offset, size) in self.offsets.items():
            out[key] = buf[offset : offset + size].reshape(self.shapes[key])
        return _unflatten(out)

    @property
    def layer_nbytes(self) -> int:
        """Packed byte footprint of one layer (group-buffer layout unit)."""
        return int(self.total * _np_dtype(self.dtype).itemsize)

    def from_bytes(self, u8: jax.Array) -> dict:
        """Unpack one layer from its raw byte slice of a group buffer
        (on-device bitcast; used inside jit)."""
        return self.unpack(_bitcast_u8(u8, self.dtype))


class _LayerStreamer:
    """Shared streaming machinery: packed layer buffers on device/host/disk,
    iterated with an async double buffer (device_put of layer i+1 is issued
    before layer i's compute is awaited — the H2D copy rides DMA while the
    MXU works)."""

    def __init__(
        self,
        model,
        layer_buffers,
        layer_on_device,
        packer: LayerPacker,
        dtype,
        stream_window_bytes: int = DEFAULT_STREAM_WINDOW_BYTES,
    ):
        self.model = model
        self.layer_buffers = layer_buffers  # packed 1D host buffers (np/memmap) or device arrays
        self.layer_on_device = layer_on_device
        self.packer = packer
        self.dtype = dtype
        self.hf_device_map: dict[str, str] = {}
        # Layers are streamed and EXECUTED in groups: one jitted program per
        # group instead of per layer, so dispatch cost is paid per group.
        # The group size is bounded by the HBM streaming
        # window: peak streaming memory ≈ 2 × group_size × layer_bytes
        # (double buffer), kept under ``stream_window_bytes``.
        self.stream_window_bytes = stream_window_bytes
        layer_bytes = self._layer_bytes()
        per_group = max(1, (stream_window_bytes // 2) // max(layer_bytes, 1))
        self.group_size = int(min(per_group, max(len(layer_buffers), 1)))

    def _layer_bytes(self) -> int:
        """Packed on-device footprint of one layer buffer."""
        return self.packer.layer_nbytes

    def _put(self, buf):
        return _device_put_packed(buf)

    def _put_group(self, idx: list[int]):
        """Stage one group: the offloaded layers' packed bytes concatenate
        into ONE contiguous uint8 host buffer and ride ONE async H2D DMA —
        each transfer pays a fixed cost, so G per-layer puts (2G for
        quantized (q, f) pairs) pay it G times for the same bytes as one
        group put. Splitting back into per-layer
        params happens on device inside the jitted group program
        (packer.from_bytes — static slices + bitcast, HBM-bandwidth cheap).

        Returns ``(u8, resident, pattern)``: the group DMA (None when every
        layer is already on device), the device-resident packed buffers, and
        the static resident/streamed pattern that keys the group program.
        """
        pattern = tuple(bool(self.layer_on_device[i]) for i in idx)
        resident = tuple(self.layer_buffers[i] for i in idx if self.layer_on_device[i])
        host_parts: list[np.ndarray] = []
        for i in idx:
            if not self.layer_on_device[i]:
                host_parts.extend(_bytes_view(self.layer_buffers[i]))
        if not host_parts:
            return None, resident, pattern
        host = host_parts[0] if len(host_parts) == 1 else np.concatenate(host_parts)
        return jax.device_put(jnp.asarray(host)), resident, pattern

    def _group_indices(self) -> list[list[int]]:
        L = len(self.layer_buffers)
        g = self.group_size
        return [list(range(i, min(i + g, L))) for i in range(0, L, g)]

    def _iter_device_layer_groups(self):
        """Yield staged groups, double-buffering: group i's compute is
        dispatched (async) by the caller right after the yield, so group
        i+1's host-side concatenation AND its H2D DMA overlap group i's
        on-device execution."""
        groups = self._group_indices()
        if not groups:
            return
        staged = self._put_group(groups[0])
        for gi in range(len(groups)):
            yield staged
            staged = self._put_group(groups[gi + 1]) if gi + 1 < len(groups) else None


class QuantizedLayerPacker:
    """Layer packer with weight-only int8/int4 quantization (reference
    utils/bnb.py:44 load_and_quantize_model): matrix leaves are quantized per
    output channel into one contiguous int8 buffer; vectors (norms, biases)
    and the per-channel scales ride in a float32 sidecar buffer. ``unpack``
    dequantizes on device inside the jitted layer program (W8A16/W4A16)."""

    def __init__(self, stacked_layers: Any, dtype, bits: int = 8, skip: Optional[list[str]] = None):
        from .utils.quantization import quantize_weight  # noqa: F401 - used in pack

        self.dtype = dtype
        self.bits = bits
        skip = skip or []
        self.shapes: dict[str, tuple] = {
            key: tuple(leaf.shape[1:]) for key, leaf in _flat_items(stacked_layers)
        }
        self.quant_keys = [
            k for k, shape in self.shapes.items() if len(shape) >= 2 and not any(s in k for s in skip)
        ]
        self.full_keys = [k for k in self.shapes if k not in self.quant_keys]

        self.q_offsets: dict[str, tuple[int, int]] = {}
        offset = 0
        for key in self.quant_keys:
            shape = self.shapes[key]
            size = int(np.prod(shape))
            if bits == 4:
                size //= 2
            self.q_offsets[key] = (offset, size)
            offset += size
        self.q_total = offset

        self.f_offsets: dict[str, tuple[int, int]] = {}
        offset = 0
        for key in self.full_keys:
            size = int(np.prod(self.shapes[key])) if self.shapes[key] else 1
            self.f_offsets[key] = (offset, size)
            offset += size
        for key in self.quant_keys:  # per-output-channel scales
            size = self.shapes[key][-1]
            self.f_offsets[f"{key}@scale"] = (offset, size)
            offset += size
        self.f_total = offset

    def pack(self, layer: Mapping[str, Any]):
        from .utils.quantization import quantize_weight

        flat = dict(_flat_items(layer))
        qbuf = np.empty((self.q_total,), np.int8)
        fbuf = np.empty((self.f_total,), np.float32)
        for key in self.quant_keys:
            q, scale = quantize_weight(np.asarray(flat[key]), bits=self.bits)
            offset, size = self.q_offsets[key]
            qbuf[offset : offset + size] = q.ravel()
            f_off, f_size = self.f_offsets[f"{key}@scale"]
            fbuf[f_off : f_off + f_size] = scale
        for key in self.full_keys:
            offset, size = self.f_offsets[key]
            fbuf[offset : offset + size] = np.asarray(flat[key], np.float32).ravel()
        return (qbuf, fbuf)

    @property
    def layer_nbytes(self) -> int:
        """Packed byte footprint (int8 data + fp32 sidecar) of one layer."""
        return int(self.q_total + self.f_total * 4)

    def from_bytes(self, u8: jax.Array) -> dict:
        """Unpack one quantized layer from its byte slice of a group buffer:
        the int8 data and the fp32 sidecar ride ONE buffer (one DMA), split
        and bitcast on device inside the jitted program."""
        q = _bitcast_u8(u8[: self.q_total], jnp.int8)
        f = _bitcast_u8(u8[self.q_total :], jnp.float32)
        return self.unpack((q, f))

    def unpack(self, bufs, quantized_resident: bool = False) -> dict:
        """Unpack one layer. ``quantized_resident=True`` (the kernel-layer
        serving path, ops/quant_matmul.py) keeps 2-D matrix leaves PACKED as
        :class:`~.utils.quantization.QuantizedWeight` instead of
        dequantizing — the fused dequant-matmul then reads them 1
        byte/element and the bf16 shadow never exists. Non-matrix leaves
        and >2-D leaves (MoE expert stacks, consumed by einsum rather than
        the ``dot_fn`` hook) dequantize exactly as before. The buffer
        layout is sliced in ONE place for both modes, so the packed path
        can never drift from the shadowed one."""
        from .utils.quantization import QuantizedWeight, dequantize_weight

        qbuf, fbuf = bufs
        out = {}
        for key in self.quant_keys:
            shape = self.shapes[key]
            offset, size = self.q_offsets[key]
            stored_shape = (shape[0] // 2,) + shape[1:] if self.bits == 4 else shape
            q = qbuf[offset : offset + size].reshape(stored_shape)
            f_off, f_size = self.f_offsets[f"{key}@scale"]
            scale = fbuf[f_off : f_off + f_size]
            if quantized_resident and len(shape) == 2:
                out[key] = QuantizedWeight(q, scale, self.bits, self.dtype)
            else:
                out[key] = dequantize_weight(q, scale, self.bits, self.dtype)
        for key in self.full_keys:
            offset, size = self.f_offsets[key]
            out[key] = fbuf[offset : offset + size].reshape(self.shapes[key]).astype(self.dtype)
        return _unflatten(out)


class StreamedModel(_LayerStreamer):
    """Generic streaming executor for any model exposing the stream protocol:

    - ``stream_prefix(resident, *args, **kwargs) -> carry`` (a pytree)
    - ``stream_layer(carry, layer_params) -> carry``
    - ``stream_suffix(resident, carry) -> output``

    where ``resident`` is the param tree minus ``layers``. The per-layer
    compute is ONE jit program reused by every layer; non-resident layers
    stream through HBM with the async double buffer. This replaces the
    reference's forward-patched AlignDevicesHook on arbitrary modules
    (hooks.py:212-382) without touching the model's code.
    """

    def __init__(
        self, model, resident_flat, layer_buffers, layer_on_device, packer, dtype,
        stream_window_bytes: int = DEFAULT_STREAM_WINDOW_BYTES,
        host_shadow: Optional[dict] = None,
    ):
        super().__init__(
            model, layer_buffers, layer_on_device, packer, dtype,
            stream_window_bytes=stream_window_bytes,
        )
        self.config = getattr(model, "config", None)
        # flat {component: array-or-host-buffer} dict; public because tools
        # and benchmarks introspect resident placement
        self.resident = self._resident_flat = resident_flat
        self._group_fns: dict = {}
        # host copies of device-placed buffers: lets evict() free the HBM
        # without a device→host fetch (see _place_components)
        self._host_shadow = host_shadow or {"resident": {}, "layers": {}}
        self._evicted = False
        # another model's offload hook, run before this model executes
        # (cpu_offload_with_hook pipeline-of-models chaining)
        self._prev_hook: Optional["UserOffloadHook"] = None

    # -- evict / restore (reference cpu_offload_with_hook, big_modeling.py:
    # 215-302: run model A, evict, run model B within one HBM budget) --------

    def evict(self) -> "StreamedModel":
        """Drop every device-resident buffer back to its host copy, freeing
        the HBM this model holds. The placement map is unchanged — the next
        :meth:`restore` (or any execution, which restores implicitly)
        re-uploads exactly the original resident set."""
        if self._evicted:
            return self
        for key, host in self._host_shadow["resident"].items():
            live = self._resident_flat[key]
            if isinstance(live, jax.Array):
                live.delete()
            self._resident_flat[key] = host
        for i, packed in self._host_shadow["layers"].items():
            live = self.layer_buffers[i]
            for part in live if isinstance(live, tuple) else (live,):
                if isinstance(part, jax.Array):
                    part.delete()
            self.layer_buffers[i] = packed
            self.layer_on_device[i] = False
        self._evicted = True
        return self

    def restore(self) -> "StreamedModel":
        """Re-upload the originally device-placed buffers after an evict."""
        if not self._evicted:
            return self
        for key in self._host_shadow["resident"]:
            self._resident_flat[key] = jax.device_put(jnp.asarray(self._resident_flat[key]))
        for i in self._host_shadow["layers"]:
            self.layer_buffers[i] = _device_put_packed(self.layer_buffers[i])
            self.layer_on_device[i] = True
        self._evicted = False
        return self

    def _before_execute(self):
        """Pipeline-of-models choreography: evict the previous model in the
        chain, then make sure this one is resident."""
        if self._prev_hook is not None:
            self._prev_hook.offload()
        if self._evicted:
            self.restore()

    def resident_tree(self) -> dict:
        """Nested resident params, streaming host/disk leaves to the device."""
        return _unflatten(
            {
                key: value if isinstance(value, jax.Array) else self._put(np.asarray(value))
                for key, value in self._resident_flat.items()
            }
        )

    def _jit_cache(self, store_name: str, key, build):
        """Per-concern jit cache, dot_fn-invalidated (utils/jit_cache.py)."""
        from .utils.jit_cache import dot_keyed_jit

        return dot_keyed_jit(self, store_name, key, build, dot_holder=self.model)

    def _iter_group_layers(self, pattern, u8, resident_bufs):
        """Per-layer param trees of one staged group, inside jit: resident
        buffers unpack directly; streamed layers slice the group's byte
        buffer at static offsets and bitcast (packer.from_bytes)."""
        packer = self.packer
        nbytes = packer.layer_nbytes
        ri = off = 0
        for is_resident in pattern:
            if is_resident:
                yield packer.unpack(resident_bufs[ri])
                ri += 1
            else:
                yield packer.from_bytes(u8[off : off + nbytes])
                off += nbytes

    def _get_group_fn(self, pattern: tuple):
        stream_layer = self.model.stream_layer
        iter_layers = self._iter_group_layers

        def build():
            @jax.jit
            def group_fn(carry, u8, resident_bufs):
                for lp in iter_layers(pattern, u8, resident_bufs):
                    carry = stream_layer(carry, lp)
                return carry

            return group_fn

        return self._jit_cache("_group_fns", pattern, build)

    def __call__(self, *args, **kwargs):
        self._before_execute()
        resident = self.resident_tree()
        carry = self.model.stream_prefix(resident, *args, **kwargs)
        for u8, res, pattern in self._iter_device_layer_groups():
            carry = self._get_group_fn(pattern)(carry, u8, res)
        return self.model.stream_suffix(resident, carry)

    # -- streamed KV-cache decode (models exposing the decode protocol:
    #    init_layer_cache / decode_prefix / stream_layer_cached / decode_suffix)

    def _get_decode_prelude(self, max_len: int):
        model = self.model

        def build():
            @jax.jit
            def prelude(resident, current, length):
                carry = model.decode_prefix(resident, current, length, max_len)
                return carry, length + current.shape[1]

            return prelude

        return self._jit_cache("_decode_preludes", max_len, build)

    def _get_decode_group_fn(self, pattern: tuple):
        model = self.model
        iter_layers = self._iter_group_layers

        def build():
            @jax.jit
            def fn(carry, u8, resident_bufs, caches, length):
                new_caches = []
                for lp, c in zip(iter_layers(pattern, u8, resident_bufs), caches):
                    carry, nc = model.stream_layer_cached(carry, lp, c, length)
                    new_caches.append(nc)
                return carry, tuple(new_caches)

            return fn

        return self._jit_cache("_decode_group_fns", pattern, build)

    def _get_decode_tail(self, sampled: bool):
        model = self.model

        def build():
            @jax.jit
            def tail(resident, carry, rng, temperature):
                logits = model.decode_suffix(resident, carry)
                if sampled:
                    rng, sub = jax.random.split(rng)
                    nxt = jax.random.categorical(sub, logits / temperature, axis=-1)
                else:
                    nxt = jnp.argmax(logits, axis=-1)
                return nxt.astype(jnp.int32), rng

            return tail

        return self._jit_cache("_decode_tails", sampled, build)

    def generate(
        self,
        input_ids,
        max_new_tokens: int = 20,
        temperature: float = 0.0,
        rng=None,
        return_device: bool = False,
    ):
        """Streamed KV-cache decode for any model implementing the decode
        protocol: grouped fetch-free decode — tokens accumulate on device
        and convert to numpy in one transfer at the end."""
        if not hasattr(self.model, "stream_layer_cached"):
            raise TypeError(
                f"{type(self.model).__name__} has no streamed-decode protocol "
                "(init_layer_cache/decode_prefix/stream_layer_cached/decode_suffix)"
            )
        self._before_execute()
        input_ids = jnp.asarray(input_ids, jnp.int32)
        b, s = input_ids.shape
        max_len = s + max_new_tokens
        L = len(self.layer_buffers)
        caches = [self.model.init_layer_cache(b, max_len, self.dtype) for _ in range(L)]
        if rng is None:
            rng = jax.random.key(0)
        temp = jnp.asarray(max(temperature, 1e-6), jnp.float32)
        resident = self.resident_tree()
        prelude = self._get_decode_prelude(max_len)
        tail = self._get_decode_tail(temperature > 0.0)
        groups = self._group_indices()

        tokens = [input_ids]
        current = input_ids
        length = jnp.zeros((), jnp.int32)
        for _ in range(max_new_tokens):
            carry, new_length = prelude(resident, current, length)
            for idx, (u8, res, pattern) in zip(groups, self._iter_device_layer_groups()):
                gcaches = tuple(caches[i] for i in idx)
                carry, new_caches = self._get_decode_group_fn(pattern)(
                    carry, u8, res, gcaches, length
                )
                for i, nc in zip(idx, new_caches):
                    caches[i] = nc
            nxt, rng = tail(resident, carry, rng, temp)
            length = new_length
            current = nxt[:, None]
            tokens.append(current)
        out = jnp.concatenate(tokens, axis=1)
        return out if return_device else np.asarray(out)


# kept as a name for the causal-LM dispatch result (historical API); all
# machinery lives on StreamedModel via the model's stream/decode protocols
StreamedCausalLM = StreamedModel


class Seq2SeqStreamedModel(StreamedModel):
    """Streaming executor for encoder-decoder models (T5 family).

    Reference parity: examples/inference/t5.py (pippy PP over T5). The
    full-sequence ``__call__`` path is inherited unchanged (the model's
    stream_prefix runs the encoder). ``generate`` differs from the causal
    loop: ``input_ids`` are ENCODER inputs, run once through a jitted
    resident-encoder program; the decode loop then streams the decoder stack
    per token starting from ``config.decoder_start_token_id``, with the
    encoder output carried into every layer's cross-attention.
    """

    def _get_encoder_fn(self, s_enc: int, has_mask: bool):
        model = self.model

        def build():
            # use_hooks=False: the model may carry a stale mesh-bound
            # enc_pipeline_fn from an earlier prepare_model; the streaming
            # executor is single-device and must not trace that schedule
            if has_mask:
                return jax.jit(
                    lambda resident, ids, am: model.encode(resident, ids, am, use_hooks=False)
                )
            return jax.jit(lambda resident, ids: model.encode(resident, ids, use_hooks=False))

        return self._jit_cache("_encoder_fns", (s_enc, has_mask), build)

    def _get_seq2seq_prelude(self, max_len: int):
        model = self.model

        def build():
            @jax.jit
            def prelude(resident, current, length, enc_out, enc_mask):
                carry = model.decode_prefix(
                    resident, current, length, max_len, enc_out=enc_out, enc_mask=enc_mask
                )
                return carry, length + current.shape[1]

            return prelude

        return self._jit_cache("_decode_preludes", max_len, build)

    def generate(
        self,
        input_ids,
        max_new_tokens: int = 20,
        temperature: float = 0.0,
        rng=None,
        return_device: bool = False,
        attention_mask=None,
    ):
        """Streamed seq2seq decode: one encoder pass, then fetch-free
        KV-cached decoder streaming (tokens accumulate on device). Returns
        the DECODER sequence [B, 1 + max_new_tokens] (start token included)."""
        self._before_execute()
        input_ids = jnp.asarray(input_ids, jnp.int32)
        b = input_ids.shape[0]
        max_len = 1 + max_new_tokens
        L = len(self.layer_buffers)
        caches = [self.model.init_layer_cache(b, max_len, self.dtype) for _ in range(L)]
        if rng is None:
            rng = jax.random.key(0)
        temp = jnp.asarray(max(temperature, 1e-6), jnp.float32)
        resident = self.resident_tree()

        has_mask = attention_mask is not None
        enc_fn = self._get_encoder_fn(input_ids.shape[1], has_mask)
        if has_mask:
            attention_mask = jnp.asarray(attention_mask, jnp.int32)
            enc_out = enc_fn(resident, input_ids, attention_mask)
            enc_mask = attention_mask[:, None, None, :].astype(bool)
        else:
            enc_out = enc_fn(resident, input_ids)
            enc_mask = jnp.ones((b, 1, 1, input_ids.shape[1]), bool)

        prelude = self._get_seq2seq_prelude(max_len)
        tail = self._get_decode_tail(temperature > 0.0)
        groups = self._group_indices()

        current = jnp.full((b, 1), self.config.decoder_start_token_id, jnp.int32)
        tokens = [current]
        length = jnp.zeros((), jnp.int32)
        for _ in range(max_new_tokens):
            carry, new_length = prelude(resident, current, length, enc_out, enc_mask)
            for idx, (u8, res, pattern) in zip(groups, self._iter_device_layer_groups()):
                gcaches = tuple(caches[i] for i in idx)
                carry, new_caches = self._get_decode_group_fn(pattern)(
                    carry, u8, res, gcaches, length
                )
                for i, nc in zip(idx, new_caches):
                    caches[i] = nc
            nxt, rng = tail(resident, carry, rng, temp)
            length = new_length
            current = nxt[:, None]
            tokens.append(current)
        out = jnp.concatenate(tokens, axis=1)
        return out if return_device else np.asarray(out)


def _place_components(params, device_map, offload_dir, dtype, quantization=None):
    """Shared placement: resident leaves + packed per-layer buffers.

    Also returns ``host_shadow`` — host copies of every DEVICE-placed buffer,
    kept so :meth:`StreamedModel.evict` can free the HBM without a
    device→host fetch (the weights already exist on the host here).
    """
    np_dtype = _np_dtype(dtype)

    resident: dict[str, Any] = {}
    host_shadow: dict[str, Any] = {"resident": {}, "layers": {}}
    for key, leaf in _flat_items({k: v for k, v in params.items() if k != "layers"}):
        target = device_map.get(key.replace("/", "."), "device")
        host = np.asarray(leaf, np_dtype)
        if target == "device":
            resident[key] = jax.device_put(jnp.asarray(host))
            host_shadow["resident"][key] = host
        elif target == "cpu":
            resident[key] = host
        elif target == "disk":
            if offload_dir is None:
                raise ValueError(f"device_map places {key} on disk — pass offload_dir")
            os.makedirs(offload_dir, exist_ok=True)
            disk_name = key.replace("/", ".")
            disk_meta = offload_weight(host, disk_name, offload_dir, {})
            resident[key] = load_offloaded_weight(
                os.path.join(offload_dir, f"{disk_name}.dat"), disk_meta[disk_name]
            )
        else:
            raise ValueError(f"Unknown target {target!r} for {key}")

    if quantization is not None:
        packer: Any = QuantizedLayerPacker(
            params["layers"], dtype, bits=quantization.bits, skip=quantization.skip_modules
        )
    else:
        packer = LayerPacker(params["layers"], dtype)
    stacked = {k: np.asarray(v) for k, v in _flat_items(params["layers"])}
    num_layers = next(iter(stacked.values())).shape[0]
    layer_buffers: list[Any] = []
    layer_on_device: list[bool] = []
    disk_index: dict = {}

    def _to_disk(packed, name):
        nonlocal disk_index
        parts = packed if isinstance(packed, tuple) else (packed,)
        loaded = []
        for j, part in enumerate(parts):
            part_name = f"{name}.{j}" if len(parts) > 1 else name
            disk_index = offload_weight(part, part_name, offload_dir, disk_index)
            loaded.append(
                load_offloaded_weight(os.path.join(offload_dir, f"{part_name}.dat"), disk_index[part_name])
            )
        return tuple(loaded) if isinstance(packed, tuple) else loaded[0]

    for i in range(num_layers):
        layer = {k: v[i] for k, v in stacked.items()}
        target = device_map.get(f"layers.{i}", "device")
        packed = packer.pack(layer)
        if target == "device":
            layer_buffers.append(_device_put_packed(packed))
            layer_on_device.append(True)
            host_shadow["layers"][i] = packed
        elif target == "cpu":
            layer_buffers.append(packed)
            layer_on_device.append(False)
        elif target == "disk":
            if offload_dir is None:
                raise ValueError("device_map places layers on disk — pass offload_dir")
            os.makedirs(offload_dir, exist_ok=True)
            layer_buffers.append(_to_disk(packed, f"layers.{i}.packed"))
            layer_on_device.append(False)
        else:
            raise ValueError(f"Unknown target {target!r} for layers.{i}")
    if disk_index:
        save_offload_index(disk_index, offload_dir)
    return resident, packer, layer_buffers, layer_on_device, host_shadow


def dispatch_model(
    model: Any,
    params: Any,
    device_map: dict[str, str] | str = "auto",
    max_memory: Optional[dict] = None,
    offload_dir: Optional[str] = None,
    dtype=jnp.bfloat16,
    quantization=None,  # utils.quantization.QuantizationConfig → W8A16/W4A16 layers
    stream_window_bytes: int = DEFAULT_STREAM_WINDOW_BYTES,  # HBM budget for streamed layer groups
):
    """Place components per ``device_map`` and return the streaming executor.

    Parity: reference dispatch_model (big_modeling.py:305) + hook attachment.
    Any model implementing the stream protocol (``stream_prefix`` /
    ``stream_layer`` / ``stream_suffix``) gets a ``StreamedModel``; models
    with the decode protocol additionally get KV-cache ``generate``.
    """
    if not isinstance(model, Llama) and not hasattr(model, "stream_layer"):
        raise TypeError(
            f"{type(model).__name__} cannot be dispatched: implement the stream "
            "protocol (stream_prefix/stream_layer/stream_suffix) or use a "
            "llama-family model."
        )
    dtype_bytes: float = _np_dtype(dtype).itemsize
    # auto placement sizes layers at their QUANTIZED footprint (resident
    # components stay full precision), or capacity is mis-estimated 2-4x
    layer_dtype_bytes = quantization.bits / 8 if quantization is not None else None
    if isinstance(device_map, str):
        device_map = infer_auto_device_map(
            model, max_memory=max_memory, dtype_bytes=dtype_bytes, layer_dtype_bytes=layer_dtype_bytes
        )
    check_device_map(model, device_map)

    resident, packer, layer_buffers, layer_on_device, host_shadow = _place_components(
        params, device_map, offload_dir, dtype, quantization=quantization
    )

    cls = Seq2SeqStreamedModel if getattr(model, "is_encoder_decoder", False) else StreamedModel
    dispatched = cls(
        model, resident, layer_buffers, layer_on_device, packer, dtype,
        stream_window_bytes=stream_window_bytes, host_shadow=host_shadow,
    )
    dispatched.hf_device_map = dict(device_map)
    return dispatched


def make_layered_device_map(model, layer_target: str) -> dict[str, str]:
    """Device map sending every ``layers.*`` entry to ``layer_target``
    (device/cpu/disk) and every other component to the device — the placement
    rule behind cpu_offload/disk_offload, exported for scripts that want the
    same split explicitly."""
    from .utils.modeling import named_component_sizes

    return {
        key: (layer_target if key.startswith("layers.") else "device")
        for key in named_component_sizes(model)
    }


def cpu_offload(model: Any, params: Any, dtype=jnp.bfloat16):
    """Everything streamed from host RAM (reference big_modeling.py:169)."""
    return dispatch_model(model, params, make_layered_device_map(model, "cpu"), dtype=dtype)


def disk_offload(model: Any, params: Any, offload_dir: str, dtype=jnp.bfloat16):
    """Everything streamed from disk memmaps (reference big_modeling.py:249)."""
    return dispatch_model(model, params, make_layered_device_map(model, "disk"), offload_dir=offload_dir, dtype=dtype)


class UserOffloadHook:
    """User handle to evict a dispatched model (reference UserCpuOffloadHook,
    hooks.py). ``offload()`` frees the model's HBM; the model restores itself
    automatically on its next execution."""

    def __init__(self, streamed: StreamedModel):
        self.model = streamed

    def offload(self) -> None:
        self.model.evict()

    def remove(self) -> None:
        """Detach the chained previous-model hook (parity with the reference's
        remove_hook_from_module semantics)."""
        self.model._prev_hook = None


def cpu_offload_with_hook(
    model: Any,
    params: Any,
    dtype=jnp.bfloat16,
    prev_module_hook: Optional[UserOffloadHook] = None,
) -> tuple[StreamedModel, UserOffloadHook]:
    """Pipeline-of-models offload (reference big_modeling.py:215-302).

    Unlike :func:`cpu_offload` — which streams every layer on every forward —
    the model here is dispatched fully DEVICE-resident and *stays* resident
    across executions; it only leaves the HBM when the returned hook's
    ``offload()`` runs. Chain hooks through ``prev_module_hook`` to run
    several models alternately inside one HBM budget::

        lm1, hook1 = cpu_offload_with_hook(model1, params1)
        lm2, hook2 = cpu_offload_with_hook(model2, params2, prev_module_hook=hook1)
        lm1(x)          # model1 uploads
        lm2(y)          # model1 evicts first, then model2 uploads
        hook2.offload() # free model2 explicitly

    Construction is HBM-free (reference semantics: the model sits on CPU
    until its first forward): the dispatched model starts in the EVICTED
    state with an all-device restore target, so chaining N models never
    holds more than the executing one resident.
    """
    from .utils.modeling import named_component_sizes

    # place everything on the host, then mark the whole set as the evicted
    # image of an all-device placement — the first execution restores it
    all_cpu = {key: "cpu" for key in named_component_sizes(model)}
    dispatched = dispatch_model(model, params, all_cpu, dtype=dtype)
    dispatched._host_shadow = {
        "resident": dict(dispatched._resident_flat),
        "layers": {i: buf for i, buf in enumerate(dispatched.layer_buffers)},
    }
    dispatched._evicted = True
    dispatched._prev_hook = prev_module_hook
    return dispatched, UserOffloadHook(dispatched)


def load_checkpoint_and_dispatch(
    model: Any,
    checkpoint: str,
    device_map: dict[str, str] | str = "auto",
    max_memory: Optional[dict] = None,
    offload_dir: Optional[str] = None,
    dtype=jnp.bfloat16,
    stream_window_bytes: int = DEFAULT_STREAM_WINDOW_BYTES,
) -> StreamedModel:
    """Load weights and dispatch (big_modeling.py:498) for any model
    implementing the stream protocol. Accepts the native flat layout
    ("layers/wq" stacked tensors) for every family; llama models additionally
    accept the HuggingFace/torch layout
    ("model.layers.0.self_attn.q_proj.weight" …), translated (transpose +
    restack) by utils/hf_import.py."""
    from .utils.hf_import import load_checkpoint_in_model

    params = load_checkpoint_in_model(model, checkpoint)
    return dispatch_model(
        model, params, device_map=device_map, max_memory=max_memory, offload_dir=offload_dir,
        dtype=dtype, stream_window_bytes=stream_window_bytes,
    )


def load_and_quantize_model(
    model: Any,
    quantization_config,
    weights_location: Optional[str] = None,
    params: Any = None,
    device_map: dict[str, str] | str = "auto",
    max_memory: Optional[dict] = None,
    offload_dir: Optional[str] = None,
    dtype=jnp.bfloat16,
    stream_window_bytes: int = DEFAULT_STREAM_WINDOW_BYTES,
):
    """Reference utils/bnb.py:44 — load a checkpoint and dispatch with layer
    weights quantized to int8/int4 (per-output-channel scales, dequantized on
    device inside the jitted layer program)."""
    if params is None:
        if weights_location is None:
            raise ValueError("Pass weights_location (a checkpoint) or params.")
        from .utils.hf_import import load_checkpoint_in_model

        params = load_checkpoint_in_model(model, weights_location)
    return dispatch_model(
        model,
        params,
        device_map=device_map,
        max_memory=max_memory,
        offload_dir=offload_dir,
        dtype=dtype,
        quantization=quantization_config,
        stream_window_bytes=stream_window_bytes,
    )
