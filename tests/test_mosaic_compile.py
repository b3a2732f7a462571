"""Mosaic accepts the kernels of the main serving path at real widths: compiled
ahead of time for a DESCRIBED v5e (no chip attached, nothing runs), so what the
chip's compiler would refuse fails here first. Interpret mode cannot show this:
it runs any shape and any ref indexing. Kept in ONE file, with the topology
described inside a fixture, so that only the xdist worker handed this file
loads the TPU's library (and skips, loudly, where it cannot)."""

import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as env:  # undone when the module is done
        if "TPU_LOG_DIR" not in os.environ:
            env.setenv("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no libtpu here, or another process holds it
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def test_paged_attention_addresses_the_stacked_pool_under_mosaic(one_chip, monkeypatch):
    """The serving cell's geometry (mistral-7b: 32 slots, 16 layers of a
    2561-page pool, 8 KV heads of 128): the page DMA's two-index source
    ``pool.at[layer, page]`` on the 5-D HBM ref lowers, the slot vmap lands
    in ONE custom call, and nothing around it writes a layer of the pool."""
    monkeypatch.setenv("ACCELERATE_PALLAS_INTERPRET", "0")  # build the real kernel off-TPU
    from accelerate_tpu.ops.paged_attention import paged_decode_attention

    slots, layers, pages, ps, kv, nh, d, pps = 32, 16, 2561, 16, 8, 32, 128, 80

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def attend(q, kn, vn, tables, lengths, pool_k, pool_v, layer):
        one = lambda q, kn, vn, row, n: paged_decode_attention(q, kn, vn, pool_k, pool_v, row, n, layer)
        return jax.vmap(one)(q, kn, vn, tables, lengths)

    pool = shape((layers, pages, ps, kv, d))
    compiled = jax.jit(attend).lower(
        shape((slots, 1, 1, nh, d)), shape((slots, 1, 1, kv, d)), shape((slots, 1, 1, kv, d)),
        shape((slots, pps), jnp.int32), shape((slots,), jnp.int32), pool, pool, shape((), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert not re.search(rf"= bf16\[{pages},{ps},{kv},{d}\]", text), "a layer of the pool is copied out"
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * pages * ps * kv * d  # under one layer's pool
