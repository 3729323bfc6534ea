"""Compiles of the retrieval path for a described TPU v5e.

Nothing runs: each test lowers a jitted step with shapes placed on the
devices of a described ``v5e:2x2`` topology and compiles it with the
TPU compiler, which refuses what the chip would refuse (Pallas lowering
gaps, scoped VMEM over the limit, programs over HBM).  Shapes are the
full retrieval config (``repro.configs.ragdb.FULL``).

The topology is described inside a module fixture, never at import
time: only one process may load the TPU library, and pytest-xdist
workers all import this file.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.ragdb import FULL

HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    # traced with compiled (not interpreted) kernels: keep those jaxprs
    # out of later CPU tests in this worker
    jax.clear_caches()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernel wrappers pick interpret mode from the live (CPU) backend;
    the described chip needs the compiled kernel."""
    from repro.kernels.hsf_score import ops

    monkeypatch.setattr(ops, "_default_interpret", lambda: False)


def _fits_hbm(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total <= HBM_BYTES, m
    return total


@pytest.mark.parametrize("n_docs,batch", [
    (FULL.docs_per_device, FULL.query_batch),  # flat scan of one chip
    (2048, FULL.query_batch),                  # IVF candidate bucket
    (64, 1),                                   # small bucket, probe mode
])
def test_fused_score_topk_compiles(one_chip, compiled_kernels,
                                   n_docs, batch):
    from repro.core.engine import _score_topk_pallas

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _score_topk_pallas.lower(
        s((n_docs, FULL.dim), jnp.float32),
        s((n_docs, FULL.sig_words), jnp.int32),
        s((batch, FULL.dim), jnp.float32),
        s((batch, FULL.sig_words), jnp.int32),
        s((), jnp.int32),
        k=FULL.top_k, alpha=FULL.alpha, beta=FULL.beta,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_hbm(compiled)


def test_sharded_local_topk_compiles_on_four_chips(topo):
    """The ``ivf-sharded`` local top-k as ``chip_smoke.py --chips 4``
    runs it: 4 x 65,536 docs, each shard's resident block padded to the
    next power of two, every row a candidate (exact mode's collapse)."""
    from repro.index.sharded import _mesh_topk_fn

    mesh = Mesh(np.array(topo.devices[:4]), ("shards",))
    rows = 2 * FULL.docs_per_device  # power-of-two bucket over ~N/4
    shard = NamedSharding(mesh, P("shards"))
    repl = NamedSharding(mesh, P())

    def s(shape, dtype, sharding=shard):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    fn = _mesh_topk_fn(mesh, FULL.top_k, FULL.alpha, FULL.beta)
    compiled = fn.lower(
        s((4, rows, FULL.dim), jnp.float32),
        s((4, rows, FULL.sig_words), jnp.int32),
        s((4, rows), jnp.int32),
        s((4, rows), jnp.int32),
        s((4,), jnp.int32),
        s((FULL.query_batch, FULL.dim), jnp.float32, repl),
        s((FULL.query_batch, FULL.sig_words), jnp.int32, repl),
    ).compile()
    per_device = _fits_hbm(compiled)
    assert per_device > rows * FULL.dim * 4  # one f32 resident block
