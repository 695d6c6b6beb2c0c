"""Compile the served read path for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, and refuses what the chip would refuse.  The
shapes are those of ``chip_smoke.py``'s phases: a 2^20-key P-CLHT
snapshot, a 2^18-key P-Masstree sorted run and P-ART export, and a
2^18-key four-shard P-Masstree for the mesh fan-out.  Compiling the
mesh probe for four described devices traces its ``shard_map`` body.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import backend
from repro.kernels.art_probe import art_descend
from repro.kernels.clht_probe import ops as clht_ops
from repro.kernels.scan import scan_window
from repro.distributed.mesh import compiled_probe

Q = 4096             # GETs per plan in chip_smoke.py
# measured on the chip_smoke.py loads (seed 0)
CLHT_ROWS = 786_432  # bucket + overflow rows of the 2^20-key P-CLHT table
CLHT_DEPTH = 4       # its longest overflow chain
CHAIN_CAP = 64       # the probe's chain-walk cap
SORTED_RUN = 1 << 18
SCAN_QUERIES = 1024  # 1024 YCSB-E scans pad to whole 512-row blocks
SCAN_WINDOW = 128    # counts 1..100 round up to one lane row
ART_NODES = 286_478  # node pages of the 2^18-key P-ART export
SHARDS = 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """One described chip, with kernels compiled as they are on a TPU
    process (this process's backend is the CPU, which interprets) and
    the persistent compile cache off, since it cannot be read back
    without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(backend, "interpret", lambda: False)
        jax.clear_caches()
        yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()


def spec(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("depth", [CLHT_DEPTH, CHAIN_CAP])
@pytest.mark.parametrize("use_fp", [False, True])
def test_clht_gather_probe_compiles(chip, depth, use_fp):
    """The fused chain gather + probe64 / probe64_fp Pallas kernel, fed
    by one packed query upload and giving one packed output."""
    table = spec((CLHT_ROWS, 3), chip)
    compiled = clht_ops._gather_probe.lower(
        spec((4, Q), chip), table, table, table, table, table,
        spec((CLHT_ROWS,), chip), depth=depth, use_fp=use_fp).compile()
    assert "tpu_custom_call" in compiled.as_text()  # a kernel, compiled
    assert compiled.out_info.shape == (5 if use_fp else 3, Q)


def test_clht_delta_scatter_compiles(chip):
    """The delta export's row scatter into the device form of the
    benchmark's P-CLHT table (2^21 buckets and a 2^20-row overflow
    arena), one patch block: a copy of each array, since nothing is
    donated, and the patch."""
    rows = 3 << 20
    table = spec((rows, 3), chip)
    compiled = clht_ops._scatter_rows.lower(
        (table,) * 5 + (spec((rows,), chip),),
        spec((clht_ops.PATCH_ROWS, clht_ops._PATCH_COLS), chip)).compile()
    mem = compiled.memory_analysis()
    # whole copies out: the stale snapshot's arrays are left as they are
    assert mem.output_size_in_bytes >= 4 * 16 * rows
    assert mem.argument_size_in_bytes >= mem.output_size_in_bytes


@pytest.mark.parametrize("queries,window", [(Q, 1),
                                            (SCAN_QUERIES, SCAN_WINDOW)])
def test_sorted_run_search_compiles(chip, queries, window):
    """Lower bound + window gather over a 2^18-entry run (lookup and
    scan shapes)."""
    run = spec((SORTED_RUN,), chip)
    compiled = scan_window.lower(
        spec((3, queries), chip), run, run, run, run, spec((), chip),
        steps=SORTED_RUN.bit_length(), max_count=window).compile()
    assert compiled.out_info.shape == (5, queries, window)
    # the whole run is an HBM argument of the XLA program
    assert compiled.memory_analysis().argument_size_in_bytes >= \
        4 * 4 * SORTED_RUN


def test_radix_descent_compiles(chip):
    """The P-ART descent over a 2^18-key export's node pages."""
    nodes = spec((ART_NODES,), chip)
    compiled = art_descend.lower(
        spec((8 + 3, Q), chip), spec((ART_NODES, 256), chip),
        *([nodes] * 7)).compile()
    assert compiled.out_info.shape == (6, Q)
    # the child pages are an HBM argument of the XLA program
    assert compiled.memory_analysis().argument_size_in_bytes >= \
        4 * 256 * ART_NODES


def test_mesh_lookup_compiles_on_four_devices(topo, chip):
    """The fused all-shard probe under shard_map, one shard per
    described device: rows of every stacked input on their own chip."""
    mesh = Mesh(np.asarray(topo.devices[:SHARDS]), ("shard",))
    rows = NamedSharding(mesh, P("shard"))
    run_len = SORTED_RUN // SHARDS
    run = spec((SHARDS, run_len), rows)
    q = spec((SHARDS, 2, 2 * Q // SHARDS), rows)
    fn = compiled_probe(run_len.bit_length(), mesh)
    compiled = fn.lower(run, run, run, run, spec((SHARDS,), rows),
                        q).compile()
    assert compiled.out_info.shape == (SHARDS, 3, 2 * Q // SHARDS)
    assert len(compiled.output_shardings.device_set) == SHARDS
