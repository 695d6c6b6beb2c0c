"""The read kernels' transfer protocol: a read-kernel call makes one
upload (its per-query arrays packed into one int32 array) and one
download (its jitted program's outputs packed into one int32 array).

For every front-end the jitted program returns exactly one array and
the ``kernel.launch`` / ``kernel.fetch`` spans count one transfer each
way; the answers stay bit-identical to scalar ``lookup`` (values above
32 bits, absent keys, batches that pad and batches that do not), and
P-CLHT's fingerprint counts equal the ``probe64_fp_ref`` oracle's over
the real queries alone.
"""

import numpy as np
import pytest

import jax

from repro import obs
from repro.api import Plan, open_index
from repro.core import PMasstree
from repro.core.conditions import PROBE_STAT_KEYS
from repro.core.plan import OpKind
from repro.distributed import ShardedIndex, mesh
from repro.kernels import art_probe, clht_probe, scan
from repro.kernels.art_probe import ops as art_ops
from repro.kernels.clht_probe import ops as clht_ops
from repro.kernels.probe import fp64, gather_chain_windows, probe64_fp_ref
from repro.kernels.probe import ops as probe_ops
from repro.kernels.scan import ops as scan_ops

N_KEYS = 3000
SHARDS = 4
# one query, one short of a block, one past a block (pads to two)
BATCHES = [1, 4095, 4097]


def _put(session_or_index, keys, vals):
    plan = Plan.from_arrays(np.full(keys.size, OpKind.PUT, np.int32),
                            keys, vals)
    assert all(session_or_index.execute(plan).results)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0x9AC4)
    keys = np.unique(rng.integers(1, 1 << 62, N_KEYS))
    vals = rng.integers(1 << 32, 1 << 62, keys.size)  # all above 32 bits
    return keys, vals


@pytest.fixture(scope="module")
def loaded(data):
    """One loaded index per front-end family."""
    keys, vals = data
    out = {}
    for kind in ("clht", "masstree", "art"):
        s = open_index(kind)
        _put(s, keys, vals)
        out[kind] = s.index
    sharded = ShardedIndex(PMasstree, SHARDS)
    _put(sharded, keys, vals)
    out["mesh"] = sharded
    return out


def _queries(keys, n, seed):
    """``n`` queries, about two thirds loaded keys and the rest absent."""
    rng = np.random.default_rng(seed)
    absent = rng.integers(1, 1 << 62, n)
    absent = np.where(np.isin(absent, keys), absent ^ 1, absent)
    return np.where(rng.random(n) < 0.65, rng.choice(keys, n), absent)


def _clht(idx, q):
    return clht_probe.snapshot_lookup(idx.snapshot(), q)


def _probe64(idx, q):
    keys, vals, nxt, n, fps = idx.snapshot().arrays
    return clht_probe.batched_lookup(q, keys, vals, nxt, n_buckets=n,
                                     fps=fps)


def _sorted(idx, q):
    return scan.snapshot_lookup(idx.snapshot(), q)


def _art(idx, q):
    return art_probe.snapshot_lookup(idx.snapshot(), q)


def _mesh(idx, q):
    """``mesh_lookup`` over the shards' sorted runs, scattered back into
    query order."""
    route = idx.route(q)
    parts = [np.flatnonzero(route == s) for s in range(SHARDS)]
    stacked = mesh.build_stacked([idx._shard_sorted_run(s)
                                  for s in range(SHARDS)])
    found = np.zeros(q.size, bool)
    values = np.zeros(q.size, np.int64)
    for part, (f, v) in zip(parts, mesh.mesh_lookup(stacked,
                                                    [q[p] for p in parts])):
        found[part], values[part] = f, v
    return found, values


def _scalar(kind, idx, q):
    if kind == "mesh":
        route = idx.route(q)
        return [idx.shards[int(s)].lookup(int(k)) for k, s in zip(q, route)]
    return [idx.lookup(int(k)) for k in q]


# front-end: (the loaded index it reads, its lookup, the module and the
# name of the jitted program it calls)
FRONTS = {
    "clht": ("clht", _clht, clht_ops, "_gather_probe"),
    "probe64": ("clht", _probe64, probe_ops, "_probe_packed"),
    "sorted_run": ("masstree", _sorted, scan_ops, "scan_window"),
    "art": ("art", _art, art_ops, "art_descend"),
    "mesh": ("mesh", _mesh, mesh, "compiled_probe"),
}


def _record_outputs(monkeypatch, module, name):
    """Wrap ``module.name`` so every program output is kept; the mesh's
    ``compiled_probe`` returns the program, so its result is wrapped."""
    outs = []
    orig = getattr(module, name)

    def keep(out):
        outs.append(out)
        return out

    if name == "compiled_probe":
        def wrapped(*a, **kw):
            fn = orig(*a, **kw)
            return lambda *x: keep(fn(*x))
    else:
        def wrapped(*a, **kw):
            return keep(orig(*a, **kw))
    monkeypatch.setattr(module, name, wrapped)
    return outs


def _sorted_scan(monkeypatch, data):
    keys, vals = data
    prepared = scan.prepare_sorted(keys, vals)
    outs = _record_outputs(monkeypatch, scan_ops, "scan_window")
    starts = _queries(keys, 40, seed=5)
    counts = np.arange(1, 41)
    rows = scan.sorted_scan(starts, counts, prepared)
    want = scan.scan_ref(starts, counts, keys, vals)
    assert rows == want
    return outs


@pytest.mark.parametrize("front", [*FRONTS, "sorted_scan"])
def test_each_read_program_returns_one_array(front, loaded, data,
                                             monkeypatch):
    obs.reset()
    obs.enable()
    try:
        if front == "sorted_scan":
            outs = _sorted_scan(monkeypatch, data)
        else:
            kind, lookup, module, name = FRONTS[front]
            outs = _record_outputs(monkeypatch, module, name)
            lookup(loaded[kind], _queries(data[0], 100, seed=1))
    finally:
        obs.disable()
    assert len(outs) == 1
    (out,) = outs
    assert isinstance(out, jax.Array) and out.dtype == np.int32
    launches = obs.spans("kernel.launch")
    fetches = obs.spans("kernel.fetch")
    assert len(launches) == len(fetches) == 1
    assert launches[0].attrs["arrays"] == 1
    assert fetches[0].attrs["arrays"] == 1
    if front == "mesh":
        assert out.shape[:2] == (SHARDS, 3)
        assert mesh.placement(SHARDS) == ("devices" if len(jax.devices())
                                          >= SHARDS else "one_device")


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("front", list(FRONTS))
def test_front_end_answers_equal_scalar_lookup(front, batch, loaded, data):
    kind, lookup, _, _ = FRONTS[front]
    idx = loaded[kind]
    q = _queries(data[0], batch, seed=batch)
    found, values = lookup(idx, q)
    assert found.dtype == bool and found.shape == (batch,)
    assert values.dtype == np.int64 and values.shape == (batch,)
    scalar = _scalar(kind, idx, q)
    assert found.tolist() == [v is not None for v in scalar]
    assert values.tolist() == [0 if v is None else v for v in scalar]
    if batch > 1:
        assert found.any() and not found.all()
        assert (values[found] >= 1 << 32).all()


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("front", ["clht", "probe64"])
def test_fingerprint_counts_equal_the_oracle(front, batch, loaded, data):
    """The packed download carries the per-query fingerprint counts; the
    front-end sums them over the real queries only, so padded rows never
    count."""
    idx = loaded["clht"]
    keys, vals, nxt, n, fps = idx.snapshot().arrays
    q = _queries(data[0], batch, seed=batch + 1)
    bucket = (clht_ops.mix64(q) % np.uint64(n)).astype(np.int64)
    kwin, vwin, fwin = gather_chain_windows(
        bucket, np.asarray(nxt, np.int64), (keys, vals, fps))
    rf, rv, match, false = probe64_fp_ref(q, kwin, vwin, fp64(q), fwin)
    stats = {k: 0 for k in PROBE_STAT_KEYS}
    if front == "clht":
        found, values = clht_probe.snapshot_lookup(idx.snapshot(), q,
                                                   stats=stats)
    else:
        found, values = clht_probe.batched_lookup(
            q, keys, vals, nxt, n_buckets=n, fps=fps, stats=stats)
    assert np.array_equal(found, rf)
    assert np.array_equal(values, rv)
    assert stats["candidates"] == int(match.sum())
    assert stats["fp_false_positives"] == int(false.sum())
    assert stats["fp_hits"] == int(match.sum() - false.sum())
