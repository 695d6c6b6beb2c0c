"""The mesh read fan-out executed under real ``shard_map`` placement:
four-shard P-Masstree on four virtual CPU devices, driven through
``Session.execute``, against a dict.  Every GET plan and the read-back
after a whole-domain powerfail must equal the dict, and two planted
faults show that the comparison can fail: one shard's group commit
abandoned before the powerfail loses keys, and one altered
``mesh_lookup`` answer gives a wrong result.

The scenarios run in one subprocess, since JAX fixes its device count
when it starts: ``python tests/test_sharded_mesh_devices.py`` with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` prints one JSON
line per scenario.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHARDS = 4
SCENARIOS = ("sound", "abandoned_commit", "altered_answer")
KEY_HIGH = (1 << 63) - 1  # the tree's high key; keys lie below it


def _altered(lookup):
    """``mesh_lookup`` with its first found answer changed, once."""
    left = [1]

    def altered(*args, **kw):
        out = lookup(*args, **kw)
        for found, vals in out:
            if left[0] and found.any():
                left[0] = 0
                vals[int(np.argmax(found))] += 1
        return out

    return altered


def run_scenario(scenario: str) -> dict:
    """Load, read in GET plans, power-fail, read back; returns the
    placement seen and the count of answers that differ from a dict."""
    import jax
    from repro import obs
    from repro.api import Plan, open_index
    from repro.core.plan import OpKind
    from repro.distributed import mesh
    rng = np.random.default_rng(21)
    session = open_index("masstree", shards=SHARDS, mesh_reads=True)
    if scenario == "abandoned_commit":
        pm = session.index.pmems[2]
        pm._close_group = pm._abandon_group
    keys = np.unique(rng.integers(1, KEY_HIGH, 3000))
    rng.shuffle(keys)
    vals = rng.integers(1, 1 << 62, keys.size)
    ref = {}
    for lo in range(0, keys.size, 512):
        k, v = keys[lo:lo + 512], vals[lo:lo + 512]
        res = session.execute(Plan.from_arrays(
            np.full(k.size, OpKind.PUT, np.int32), k, v))
        assert all(res.results)
        ref.update(zip(k.tolist(), v.tolist()))

    def gets(q):
        return session.execute(Plan.from_arrays(
            np.full(q.size, OpKind.GET, np.int32), q, np.zeros_like(q)))

    lookup = mesh.mesh_lookup
    if scenario == "altered_answer":
        mesh.mesh_lookup = _altered(lookup)
    obs.reset()
    obs.enable()
    wrong = 0
    try:
        for _ in range(4):
            q = np.concatenate([rng.choice(keys, 900),
                                rng.integers(1, KEY_HIGH, 100)])
            res = gets(q)
            wrong += sum(r != ref.get(k)
                         for r, k in zip(res.results, q.tolist()))
    finally:
        obs.disable()
        mesh.mesh_lookup = lookup
    spans = obs.RECORDER.spans
    outer = {s.span_id for s in spans if s.name == "shard.mesh_lookup"}
    inner = sorted({s.name for s in spans if s.parent_id in outer})
    lookups = [s.attrs for s in spans if s.name == "shard.mesh_lookup"]
    session.crash()
    back = gets(keys).results
    lost = sum(r != ref[k] for r, k in zip(back, keys.tolist()))
    return {"scenario": scenario, "devices": len(jax.devices()),
            "placements": sorted({a["placement"] for a in lookups}),
            "pads": [[a["q_pad"], a["n_pad"], a["run_max"]]
                     for a in lookups],
            "inner": inner,
            "route_spans": len([s for s in spans
                                if s.name == "shard.route"]),
            "results_spans": len([s for s in spans
                                  if s.name == "shard.results"]),
            "mesh_plans": session.index.stats["mesh_plans"],
            "wrong": int(wrong), "lost": int(lost)}


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                        f"platform_device_count={SHARDS}").strip()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    rows = [json.loads(line) for line in p.stdout.splitlines()]
    return {r["scenario"]: r for r in rows}


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_mesh_reads_on_four_devices_against_a_dict(runs, scenario):
    r = runs[scenario]
    # the shard_map form ran: one device per shard
    assert r["devices"] == SHARDS and r["placements"] == ["devices"]
    assert r["mesh_plans"] >= 5  # four GET plans and the read-back
    if scenario == "sound":
        assert r["wrong"] == 0 and r["lost"] == 0
        assert r["inner"] == ["kernel.fetch", "kernel.launch"]
        assert r["route_spans"] == r["results_spans"] == 4
        for q_pad, n_pad, run_max in r["pads"]:
            assert q_pad >= 256 and n_pad >= run_max >= 3000 // SHARDS // 2
    elif scenario == "abandoned_commit":
        # the plans read the volatile cache; the powerfail loses shard 2
        assert r["wrong"] == 0 and r["lost"] > 0
    else:
        assert r["wrong"] == 1 and r["lost"] == 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(ROOT, "src"))
    for name in SCENARIOS:
        print(json.dumps(run_scenario(name)), flush=True)
