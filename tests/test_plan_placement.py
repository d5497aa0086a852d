"""Plan placement for the distributed engines: a plan whose policy is
``mode="distributed"`` is placed once, when it is built, with its rows
sharded ``P("graph")`` over a mesh chosen from the device count, the
plan's bytes and one device's memory; every wave then runs on it as it
lies.  The four-device checks run in one subprocess on virtual CPU
devices (``--xla_force_host_platform_device_count=4``)."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import api, obs
from repro.core import engine as eng
from repro.core import graph as G
from repro.core import placement as PL
from repro.kernels import ref
from repro.serve.graph import PlanStore

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the graph extent, from bytes alone --------------------------------------

# a plan of 64 row-blocks of 1,000 bytes in groups of 4; a query holds
# 100 bytes for each row of a step (4 rows) and nothing else
SHAPE = dict(rows=64, row_bytes=1000, group_rows=4, query_row_bytes=100,
             query_bytes=0)


@pytest.mark.parametrize("limit,extent", [
    (None, 1),          # no limit: factor_query_axis's floor (8 queries)
    (100_000, 1),       # 64 * 1000 + 2 * 4 * 100 = 64,800 fits 75,000
    (60_000, 2),        # 32 * 1000 + 4 * 4 * 100 = 33,600 fits 45,000
    (40_000, 4),        # 16 * 1000 + 8 * 4 * 100 = 19,200 fits 30,000
    (100, 4),           # nothing fits: every device takes a share
])
def test_graph_extent_grows_until_the_share_fits(limit, extent):
    assert PL.HEADROOM == 0.75
    assert PL.graph_extent(**SHAPE, num_devices=4, max_wave=8,
                           bytes_limit=limit) == extent


@pytest.mark.parametrize("ndev,wave,floor", [(4, 1, 4), (4, 2, 2),
                                             (8, 3, 4), (1, 8, 1)])
def test_graph_extent_never_below_factor_query_axis(ndev, wave, floor):
    """With room to spare the extent is what ``factor_query_axis``
    leaves for the wave, as the engines chose before plans were placed."""
    assert floor == ndev // PL.factor_query_axis(ndev, wave)
    assert PL.graph_extent(**SHAPE, num_devices=ndev, max_wave=wave,
                           bytes_limit=None) == floor


def test_the_scale_17_graph500_plan_takes_four_chips():
    """g500_s17's plan (8,192 row-blocks in groups of 128, K 3,112)
    against a v5e's 16 GiB with waves of 8.  Half the plan (13.1 GB)
    plus four queries' gathered source blocks of one 128-row step and
    their relayout, lane-dense, would take 78% of the chip, over the
    headroom; a quarter plus eight queries takes 41%."""
    r_pad, b, k, limit = 8192, 16, 3112, 16 << 30
    shape = (r_pad, b * k * b * 4 + k * 4 + 4 + 2 * b + 8, 128,
             2 * k * b * 4, 2 * r_pad * b * 4)
    assert PL.graph_extent(*shape, num_devices=4, max_wave=8,
                           bytes_limit=limit) == 4
    half = PL.device_need(*shape, num_devices=4, max_wave=8, d_g=2)
    quarter = PL.device_need(*shape, num_devices=4, max_wave=8, d_g=4)
    assert PL.HEADROOM * limit < half < 0.79 * limit
    assert quarter < 0.42 * limit


@pytest.mark.parametrize("local,gb,step", [(2048, 128, 128), (16, 4, 4),
                                           (8, 3, 2), (5, 64, 5)])
def test_rows_per_step_divides_the_local_rows(local, gb, step):
    assert PL.rows_per_step(local, gb) == step


@pytest.mark.parametrize("q", [1, 3, 8])
@pytest.mark.parametrize("semiring", ["min_plus", "min_select", "max_min",
                                      "plus_times"])
def test_stepped_local_sweep_equals_one_call_over_all_rows(semiring, q):
    """The wave form, (C, Q, B) → (R, Q, B), stepped or in one call, is
    the per-query kernel vmapped over the queries, bit for bit."""
    g = G.rmat(200, 900, seed=6)
    p = eng.prepare(g, semiring, b=8, num_clusters=8)
    x = np.random.default_rng(q).random((p.r_pad, q, p.b), np.float32)
    whole = PL.local_spmv(p.vals, p.cols, p.nnz, x, semiring, p.r_pad)
    stepped = PL.local_spmv(p.vals, p.cols, p.nnz, x, semiring, p.gb)
    assert p.gb < p.r_pad and stepped.shape == (p.r_pad, q, p.b)
    np.testing.assert_array_equal(np.asarray(stepped), np.asarray(whole))
    per_query = jax.vmap(lambda xq: ref.bsr_spmv_ref(
        p.vals, p.cols, xq, semiring), in_axes=1, out_axes=1)(x)
    np.testing.assert_array_equal(np.asarray(stepped),
                                  np.asarray(per_query))


# -- one device: prepare is unchanged ----------------------------------------


def test_one_device_prepare_places_the_plan_as_before():
    g = G.rmat(300, 1500, seed=5)
    obs.clear()
    p = eng.prepare(g, "min_plus", b=16, num_clusters=8)
    assert p.vals.shape == (p.r_pad, p.b, p.k_max * p.b)
    for f in ("vals", "cols", "nnz", "valid", "group_tiles"):
        assert isinstance(getattr(p, f).sharding, SingleDeviceSharding)
    assert p.shard_nbytes == p.nbytes
    up = obs.spans("plan.upload")[-1]
    assert up.attrs == {"devices": 1, "mesh": "1x1",
                        "shard_bytes": p.vals.nbytes}
    assert not obs.spans("plan.place")


def test_one_device_distributed_session_shares_the_single_device_plan():
    g = G.rmat(300, 1500, seed=5)
    proc = api.GraphProcessor(g, b=16, num_clusters=8,
                              policy=api.ExecutionPolicy(mode="distributed"))
    assert len(jax.devices()) == 1 and proc.plan_devices() == 1
    p = proc.prepare("min_plus")
    assert proc.plan_key("min_plus").devices == 1
    assert proc.prepare("min_plus", devices=1) is p
    assert isinstance(p.vals.sharding, SingleDeviceSharding)


def test_plan_key_tells_placements_apart():
    proc = api.GraphProcessor(G.ring(8), b=4)
    one, four = proc.plan_key("min_plus"), proc.plan_key("min_plus",
                                                         devices=4)
    assert one != four and (one.devices, four.devices) == (1, 4)


def test_store_keeps_sharded_plans_off_its_disk_tier(tmp_path):
    g = G.rmat(300, 1500, seed=5)
    p = eng.prepare(g, "min_plus", b=16, num_clusters=8)
    key = api.GraphProcessor(g, b=16).plan_key("min_plus", devices=4)
    store = PlanStore(cache_dir=str(tmp_path))
    store.put(g.fingerprint(), key, p)
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
    assert PlanStore(cache_dir=str(tmp_path)).get(g.fingerprint(),
                                                  key) is None


# -- four virtual devices: the served path -----------------------------------

_FOUR = r"""
import os, json
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax
from jax.sharding import PartitionSpec as P
from repro import api, obs
from repro.core import graph as G, oracles as O, placement as PL
from repro.core.algorithms import get_algorithm
from repro.serve.sched import WavePolicy

out = {}
g = G.rmat(1024, 16 * 1024, seed=3).to_undirected()
deg = np.diff(g.indptr)
roots = [int(v) for v in np.flatnonzero(deg > 0)[::37][:17]]
want = np.stack([O.bfs_oracle(g, r) for r in roots])

# the single-device plan and engine, for bit-identity
single = api.GraphProcessor(g, b=16, num_clusters=16)
out["single_equal_oracle"] = bool(np.array_equal(
    single.bfs(roots[:8]).values, want[:8]))
one = single.bfs(roots[:8]).values

# plan row-blocks of this graph: 64 in groups of 4, K 56; with waves of
# 8 a device needs 1,991,040 bytes at graph extent 2 and 1,216,704 at
# 4 (placement.device_need): a limit that admits a quarter of the plan
# with its waves but not half (the g500_s17 cell's shape)
LIMIT = 2_200_000
PL.device_bytes_limit = lambda: LIMIT
svc = api.GraphService(max_plan_bytes=LIMIT, max_wave=8,
                       policy=api.ExecutionPolicy(mode="distributed",
                                                  degrade=False))
server = api.GraphServer(service=svc, wave=WavePolicy(
    max_wave=8, max_wait_s=0.05, workers=1), warm_limit=0)
proc = server.register("g", g, b=16, num_clusters=16, warm=False)
obs.clear()
a = get_algorithm("bfs")
p = proc.prepare(a.semiring, variant=a.variant, pull=a.pull,
                 normalize=a.normalize)
up = obs.spans("plan.upload")[-1].attrs
out["upload"] = up
out["place_at_upload"] = len(obs.spans("plan.place"))
out["spec"] = str(p.vals.sharding.spec)
out["mesh"] = PL.mesh_name(p.vals.sharding.mesh)
d_g = p.vals.sharding.mesh.shape["graph"]
out["rows_per_device"] = sorted({s.data.shape[0]
                                 for s in p.vals.addressable_shards})
out["expected_rows"] = -(-p.r_pad // d_g)
out["devices_holding"] = len(p.vals.sharding.device_set)
out["shard_bytes_attr_ok"] = up["shard_bytes"] == p.vals.nbytes // d_g
st = svc.store.stats()
out["store_bytes"] = st["bytes"]
out["shard_nbytes"] = p.shard_nbytes
out["nbytes"] = p.nbytes
dev = [f for f in ("vals", "cols", "nnz", "valid", "dangling",
                   "row_edges", "row_ext")]
share = sum(getattr(p, f).nbytes // d_g for f in dev) + sum(
    getattr(p, f).nbytes for f in ("group_tiles", "group_edges",
                                   "group_ext_tiles"))
out["shard_nbytes_expected"] = share + p.nbytes - sum(
    getattr(p, f).nbytes for f in dev + ["group_tiles", "group_edges",
                                          "group_ext_tiles"])

# two waves of 8 and one single query through the server
t_waves = obs.spans("plan.upload")[-1].end_ns
futs = [server.submit("g", api.QuerySpec(algo="bfs", sources=(r,)))
        for r in roots]
res = [f.result() for f in futs]
server.close()
got = np.stack([np.asarray(r.values) for r in res])
out["equal_oracle"] = bool(np.array_equal(got, want))
out["equal_single_device"] = bool(np.array_equal(got[:8], one))
out["places_during_waves"] = len([s for s in obs.spans("plan.place")
                                  if s.start_ns > t_waves])
out["plans_built"] = proc.cache_info()["prepare_calls"]
runs = [s.attrs for s in obs.spans("run.device")]
out["run_meshes"] = sorted({a.get("mesh") for a in runs})
out["run_gathers"] = sorted({(a["gather"], a["q"]) for a in runs})
dists = list({id(r.extra["dist"]): r.extra["dist"] for r in res}.values())
out["halo_ok"] = bool(runs) and sorted(
    (a["halo_exchanges"], a["halo_bytes"]) for a in runs) == sorted(
    (d.halo_exchanges, d.halo_exchanges * d.halo_bytes_per_sweep)
    for d in dists)
out["halo_positive"] = all(a["halo_bytes"] > 0 for a in runs)
out["exchanges_are_sweeps"] = all(d.halo_exchanges == d.sweeps
                                  for d in dists)
out["wave_sizes"] = sorted({int(r.extra.get("coalesced", 1)) for r in res})

# an engine fault under degrade=True: the sync rung would need a second,
# whole plan on one device, so the fault propagates and none is built
from repro import resilience as rz
proc2 = api.GraphProcessor(g, b=16, num_clusters=16, max_wave=8,
                           policy=api.ExecutionPolicy(mode="distributed"))
plans = []
for batch in (roots[:8], roots[0]):
    try:
        with rz.inject(rz.FaultPlan([rz.FaultSpec("dist.dispatch")],
                                    seed=0)):
            proc2.bfs(batch)
        plans.append("answered")
    except rz.FaultInjected:
        plans.append("raised")
out["degrade_outcomes"] = plans
out["degrade_plans_built"] = proc2.cache_info()["prepare_calls"]
out["degrade_plan_devices"] = sorted(k.devices for k in proc2._plans)
print("RESULT" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    run = subprocess.run([sys.executable, "-c", _FOUR], capture_output=True,
                         text=True, env=env, cwd=ROOT, timeout=600)
    lines = [ln for ln in run.stdout.splitlines() if ln.startswith("RESULT")]
    assert lines, run.stderr[-3000:]
    return json.loads(lines[-1][len("RESULT"):])


def test_distributed_plan_rows_are_sharded_over_the_mesh(four):
    assert four["spec"] == str(jax.sharding.PartitionSpec("graph"))
    assert four["mesh"] == "4x1" and four["devices_holding"] == 4
    assert four["rows_per_device"] == [four["expected_rows"]]
    assert four["upload"]["devices"] == 4
    assert four["upload"]["mesh"] == "4x1"
    assert four["shard_bytes_attr_ok"]
    assert four["place_at_upload"] == 1


def test_distributed_waves_equal_the_bfs_oracle(four):
    assert four["single_equal_oracle"]
    assert four["equal_oracle"]
    assert four["equal_single_device"]
    assert four["wave_sizes"] == [1, 8]


def test_no_plan_place_during_the_waves(four):
    assert four["places_during_waves"] == 0
    assert four["plans_built"] == 1


def test_run_device_spans_carry_the_mesh_and_halo_bytes(four):
    assert four["run_meshes"] == ["4x1"]
    assert four["halo_ok"] and four["halo_positive"]
    assert four["exchanges_are_sweeps"]


def test_run_device_spans_say_a_distributed_wave_gathers_once(four):
    """A distributed wave of 8 runs the wave kernel (each tile's source
    block gathered once for the wave); the single query, per query."""
    assert four["run_gathers"] == [["per_query", 1], ["wave", 8]]


def test_store_charges_a_sharded_plan_by_its_fullest_device(four):
    assert four["store_bytes"] == four["shard_nbytes"]
    assert four["shard_nbytes"] == four["shard_nbytes_expected"]
    assert four["shard_nbytes"] < four["nbytes"]


def test_a_sharded_plan_fails_fast_instead_of_degrading_to_one_device(four):
    """``degrade=True`` would fall back from the distributed engine to the
    single-device sync engine, which needs the whole plan on one device:
    for a plan sharded over the mesh the fault propagates instead, and
    no second plan is built."""
    assert four["degrade_outcomes"] == ["raised", "raised"]
    assert four["degrade_plans_built"] == 1
    assert four["degrade_plan_devices"] == [4]
