"""Distribution: sharding rules, distributed graph engine (1 and 8 fake
devices via subprocess), the 2-D ("graph", "query") batched engine
across mesh factorizations, dry-run cell smoke.

The factorization parity tests read one subprocess on four virtual CPU
devices (``tests/dist_parity.py``); the 8-device checks run in another."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.sharding.rules import parse_axes, spec_for

MESH = SimpleNamespace(shape={"data": 16, "model": 16})
MESH_MP = SimpleNamespace(shape={"pod": 2, "data": 16, "model": 16})


def test_spec_basic_tp_fsdp():
    assert spec_for((18432, 96, 192), "embed heads head_dim", MESH) == \
        P("data", "model", None)
    # batch spans pod+data on the multi-pod mesh
    assert spec_for((256, 4096), "batch seq", MESH_MP) == \
        P(("pod", "data"), None)


def test_spec_indivisible_falls_back_replicated():
    # 49155 vocab is indivisible by 16 → replicated
    assert spec_for((49155, 2048), "vocab embed", MESH) == \
        P(None, ("data", "model"))


def test_spec_greedy_fill_soaks_unused_axes():
    # kv_heads=8 can't take model(16); embed takes data AND model
    assert spec_for((18432, 8, 192), "embed kv_heads head_dim", MESH) == \
        P(("data", "model"), None, None)
    # but when heads CAN take model, embed only takes data
    assert spec_for((18432, 96, 192), "embed heads head_dim", MESH) == \
        P("data", "model", None)
    # embed_kv never takes model (GSPMD conflict, see rules.py)
    assert spec_for((18432, 8, 192), "embed_kv kv_heads head_dim",
                    MESH) == P("data", None, None)


def test_spec_no_axis_reuse():
    sp = spec_for((4096, 4096), "embed mlp", MESH)
    used = [a for part in sp for a in
            ((part,) if isinstance(part, str) else (part or ()))]
    assert len(used) == len(set(used))


def test_parse_axes():
    assert parse_axes("embed . heads") == ("embed", None, "heads")
    assert parse_axes("") == ()


def test_distributed_graph_engine_single_device():
    from repro.core import algorithms as A
    from repro.core import graph as G
    from repro.core import oracles as O
    from repro.core import placement as PL
    import jax.numpy as jnp

    g = G.rmat(300, 1500, seed=5)
    r = A.sssp(g, 0, mode="async", b=16, num_clusters=8)
    p = r.prepared
    x0f = np.full(g.n, np.inf, dtype=np.float32)
    x0f[0] = 0
    x0 = p.to_blocks(x0f, np.inf)
    x, ds = PL.distributed_sync_run(p, x0, "relax")
    np.testing.assert_allclose(np.asarray(x).reshape(-1)[p.perm],
                               O.sssp_oracle(g, 0), rtol=1e-5, atol=1e-4)
    assert ds.converged
    _ = jnp


def test_make_graph_mesh_is_2d_and_degenerates():
    from repro.core import placement as PL
    mesh = PL.make_graph_mesh(1)
    assert dict(mesh.shape) == {"graph": 1, "query": 1}
    with pytest.raises(ValueError):
        PL.make_graph_mesh(1, 0)
    with pytest.raises(ValueError):
        PL.make_graph_mesh(4, 3)   # 3 does not divide 4


def test_factor_query_axis():
    from repro.core import placement as PL
    assert PL.factor_query_axis(8, 1) == 1
    assert PL.factor_query_axis(8, 3) == 2    # largest divisor <= 3
    assert PL.factor_query_axis(8, 5) == 4
    assert PL.factor_query_axis(8, 64) == 8
    assert PL.factor_query_axis(1, 64) == 1
    assert PL.factor_query_axis(6, 4) == 3


def test_batched_engine_rejects_query_axis_0():
    """The query_axis=0 per-source escape hatch is the session API's —
    the engine must refuse it rather than silently auto-factor."""
    from repro.core import placement as PL
    from repro.core import engine as eng
    from repro.core import graph as G
    g = G.rmat(200, 900, seed=6)
    p = eng.prepare(g, "min_plus", b=8, num_clusters=8)
    x0 = np.full((2, p.r_pad, p.b), np.inf, np.float32)
    with pytest.raises(ValueError, match="query_axis"):
        PL.distributed_sync_run_batched(p, x0, query_axis=0)


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (num_devices, query_axis): meshes 1x1, 4x1, 2x2 and 1x4
FACTORIZATIONS = [(1, 1), (4, 1), (4, 2), (4, 4)]
SEMIRINGS = ["min_plus", "min_select", "max_min", "plus_times"]


@pytest.fixture(scope="module")
def parity():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "dist_parity.py"),
         "sync"], capture_output=True, text=True, env=env, cwd=ROOT,
        timeout=600)
    lines = [ln for ln in run.stdout.splitlines() if ln.startswith("RESULT")]
    assert lines, run.stderr[-3000:]
    return json.loads(lines[-1][len("RESULT"):])


@pytest.mark.parametrize("semiring", SEMIRINGS)
@pytest.mark.parametrize("ndev,qaxis", FACTORIZATIONS)
def test_batched_distributed_parity_across_factorizations(
        parity, semiring, ndev, qaxis):
    """Batched-distributed == the per-source sequential distributed
    engine, BIT-identical, with the same per-query sweeps, on every mesh
    factorization, for a wave of 5 queries (padding queries on 2x2 and
    1x4)."""
    r = parity[f"{semiring}/{ndev // qaxis}x{qaxis}"]
    assert r["equal"] and r["converged"]
    assert r["mesh"] == [ndev // qaxis, qaxis]
    assert r["query_sweeps"] == r["ref_sweeps"]
    assert r["sweeps"] == max(r["query_sweeps"])


_SUBPROCESS_8DEV = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"  # 8 fake host devices; never a chip
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
from repro.core import algorithms as A, engine as E, graph as G, \
    oracles as O, placement as PL
g = G.rmat(200, 900, seed=6)
r = A.sssp(g, 0, mode="async", b=8, num_clusters=8)
p = r.prepared
x0f = np.full(g.n, np.inf, dtype=np.float32); x0f[0] = 0
x0 = p.to_blocks(x0f, np.inf)
mesh = PL.make_graph_mesh(8)
x, ds = PL.distributed_sync_run(p, x0, "relax", mesh=mesh)
got = np.asarray(x).reshape(-1)[p.perm]
np.testing.assert_allclose(got, O.sssp_oracle(g, 0), rtol=1e-5, atol=1e-4)
low = PL.lower_distributed(p, mesh)
txt = low.compile().as_text()
assert "all-gather" in txt or "all-reduce" in txt, "no collectives?"
print("OK8")

# 2-D batched engine: bit-identical to the vmap sync oracle on every
# factorization of the 8 fake devices (1x1, 2x2, 8x1, 1x8)
sources = [0, 5, 9, 13, 17]
X0 = np.stack([np.asarray(p.to_blocks(
    np.where(np.arange(g.n) == s, 0, np.inf).astype(np.float32),
    np.inf)) for s in sources])
ref, _ = E.run_sync_batched(p, X0, max_sweeps=100_000)
ref = np.asarray(ref)
for nd, qa in [(1, 1), (4, 2), (8, 1), (8, 8)]:
    m2 = PL.make_graph_mesh(nd, qa)
    xb, db = PL.distributed_sync_run_batched(
        p, X0, "relax", max_sweeps=100_000, mesh=m2)
    assert np.array_equal(np.asarray(xb), ref), (nd, qa)
    assert db.converged and db.mesh_shape == (nd // qa, qa)
low_b = PL.lower_distributed(p, PL.make_graph_mesh(8, 4), batch=len(sources))
txt_b = low_b.compile().as_text()
assert "all-gather" in txt_b or "all-reduce" in txt_b, "no collectives?"
print("OK8-2D")
"""


def test_distributed_graph_engine_8_fake_devices():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS_8DEV],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), timeout=600)
    assert "OK8" in out.stdout and "OK8-2D" in out.stdout, \
        out.stderr[-2000:]


def test_dryrun_single_cell_subprocess():
    """One real dry-run cell end-to-end (whisper decode: cheapest)."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "whisper-tiny", "--shape", "decode_32k", "--no-pieces"],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=900)
    assert "ok" in out.stdout and "0 errors" in out.stdout, \
        out.stdout + out.stderr[-2000:]


def test_dryrun_results_if_present():
    """Validate the committed sweep results when available: every cell is
    ok or a documented skip."""
    base = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "results")
    for sub in ("dryrun_single", "dryrun_multi"):
        d = os.path.join(base, sub)
        if not os.path.isdir(d):
            pytest.skip("sweep results not present")
        cells = []
        for name in os.listdir(d):
            with open(os.path.join(d, name)) as f:
                cells.append(json.load(f))
        assert len(cells) >= 40
        bad = [c for c in cells if c["status"] == "error"]
        assert not bad, [(c["arch"], c["shape"], c["error"]) for c in bad]
