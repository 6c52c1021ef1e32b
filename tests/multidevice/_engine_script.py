"""Subprocess body: engine + linalg semantics on a real 2x4 device mesh.
Run by test_multidevice.py with XLA_FLAGS set for 8 host devices."""

import os

assert "--xla_force_host_platform_device_count=8" in os.environ.get("XLA_FLAGS", "")

import threading

import numpy as np
import jax

import repro
from repro.core.layouts import GRID, ROW
from repro.core.relayout import transfer_cost

assert len(jax.devices()) == 8

engine = repro.AlchemistEngine()

# --- concurrent sessions get disjoint worker groups (paper §2.4) ---------
ac1 = repro.AlchemistContext(engine, num_workers=4, name="app1")
ac2 = repro.AlchemistContext(engine, num_workers=4, name="app2")
d1 = {d.id for d in ac1.session.worker_devices}
d2 = {d.id for d in ac2.session.worker_devices}
assert d1.isdisjoint(d2), "worker groups overlap"
assert engine.available_workers == 0

rng = np.random.default_rng(0)
a = rng.standard_normal((128, 64)).astype(np.float32)
b = rng.standard_normal((64, 32)).astype(np.float32)

ac1.register_library("elemental", "repro.linalg.library:ElementalLib")
ac2.register_library("elemental", "repro.linalg.library:ElementalLib")

# both sessions compute independently and correctly
h1 = ac1.send(a)
h2 = ac2.send(a)
g1 = ac1.run("elemental", "gemm", h1, ac1.send(b))
g2 = ac2.run("elemental", "gemm", h2, ac2.send(b), schedule="allgather")
np.testing.assert_allclose(np.asarray(ac1.collect(g1)), a @ b, atol=1e-3)
np.testing.assert_allclose(np.asarray(ac2.collect(g2)), a @ b, atol=1e-3)

# engine-resident data is actually distributed over the session grid
live = ac1.session.resolve(h1).data()
n_shards = len({s.device.id for s in live.addressable_shards})
assert n_shards == 4, f"expected 4 shards, got {n_shards}"

# the analytic transfer model predicts real movement on this mesh
cost = transfer_cost((128, 64), "float32", ROW, GRID, ac1.mesh)
assert cost.bytes_moved > 0 and cost.messages > 0

# SVD on a worker group
u, s, v = ac1.run("elemental", "truncated_svd", h1, k=4)
s_ref = np.linalg.svd(a, compute_uv=False)[:4]
np.testing.assert_allclose(np.asarray(s), s_ref, rtol=0.05)

# TSQR on a 2x2 grid (regression: _flat_rank used jax.lax.axis_size, which
# older jax lacked — multi-axis meshes crashed)
hq, hr = ac1.run("elemental", "tsqr", h1)
r_np = np.asarray(ac1.collect(hr))
np.testing.assert_allclose(r_np.T @ r_np, a.T @ a, atol=2e-2)

# lazy offload planner on a worker group (DESIGN.md §6): chained routines
# elide the bridge, equal sends dedup, numerics match the eager path above
pl = ac1.planner
lc = pl.run("elemental", "gemm", pl.send(a), pl.send(b))
lr = pl.run("elemental", "tsqr", lc, n_outputs=2)[1]        # elided: lc
# elided: lr
r2 = np.asarray(pl.collect(pl.run("elemental", "gemm", lr, np.eye(32, dtype=np.float32))))
np.testing.assert_allclose(r2.T @ r2, (a @ b).T @ (a @ b), rtol=1e-2)
lc2 = pl.run("elemental", "gemm", pl.send(a.copy()), pl.send(b.copy()))  # both dedup
assert isinstance(pl.materialize(lc2), repro.AlMatrix)
ps = ac1.stats.summary()
assert ps["elided_crossings"] >= 2, ps
assert ps["resident_reuses"] >= 2, ps

ac1.stop()
ac2.stop()
assert engine.available_workers == 8

# --- memory governor on a real worker group (DESIGN.md §7) ----------------
# Working set of 6 matrices against a 3-matrix HBM budget: the governor
# spills genuinely sharded resident arrays to host and refills them with
# identical bytes; high water stays bounded on the real mesh too.
mat_bytes = 128 * 64 * 4
ac3 = repro.AlchemistContext(engine, num_workers=4, name="gov", hbm_budget=3 * mat_bytes)
ac3.register_library("elemental", "repro.linalg.library:ElementalLib")
mats = [rng.standard_normal((128, 64)).astype(np.float32) for _ in range(6)]
handles = [ac3.send(m) for m in mats]
# collects of spilled matrices are served from the host store, bit-exactly
for m, h in zip(mats, handles):
    np.testing.assert_array_equal(np.asarray(ac3.collect(h)), m)
gs = ac3.stats.summary()
assert gs["spills"] > 0, gs
assert gs["hbm_high_water"] <= 3 * mat_bytes, gs
# engine-side consumption refills spilled matrices onto the real mesh
for m, h in zip(mats, handles):
    norm = float(ac3.run("elemental", "normest", h))
    assert abs(norm - np.linalg.norm(m)) < 1e-2
gs = ac3.stats.summary()
assert gs["refills"] > 0, gs
assert gs["hbm_high_water"] <= 3 * mat_bytes, gs
ac3.stop()
assert engine.available_workers == 8

# --- v2 admission-aware connect on a real mesh (DESIGN.md §9) -------------
# Content-affinity placement end-to-end: content X was last placed on the
# SECOND half of the device pool; a new session declaring X must be steered
# there (the canonical default pick would be devices 0-3), and its send of X
# must attach with zero bridge bytes.
aff_engine = repro.AlchemistEngine()
s_a = repro.connect(aff_engine, workers=4, name="aff_a")  # devices 0-3
s_b = repro.connect(aff_engine, workers=4, name="aff_b")  # devices 4-7
assert {d.id for d in s_b.session.worker_devices} == {4, 5, 6, 7}
x_payload = rng.standard_normal((64, 32)).astype(np.float32)
s_b.send(x_payload, name="X").materialize()  # placed (and published) on 4-7
s_a.close()
s_b.close()  # uniquely-referenced content migrates host-side, keyed by X
assert aff_engine.available_workers == 8
s_c = repro.connect(
    aff_engine,
    name="aff_c",
    placement=repro.PlacementRequest(workers=4, affinity=(x_payload,)),
)
assert {d.id for d in s_c.session.worker_devices} == {4, 5, 6, 7}, (
    "content affinity should pick the reuse-bearing group"
)
assert aff_engine.admissions["affinity_hits"] == 1
with s_c.policy("eager"):
    s_c.send(x_payload, name="X")
summ = s_c.stats.summary()
assert summ["cross_session_reuses"] == 1 and summ["send_bytes"] == 0, summ

# Queued admission under real contention: a connect for the whole pool waits
# for the running session instead of failing, then is placed.
threading.Timer(0.3, s_c.close).start()
s_d = repro.connect(
    aff_engine, name="aff_d", placement=repro.PlacementRequest(workers=8, deadline=60)
)
assert aff_engine.admissions["queued"] == 1
assert len(s_d.session.worker_devices) == 8
s_d.close()
snap = aff_engine.stats()
assert snap["engine"]["admissions"]["queued"] == 1, snap
print("MULTIDEVICE_ENGINE_OK")
