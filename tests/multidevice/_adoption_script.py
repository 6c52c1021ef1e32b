"""Subprocess body: a multi-shard TCP send's received slabs become the store
entry's payload, uncopied, and serve a live attach, a migration and a refill
bit for bit; the slabs go back to the staging pool when the entry dies. Run
by test_multidevice.py with XLA_FLAGS set for 8 host devices."""

import os

assert "--xla_force_host_platform_device_count=8" in os.environ.get("XLA_FLAGS", "")

import numpy as np
import jax

import repro
from repro.core.expr import content_key
from repro.core.payload import SlabPayload

assert len(jax.devices()) == 8

engine = repro.AlchemistEngine()
pool = engine.memgov.staging
handed = []
_acquire = pool.acquire


def recording(shape, dtype):
    # Fresh slabs 16 bytes past a 64-byte boundary: a CPU device_put would
    # alias (and so pin) an aligned one, where a TPU never aliases.
    if any(b.shape == tuple(shape) and b.dtype == dtype for b in pool._free):
        buf = _acquire(shape, dtype)
    else:
        n = int(np.prod(shape)) * np.dtype(dtype).itemsize
        raw = np.empty(n + 128, np.uint8)
        start = (16 - raw.ctypes.data) % 64
        buf = raw[start : start + n].view(dtype).reshape(shape)
    handed.append(buf)
    return buf


pool.acquire = recording

# Every time a reader joins a multi-slab payload into one host array.
joins = []
_join = SlabPayload.__array__


def counting_join(self, *args, **kw):
    joins.append(len(self.slabs))
    return _join(self, *args, **kw)


SlabPayload.__array__ = counting_join


def tcp(name, workers):
    s = repro.connect(engine, name=name, workers=workers, transport="tcp")
    s.register_library("elemental", "repro.linalg.library:ElementalLib")
    return s


rng = np.random.default_rng(0)
a, b, c = (rng.standard_normal((130, 48)).astype(np.float32) for _ in range(3))

# --- a four-shard receive is adopted slab by slab -------------------------
s1 = tcp("s1", 4)
la = s1.send(a)
la.materialize()
slabs_a = list(handed)
assert len(slabs_a) == 4, len(slabs_a)
payload = engine.residents.lookup(content_key(a)).payload
assert isinstance(payload, SlabPayload) and len(payload.slabs) == 4
assert [s.shape[0] for s in payload.slabs] == [33, 33, 33, 31]
for view, slab in zip(payload.slabs, slabs_a):
    assert np.shares_memory(view, slab) and not view.flags.writeable
np.testing.assert_array_equal(np.asarray(payload), a)
st = engine.residents.stats()
assert st["payload_adopted_bytes"] == a.nbytes and st["payload_copied_bytes"] == 0, st

# --- a live attach from a second session reads the slabs, zero bridge bytes
s2 = tcp("s2", 4)
group1 = {d.id for d in s1.session.worker_devices}
assert group1.isdisjoint(d.id for d in s2.session.worker_devices)
joins.clear()
la2 = s2.send(a.copy())
np.testing.assert_array_equal(np.asarray(la2.data()), a)
assert not joins, joins  # placed block by block, never joined on the host
summ = s2.stats.summary()
assert summ["cross_session_reuses"] == 1 and summ["send_bytes"] == 0, summ

# --- another send while a lives: a's slabs are not recycled under it ------
lb = s1.send(b)
lb.materialize()
assert not any(buf is slab for buf in handed[4:] for slab in slabs_a)
np.testing.assert_array_equal(np.asarray(payload), a)

# --- a dies with its last placement: its slabs serve the next receive -----
la.free()
la2.free()
assert engine.residents.lookup(content_key(a)) is None
reuses = pool.reuses
n = len(handed)
lc = s1.send(c)
np.testing.assert_array_equal(np.asarray(lc.data()), c)
assert pool.reuses == reuses + 4, (pool.reuses, reuses)
assert {id(buf) for buf in handed[n : n + 4]} == {id(slab) for slab in slabs_a}

# --- migration on close, then a refill into another geometry -------------
s2.close()
s1.close()  # b and c uniquely held: migrated with their slabs as payload
assert isinstance(engine.residents.lookup(content_key(b)).payload, SlabPayload)
s3 = tcp("s3", 2)
joins.clear()
lb3 = s3.send(b.copy())
np.testing.assert_array_equal(np.asarray(lb3.data()), b)
assert not joins, joins
summ = s3.stats.summary()
assert summ["cross_session_reuses"] == 1 and summ["send_bytes"] == 0, summ
assert engine.residents.stats()["payload_copied_bytes"] == 0
s3.close()

# --- a free governor spill, a collect from host and a refill -------------
d, e = (rng.standard_normal((130, 48)).astype(np.float32) for _ in range(2))
s4 = repro.connect(engine, name="s4", workers=4, transport="tcp", hbm_budget=3 * d.nbytes // 2)
s4.register_library("elemental", "repro.linalg.library:ElementalLib")
md = s4.send(d).materialize()
hd = s4.session.resolve(md)
assert isinstance(hd._host_fallback, SlabPayload) and len(hd._host_fallback.slabs) == 4
s4.send(e).materialize()
assert hd.state == "spilled" and hd.id not in s4.session.memgov._host_store
joins.clear()
np.testing.assert_array_equal(np.asarray(s4.collect(md)), d)
assert hd.state == "spilled"  # the collect was served from the slabs
norm = float(s4.run("elemental", "normest", s4.send(d)).data())  # refill
assert abs(norm - np.linalg.norm(d)) < 1e-3 * np.linalg.norm(d), norm
assert hd.state == "materialized" and s4.session.stats.summary()["refills"] >= 1
assert not joins, joins
s4.close()

print("MULTIDEVICE_ADOPTION_OK")
