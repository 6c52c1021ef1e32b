"""End-to-end padded-send property: send → collect round-trips arbitrary
(m, n, worker_count) shapes bit-exactly on an 8-emulated-device engine,
including m < worker_count (DESIGN.md §7). Run via tests/test_multidevice.py.

Uses hypothesis when installed (CI); otherwise falls back to a deterministic
sweep that still covers every worker count and the awkward-shape corners.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

import repro  # noqa: E402

engine = repro.AlchemistEngine()
assert engine.num_workers == 8, engine.num_workers

checked = 0
try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def roundtrip(ac, workers: int, m: int, n: int, seed: int) -> None:
    global checked
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((m, n)) * 8).astype(np.float32)
    h = ac.send(x)
    live = ac.session.resolve(h)
    # physical residency is put-legal; logical metadata is the true shape
    assert live.shape == (m, n)
    assert (live.shape[0] + live.pads[0]) % workers == 0 or live.pads[0] == 0
    got = np.asarray(ac.collect(h))
    assert got.shape == (m, n)
    np.testing.assert_array_equal(got, x)  # bit-exact through pad + strip
    ac.free(h)
    checked += 1


# One worker-group size at a time (a 2+4+8 split would oversubscribe the
# 8-device pool); the session is reused across examples for speed.
for workers in (2, 4, 8):
    ac = repro.AlchemistContext(engine, num_workers=workers, name=f"pad{workers}")
    if HAVE_HYPOTHESIS:

        def make_prop(ac, workers):
            # hypothesis refuses @given on a function with defaults, so the
            # session and group size are closed over instead of bound.
            @given(
                m=st.integers(min_value=1, max_value=24),
                n=st.integers(min_value=1, max_value=12),
                seed=st.integers(min_value=0, max_value=2**31 - 1),
            )
            @settings(max_examples=25, deadline=None)
            def prop(m, n, seed):
                roundtrip(ac, workers, m, n, seed)

            return prop

        make_prop(ac, workers)()
    else:
        for m, n in [(1, 1), (2, 5), (6, 6), (7, 3), (13, 9), (16, 8), (workers - 1, 3)]:
            roundtrip(ac, workers, m, n, seed=m * 100 + n)
    if workers == 4:
        # The ROADMAP's headline case, spelled out: 6x6 onto a 2x2 group.
        roundtrip(ac, 4, 6, 6, seed=0)
    ac.stop()

assert engine.available_workers == 8  # no leaked worker-group devices

# Cyclic engine layouts are never pre-padded (the emulation's permutation
# would interleave the zero rows): divisible shapes round-trip exactly,
# uneven ones fail loudly instead of silently corrupting.
from repro.core.layouts import GRID  # noqa: E402

ac = repro.AlchemistContext(engine, num_workers=4, engine_layout=GRID.with_cyclic())
x8 = np.arange(48, dtype=np.float32).reshape(8, 6)
np.testing.assert_array_equal(np.asarray(ac.collect(ac.send(x8))), x8)
try:
    ac.send(np.ones((6, 6), np.float32))  # 6 % 4 != 0 on the ROW staging
    raise SystemExit("uneven cyclic send unexpectedly succeeded")
except Exception as exc:  # jax raises ValueError at the staging device_put
    assert "divisible" in str(exc), exc
ac.stop()
assert engine.available_workers == 8

# Fused pad/strip (DESIGN.md §10), deterministic interpret-mode case: force
# the Pallas kernel dispatch (interpret mode runs the same kernel body the
# TPU path compiles) and round-trip an uneven matrix through a real 4-worker
# session — bit-exact, and the session must count the fused relayouts.
from repro.kernels import ops as kops  # noqa: E402

_saved_backend = kops._BACKEND
kops._BACKEND = "pallas-interpret"
try:
    ac = repro.AlchemistContext(engine, num_workers=4, name="fused")
    xf = (np.random.default_rng(7).standard_normal((6, 7)) * 8).astype(np.float32)
    hf = ac.send(xf)  # 6 % 4 != 0: the ROW staging pad runs through the kernel
    np.testing.assert_array_equal(np.asarray(ac.collect(hf)), xf)
    fused_count = ac.stats.summary()["fused_relayouts"]
    assert fused_count >= 1, f"expected fused relayouts, got {fused_count}"
    ac.stop()
finally:
    kops._BACKEND = _saved_backend
assert engine.available_workers == 8

print(f"checked {checked} shapes via {'hypothesis' if HAVE_HYPOTHESIS else 'deterministic'}")
print("MULTIDEVICE_PADDING_OK")
