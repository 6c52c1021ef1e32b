"""Property tests for pad-to-divisible send geometry (DESIGN.md §7).

The bridge pads dim0/dim1 up to the next multiple of the destination layout's
shard counts before ``device_put`` and slices the padding off on
collect/refill. Two layers of coverage:

- here: the pure geometry, on arbitrary (m, n, row_shards, col_shards) —
  pad amounts are minimal and correct, and a pad → block-shard → reassemble →
  strip round trip is bit-exact, including m < worker_count;
- tests/multidevice/_padding_script.py: the same property end-to-end through
  a real 8-device engine (send → collect across worker groups).

Runs under hypothesis when installed (CI); the deterministic parametrized
cases keep the invariants exercised everywhere else (the
tests/_hypothesis_compat.py shim skips only the property tests).
"""

import dataclasses
from typing import Tuple

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.errors import LayoutError
from repro.core.layouts import GRID, ROW, LayoutSpec
from repro.core.relayout import pad_amounts, shard_intervals

DTYPES = ["float32", "float64", "int32", "float16"]


@dataclasses.dataclass
class _FakeMesh:
    """(axis_names, devices.shape) duck-type for shard-geometry helpers."""

    shape: Tuple[int, ...]
    axis_names: Tuple[str, ...] = ("data", "model")

    class _Dev:
        def __init__(self, shape):
            self.shape = shape

    @property
    def devices(self):
        return _FakeMesh._Dev(self.shape)


def _roundtrip(m: int, n: int, r: int, c: int, dtype: str, seed: int) -> None:
    """Pad → block-shard over an r x c grid → reassemble → strip == identity."""
    mesh = _FakeMesh((r, c))
    spec = LayoutSpec("grid", row_axes=("data",), col_axes=("model",))
    pr, pc = pad_amounts((m, n), spec, mesh)
    # pads are minimal and make the physical shape exactly divisible
    assert 0 <= pr < r and 0 <= pc < c
    assert (m + pr) % r == 0 and (n + pc) % c == 0

    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((m, n)) * 8).astype(dtype)
    phys = np.pad(x, ((0, pr), (0, pc)))

    rows = shard_intervals(m + pr, r)
    cols = shard_intervals(n + pc, c)
    # every shard of the padded matrix is full-size (what device_put needs)
    assert {int(e - s) for s, e in rows} == {(m + pr) // r}
    assert {int(e - s) for s, e in cols} == {(n + pc) // c}

    reassembled = np.block(
        [[phys[rs:re, cs:ce] for cs, ce in cols] for rs, re in rows]
    )
    np.testing.assert_array_equal(reassembled[:m, :n], x)  # bit-exact strip


def _worker_count_pad(m: int, w: int) -> None:
    """ROW staging pads dim0 to the next worker-count multiple (dim1 free)."""
    mesh = _FakeMesh((w, 1), axis_names=("data", "model"))
    spec = LayoutSpec("row", row_axes=("data", "model"), col_axes=())
    pr, pc = pad_amounts((m, 7), spec, mesh)
    assert pc == 0
    assert (m + pr) % w == 0 and pr < w
    if m % w == 0:
        assert pr == 0  # divisible shapes stay byte-identical to before


# -- hypothesis properties --------------------------------------------------

@given(
    m=st.integers(min_value=1, max_value=64),
    n=st.integers(min_value=1, max_value=32),
    r=st.integers(min_value=1, max_value=8),
    c=st.integers(min_value=1, max_value=8),
    dtype=st.sampled_from(DTYPES),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
@settings(max_examples=150, deadline=None)
def test_pad_shard_strip_roundtrip(m, n, r, c, dtype, seed):
    _roundtrip(m, n, r, c, dtype, seed)


@given(m=st.integers(min_value=1, max_value=128), w=st.integers(min_value=1, max_value=16))
@settings(max_examples=150, deadline=None)
def test_row_staging_pads_to_worker_multiple(m, w):
    _worker_count_pad(m, w)


# -- deterministic fallback cases -------------------------------------------

@pytest.mark.parametrize(
    "m,n,r,c",
    [
        (6, 6, 2, 2),  # the ROADMAP's 6x6-to-4-workers case
        (1, 1, 8, 8),  # single element, m < worker count
        (2, 5, 4, 2),  # m < row shards
        (7, 3, 3, 5),  # nothing divides anything
        (16, 8, 4, 2),  # already divisible: zero pads
        (5, 5, 1, 1),  # single worker: zero pads
    ],
)
@pytest.mark.parametrize("dtype", DTYPES)
def test_pad_shard_strip_roundtrip_cases(m, n, r, c, dtype):
    _roundtrip(m, n, r, c, dtype, seed=m * 1000 + n)


@pytest.mark.parametrize("m,w", [(6, 4), (1, 8), (12, 4), (13, 8), (128, 16)])
def test_row_staging_cases(m, w):
    _worker_count_pad(m, w)


def test_grid_layout_pad_amounts_on_fake_mesh():
    mesh = _FakeMesh((2, 2))
    assert pad_amounts((6, 6), GRID, mesh) == (0, 0)  # 6 % 2 == 0 both dims
    assert pad_amounts((6, 6), ROW, mesh) == (2, 0)  # row shards = 4
    assert pad_amounts((5, 3), GRID, mesh) == (1, 1)


# -- fused pad/strip kernels (DESIGN.md §10) ---------------------------------


def _fused_roundtrip(m: int, n: int, r: int, c: int, dtype: str, seed: int) -> None:
    """The Pallas pad/strip kernels agree bit-exactly with the kernels/ref.py
    oracles and round-trip as the identity — arbitrary grids, m < workers
    included. Interpret mode: the same kernel body the TPU path compiles,
    executed on any backend."""
    import jax.numpy as jnp

    from repro.kernels import ref as kref
    from repro.kernels import relayout_pad as krp

    mesh = _FakeMesh((r, c))
    spec = LayoutSpec("grid", row_axes=("data",), col_axes=("model",))
    pr, pc = pad_amounts((m, n), spec, mesh)
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((m, n)) * 8).astype(dtype)
    xd = jnp.asarray(x)  # canonicalized as the device sees it (f64 -> f32)
    physical = (m + pr, n + pc)

    fused = krp.pad_to(xd, physical, interpret=True)
    oracle = kref.pad_to(xd, physical)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(oracle))

    back = krp.strip_to(fused, (m, n), interpret=True)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(xd))
    np.testing.assert_array_equal(
        np.asarray(kref.strip_to(oracle, (m, n))), np.asarray(xd)
    )


@given(
    m=st.integers(min_value=1, max_value=16),
    n=st.integers(min_value=1, max_value=16),
    r=st.integers(min_value=1, max_value=4),
    c=st.integers(min_value=1, max_value=4),
    dtype=st.sampled_from(DTYPES),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
@settings(max_examples=30, deadline=None)
def test_fused_pad_strip_matches_ref(m, n, r, c, dtype, seed):
    _fused_roundtrip(m, n, r, c, dtype, seed)


@pytest.mark.parametrize(
    "m,n,r,c",
    [
        (6, 6, 2, 2),  # pads (0, 0): the kernels must pass through untouched
        (1, 1, 8, 8),  # single element, m < worker count
        (2, 5, 4, 2),  # m < row shards
        (7, 3, 3, 5),  # nothing divides anything
        (5, 5, 1, 1),  # single worker: zero pads
        (1030, 7, 4, 1),  # rows span several blocks, partial last block
        (9, 1000, 2, 3),  # columns span several blocks, partial last block
    ],
)
@pytest.mark.parametrize("dtype", DTYPES)
def test_fused_pad_strip_cases(m, n, r, c, dtype):
    _fused_roundtrip(m, n, r, c, dtype, seed=m * 100 + n)


def test_fused_kernels_refuse_impossible_directions():
    from repro.kernels import ref as kref
    from repro.kernels import relayout_pad as krp

    x = np.ones((4, 4), np.float32)
    for mod in (krp, kref):
        with pytest.raises(ValueError):
            mod.pad_to(x, (2, 4))  # pad may never shrink
        with pytest.raises(ValueError):
            mod.strip_to(x, (8, 4))  # strip may never grow


def test_cyclic_layouts_refuse_padding():
    # The cyclic emulation permutes rows as a function of the physical
    # length: appended zero rows would interleave into the interior and
    # silently corrupt logical reads. Uneven + cyclic must fail loudly.
    mesh = _FakeMesh((2, 2))
    cyc = GRID.with_cyclic()
    assert pad_amounts((6, 6), cyc, mesh) == (0, 0)  # divisible: fine
    with pytest.raises(LayoutError, match="cyclic"):
        pad_amounts((5, 6), cyc, mesh)
