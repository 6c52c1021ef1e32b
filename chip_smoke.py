#!/usr/bin/env python3
"""Drive the engine's served path once on a TPU and check what comes back.

One process: an ``AlchemistEngine`` over ``jax.devices()``, a client that
connects to it over TCP (``repro.connect(engine, transport="tcp")``: the real
``EngineServer`` and ``TcpTransport``, in this process), then per phase
send → run → collect through the ``elemental`` library:

- ``svd``: an m x 10,000 f32 matrix with a known spectrum, rank-20
  ``truncated_svd``; singular values, residuals and orthonormality checked in
  float64 on the host. m is paper section 4.2's smallest case, 312,500
  (12.5 GB), on four chips, and 100,000 on one, whose host memory binds.
- ``gemm``: paper Table 1's 10k x 10k x 10k ``gemm``; a seeded sample of rows
  checked against float64 NumPy.

With ``--chips 4`` it runs only what spans chips, on a 2x2 worker group: the
``svd`` phase, SUMMA ``gemm``, and an uneven-row send → collect that must come
back bit-exact, once through the wire and once placed from the engine's host
copy by the fused Pallas pad kernel.

Usage, from the repository root:

    python3 chip_smoke.py [--chips 4] [--seed 0]

Earlier lines are JSON records (device, phases, memory). The last line is
``{"ok": true, "device": {...}}``. Any failed phase or tolerance, a host with
no TPU, or kernels that would not run compiled exit non-zero without it.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import resource
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

SVD_ROWS = 312_500  # paper section 4.2, smallest case: the --chips 4 run
# One chip's host (45 GiB) cannot hold the 12.5 GB matrix as the send path
# copies it (HOST_COPIES below), so the one-chip run cuts the rows.
SVD_ROWS_ONE_CHIP = 100_000
SVD_COLS = 10_000
SVD_RANK = 20
SVD_TAIL = 44  # singular values past the 20 asked for, decaying from 0.1
GEMM_N = 10_000  # paper Table 1, first case
GEMM_SAMPLE_ROWS = 64
UNEVEN_SHAPE = (20_003, SVD_COLS)  # rows not a multiple of the 2x2 group's shards
GEN_BLOCK = 8_192  # rows per block when building or checking a matrix
# Host memory the svd phase adds, in copies of its matrix: peak RSS grew by
# 5.0 times the matrix bytes on a four-chip TPU v5e host at 312,500 rows. Three are
# traced (the session's send snapshot, the server's staging slab, the content
# store's payload); the client drops its own array before the bytes cross.
HOST_COPIES = 6
HOST_SLACK = 2 << 30  # factors, check blocks

# Tolerances. lanczos.py and the GEMM kernel set no matmul precision; a TPU's
# default f32 matmul may round each operand to bfloat16 (8 significant bits)
# and accumulate in f32. The bounds below hold for that, and fail for a
# result that is wrong by more than such rounding can explain.
U_BF16 = 2.0**-9  # unit roundoff of bfloat16
U_F32 = 2.0**-24  # unit roundoff of float32


def _now() -> float:
    return time.perf_counter()


def _peak_rss() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def _connect(engine, grid, name):
    import repro

    session = repro.connect(engine, name=name, grid=grid, transport="tcp")
    session.register_library("elemental", "repro.linalg.library:ElementalLib")
    return session


def _devices_of(engine, shape):
    """Ids of the devices holding the engine-side matrices of ``shape``."""
    ids = set()
    for sess in list(engine.sessions.values()):
        for h in list(sess.handles.values()):
            if tuple(h.shape) == tuple(shape) and h.is_live:
                ids |= {s.device.id for s in h.data().addressable_shards}
    return sorted(ids)


# -- svd -------------------------------------------------------------------
class LowRankProblem:
    """A = U diag(sigma) V^T with U [m, r], V [n, r] orthonormal (float64 QR).

    sigma is k, k-1, ..., 1 (a gap of 1 between the values asked for),
    then a tail decaying from 0.1, so the top k are known (to the f32
    rounding of the factors) and well separated from the rest. The client's matrix is the f32 product, built
    in row blocks; :meth:`rows` rebuilds any block bit-identically for the
    host-side reference, so the client need not keep its copy.
    """

    def __init__(self, m: int, n: int, k: int, seed: int):
        rng = np.random.default_rng(seed)
        r = min(k + SVD_TAIL, m, n)
        self.shape, self.k = (m, n), k
        self.sigma = np.concatenate(
            [np.arange(k, 0, -1, dtype=np.float64), 0.1 * 0.8 ** np.arange(r - k)]
        )
        self.us = (np.linalg.qr(rng.standard_normal((m, r)))[0] * self.sigma).astype(np.float32)
        self.vt = np.linalg.qr(rng.standard_normal((n, r)))[0].T.astype(np.float32)
        self.fro = float(np.linalg.norm(self.sigma))  # ||A||_F

    def rows(self, lo: int, hi: int) -> np.ndarray:
        return self.us[lo:hi] @ self.vt

    def matrix(self) -> np.ndarray:
        out = np.empty(self.shape, np.float32)
        for lo in range(0, self.shape[0], GEN_BLOCK):
            out[lo : lo + GEN_BLOCK] = self.rows(lo, lo + GEN_BLOCK)
        return out


def check_svd(problem: LowRankProblem, u, s, v) -> dict:
    """Compare a computed (U, s, V) with the problem, in float64."""
    m, n = problem.shape
    k = problem.k
    u, s, v = (np.asarray(x, np.float64) for x in (u, s, v))
    shapes_ok = u.shape == (m, k) and s.shape == (k,) and v.shape == (n, k)
    finite = bool(np.isfinite(u).all() and np.isfinite(s).all() and np.isfinite(v).all())
    out = {"shapes_ok": shapes_ok, "finite": finite}
    if not (shapes_ok and finite):
        return {**out, "ok": False}
    # Residuals ||A v_i - s_i u_i|| and ||A^T u_i - s_i v_i||, over the very
    # f32 bytes the client sent, rebuilt block by block.
    av = np.zeros(k)
    atu = np.zeros((n, k))
    for lo in range(0, m, GEN_BLOCK):
        a = problem.rows(lo, lo + GEN_BLOCK).astype(np.float64)
        ub = u[lo : lo + GEN_BLOCK]
        av += ((a @ v - ub * s) ** 2).sum(axis=0)
        atu += a.T @ ub
    res_av = np.sqrt(av)
    res_atu = np.linalg.norm(atu - v * s, axis=0)
    sigma_err = np.abs(s - problem.sigma[:k])
    ortho_u = np.abs(u.T @ u - np.eye(k)).max()
    ortho_v = np.abs(v.T @ v - np.eye(k)).max()
    # Weyl: rounding A (and each Krylov vector) to bf16 perturbs A by at most
    # 2 u_bf16 ||A||_F, which bounds both the shift of every singular value and
    # the residual of a converged triplet. The values asked for are 1 apart, so
    # this bound (0.21 here) still fails a missed or misplaced value.
    tol_sigma = 2 * U_BF16 * problem.fro
    # U = Us^T Ub and V = Vs^T Vb are sums of L = k + 10 products of two
    # bf16-rounded factors, so each column is unit-norm and orthogonal to
    # within 2 u_bf16 sqrt(L).
    tol_ortho = 2 * U_BF16 * np.sqrt(k + 10)
    out.update(
        sigma_max_abs_err=float(sigma_err.max()),
        sigma_max_rel_err=float((sigma_err / problem.sigma[:k]).max()),
        residual_av_max=float(res_av.max()),
        residual_atu_max=float(res_atu.max()),
        ortho_u_max=float(ortho_u),
        ortho_v_max=float(ortho_v),
        tol_sigma=tol_sigma,
        tol_residual=tol_sigma,
        tol_ortho=float(tol_ortho),
    )
    out["ok"] = bool(
        sigma_err.max() <= tol_sigma
        and res_av.max() <= tol_sigma
        and res_atu.max() <= tol_sigma
        and max(ortho_u, ortho_v) <= tol_ortho
    )
    return out


def phase_svd(engine, grid, *, m=SVD_ROWS, n=SVD_COLS, k=SVD_RANK, seed=0) -> dict:
    t = _now()
    problem = LowRankProblem(m, n, k, seed)
    a = problem.matrix()
    rec = {"phase": "svd", "grid": list(grid), "shape": [m, n], "k": k, "build_s": _now() - t}
    session = _connect(engine, grid, "smoke-svd")
    try:
        # A lazy send snapshots the array; dropping the client's own copy
        # before the bytes cross keeps one copy fewer on the host (the check
        # rebuilds rows from the factors).
        t = _now()
        al_a = session.send(a, name="A")
        del a
        with session.policy("eager"):
            # The first routine on A forces the send: ||A||_F, one pass.
            norm = float(np.asarray(session.run("elemental", "normest", al_a).data()))
            rec["send_s"] = _now() - t
            rec["devices"] = _devices_of(engine, (m, n))
            # cse=False: the warm call must run again, not reuse the first.
            outs = []
            for key in ("run_cold_s", "run_warm_s"):
                t = _now()
                al_u, al_s, al_v = session.run(
                    "elemental", "truncated_svd", al_a, n_outputs=3, k=k, cse=False
                )
                s = np.asarray(al_s.data())  # the program has finished on device
                rec[key] = _now() - t
                outs.append((al_u, s, al_v))
        al_u, s, al_v = outs[-1]
        t = _now()
        u, v = np.asarray(al_u.data()), np.asarray(al_v.data())
        rec["collect_s"] = _now() - t
        al_a.free()
        # the handles' graph holds the send snapshot: drop it before the check
        del al_a, al_u, al_v, outs
    finally:
        session.close()
    t = _now()
    rec.update(check_svd(problem, u, s, v))
    rec["check_s"] = _now() - t
    # A sum of squares in f32: relative error far below one bf16 rounding.
    rec["fro_rel_err"] = abs(norm - problem.fro) / problem.fro
    rec["ok"] = bool(rec["ok"] and rec["fro_rel_err"] <= 2 * U_BF16)
    return rec


# -- gemm ------------------------------------------------------------------
def check_gemm(a, b, c, rows) -> dict:
    """Sampled rows of C against float64 NumPy, elementwise."""
    n = a.shape[1]
    a64 = a[rows].astype(np.float64)
    b64 = b.astype(np.float64)
    ref = a64 @ b64
    got = np.asarray(c[rows], np.float64)
    # One bf16 pass rounds both operands (2 u_bf16 per product); f32
    # accumulation of n products adds at most n u_f32, all relative to |A||B|.
    bound = (2 * U_BF16 + (n + 2) * U_F32) * (np.abs(a64) @ np.abs(b64))
    err = np.abs(got - ref)
    finite = bool(np.isfinite(got).all())
    return {
        "finite": finite,
        "max_err_over_bound": float((err / bound).max()),
        "rel_fro_err": float(np.linalg.norm(got - ref) / np.linalg.norm(ref)),
        "ok": bool(finite and (err <= bound).all()),
    }


def phase_gemm(engine, grid, *, n=GEMM_N, seed=0) -> dict:
    rng = np.random.default_rng(seed + 1)
    a = rng.standard_normal((n, n), dtype=np.float32)
    b = rng.standard_normal((n, n), dtype=np.float32)
    rows = np.sort(rng.choice(n, size=min(GEMM_SAMPLE_ROWS, n), replace=False))
    rec = {"phase": "gemm", "grid": list(grid), "shape": [n, n, n]}
    session = _connect(engine, grid, "smoke-gemm")
    try:
        with session.policy("eager"):
            t = _now()
            al_a, al_b = session.send(a, name="A"), session.send(b, name="B")
            rec["send_s"] = _now() - t
            for key in ("run_cold_s", "run_warm_s"):
                t = _now()
                al_c = session.run("elemental", "gemm", al_a, al_b, cse=False)
                c = np.asarray(al_c.data())  # collect: ends once C is on the host
                rec[key] = _now() - t
        rec["devices"] = _devices_of(engine, (n, n))
    finally:
        session.close()
    rec.update(check_gemm(a, b, c, rows))
    return rec


# -- uneven send (four chips) ------------------------------------------------
def phase_uneven(engine, grid, *, shape=UNEVEN_SHAPE, seed=0) -> dict:
    """Uneven rows → pad → resident → collect → strip, bit-exact, twice: the
    first send pads in the wire decode; after the session closes, the engine
    keeps the bytes host-side, and a second session's send of the same bytes
    is placed from there through the fused pad kernel."""
    x = np.random.default_rng(seed + 2).standard_normal(shape, dtype=np.float32)
    rec = {"phase": "uneven", "grid": list(grid), "shape": list(shape)}
    for key in ("wire", "attach"):
        session = _connect(engine, grid, f"smoke-uneven-{key}")
        try:
            t = _now()
            back = np.asarray(session.send(x, name="X").data())
            rec[f"{key}_roundtrip_s"] = _now() - t
            rec[f"{key}_devices"] = _devices_of(engine, shape)
            rec[f"{key}_bit_exact"] = bool(np.array_equal(back, x))
            stats = session.stats.summary()
            rec[f"{key}_fused_relayouts"] = int(stats["fused_relayouts"])
            rec[f"{key}_cross_session_reuses"] = int(stats["cross_session_reuses"])
        finally:
            session.close()
    rec["ok"] = bool(
        rec["wire_bit_exact"]
        and rec["attach_bit_exact"]
        and rec["attach_cross_session_reuses"] >= 1
        and rec["attach_fused_relayouts"] >= 1
    )
    return rec


# -- driver ----------------------------------------------------------------
def host_bytes_free() -> int:
    """Bytes this process may still allocate: MemAvailable, capped by the
    cgroup's limit where there is one."""
    with open("/proc/meminfo") as f:
        free = next(int(ln.split()[1]) * 1024 for ln in f if ln.startswith("MemAvailable:"))
    try:
        with open("/sys/fs/cgroup/memory.max") as f:
            limit = f.read().strip()
        with open("/sys/fs/cgroup/memory.current") as f:
            used = int(f.read())
    except OSError:
        return free
    return free if limit == "max" else min(free, int(limit) - used)


def _fail(msg: str) -> int:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips",
        type=int,
        choices=(1, 4),
        default=1,
        help="4: only the paths that span chips, on a 2x2 worker group",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_FORCE_PALLAS"):
        return _fail("REPRO_FORCE_PALLAS is a test knob; unset it to measure the chip")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _fail(f"no TPU: jax sees {devices[0].platform} devices only")
    if len(devices) < args.chips:
        return _fail(f"--chips {args.chips} but jax sees {len(devices)} device(s)")

    rows = SVD_ROWS if args.chips == 4 else SVD_ROWS_ONE_CHIP
    need = HOST_COPIES * rows * SVD_COLS * 4 + HOST_SLACK
    free = host_bytes_free()  # the runtime, started above, holds its share
    if need > free:
        # Refuse before allocating: running out of host memory kills the run.
        return _fail(
            f"the svd phase needs about {need / 1e9:.1f} GB of host memory at "
            f"{rows} rows and {free / 1e9:.1f} GB is free"
        )
    from repro.launch import runtime

    cache = runtime.enable_compile_cache()
    import repro
    from repro.kernels import ops
    from repro.serve.wire import server_for

    if ops.backend() != "pallas":
        return _fail(f"kernels would run as {ops.backend()!r}, not compiled Pallas")
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    header = {
        "device": device,
        "jax": jax.__version__,
        "kernels": ops.backend(),
        "compile_cache": cache,
        "peak_rss_bytes": _peak_rss(),
    }
    print(json.dumps(header), flush=True)

    engine = repro.AlchemistEngine()
    grid = (2, 2) if args.chips == 4 else (1, 1)
    phases = [functools.partial(phase_svd, m=rows), phase_gemm]
    if args.chips == 4:
        phases.append(phase_uneven)
    results = []
    try:
        for phase in phases:
            rec = phase(engine, grid, seed=args.seed)
            rec["peak_rss_bytes"] = _peak_rss()  # of the process, so far
            results.append(rec)
            print(json.dumps(rec), flush=True)
    finally:
        srv = server_for(engine)
        if srv is not None:
            srv.stop()
        engine.shutdown()
    memory = {
        "peak_hbm_bytes": [
            (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices[: args.chips]
        ],
        "peak_rss_bytes": _peak_rss(),
    }
    print(json.dumps({"memory": memory}), flush=True)
    failed = [r["phase"] for r in results if not r["ok"]]
    if failed:
        return _fail(f"phase(s) out of tolerance: {failed}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
