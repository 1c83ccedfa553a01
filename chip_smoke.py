#!/usr/bin/env python3
"""Drive heat_tpu's analytics main path once on a TPU and check what comes out.

    python3 chip_smoke.py [--seed N]          # one chip, nine phases
    python3 chip_smoke.py --four-chips        # the cross-chip paths only

One process, one chip (or one host's four). The script sets no platform:
JAX picks the device, and anything but a TPU ends the run before any work.
Each phase goes through ``import heat_tpu as ht`` and nothing below it, at
f32, on data made on the device from ``--seed``; it is checked against a
plain ``jax.numpy``/NumPy expression of the same maths with the tolerance
stated at the check, and prints one JSON line of its own. No failure is
caught: an exception or a failed comparison ends the run non-zero and no
result line is printed. The last line of a good run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Tolerances: on a TPU, XLA's default for an f32 ``dot`` is one bf16 pass
(relative 2^-9 per product), and the public calls that contract through it
(cdist's and KMeans' quadratic expansion, the gram matmul, Lasso's
matvecs) inherit that. Their references are computed at ``"highest"`` and
their tolerances admit the bf16 pass; the linalg calls and the kernels
ask for full f32 themselves and are held to f32 tolerances.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time
import warnings

import numpy as np

# Floors from ISSUE 21; a rehearsal may shrink them, the chip run does not.
SIZES = {
    "moments": (1 << 25, 32),  # 4 GiB, the statistical_moments protocol
    "kmeans": (1 << 24, 32, 8, 10),  # rows, features, clusters, iterations
    "cdist": (40_000, 18),  # SUSY strong-scaling size: a 6.4 GB result
    "qr": (1 << 20, 64),
    "chol": 1024,
    "lasso": (1 << 22, 64, 5),  # rows, features, sweeps
    "knn": (4096, 1 << 16, 18, 5),  # queries, references, features, k
    "frame": (1 << 24, 4096),  # rows, distinct keys
    "stream": (1 << 23, 32, 1 << 20),  # rows, features, chunk rows: a 1 GiB file
    "ragged": 1_000_003,  # rows that no tile divides: the kernels' ragged last tile
    "serve": ((1 << 16) + 37, 32, 8, 36),  # fit rows (ragged too), features, clusters, requests
    # the cross-chip operand (rows, features); rows of the sort; rows and
    # distinct keys of the groupby: four rows to a key, so the per-shard
    # combine leaves a quarter of a million partials for the exchange to
    # move. A million rows each: the chip's compiler takes minutes over
    # every program with a sort in it (221 s over the four-chip sort at
    # this size, 280 s at 2^22, compiled in the sandbox), and this phase has six
    # such: the sort, the groupby's combine and its merge, for four chips and for one
    "four": (1 << 22, 32, 1 << 20, 1 << 20, 1 << 18),
}
KERNEL_MODE = "pallas"  # what every kernel a phase drives must have been dispatched as


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(cond, msg: str):
    if not cond:
        fail(msg)


# --------------------------------------------------------------------- harness
def fenced(fn, arrays=lambda out: out):
    """(result, its device buffers, seconds): ``fn()`` timed to
    ``jax.block_until_ready``. ``arrays`` picks the device arrays out of
    what ``fn`` returns (an estimator's fitted state)."""
    import jax

    is_dnd = lambda x: hasattr(x, "larray")
    t0 = time.perf_counter()
    out = fn()
    bufs = [getattr(x, "larray", x) for x in jax.tree_util.tree_leaves(arrays(out), is_leaf=is_dnd)]
    jax.block_until_ready(bufs)
    return out, bufs, round(time.perf_counter() - t0, 4)


class Phase:
    """One phase's record: its JSON line is printed when the block ends
    without an exception, and only then."""

    def __init__(self, name: str, kernels=None, twins=None):
        """``kernels``: how many times the phase's own calls dispatch each
        kernel, all of them as ``.{KERNEL_MODE}``. ``twins``: the
        KERNEL_STATS entries of calls that by their shape belong to a
        kernel's declared XLA twin. Nothing else may be counted."""
        self.rec = {"phase": name, "ok": False}
        self.expected = {f"{k}.{KERNEL_MODE}": n for k, n in (kernels or {}).items()} | (twins or {})

    def __enter__(self):
        from heat_tpu.core.kernels import reset_kernel_stats

        gc.collect()
        reset_kernel_stats()
        return self

    def cold(self, fn, arrays=lambda out: out, tag=""):
        """First call, compile included, fenced on ``arrays`` of what it
        returns (see ``fenced``); the warm call is fenced on the same.
        ``tag`` prefixes the record's keys where a phase times two calls."""
        self._arrays = arrays
        out, bufs, self.rec[tag + "cold_s"] = fenced(fn, arrays)
        self.rec["out_dtypes"] = sorted({str(b.dtype) for b in bufs} | set(self.rec.get("out_dtypes", ())))
        return out

    def warm(self, fn, tag=""):
        """The same call again: fenced seconds, and it must compile nothing."""
        from heat_tpu.analysis.sanitizer import sanitizer

        with sanitizer(self.rec["phase"] + " warm") as region:
            out, _, self.rec[tag + "warm_s"] = fenced(fn, self._arrays)
        self.rec[tag + "warm_compiles"] = region.compiles
        check(region.compiles == 0, f"{self.rec['phase']}: {tag}warm call compiled {region.compiles} programs")
        return out

    def kernels_dispatched(self):
        """Freeze KERNEL_STATS for the phase's own calls (references and
        forced comparisons come after) and hold them to the phase's count,
        entry for entry: a kernel that declined, fell back or ran
        interpreted shows as an entry that is not expected or as a count
        that is short."""
        import heat_tpu as ht

        stats = {k: v for k, v in ht.KERNEL_STATS.items() if k != "dispatches"}
        self.rec["kernel_stats"] = stats
        check(stats == self.expected,
              f"{self.rec['phase']}: kernel dispatch was {stats}, expected {self.expected}")

    def __exit__(self, et, ev, tb):
        if et is not None:
            return False
        import jax

        wide = [d for d in self.rec.get("out_dtypes", []) if d.endswith("64")]
        if wide:  # 64-bit arrays that the phase's f32 inputs did not ask for
            self.rec["x64_findings"] = wide
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
        self.rec["peak_bytes_in_use"] = peaks[0] if len(peaks) == 1 else peaks
        self.rec["ok"] = True
        print(json.dumps(self.rec), flush=True)
        return False


def on_mesh(comm, shape, fn, *args, axis=0):
    """Run ``fn`` jitted with its ``shape`` output born split along ``axis`` of ``comm``."""
    import jax

    return jax.jit(fn, out_shardings=comm.array_sharding(shape, axis))(*args)


def make_blobs(comm, key, n: int, f: int, k: int):
    """(x, centers): ``n`` rows around ``k`` centres 8 sigma apart, made on the mesh."""
    import jax
    import jax.numpy as jnp

    kc, kx = jax.random.split(key)
    centers = jax.random.normal(kc, (k, f), jnp.float32) * 8.0

    def gen(kx, centers):
        lab = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0) % k
        pick = (lab == jnp.arange(k, dtype=jnp.int32)[None, :]).astype(jnp.float32)
        return jax.random.normal(kx, (n, f), jnp.float32) + pick @ centers

    return on_mesh(comm, (n, f), gen, kx, centers), centers


def rel_err(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))


# ---------------------------------------------------------------------- phases
def phase_device(args, cache_dir: str):
    import jax

    from heat_tpu import native

    dev = jax.devices()[0]
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)  # a failed build raises here
        built = native.available()
    check(built, "heat_tpu.native is not available (g++ build of native/src/*.cpp)")
    rec = {
        "phase": "device", "ok": True, "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()), "seed": args.seed,
        "jax_enable_x64": bool(jax.config.jax_enable_x64),
        "compile_cache_dir": cache_dir,
        "native_available": built,
        "memory_stats": {k: v for k, v in (dev.memory_stats() or {}).items()
                         if k in ("bytes_in_use", "bytes_limit", "peak_bytes_in_use")},
    }
    print(json.dumps(rec), flush=True)


def phase_moments(key):
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht

    n, f = SIZES["moments"]
    comm = ht.get_comm()
    make_rows = lambda rows, k: ht.array(
        on_mesh(comm, (rows, f), lambda k: jax.random.normal(k, (rows, f), jnp.float32) * 3.0 + 1.5, k),
        split=0,
    )
    make = lambda k: make_rows(n, k)

    def sweep(x):
        return [(ht.mean(x, axis=a), ht.std(x, axis=a)) for a in (None, 0, 1)]

    # per sweep: mean and std over axes None and 0 are the kernel's, over
    # axis 1 (a reduction along the lanes of each row) its declared XLA
    # twin's; two sweeps, and mean and std of the ragged buffer
    with Phase("moments", kernels={"moments_onepass": 2 * 4 + 2}, twins={"moments_onepass.xla": 2 * 2}) as ph:
        ph.rec["shape"] = [n, f]
        k1, k2 = jax.random.split(key)
        x = make(k1)
        got = ph.cold(lambda: sweep(x))
        for a, (m, s) in zip((None, 0, 1), got):
            # f32 re-association over 2^25 rows: 1e-4 absolute and relative
            np.testing.assert_allclose(m.numpy(), np.asarray(jnp.mean(x.larray, axis=a)), rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(s.numpy(), np.asarray(jnp.std(x.larray, axis=a)), rtol=1e-4, atol=1e-4)
        del x, got
        x = make(k2)  # a fresh buffer: the moments panel memoizes per buffer
        jax.block_until_ready(x.larray)
        ph.warm(lambda: sweep(x))
        del x
        # the kernel's last tile hangs over the end of the buffer here
        r = make_rows(SIZES["ragged"], k1)
        for got_r, want_r in ((ht.mean(r, axis=0), jnp.mean(r.larray, axis=0)), (ht.std(r, axis=0), jnp.std(r.larray, axis=0))):
            np.testing.assert_allclose(got_r.numpy(), np.asarray(want_r), rtol=1e-4, atol=1e-4)
        ph.kernels_dispatched()


def phase_kmeans(key):
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht
    from heat_tpu.core.kernels import forced_mode

    n, f, k, iters = SIZES["kmeans"]
    comm = ht.get_comm()
    with Phase("kmeans", kernels={"lloyd_fused": 3}) as ph:  # counted once a fit: cold, warm, the one-iteration fit
        ph.rec["shape"] = [n, f]
        kb, ki = jax.random.split(key)
        xa, centers = make_blobs(comm, kb, n, f, k)
        x = ht.array(xa, split=0)
        init = ht.array(centers + jax.random.normal(ki, (k, f), jnp.float32))
        fit = lambda it, c0=init: ht.cluster.KMeans(n_clusters=k, init=c0, max_iter=it, tol=None).fit(x)
        km = ph.cold(lambda: fit(iters), arrays=lambda m: (m.cluster_centers_, m.labels_))
        check(km.n_iter_ == iters, f"kmeans ran {km.n_iter_} iterations, not {iters}")
        ph.warm(lambda: fit(iters))
        # one iteration from the fitted centres: its labels are the kernel's
        # assignment against exactly those centres
        one = fit(1, km.cluster_centers_)
        ph.kernels_dispatched()
        with forced_mode("lloyd_fused", "fallback"):
            ref = fit(iters)
        # kernel: full-f32 products; fallback: XLA's one bf16 pass. Blobs are
        # 8 sigma apart, so no label moves and centroids differ by rounding
        np.testing.assert_allclose(km.cluster_centers_.numpy(), ref.cluster_centers_.numpy(), rtol=1e-3, atol=1e-3)
        c = km.cluster_centers_.larray
        with jax.default_matmul_precision("highest"):
            d2 = jnp.sum(xa * xa, 1, keepdims=True) + jnp.sum(c * c, 1)[None, :] - 2.0 * (xa @ c.T)
            want = jnp.argmin(jnp.maximum(d2, 0.0), axis=1)
        moved = int(jnp.sum(want != one.labels_.larray[:n]))
        ph.rec["labels_differing_from_argmin"] = moved
        # bit-identical up to summation order at exact near-ties: 1e-6 of the rows
        check(moved <= n // 1_000_000, f"{moved} of {n} labels differ from jnp.argmin")
        check(np.isfinite(km.inertia_), "inertia is not finite")


def phase_cdist(key):
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht

    n, f = SIZES["cdist"]
    comm = ht.get_comm()
    with Phase("cdist") as ph:
        ph.rec["shape"] = [n, f]
        ph.rec["result_bytes"] = n * n * 4
        x = ht.array(on_mesh(comm, (n, f), lambda k: jax.random.normal(k, (n, f), jnp.float32), key), split=0)
        d = ph.cold(lambda: ht.spatial.cdist(x, quadratic_expansion=True))
        check(tuple(d.shape) == (n, n), f"cdist shape {d.shape}")
        rows = np.random.default_rng(0).choice(n, 64, replace=False)
        got = np.asarray(jnp.take(d.larray, jnp.asarray(rows, jnp.int32), axis=0))[:, :n]
        del d
        ph.warm(lambda: ht.spatial.cdist(x, quadratic_expansion=True))
        xh = x.numpy().astype(np.float64)
        want2 = ((xh[rows, None, :] - xh[None, :, :]) ** 2).sum(-1)
        # the error lives in d^2, in the cross term 2·x·y of the one bf16
        # pass: each factor rounded to 8 bits moves a product by at most
        # 2^-8 of itself (2^-7 were it truncated), so d^2 by 2·2^-7·|x|·|y|
        # summed over features, whatever the data; 1e-3 covers the f32 rest.
        # On the diagonal d^2 is that error alone, and d its square root
        err2 = np.abs(got.astype(np.float64) ** 2 - want2)
        bound = 2.0**-6 * (np.abs(xh[rows]) @ np.abs(xh).T) + 1e-3
        ph.rec["max_abs_err_d2"] = float(err2.max())
        ph.rec["max_err_over_bound"] = float((err2 / bound).max())
        ph.rec["max_rel_err_d"] = float(np.abs(got[want2 > 1] / np.sqrt(want2[want2 > 1]) - 1).max())
        check(bool((err2 <= bound).all()), f"cdist: d^2 off by {err2.max()}, {(err2 / bound).max():.2f} of one bf16 pass")
        ph.kernels_dispatched()


def phase_linalg(key):
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht

    n, f = SIZES["qr"]
    nc = SIZES["chol"]
    comm = ht.get_comm()
    with Phase("linalg", kernels={"chol_panel_fused": 2}) as ph:  # cold and warm
        ph.rec["shape"] = {"qr": [n, f], "cholesky": [nc, nc]}
        kq, kc = jax.random.split(key)
        x = ht.array(on_mesh(comm, (n, f), lambda k: jax.random.normal(k, (n, f), jnp.float32), kq), split=0)
        xt = ht.array(on_mesh(comm, (f, n), lambda a: a.T, x.larray, axis=1), split=1)  # a buffer of its own
        y = jax.random.normal(kc, (4 * nc, nc), jnp.float32)
        with jax.default_matmul_precision("highest"):
            spd = ht.array(y.T @ y / (4 * nc) + jnp.eye(nc, dtype=jnp.float32))

        def run():
            q, r = ht.linalg.qr(x)
            return q, r, ht.matmul(xt, x), ht.linalg.cholesky(spd)

        q, r, g, L = ph.cold(run)
        ph.warm(run)
        ph.kernels_dispatched()
        with jax.default_matmul_precision("highest"):
            qa, ra, xa = q.larray[:n], r.larray, x.larray[:n]
            res = float(jnp.linalg.norm(qa @ ra - xa) / jnp.linalg.norm(xa))
            orth = float(jnp.linalg.norm(qa.T @ qa - jnp.eye(f, dtype=jnp.float32)))
            gref = xa.T @ xa
            La = L.larray
            cres = float(jnp.linalg.norm(La @ La.T - spd.larray) / jnp.linalg.norm(spd.larray))
            upper = float(jnp.max(jnp.abs(jnp.triu(La, 1))))
            cref = jnp.linalg.cholesky(spd.larray)
        ph.rec.update(qr_residual=res, qr_orthogonality=orth, chol_residual=cres)
        # f32 factorizations at "highest": residuals of a few hundred ulp
        check(res < 1e-4, f"QR residual {res}")
        check(orth < 1e-3, f"QR orthogonality {orth}")
        check(cres < 1e-5 and upper == 0.0, f"Cholesky residual {cres}, upper {upper}")
        np.testing.assert_allclose(np.asarray(La), np.asarray(cref), rtol=1e-4, atol=1e-5)
        # the gram goes through XLA's default precision: one bf16 pass
        gerr = rel_err(g.numpy(), np.asarray(gref))
        ph.rec["gram_rel_err"] = gerr
        check(gerr < 1e-3, f"gram matmul relative error {gerr}")


def _lasso_reference(X, y, lam, sweeps: int):
    """Plain coordinate descent, the same sweeps in the same column order
    (column 0 is the unregularized intercept), one residual kept up to date.
    The smoke's on-device comparison; the reference of record is
    ``heat_tpu/regression/reference.py`` (NumPy float64, on the host)."""
    import jax
    import jax.numpy as jnp

    n, m = X.shape
    sq = jnp.sum(X * X, axis=0)

    def column(j, carry):
        th, r = carry
        xj = jax.lax.dynamic_index_in_dim(X, j, axis=1, keepdims=False)
        rho = jnp.sum(xj * (r + xj * th[j]))
        soft = jnp.sign(rho) * jnp.maximum(jnp.abs(rho) - lam * n, 0.0)
        new = jnp.where(j == 0, rho, soft) / sq[j]
        return th.at[j].set(new), r - xj * (new - th[j])

    def sweep(_, carry):
        return jax.lax.fori_loop(jnp.int32(0), jnp.int32(m), column, carry)

    th, _ = jax.lax.fori_loop(0, sweeps, sweep, (jnp.zeros((m,), X.dtype), y))
    return th


def phase_lasso(key):
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht

    n, f, sweeps = SIZES["lasso"]
    lam = 0.05
    comm = ht.get_comm()
    with Phase("lasso") as ph:
        ph.rec["shape"] = [n, f]
        kx, kt, ke = jax.random.split(key, 3)
        theta_true = jnp.where(jnp.arange(f) % 4 == 0, jax.random.normal(kt, (f,), jnp.float32) * 2.0, 0.0)

        def gen(kx):
            X = jax.random.normal(kx, (n, f), jnp.float32).at[:, 0].set(1.0)
            return X

        Xa = on_mesh(comm, (n, f), gen, kx)
        ya = on_mesh(comm, (n,), lambda X, ke: jnp.sum(X * theta_true[None, :], axis=1)
                     + 0.1 * jax.random.normal(ke, (n,), jnp.float32), Xa, ke)
        X, y = ht.array(Xa, split=0), ht.array(ya, split=0)
        # tol=0.0 keeps the loop going for exactly max_iter sweeps
        fit = lambda: ht.regression.Lasso(lam=lam, max_iter=sweeps, tol=0.0).fit(X, y)
        est = ph.cold(fit, arrays=lambda m: m.theta)
        ph.warm(fit)
        ph.kernels_dispatched()
        check(int(est.n_iter) == sweeps, f"lasso ran {est.n_iter} sweeps, not {sweeps}")
        theta = est.theta.larray.ravel()
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(_lasso_reference, static_argnums=3)(Xa, ya, jnp.float32(lam), sweeps)
            obj = lambda th: float(jnp.sum((ya - Xa @ th) ** 2) / (2 * n) + lam * jnp.sum(jnp.abs(th[1:])))
            o0, o1 = obj(jnp.zeros_like(theta)), obj(theta)
        ph.rec.update(objective_at_zero=o0, objective_after=o1)
        check(o1 < o0, f"lasso objective did not decrease: {o0} -> {o1}")
        err = float(jnp.max(jnp.abs(theta - ref)))
        ph.rec["max_abs_coef_err"] = err
        # coefficients are O(1); XLA's default dot admits one bf16 pass
        check(err < 2e-2, f"lasso coefficients differ from plain coordinate descent by {err}")


def phase_knn(key):
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht

    nq, nt, f, k = SIZES["knn"]
    classes = 4
    comm = ht.get_comm()
    with Phase("knn", kernels={"topk_distance": 3}) as ph:  # predict cold and warm, nearest_neighbors
        ph.rec["shape"] = {"queries": [nq, f], "references": [nt, f], "k": k}
        check(nq * nt > 1 << 22, "knn sizes are below the fused-kernel gate")
        kt, kq = jax.random.split(key)
        xt_a, centers = make_blobs(comm, kt, nt, f, classes)
        xq_a = on_mesh(comm, (nq, f), lambda kq: jax.random.normal(kq, (nq, f), jnp.float32)
                       + centers[jnp.arange(nq) % classes], kq)
        xt, xq = ht.array(xt_a, split=0), ht.array(xq_a, split=0)
        yt = ht.array((jnp.arange(nt) % classes).astype(jnp.int32), split=0)
        clf = ht.classification.KNeighborsClassifier(n_neighbors=k).fit(xt, yt)
        pred = ph.cold(lambda: clf.predict(xq))
        ph.warm(lambda: clf.predict(xq))
        d2, idx = ht.spatial.nearest_neighbors(xq, xt, k)  # what predict runs inside
        ph.kernels_dispatched()
        got = pred.numpy()
        np.testing.assert_array_equal(got, np.arange(nq) % classes)  # blobs are 8 sigma apart
        rows = np.random.default_rng(1).choice(nq, 64, replace=False)
        th, qh = xt.numpy().astype(np.float64), xq.numpy().astype(np.float64)[rows]
        true = ((qh[:, None, :] - th[None, :, :]) ** 2).sum(-1)
        want = np.sort(true, axis=1)[:, :k]
        # the neighbours returned are the k nearest: their exact distances
        # equal the k smallest, to the kernel's f32 quadratic expansion
        np.testing.assert_allclose(np.sort(np.take_along_axis(true, idx.numpy()[rows].astype(np.int64), 1), 1), want, rtol=1e-3)
        np.testing.assert_allclose(d2.numpy()[rows], want, rtol=1e-3, atol=1e-3)


def phase_frame_stream(key):
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht
    from heat_tpu import stream

    rows, nkeys = SIZES["frame"]
    sn, sf, chunk = SIZES["stream"]
    comm = ht.get_comm()
    # mean and std of the whole array, then one fold a chunk in each of two passes
    with Phase("frame_stream", kernels={"moments_onepass": 2 + 2 * -(-sn // chunk)}) as ph:
        ph.rec["shape"] = {"frame_rows": rows, "keys": nkeys, "stream": [sn, sf], "chunk_rows": chunk}
        kk, kv, ks = jax.random.split(key, 3)
        keys = on_mesh(comm, (rows,), lambda k: jax.random.randint(k, (rows,), 0, nkeys, jnp.int32), kk)
        vals = on_mesh(comm, (rows,), lambda k: jax.random.normal(k, (rows,), jnp.float32), kv)
        frame = ht.frame.Frame({"k": ht.array(keys, split=0), "v": ht.array(vals, split=0)})
        cols = ("k", "v_sum", "v_mean", "count")
        agg = lambda: frame.groupby("k").agg(["sum", "mean", "count"])
        out = ph.cold(agg, arrays=lambda fr: [fr[c] for c in cols], tag="frame_")
        ph.warm(agg, tag="frame_")
        got = {c: out[c].numpy() for c in cols}
        kh, vh = np.asarray(keys), np.asarray(vals).astype(np.float64)
        cnt = np.bincount(kh, minlength=nkeys)
        sums = np.bincount(kh, weights=vh, minlength=nkeys)
        np.testing.assert_array_equal(got["k"], np.arange(nkeys))
        np.testing.assert_array_equal(got["count"], cnt)
        # f32 sums of ~rows/keys standard normals: 1e-4 of their sqrt(n) scale
        np.testing.assert_allclose(got["v_sum"], sums, rtol=1e-4, atol=1e-4 * np.sqrt(rows / nkeys))
        np.testing.assert_allclose(got["v_mean"], sums / cnt, rtol=1e-4, atol=1e-6)
        del frame, out, keys, vals

        x = ht.array(on_mesh(comm, (sn, sf), lambda k: jax.random.normal(k, (sn, sf), jnp.float32) * 2.0 - 0.5, ks), split=0)
        want_mean, want_std = ht.mean(x, axis=0).numpy(), ht.std(x, axis=0).numpy()
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            path = os.path.join(tmp, "stream.h5")
            *_, ph.rec["save_s"] = fenced(lambda: ht.save(x, path, "data"))
            ph.rec["file_bytes"] = os.path.getsize(path)
            del x

            def one_pass():
                est = stream.StreamingMoments()
                for ch in stream.ChunkIterator(path, chunk, dataset="data"):
                    est.update(ch)
                return est.mean, est.std

            m, s = ph.cold(one_pass, tag="stream_")
            ph.warm(one_pass, tag="stream_")
        ph.kernels_dispatched()
        # chunked Chan merges vs the one-shot answer on the same rows: f32 re-association
        np.testing.assert_allclose(m.numpy(), want_mean, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(s.numpy(), want_std, rtol=1e-4, atol=1e-4)


def phase_serve(key):
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht
    from heat_tpu import serve
    from heat_tpu.analysis.sanitizer import sanitizer
    from heat_tpu.core.kernels import forced_mode

    n, f, k, nreq = SIZES["serve"]
    comm = ht.get_comm()
    with Phase("serve", kernels={"lloyd_fused": 1}) as ph:  # the fit; predict is plain XLA
        kb, kr = jax.random.split(key)
        xa, centers = make_blobs(comm, kb, n, f, k)
        fit = lambda: ht.cluster.KMeans(n_clusters=k, init=ht.array(centers), max_iter=5, tol=None).fit(ht.array(xa, split=0))
        km = fit()
        rng = np.random.default_rng(int(jax.random.randint(kr, (), 0, 1 << 30)))
        pool = np.asarray(xa[: 1 << 12])
        payloads = [pool[rng.integers(0, len(pool), size=int(r))] for r in rng.choice([1, 3, 8, 17, 32, 50], size=nreq)]
        ph.rec["shape"] = {"requests": nreq, "rows": sorted({len(p) for p in payloads})}
        svc = serve.ServeService()
        try:
            svc.register_model("km", km)
            # one request in flight at a time: batch formation, and so the set
            # of buckets, does not depend on arrival timing
            one_pass = lambda: [svc.predict("km", p, timeout=300) for p in payloads]
            t0 = time.perf_counter()
            first = one_pass()
            ph.rec["cold_s"] = round(time.perf_counter() - t0, 4)
            with sanitizer("serve warm") as region:
                t0 = time.perf_counter()
                second = one_pass()
                ph.rec["warm_s"] = round(time.perf_counter() - t0, 4)
            ph.rec["warm_compiles"] = region.compiles
            check(region.compiles == 0, f"second pass over the same buckets compiled {region.compiles} programs")
            ph.rec["serve_stats"] = {k: v for k, v in svc.stats().items() if isinstance(v, (int, float))}
        finally:
            svc.close(timeout=60)
        ph.rec["out_dtypes"] = sorted({str(np.asarray(a).dtype) for a in first})
        ph.kernels_dispatched()
        with forced_mode("lloyd_fused", "fallback"):  # n is ragged: the kernel's last tile hangs over
            np.testing.assert_allclose(km.cluster_centers_.numpy(), fit().cluster_centers_.numpy(), rtol=1e-3, atol=1e-3)
        for p, a, b in zip(payloads, first, second):
            direct = km.predict(ht.array(p)).numpy()
            np.testing.assert_array_equal(np.asarray(a), direct)
            np.testing.assert_array_equal(np.asarray(b), direct)


# ------------------------------------------------------------------ four chips
def phase_four_chips(key):
    """Every cross-chip path on the 4-chip communicator and on a 1-chip
    communicator in this same process, on the same seeded data.

    The four-chip side of every path runs first, and no one-chip operand
    exists until it has: per-device memory is then the four-chip paths'
    alone, and is held to an even spread three times — peaks after the
    exchange paths (sort, groupby) on otherwise empty chips, bytes in use
    once the large operand is built, peaks again after every path. A path
    that gathered its operand onto one chip would show there as a peak the
    others do not have. Then the one-chip side, and the comparisons."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import heat_tpu as ht
    from heat_tpu.core.communication import MeshCommunication
    from heat_tpu.parallel import ring_attention

    n, f, n_sort, n_group, n_keys = SIZES["four"]
    m_ring, m_attn, m_resplit = min(n, 1 << 13), min(n, 1 << 12), min(n, 1 << 16)
    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, JAX reports {len(devs)}")
    comm4 = ht.get_comm()
    comm1 = MeshCommunication(devices=devs[:1])
    check(comm4.size == 4 and comm1.size == 1, f"mesh sizes {comm4.size}, {comm1.size}")

    # lloyd_sharded once and moments_sharded four times (mean and std over
    # axes None and 0) on four chips; the same calls again on one
    with Phase("four_chips", kernels={"lloyd_fused": 2, "moments_onepass": 8}) as ph:
        ph.rec.update(shape=[n, f], sort_rows=n_sort, groupby_rows=n_group, groupby_keys=n_keys)
        four, one_side, paths = {}, [], {}

        def placed(name, a, split):
            """Four devices, sharded unless split=None, a quarter of the buffer on each."""
            buf = a.larray
            check(len(buf.sharding.device_set) == 4, f"{name}: on {len(buf.sharding.device_set)} devices")
            check((split is None) == buf.sharding.is_fully_replicated,
                  f"{name}: split={split} but replicated={buf.sharding.is_fully_replicated}")
            held = sorted(sh.data.nbytes for sh in buf.addressable_shards)
            want = buf.nbytes if split is None else buf.nbytes // 4
            check(held == [want] * 4, f"{name}: the devices hold {held} bytes, not {want} each")

        def spread(what, stat):
            """``stat`` of every device's memory, within 25 % of each other."""
            vals = [d.memory_stats()[stat] for d in devs]
            ph.rec[what] = vals
            check(max(vals) <= 1.25 * min(vals), f"{what} is uneven across the chips: {vals}")

        def path(name, f4, f1, cmp, arrays=lambda out: out):
            """Run the four-chip side now; queue the one-chip side and the comparison."""
            four[name], _, s4 = fenced(f4, arrays)
            paths[name] = {"four_cold_s": s4}
            one_side.append((name, f1, cmp, arrays))

        def one(a4):
            """The same buffer on the one-chip communicator (device 0)."""
            buf = jax.device_put(a4.larray, SingleDeviceSharding(devs[0]))
            return ht.array(buf, split=a4.split, comm=comm1)

        born4 = lambda shape, fn, *a: ht.array(on_mesh(comm4, shape, fn, *a), split=0)
        kb, ka, ks, kv, kk = jax.random.split(key, 5)

        # --- the exchange paths, on chips that hold nothing else yet
        s4 = born4((n_sort,), lambda k: jax.random.normal(k, (n_sort,), jnp.float32), ks)
        v4 = born4((n_group,), lambda k: jax.random.normal(k, (n_group,), jnp.float32), kv)
        keys4 = born4((n_group,), lambda k: jax.random.randint(k, (n_group,), 0, n_keys, jnp.int32), kk)
        placed("sort operand", s4, 0)

        def sort_cmp(a, b):
            placed("sorted values", a[0], 0)
            placed("sort indices", a[1], 0)
            np.testing.assert_array_equal(a[0].numpy(), b[0].numpy())
            np.testing.assert_array_equal(a[1].numpy(), b[1].numpy())  # stable: one right answer

        path("dsort", lambda: ht.sort(s4, axis=0), lambda: ht.sort(one(s4), axis=0), sort_cmp)
        gb = lambda k, v: ht.frame.Frame({"k": k, "v": v}).groupby("k").agg(["sum", "count"])
        gb_cols = lambda fr: [fr[c] for c in ("k", "v", "count")]
        moves0 = ht.MOVE_STATS.get("bucket_moves", 0)

        def gb_cmp(a, b):
            for c in ("k", "v", "count"):  # ragged split-0 columns: sharded, not gathered
                sh = a[c].larray.sharding
                check(len(sh.device_set) == 4 and not sh.is_fully_replicated, f"groupby column {c}: {sh}")
            np.testing.assert_array_equal(a["k"].numpy(), b["k"].numpy())
            np.testing.assert_array_equal(a["count"].numpy(), b["count"].numpy())
            np.testing.assert_allclose(a["v"].numpy(), b["v"].numpy(), rtol=1e-4, atol=1e-3)

        path("groupby", lambda: gb(keys4, v4), lambda: gb(one(keys4), one(v4)), gb_cmp, arrays=gb_cols)
        check(ht.MOVE_STATS.get("bucket_moves", 0) > moves0, "the 4-chip groupby made no bucket exchange")
        spread("peak_bytes_after_exchanges", "peak_bytes_in_use")

        # --- the large operand
        before = [d.memory_stats()["bytes_in_use"] for d in devs]
        xa, centers = make_blobs(comm4, kb, n, f, 8)
        x4 = ht.array(xa, split=0)
        placed("x", x4, 0)
        used = [d.memory_stats()["bytes_in_use"] - b for d, b in zip(devs, before)]
        ph.rec["operand_bytes_per_device"] = used
        check(max(used) <= 1.25 * min(used) and min(used) >= n * f * 4 // 4,
              f"the operand is not spread evenly: {used}")
        init4 = ht.array(centers + 1.0)
        close = lambda rtol, atol: (lambda a, b: np.testing.assert_allclose(a.numpy(), b.numpy(), rtol=rtol, atol=atol))

        def kmeans_cmp(a, b):
            placed("kmeans.labels_", a.labels_, 0)
            np.testing.assert_allclose(a.cluster_centers_.numpy(), b.cluster_centers_.numpy(), rtol=1e-4, atol=1e-4)
            np.testing.assert_array_equal(a.labels_.numpy(), b.labels_.numpy())

        fit = lambda x, c0: ht.cluster.KMeans(n_clusters=8, init=c0, max_iter=5, tol=None).fit(x)
        fitted = lambda m: (m.cluster_centers_, m.labels_)
        # lloyd_sharded: psum of the shard sums
        path("kmeans", lambda: fit(x4, init4), lambda: fit(x1, one(init4)), kmeans_cmp, arrays=fitted)
        for axis in (None, 0):  # moments_sharded: psum Chan combine
            path(f"mean_std_axis_{axis}", lambda axis=axis: ht.std(x4, axis=axis) + ht.mean(x4, axis=axis),
                 lambda axis=axis: ht.std(x1, axis=axis) + ht.mean(x1, axis=axis), close(1e-5, 1e-5))

        def qr_cmp(a, b):  # TSQR: R is unique up to row signs
            placed("q", a[0], 0)
            np.testing.assert_allclose(np.abs(a[1].numpy()), np.abs(b[1].numpy()), rtol=1e-3, atol=1e-2)
            with jax.default_matmul_precision("highest"):
                res = float(jnp.linalg.norm(a[0].larray @ a[1].larray - x4.larray) / jnp.linalg.norm(x4.larray))
            check(res < 1e-4, f"4-chip TSQR residual {res}")

        path("qr", lambda: ht.linalg.qr(x4), lambda: ht.linalg.qr(x1), qr_cmp)

        def resplit_cmp(a, b):
            placed("resplit 0->1", a[0], 1)
            placed("resplit 1->None", a[1], None)
            np.testing.assert_array_equal(a[1].numpy(), b.numpy())

        small4 = x4[:m_resplit]
        path("resplit", lambda: (small4.resplit(1), small4.resplit(1).resplit(None)), lambda: x1[:m_resplit], resplit_cmp)

        y4 = x4[:m_ring]

        def ring_cmp(a, b):  # each side is within one bf16 pass of the truth: see phase_cdist
            placed("ring cdist", a, 0)
            yh = np.abs(y4.numpy().astype(np.float64))
            diff2 = np.abs(a.numpy().astype(np.float64) ** 2 - b.numpy().astype(np.float64) ** 2)
            paths["ring_cdist"]["max_abs_diff_d2"] = float(diff2.max())
            check(bool((diff2 <= 2 * (2.0**-6 * (yh @ yh.T) + 1e-3)).all()), f"ring cdist differs by {diff2.max()} in d^2")

        path("ring_cdist", lambda: ht.spatial.cdist(y4, y4, quadratic_expansion=True, use_ring=True),
             lambda: ht.spatial.cdist(x1[:m_ring], x1[:m_ring], quadratic_expansion=True), ring_cmp)
        qkv = jax.random.normal(ka, (3, m_attn, 64), jnp.float32)
        q4 = [jax.device_put(a, comm4.array_sharding(a.shape, 0)) for a in qkv]

        def attn_cmp(a, b):
            check(len(a.sharding.device_set) == 4 and not a.sharding.is_fully_replicated, f"ring attention: {a.sharding}")
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-2, atol=2e-2)

        path("ring_attention", lambda: ring_attention(*q4, comm4, causal=True),
             lambda: ring_attention(*[jax.device_put(a, SingleDeviceSharding(devs[0])) for a in qkv], comm1, causal=True),
             attn_cmp)
        spread("peak_bytes_after_four_chip_side", "peak_bytes_in_use")

        # --- the one-chip side (device 0 holds it all) and the comparisons
        x1 = one(x4)
        for name, f1, cmp, arrays in one_side:
            r1, _, paths[name]["one_cold_s"] = fenced(f1, arrays)
            cmp(four.pop(name), r1)
            del r1
        ph.rec["paths"] = paths
        ph.kernels_dispatched()


# ------------------------------------------------------------------------ main
PHASES = (phase_moments, phase_kmeans, phase_cdist, phase_linalg, phase_lasso,
          phase_knn, phase_frame_stream, phase_serve)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="seed of every phase's data")
    ap.add_argument("--four-chips", action="store_true",
                    help="run the cross-chip paths on 4 chips against 1, and nothing else")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX reports platform {dev.platform!r}, not 'tpu': nothing was run",
              file=sys.stderr)
        return 2
    from heat_tpu.utils.profiling import configure_compile_cache

    phase_device(args, configure_compile_cache())  # before the first compile
    key = jax.random.PRNGKey(args.seed)
    if args.four_chips:
        phase_four_chips(key)
    else:
        check(len(jax.devices()) == 1, f"one chip expected, JAX reports {len(jax.devices())}; "
              "the four-chip path is behind --four-chips")
        for i, phase in enumerate(PHASES):
            phase(jax.random.fold_in(key, i))
    print(json.dumps({"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind,
                                             "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
