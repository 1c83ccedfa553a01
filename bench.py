"""Benchmark driver: ALL FIVE BASELINE.md progression configs.

1. factory/reduction smoke (zeros/arange + sum/mean) — correctness gate;
2. statistical_moments: mean+std over axes {None, 0, 1}, reference
   protocol ``/root/reference/benchmarks/statistical_moments/heat-cpu.py``;
3. cdist GB/s, reference protocol ``/root/reference/benchmarks/
   distance_matrix/heat-cpu.py:20-34`` (SUSY-like n x 18), reported as
   bytes of the materialized (n, n) f32 output per second;
4. KMeans throughput, reference protocol ``/root/reference/benchmarks/
   kmeans/heat-cpu.py:20-26`` (k=8 on synthetic blobs);
5. tall-skinny QR + gram matmul GFLOP/s (progression config 5), plus the
   lasso 1-iter protocol (``/root/reference/benchmarks/lasso/heat-cpu.py``)
   as coordinate-descent sweeps/s.

Measurement protocol (r5, "api-r5"): every HEADLINE metric is measured
through the PUBLIC DNDarray API — ``KMeans(...).fit(x)``,
``ht.spatial.cdist(x)``, ``ht.mean``/``ht.std``, ``ht.linalg.qr``,
``ht.matmul``, ``Lasso().fit`` — on split=0 DNDarrays, exactly the program
a user runs (the reference protocol times ``fit()``/``cdist`` on
distributed arrays, ``/root/reference/benchmarks/kmeans/heat-cpu.py:20-26``).
The raw-jnp kernel measurements ride along as ``kernel_*`` diagnostics and
feed the per-workload ``api_over_kernel`` ratio: headline / the
same-program-structure jnp kernel, i.e. the pure cost of DNDarray
dispatch. Two workloads changed program structure when moving to the API
(their old kernel series continue under new keys, see ``update_history``):

- moments: the API sequence is SIX separate reduction programs (mean+std
  per axis, like the reference protocol) — the pre-r5 number timed one
  artificially fused 6-in-1 jit no user can express; that series
  continues as ``kernel_moments_fused_gbps``.
- matmul: the API gram is ``ht.matmul(xT, x)`` over two distinct buffers
  (the reference API has no lazy transpose), reading 2x the bytes of the
  pre-r5 same-buffer ``x.T @ x`` kernel; that series continues as
  ``kernel_matmul_gram_gflops``.

Every metric's ``*_vs_baseline`` is the speedup over a single-CPU-process
NumPy implementation of the identical computation (BASELINE.json target:
>=8x). All device timing uses chained programs + marginal (long-minus-
short) differencing, a protocol from before ``jax.block_until_ready``
could be trusted as a fence; it is kept until roadmap item S1 rewrites
this file into cells measured on the chip (``chip_smoke.py`` fences with
``block_until_ready`` and does not import this file).
API-path batches need no eps-chaining: a single device executes programs
in dispatch order, so fetching one scalar from the LAST output fences the
whole batch (and an eps-chain would add a full extra pass over the
operand as a separate program on the API path, corrupting the number).

Regression visibility: BENCH_HISTORY.json records the best value ever
seen per metric; each run appends a ``vs_best`` map (current/best) to
the output and updates the file. Every metric carries a physical cap (``CAPS``): a marginal
estimate above the workload's achievable ceiling is a corrupted timer,
not a capability, and can neither become a best nor pass as a rep.

A sixth workload, ``ragged_elementwise``, runs once per invocation in an
8-virtual-CPU-device subprocess (``bench.py --ragged-worker``): the
redistribute -> elementwise -> redistribute round trip on a skewed layout,
new direct-ragged-compute path vs the seed's forced-rebalance path, with
layout-exchange counts asserted via ``MOVE_STATS``.

A seventh, ``fused_pipeline`` (``bench.py --fused-worker``, same
8-virtual-device subprocess pattern), times the 3-op standardize chain
``(x - mu) * isig * w`` through the public API with ``ht.lazy()`` (one
fused program) against eager dispatch (three programs), plus a raw-jnp
fused-kernel comparator row; the warm fused trip is counter-asserted in
the worker to be exactly 1 dispatch, 0 compiles, 0 traces.

An eighth, ``stream_pipeline`` (``bench.py --stream-worker``, same
subprocess pattern), runs the out-of-core chunked pipeline: single-pass
streaming estimators (moments + cov + histogram) over an HDF5 file via
``ChunkIterator``, with the double-buffered ``Prefetcher`` ON vs OFF and
a per-chunk host fence on the consumer (see the worker docstring for why
the fence is what makes the synchronous comparator honest under JAX's
async dispatch). Warm passes are counter-asserted to 0 compiles/0 traces
and the streaming results are oracle-checked in-worker against the
in-memory ``ht.mean``/``ht.var``/``ht.cov``/``ht.histogram``.

A ninth, ``frame_groupby`` (``bench.py --frame-worker``, same
subprocess pattern), drives the sort-based shuffle engine: a
``Frame.groupby(key).sum()`` over 2^16 rows at key cardinalities 16 /
4096 / 2^16, counter-asserted to exactly ONE bucketed exchange per
operand (``MOVE_STATS["bucket_moves"]``) and 0 warm compiles/traces,
oracle-checked against numpy in-worker. Two comparator rows: a raw-jnp
``jax.ops.segment_sum`` program (the single-device speed-of-light) and
the sort-then-loop decomposition a user would write from the existing
public API (``ht.sort`` + one masked reduction per key) — the engine
must beat the latter >= 2x at low cardinality (gated by bench_check).

A tenth, ``serve_ws2`` (``bench.py --serve-ws2-worker``, TWO
coordinated ``jax.distributed`` subprocesses of 4 virtual devices
each), proves the replicated dispatch tick earns its keep at real
world size 2: the same burst of requests against process-spanning
sharded weights is served once with the tick armed (the ws>1 default —
no flush() anywhere, timer/count batching re-armed) and once in the
tick-disabled barrier-per-request discipline the disarmed triggers
force on an interactive client. Gated: tick-batched throughput >= 2x
barrier-driven, 0 lockstep divergences, 0 warm compiles/traces, and at
least one tick actually fired.

An eleventh, ``sketch_pipeline`` (``bench.py --sketch-worker``, same
8-virtual-device subprocess pattern), folds the three fixed-size
sketches (KLL quantiles + HyperLogLog distinct + Count-Min top-k) in a
SINGLE pass over the same gzip HDF5 chunk stream, against the exact
in-memory comparator row (np.percentile + np.unique + full-count top-k
on identical rows). Every error column is paired with the sketch's own
promised bound and checked in-worker (``sketch_divergences``, gated
== 0), and the warm pass is counter-asserted to 0 compiles/0 traces.

Protocol r7 additionally bounds the two DMA-overlap-banded kernel
diagnostics (``OVERLAP_BAND``): their best/best_median can never ratchet
beyond 1.2x the trailing clean median, retiring the stale single-run
spikes that made healthy in-band runs read as regressions (the numbers
themselves were inside the overlap band; the bar was the artifact. The
band's width is not measured on the current toolchain).

Protocol r8 (the fused-kernel layer): the moments API sweep runs on a
FRESH buffer per trial — the one-pass moments panel memoizes per buffer,
so re-sweeping the same buffer would time host-side memo lookups — and
two fused-kernel rows join the summary: ``kernel_moments_onepass_gbps``
(public mean+std pair, fresh buffer, Region-asserted 0 warm compiles)
and ``kmeans_fused_ratio`` (fused Lloyd iteration rate over the unfused
component-sum floor probe; ``bench_check`` gates it at >= 1.0).

Prints exactly ONE compact JSON line (headline numbers + gate state,
< 2 KB — validated by ``tools/bench_check.py``); the full result dict is
written to the ``BENCH_DETAIL.json`` sidecar.
"""
import json
import os
import time

import numpy as np

HISTORY_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "BENCH_HISTORY.json")

N = 1 << 19  # 524288 samples
F = 32
K = 8
ITERS = 30

CDIST_N = 30000  # (n, n) f32 output = 3.6 GB, fits single-chip HBM
CDIST_F = 18  # SUSY feature count (reference config)

MOM_N, MOM_F = 1 << 22, 32
QR_N, QR_F = 1 << 20, 64
LASSO_N, LASSO_F = 1 << 19, 64
SOLVE_N = 2048


def numpy_lloyd(x, c, iters):
    for _ in range(iters):
        d2 = (x * x).sum(1)[:, None] + (c * c).sum(1)[None, :] - 2.0 * (x @ c.T)
        labels = d2.argmin(1)
        onehot = np.eye(K, dtype=x.dtype)[labels]
        counts = onehot.sum(0)
        c = np.where(counts[:, None] > 0, (onehot.T @ x) / np.maximum(counts, 1)[:, None], c)
    return c


# graftlint: unbounded-cache - holds a handful of numpy baselines, not executables
_BASELINE_CACHE = {}  # numpy baselines measured once, reused across reps

# headline metrics (public-API measured) the history/floor/median
# machinery gates on
HEADLINE = (
    "kmeans_iters_per_sec",
    "cdist_gbps",
    "moments_gbps",
    "qr_gflops",
    "matmul_gflops",
    "solve_gflops",
    "lasso_sweeps_per_sec",
)

# kernel diagnostics recorded in history (never gated): the raw-jnp
# programs matching each headline's structure, plus the two legacy fused
# kernels whose pre-r5 series migrated to these keys
KERNEL_TRACKED = (
    "kernel_kmeans_iters_per_sec",
    "kernel_cdist_gbps",
    "kernel_moments_gbps",
    "kernel_moments_fused_gbps",
    "kernel_moments_onepass_gbps",
    "kernel_qr_gflops",
    "kernel_matmul_gflops",
    "kernel_matmul_gram_gflops",
    "kernel_solve_gflops",
    "kernel_lasso_sweeps_per_sec",
)

# Chip model (v5e-1, the bench chip): peak dense bf16 matmul rate and HBM
# bandwidth from the public TPU v5e spec. Default matmul precision on
# this chip IS bf16 (MXU passes).
PEAK_BF16_GFLOPS = 197_000.0
PEAK_HBM_GBPS = 819.0

# Intensity-aware achievable ceilings, in each metric's COUNTED units
# (the counted work per trial is a normalization constant; the ceiling is
# counted_work / min_time where min_time = max(bytes/HBM, flops/MXU) from
# the byte/flop accounting documented per workload in _roofline). The
# binding bound and the accounting ride in the roofline JSON.
ACHIEVABLE = {
    # API gram ht.matmul(xT, x): 2 distinct (n, f) f32 operands ->
    # AI = 2nf^2 / (2*4nf) = f/4 = 16 FLOP/byte; min(197e3, 16*819)
    "matmul_gflops": 16 * PEAK_HBM_GBPS,  # 13_104
    # legacy same-buffer gram x.T @ x: one operand read -> AI = f/2 = 32
    "kernel_matmul_gram_gflops": 32 * PEAK_HBM_GBPS,  # 26_208
    "kernel_matmul_gflops": 16 * PEAK_HBM_GBPS,
    # CholQR2 traffic, compiled-program accounting: the old 7-pass hand
    # model (2x read X, W+2xR Q1, W+R Q2) assumed every consumer reads a
    # fused producer exactly once. cost_analysis() on the compiled
    # guarded CholQR2 reports 15.5 operand passes (each triangular solve
    # re-reads its (n,f) input AND commits its Q intermediate before the
    # next Gram re-reads it; the orthogonality-check Gram re-reads Q2;
    # 22.5 counting the cond's budgeted Householder branch), and the
    # measured steady-state rate pins the on-chip effective count at ~14
    # -> ceiling = 2nf^2 / (14*4nf / HBM) = f*HBM/28
    "qr_gflops": QR_F * PEAK_HBM_GBPS / 28.0,  # 1_872
    "kernel_qr_gflops": QR_F * PEAK_HBM_GBPS / 28.0,
    # cdist: the (n, n) f32 output MUST commit to HBM (3.6 GB >> VMEM);
    # counted bytes = that output, so the ceiling IS the HBM write rate
    "cdist_gbps": PEAK_HBM_GBPS,
    "kernel_cdist_gbps": PEAK_HBM_GBPS,
    # API moments (r8, fresh buffer per sweep): the one-pass panel serves
    # mean+std for ALL axes from 2 reads (kernel read covers axis None+0,
    # one more for axis 1) + the 2 passes generating the buffer = 4
    # physical passes; counted bytes = 3 passes -> ceiling = 819 * 3/4.
    # (pre-r8 the same-buffer sequence was 9 passes minimum = 273)
    "moments_gbps": PEAK_HBM_GBPS * 3.0 / 4.0,  # 614
    # unfused jnp comparator: mean (1 pass) + std (2 passes) per axis =
    # 9 passes for the 6-program sequence; counted = 3 -> 819 * 3/9
    "kernel_moments_gbps": PEAK_HBM_GBPS / 3.0,
    # public mean+std pair (axis=None) on a fresh buffer: generate (2
    # passes) + ONE panel read = 3 physical passes = the counted 3-pass
    # normalization exactly, so the ceiling is the raw HBM rate
    "kernel_moments_onepass_gbps": PEAK_HBM_GBPS,
    # fused 6-in-1 sweep: information minimum is 2 passes (all three
    # means in one read, all three centered moments in a second);
    # counted bytes = 3 passes -> ceiling = 819 * 3/2
    "kernel_moments_fused_gbps": PEAK_HBM_GBPS * 1.5,  # 1_228
    # kmeans ceiling: the k=8 distance matmul alone (2NFK flops) on an
    # MXU running 8-of-128 output lanes cannot beat ~22 us/iter -> 45k
    # iters/s; the empirical floor probe in the roofline is the honest
    # per-round number, this static cap only guards the history
    "kmeans_iters_per_sec": 45_000.0,
    "kernel_kmeans_iters_per_sec": 45_000.0,
    # solve: LU + two triangular solves at n=2048 is compute-bound (the
    # 16.8 MB operand gives AI ~ 170 FLOP/B on the counted 2/3 n^3
    # flops, far past the ridge). The bound is the f32 MXU rate
    # ("highest" precision, ~peak/8); the sequential panel/triangular
    # chain keeps ~80% of the flops in trailing GEMMs -> effective
    # ceiling ~ peak/10 in counted units
    "solve_gflops": PEAK_BF16_GFLOPS / 10.0,  # 19_700
    "kernel_solve_gflops": PEAK_BF16_GFLOPS / 10.0,
    # lasso: 65-column sequential CD chain; per sweep >= 2 passes over X
    # (each column read for rho and for the residual update)
    "lasso_sweeps_per_sec": 2 * PEAK_HBM_GBPS / (2 * LASSO_N * (LASSO_F + 1) * 4 / 1e9),
    "kernel_lasso_sweeps_per_sec": 2 * PEAK_HBM_GBPS / (2 * LASSO_N * (LASSO_F + 1) * 4 / 1e9),
}

# Physical caps = achievable x grace. Committed-output and latency-chain
# workloads get 1.1x (nothing can hide the bound); matmul-family
# workloads get 1.35x (DMA prefetch of the next chained trial overlaps
# with MXU compute, hiding up to ~1/3 of the read time — measured: the
# honest same-buffer gram band is 25-33 TFLOP/s vs the 26.2 no-overlap
# ceiling; the retired 50.5/102.8 TFLOP/s spikes sit at 1.9x/3.9x).
def _cap(key: str) -> float:
    grace = 1.35 if "matmul" in key or "kmeans" in key else 1.1
    if "cdist" in key:
        grace = 1.02  # committed HBM write; spec tolerance only
    return ACHIEVABLE[key] * grace


CAPS = {k: _cap(k) for k in ACHIEVABLE}


def _api_timed(call, fence, attempts=4):
    """best-of-``attempts`` timer for back-to-back public-API calls.

    A single device executes dispatched programs in order, so one scalar
    fetch from the LAST output fences the whole batch; refs to earlier
    outputs are dropped as the loop advances, keeping device memory
    bounded (at most two live outputs)."""

    def timed(reps):
        best = float("inf")
        for _ in range(attempts):
            t0 = time.perf_counter()
            out = None
            for _ in range(reps):
                out = call()
            fence(out)
            best = min(best, time.perf_counter() - t0)
        return best

    return timed


def kmeans_bench():
    import jax.numpy as jnp

    import heat_tpu as ht
    from heat_tpu.cluster.kmeans import _lloyd_fit

    rng = np.random.default_rng(7)
    true_centers = rng.normal(size=(K, F)).astype(np.float32) * 8
    data = np.concatenate(
        [tc + rng.normal(size=(N // K, F)).astype(np.float32) for tc in true_centers]
    )
    rng.shuffle(data)
    init = data[rng.choice(N, K, replace=False)].copy()

    # the whole fit is ONE device program (lax.while_loop), so host<->TPU
    # latency is paid once. Completion is forced with a device->host
    # fetch, and the per-call overhead is excluded by differencing a
    # long and a short run (marginal throughput, the sustained rate the
    # reference protocol's 30x10-trial loop measures).
    x = ht.array(data, split=0)
    xa = x.larray
    c = jnp.asarray(init)
    init_dnd = ht.array(init)  # replicated initial centroids for the API fit

    def timed_fit_kernel(iters: int, repeats: int = 5) -> float:
        np.asarray(_lloyd_fit(xa, c, K, iters, -1.0)[0])  # warm compile
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            c_run, _, n_done = _lloyd_fit(xa, c, K, iters, -1.0)
            np.asarray(c_run)  # force full sync via host fetch
            best = min(best, time.perf_counter() - t0)
            assert int(n_done) == iters
        return best

    def timed_fit_api(iters: int, repeats: int = 5) -> float:
        # the public path: fit() itself syncs (inertia + n_iter fetches);
        # those constants cancel in the long-minus-short difference
        model = ht.cluster.KMeans(n_clusters=K, init=init_dnd, max_iter=iters, tol=None)
        model.fit(x)  # warm compile for this max_iter
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            fitted = ht.cluster.KMeans(
                n_clusters=K, init=init_dnd, max_iter=iters, tol=None
            ).fit(x)
            best = min(best, time.perf_counter() - t0)
            assert fitted.n_iter_ == iters if hasattr(fitted, "n_iter_") else True
        return best

    short, long_ = 10, 4010  # marginal window >> per-call RPC jitter

    def marginal_ips(timed_fit, cap: float) -> float:
        # An above-cap marginal estimate is a corrupted measurement (a
        # noise spike shrinking t_long - t_short), not a capability:
        # discard it and fall back to the conservative whole-run rate,
        # same policy as _marginal. Clamping the broken estimate to the
        # cap would report the hardware ceiling as if it were measured.
        t_long = timed_fit(long_)
        est = (long_ - short) / max(t_long - timed_fit(short), 1e-9)
        if est <= cap:
            return est
        return min(long_ / t_long, cap)

    k_ips = marginal_ips(timed_fit_kernel, CAPS["kernel_kmeans_iters_per_sec"])
    a_ips = marginal_ips(timed_fit_api, CAPS["kmeans_iters_per_sec"])

    # --- single-process numpy baseline (best of 3 timed runs, cached) ---
    if "kmeans" not in _BASELINE_CACHE:
        nb_iters = 3
        nb_best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            numpy_lloyd(data, init.copy(), nb_iters)
            nb_best = min(nb_best, time.perf_counter() - t0)
        _BASELINE_CACHE["kmeans"] = nb_iters / nb_best
    baseline_ips = _BASELINE_CACHE["kmeans"]

    out = {
        "kmeans_iters_per_sec": round(a_ips, 3),
        "unit": f"iters/s via KMeans.fit on a split=0 DNDarray (n={N}, f={F}, k={K})",
        "vs_baseline": round(a_ips / baseline_ips, 3),
        "kernel_kmeans_iters_per_sec": round(k_ips, 3),
    }
    if "kmeans_probe" not in _BASELINE_CACHE:
        _BASELINE_CACHE["kmeans_probe"] = kmeans_floor_probe(xa, c)
    return out


def kmeans_floor_probe(xa, c):
    """Empirical k=8 floor: time the Lloyd iteration's two matmul halves
    in isolation (chained-eps marginal protocol). The fused while-loop
    iteration should land at or below their sum — if it does, the
    measured iters/s IS the small-k floor of this decomposition and the
    remaining headroom is only what a single-pass fused kernel could
    reclaim (VERDICT r4 weak item 4)."""
    import jax
    import jax.numpy as jnp

    from heat_tpu.spatial.distance import _quadratic_expand

    k = c.shape[0]

    @jax.jit
    def dist_argmin(x, eps):
        d2 = _quadratic_expand(x + eps * jnp.float32(1e-30), c)
        return jnp.sum(jnp.argmin(d2, axis=1))

    labels = jnp.argmin(_quadratic_expand(xa, c), axis=1)
    onehot = jax.nn.one_hot(labels, k, dtype=xa.dtype)

    @jax.jit
    def update(x, eps):
        xx = x + eps * jnp.float32(1e-30)
        s = onehot.T @ xx
        return s[0, 0]

    float(dist_argmin(xa, jnp.float32(0)))
    float(update(xa, jnp.float32(0)))
    r_dist = _marginal(_chained_timed(dist_argmin, xa), 20, 220, 1.0)
    r_upd = _marginal(_chained_timed(update, xa), 20, 220, 1.0)
    t_sum_us = 1e6 / r_dist + 1e6 / r_upd
    return {
        "dist_argmin_us": round(1e6 / r_dist, 1),
        "update_matmul_us": round(1e6 / r_upd, 1),
        "component_sum_us": round(t_sum_us, 1),
        "floor_iters_per_sec": round(1e6 / t_sum_us, 1),
        "note": (
            "k=8 leaves 8-of-128 MXU output lanes active; the update "
            "matmul (k x n @ n x f) dominates. A fused-iteration rate at "
            "or above floor_iters_per_sec means the while-loop body "
            "already overlaps/fuses as well as the decomposition allows."
        ),
    }


def _merge_median(runs):
    """Per-key median of numeric values across full bench invocations
    (VERDICT r3 weak item 1: one sample per round rode the ±20% noise);
    non-numeric keys take the first run's value."""
    import statistics

    merged = {}
    for key in runs[0]:
        vals = [r[key] for r in runs if key in r]
        if all(isinstance(v, (int, float)) and not isinstance(v, bool) for v in vals):
            merged[key] = round(statistics.median(vals), 3)
        else:
            merged[key] = vals[0]
    return merged


def _roofline(merged):
    """Per-workload achieved fraction of the ACHIEVABLE ceiling — the
    intensity-aware bound min(MXU peak, AI x HBM peak) computed from the
    byte/flop accounting in ``ACHIEVABLE`` — with the binding bound and
    the accounting stated per row. fraction_of_achievable ~ 1.0 means
    the kernel is done; > 1.0 happens only where cross-trial DMA overlap
    can hide part of the (already counted) traffic."""
    rows = {
        "matmul": {
            "achieved": merged.get("matmul_gflops"),
            "achievable": ACHIEVABLE["matmul_gflops"],
            "unit": "counted GFLOP/s",
            "bound": "hbm",
            "model": "2nf^2 FLOP vs two distinct (n,f) f32 operand reads: AI=f/4=16 FLOP/B",
        },
        "matmul_gram_kernel": {
            "achieved": merged.get("kernel_matmul_gram_gflops"),
            "achievable": ACHIEVABLE["kernel_matmul_gram_gflops"],
            "unit": "counted GFLOP/s",
            "bound": "hbm",
            "model": "same-buffer x.T@x: one (n,f) read, AI=f/2=32 FLOP/B; >1.0 = chained-trial DMA overlap",
        },
        "qr": {
            "achieved": merged.get("qr_gflops"),
            "achievable": ACHIEVABLE["qr_gflops"],
            "unit": "counted GFLOP/s (nominal 2nf^2)",
            "bound": "hbm",
            "model": (
                "CholQR2 ~14 effective passes over the 268 MB operand "
                "(compiled cost_analysis: the 7-pass hand model missed "
                "triangular-solve re-reads, Q intermediates, the guard Gram)"
            ),
        },
        "solve": {
            "achieved": merged.get("solve_gflops"),
            "achievable": ACHIEVABLE["solve_gflops"],
            "unit": "counted GFLOP/s (2/3 n^3 + 2n^2)",
            "bound": "mxu-f32",
            "model": (
                f"n={SOLVE_N} LU + 2 trisolves: 16.8 MB operand, AI~170 FLOP/B "
                "-> compute-bound; f32-highest MXU ~peak/8, ~80% of flops in "
                "trailing GEMMs -> ~peak/10 in counted units"
            ),
        },
        "cdist": {
            "achieved": merged.get("cdist_gbps"),
            "achievable": ACHIEVABLE["cdist_gbps"],
            "unit": "GB/s of committed (n,n) output",
            "bound": "hbm-output",
            "model": "3.6 GB output write >> VMEM: the write rate IS the bound",
        },
        "moments": {
            "achieved": merged.get("moments_gbps"),
            "achievable": ACHIEVABLE["moments_gbps"],
            "unit": "counted GB/s (3-pass normalization)",
            "bound": "hbm",
            "model": (
                "r8 fresh-buffer 6-call sequence on the one-pass panel: "
                "generate (2) + kernel read for axes None+0 (1) + axis-1 "
                "read (1) = 4 physical passes"
            ),
        },
        "moments_onepass_kernel": {
            "achieved": merged.get("kernel_moments_onepass_gbps"),
            "achievable": ACHIEVABLE["kernel_moments_onepass_gbps"],
            "unit": "counted GB/s (3-pass normalization)",
            "bound": "hbm",
            "model": (
                "public mean+std pair, fresh buffer: generate (2) + ONE "
                "panel read (1) = 3 physical passes = the counted bytes"
            ),
        },
        "moments_fused_kernel": {
            "achieved": merged.get("kernel_moments_fused_gbps"),
            "achievable": ACHIEVABLE["kernel_moments_fused_gbps"],
            "unit": "counted GB/s (3-pass normalization)",
            "bound": "hbm",
            "model": "6-in-1 fused sweep: information minimum 2 passes; XLA compiles ~4",
        },
        "lasso": {
            "achieved": merged.get("lasso_sweeps_per_sec"),
            "achievable": None,
            "unit": "CD sweeps/s",
            "bound": "latency-chain",
            "model": (
                "65-column strictly sequential coordinate descent: 130 dependent "
                "(n,)-vector ops per sweep; bandwidth model (2 passes over X = "
                f"{ACHIEVABLE['lasso_sweeps_per_sec']:.0f}/s) is NOT the binding bound"
            ),
        },
    }
    probe = _BASELINE_CACHE.get("kmeans_probe")
    km = {
        "achieved": merged.get("kmeans_iters_per_sec"),
        "unit": "iters/s",
        "bound": "mxu-narrow-output (k=8: 8-of-128 lanes)",
        "model": "empirical floor probe: unfused dist+argmin and onehot-update matmul timed in isolation",
    }
    if probe:
        km["achievable"] = probe["floor_iters_per_sec"]
        km["probe"] = probe
    else:
        km["achievable"] = None
    rows["kmeans"] = km
    for row in rows.values():
        ach, ceil = row.get("achieved"), row.get("achievable")
        row["fraction_of_achievable"] = (
            round(ach / ceil, 4) if (ach and ceil) else None
        )
    return rows


FLOOR = 0.7  # fail the run when a median falls below 0.7x the gate baseline


def main():
    import sys

    reps = int(os.environ.get("HEAT_TPU_BENCH_REPS", "3"))
    from heat_tpu import analysis
    from heat_tpu.utils.profiling import configure_compile_cache

    configure_compile_cache()  # before the first compile

    runs = []
    # the timed section runs under the collective-lockstep sanitizer:
    # recording is pure host bookkeeping (zero extra compiles/syncs,
    # counter-asserted in tests/test_lockstep.py), and on a multi-process
    # pod the exit check turns a rank that lost lockstep into a hard
    # LockstepError instead of a silently skewed headline number
    with analysis.lockstep() as _ls:
        for _ in range(reps):
            runs.append(
                {
                    **kmeans_bench(),
                    **cdist_bench(),
                    **moments_bench(),
                    **qr_matmul_bench(),
                    **solve_bench(),
                    **lasso_bench(),
                }
            )
    merged = _merge_median(runs)
    tracked = HEADLINE + KERNEL_TRACKED
    best = {
        k: round(max(r[k] for r in runs), 3) for k in tracked if k in merged
    }
    # a single rep wildly above its own run's median is a timing artifact
    # (e.g. a marginal-differencing glitch under the roofline cap), not a
    # best — flag it so best_of_reps stays readable as real headroom
    suspect = {
        k: v for k, v in best.items() if merged.get(k) and v > 2.0 * merged[k]
    }
    if suspect:
        best = {**best, "suspect_timer_artifacts": sorted(suspect)}
    out = {
        "metric": "kmeans_iters_per_sec",
        "value": merged.pop("kmeans_iters_per_sec"),
        **merged,
        **smoke_check(),
        "bench_reps": reps,
        "bench_protocol": "api-r8 (headline metrics timed through the public DNDarray API)",
        "best_of_reps": best,
    }
    out["api_over_kernel"] = _api_over_kernel(out)
    out["roofline"] = _roofline({**merged, "kmeans_iters_per_sec": out["value"]})
    # fused Lloyd iteration vs the unfused component-sum floor probe
    # (dist+argmin and update matmul timed in isolation on the same
    # mesh): >= 1.0 means fusing never made an iteration slower than its
    # own parts — the bench_check gate for the fused-kernel layer
    probe = _BASELINE_CACHE.get("kmeans_probe")
    if probe and probe.get("floor_iters_per_sec"):
        out["kmeans_fused_ratio"] = round(
            out["value"] / probe["floor_iters_per_sec"], 3
        )
    # the gate uses the deltas computed THIS run, not a file round-trip
    # (a swallowed history-write failure must not evaluate stale numbers)
    out["vs_best"], out["vs_best_median"], out["vs_trailing_median"] = (
        update_history(out, suspect=set(suspect))
    )
    violations = {
        k: v
        for k, v in out["vs_trailing_median"].items()
        if v < FLOOR and k in HEADLINE
    }
    if violations:
        out["floor_violations"] = violations
    out["suite_seconds"] = _suite_seconds()
    out["lockstep_events"] = _ls.events
    out["lockstep_divergences"] = int(analysis.LOCKSTEP_STATS["divergences"])
    # once per invocation, not per rep: these workloads are their own
    # subprocesses with their own repeats, and their gates are the
    # asserted exchange/dispatch counts
    out.update(ragged_bench())
    out.update(fused_bench())
    out.update(stream_bench())
    out.update(sketch_bench())
    out.update(serve_bench())
    out.update(serve_ws2_bench())
    out.update(frame_bench())
    detail_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_DETAIL.json"
    )
    try:
        with open(detail_path, "w") as fh:
            json.dump(out, fh, indent=1, sort_keys=True)
    except OSError:
        pass
    print(json.dumps(_compact_summary(out, detail_path)))
    if violations and not os.environ.get("HEAT_TPU_BENCH_NO_FLOOR"):
        # median-of-reps below 0.7x the trailing median of prior runs is
        # a regression, not chip-allocation noise — fail loudly
        # (trailing baseline so a slower chip doesn't false-fail against
        # a faster chip's best)
        sys.exit(1)


def _api_over_kernel(out):
    """headline / matching-structure kernel, per workload. The kernel in
    each denominator runs the SAME program shape as the API path (for
    matmul, the two-buffer jnp gram), so the ratio isolates DNDarray
    dispatch cost. Exception since r8: the moments denominator is still
    the 6-program unfused jnp sequence while the API path runs the
    one-pass panel, so a moments ratio > 1 reads as fusion gain, not
    dispatch overhead."""
    pairs = {
        "kmeans": ("kmeans_iters_per_sec", "kernel_kmeans_iters_per_sec"),
        "cdist": ("cdist_gbps", "kernel_cdist_gbps"),
        "moments": ("moments_gbps", "kernel_moments_gbps"),
        "qr": ("qr_gflops", "kernel_qr_gflops"),
        "matmul": ("matmul_gflops", "kernel_matmul_gflops"),
        "solve": ("solve_gflops", "kernel_solve_gflops"),
        "lasso": ("lasso_sweeps_per_sec", "kernel_lasso_sweeps_per_sec"),
    }
    value = lambda k: out["value"] if k == "kmeans_iters_per_sec" else out.get(k)
    return {
        name: round(value(a) / value(b), 3)
        for name, (a, b) in pairs.items()
        if value(a) and value(b)
    }


def smoke_check():
    """Progression config 1: factories + reductions, split=None, 1 chip."""
    import heat_tpu as ht

    z = ht.zeros((64, 8))
    a = ht.arange(512, dtype=ht.float32)
    ok = (
        float(z.sum().item()) == 0.0
        and float(a.sum().item()) == 511 * 512 / 2
        and abs(float(a.mean().item()) - 255.5) < 1e-4
    )
    return {"smoke_ok": bool(ok)}


RAGGED_ROWS = (1 << 16) + 5
RAGGED_COLS = 8


def ragged_worker():
    """Subprocess body for the ``ragged_elementwise`` workload: the cost of
    a redistribute -> elementwise -> redistribute round trip on a skewed
    layout, new direct-ragged path vs the seed's forced-rebalance path.

    Runs under JAX_PLATFORMS=cpu with 8 virtual devices (the bench chip is
    ONE device, where raggedness is trivial — any partition over one shard
    is canonical). The seed path is reproduced faithfully: the op consumed
    ``larray``, which rebalanced the operand (exchange 1) and produced a
    canonical result the user had to move back to their layout
    (exchange 2); the new path computes in place (0 exchanges). Exchange
    counts are asserted via MOVE_STATS, not assumed."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import heat_tpu as ht
    from heat_tpu.parallel.flatmove import MOVE_STATS

    p = ht.get_comm().size
    rows, cols = RAGGED_ROWS, RAGGED_COLS
    rng = np.random.default_rng(0)
    full = rng.normal(size=(rows, cols)).astype(np.float32)
    # skewed: every shard holds half its canonical share, the tail the rest
    counts = [rows // (2 * p)] * p
    counts[-1] += rows - sum(counts)
    target = np.tile([rows, cols], (p, 1))
    target[:, 0] = counts

    x = ht.array(full, split=0)
    x.redistribute_(target_map=target)

    def fence(z):
        # device fence without host assembly (numpy() would rebalance)
        float(np.asarray(z._raw[(0,) * z._raw.ndim]))

    def new_trip():
        z = (x + 1.0) * 2.0  # computes directly on the ragged layout
        z.redistribute_(target_map=target)  # already there: no-op
        return z

    def seed_trip():
        xb = ht.balance(x, copy=True)  # exchange 1: the forced rebalance
        z = (xb + 1.0) * 2.0
        z.redistribute_(target_map=target)  # exchange 2: back to the layout
        return z

    fence(new_trip())  # warm both programs
    fence(seed_trip())

    def moves_per_trip(trip):
        m0 = MOVE_STATS["ragged_moves"]
        fence(trip())
        return MOVE_STATS["ragged_moves"] - m0

    new_moves = moves_per_trip(new_trip)
    seed_moves = moves_per_trip(seed_trip)

    def rate(trip, reps=20, attempts=3):
        best = float("inf")
        for _ in range(attempts):
            t0 = time.perf_counter()
            z = None
            for _ in range(reps):
                z = trip()
            fence(z)
            best = min(best, time.perf_counter() - t0)
        return reps / best

    new_tps = rate(new_trip)
    seed_tps = rate(seed_trip)
    print(
        json.dumps(
            {
                "ragged_elementwise_speedup": round(new_tps / seed_tps, 3),
                "ragged_new_trips_per_sec": round(new_tps, 2),
                "ragged_seed_trips_per_sec": round(seed_tps, 2),
                "ragged_new_moves_per_trip": new_moves,
                "ragged_seed_moves_per_trip": seed_moves,
                "ragged_unit": (
                    f"redistribute->(x+1)*2->redistribute trips/s, skewed "
                    f"split=0 (n={rows}, f={cols}, 8 virtual CPU devices)"
                ),
            }
        )
    )


FUSED_ROWS = 1 << 16
FUSED_COLS = 16


def fused_worker():
    """Subprocess body for the ``fused_pipeline`` workload: the 3-op
    standardize chain ``(x - mu) * isig * w`` through the public API,
    ``ht.lazy()`` (ONE fused program per trip) vs eager dispatch (three
    programs per trip), with a raw-jnp jitted kernel as the structural
    comparator. The gated number is ``fused_pipeline_speedup`` =
    fused / eager trips per second.

    Counters are asserted, not assumed: after warmup one fused trip must
    be exactly 1 fused dispatch served from the program cache with 0 XLA
    compiles and 0 traces (``Region`` over COMPILE_STATS + FUSE_STATS) —
    a fusion "speedup" that secretly recompiles per trip would be a lie
    the timer can't see on a warm chip."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import heat_tpu as ht
    from heat_tpu.analysis.sanitizer import Region
    from heat_tpu.core.lazy import FUSE_STATS, reset_fuse_stats

    rows, cols = FUSED_ROWS, FUSED_COLS
    rng = np.random.default_rng(0)
    full = rng.normal(size=(rows, cols)).astype(np.float32)
    x = ht.array(full, split=0)
    mu = ht.mean(x, axis=0)
    isig = 1.0 / (ht.std(x, axis=0) + 1e-6)
    w = ht.array(rng.normal(size=(cols,)).astype(np.float32), split=None)

    def fence(z):
        # device fence without host assembly (numpy() would gather)
        float(np.asarray(z._raw[(0,) * z._raw.ndim]))

    def eager_trip():
        return (x - mu) * isig * w

    def fused_trip():
        with ht.lazy():
            return (x - mu) * isig * w

    fence(eager_trip())  # warm both paths
    fence(fused_trip())

    # the warm-path budget: 1 dispatch, cache-served, 0 compiles/traces
    reset_fuse_stats()
    region = Region("warm fused trip")
    fence(fused_trip())
    warm_compiles = region.compiles + region.traces
    warm_dispatches = FUSE_STATS["fused_dispatches"]
    assert warm_compiles == 0, region.stats()
    assert warm_dispatches == 1 and FUSE_STATS["cache_hits"] == 1, FUSE_STATS
    assert FUSE_STATS["eager_fallbacks"] == 0, FUSE_STATS

    def rate(trip, reps=30, attempts=3):
        best = float("inf")
        for _ in range(attempts):
            t0 = time.perf_counter()
            z = None
            for _ in range(reps):
                z = trip()
            fence(z)
            best = min(best, time.perf_counter() - t0)
        return reps / best

    fused_tps = rate(fused_trip)
    eager_tps = rate(eager_trip)

    # structural comparator: the same chain as ONE hand-fused jnp program
    # over the raw sharded buffers — the ceiling dispatch can reach
    kern = jax.jit(lambda xa, m, s, ww: (xa - m) * s * ww)  # graftlint: retrace - built once per bench run
    xa, m, s, ww = x._raw, mu._raw, isig._raw, w._raw
    kern(xa, m, s, ww).block_until_ready()

    def kernel_trip():
        return kern(xa, m, s, ww)

    def kernel_fence(z):
        float(np.asarray(z[(0,) * z.ndim]))

    def kernel_rate(reps=30, attempts=3):
        best = float("inf")
        for _ in range(attempts):
            t0 = time.perf_counter()
            z = None
            for _ in range(reps):
                z = kernel_trip()
            kernel_fence(z)
            best = min(best, time.perf_counter() - t0)
        return reps / best

    kernel_tps = kernel_rate()
    print(
        json.dumps(
            {
                "fused_pipeline_speedup": round(fused_tps / eager_tps, 3),
                "fused_trips_per_sec": round(fused_tps, 2),
                "eager_trips_per_sec": round(eager_tps, 2),
                "fused_kernel_trips_per_sec": round(kernel_tps, 2),
                "fused_warm_compiles": int(warm_compiles),
                "fused_warm_dispatches": int(warm_dispatches),
                "fused_unit": (
                    f"(x-mu)*isig*w standardize trips/s, split=0 "
                    f"(n={rows}, f={cols}, 8 virtual CPU devices)"
                ),
            }
        )
    )


STREAM_ROWS = 1 << 18
STREAM_COLS = 64
STREAM_CHUNK = 1 << 15


def stream_worker():
    """Subprocess body for the ``stream_pipeline`` workload: single-pass
    streaming estimators (moments + cov + histogram) over a chunked HDF5
    file, double-buffered prefetch ON (depth=2) vs OFF (synchronous
    inline reads), identical chunk loop otherwise.

    The consumer fetches one scalar of estimator state per chunk — the
    host fence every real streaming consumer has (per-chunk monitoring,
    progress, backpressure). The fence is what keeps the comparator
    honest: without it JAX's async dispatch queues the whole
    "synchronous" loop ahead of execution and the reader overlaps compute
    anyway, so both modes would time identically. With it the sync pass
    costs sum(read + compute) per chunk while the prefetcher still
    overlaps the NEXT read/stage with the current compute:
    sum(max(read, compute)).

    Counters asserted, not assumed: the warm pass runs 0 XLA compiles and
    0 traces (``Region`` over COMPILE_STATS — the compile-once chunk-loop
    contract) and the producer's busy time measurably overlapped consumer
    compute (STREAM_STATS); correctness is checked in-worker — streaming
    mean/var/cov/histogram vs the in-memory ``ht`` oracles on the same
    rows, divergences counted.

    The prefetch-vs-sync comparator (``stream_speedup``, gated >= 1.15 by
    tools/bench_check.py) is only REPORTED when the host has a second CPU
    core to run the producer on. On a single-core host both legs of the
    pipeline are CPU-bound work sharing one core — the comparator would
    measure scheduler noise around 1.0x, not the prefetcher — so the
    worker emits an explicit ``stream_overlap`` note instead of a number
    that cannot mean anything (same philosophy as the ``*_error`` degrade
    fields: absent-with-reason beats present-but-meaningless).
    """
    import shutil
    import tempfile

    import h5py
    import jax

    jax.config.update("jax_platforms", "cpu")
    import heat_tpu as ht
    from heat_tpu.analysis.sanitizer import Region
    from heat_tpu.stream import (
        STREAM_STATS,
        ChunkIterator,
        Prefetcher,
        StreamingCov,
        StreamingHistogram,
        StreamingMoments,
        reset_stream_stats,
    )

    rows, cols, chunk = STREAM_ROWS, STREAM_COLS, STREAM_CHUNK
    rng = np.random.default_rng(7)
    data = rng.normal(size=(rows, cols)).astype(np.float32)
    tmp = tempfile.mkdtemp(prefix="heat_tpu_stream_bench_")
    path = os.path.join(tmp, "stream.h5")
    try:
        # gzip chunks aligned to the read window: decompression is real
        # producer-side work (the out-of-core archive case), so the read
        # leg is comparable to the estimator compute leg and the overlap
        # the prefetcher buys is measurable rather than noise
        with h5py.File(path, "w") as fh:
            fh.create_dataset(
                "data",
                data=data,
                compression="gzip",
                compression_opts=1,
                chunks=(chunk, cols),
            )

        def one_pass(depth):
            mom = StreamingMoments()
            cov = StreamingCov()
            hist = StreamingHistogram(bins=64, range=(-5.0, 5.0))
            it = Prefetcher(ChunkIterator(path, chunk, dataset="data"), depth=depth)
            for ch in it:
                mom.update(ch)
                cov.update(ch)
                hist.update(ch)
                float(mom._mean[0])  # per-chunk host fence (see docstring)
            return mom, cov, hist

        one_pass(2)  # cold pass: compiles the estimator programs

        reset_stream_stats()
        region = Region("warm stream pass")
        mom, cov, hist = one_pass(2)
        warm_compiles = region.compiles + region.traces
        hits = int(STREAM_STATS["prefetch_hits"])
        overlap = float(STREAM_STATS["overlap_seconds"])
        assert warm_compiles == 0, region.stats()
        # hits counts chunks served instantly — 0 in a read-bound pipeline
        # (the consumer always waits a little); the invariant that holds on
        # BOTH sides of the read/compute balance is that the producer's
        # busy time overlapped consumer compute at all
        assert overlap > 0.0, dict(STREAM_STATS)

        # in-worker oracle: identical statistics computed in memory
        x = ht.array(data, split=0)
        divergences = 0
        for got, want in (
            (mom.mean.numpy(), ht.mean(x, axis=0).numpy()),
            (mom.var.numpy(), ht.var(x, axis=0).numpy()),
            (cov.cov.numpy(), ht.cov(x, rowvar=False).numpy()),
        ):
            if not np.allclose(got, want, rtol=1e-4, atol=1e-5):
                divergences += 1
        oracle_hist, _ = ht.histogram(x, bins=64, range=(-5.0, 5.0))
        if not np.array_equal(hist.hist.numpy(), oracle_hist.numpy()):
            divergences += 1

        gb = rows * cols * 4 / 1e9

        def rate(depth, attempts=3):
            best = float("inf")
            for _ in range(attempts):
                t0 = time.perf_counter()
                one_pass(depth)
                best = min(best, time.perf_counter() - t0)
            return gb / best

        pre_gbps = rate(2)
        result = {
            "stream_gbps": round(pre_gbps, 3),
            "stream_prefetch_hits": hits,
            "stream_overlap_seconds": round(overlap, 3),
            "stream_warm_compiles": int(warm_compiles),
            "stream_divergences": int(divergences),
            "stream_unit": (
                f"GB/s of gzip HDF5 rows through moments+cov+hist "
                f"estimators, chunk={chunk} rows (n={rows}, f={cols}, "
                f"8 virtual CPU devices, prefetch depth=2 vs sync)"
            ),
        }
        cores = len(os.sched_getaffinity(0))
        if cores >= 2:
            sync_gbps = rate(0)
            result["stream_sync_gbps"] = round(sync_gbps, 3)
            result["stream_speedup"] = round(pre_gbps / sync_gbps, 3)
        else:
            result["stream_overlap"] = (
                f"comparator unavailable: {cores} CPU core — producer and "
                "consumer share the core, so prefetch-vs-sync compares "
                "CPU-bound work against itself (scheduler noise around "
                "1.0x, not the prefetcher)"
            )
        print(json.dumps(result))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


SKETCH_ROWS = 1 << 18
SKETCH_COLS = 16
SKETCH_CHUNK = 1 << 15
SKETCH_TOPK = 8


def sketch_worker():
    """Subprocess body for the ``sketch_pipeline`` workload: the three
    fixed-size sketches (KLL quantiles + HyperLogLog distinct + Count-Min
    top-k) folded in a SINGLE pass over the same gzip HDF5 chunk stream
    the streaming estimators use, against the exact in-memory comparator
    row (``np.percentile`` + ``np.unique`` + full-count top-k on the
    identical rows).

    The stream is a capped Zipf draw — discrete heavy-tailed data so all
    three sketches are exercised by ONE source: big atoms for the
    heavy-hitter sketch, a few thousand distinct values for the
    cardinality sketch, and a stepped CDF that makes the KLL rank-error
    check honest (rank error of an estimate against an atom is its
    distance to the whole rank INTERVAL the atom occupies, not to one
    arbitrary side of it).

    Counters asserted, not assumed: the warm pass runs 0 XLA compiles
    and 0 traces (``Region`` over COMPILE_STATS — one cached fold
    program per sketch, replayed per chunk), and every reported error
    column is paired with the sketch's own promised bound, checked
    in-worker: KLL rank error <= ``eps``, HLL relative error <= the 4
    sigma band of ``rel_error``, top-k recall == 1.0 over true heavy
    hitters that clear the Count-Min noise floor. Misses count into
    ``sketch_divergences`` (gated == 0 by tools/bench_check.py) — the
    observed-vs-promised contract is the product here; the GB/s column
    is the price tag."""
    import shutil
    import tempfile

    import h5py
    import jax

    jax.config.update("jax_platforms", "cpu")
    from heat_tpu.analysis.sanitizer import Region
    from heat_tpu.stream import (
        ChunkIterator,
        CountMinTopK,
        HyperLogLog,
        KLLSketch,
    )

    rows, cols, chunk = SKETCH_ROWS, SKETCH_COLS, SKETCH_CHUNK
    rng = np.random.default_rng(11)
    # capped Zipf: heavy hitters for CM, ~10^4 distinct values for HLL,
    # discrete stepped CDF for the KLL interval rank check
    data = np.minimum(rng.zipf(1.3, size=(rows, cols)), 20000).astype(np.float32)
    tmp = tempfile.mkdtemp(prefix="heat_tpu_sketch_bench_")
    path = os.path.join(tmp, "sketch.h5")
    try:
        with h5py.File(path, "w") as fh:
            fh.create_dataset(
                "data",
                data=data,
                compression="gzip",
                compression_opts=1,
                chunks=(chunk, cols),
            )

        def one_pass():
            kll = KLLSketch(k=256)
            hll = HyperLogLog(p=12)
            cm = CountMinTopK(width=2048, depth=4, k=64)
            for ch in ChunkIterator(path, chunk, dataset="data"):
                kll.update(ch)
                hll.update(ch)
                cm.update(ch)
            # one host fence: the pass is measured stream-to-state, and
            # the states are a few KB each — fetching one register drains
            # the async dispatch queue without touching the chunk loop
            jax.block_until_ready(hll._regs)
            return kll, hll, cm

        one_pass()  # cold pass: compiles the three fold programs

        region = Region("warm sketch pass")
        kll, hll, cm = one_pass()
        warm_compiles = region.compiles + region.traces
        assert warm_compiles == 0, region.stats()

        # exact comparator row: the same answers computed in memory
        flat = data.ravel()
        t0 = time.perf_counter()
        exact_q = np.percentile(flat, [50.0, 90.0, 99.0])
        uniq, counts = np.unique(flat, return_counts=True)
        order = np.argsort(counts)[::-1]
        true_top = uniq[order[:SKETCH_TOPK]]
        exact_seconds = time.perf_counter() - t0

        # observed vs promised, checked in-worker. KLL rank error of an
        # estimate vs an atom-heavy CDF is the distance from q to the
        # rank interval [P(X < est), P(X <= est)] the estimate occupies.
        srt = np.sort(flat)
        kll_err = 0.0
        for q in (50.0, 90.0, 99.0):
            est = float(kll.percentile(q).numpy())
            lo = np.searchsorted(srt, est, side="left") / flat.size
            hi = np.searchsorted(srt, est, side="right") / flat.size
            kll_err = max(kll_err, lo - q / 100.0, q / 100.0 - hi, 0.0)
        hll_err = abs(hll.distinct() - uniq.size) / uniq.size
        hll_bound = 4.0 * hll.rel_error
        # recall over true heavy hitters that clear the CM noise floor
        # (eps * items): below it a hitter is indistinguishable from
        # collision noise by the sketch's own promise
        floor = cm.eps * cm.items
        promised = true_top[counts[order[:SKETCH_TOPK]] > floor]
        got_top = cm.topk(SKETCH_TOPK)[0].numpy()
        recall = float(np.isin(promised, got_top).mean()) if promised.size else 1.0
        divergences = int(kll_err > kll.eps) + int(hll_err > hll_bound) + int(
            recall < 1.0
        )

        gb = rows * cols * 4 / 1e9

        # best-of-2 and 4 decimals: the virtual-CPU fold is sort-bound
        # (XLA CPU comparator sort, replicated over 8 virtual devices
        # sharing the cores), so the honest number here is single-digit
        # MB/s — the gate is > 0 plus the error contract, not the rate
        def rate(attempts=2):
            best = float("inf")
            for _ in range(attempts):
                t0 = time.perf_counter()
                one_pass()
                best = min(best, time.perf_counter() - t0)
            return gb / best

        print(
            json.dumps(
                {
                    "sketch_gbps": round(rate(), 4),
                    "sketch_exact_gbps": round(gb / exact_seconds, 4),
                    "sketch_warm_compiles": int(warm_compiles),
                    "sketch_divergences": divergences,
                    "sketch_kll_rank_err": round(float(kll_err), 5),
                    "sketch_kll_eps": round(float(kll.eps), 5),
                    "sketch_hll_rel_err": round(float(hll_err), 5),
                    "sketch_hll_bound": round(float(hll_bound), 5),
                    "sketch_topk_recall": round(recall, 3),
                    "sketch_exact_quantiles": [round(float(v), 1) for v in exact_q],
                    "sketch_distinct_true": int(uniq.size),
                    "sketch_unit": (
                        f"GB/s of gzip HDF5 rows through KLL+HLL+CountMin "
                        f"folds in one pass, chunk={chunk} rows (n={rows}, "
                        f"f={cols}, 8 virtual CPU devices; exact row = "
                        f"np.percentile+np.unique+top-k on the same data)"
                    ),
                }
            )
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


FRAME_ROWS = 1 << 16
FRAME_CARDS = (16, 4096, 1 << 16)
FRAME_GATE_CARD = 16  # the sort-then-loop comparator runs here


def frame_worker():
    """Subprocess body for the ``frame_groupby`` workload: distributed
    groupby-sum through the shuffle engine at three key cardinalities.

    The engine's contract is asserted, not assumed, on the warm repeat:
    exactly ONE bucketed exchange per operand (key + value = 2 bucket
    moves per groupby, read from ``MOVE_STATS["bucket_moves"]``) and 0
    compiles / 0 traces (``Region``) — a warm groupby replays cached
    executables end to end. Results are oracle-checked against
    ``np.bincount`` per cardinality (divergences counted).

    Comparators: ``frame_jnp_rows_per_s`` is a jitted global
    ``jax.ops.segment_sum`` — the no-distribution speed-of-light for the
    same reduction; ``frame_loop_rows_per_s`` is the sort-then-loop
    decomposition available from the public API before this layer
    (``ht.sort`` once, then one masked ``(x * (k == u)).sum()`` reduction
    per key): its dispatch count scales with cardinality, which is
    exactly the per-key traffic the shuffle engine exists to avoid.
    ``frame_groupby_speedup`` (engine over sort-then-loop at cardinality
    16) is gated >= 2.0 by tools/bench_check.py.
    """
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    import heat_tpu as ht
    from heat_tpu.analysis.sanitizer import Region
    from heat_tpu.parallel.flatmove import MOVE_STATS

    n = FRAME_ROWS
    rng = np.random.default_rng(11)
    x = rng.standard_normal(n).astype(np.float32)
    divergences = 0
    warm_compiles = 0
    exchanges_per_operand = set()
    by_card = {}
    result = {}
    for card in FRAME_CARDS:
        keys = rng.integers(0, card, n).astype(np.int32)
        f = ht.Frame({"k": keys, "x": x})
        f.groupby("k").sum()  # cold pass compiles the engine programs

        before = MOVE_STATS["bucket_moves"]
        region = Region(f"warm frame groupby card={card}")
        g = f.groupby("k").sum()
        warm_compiles += region.compiles + region.traces
        moves = MOVE_STATS["bucket_moves"] - before
        # 2 operands (key column + one value column) -> 2 bucket moves
        assert moves == 2, (card, moves)
        exchanges_per_operand.add(moves // 2)

        d = {k: np.asarray(c._logical()) for k, c in g._cols.items()}
        oracle = np.bincount(keys, weights=x.astype(np.float64), minlength=card)
        present = np.unique(keys)
        if not (
            np.array_equal(d["k"], present)
            and np.allclose(d["x"], oracle[present], rtol=1e-3, atol=1e-3)
        ):
            divergences += 1

        def trip():
            out = f.groupby("k").sum()
            np.asarray(out["x"]._raw)  # host fence

        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            trip()
            best = min(best, time.perf_counter() - t0)
        by_card[str(card)] = round(n / best, 1)

        if card == FRAME_GATE_CARD:
            # sort-then-loop decomposition from the pre-frame public API
            kh = ht.array(keys, split=0)
            xh = ht.array(x, split=0)

            def loop_trip():
                ht.sort(kh)  # co-locate equal keys, as the engine does
                sums = [
                    (xh * (kh == u).astype(ht.float32)).sum() for u in range(card)
                ]
                np.asarray(sums[-1].larray)  # host fence

            loop_trip()  # warm
            lbest = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                loop_trip()
                lbest = min(lbest, time.perf_counter() - t0)
            result["frame_loop_rows_per_s"] = round(n / lbest, 1)
            result["frame_groupby_speedup"] = round(lbest / best, 2)

            # raw-jnp comparator: one global segment_sum, no distribution
            seg = jax.jit(  # graftlint: G001 - one-shot comparator, warmed then timed
                lambda k, v: jax.ops.segment_sum(v, k, num_segments=card)
            )
            kj, xj = jnp.asarray(keys), jnp.asarray(x)
            np.asarray(seg(kj, xj))  # warm
            jbest = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                np.asarray(seg(kj, xj))
                jbest = min(jbest, time.perf_counter() - t0)
            result["frame_jnp_rows_per_s"] = round(n / jbest, 1)

    result.update(
        {
            "frame_groupby_rows_per_s": by_card[str(FRAME_GATE_CARD)],
            "frame_groupby_rows_per_s_by_card": by_card,
            "frame_warm_compiles": int(warm_compiles),
            "frame_divergences": int(divergences),
            "frame_exchanges_per_operand": max(exchanges_per_operand),
            "frame_unit": (
                f"rows/s through Frame.groupby(k).sum() (n={n}, key "
                f"cardinalities {list(FRAME_CARDS)}, 8 virtual CPU devices; "
                "speedup vs ht.sort + per-key masked reductions at "
                f"cardinality {FRAME_GATE_CARD})"
            ),
        }
    )
    print(json.dumps(result))


def frame_bench():
    """Run the frame_groupby workload ONCE in a fresh 8-virtual-CPU-
    device subprocess and fold its JSON line into the output; a failure
    degrades to a ``frame_error`` field, never kills the bench."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--frame-worker"],
            env=env,
            capture_output=True,
            text=True,
            timeout=900,
        )
        lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
        if proc.returncode != 0 or not lines:
            return {"frame_error": (proc.stderr or proc.stdout or "no output")[-400:]}
        return json.loads(lines[-1])
    except Exception as e:  # noqa: BLE001 - diagnostics ride in the output
        return {"frame_error": repr(e)[:400]}


SERVE_COLS = 16
SERVE_CLASSES = 8
SERVE_REQUESTS = 192
# offered load is set well above single-request dispatch capacity so
# BOTH legs run capacity-limited and the speedup is a clean capacity
# ratio (at lower load the batched leg just keeps up with arrivals and
# the ratio measures the load generator, not batching)
SERVE_INTERARRIVAL_S = 0.0004
SERVE_WS2_REQUESTS = 64  # burst size per measured ws2 leg
SERVE_MAX_BATCH = 32
HEALTH_TICKS = 50  # probe ticks timed for the health_probe_ms metric


def serve_worker():
    """Subprocess body for the ``serve_pipeline`` workload: an open-loop
    load generator against the resident :class:`heat_tpu.serve.ServeService`.

    The request stream is FIXED (seeded row counts in 1..8, fixed
    interarrival — offered load does not react to completions, the
    open-loop discipline) and is played twice through the same process:
    once BATCHED (max_batch=32 shape-bucketed batching, the tentpole
    path) and once UNBATCHED (max_batch=1: every request dispatches
    alone, still bucket-padded so both legs replay warm programs). The
    gated number is ``serve_batched_speedup`` = batched / unbatched
    completed-requests-per-second at the SAME offered load; p50/p99
    latency is reported for the batched leg.

    Counters asserted, not assumed: after an explicit warm-up pass over
    every bucket the measured legs run 0 XLA compiles and 0 traces
    (``Region``), every batched-leg batch lands in a warm bucket, and
    the whole phase runs under ``analysis.lockstep()`` with the
    divergence count reported (0 with one controller by construction,
    and the same wiring a multi-process run would check for real)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import heat_tpu as ht
    from heat_tpu import analysis
    from heat_tpu.analysis.sanitizer import Region
    from heat_tpu.resilience.monitor import HEALTH_STATS, HealthMonitor
    from heat_tpu.serve import (
        SERVE_STATS,
        Autoscaler,
        BucketPolicy,
        ServeService,
        refresh_latency_stats,
        reset_serve_stats,
    )

    cols, classes = SERVE_COLS, SERVE_CLASSES
    rng = np.random.default_rng(11)
    train = rng.normal(size=(1 << 12, cols)).astype(np.float32)
    mu = ht.array(train.mean(axis=0))
    isig = ht.array((1.0 / (train.std(axis=0) + 1e-6)).astype(np.float32))
    w = ht.array(rng.normal(size=(cols, classes)).astype(np.float32))

    @ht.fuse
    def predict_pipeline(x):
        # the canonical captured predict pipeline: standardize -> matmul
        # -> argmax, ONE fused program per bucket (PR 8 capture extended
        # to matmul/argreduce in this PR)
        return ht.argmax((x - mu) * isig @ w, axis=1)

    # one fixed request trace, shared by both legs: open-loop offered
    # load with seeded mixed row counts
    req_rows = [int(r) for r in rng.integers(1, 9, size=SERVE_REQUESTS)]
    payloads = [
        rng.normal(size=(r, cols)).astype(np.float32) for r in req_rows
    ]
    buckets_needed = (1, 2, 4, 8, 16, 32)

    def run_leg(service):
        """Play the trace open-loop; returns (rps, p50_ms, p99_ms)."""
        reset_serve_stats()
        t0 = time.perf_counter()
        requests = []
        for i, payload in enumerate(payloads):
            target = t0 + i * SERVE_INTERARRIVAL_S
            delay = target - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            requests.append(service.submit("pipe", payload))
        service.flush()
        for r in requests:
            r.result(120)
        elapsed = time.perf_counter() - t0
        refresh_latency_stats()
        return (
            len(requests) / elapsed,
            float(SERVE_STATS["p50_latency_ms"]),
            float(SERVE_STATS["p99_latency_ms"]),
            dict(SERVE_STATS),
        )

    with analysis.lockstep():
        # the batched leg carries a live autoscaler (r17): the dispatcher
        # consults it after every work unit, so the measured warm phase
        # proves the consult hook is free — a healthy idle mesh must
        # produce ZERO scale events and no extra compiles. The long
        # interval keeps probe ticks out of the measured legs; the first
        # (always-due) tick lands in warm-up.
        batched = ServeService(
            policy=BucketPolicy(max_batch=SERVE_MAX_BATCH, max_latency_ms=2.0),
            autoscaler=Autoscaler(HealthMonitor(interval_s=3600.0)),
        )
        batched.register_endpoint("pipe", predict_pipeline)
        unbatched = ServeService(policy=BucketPolicy(max_batch=1))
        unbatched.register_endpoint("pipe", predict_pipeline)

        # cold pass: cover every bucket either leg can form, then assert
        # the measured phase replays cached programs only. Each warm-up
        # request drains ALONE (flush sets the barrier without blocking,
        # so back-to-back submits would coalesce into one grouped batch
        # and leave the smaller buckets cold).
        for service in (batched, unbatched):
            for b in buckets_needed:
                r = service.submit(
                    "pipe", rng.normal(size=(b, cols)).astype(np.float32)
                )
                service.flush()
                r.result(120)

        region = Region("warm serve phase")
        batched_rps, p50_ms, p99_ms, batched_stats = run_leg(batched)
        unbatched_rps, _, _, unbatched_stats = run_leg(unbatched)
        warm_compiles = region.compiles + region.traces
        assert warm_compiles == 0, region.stats()
        assert batched_stats["bucket_misses"] == 0, batched_stats

        # correctness spot-check on the warm service: served rows match
        # the eager pipeline
        probe = payloads[0]
        served = batched.submit("pipe", probe).result(120)
        oracle = np.argmax(
            (probe - train.mean(axis=0))
            * (1.0 / (train.std(axis=0) + 1e-6))
            @ np.asarray(w._raw),
            axis=1,
        )
        assert np.array_equal(served, oracle), (served, oracle)

        batched.close()
        unbatched.close()
    divergences = int(analysis.LOCKSTEP_STATS["divergences"])

    # r17 health-monitor overhead: steady-state probe ticks must be
    # trace-free (one device_put/get round-trip per device, no jit, no
    # host sync), so monitoring is cheap enough to leave always-on.
    mon = HealthMonitor(interval_s=0.0)
    mon.tick()  # warm (first device_put touches lazy per-device state)
    probe_region = Region("health probe ticks")
    ms_before = float(HEALTH_STATS["probe_ms_total"])
    for _ in range(HEALTH_TICKS):
        mon.tick()
    probe_ms = (float(HEALTH_STATS["probe_ms_total"]) - ms_before) / HEALTH_TICKS
    probe_compiles = probe_region.compiles + probe_region.traces
    assert probe_compiles == 0, probe_region.stats()

    occupancy = batched_stats["batched_rows"] / max(1, batched_stats["batches"])
    hits = batched_stats["bucket_hits"]
    total_b = hits + batched_stats["bucket_misses"]
    print(
        json.dumps(
            {
                "serve_batched_speedup": round(batched_rps / unbatched_rps, 3),
                "serve_requests_per_sec": round(batched_rps, 2),
                "serve_unbatched_requests_per_sec": round(unbatched_rps, 2),
                "serve_p50_ms": round(p50_ms, 3),
                "serve_p99_ms": round(p99_ms, 3),
                "serve_batch_occupancy": round(occupancy, 2),
                "serve_bucket_hit_rate": round(hits / max(1, total_b), 3),
                "serve_warm_compiles": int(warm_compiles),
                "serve_lockstep_divergences": divergences,
                # r16 fault-ladder counters: the warm measured path must
                # never climb a recovery rung or shed a deadline
                "serve_shed": int(
                    batched_stats["shed"] + unbatched_stats["shed"]
                ),
                "serve_restores": int(
                    batched_stats["restores"] + unbatched_stats["restores"]
                ),
                # r17 autoscaler + health monitor: a healthy idle mesh
                # must never scale, and steady-state probe ticks must
                # replay trace-free
                "serve_scale_events": int(
                    batched_stats["scale_events"]
                    + unbatched_stats["scale_events"]
                ),
                "health_probe_ms": round(probe_ms, 4),
                "health_probe_warm_compiles": int(probe_compiles),
                "serve_unit": (
                    f"open-loop predict pipeline requests/s at "
                    f"{1.0 / SERVE_INTERARRIVAL_S:.0f} req/s offered load "
                    f"(rows 1..8, f={cols}, 8 virtual CPU devices)"
                ),
            }
        )
    )


def serve_ws2_worker(pid, nproc, port):
    """One rank of the ``serve_ws2`` workload: replicated-tick batching
    vs the barrier-per-request discipline at real world size 2.

    Both ranks play the SAME seeded burst of requests against an
    endpoint whose weights are split across the process boundary (every
    dispatch is a cross-process collective). Two service lifetimes run
    strictly one after the other — two live dispatchers would interleave
    collectives from two threads per rank:

    - TICK leg (``tick_ms=None``, the ws>1 default): the replicated
      dispatch tick re-arms the timer/count triggers, so the burst is
      submitted with NO flush() anywhere and batches form tick-decided,
      identically on both ranks.
    - BARRIER leg (``tick_ms=0``, the pre-tick mode): async triggers are
      disarmed, so an interactive client that cannot know whether more
      work is coming must flush after EVERY submit to bound its latency
      — each request dispatches alone behind its own barrier.

    The gated number is ``serve_ws2_speedup`` = tick / barrier completed
    requests-per-second on the same trace. Both measured legs run under
    one ``analysis.lockstep()`` with 0 divergences and 0 compiles/traces
    (Region) asserted in-worker; results are oracle-checked against the
    numpy pipeline."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import heat_tpu as ht
    from heat_tpu import analysis
    from heat_tpu.analysis.sanitizer import Region
    from heat_tpu.serve import (
        SERVE_STATS,
        BucketPolicy,
        ServeService,
        refresh_latency_stats,
        reset_serve_stats,
    )

    ht.init_distributed(
        coordinator_address=f"localhost:{port}", num_processes=nproc, process_id=pid
    )

    cols = 8
    rng = np.random.default_rng(47)
    w_np = rng.normal(size=(cols, 4)).astype(np.float32)
    mu_np = rng.normal(size=(4,)).astype(np.float32)
    # weights split across the process boundary: x @ w contracts over
    # the sharded axis, so every batch dispatch is a collective
    w = ht.array(w_np, split=0)
    mu = ht.array(mu_np)

    def linear(x):
        return x @ w + mu

    # warm-up must cover every bucket a GROUPED batch can land in: the
    # tick leg stacks requests up to max_batch=16 rows, so the batch
    # buckets reach 16 even though no single request exceeds 8 rows
    buckets_needed = (1, 2, 4, 8, 16)
    trace = [
        rng.normal(size=(1 + i % 8, cols)).astype(np.float32)
        for i in range(SERVE_WS2_REQUESTS)
    ]

    def run_epoch(tick_ms, barrier_per_request):
        """One full service lifetime: cold pass over every bucket, one
        measured burst, close. Returns (rps, p50, p99, warm, stats)."""
        svc = ServeService(
            policy=BucketPolicy(
                edges=buckets_needed, max_batch=16, max_latency_ms=2.0
            ),
            tick_ms=tick_ms,
        )
        svc.register_endpoint("linear", linear)
        assert svc._tick_armed is (tick_ms is None)
        for b in buckets_needed:
            r = svc.submit("linear", rng.normal(size=(b, cols)).astype(np.float32))
            if barrier_per_request:
                svc.flush()
            r.result(300)

        reset_serve_stats()
        region = Region("ws2 measured leg")
        t0 = time.perf_counter()
        if barrier_per_request:
            results = []
            for payload in trace:
                r = svc.submit("linear", payload)
                svc.flush()
                results.append(r.result(300))
        else:
            requests = [svc.submit("linear", payload) for payload in trace]
            results = [r.result(300) for r in requests]
        elapsed = time.perf_counter() - t0
        warm = region.compiles + region.traces
        refresh_latency_stats()
        p50 = float(SERVE_STATS["p50_latency_ms"])
        p99 = float(SERVE_STATS["p99_latency_ms"])
        # close() joins the dispatcher: counters quiescent before the read
        svc.close(300)
        stats = svc.stats()
        for payload, out in zip(trace, results):
            np.testing.assert_allclose(
                np.asarray(out), payload @ w_np + mu_np, atol=1e-4
            )
        return len(trace) / elapsed, p50, p99, warm, stats

    with analysis.lockstep():
        tick_rps, tick_p50, tick_p99, tick_warm, tick_stats = run_epoch(None, False)
        bar_rps, _, bar_p99, bar_warm, bar_stats = run_epoch(0.0, True)
    divergences = int(analysis.LOCKSTEP_STATS["divergences"])
    warm_compiles = tick_warm + bar_warm
    assert warm_compiles == 0, (tick_warm, bar_warm)
    assert tick_stats["ticks"] > 0, tick_stats
    assert tick_stats["tick_batches"] == tick_stats["batches"] > 0, tick_stats
    assert tick_stats["shed"] == 0 and tick_stats["rejected"] == 0, tick_stats
    assert bar_stats["errors"] == 0 and tick_stats["errors"] == 0

    print(
        json.dumps(
            {
                "serve_ws2_speedup": round(tick_rps / bar_rps, 3),
                "serve_ws2_requests_per_sec": round(tick_rps, 2),
                "serve_ws2_barrier_requests_per_sec": round(bar_rps, 2),
                "serve_ws2_p50_ms": round(tick_p50, 3),
                "serve_ws2_p99_ms": round(tick_p99, 3),
                "serve_ws2_barrier_p99_ms": round(bar_p99, 3),
                "serve_ws2_warm_compiles": int(warm_compiles),
                "serve_ws2_lockstep_divergences": divergences,
                "serve_ws2_ticks": int(tick_stats["ticks"]),
                "serve_ws2_batches": int(tick_stats["batches"]),
                "serve_ws2_unit": (
                    f"burst of {SERVE_WS2_REQUESTS} requests (rows 1..8, "
                    f"f={cols}) over 2 processes x 4 virtual CPU devices; "
                    "tick-batched vs flush-per-request"
                ),
            }
        )
    )


def serve_ws2_bench():
    """Run the serve_ws2 workload ONCE across two coordinated
    ``jax.distributed`` subprocesses (4 virtual CPU devices each) and
    fold rank 0's JSON line into the output; any failure degrades to a
    ``serve_ws2_error`` field, never kills the bench. Both ranks must
    report the IDENTICAL tick-batch count — the replicated plan is pure,
    so a mismatch means rank-divergent batch formation."""
    import socket
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.pop("HEAT_TPU_TEST_DEVICES", None)
    try:
        with socket.socket() as s:
            s.bind(("localhost", 0))
            port = s.getsockname()[1]
        procs = [
            subprocess.Popen(
                [
                    sys.executable, os.path.abspath(__file__),
                    "--serve-ws2-worker", str(i), "2", str(port),
                ],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
            for i in range(2)
        ]
        outs = [p.communicate(timeout=900)[0] for p in procs]
        if any(p.returncode != 0 for p in procs):
            bad = next(
                out for p, out in zip(procs, outs) if p.returncode != 0
            )
            return {"serve_ws2_error": (bad or "no output")[-400:]}
        parsed = []
        for out in outs:
            lines = [ln for ln in out.strip().splitlines() if ln.strip()]
            parsed.append(json.loads(lines[-1]))
        if parsed[0]["serve_ws2_batches"] != parsed[1]["serve_ws2_batches"]:
            return {
                "serve_ws2_error": (
                    "rank-divergent batch formation: "
                    f"{parsed[0]['serve_ws2_batches']} vs "
                    f"{parsed[1]['serve_ws2_batches']} batches"
                )
            }
        return parsed[0]
    except Exception as e:  # noqa: BLE001 - diagnostics ride in the output
        return {"serve_ws2_error": repr(e)[:400]}


def stream_bench():
    """Run the stream_pipeline workload ONCE in a fresh 8-virtual-CPU-
    device subprocess and fold its JSON line into the output; a failure
    degrades to a ``stream_error`` field, never kills the bench."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--stream-worker"],
            env=env,
            capture_output=True,
            text=True,
            timeout=900,
        )
        lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
        if proc.returncode != 0 or not lines:
            return {"stream_error": (proc.stderr or proc.stdout or "no output")[-400:]}
        return json.loads(lines[-1])
    except Exception as e:  # noqa: BLE001 - diagnostics ride in the output
        return {"stream_error": repr(e)[:400]}


def sketch_bench():
    """Run the sketch_pipeline workload ONCE in a fresh 8-virtual-CPU-
    device subprocess and fold its JSON line into the output; a failure
    degrades to a ``sketch_error`` field, never kills the bench."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--sketch-worker"],
            env=env,
            capture_output=True,
            text=True,
            timeout=900,
        )
        lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
        if proc.returncode != 0 or not lines:
            return {"sketch_error": (proc.stderr or proc.stdout or "no output")[-400:]}
        return json.loads(lines[-1])
    except Exception as e:  # noqa: BLE001 - diagnostics ride in the output
        return {"sketch_error": repr(e)[:400]}


def fused_bench():
    """Run the fused_pipeline workload ONCE in a fresh 8-virtual-CPU-
    device subprocess and fold its JSON line into the output; a failure
    degrades to a ``fused_error`` field, never kills the bench."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--fused-worker"],
            env=env,
            capture_output=True,
            text=True,
            timeout=900,
        )
        lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
        if proc.returncode != 0 or not lines:
            return {"fused_error": (proc.stderr or proc.stdout or "no output")[-400:]}
        return json.loads(lines[-1])
    except Exception as e:  # noqa: BLE001 - diagnostics ride in the output
        return {"fused_error": repr(e)[:400]}


def ragged_bench():
    """Run the ragged_elementwise workload ONCE in a fresh 8-virtual-CPU-
    device subprocess and fold its JSON line into the output; a failure
    degrades to a ``ragged_error`` field, never kills the bench."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--ragged-worker"],
            env=env,
            capture_output=True,
            text=True,
            timeout=900,
        )
        lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
        if proc.returncode != 0 or not lines:
            return {"ragged_error": (proc.stderr or proc.stdout or "no output")[-400:]}
        return json.loads(lines[-1])
    except Exception as e:  # noqa: BLE001 - diagnostics ride in the output
        return {"ragged_error": repr(e)[:400]}


def serve_bench():
    """Run the serve_pipeline workload ONCE in a fresh 8-virtual-CPU-
    device subprocess and fold its JSON line into the output; a failure
    degrades to a ``serve_error`` field, never kills the bench."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--serve-worker"],
            env=env,
            capture_output=True,
            text=True,
            timeout=900,
        )
        lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
        if proc.returncode != 0 or not lines:
            return {"serve_error": (proc.stderr or proc.stdout or "no output")[-400:]}
        return json.loads(lines[-1])
    except Exception as e:  # noqa: BLE001 - diagnostics ride in the output
        return {"serve_error": repr(e)[:400]}


def _suite_seconds():
    """Tier-1 suite wall clock, recorded by tests/conftest.py into
    SUITE_SECONDS.json next to this file; null when no suite has run."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "SUITE_SECONDS.json")
    try:
        with open(path) as fh:
            return round(float(json.load(fh)["suite_seconds"]), 1)
    except (OSError, ValueError, KeyError, TypeError):
        return None


def _compact_summary(out, detail_path):
    """The single stdout line: headline numbers plus gate state, kept well
    under 2 KB. (The full dict is ~8 KB — longer than common log-tail
    captures, which is how BENCH parsed as null in r5 — and now lives in
    the ``BENCH_DETAIL.json`` sidecar instead.)"""
    compact = {"metric": out["metric"], "value": out["value"]}
    for k in HEADLINE[1:]:
        if k in out:
            compact[k] = out[k]
    for k in (
        "smoke_ok",
        "bench_reps",
        "suite_seconds",
        "ragged_elementwise_speedup",
        "ragged_new_moves_per_trip",
        "ragged_seed_moves_per_trip",
        "ragged_error",
        "fused_pipeline_speedup",
        "fused_warm_compiles",
        "fused_warm_dispatches",
        "fused_error",
        "stream_speedup",
        "stream_gbps",
        "stream_prefetch_hits",
        "stream_warm_compiles",
        "stream_divergences",
        "stream_error",
        "sketch_gbps",
        "sketch_exact_gbps",
        "sketch_warm_compiles",
        "sketch_divergences",
        "sketch_kll_rank_err",
        "sketch_kll_eps",
        "sketch_hll_rel_err",
        "sketch_hll_bound",
        "sketch_topk_recall",
        "sketch_error",
        "serve_batched_speedup",
        "serve_requests_per_sec",
        "serve_p50_ms",
        "serve_p99_ms",
        "serve_warm_compiles",
        "serve_lockstep_divergences",
        "serve_shed",
        "serve_restores",
        "serve_scale_events",
        "health_probe_ms",
        "health_probe_warm_compiles",
        "serve_error",
        "serve_ws2_speedup",
        "serve_ws2_requests_per_sec",
        "serve_ws2_p99_ms",
        "serve_ws2_warm_compiles",
        "serve_ws2_lockstep_divergences",
        "serve_ws2_ticks",
        "serve_ws2_error",
        "frame_groupby_rows_per_s",
        "frame_groupby_speedup",
        "frame_loop_rows_per_s",
        "frame_jnp_rows_per_s",
        "frame_warm_compiles",
        "frame_divergences",
        "frame_exchanges_per_operand",
        "frame_error",
        "lockstep_events",
        "lockstep_divergences",
        "kmeans_fused_ratio",
        "kernel_moments_onepass_gbps",
        "kernel_moments_fused_gbps",
        "moments_onepass_warm_compiles",
    ):
        if k in out:
            compact[k] = out[k]
    if out.get("api_over_kernel"):
        compact["api_over_kernel"] = out["api_over_kernel"]
    compact["vs_trailing_median"] = {
        k: v for k, v in out.get("vs_trailing_median", {}).items() if k in HEADLINE
    }
    if "floor_violations" in out:
        compact["floor_violations"] = out["floor_violations"]
    compact["detail"] = os.path.basename(detail_path)
    return compact


def _chained_timed(trial, xa):
    """best-of-4 timer for eps-chained device trials: ``trial(xa, s)``
    returns a device scalar that seeds the next call, so the trials
    serialize on device with ONE host sync at the end (the chip's
    block_until_ready does not synchronize; see module docstring)."""
    import jax.numpy as jnp

    def timed(reps):
        best = float("inf")
        for _ in range(4):
            s = jnp.float32(0)
            t0 = time.perf_counter()
            for _ in range(reps):
                s = trial(xa, s) * jnp.float32(1e-30)
            float(s)
            best = min(best, time.perf_counter() - t0)
        return best

    return timed


def _marginal(timed, short, long_, work_per_unit, cap=None):
    """Best-of-two positive marginal estimates (shared-chip spread).

    ``cap`` is the physical ceiling for the metric (CAPS): an estimate
    above it is a corrupted measurement (a noise spike shrinking
    t_long - t_short), not a capability, and is discarded — a reported
    "best" beyond the hardware bound would only advertise that the timer
    broke."""
    estimates = []
    t_long_min = float("inf")
    for _ in range(3):
        t_long = timed(long_)
        t_long_min = min(t_long_min, t_long)
        dt = (t_long - timed(short)) / (long_ - short)
        if dt > 0:
            est = work_per_unit / dt
            if cap is None or est <= cap:
                estimates.append(est)
            if len(estimates) == 2:
                break
    if estimates:
        return max(estimates)
    # conservative whole-run fallback from the BEST long run (the last
    # one may carry a noise spike; r3 ADVICE)
    fallback = work_per_unit * long_ / t_long_min
    return min(fallback, cap) if cap is not None else fallback


def moments_bench():
    """Progression config 2: mean+std over axes {None, 0, 1} on a random
    split=0 array.

    Headline: the 6-call public sequence ``ht.mean(x, axis)`` +
    ``ht.std(x, axis)`` (the reference protocol's own call structure,
    ``statistical_moments/heat-cpu.py:20-27``). r8: the one-pass moments
    panel memoizes per buffer, so the sweep runs on a FRESH buffer each
    trial (a public elementwise copy) — timing the same buffer twice
    would measure host-side memo lookups, not data movement. Kernel
    comparator: the same six programs, unfused, on the raw jnp buffer.
    Legacy fused 6-in-1 sweep rides as ``kernel_moments_fused_gbps``
    (pre-r5 series continuity), and ``kernel_moments_onepass_gbps`` times
    the public mean+std pair on a fresh buffer (generate + ONE panel
    read, Region-asserted 0 warm compiles). All series share the 3-pass
    byte normalization so they graph on one axis; the
    fraction-of-achievable accounting lives in _roofline."""
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht

    n, f = MOM_N, MOM_F
    rng = np.random.default_rng(2)
    data = rng.normal(size=(n, f)).astype(np.float32)
    X = ht.array(data, split=0)
    xa = X.larray
    gb_per_sweep = n * f * 4 * 3 / 1e9  # 3-pass normalization (all series)

    # --- legacy fused sweep (one jit, trials chained through eps) ---
    @jax.jit
    def fused_sweep(x, eps):
        xx = x + eps * jnp.float32(1e-30)
        outs = []
        for axis in (None, 0, 1):
            outs.append(jnp.mean(xx, axis=axis))
            outs.append(jnp.std(xx, axis=axis))
        return sum(jnp.sum(o) for o in outs)

    float(fused_sweep(xa, jnp.float32(0)))  # warm compile
    fused_gbps = _marginal(
        _chained_timed(fused_sweep, xa), 3, 23, gb_per_sweep,
        cap=CAPS["kernel_moments_fused_gbps"],
    )

    # --- unfused kernel comparator: the API's program structure on jnp ---
    # graftlint: retrace - built once per bench run, reused across all reps
    mean_j = {ax: jax.jit(lambda v, a=ax: jnp.mean(v, axis=a)) for ax in (None, 0, 1)}
    std_j = {ax: jax.jit(lambda v, a=ax: jnp.std(v, axis=a)) for ax in (None, 0, 1)}  # graftlint: retrace

    def kernel_sweep():
        last = None
        for ax in (None, 0, 1):
            mean_j[ax](xa)
            last = std_j[ax](xa)
        return last

    def api_sweep():
        # fresh buffer per sweep (r8): the copy's read+write plus the
        # panel's reads are the honest traffic; the dying buffer's memo
        # slot is reclaimed by its weakref death callback
        Xf = X + 0.0
        last = None
        for ax in (None, 0, 1):
            ht.mean(Xf, axis=ax)
            last = ht.std(Xf, axis=ax)
        return last

    def onepass_pair():
        # the tightest public one-pass probe: mean+std, whole buffer —
        # generate (2 passes) + one panel read = the counted 3 passes
        Xf = X + 0.0
        ht.mean(Xf)
        return ht.std(Xf)

    kernel_sweep()  # warm all six compiles
    api_sweep()
    onepass_pair()
    fence = lambda out: float(np.asarray(out[0] if out.ndim else out))
    fence_api = lambda out: float(np.asarray((out.larray[0] if out.larray.ndim else out.larray)))
    kernel_gbps = _marginal(
        _api_timed(kernel_sweep, fence), 3, 23, gb_per_sweep,
        cap=CAPS["kernel_moments_gbps"],
    )
    api_gbps = _marginal(
        _api_timed(api_sweep, fence_api), 3, 23, gb_per_sweep,
        cap=CAPS["moments_gbps"],
    )
    from heat_tpu.analysis import Region

    reg = Region("bench.moments_onepass_warm")
    onepass_gbps = _marginal(
        _api_timed(onepass_pair, fence_api), 3, 23, gb_per_sweep,
        cap=CAPS["kernel_moments_onepass_gbps"],
    )
    onepass_warm_compiles = int(reg.compiles)

    if "moments" not in _BASELINE_CACHE:
        sub = data[: n // 8]
        t0 = time.perf_counter()
        for axis in (None, 0, 1):
            np.mean(sub, axis=axis)
            np.std(sub, axis=axis)
        _BASELINE_CACHE["moments"] = (sub.nbytes * 3 / 1e9) / (time.perf_counter() - t0)
    base_gbps = _BASELINE_CACHE["moments"]
    return {
        "moments_gbps": round(api_gbps, 2),
        "moments_unit": f"GB/s (3-pass norm), ht.mean+ht.std x axes(None,0,1) (n={n}, f={f})",
        "moments_vs_baseline": round(api_gbps / base_gbps, 2),
        "kernel_moments_gbps": round(kernel_gbps, 2),
        "kernel_moments_fused_gbps": round(fused_gbps, 2),
        "kernel_moments_onepass_gbps": round(onepass_gbps, 2),
        "moments_onepass_warm_compiles": onepass_warm_compiles,
    }


def qr_matmul_bench():
    """Progression config 5: tall-skinny QR + gram matmul GFLOP/s.

    Headline qr: ``ht.linalg.qr(A, calc_q=False)`` on a split=0 DNDarray
    (the kernel trial consumes only R, so calc_q=False is the matching
    user call — XLA dead-code-eliminates Q identically in both).
    Headline matmul: ``ht.matmul(xT, x)`` with the transpose hoisted
    outside the timed window, as a user would; its jnp twin is the
    two-buffer kernel comparator, and the legacy same-buffer gram rides
    as ``kernel_matmul_gram_gflops``."""
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht
    from heat_tpu.core.linalg.qr import _cholqr2_with_fallback

    n, f = QR_N, QR_F
    rng = np.random.default_rng(3)
    data = rng.normal(size=(n, f)).astype(np.float32)
    A = ht.array(data, split=0)
    xa = A.larray
    AT = ht.array(jnp.asarray(xa.T))  # hoisted, like a user would

    @jax.jit
    def qr_trial(x, eps):
        # the library's auto path for tall-skinny floats (CholeskyQR2 on
        # the MXU with the on-device ill-conditioning fallback)
        with jax.default_matmul_precision("highest"):
            q, r = _cholqr2_with_fallback(x + eps * jnp.float32(1e-30))
        return r[0, 0]

    @jax.jit
    def mm_gram_trial(x, eps):
        xx = x + eps * jnp.float32(1e-30)
        return (xx.T @ xx)[0, 0]

    xaT = jnp.asarray(xa.T)

    # two-buffer kernel comparator: the SAME program structure and timing
    # protocol as the API path below — a jitted full-result gram over two
    # distinct buffers, back-to-back calls fenced by one scalar fetch from
    # the last output. (The pre-PR3 comparator eps-chained a [0,0]-only
    # trial: a different program under a different timer, so both sides
    # routinely hit their caps and api_over_kernel pinned at 1.0.)
    mm2_kernel = jax.jit(lambda at, b: at @ b)  # graftlint: retrace - one bench run

    float(qr_trial(xa, jnp.float32(0)))
    float(mm_gram_trial(xa, jnp.float32(0)))

    flops = 2.0 * n * f * f / 1e9  # GFLOP per trial (all kernels)
    k_qr = _marginal(_chained_timed(qr_trial, xa), 2, 10, flops, cap=CAPS["kernel_qr_gflops"])
    k_gram = _marginal(_chained_timed(mm_gram_trial, xa), 3, 23, flops, cap=CAPS["kernel_matmul_gram_gflops"])

    mm2_call = lambda: mm2_kernel(xaT, xa)
    fence_k = lambda out: float(np.asarray(out[0, 0]))
    fence_k(mm2_call())  # warm
    k_mm2 = _marginal(
        _api_timed(mm2_call, fence_k), 3, 23, flops, cap=CAPS["kernel_matmul_gflops"]
    )

    # --- public API paths ---
    api_qr_call = lambda: ht.linalg.qr(A, calc_q=False)
    api_mm_call = lambda: ht.matmul(AT, A)
    fence_r = lambda out: float(np.asarray(out.R.larray[0, 0]))
    fence_mm = lambda out: float(np.asarray(out.larray[0, 0]))
    api_qr_call()  # warm
    api_mm_call()
    a_qr = _marginal(_api_timed(api_qr_call, fence_r), 2, 10, flops, cap=CAPS["qr_gflops"])
    a_mm = _marginal(_api_timed(api_mm_call, fence_mm), 3, 23, flops, cap=CAPS["matmul_gflops"])

    if "qr" not in _BASELINE_CACHE:
        sub = data[: n // 16]
        t0 = time.perf_counter()
        np.linalg.qr(sub)
        _BASELINE_CACHE["qr"] = (2.0 * sub.shape[0] * f * f / 1e9) / (time.perf_counter() - t0)
        t0 = time.perf_counter()
        sub.T @ sub
        _BASELINE_CACHE["mm"] = (2.0 * sub.shape[0] * f * f / 1e9) / (time.perf_counter() - t0)
    base_qr, base_mm = _BASELINE_CACHE["qr"], _BASELINE_CACHE["mm"]
    return {
        "qr_gflops": round(a_qr, 2),
        "qr_unit": f"GFLOP/s ht.linalg.qr(calc_q=False), split=0 (n={n}, f={f})",
        "qr_vs_baseline": round(a_qr / base_qr, 2),
        "matmul_gflops": round(a_mm, 2),
        "matmul_unit": f"GFLOP/s ht.matmul(xT, x), two (n,f) buffers (n={n}, f={f})",
        "matmul_vs_baseline": round(a_mm / base_mm, 2),
        "kernel_qr_gflops": round(k_qr, 2),
        "kernel_matmul_gflops": round(k_mm2, 2),
        "kernel_matmul_gram_gflops": round(k_gram, 2),
    }


def solve_bench():
    """Dense linear solve GFLOP/s through the public API.

    Headline: ``ht.linalg.solve(A, b)`` on a split=0 SPD system (the
    distributed LU kernel when the mesh has >1 device; on the 1-chip
    bench the local ``jnp.linalg.solve`` branch — same public call
    either way). The kernel comparator is the jitted ``jnp.linalg.solve``
    on the same buffers under the same full-result timing protocol
    (PR 3): back-to-back calls fenced by one scalar fetch from the last
    output. Counted work is 2/3 n^3 (LU) + 2n^2 (two trisolves)."""
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht

    n = SOLVE_N
    rng = np.random.default_rng(5)
    M = rng.normal(size=(n, n)).astype(np.float32) / np.sqrt(n)
    SPD = (M @ M.T + np.eye(n, dtype=np.float32)).astype(np.float32)
    bnp = rng.normal(size=n).astype(np.float32)
    A = ht.array(SPD, split=0)
    b = ht.array(bnp, split=0)
    Aa, ba = A.larray, b.larray

    flops = (2.0 / 3.0 * n**3 + 2.0 * n * n) / 1e9  # GFLOP per trial

    kernel = jax.jit(jnp.linalg.solve)
    kernel_call = lambda: kernel(Aa, ba)
    fence_k = lambda out: float(np.asarray(out[0]))
    fence_k(kernel_call())  # warm
    k_solve = _marginal(
        _api_timed(kernel_call, fence_k), 2, 10, flops, cap=CAPS["kernel_solve_gflops"]
    )

    api_call = lambda: ht.linalg.solve(A, b)
    fence_a = lambda out: float(np.asarray(out.larray[0]))
    fence_a(api_call())  # warm
    a_solve = _marginal(
        _api_timed(api_call, fence_a), 2, 10, flops, cap=CAPS["solve_gflops"]
    )

    if "solve" not in _BASELINE_CACHE:
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            np.linalg.solve(SPD, bnp)
            best = min(best, time.perf_counter() - t0)
        _BASELINE_CACHE["solve"] = flops / best
    base = _BASELINE_CACHE["solve"]
    return {
        "solve_gflops": round(a_solve, 2),
        "solve_unit": f"GFLOP/s ht.linalg.solve(A, b), SPD split=0 (n={n})",
        "solve_vs_baseline": round(a_solve / base, 2),
        "kernel_solve_gflops": round(k_solve, 2),
    }


def lasso_bench():
    """Lasso protocol: coordinate-descent sweeps/s (the reference times
    1-iteration fits; a sweep = one fit iteration). The whole fit is one
    device program (lax.while_loop), so sweeps/s comes from differencing
    a long and a short max_iter — through ``Lasso.fit`` on DNDarrays for
    the headline, through the raw ``_cd_fit`` kernel for the comparator."""
    import jax.numpy as jnp

    import heat_tpu as ht
    from heat_tpu.regression.lasso import _cd_fit

    n, f = LASSO_N, LASSO_F
    rng = np.random.default_rng(4)
    Xnp = rng.normal(size=(n, f)).astype(np.float32)
    yv = (Xnp @ rng.normal(size=f).astype(np.float32)).astype(np.float32)
    Xb = np.concatenate([np.ones((n, 1), np.float32), Xnp], axis=1)
    Xd = ht.array(Xb, split=0)
    yd = ht.array(yv, split=0)
    Xa, ya = Xd.larray, jnp.asarray(yv)
    theta0 = jnp.zeros(f + 1, jnp.float32)
    lam = jnp.float32(0.01)
    tol = jnp.float32(0.0)  # run exactly max_iter sweeps

    def timed_kernel(iters):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            th, it = _cd_fit(Xa, ya, theta0, lam, tol, jnp.int32(iters))
            np.asarray(th)  # host fetch = the only reliable fence
            best = min(best, time.perf_counter() - t0)
            # the iteration-count check stays OUTSIDE the timed window
            # (its host fetch would bias the rate low; r3 ADVICE)
            assert int(it) == iters
        return best

    def timed_api(iters):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            est = ht.regression.Lasso(lam=0.01, max_iter=iters, tol=0.0).fit(Xd, yd)
            best = min(best, time.perf_counter() - t0)
            assert est.n_iter == iters
        return best

    np.asarray(_cd_fit(Xa, ya, theta0, lam, tol, jnp.int32(1))[0])  # warm
    ht.regression.Lasso(lam=0.01, max_iter=1, tol=0.0).fit(Xd, yd)
    # window sized so t_long - t_short >> the jitter of one device→host
    # sync (a 2->22 window of 20 sweeps produced 100x-spread garbage
    # both directions)
    k_sps = _marginal(timed_kernel, 50, 1050, 1.0, cap=CAPS["kernel_lasso_sweeps_per_sec"])
    a_sps = _marginal(timed_api, 50, 1050, 1.0, cap=CAPS["lasso_sweeps_per_sec"])

    if "lasso" not in _BASELINE_CACHE:
        sub = Xb[: n // 8]
        ysub = yv[: n // 8]
        t0 = time.perf_counter()
        _numpy_cd_sweep(sub, ysub, np.zeros(f + 1, np.float32), 0.01)
        # measured on n/8 rows -> full-size numpy rate is ~1/8 of this
        _BASELINE_CACHE["lasso"] = (1.0 / (time.perf_counter() - t0)) / 8.0
    base_sps_full = _BASELINE_CACHE["lasso"]
    return {
        "lasso_sweeps_per_sec": round(a_sps, 2),
        "lasso_unit": f"CD sweeps/s via Lasso.fit on split=0 DNDarrays (n={n}, f={f + 1})",
        "lasso_vs_baseline": round(a_sps / base_sps_full, 2),
        "kernel_lasso_sweeps_per_sec": round(k_sps, 2),
    }


def _numpy_cd_sweep(X, y, theta, lam):
    n, m = X.shape
    col_sq = (X * X).sum(0)
    r = y - X @ theta
    for j in range(m):
        rho = X[:, j] @ (r + X[:, j] * theta[j])
        soft = np.sign(rho) * max(abs(rho) - lam * n, 0.0)
        numer = rho if j == 0 else soft
        new_tj = numer / max(col_sq[j], 1e-30) if col_sq[j] > 0 else 0.0
        r = r - X[:, j] * (new_tj - theta[j])
        theta[j] = new_tj
    return theta


PROTOCOL = "api-r8"

# DMA-overlap-banded kernel diagnostics: their trial-to-trial spread is
# dominated by how much of the operand read the next chained trial's DMA
# prefetch hides (measured band for the same-buffer gram: 25-33 TFLOP/s
# against the 26.2 no-overlap ceiling; same mechanism for the fused
# moments sweep). A single run that caught the top of the band is a real
# measurement but a meaningless BAR: healthy in-band runs then read as
# 0.78-0.81x "regressions" forever (the BENCH_r05 kernel_matmul_gram /
# kernel_moments_fused diagnosis — both runs sat within 6% of their
# trailing clean medians). For these metrics best/best_median may never
# exceed OVERLAP_BAND x the trailing clean median: the ratchet tracks the
# band's center, not its lucky tail. Never gated (KERNEL_TRACKED), so
# this only fixes the reported ratios.
OVERLAP_BAND = {
    "kernel_matmul_gram_gflops": 1.2,
    "kernel_moments_fused_gbps": 1.2,
}


def _band_limit(rec, band):
    """band x trailing clean median of a history record (None if empty)."""
    pool = (rec.get("clean") or rec.get("runs", []))[-9:]
    if not pool:
        return None
    return band * sorted(pool)[len(pool) // 2]


def _purge_record(rec, cap):
    """Recompute best/best_median from physically possible values only;
    retire the impossible ones visibly (VERDICT r4 weak item 3: corrupt
    bests make healthy at-roofline medians read as regressions)."""
    pools = [v for key in ("runs", "clean") for v in rec.get(key, [])]
    retired = sorted({v for v in pools + [rec.get("best"), rec.get("best_median")]
                      if isinstance(v, (int, float)) and v > cap})
    if not retired:
        return rec
    rec["retired_artifacts"] = sorted(
        set(retired) | set(rec.get("retired_artifacts", []))
    )
    rec["artifact_note"] = (
        f"values above the physical cap {round(cap, 2)} are corrupted "
        "marginal-timer spikes, not capabilities; best/best_median/clean "
        "recomputed from possible values only"
    )
    rec["runs"] = [v for v in rec.get("runs", []) if v <= cap]
    if "clean" in rec:
        rec["clean"] = [v for v in rec["clean"] if v <= cap]
    possible = [v for v in pools if v <= cap]
    if possible:
        rec["best"] = max(possible)
        rec["best_median"] = max(rec["runs"]) if rec["runs"] else max(possible)
    else:
        rec.pop("best", None)
        rec.pop("best_median", None)
    return rec


def _migrate_history(hist):
    """One-time protocol migration (idempotent renames, re-run per bump):

    - the pre-r5 moments/matmul series measured different PROGRAMS than
      the new API headline (an unexpressible fused sweep; a same-buffer
      gram) — they continue under their kernel_* keys so the series stay
      comparable, and the API headline starts a fresh record;
    - every record is purged of physically impossible values (CAPS).
      r6 lowers the qr cap to the compiled-traffic (~14-pass) model, so
      the purge re-runs to retire any qr values only the old 7-pass cap
      let through;
    - r7 clamps the OVERLAP_BAND diagnostics' best/best_median to
      band x trailing-clean-median, retiring stale top-of-band spikes
      into ``retired_band_outliers`` (see OVERLAP_BAND);
    - r8 (fused-kernel layer) changes the moments API sweep to a fresh
      buffer per trial (the one-pass panel memoizes per buffer) and
      raises the moments ceiling to the 4-pass panel model. No renames:
      the bump re-runs this migration, which idempotently re-applies the
      r7 band retirement to any top-of-band bests recorded since.
    """
    if hist.get("_protocol") == PROTOCOL:
        return hist
    renames = {
        "moments_gbps": "kernel_moments_fused_gbps",
        "matmul_gflops": "kernel_matmul_gram_gflops",
    }
    for old, new in renames.items():
        if old in hist and new not in hist:
            rec = hist.pop(old)
            rec["migrated_from"] = old
            rec["migration_note"] = (
                "pre-r5 series measured the kernel program now tracked "
                f"under {new}; the {old} headline is API-measured from r5 on"
            )
            hist[new] = rec
    for key, cap in CAPS.items():
        if key in hist and isinstance(hist[key], dict):
            _purge_record(hist[key], cap)
    for key, band in OVERLAP_BAND.items():
        rec = hist.get(key)
        if not isinstance(rec, dict):
            continue
        limit = _band_limit(rec, band)
        if limit is None:
            continue
        outliers = sorted(
            {
                v
                for v in (rec.get("best"), rec.get("best_median"))
                if isinstance(v, (int, float)) and v > limit
            }
        )
        if not outliers:
            continue
        rec["retired_band_outliers"] = sorted(
            set(outliers) | set(rec.get("retired_band_outliers", []))
        )
        rec["band_note"] = (
            f"bests above {band}x the trailing clean median are "
            "top-of-DMA-overlap-band catches, a real measurement but a "
            "meaningless bar; best/best_median recomputed from in-band "
            "values (see OVERLAP_BAND)"
        )
        in_band = [
            v
            for key2 in ("runs", "clean")
            for v in rec.get(key2, [])
            if isinstance(v, (int, float)) and v <= limit
        ]
        if in_band:
            rec["best"] = max(in_band)
            rec["best_median"] = max(in_band)
        else:
            rec.pop("best", None)
            rec.pop("best_median", None)
    hist["_protocol"] = PROTOCOL
    return hist


def update_history(out, suspect=frozenset()):
    """Record per-metric best-so-far; return {metric: current/best}.

    ``suspect`` metrics (a rep > 2x the run's own median — timer
    corruption under the roofline cap) never RATCHET the history: their
    median still appends to ``runs`` and still faces the existing floor,
    but cannot set a new ``best``/``best_median`` that would falsely arm
    the 0.7x gate against future honest runs. Values above a metric's
    physical cap (CAPS) can never ratchet either, and the OVERLAP_BAND
    diagnostics additionally cannot ratchet past band x their trailing
    clean median (a top-of-band catch must not become the bar healthy
    in-band runs are compared to).
    """
    metrics = {"kmeans_iters_per_sec": out["value"]}
    for k in HEADLINE[1:] + KERNEL_TRACKED:
        metrics[k] = out.get(k)
    try:
        with open(HISTORY_PATH) as fh:
            hist = json.load(fh)
    except (OSError, ValueError):
        hist = {}
    hist = _migrate_history(hist)
    deltas = {}
    best_median_deltas = {}
    gate_deltas = {}
    for k, v in metrics.items():
        if v is None:
            continue
        cap = CAPS.get(k, float("inf"))
        rec = hist.setdefault(k, {"runs": []})
        band = OVERLAP_BAND.get(k)
        if band is not None:
            # ratchet bound only — the value itself still records below
            limit = _band_limit(rec, band)
            if limit is not None:
                cap = min(cap, limit)
        rec["runs"] = (rec.get("runs", []) + [v])[-20:]
        # a suspect or physically impossible first-ever entry must not
        # seed `best` either — setdefault seeding would persist the
        # corrupted value as the bar
        if v > rec.get("best", 0) and k not in suspect and v <= cap:
            rec["best"] = v
        deltas[k] = round(v / rec.get("best", v), 3)
        # medians compare against the best MEDIAN, not the pre-round-4
        # single-shot maxima the "best" field accumulated (those rode the
        # +20% tail of the noise band; a median can sit at 0.8x of them
        # forever without any regression)
        if v > rec.get("best_median", 0) and k not in suspect and v <= cap:
            rec["best_median"] = v
        best_median_deltas[k] = round(v / rec.get("best_median", v), 3)
        # the GATE baseline is the trailing median of prior CLEAN runs
        # (runs that passed their own gate), not the best-ever median:
        # honest medians swing up to ~2x between chip
        # allocations, so a 0.7x-of-best floor would fail a healthy run
        # on a slower chip. Violating runs are kept out of the baseline
        # window — otherwise a sustained regression would drag the median
        # down to itself within a few runs and the gate would
        # self-normalize. If three consecutive violations agree within
        # 15% the new level is accepted as a re-baseline (a persistent
        # environment change, e.g. a permanently slower chip) — after
        # failing visibly three times, not silently.
        clean = rec.get("clean")
        if clean is None:
            clean = rec["runs"][:-1][-9:]  # migrate: prior history assumed clean
        prior = clean[-9:]
        baseline = sorted(prior)[len(prior) // 2] if prior else v
        gate = round(min(v / baseline, 9.999), 3)
        gate_deltas[k] = gate
        pending = rec.get("pending_violations", [])
        if gate >= FLOOR:
            if k not in suspect:  # corrupted timers never move the baseline
                clean = (clean + [v])[-20:]
                # a suspect run that happens to pass must not reset the
                # three-consecutive-violation rebaseline vote either:
                # corrupted timers neither vote for nor against
                pending = []
        elif k not in suspect:  # corrupted timers cannot vote to rebaseline either
            pending = (pending + [v])[-3:]
            if len(pending) == 3 and max(pending) <= 1.15 * min(pending):
                clean = list(pending)  # the new sustained level IS the baseline now
                rec["rebaselined_at"] = v
                pending = []
        rec["clean"] = clean
        rec["pending_violations"] = pending
    hist["_floor_deltas"] = gate_deltas  # informational in the file
    try:
        with open(HISTORY_PATH, "w") as fh:
            json.dump(hist, fh, indent=1, sort_keys=True)
    except OSError:
        pass
    return deltas, best_median_deltas, gate_deltas


def numpy_cdist(x):
    return np.sqrt(
        np.maximum(
            (x * x).sum(1)[:, None] + (x * x).sum(1)[None, :] - 2.0 * (x @ x.T), 0.0
        )
    )


def cdist_bench():
    """cdist GB/s on device vs single-process numpy.

    Each trial is a separate program whose (n, n) output is a committed
    HBM buffer — XLA cannot elide the write (inside one fused loop it
    can: only the final scalar would be observable). Headline: the
    public ``ht.spatial.cdist(X, quadratic_expansion=True)`` on a
    split=0 DNDarray (since r5 the GSPMD path dispatches ONE fused jitted
    program, so the API writes the same single output buffer the kernel
    trial does). Kernel comparator: the eps-chained jnp trial. The host
    drops each output reference immediately, keeping device memory
    bounded. Constant per-run overhead cancels in the long-minus-short
    marginal difference, like the kmeans timer above.
    """
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht

    n, f = CDIST_N, CDIST_F
    rng = np.random.default_rng(1)
    data = rng.normal(size=(n, f)).astype(np.float32)
    X = ht.array(data, split=0)
    xa = X.larray

    @jax.jit
    def one_trial(x, eps):
        xx = x + eps * jnp.float32(1e-30)
        sq = jnp.sum(xx * xx, axis=1)
        d2 = sq[:, None] + sq[None, :] - 2.0 * (xx @ xx.T)
        return jnp.sqrt(jnp.maximum(d2, 0.0))

    # No mid-run host syncs: one float() is a device→host sync per step
    # and would dominate the short trials. Memory stays bounded anyway — the
    # host drops each d reference right after extracting the chain scalar,
    # execution is serialized by that data dependency, so at most two
    # (n, n) buffers are ever live on device (validated: no
    # RESOURCE_EXHAUSTED across repeated reps=24 runs on a single chip).
    def timed_kernel(reps):
        best = float("inf")
        for _ in range(5):
            s = jnp.float32(0)
            t0 = time.perf_counter()
            for _ in range(reps):
                d = one_trial(xa, s)
                s = d[0, 1]  # device scalar: chains the trials
            float(s)  # single host sync
            best = min(best, time.perf_counter() - t0)
        return best

    float(one_trial(xa, jnp.float32(0))[0, 1])  # warm compile
    out_gb = n * n * 4 / 1e9

    api_call = lambda: ht.spatial.cdist(X, quadratic_expansion=True)
    fence = lambda d: float(np.asarray(d.larray[0, 1]))
    fence(api_call())  # warm

    k_gbps = _marginal(timed_kernel, 4, 24, out_gb, cap=CAPS["kernel_cdist_gbps"])
    a_gbps = _marginal(
        _api_timed(api_call, fence, attempts=5), 4, 24, out_gb, cap=CAPS["cdist_gbps"]
    )

    # numpy baseline on a smaller n (same bytes/s semantics), best of 3
    nb = 8000
    if "cdist" not in _BASELINE_CACHE:
        xb = data[:nb]
        nb_best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            numpy_cdist(xb)
            nb_best = min(nb_best, time.perf_counter() - t0)
        _BASELINE_CACHE["cdist"] = (nb * nb * 4 / 1e9) / nb_best
    base_gbps = _BASELINE_CACHE["cdist"]

    return {
        "cdist_gbps": round(a_gbps, 2),
        "cdist_unit": f"GB/s of (n,n) f32 output via ht.spatial.cdist (n={n}, f={f})",
        "cdist_vs_baseline": round(a_gbps / base_gbps, 2),
        "kernel_cdist_gbps": round(k_gbps, 2),
    }


if __name__ == "__main__":
    import sys

    if "--serve-ws2-worker" in sys.argv:
        i = sys.argv.index("--serve-ws2-worker")
        serve_ws2_worker(
            int(sys.argv[i + 1]), int(sys.argv[i + 2]), sys.argv[i + 3]
        )
    elif "--ragged-worker" in sys.argv:
        ragged_worker()
    elif "--fused-worker" in sys.argv:
        fused_worker()
    elif "--stream-worker" in sys.argv:
        stream_worker()
    elif "--sketch-worker" in sys.argv:
        sketch_worker()
    elif "--serve-worker" in sys.argv:
        serve_worker()
    elif "--frame-worker" in sys.argv:
        frame_worker()
    else:
        main()
