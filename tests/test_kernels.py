"""Pallas kernel tests (interpret mode on the virtual CPU mesh).

Oracles, per kernel:

- top-k-distance: the materializing ``cdist`` + ``top_k`` path — values
  and indices — for ragged shapes, every k regime, and both split states
  of the query operand;
- lloyd_fused: the raw numpy Lloyd assignment (labels EXACT; sums /
  counts / inertia to f32 reassociation tolerance);
- moments_onepass: numpy mean/var (count exact; mean/M2 to ~ULP-scale
  reassociation tolerance — the kernel sums shifted values per tile, so
  equality is not bitwise but bounded by the documented rtol);
- chol_panel_fused: ``np.linalg.cholesky`` (strict upper triangle
  EXACTLY zero; entries to f32 factorization tolerance).

Every kernel runs its pallas body here via ``forced_mode(..,
"interpret")`` — the same kernel code TPUs compile, discharged on CPU —
at mesh world sizes 1 and 2, and the public entry points are
counter-asserted through ``KERNEL_STATS`` and Region-asserted to
0 compiles / 0 traces warm.
"""
from __future__ import annotations

import unittest

import numpy as np
import pytest

import heat_tpu as ht
from tests.base import TestCase


def _np_moments(x: np.ndarray, axis):
    cnt = x.size if axis is None else x.shape[axis]
    mean = x.mean(axis=axis)
    m2 = ((x - np.mean(x, axis=axis, keepdims=True)) ** 2).sum(axis=axis)
    return cnt, mean, m2


def _np_lloyd_stats(x: np.ndarray, c: np.ndarray):
    d2 = (x * x).sum(1)[:, None] + (c * c).sum(1)[None, :] - 2.0 * (x @ c.T)
    labels = d2.argmin(1)
    onehot = np.eye(c.shape[0], dtype=x.dtype)[labels]
    return onehot.T @ x, onehot.sum(0), labels, np.maximum(d2.min(1), 0.0).sum()


def _submesh(world: int):
    """A ws-``world`` mesh over the first ``world`` LOCAL devices — under
    a multi-process run every rank must build its mesh from devices it
    can address (a global-ID submesh leaves rank 1 with no local devices
    and XLA rejects the computation)."""
    import jax

    from heat_tpu.core.communication import SPLIT_AXIS
    from jax.sharding import Mesh

    if len(jax.local_devices()) < world:
        pytest.skip(f"needs {world} local devices")
    return Mesh(np.array(jax.local_devices()[:world]), axis_names=(SPLIT_AXIS,))


def _reference_knn(x: np.ndarray, y: np.ndarray, k: int):
    d2 = np.maximum(
        (x * x).sum(1)[:, None] + (y * y).sum(1)[None, :] - 2.0 * x @ y.T, 0.0
    )
    idx = np.argsort(d2, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(d2, idx, axis=1), idx


class TestTopkDistanceKernel(TestCase):
    def test_local_kernel_matches_reference(self):
        from heat_tpu.core.kernels import nearest_neighbors

        import jax.numpy as jnp

        rng = np.random.default_rng(11)
        for (n, m, f, k) in [(64, 200, 8, 5), (130, 512, 32, 1), (37, 999, 16, 7)]:
            x = rng.normal(size=(n, f)).astype(np.float32)
            y = rng.normal(size=(m, f)).astype(np.float32)
            d, i = nearest_neighbors(jnp.asarray(x), jnp.asarray(y), k, interpret=True)
            ref_d, ref_i = _reference_knn(x, y, k)
            np.testing.assert_array_equal(np.asarray(i), ref_i)
            np.testing.assert_allclose(np.asarray(d), ref_d, rtol=1e-4, atol=1e-5)

    def test_k_equals_m(self):
        from heat_tpu.core.kernels import nearest_neighbors

        import jax.numpy as jnp

        rng = np.random.default_rng(5)
        x = rng.normal(size=(16, 4)).astype(np.float32)
        y = rng.normal(size=(20, 4)).astype(np.float32)
        d, i = nearest_neighbors(jnp.asarray(x), jnp.asarray(y), 20, interpret=True)
        ref_d, ref_i = _reference_knn(x, y, 20)
        np.testing.assert_array_equal(np.asarray(i), ref_i)

    def test_invalid_k_raises(self):
        from heat_tpu.core.kernels import nearest_neighbors

        import jax.numpy as jnp

        x = jnp.zeros((4, 3))
        y = jnp.zeros((5, 3))
        with self.assertRaises(ValueError):
            nearest_neighbors(x, y, 0, interpret=True)
        with self.assertRaises(ValueError):
            nearest_neighbors(x, y, 6, interpret=True)

    def test_dndarray_api_split_sweep(self):
        """Both routes of the public entry — the kernel body (interpret,
        forced by name) and the materializing comparator a CPU backend
        dispatches by itself — against the same oracle."""
        from heat_tpu.core.kernels import forced_mode

        rng = np.random.default_rng(23)
        x = rng.normal(size=(64, 8)).astype(np.float32)
        y = rng.normal(size=(96, 8)).astype(np.float32)
        ref_d, ref_i = _reference_knn(x, y, 3)
        for mode in ("interpret", "fallback"):
            for sx in (None, 0):
                for sy in (None, 0):
                    with forced_mode("topk_distance", mode):
                        d, i = ht.spatial.nearest_neighbors(
                            ht.array(x, split=sx), ht.array(y, split=sy), 3
                        )
                    self.assertEqual(d.split, sx)
                    self.assertEqual(i.split, sx)
                    np.testing.assert_array_equal(i.numpy(), ref_i, mode)
                    np.testing.assert_allclose(
                        d.numpy(), ref_d, rtol=1e-4, atol=1e-5, err_msg=mode
                    )

    def test_knn_classifier_fused_path_matches(self):
        """The fused route ``predict`` takes on a TPU backend — past the
        nq*nt gate, kernel forced to interpret by name — against the
        materializing predict a CPU backend dispatches by itself."""
        from heat_tpu.classification.kneighborsclassifier import KNeighborsClassifier
        from heat_tpu.core.kernels import forced_mode, reset_kernel_stats

        rng = np.random.default_rng(31)
        nq, nt = 1024, 4100  # nq * nt just over the classifier's 2^22 gate
        xt = rng.normal(size=(nt, 6)).astype(np.float32)
        yt = (rng.integers(0, 3, size=(nt,))).astype(np.int32)
        xq = rng.normal(size=(nq, 6)).astype(np.float32)

        clf = KNeighborsClassifier(n_neighbors=5).fit(ht.array(xt), ht.array(yt))
        reset_kernel_stats()
        base = clf.predict(ht.array(xq)).numpy()
        self.assertEqual(ht.KERNEL_STATS.get("topk_distance.fallback"), 1)
        with forced_mode("topk_distance", "interpret"):
            fused = clf.predict(ht.array(xq)).numpy()
        self.assertEqual(ht.KERNEL_STATS.get("topk_distance.interpret"), 1)
        np.testing.assert_array_equal(base, fused)


class TestDispatchRegistry(TestCase):
    def test_registry_catalog(self):
        """Every fused kernel registers a fallback mode, a raw-jnp
        comparator note and a roofline statement."""
        from heat_tpu.core import kernels

        for name in (
            "topk_distance",
            "lloyd_fused",
            "moments_onepass",
            "chol_panel_fused",
        ):
            spec = kernels.kernel_spec(name)
            self.assertIn(spec["fallback"], ("fallback", "xla"), name)
            self.assertTrue(spec["comparator"], name)
            self.assertTrue(spec["roofline"], name)
            # CPU mesh: the compiled pallas probe must answer False
            self.assertFalse(kernels.pallas_supported(name))

    def test_dispatch_defaults_and_forced_mode(self):
        from heat_tpu.core.kernels import dispatch_mode, forced_mode

        self.assertEqual(dispatch_mode("lloyd_fused"), "fallback")
        self.assertEqual(dispatch_mode("moments_onepass"), "xla")
        self.assertEqual(dispatch_mode("chol_panel_fused"), "fallback")
        with forced_mode("lloyd_fused", "interpret"):
            self.assertEqual(dispatch_mode("lloyd_fused"), "interpret")
            with forced_mode("lloyd_fused", "fallback"):
                self.assertEqual(dispatch_mode("lloyd_fused"), "fallback")
            self.assertEqual(dispatch_mode("lloyd_fused"), "interpret")
        self.assertEqual(dispatch_mode("lloyd_fused"), "fallback")

    def test_kernel_stats_export_and_counters(self):
        from heat_tpu.core import kernels

        self.assertIs(ht.KERNEL_STATS, kernels.KERNEL_STATS)
        kernels.reset_kernel_stats()
        kernels.record_dispatch("lloyd_fused", "pallas")
        kernels.record_dispatch("lloyd_fused", "fallback")
        kernels.record_dispatch("moments_onepass", "xla")
        self.assertEqual(ht.KERNEL_STATS["dispatches"], 3)
        self.assertEqual(ht.KERNEL_STATS["lloyd_fused.pallas"], 1)
        self.assertEqual(ht.KERNEL_STATS["lloyd_fused.fallback"], 1)
        self.assertEqual(ht.KERNEL_STATS["moments_onepass.xla"], 1)
        kernels.reset_kernel_stats()
        self.assertEqual(ht.KERNEL_STATS, {"dispatches": 0})

    def test_flash_knn_dispatch_counted(self):
        """The public nearest_neighbors entry reports its kernel-vs-
        fallback decision once per call (satellite: counter-assert the
        flash-kNN dispatch)."""
        from heat_tpu.core.kernels import forced_mode, reset_kernel_stats

        rng = np.random.default_rng(3)
        x = ht.array(rng.normal(size=(32, 4)).astype(np.float32))
        y = ht.array(rng.normal(size=(48, 4)).astype(np.float32))
        reset_kernel_stats()
        ht.spatial.nearest_neighbors(x, y, 3)
        # CPU mesh: no compiled pallas -> the registered fallback; the
        # interpreter is never picked silently
        self.assertEqual(ht.KERNEL_STATS["topk_distance.fallback"], 1)
        self.assertEqual(ht.KERNEL_STATS["dispatches"], 1)
        with forced_mode("topk_distance", "interpret"):
            ht.spatial.nearest_neighbors(x, y, 3)
        self.assertEqual(ht.KERNEL_STATS["topk_distance.interpret"], 1)
        self.assertNotIn("topk_distance.pallas", ht.KERNEL_STATS)


class TestMomentsKernel(TestCase):
    def test_local_interpret_parity(self):
        """Interpret-mode kernel vs numpy across shapes, including a
        padded tail masked by n_valid."""
        import jax.numpy as jnp

        from heat_tpu.core.kernels import moments_local

        rng = np.random.default_rng(17)
        for n, f, pad in [(64, 8, 0), (999, 7, 25), (40, 1, 0), (130, 16, 6)]:
            x = rng.normal(size=(n, f)).astype(np.float32) * 3 + 1.5
            buf = np.concatenate(
                [x, np.full((pad, f), 1e30, np.float32)]
            ) if pad else x
            cnt, mean, m2 = moments_local(jnp.asarray(buf), n, interpret=True)
            ref_c, ref_mean, ref_m2 = _np_moments(x, 0)
            self.assertEqual(float(cnt), ref_c)
            np.testing.assert_allclose(np.asarray(mean), ref_mean, rtol=2e-6, atol=2e-6)
            # M2 reassociates (tiled shifted sums): ~ULP-scale tolerance
            np.testing.assert_allclose(np.asarray(m2), ref_m2, rtol=2e-4, atol=2e-4)

    def test_chunk_merge_matches_whole(self):
        """chunk_moments + Chan merge over two halves == whole buffer."""
        import jax.numpy as jnp

        from heat_tpu.core.kernels import chunk_moments, merge_moments

        rng = np.random.default_rng(8)
        x = rng.normal(size=(200, 5)).astype(np.float32)
        na, ma, m2a = chunk_moments(jnp.asarray(x[:80]), 80)
        nb, mb, m2b = chunk_moments(jnp.asarray(x[80:]), 120)
        n, mean, m2 = merge_moments(na, ma, m2a, nb, mb, m2b)
        _, ref_mean, ref_m2 = _np_moments(x, 0)
        self.assertEqual(float(n), 200)
        np.testing.assert_allclose(np.asarray(mean), ref_mean, rtol=2e-6, atol=2e-6)
        np.testing.assert_allclose(np.asarray(m2), ref_m2, rtol=2e-4, atol=2e-4)

    def test_sharded_interpret_parity_ws_1_2(self):
        """The shard_map wrapper at mesh world sizes 1 and 2: per-shard
        kernel + psum Chan combine equals the numpy whole."""
        import jax.numpy as jnp

        from heat_tpu.core.kernels import moments_sharded

        rng = np.random.default_rng(29)
        x = rng.normal(size=(80, 6)).astype(np.float32)
        ref_c, ref_mean, ref_m2 = _np_moments(x, 0)
        for world in (1, 2):
            mesh = _submesh(world)
            cnt, mean, m2 = moments_sharded(jnp.asarray(x), 80, mesh, interpret=True)
            self.assertEqual(float(cnt), ref_c, f"ws={world}")
            np.testing.assert_allclose(np.asarray(mean), ref_mean, rtol=2e-6, atol=2e-6)
            np.testing.assert_allclose(np.asarray(m2), ref_m2, rtol=2e-4, atol=2e-4)


class TestOnePassStatisticsDispatch(TestCase):
    """Public ht.mean/ht.std/ht.var through the one-pass panel."""

    def _data(self, shape, seed=5):
        rng = np.random.default_rng(seed)
        return (rng.normal(size=shape).astype(np.float32) * 2 + 0.75)

    def test_public_parity_sweep(self):
        """mean/std/var vs numpy for every split and axis (the default
        xla one-pass panel on CPU), ddof 0 and 1."""
        for shape in [(40,), (37,), (64, 8), (37, 5)]:
            x = self._data(shape)
            splits = (None,) + tuple(range(len(shape)))
            axes = (None,) + tuple(range(len(shape)))
            for split in splits:
                xd = ht.array(x, split=split)
                for axis in axes:
                    np.testing.assert_allclose(
                        ht.mean(xd, axis=axis).numpy(), x.mean(axis=axis),
                        rtol=2e-5, atol=2e-5,
                    )
                    for ddof in (0, 1):
                        np.testing.assert_allclose(
                            ht.var(xd, axis=axis, ddof=ddof).numpy(),
                            x.var(axis=axis, ddof=ddof),
                            rtol=2e-4, atol=2e-4,
                        )
                        np.testing.assert_allclose(
                            ht.std(xd, axis=axis, ddof=ddof).numpy(),
                            x.std(axis=axis, ddof=ddof),
                            rtol=2e-4, atol=2e-4,
                        )

    @pytest.mark.multihost
    def test_forced_interpret_kernel_parity(self):
        """The SAME public calls through the pallas kernel body
        (interpret): split None and 0, axis None/0, 1-D and 2-D."""
        from heat_tpu.core.kernels import forced_mode, reset_kernel_stats

        with forced_mode("moments_onepass", "interpret"):
            for shape, split, axis in [
                ((64, 8), 0, 0),
                ((64, 8), 0, None),
                ((64, 8), None, 0),
                ((40,), 0, None),
                ((40,), 0, 0),
                ((40,), None, None),
                ((40,), None, 0),
            ]:
                x = self._data(shape, seed=13)
                xd = ht.array(x, split=split)
                reset_kernel_stats()
                got_mean = ht.mean(xd, axis=axis).numpy()
                got_var = ht.var(xd, axis=axis, ddof=1).numpy()
                mode = "interpret"
                if split == 0 and self.comm.size > 1 and shape[0] % self.comm.size:
                    mode = "xla"  # uneven shards decline to the XLA panel
                self.assertGreaterEqual(
                    ht.KERNEL_STATS.get(f"moments_onepass.{mode}", 0), 1,
                    ht.KERNEL_STATS,
                )
                np.testing.assert_allclose(
                    got_mean, x.mean(axis=axis), rtol=2e-5, atol=2e-5
                )
                np.testing.assert_allclose(
                    got_var, x.var(axis=axis, ddof=1), rtol=2e-4, atol=2e-4
                )

    def test_memo_second_call_is_free(self):
        """A following std/var on the same buffer is a memo hit: counted
        as a dispatch, but no new panel computation (0 compiles)."""
        from heat_tpu.analysis import Region
        from heat_tpu.core.kernels import reset_kernel_stats

        x = self._data((64, 8), seed=21)
        xd = ht.array(x)
        # warm every finalize program on a twin buffer first
        twin = ht.array(self._data((64, 8), seed=22))
        for op in (ht.mean, ht.std, ht.var):
            op(twin)
        reset_kernel_stats()
        reg = Region("kernels-moments-warm")
        ht.mean(xd)
        ht.std(xd)
        ht.var(xd, ddof=1)
        self.assertEqual(reg.compiles, 0, "warm one-pass moments compiled")
        self.assertEqual(reg.traces, 0, "warm one-pass moments retraced")
        self.assertEqual(ht.KERNEL_STATS["dispatches"], 3)
        self.assertEqual(ht.KERNEL_STATS["moments_onepass.xla"], 3)

    def test_declined_axis_memoizes_beside_kernel_axes(self):
        """An axis the kernel declines (axis=1) computes via the XLA
        panel but memoizes under the REQUESTED mode: later calls are memo
        hits reporting the mode that computed each axis, and the declined
        axis does not evict the buffer's kernel-computed axes."""
        from heat_tpu.core import statistics
        from heat_tpu.core.kernels import forced_mode, reset_kernel_stats

        x = self._data((64, 8), seed=17)
        with forced_mode("moments_onepass", "interpret"):
            xd = ht.array(x)
            reset_kernel_stats()
            ht.mean(xd, axis=1)  # kernel declines -> XLA panel
            ht.mean(xd, axis=0)  # kernel path
            self.assertEqual(ht.KERNEL_STATS.get("moments_onepass.xla", 0), 1)
            self.assertEqual(
                ht.KERNEL_STATS.get("moments_onepass.interpret", 0), 1
            )
            ent = statistics._PANELS[id(xd.larray)]
            self.assertEqual(set(ent[2]), {"0", "1", "all"})
            reset_kernel_stats()
            ht.var(xd, axis=1, ddof=1)  # memo hit on the declined axis
            ht.var(xd, axis=0, ddof=1)  # memo hit on the kernel axis
            self.assertEqual(ht.KERNEL_STATS.get("moments_onepass.xla", 0), 1)
            self.assertEqual(
                ht.KERNEL_STATS.get("moments_onepass.interpret", 0), 1
            )
            self.assertIs(statistics._PANELS[id(xd.larray)], ent)
            np.testing.assert_allclose(
                ht.var(xd, axis=1, ddof=1).numpy(),
                x.var(axis=1, ddof=1),
                rtol=2e-4, atol=2e-4,
            )

    def test_panel_memo_stays_bounded(self):
        """The per-buffer memo is FIFO-bounded (G002): folding many
        distinct buffers cannot grow it past the cap."""
        from heat_tpu.core import statistics

        for i in range(statistics._PANELS_CAP + 8):
            ht.mean(ht.array(self._data((8, 3), seed=100 + i)))
        self.assertLessEqual(len(statistics._PANELS), statistics._PANELS_CAP)

    def test_where_and_ddof_plumbing(self):
        """where= routes through the decline-to-eager masked path and
        still matches numpy; ddof plumbs through both panel and where
        paths."""
        x = self._data((30, 4), seed=9)
        mask = x > 0
        xd = ht.array(x)
        md = ht.array(mask)
        np.testing.assert_allclose(
            ht.mean(xd, axis=0, where=md).numpy(),
            np.mean(x, axis=0, where=mask),
            rtol=2e-5, atol=2e-5,
        )
        np.testing.assert_allclose(
            ht.var(xd, axis=0, ddof=1, where=md).numpy(),
            np.var(x, axis=0, ddof=1, where=mask),
            rtol=2e-4, atol=2e-4,
        )
        np.testing.assert_allclose(
            ht.std(xd, axis=0, ddof=1, where=md).numpy(),
            np.std(x, axis=0, ddof=1, where=mask),
            rtol=2e-4, atol=2e-4,
        )

    def test_streaming_moments_forced_interpret(self):
        """StreamingMoments folds each chunk through the kernel body in
        interpret mode and matches the in-memory oracle."""
        from heat_tpu.core.kernels import forced_mode, reset_kernel_stats
        from heat_tpu.stream import StreamingMoments

        x = self._data((96, 5), seed=33)
        with forced_mode("moments_onepass", "interpret"):
            reset_kernel_stats()
            est = StreamingMoments(ddof=1)
            for i in range(0, 96, 24):
                est.update(ht.array(x[i:i + 24]))
            folds = ht.KERNEL_STATS.get("moments_onepass.interpret", 0)
            self.assertEqual(folds, 4, ht.KERNEL_STATS)
        np.testing.assert_allclose(est.mean.numpy(), x.mean(0), rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(
            est.var.numpy(), x.var(0, ddof=1), rtol=2e-4, atol=2e-4
        )


class TestLloydKernel(TestCase):
    def test_local_interpret_parity(self):
        """Fused distance+argmin+centroid-stats vs the numpy Lloyd
        assignment: labels exact, stats to f32 reassociation tolerance,
        padded tail excluded."""
        import jax.numpy as jnp

        from heat_tpu.core.kernels import lloyd_local

        rng = np.random.default_rng(41)
        for n, f, k, pad in [(64, 4, 3, 0), (120, 8, 8, 0), (90, 5, 4, 10)]:
            x = rng.normal(size=(n, f)).astype(np.float32) * 4
            c = x[rng.choice(n, k, replace=False)].copy()
            buf = np.concatenate(
                [x, np.full((pad, f), 7e7, np.float32)]
            ) if pad else x
            sums, counts, labels, inertia = lloyd_local(
                jnp.asarray(buf), jnp.asarray(c), n, interpret=True
            )
            ref_s, ref_c, ref_l, ref_i = _np_lloyd_stats(x, c)
            np.testing.assert_array_equal(np.asarray(labels)[:n], ref_l)
            np.testing.assert_array_equal(np.asarray(counts), ref_c)
            np.testing.assert_allclose(np.asarray(sums), ref_s, rtol=1e-5, atol=1e-4)
            np.testing.assert_allclose(float(inertia), ref_i, rtol=1e-4)

    def test_sharded_interpret_parity_ws_1_2(self):
        import jax.numpy as jnp

        from heat_tpu.core.kernels import lloyd_sharded

        rng = np.random.default_rng(43)
        x = rng.normal(size=(80, 6)).astype(np.float32)
        c = x[:5].copy()
        ref_s, ref_c, ref_l, ref_i = _np_lloyd_stats(x, c)
        for world in (1, 2):
            mesh = _submesh(world)
            sums, counts, labels, inertia = lloyd_sharded(
                jnp.asarray(x), jnp.asarray(c), 80, mesh, interpret=True
            )
            np.testing.assert_array_equal(np.asarray(labels), ref_l, f"ws={world}")
            np.testing.assert_array_equal(np.asarray(counts), ref_c)
            np.testing.assert_allclose(np.asarray(sums), ref_s, rtol=1e-5, atol=1e-4)
            np.testing.assert_allclose(float(inertia), ref_i, rtol=1e-4)

    @pytest.mark.multihost
    def test_kmeans_forced_interpret_matches_fallback(self):
        """Public KMeans.fit through the fused kernel == the fused-XLA
        fallback: same centers, labels, inertia (the kernel computes the
        identical reduction), dispatch counted per fit."""
        from heat_tpu.core.kernels import forced_mode, reset_kernel_stats

        rng = np.random.default_rng(47)
        x = rng.normal(size=(80, 4)).astype(np.float32)
        init = ht.array(x[rng.choice(80, 3, replace=False)].copy())
        for split in (None, 0):
            xd = ht.array(x, split=split)
            base = ht.cluster.KMeans(n_clusters=3, init=init, max_iter=7).fit(xd)
            reset_kernel_stats()
            with forced_mode("lloyd_fused", "interpret"):
                fused = ht.cluster.KMeans(n_clusters=3, init=init, max_iter=7).fit(xd)
            modes = [k for k in ht.KERNEL_STATS if k.startswith("lloyd_fused.")]
            self.assertTrue(modes, ht.KERNEL_STATS)
            np.testing.assert_allclose(
                fused.cluster_centers_.numpy(), base.cluster_centers_.numpy(),
                rtol=1e-5, atol=1e-5,
            )
            np.testing.assert_array_equal(
                fused.labels_.numpy(), base.labels_.numpy()
            )
            self.assertAlmostEqual(
                fused.inertia_, base.inertia_, delta=1e-3 * (1 + abs(base.inertia_))
            )

    def test_streaming_kmeans_forced_interpret(self):
        """StreamingKMeans drives the same dispatch per chunk; a global
        epoch under the kernel equals the fallback epoch."""
        from heat_tpu.core.kernels import forced_mode, reset_kernel_stats
        from heat_tpu.stream.chunked import ChunkIterator

        rng = np.random.default_rng(51)
        x = rng.normal(size=(96, 4)).astype(np.float32)
        init = ht.array(x[:4].copy())

        def chunks():
            return [ht.array(x[i:i + 24]) for i in range(0, 96, 24)]

        base = ht.cluster.StreamingKMeans(
            n_clusters=4, init=init, max_iter=3, tol=None
        ).fit(chunks())
        reset_kernel_stats()
        with forced_mode("lloyd_fused", "interpret"):
            fused = ht.cluster.StreamingKMeans(
                n_clusters=4, init=init, max_iter=3, tol=None
            ).fit(chunks())
        self.assertGreaterEqual(
            ht.KERNEL_STATS.get("lloyd_fused.interpret", 0), 4, ht.KERNEL_STATS
        )
        np.testing.assert_allclose(
            fused.cluster_centers_.numpy(), base.cluster_centers_.numpy(),
            rtol=1e-5, atol=1e-5,
        )

    def test_warm_refit_zero_compiles(self):
        """A second fit with identical shapes/statics reuses every cached
        program: Region-asserted 0 compiles / 0 traces."""
        from heat_tpu.analysis import Region

        rng = np.random.default_rng(53)
        x = ht.array(rng.normal(size=(64, 4)).astype(np.float32), split=0)
        init = ht.array(np.asarray(rng.normal(size=(3, 4)), np.float32))
        ht.cluster.KMeans(n_clusters=3, init=init, max_iter=5).fit(x)  # warm
        reg = Region("kernels-kmeans-warm")
        ht.cluster.KMeans(n_clusters=3, init=init, max_iter=5).fit(x)
        self.assertEqual(reg.compiles, 0)
        self.assertEqual(reg.traces, 0)


class TestCholKernel(TestCase):
    def _spd(self, n, seed=0):
        rng = np.random.default_rng(seed)
        a = rng.standard_normal((n, n)).astype(np.float32)
        return a @ a.T + n * np.eye(n, dtype=np.float32)

    def test_blocked_interpret_parity(self):
        """Panel-fused blocked factorization vs np.linalg.cholesky across
        sizes and block sizes, including n not divisible by bs; the
        strict upper triangle is EXACTLY zero."""
        import jax.numpy as jnp

        from heat_tpu.core.kernels import cholesky_blocked

        for n, bs in [(5, 8), (37, 16), (64, 32), (130, 64), (200, 128)]:
            spd = self._spd(n, seed=n)
            L = np.asarray(
                cholesky_blocked(jnp.asarray(spd), bs=bs, interpret=True)
            )
            ref = np.linalg.cholesky(spd)
            self.assertEqual(np.abs(np.triu(L, 1)).max(), 0.0)
            np.testing.assert_allclose(L, ref, rtol=2e-4, atol=2e-4 * n)
            # and the factorization property itself
            np.testing.assert_allclose(
                L @ L.T, spd, rtol=2e-4, atol=2e-4 * np.abs(spd).max()
            )

    def test_validation(self):
        import jax.numpy as jnp

        from heat_tpu.core.kernels import MAX_FUSED_N, cholesky_blocked

        with self.assertRaises(ValueError):
            cholesky_blocked(jnp.zeros((4, 5)), interpret=True)
        with self.assertRaises(ValueError):
            cholesky_blocked(jnp.zeros((MAX_FUSED_N + 8, MAX_FUSED_N + 8)),
                             interpret=True)

    def test_public_forced_interpret_matches_fallback(self):
        """ht.linalg.cholesky through the kernel == jnp fallback; f64
        and oversize inputs decline to fallback with the decision
        counted."""
        from heat_tpu.core.kernels import forced_mode, reset_kernel_stats

        spd = self._spd(37, seed=2)
        x = ht.array(spd)
        reset_kernel_stats()
        base = ht.linalg.cholesky(x)
        self.assertEqual(ht.KERNEL_STATS.get("chol_panel_fused.fallback"), 1)
        with forced_mode("chol_panel_fused", "interpret"):
            reset_kernel_stats()
            fused = ht.linalg.cholesky(x)
            self.assertEqual(ht.KERNEL_STATS.get("chol_panel_fused.interpret"), 1)
            np.testing.assert_allclose(
                fused.numpy(), base.numpy(), rtol=2e-4, atol=5e-4
            )
            # f32-only kernel: f64 declines to the XLA fallback
            reset_kernel_stats()
            ht.linalg.cholesky(ht.array(spd.astype(np.float64)))
            self.assertEqual(ht.KERNEL_STATS.get("chol_panel_fused.fallback"), 1)


if __name__ == "__main__":
    unittest.main()


def _fit_kmeans(f: int, k: int):
    x = np.random.default_rng(5).normal(size=(k + 40, f)).astype(np.float32)
    ht.cluster.KMeans(n_clusters=k, init=ht.array(x[:k].copy()), max_iter=2, tol=None).fit(ht.array(x, split=0))


def _fit_streaming_kmeans(f: int, k: int):
    x = np.random.default_rng(6).normal(size=(48, f)).astype(np.float32)
    ht.cluster.StreamingKMeans(n_clusters=k, init=ht.array(x[:k].copy()), max_iter=1, tol=None).fit(
        [ht.array(x[:24]), ht.array(x[24:])]
    )


def _mean_std(f: int, _k: int):
    x = np.random.default_rng(7).normal(size=(40, f)).astype(np.float32)
    xd = ht.array(x, split=0)
    np.testing.assert_allclose(ht.mean(xd, axis=0).numpy(), x.mean(0), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ht.std(xd, axis=0).numpy(), x.std(0), rtol=1e-4, atol=1e-5)


def _streaming_moments(f: int, _k: int):
    from heat_tpu.stream import StreamingMoments

    x = np.random.default_rng(8).normal(size=(48, f)).astype(np.float32)
    est = StreamingMoments()
    for i in (0, 24):
        est.update(ht.array(x[i:i + 24]))
    np.testing.assert_allclose(est.mean.numpy(), x.mean(0), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "kernel, declined_as, run, f, k",
    [
        ("lloyd_fused", "fallback", _fit_kmeans, 65, 3),
        ("lloyd_fused", "fallback", _fit_kmeans, 2, 1025),
        ("lloyd_fused", "fallback", _fit_streaming_kmeans, 65, 3),
        ("moments_onepass", "xla", _mean_std, 65, 0),
        ("moments_onepass", "xla", _streaming_moments, 65, 0),
    ],
    ids=["kmeans-wide", "kmeans-many-centers", "streaming-kmeans-wide", "mean-std-wide", "streaming-moments-wide"],
)
def test_shapes_past_kernel_fits_are_declined_and_counted(kernel, declined_as, run, f, k):
    """A shape the kernel was not compiled for (rows wider than the
    chip keeps column-major, more centers than VMEM holds resident) never
    reaches it: dispatch records the declared twin instead, and one
    inside the bound still takes the kernel."""
    from heat_tpu.core.kernels import forced_mode, reset_kernel_stats

    with forced_mode(kernel, "interpret"):
        reset_kernel_stats()
        run(f, k)
        stats = dict(ht.KERNEL_STATS)
        assert stats.get(f"{kernel}.{declined_as}", 0) >= 1, stats
        assert f"{kernel}.interpret" not in stats, stats
        reset_kernel_stats()
        run(min(f, 64), min(k, 3))
        assert ht.KERNEL_STATS.get(f"{kernel}.interpret", 0) >= 1, dict(ht.KERNEL_STATS)
