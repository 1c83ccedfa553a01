"""Ring attention / checkpoint / profiling tests."""
import os
import tempfile

import numpy as np
import pytest

import heat_tpu as ht

from .base import TestCase


class TestUlyssesAttention(TestCase):
    """All-to-all sequence parallelism (the second long-context schedule
    next to ring attention): reshard to head-sharded, full-sequence local
    attention, reshard back — exact vs the dense oracle."""

    def _run(self, causal):
        import jax.numpy as jnp

        from heat_tpu.parallel import ulysses_attention
        from heat_tpu.parallel.ring_attention import attention

        comm = ht.get_comm()
        if comm.size == 1:
            pytest.skip("needs multi-device mesh")
        rng = np.random.default_rng(3)
        p = comm.size
        n, h, d = p * 8, p * 2, 16  # sequence AND heads divisible
        mk = lambda: jnp.asarray(rng.normal(size=(n, h, d)).astype(np.float32))
        q, k, v = mk(), mk(), mk()
        qs = ht.array(np.asarray(q), split=0).larray
        ks = ht.array(np.asarray(k), split=0).larray
        vs = ht.array(np.asarray(v), split=0).larray
        out = ulysses_attention(qs, ks, vs, comm, causal=causal)
        # oracle: heads as batch dim
        expected = jnp.moveaxis(
            attention(
                jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0),
                causal=causal,
            ),
            0,
            1,
        )
        assert out.shape == (n, h, d)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), rtol=2e-4, atol=2e-4)

    def test_full(self):
        self._run(causal=False)

    def test_causal(self):
        self._run(causal=True)

    def test_matches_ring_attention(self):
        """Both schedules are exact: per-head results must agree."""
        import jax.numpy as jnp

        from heat_tpu.parallel import ring_attention, ulysses_attention

        comm = ht.get_comm()
        if comm.size == 1:
            pytest.skip("needs multi-device mesh")
        rng = np.random.default_rng(4)
        p = comm.size
        n, h, d = p * 8, p, 8
        mk = lambda: rng.normal(size=(n, h, d)).astype(np.float32)
        q, k, v = mk(), mk(), mk()
        qs = ht.array(q, split=0).larray
        ks = ht.array(k, split=0).larray
        vs = ht.array(v, split=0).larray
        uly = np.asarray(ulysses_attention(qs, ks, vs, comm, causal=True))
        for head in range(h):
            ring = np.asarray(
                ring_attention(
                    ht.array(q[:, head], split=0).larray,
                    ht.array(k[:, head], split=0).larray,
                    ht.array(v[:, head], split=0).larray,
                    comm,
                    causal=True,
                )
            )
            np.testing.assert_allclose(uly[:, head], ring, rtol=2e-4, atol=2e-4)

    def test_validation(self):
        import jax.numpy as jnp

        from heat_tpu.parallel import ulysses_attention

        comm = ht.get_comm()
        if comm.size == 1:
            pytest.skip("needs multi-device mesh")
        z = jnp.zeros((comm.size * 4, comm.size, 4))
        with pytest.raises(ValueError):  # 2-D input
            ulysses_attention(z[:, 0], z[:, 0], z[:, 0], comm)

    def test_pad_and_trim_non_divisible(self):
        """Non-divisible N AND H must be tail-padded, masked, trimmed —
        not raise (VERDICT r2 item 4); exercised at world sizes 5/8 by the
        HEAT_TPU_TEST_DEVICES matrix."""
        import jax.numpy as jnp

        from heat_tpu.parallel import ulysses_attention
        from heat_tpu.parallel.ring_attention import attention

        comm = ht.get_comm()
        if comm.size == 1:
            pytest.skip("needs multi-device mesh")
        rng = np.random.default_rng(7)
        p = comm.size
        # neither divides: sequence p*6+3, heads p+1
        for n, h in [(p * 6 + 3, p + 1), (p * 4 + 1, 2 * p - 1)]:
            d = 8
            q, k, v = (rng.normal(size=(n, h, d)).astype(np.float32) for _ in range(3))
            for causal in (False, True):
                out = ulysses_attention(
                    jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), comm, causal=causal
                )
                expected = jnp.moveaxis(
                    attention(
                        jnp.moveaxis(jnp.asarray(q), 1, 0),
                        jnp.moveaxis(jnp.asarray(k), 1, 0),
                        jnp.moveaxis(jnp.asarray(v), 1, 0),
                        causal=causal,
                    ),
                    0, 1,
                )
                assert out.shape == (n, h, d)
                np.testing.assert_allclose(
                    np.asarray(out), np.asarray(expected), rtol=2e-4, atol=2e-4,
                    err_msg=f"n={n} h={h} causal={causal}",
                )


class TestRingAttention(TestCase):
    def _run(self, causal):
        import jax.numpy as jnp

        from heat_tpu.parallel import ring_attention
        from heat_tpu.parallel.ring_attention import attention

        comm = ht.get_comm()
        if comm.size == 1:
            pytest.skip("needs multi-device mesh")
        rng = np.random.default_rng(0)
        n, d = comm.size * 16, 16  # sequence divisible by any world size
        q = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        qs = ht.array(np.asarray(q), split=0).larray
        ks = ht.array(np.asarray(k), split=0).larray
        vs = ht.array(np.asarray(v), split=0).larray
        out = ring_attention(qs, ks, vs, comm, causal=causal)
        expected = attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), rtol=2e-4, atol=2e-4)

    def test_full(self):
        self._run(causal=False)

    def test_causal(self):
        self._run(causal=True)

    def test_pad_and_trim_non_divisible(self):
        import jax.numpy as jnp

        from heat_tpu.parallel import ring_attention
        from heat_tpu.parallel.ring_attention import attention

        comm = ht.get_comm()
        if comm.size == 1:
            pytest.skip("needs multi-device mesh")
        rng = np.random.default_rng(8)
        for n in (comm.size * 5 + 2, comm.size + 1, 2 * comm.size - 1):
            d = 8
            q, k, v = (
                jnp.asarray(rng.normal(size=(n, d)).astype(np.float32)) for _ in range(3)
            )
            for causal in (False, True):
                out = ring_attention(q, k, v, comm, causal=causal)
                expected = attention(q, k, v, causal=causal)
                assert out.shape == (n, d)
                np.testing.assert_allclose(
                    np.asarray(out), np.asarray(expected), rtol=2e-4, atol=2e-4,
                    err_msg=f"n={n} causal={causal}",
                )

    def test_validates(self):
        import jax.numpy as jnp

        from heat_tpu.parallel import ring_attention

        with pytest.raises(ValueError):
            ring_attention(jnp.zeros((4, 2, 2)), jnp.zeros((4, 2, 2)), jnp.zeros((4, 2, 2)), ht.get_comm())


class TestCheckpointing(TestCase):
    def test_roundtrip_tree(self):
        import jax.numpy as jnp

        ht.random.seed(123)
        ht.random.rand(4)
        state = {
            "params": {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)},
            "data": ht.arange(16, dtype=ht.float32, split=0),
        }
        with tempfile.TemporaryDirectory() as d:
            ht.utils.save_checkpoint(d, state, step=7, metadata={"note": "test"})
            rng_before = ht.random.get_state()
            ht.random.seed(999)  # clobber
            like = {
                "params": {"w": jnp.zeros((2, 3), dtype=jnp.float32)},
                "data": ht.zeros(16, split=0),
            }
            restored, step, meta = ht.utils.load_checkpoint(d, like=like)
            assert step == 7
            assert meta["note"] == "test"
            np.testing.assert_array_equal(np.asarray(restored["params"]["w"]), np.arange(6).reshape(2, 3))
            assert isinstance(restored["data"], ht.DNDarray)
            assert restored["data"].split == 0
            np.testing.assert_array_equal(restored["data"].numpy(), np.arange(16))
            assert ht.random.get_state()[1] == rng_before[1]  # rng restored

    def test_resume_equivalence(self):
        """The checkpoint guarantee: save mid-training, clobber everything,
        restore, continue — results identical to the uninterrupted run
        (params, sharded data incl. padded shapes, and the RNG stream)."""
        import jax
        import jax.numpy as jnp

        def step(params, x, key):
            noise = jax.random.normal(key, params.shape) * 0.01
            return params - 0.1 * (params - x.mean()) + noise

        x = ht.array(np.arange(9 * 3, dtype=np.float32).reshape(9, 3), split=0)

        def run(params, n, seed_counter_start):
            for i in range(n):
                params = step(params, x._logical(), jax.random.PRNGKey(i + seed_counter_start))
            return params

        p0 = jnp.zeros((4,), jnp.float32)
        uninterrupted = run(run(p0, 3, 0), 3, 3)

        mid = run(p0, 3, 0)
        ht.random.seed(55)
        ht.random.rand(5)  # advance the stream
        with tempfile.TemporaryDirectory() as d:
            ht.utils.save_checkpoint(d, {"p": mid, "x": x}, step=3)
            ht.random.seed(0)  # clobber stream + params
            like = {"p": jnp.ones((4,), jnp.float32), "x": ht.zeros((9, 3), split=0)}
            restored, step_no, _ = ht.utils.load_checkpoint(d, like=like)
            assert step_no == 3
            assert restored["x"].split == 0
            if ht.get_comm().size > 1:
                assert not restored["x"].larray.sharding.is_fully_replicated
            np.testing.assert_array_equal(restored["x"].numpy(), x.numpy())
            resumed = run(restored["p"], 3, 3)
            np.testing.assert_allclose(np.asarray(resumed), np.asarray(uninterrupted), rtol=1e-7)
            # the RNG stream continues where the checkpoint left it
            cont = ht.random.rand(5).numpy()
            ht.random.seed(55)
            ht.random.rand(5)
            np.testing.assert_array_equal(cont, ht.random.rand(5).numpy())

    def test_checkpoint_split1_padded(self):
        x = ht.array(np.arange(4 * 9, dtype=np.float32).reshape(4, 9), split=1)
        with tempfile.TemporaryDirectory() as d:
            ht.utils.save_checkpoint(d, {"x": x})
            restored, _, _ = ht.utils.load_checkpoint(
                d, like={"x": ht.zeros((4, 9), split=1)}
            )
            assert restored["x"].split == 1
            np.testing.assert_array_equal(restored["x"].numpy(), x.numpy())

    def test_leaf_mismatch(self):
        import jax.numpy as jnp

        with tempfile.TemporaryDirectory() as d:
            ht.utils.save_checkpoint(d, {"a": jnp.zeros(3)})
            with pytest.raises(ValueError):
                ht.utils.load_checkpoint(d, like={"a": jnp.zeros(3), "b": jnp.zeros(2)})


class TestProfiling(TestCase):
    def test_timer(self):
        x = ht.random.randn(64, 64, split=0)
        with ht.utils.profiling.Timer() as t:
            y = ht.matmul(x, x.T)
        assert t.elapsed is not None and t.elapsed >= 0

    def test_annotate(self):
        with ht.utils.profiling.annotate("region"):
            pass

    def test_timer_stop_fences_what_it_is_given(self):
        """``stop(x)`` is ``jax.block_until_ready`` on x's buffers —
        DNDarrays and pytrees of them unwrapped — with no host fetch."""
        from heat_tpu.analysis.sanitizer import sanitizer

        x = ht.random.randn(256, 256, split=0)
        t = ht.utils.profiling.Timer().start()
        y = ht.matmul(x, x.T)
        with sanitizer("timer fence") as region:
            t.stop(y, {"also": [y.larray]})
        self.assertTrue(y.larray.is_ready())
        self.assertEqual(region.host_syncs, 0)
        ht.utils.profiling.force_sync()  # nothing given: nothing to do

    def test_compile_cache_dir_env_wins_else_fixed_path(self):
        """JAX_COMPILATION_CACHE_DIR set: no directory is set in code.
        Unset: <checkout>/.jax_cache, the same path on every call."""
        import os
        from unittest import mock

        import jax
        from jax.experimental.compilation_cache import compilation_cache

        from heat_tpu.utils.profiling import configure_compile_cache

        names = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")
        before = {n: getattr(jax.config, n) for n in names}
        try:
            with mock.patch.dict(os.environ, {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else"}):
                self.assertEqual(configure_compile_cache(), "/somewhere/else")
                self.assertEqual(jax.config.jax_compilation_cache_dir, before[names[0]])
            env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
            with mock.patch.dict(os.environ, env, clear=True):
                path = configure_compile_cache()
                self.assertEqual(path, configure_compile_cache())
            root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            self.assertEqual(path, os.path.join(root, ".jax_cache"))
            self.assertEqual(jax.config.jax_compilation_cache_dir, path)
        finally:
            for n, v in before.items():
                jax.config.update(n, v)
            compilation_cache.reset_cache()


class TestLongContextGradients(TestCase):
    """Long-context training is first-class: both sequence-parallel
    schedules must be exactly differentiable — grads through the ppermute
    ring / all-to-all reshards equal grads of the dense oracle."""

    def _qkv(self, shape, seed=17):
        import jax.numpy as jnp

        rng = np.random.default_rng(seed)
        mk = lambda: jnp.asarray(rng.normal(size=shape).astype(np.float32))
        return mk(), mk(), mk()

    def test_ring_attention_grads_match_dense(self):
        import jax
        import jax.numpy as jnp

        from heat_tpu.parallel.ring_attention import attention, ring_attention

        comm = ht.get_comm()
        if comm.size == 1:
            pytest.skip("needs multi-device mesh")
        q, k, v = self._qkv((comm.size * 4, 8))
        for causal in (False, True):
            g_ring = jax.grad(
                lambda *a: (ring_attention(*a, comm, causal=causal) ** 2).sum(),
                argnums=(0, 1, 2),
            )(q, k, v)
            g_dense = jax.grad(
                lambda *a: (attention(*a, causal=causal) ** 2).sum(),
                argnums=(0, 1, 2),
            )(q, k, v)
            for got, want, name in zip(g_ring, g_dense, "qkv"):
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4,
                    err_msg=f"causal={causal} d{name}",
                )

    def test_ring_attention_grads_non_divisible(self):
        """Pad-and-trim must be transparent to AD: grads on a sequence
        length that does not divide the mesh still match dense."""
        import jax

        from heat_tpu.parallel.ring_attention import attention, ring_attention

        comm = ht.get_comm()
        if comm.size == 1:
            pytest.skip("needs multi-device mesh")
        q, k, v = self._qkv((comm.size * 3 + 1, 4), seed=18)
        g_ring = jax.grad(
            lambda *a: (ring_attention(*a, comm, causal=True) ** 2).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        g_dense = jax.grad(
            lambda *a: (attention(*a, causal=True) ** 2).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for got, want in zip(g_ring, g_dense):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
            )

    def test_ulysses_grads_match_dense(self):
        import jax
        import jax.numpy as jnp

        from heat_tpu.parallel import ulysses_attention
        from heat_tpu.parallel.ring_attention import attention

        comm = ht.get_comm()
        if comm.size == 1:
            pytest.skip("needs multi-device mesh")
        p = comm.size
        q, k, v = self._qkv((p * 4, p, 8), seed=19)

        def dense(qq, kk, vv):
            import jax.numpy as jnp

            out = attention(
                jnp.moveaxis(qq, 1, 0), jnp.moveaxis(kk, 1, 0), jnp.moveaxis(vv, 1, 0),
                causal=True,
            )
            return (jnp.moveaxis(out, 0, 1) ** 2).sum()

        g_u = jax.grad(
            lambda *a: (ulysses_attention(*a, comm, causal=True) ** 2).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        g_d = jax.grad(dense, argnums=(0, 1, 2))(q, k, v)
        for got, want in zip(g_u, g_d):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
            )

    def test_training_step_through_ring_attention(self):
        """A real optimization loop through the sequence-parallel kernel:
        loss must decrease when fitting a toy target."""
        import jax
        import jax.numpy as jnp

        from heat_tpu.parallel.ring_attention import ring_attention

        comm = ht.get_comm()
        if comm.size == 1:
            pytest.skip("needs multi-device mesh")
        rng = np.random.default_rng(20)
        n, d = comm.size * 4, 8
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        target = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        params = {
            "wq": jnp.eye(d), "wk": jnp.eye(d), "wv": jnp.eye(d),
        }

        def loss_fn(p):
            out = ring_attention(x @ p["wq"], x @ p["wk"], x @ p["wv"], comm)
            return ((out - target) ** 2).mean()

        step = jax.jit(
            lambda p: jax.tree.map(
                lambda w, g: w - 0.1 * g, p, jax.grad(loss_fn)(p)
            )
        )
        l0 = float(loss_fn(params))
        for _ in range(30):
            params = step(params)
        l1 = float(loss_fn(params))
        # random-target attention fit: expect steady descent, not zero
        assert l1 < 0.8 * l0, (l0, l1)
