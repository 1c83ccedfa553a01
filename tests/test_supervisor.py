"""Supervised execution (PR 6): the Supervisor step loop, checkpoint
cadence/retention, fault classification, elastic device-loss recovery,
RECOVERY_STATS accounting, resumable ML state, and the zero-overhead
no-fault contract.

Everything runs on the virtual 8-device CPU mesh (conftest); faults are
simulated (chaos / FaultSchedule / hand-raised exceptions), never real.
"""
import os
import unittest

import jax
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import resilience as rz
from heat_tpu.core import communication as comm_mod
from heat_tpu.resilience.supervisor import RECOVERY_STATS, _classify

from . import _mh_helpers as mh
from .base import TestCase


def nosleep(attempts=3, **kw):
    """Retry policy with simulated sleeps (tests stay fast)."""
    return rz.RetryPolicy(
        max_attempts=attempts, base_delay=0.001, seed=0, sleep=lambda s: None, **kw
    )


def snap():
    return dict(RECOVERY_STATS)


def delta(before):
    return {k: RECOVERY_STATS[k] - before[k] for k in before}


def make_state():
    return {"x": ht.arange(16, dtype=ht.float32, split=0), "n": 0}


def bump(state, data, step):
    """The canonical supervised step: x += 1, n += 1, never done."""
    return {"x": state["x"] + 1.0, "n": state["n"] + 1}, False


def assert_bumped(test, state, n):
    test.assertEqual(state["n"], n)
    np.testing.assert_array_equal(
        state["x"].numpy(), np.arange(16, dtype=np.float32) + n
    )


def step_dirs(d):
    """Sorted step numbers of the committed checkpoints in ``d``."""
    out = []
    for name in sorted(os.listdir(d)):
        if name.startswith("step-") and os.path.exists(
            os.path.join(d, name, "state.json")
        ):
            out.append(int(name.split("-")[1]))
    return out


class TestCheckpointSchedule(TestCase):
    def test_validation(self):
        with self.assertRaises(ValueError):
            rz.CheckpointSchedule()
        with self.assertRaises(ValueError):
            rz.CheckpointSchedule(every_steps=0)
        with self.assertRaises(ValueError):
            rz.CheckpointSchedule(every_steps=1, keep_last=0)
        with self.assertRaises(ValueError):
            rz.CheckpointSchedule(every_seconds=-1.0)

    def test_due_semantics(self):
        s = rz.CheckpointSchedule(every_steps=3)
        self.assertFalse(s.due(step=2, last_step=0, now=0.0, last_time=0.0))
        self.assertTrue(s.due(step=3, last_step=0, now=0.0, last_time=0.0))
        t = rz.CheckpointSchedule(every_seconds=5.0)
        self.assertFalse(t.due(step=99, last_step=0, now=4.0, last_time=0.0))
        self.assertTrue(t.due(step=1, last_step=0, now=5.0, last_time=0.0))
        # OR'd: either interval triggers
        both = rz.CheckpointSchedule(every_steps=10, every_seconds=5.0)
        self.assertTrue(both.due(step=1, last_step=0, now=6.0, last_time=0.0))

    def test_schedule_without_directory_rejected(self):
        with self.assertRaises(ValueError):
            rz.Supervisor(None, rz.CheckpointSchedule(every_steps=1))


class TestPlainLoop(TestCase):
    def test_runs_to_n_steps(self):
        before = snap()
        res = rz.Supervisor().run(bump, make_state(), n_steps=5)
        assert_bumped(self, res.state, 5)
        self.assertEqual(res.steps, 5)
        self.assertEqual(res.recoveries, 0)
        self.assertFalse(res.detached)
        self.assertEqual(delta(before), {k: 0 for k in before})

    def test_done_stops_early(self):
        def step(state, data, i):
            new, _ = bump(state, data, i)
            return new, new["n"] >= 3

        res = rz.Supervisor().run(step, make_state(), n_steps=100)
        self.assertEqual(res.steps, 3)
        assert_bumped(self, res.state, 3)

    def test_state_must_be_dict(self):
        with self.assertRaises(TypeError):
            rz.Supervisor().run(bump, [1, 2, 3])

    def test_supervise_convenience(self):
        res = rz.supervise(bump, make_state(), n_steps=2)
        assert_bumped(self, res.state, 2)

    def test_recovery_stats_exported_at_top_level(self):
        self.assertIs(ht.RECOVERY_STATS, RECOVERY_STATS)


class TestZeroOverhead(TestCase):
    def test_supervised_fit_adds_no_compiles_or_syncs(self):
        """Acceptance: a supervised fit with no faults and no checkpoint
        directory performs 0 extra XLA compiles, and no host sync beyond
        the fetches each path makes by design (counter-asserted, now that
        ``_hooks.fetch`` lets the counter hear them): the plain fit reads
        inertia and n_iter, the supervised one the shift and the iteration
        count of each chunk, then inertia."""
        from heat_tpu.analysis.sanitizer import Region
        from heat_tpu.cluster import KMeans

        rng = np.random.default_rng(0)
        x = ht.array(rng.normal(size=(32, 3)).astype(np.float32), split=0)

        def mk():
            return KMeans(n_clusters=2, init="random", max_iter=6, tol=0.0,
                          random_state=0)

        # warm both code paths so only steady-state cost is measured
        mk().fit(x)
        mk().fit(x, supervisor=rz.Supervisor(), block_iters=2)

        base = Region("kmeans.unsupervised")
        mk().fit(x)
        base.assert_compiles(0)
        self.assertEqual(base.host_syncs, 2)

        sup = Region("kmeans.supervised")
        mk().fit(x, supervisor=rz.Supervisor(), block_iters=2)
        sup.assert_compiles(0)
        self.assertEqual(sup.host_syncs, 2 * 3 + 1)  # 6 iterations in chunks of 2


class TestCheckpointCadence(TestCase):
    def test_every_steps_cadence_exact(self):
        before = snap()
        with mh.TemporaryDirectory() as d:
            sup = rz.Supervisor(
                d, rz.CheckpointSchedule(every_steps=2, keep_last=10),
                retry=nosleep(), checkpoint_retry=nosleep(),
            )
            res = sup.run(bump, make_state(), n_steps=6)
            # baseline at 0, then exactly every 2nd step — no more, no less
            self.assertEqual(step_dirs(d), [0, 2, 4, 6])
        assert_bumped(self, res.state, 6)
        self.assertEqual(delta(before)["checkpoints"], 4)
        self.assertEqual(delta(before)["checkpoint_failures"], 0)

    def test_done_forces_final_commit(self):
        def step(state, data, i):
            new, _ = bump(state, data, i)
            return new, new["n"] >= 3

        before = snap()
        with mh.TemporaryDirectory() as d:
            sup = rz.Supervisor(
                d, rz.CheckpointSchedule(every_steps=10, keep_last=10),
                retry=nosleep(), checkpoint_retry=nosleep(),
            )
            sup.run(step, make_state(), n_steps=100)
            self.assertEqual(step_dirs(d), [0, 3])
        self.assertEqual(delta(before)["checkpoints"], 2)

    def test_every_seconds_only(self):
        # an enormous time interval: baseline + the forced final commit
        def step(state, data, i):
            new, _ = bump(state, data, i)
            return new, new["n"] >= 4

        with mh.TemporaryDirectory() as d:
            sup = rz.Supervisor(
                d, rz.CheckpointSchedule(every_seconds=1e9, keep_last=10),
                retry=nosleep(), checkpoint_retry=nosleep(),
            )
            sup.run(step, make_state())
            self.assertEqual(step_dirs(d), [0, 4])

    def test_keep_last_retention_and_gc_counter(self):
        before = snap()
        with mh.TemporaryDirectory() as d:
            sup = rz.Supervisor(
                d, rz.CheckpointSchedule(every_steps=1, keep_last=2),
                retry=nosleep(), checkpoint_retry=nosleep(),
            )
            sup.run(bump, make_state(), n_steps=5)
            self.assertEqual(step_dirs(d), [4, 5])
        dd = delta(before)
        self.assertEqual(dd["checkpoints"], 6)  # 0..5
        self.assertEqual(dd["gc_removed"], 4)

    def test_checkpointed_state_restorable(self):
        with mh.TemporaryDirectory() as d:
            sup = rz.Supervisor(d, retry=nosleep(), checkpoint_retry=nosleep())
            sup.run(bump, make_state(), n_steps=3)
            loaded = sup._restore_latest()
            self.assertIsNotNone(loaded)
            state, step = loaded
            self.assertEqual(step, 3)
            assert_bumped(self, state, 3)


class TestResumeAndOwnership(TestCase):
    def test_resume_adopts_previous_checkpoint(self):
        calls = []

        def step(state, data, i):
            calls.append(i)
            return bump(state, data, i)

        with mh.TemporaryDirectory() as d:
            sup = rz.Supervisor(d, retry=nosleep(), checkpoint_retry=nosleep())
            sup.run(step, make_state(), n_steps=3)
            calls.clear()
            # same n_steps: the resumed run has nothing left to do
            res = rz.Supervisor(d, retry=nosleep(), checkpoint_retry=nosleep()).run(
                step, make_state(), n_steps=3, resume=True
            )
            self.assertEqual(calls, [])
            self.assertEqual(res.steps, 3)
            assert_bumped(self, res.state, 3)
            # a larger budget continues from the adopted step
            res = rz.Supervisor(d, retry=nosleep(), checkpoint_retry=nosleep()).run(
                step, make_state(), n_steps=5, resume=True
            )
            self.assertEqual(calls, [3, 4])
            assert_bumped(self, res.state, 5)

    def test_fresh_run_purges_stale_checkpoints(self):
        with mh.TemporaryDirectory() as d:
            sup = rz.Supervisor(d, retry=nosleep(), checkpoint_retry=nosleep())
            sup.run(bump, make_state(), n_steps=4)
            self.assertIn(4, step_dirs(d))
            res = rz.Supervisor(d, retry=nosleep(), checkpoint_retry=nosleep()).run(
                bump, make_state(), n_steps=2
            )
            assert_bumped(self, res.state, 2)  # not 6: old state never adopted
            self.assertEqual(step_dirs(d), [0, 1, 2])

    def test_fresh_run_restores_its_own_baseline_not_stale_state(self):
        """A restore-class fault in run 2 must rewind to run 2's own
        checkpoints even though run 1 left newer-looking state behind."""
        with mh.TemporaryDirectory() as d:
            rz.Supervisor(d, retry=nosleep(), checkpoint_retry=nosleep()).run(
                bump, make_state(), n_steps=6
            )
            fired = []

            def step(state, data, i):
                if i == 1 and not fired:
                    fired.append(i)
                    raise rz.DivergenceError("simulated silent divergence")
                return bump(state, data, i)

            before = snap()
            res = rz.Supervisor(d, retry=nosleep(), checkpoint_retry=nosleep()).run(
                step, make_state(), n_steps=3
            )
            assert_bumped(self, res.state, 3)
            self.assertEqual(delta(before)["restores"], 1)


class TestFaultClassification(TestCase):
    def test_classify_table(self):
        self.assertEqual(_classify(OSError("io")), "retry")
        self.assertEqual(_classify(TimeoutError("t")), "retry")
        self.assertEqual(_classify(rz.DivergenceError("d")), "restore")
        # CollectiveTimeout subclasses TimeoutError but must NOT be
        # retried in place: suspect state -> restore
        self.assertEqual(_classify(rz.CollectiveTimeout("c", 1.0, 0.5)), "restore")
        self.assertEqual(_classify(RuntimeError("xla")), "probe")
        self.assertEqual(_classify(rz.NoHealthyDevicesError(8)), "fatal")
        self.assertEqual(_classify(ValueError("v")), "fatal")

    def test_transient_errors_retried(self):
        failures = []

        def step(state, data, i):
            if i == 1 and len(failures) < 2:
                failures.append(i)
                raise OSError("transient I/O flake")
            return bump(state, data, i)

        before = snap()
        res = rz.Supervisor(retry=nosleep(4)).run(step, make_state(), n_steps=3)
        assert_bumped(self, res.state, 3)
        dd = delta(before)
        self.assertEqual(dd["detections"], 2)
        self.assertEqual(dd["retries"], 2)
        self.assertEqual(dd["restores"], 0)
        self.assertEqual(res.recoveries, 2)
        self.assertGreater(dd["recovery_seconds_total"], 0.0)

    def test_divergence_restores_last_checkpoint(self):
        fired = []

        def step(state, data, i):
            if i == 2 and not fired:
                fired.append(i)
                raise rz.DivergenceError("replicas disagree")
            return bump(state, data, i)

        before = snap()
        with mh.TemporaryDirectory() as d:
            res = rz.Supervisor(d, retry=nosleep(), checkpoint_retry=nosleep()).run(
                step, make_state(), n_steps=4
            )
        assert_bumped(self, res.state, 4)
        self.assertEqual(delta(before)["restores"], 1)

    def test_restore_without_directory_is_supervisor_error(self):
        def step(state, data, i):
            raise rz.DivergenceError("no checkpoint to rewind to")

        with self.assertRaises(rz.SupervisorError):
            rz.Supervisor(retry=nosleep()).run(step, make_state(), n_steps=2)

    def test_runtime_error_on_healthy_mesh_reraised(self):
        def step(state, data, i):
            raise RuntimeError("not actually a device failure")

        rz.clear_unhealthy()
        try:
            with self.assertRaises(RuntimeError) as cm:
                rz.Supervisor(retry=nosleep()).run(step, make_state(), n_steps=2)
            self.assertIn("not actually", str(cm.exception))
        finally:
            rz.clear_unhealthy()

    def test_fatal_errors_propagate_unwrapped(self):
        def step(state, data, i):
            raise rz.NoHealthyDevicesError(8)

        with self.assertRaises(rz.NoHealthyDevicesError):
            rz.Supervisor(retry=nosleep()).run(step, make_state(), n_steps=2)

    def test_recovery_budget_exhaustion(self):
        def step(state, data, i):
            raise OSError("permanently broken")

        with self.assertRaises(rz.SupervisorError) as cm:
            rz.Supervisor(retry=nosleep(4), max_recoveries=1).run(
                step, make_state(), n_steps=2
            )
        self.assertIn("recovery budget exhausted", str(cm.exception))

    def test_retry_exhaustion_escalates_to_restore_then_probe(self):
        """A step that keeps failing walks the whole ladder: retry budget,
        then bounded restores, then probe — which, finding the mesh
        healthy, surfaces the original error."""

        def step(state, data, i):
            raise OSError("stuck")

        rz.clear_unhealthy()
        before = snap()
        try:
            with mh.TemporaryDirectory() as d:
                with self.assertRaises(OSError):
                    rz.Supervisor(
                        d, retry=nosleep(2), checkpoint_retry=nosleep(),
                        max_restores_per_step=2,
                    ).run(step, make_state(), n_steps=2)
        finally:
            rz.clear_unhealthy()
        dd = delta(before)
        self.assertEqual(dd["retries"], 1)   # nosleep(2) allows one retry
        self.assertEqual(dd["restores"], 2)  # then max_restores_per_step
        self.assertEqual(dd["shrinks"], 0)   # probe found nothing to shrink


class TestDeviceLossRecovery(TestCase):
    def _run_with_device_loss(self, directory):
        sup = rz.Supervisor(
            directory, retry=nosleep(), checkpoint_retry=nosleep()
        ) if directory else rz.Supervisor(retry=nosleep())
        with rz.FaultSchedule(events=[("supervisor.step", 3, "device_loss")]) as sched:
            res = sup.run(bump, make_state(), n_steps=5)
        self.assertEqual(sched.pending(), [])
        return res

    def test_shrink_restores_checkpoint_onto_surviving_mesh(self):
        orig = comm_mod.sanitize_comm(None)
        before = snap()
        try:
            with mh.TemporaryDirectory() as d:
                res = self._run_with_device_loss(d)
            assert_bumped(self, res.state, 5)
            self.assertEqual(res.comm.size, orig.size - 1)
            self.assertEqual(res.state["x"].comm.size, orig.size - 1)
            dd = delta(before)
            self.assertEqual(dd["shrinks"], 1)
            self.assertGreaterEqual(dd["checkpoints"], 5)
        finally:
            comm_mod.use_comm(orig)
            rz.clear_unhealthy()

    def test_shrink_moves_live_state_without_checkpoints(self):
        orig = comm_mod.sanitize_comm(None)
        before = snap()
        try:
            res = self._run_with_device_loss(None)
            assert_bumped(self, res.state, 5)
            self.assertEqual(res.comm.size, orig.size - 1)
            dd = delta(before)
            self.assertEqual(dd["shrinks"], 1)
            self.assertEqual(dd["restores"], 0)
            self.assertEqual(dd["checkpoints"], 0)
        finally:
            comm_mod.use_comm(orig)
            rz.clear_unhealthy()


class TestRestoreFallback(TestCase):
    def test_corrupt_newest_checkpoint_falls_back_to_older(self):
        fired = []

        def corrupt_newest(d):
            newest = f"step-{max(step_dirs(d)):08d}"
            for root, _, files in os.walk(os.path.join(d, newest)):
                for f in files:
                    if f.startswith("shard_"):
                        p = os.path.join(root, f)
                        with open(p, "r+b") as fh:
                            fh.seek(-1, os.SEEK_END)
                            b = fh.read(1)
                            fh.seek(-1, os.SEEK_END)
                            fh.write(bytes([b[0] ^ 0xFF]))

        with mh.TemporaryDirectory() as d:
            def step(state, data, i):
                if i == 3 and not fired:
                    fired.append(i)
                    # two ranks XOR-ing the same byte would restore it
                    mh.on_pid0(lambda: corrupt_newest(d))  # newest commit is step-3
                    raise rz.DivergenceError("suspect state")
                return bump(state, data, i)

            before = snap()
            res = rz.Supervisor(
                d, rz.CheckpointSchedule(every_steps=1, keep_last=5),
                retry=nosleep(), checkpoint_retry=nosleep(),
            ).run(step, make_state(), n_steps=5)
        assert_bumped(self, res.state, 5)
        # one recovery (checksum verification rejected step-3, the restore
        # silently fell back to step-2 and re-ran from there)
        self.assertEqual(delta(before)["restores"], 1)

    def test_unreadable_manifest_skips_candidate_on_every_rank(self):
        """An io_error reading the NEWEST candidate's state manifest: the
        per-candidate replicated verdict makes every rank skip it together
        (a rank that silently fell back alone would desert the
        load_checkpoint collectives and hang the group) and the restore
        falls back to the older commit."""
        with mh.TemporaryDirectory() as d:
            armed = []

            def step(state, data, i):
                if i == 3 and not armed:
                    armed.append(i)
                    raise rz.DivergenceError("suspect state")
                return bump(state, data, i)

            before = snap()
            sched = rz.FaultSchedule(
                events=[("supervisor.restore_manifest", 1, "io_error")], seed=0
            )
            with sched:
                res = rz.Supervisor(
                    d, rz.CheckpointSchedule(every_steps=1, keep_last=5),
                    retry=nosleep(), checkpoint_retry=nosleep(),
                ).run(step, make_state(), n_steps=5)
            self.assertEqual(sched.pending(), [])
        assert_bumped(self, res.state, 5)
        # the newest (step-3) manifest was unreadable; the restore landed
        # on step-2 and re-ran from there — one recovery, not a hang
        self.assertEqual(delta(before)["restores"], 1)


class TestRetryPolicyMaxElapsed(TestCase):
    def test_budget_cuts_schedule_short(self):
        t = {"now": 0.0}
        sleeps = []

        def fake_sleep(s):
            sleeps.append(s)
            t["now"] += s

        pol = rz.RetryPolicy(
            max_attempts=10, base_delay=1.0, multiplier=2.0, jitter=0.0,
            seed=0, max_elapsed=4.0, clock=lambda: t["now"], sleep=fake_sleep,
        )
        calls = []

        def boom():
            calls.append(1)
            raise OSError("flaky")

        with self.assertRaises(rz.RetryError) as cm:
            pol.call(boom, label="op")
        # delays 1, 2, 4, ...: after sleeping 1+2=3s the next 4s sleep
        # would pass the 4s budget, so the policy gives up at attempt 3
        self.assertEqual(len(calls), 3)
        self.assertEqual(sleeps, [1.0, 2.0])
        self.assertIn("max_elapsed", str(cm.exception))

    def test_unbounded_when_none(self):
        pol = nosleep(3)
        calls = []

        def boom():
            calls.append(1)
            raise OSError("flaky")

        with self.assertRaises(rz.RetryError) as cm:
            pol.call(boom)
        self.assertEqual(len(calls), 3)
        self.assertNotIn("max_elapsed", str(cm.exception))

    def test_zero_budget_means_no_retry(self):
        pol = rz.RetryPolicy(
            max_attempts=5, base_delay=0.5, jitter=0.0, seed=0,
            max_elapsed=0.0, sleep=lambda s: None,
        )
        calls = []

        def boom():
            calls.append(1)
            raise OSError("flaky")

        with self.assertRaises(rz.RetryError):
            pol.call(boom)
        self.assertEqual(len(calls), 1)

    def test_success_within_budget_unaffected(self):
        state = {"n": 0}

        def flaky():
            state["n"] += 1
            if state["n"] < 2:
                raise OSError("once")
            return "ok"

        pol = rz.RetryPolicy(
            max_attempts=5, base_delay=0.001, seed=0, max_elapsed=60.0,
            sleep=lambda s: None,
        )
        self.assertEqual(pol.call(flaky), "ok")

    def test_supervisor_honors_retry_budget(self):
        """With a zero wall-clock budget the supervisor never sleeps on a
        transient error — it escalates straight to a checkpoint restore."""
        fired = []

        def step(state, data, i):
            if i == 1 and not fired:
                fired.append(i)
                raise OSError("transient, but the budget is zero")
            return bump(state, data, i)

        before = snap()
        with mh.TemporaryDirectory() as d:
            res = rz.Supervisor(
                d,
                retry=rz.RetryPolicy(
                    max_attempts=3, base_delay=0.5, jitter=0.0, seed=0,
                    max_elapsed=0.0, sleep=lambda s: None,
                ),
                checkpoint_retry=nosleep(),
            ).run(step, make_state(), n_steps=3)
        assert_bumped(self, res.state, 3)
        dd = delta(before)
        self.assertEqual(dd["retries"], 0)
        self.assertEqual(dd["restores"], 1)


class TestShardGCAcrossWorldSizes(TestCase):
    def test_resave_smaller_world_removes_stale_shards(self):
        """ws-8 -> ws-2 re-save into the same directory: the new manifest
        must name every on-disk shard (no stale ws-8 files that a later
        save at another geometry could alias)."""
        x8 = ht.arange(24, dtype=ht.float32, split=0)
        comm2 = ht.MeshCommunication(devices=mh.submesh(2))
        y2 = ht.arange(10, dtype=ht.float32, split=0, comm=comm2) + 100.0
        with mh.TemporaryDirectory() as d:
            rz.save_checkpoint(x8, d)
            self.assertEqual(
                len([f for f in os.listdir(d) if f.startswith("shard_")]), 8
            )
            rz.save_checkpoint(y2, d)
            named = {e["file"] for e in rz.read_manifest(d)["shards"]}
            on_disk = {f for f in os.listdir(d) if f.startswith("shard_")}
            self.assertEqual(on_disk, named)
            z = rz.load_checkpoint(d)
            np.testing.assert_array_equal(z.numpy(), y2.numpy())

    def test_resave_larger_world_roundtrips(self):
        comm2 = ht.MeshCommunication(devices=mh.submesh(2))
        x2 = ht.arange(10, dtype=ht.float32, split=0, comm=comm2)
        y8 = ht.arange(24, dtype=ht.float32, split=0) * 3.0
        with mh.TemporaryDirectory() as d:
            rz.save_checkpoint(x2, d)
            rz.save_checkpoint(y8, d)
            named = {e["file"] for e in rz.read_manifest(d)["shards"]}
            on_disk = {f for f in os.listdir(d) if f.startswith("shard_")}
            self.assertEqual(on_disk, named)
            z = rz.load_checkpoint(d)
            np.testing.assert_array_equal(z.numpy(), y8.numpy())


class TestEstimatorStateDicts(TestCase):
    def _blobs(self, n=40, f=3, k=2, seed=3):
        rng = np.random.default_rng(seed)
        c = rng.normal(size=(k, f)) * 4.0
        pts = c[rng.integers(0, k, size=n)] + rng.normal(size=(n, f)) * 0.2
        return ht.array(pts.astype(np.float32), split=0)

    def test_kmeans_state_dict_roundtrip(self):
        from heat_tpu.cluster import KMeans

        x = self._blobs()
        m = KMeans(n_clusters=2, init="random", max_iter=10, random_state=0).fit(x)
        m2 = KMeans().load_state_dict(m.state_dict())
        np.testing.assert_array_equal(
            m2.cluster_centers_.numpy(), m.cluster_centers_.numpy()
        )
        np.testing.assert_array_equal(m2.labels_.numpy(), m.labels_.numpy())
        self.assertEqual(m2.labels_.split, m.labels_.split)
        self.assertEqual(m2.n_iter_, m.n_iter_)
        np.testing.assert_array_equal(m2.predict(x).numpy(), m.predict(x).numpy())

    def test_kmedians_supervised_matches_unsupervised(self):
        from heat_tpu.cluster import KMedians

        x = self._blobs(seed=4)

        def mk():
            return KMedians(n_clusters=2, init="random", max_iter=10,
                            tol=0.0, random_state=1)

        a = mk().fit(x)
        b = mk().fit(x, supervisor=rz.Supervisor(retry=nosleep()), block_iters=3)
        np.testing.assert_array_equal(
            b.cluster_centers_.numpy(), a.cluster_centers_.numpy()
        )
        np.testing.assert_array_equal(b.labels_.numpy(), a.labels_.numpy())
        self.assertEqual(b.n_iter_, a.n_iter_)

    def test_kmedoids_supervised_matches_unsupervised(self):
        from heat_tpu.cluster import KMedoids

        x = self._blobs(seed=5)

        def mk():
            return KMedoids(n_clusters=2, init="random", max_iter=10, random_state=2)

        a = mk().fit(x)
        b = mk().fit(x, supervisor=rz.Supervisor(retry=nosleep()), block_iters=3)
        np.testing.assert_array_equal(
            b.cluster_centers_.numpy(), a.cluster_centers_.numpy()
        )
        np.testing.assert_array_equal(b.labels_.numpy(), a.labels_.numpy())
        self.assertEqual(b.n_iter_, a.n_iter_)

    def test_lasso_state_dict_roundtrip(self):
        from heat_tpu.regression import Lasso

        rng = np.random.default_rng(6)
        X = rng.normal(size=(40, 5))
        X[:, 0] = 1.0
        yv = X @ np.array([0.5, 1.0, -1.0, 0.0, 0.2]) + rng.normal(size=40) * 0.01
        x = ht.array(X.astype(np.float32), split=0)
        y = ht.array(yv.astype(np.float32).reshape(-1, 1), split=0)
        m = Lasso(lam=0.01, max_iter=20).fit(x, y)
        m2 = Lasso().load_state_dict(m.state_dict())
        np.testing.assert_array_equal(m2.theta.numpy(), m.theta.numpy())
        self.assertEqual(m2.n_iter, m.n_iter)
        np.testing.assert_allclose(
            m2.predict(x).numpy(), m.predict(x).numpy(), rtol=1e-6
        )


class TestNNStateDicts(TestCase):
    def _fit_fixture(self, seed=0):
        import flax.linen as fnn
        import jax.numpy as jnp
        import optax

        class Model(fnn.Module):
            @fnn.compact
            def __call__(self, x):
                return fnn.Dense(1)(x)

        rng = np.random.default_rng(7)
        X = ht.array(rng.normal(size=(32, 4)).astype(np.float32), split=0)
        y = ht.array(rng.normal(size=(32, 1)).astype(np.float32), split=0)

        def loss_fn(logits, labels):
            return jnp.mean((logits - labels) ** 2)

        dp = ht.nn.DataParallel(Model(), optimizer=optax.sgd(0.05), seed=seed)
        dp.init(X)
        return dp, loss_fn, X, y

    def _params_flat(self, dp):
        return {
            k: np.asarray(v)
            for k, v in dp.state_dict().items()
            if isinstance(v, np.ndarray)
        }

    def test_state_dict_roundtrip(self):
        dp, loss_fn, X, y = self._fit_fixture()
        for _ in range(3):
            dp.train_step(loss_fn, X, y)
        sd = dp.state_dict()
        dp2, loss_fn2, _, _ = self._fit_fixture()
        dp2.load_state_dict(sd)
        for k, v in self._params_flat(dp).items():
            np.testing.assert_array_equal(self._params_flat(dp2)[k], v, err_msg=k)
        # both continue identically from the restored state
        a = float(dp.train_step(loss_fn, X, y))
        b = float(dp2.train_step(loss_fn2, X, y))
        self.assertEqual(a, b)

    def test_supervised_fit_matches_plain_fit(self):
        dp, loss_fn, X, y = self._fit_fixture()
        dp.fit(loss_fn, X, y, n_steps=6)
        dp2, loss_fn2, _, _ = self._fit_fixture()
        dp2.fit(loss_fn2, X, y, n_steps=6,
                supervisor=rz.Supervisor(retry=nosleep()), steps_per_block=2)
        for k, v in self._params_flat(dp).items():
            np.testing.assert_allclose(
                self._params_flat(dp2)[k], v, rtol=1e-6, atol=1e-7, err_msg=k
            )

    def test_supervised_fit_recovers_from_divergence(self):
        dp, loss_fn, X, y = self._fit_fixture()
        dp.fit(loss_fn, X, y, n_steps=6)
        dp2, loss_fn2, _, _ = self._fit_fixture()
        with mh.TemporaryDirectory() as d:
            with rz.FaultSchedule(
                events=[("supervisor.step", 2, "io_error")]
            ) as sched:
                dp2.fit(loss_fn2, X, y, n_steps=6,
                        supervisor=rz.Supervisor(
                            d, retry=nosleep(), checkpoint_retry=nosleep()
                        ),
                        steps_per_block=2)
            self.assertEqual(sched.pending(), [])
        for k, v in self._params_flat(dp).items():
            np.testing.assert_allclose(
                self._params_flat(dp2)[k], v, rtol=1e-6, atol=1e-7, err_msg=k
            )

    def test_daso_state_dict_roundtrip(self):
        import jax.numpy as jnp
        import optax

        from heat_tpu.parallel import make_hierarchical_mesh

        if len(jax.devices()) < 4 or len(jax.devices()) % 2:
            pytest.skip("needs an even device count >= 4")
        mesh = make_hierarchical_mesh(n_slow=2)
        rng = np.random.default_rng(8)
        X = jnp.asarray(rng.normal(size=(32, 4)).astype(np.float32))
        y = jnp.asarray(rng.normal(size=(32, 1)).astype(np.float32))

        def loss_and_grad(p, xb, yb):
            return jax.value_and_grad(lambda p: jnp.mean((xb @ p["w"] - yb) ** 2))(p)

        def fresh():
            daso = ht.optim.DASO(
                optax.sgd(0.1), total_epochs=4, warmup_epochs=0, cooldown_epochs=0
            )
            params = daso.init({"w": jnp.zeros((4, 1))}, mesh)
            return daso, params

        def fetch(a):
            # at ws>1 the params span non-addressable devices; each
            # process checks its own slow-group's replicas (identical
            # shardings on both sides, so device order lines up)
            if a.is_fully_addressable:
                return np.asarray(a)
            shards = sorted(a.addressable_shards, key=lambda s: s.device.id)
            return np.concatenate([np.asarray(s.data).ravel() for s in shards])

        daso, params = fresh()
        for _ in range(3):
            params, _ = daso.step(loss_and_grad, params, X, y)
        sd = daso.state_dict(params)

        daso2, params2 = fresh()
        params2 = daso2.load_state_dict(sd, params=params2)
        np.testing.assert_allclose(
            fetch(params2["w"]), fetch(params["w"]), rtol=1e-6
        )
        self.assertEqual(daso2._batch, daso._batch)
        self.assertEqual(daso2.epoch, daso.epoch)
        # both continue identically from the restored state
        params, la = daso.step(loss_and_grad, params, X, y)
        params2, lb = daso2.step(loss_and_grad, params2, X, y)
        np.testing.assert_allclose(
            fetch(params2["w"]), fetch(params["w"]), rtol=1e-6
        )
        np.testing.assert_allclose(float(la), float(lb), rtol=1e-6)


if __name__ == "__main__":
    unittest.main()
