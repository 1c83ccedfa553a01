"""Sanitation / stride-tricks / devices depth wave (reference
``test_sanitation.py`` / ``test_stride_tricks.py`` / ``test_devices.py``):
the shape/axis/slice sanitizer contracts every op rides on, distribution
matching, and the device selection surface.
"""
from __future__ import annotations

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.core import sanitation, stride_tricks

from tests.base import TestCase


class TestBroadcastShape(TestCase):
    def test_valid_matrix(self):
        cases = [
            ((3, 4), (4,), (3, 4)),
            ((1, 4), (3, 1), (3, 4)),
            ((2, 3, 4), (3, 4), (2, 3, 4)),
            ((5,), (5,), (5,)),
            ((), (3,), (3,)),
            ((1,), (7, 1), (7, 1)),
        ]
        for a, b, want in cases:
            assert stride_tricks.broadcast_shape(a, b) == want, (a, b)
            np.testing.assert_array_equal(
                np.broadcast_shapes(a, b), want
            )  # numpy agrees

    def test_incompatible_raises(self):
        for a, b in [((3,), (4,)), ((2, 3), (3, 2)), ((5, 1, 4), (2, 3))]:
            with pytest.raises(ValueError):
                stride_tricks.broadcast_shape(a, b)

    def test_variadic(self):
        assert stride_tricks.broadcast_shapes((2, 1), (1, 3), (1, 1)) == (2, 3)


class TestSanitizeAxis(TestCase):
    def test_negative_and_positive(self):
        assert stride_tricks.sanitize_axis((3, 4, 5), -1) == 2
        assert stride_tricks.sanitize_axis((3, 4, 5), -3) == 0
        assert stride_tricks.sanitize_axis((3, 4, 5), 1) == 1

    def test_none_passthrough(self):
        assert stride_tricks.sanitize_axis((3, 4), None) is None

    def test_out_of_range(self):
        with pytest.raises(ValueError):
            stride_tricks.sanitize_axis((3, 4), 2)
        with pytest.raises(ValueError):
            stride_tricks.sanitize_axis((3, 4), -3)

    def test_tuple_axes(self):
        got = stride_tricks.sanitize_axis((3, 4, 5), (-1, 0))
        assert tuple(sorted(got)) == (0, 2)

    def test_non_int_rejected(self):
        with pytest.raises(TypeError):
            stride_tricks.sanitize_axis((3, 4), 1.5)


class TestSanitizeShape(TestCase):
    def test_forms(self):
        assert stride_tricks.sanitize_shape(5) == (5,)
        assert stride_tricks.sanitize_shape((2, 3)) == (2, 3)
        assert stride_tricks.sanitize_shape([4, 5]) == (4, 5)
        assert stride_tricks.sanitize_shape(np.int64(3)) == (3,)

    def test_negative_extent_rejected(self):
        with pytest.raises(ValueError):
            stride_tricks.sanitize_shape((2, -3))

    def test_non_integral_rejected(self):
        with pytest.raises(TypeError):
            stride_tricks.sanitize_shape((2.5, 3))


class TestSanitizeSlice(TestCase):
    def test_clamps_and_defaults(self):
        s = stride_tricks.sanitize_slice(slice(None), 10)
        assert (s.start, s.stop, s.step) == (0, 10, 1)
        s = stride_tricks.sanitize_slice(slice(-3, None), 10)
        assert s.start == 7 and s.stop == 10
        s = stride_tricks.sanitize_slice(slice(2, 100), 10)
        assert s.stop in (10, 100)  # clamped or raw, but indexing-safe

    def test_non_slice_rejected(self):
        with pytest.raises(TypeError):
            stride_tricks.sanitize_slice(3, 10)


class TestSanitationHelpers(TestCase):
    def test_sanitize_in_contract(self):
        sanitation.sanitize_in(ht.zeros(3))
        with pytest.raises(TypeError):
            sanitation.sanitize_in(np.zeros(3))

    def test_sanitize_sequence(self):
        assert sanitation.sanitize_sequence((1, 2)) == [1, 2]
        assert sanitation.sanitize_sequence([3]) == [3]
        with pytest.raises(TypeError):
            sanitation.sanitize_sequence(5)

    def test_scalar_to_1d(self):
        s = ht.array(3.0)
        v = sanitation.scalar_to_1d(s)
        assert v.shape == (1,)
        assert float(np.asarray(v.numpy())[0]) == 3.0

    def test_sanitize_out_shape_mismatch(self):
        out = ht.zeros((3, 3), split=0)
        with pytest.raises(ValueError):
            sanitation.sanitize_out(out, (2, 2), 0, out.device)
        with pytest.raises(TypeError):
            sanitation.sanitize_out(np.zeros((2, 2)), (2, 2), 0, None)

    def test_sanitize_distribution_matches_target(self):
        x = ht.arange(12, split=0).reshape((3, 4))
        y = ht.arange(12, split=None).reshape((3, 4))
        res = sanitation.sanitize_distribution(y, target=x)  # single arg -> single result
        assert res.split == x.split
        np.testing.assert_array_equal(res.numpy(), y.numpy())

    def test_sanitize_infinity(self):
        assert sanitation.sanitize_infinity(ht.array(np.array([1, 2], np.int32))) in (
            np.iinfo(np.int32).max,
            np.iinfo(np.int64).max,
        )


class TestDeviceSurface(TestCase):
    def test_cpu_singleton_and_lookup(self):
        d = ht.get_device()
        assert isinstance(d, ht.Device)
        assert ht.sanitize_device(None) is d
        assert ht.sanitize_device("cpu").device_type == "cpu"

    def test_use_device_roundtrip(self):
        before = ht.get_device()
        ht.use_device("cpu")
        assert ht.get_device().device_type == "cpu"
        ht.use_device(before)

    def test_unknown_device_rejected(self):
        with pytest.raises(ValueError):
            ht.sanitize_device("quantum")

    def test_failed_backend_probe_is_an_error_not_cpu(self):
        """A backend probe that raises (JAX fails at start-up when the
        platform it was told to use is missing) propagates — it is never
        read as "no accelerator" — and the next lookup probes again."""
        from unittest import mock

        import jax

        from heat_tpu.core import devices

        with mock.patch.object(devices, "_accel_probed", False), \
                mock.patch.object(devices, "_accel", None):
            with mock.patch.object(jax, "default_backend", side_effect=RuntimeError("no TPU")):
                with pytest.raises(RuntimeError, match="no TPU"):
                    ht.sanitize_device("tpu")
                assert not devices._accel_probed
            with pytest.raises(ValueError):  # probed for real: a CPU backend has no 'tpu'
                ht.sanitize_device("tpu")
            assert devices._accel_probed

    def test_entry_dry_run_refuses_more_devices_than_exist(self):
        """Asking for more devices than are visible is an error: the entry
        point never re-initialises JAX on another platform to find them."""
        import jax

        import __graft_entry__ as entry

        before = jax.default_backend(), len(jax.devices())
        with pytest.raises(RuntimeError, match="device"):
            entry.dryrun_multichip(len(jax.devices()) + 1)
        assert (jax.default_backend(), len(jax.devices())) == before

    def test_device_repr_fields(self):
        d = ht.sanitize_device("cpu")
        assert "cpu" in repr(d)
        assert d.device_id >= 0


class TestMemoryHelpers(TestCase):
    def test_copy_deep(self):
        a = ht.arange(6, split=0)
        b = ht.copy(a)
        b += 1
        np.testing.assert_array_equal(a.numpy(), np.arange(6))
        np.testing.assert_array_equal(b.numpy(), np.arange(6) + 1)

    def test_sanitize_memory_layout_orders(self):
        a = ht.arange(6).reshape((2, 3))
        c = ht.sanitize_memory_layout(a, order="C")
        np.testing.assert_array_equal(c.numpy(), a.numpy())
        with pytest.raises((ValueError, NotImplementedError)):
            ht.sanitize_memory_layout(a, order="Z")
