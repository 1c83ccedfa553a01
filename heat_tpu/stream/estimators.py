"""Single-pass streaming estimators: moments, covariance, histogram.

Each estimator folds chunks into a tiny replicated state via the
numerically stable pairwise merge formulas (Chan et al. / Welford):
merging a chunk of ``n_b`` rows into ``n_a`` accumulated rows uses

.. math::

    \\delta = \\bar{x}_b - \\bar{x}_a,\\quad
    \\bar{x} = \\bar{x}_a + \\delta\\,n_b/n,\\quad
    M_2 = M_{2,a} + M_{2,b} + \\delta^2\\,n_a n_b / n

(and the matrix analogue with ``outer(δ, δ)`` for the covariance
co-moment). Results match the in-memory ``ht.mean/var/cov/histogram`` up
to float32 re-association (the oracle sweeps in ``tests/test_stream.py``
assert it at rtol≈1e-4).

Compile-once discipline: ONE jitted update program per estimator kind
(histogram: per bin count) lives in a bounded ``ExecutableCache``; jax's
own executable cache then specializes per chunk geometry, of which a
``ChunkIterator`` pass produces at most two (full + tail) — so a warm
chunk loop is 0 traces / 0 compiles per chunk (Region-asserted in
tests). Chunks arrive as padded device buffers; every program masks rows
``>= n_valid`` so buffer tail padding never contaminates a statistic.

``merge()`` combines two estimators pairwise (tree reductions over
shards of a dataset processed by different pipelines).

Multi-controller: each fold is pinned with ``collective_lockstep`` —
two independent folds (moments and cov of the same chunk) otherwise
execute concurrently on the runtime thread pool and interleave their
collectives differently per process, corrupting or deadlocking the
rendezvous. Single-process dispatch stays fully async.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..core._cache import ExecutableCache
from ..core.communication import collective_lockstep, tree_merge
from ..core.dndarray import DNDarray

__all__ = ["StreamingMoments", "StreamingCov", "StreamingHistogram"]

# one entry per estimator kind (histogram: per bin count) — the chunk
# loop re-dispatches the same executable every chunk
_PROGRAMS = ExecutableCache(maxsize=64)


# -- pure cross-process state combines (the ``tree_merge`` operands) -------
#
# Module-level (stable identity keys the butterfly program cache) and
# jax-traceable: counts travel as an int32 leaf so huge row totals stay
# exact, and are cast to the statistic dtype only inside the arithmetic.

def _combine_moments(a, b):
    na, mean_a, m2a = a
    nb, mean_b, m2b = b
    naf, nbf = na.astype(mean_a.dtype), nb.astype(mean_a.dtype)
    nf = jnp.maximum(naf + nbf, 1.0)
    delta = mean_b - mean_a
    m2 = m2a + m2b + delta * delta * (naf * nbf / nf)
    mean = mean_a + delta * (nbf / nf)
    return na + nb, mean, m2


def _combine_cov(a, b):
    na, mean_a, ca = a
    nb, mean_b, cb = b
    naf, nbf = na.astype(mean_a.dtype), nb.astype(mean_a.dtype)
    nf = jnp.maximum(naf + nbf, 1.0)
    delta = mean_b - mean_a
    c = ca + cb + jnp.outer(delta, delta) * (naf * nbf / nf)
    mean = mean_a + delta * (nbf / nf)
    return na + nb, mean, c


def _combine_hist(a, b):
    return a[0] + b[0], a[1] + b[1]


def _mask(xa: jnp.ndarray, n_valid):
    """(zeroed-padding buffer, per-row validity, valid count as dtype)."""
    valid = jnp.arange(xa.shape[0]) < n_valid
    xs = jnp.where(valid[:, None], xa, 0.0)
    return xs, valid, n_valid.astype(xa.dtype)


def _moments_program(mode: str = "xla", mesh=None):
    """Per-chunk moments fold, keyed by dispatch mode: the chunk's
    (count, mean, M2) come from ``kernels.chunk_moments`` (shifted
    one-pass sums — ONE read of the chunk, where the old fold's
    ``mean_b`` → ``xa - mean_b`` chain was two) or from the pallas kernel
    (``moments_local`` / ``moments_sharded``), then Chan-merge into the
    carried state via ``kernels.merge_moments``."""
    key = ("moments", mode, mesh)
    prog = _PROGRAMS.get(key)
    if prog is None:
        from ..core.kernels import (
            chunk_moments,
            merge_moments,
            moments_local,
            moments_sharded,
        )

        def step(xa, n_valid, count, mean, m2):
            if mode in ("pallas", "interpret"):
                interp = mode != "pallas"
                if mesh is not None:
                    nb, mean_b, m2_b = moments_sharded(xa, n_valid, mesh, interpret=interp)
                else:
                    nb, mean_b, m2_b = moments_local(xa, n_valid, interpret=interp)
            else:
                nb, mean_b, m2_b = chunk_moments(xa, n_valid)
            _, new_mean, new_m2 = merge_moments(count, mean, m2, nb, mean_b, m2_b)
            return new_mean, new_m2

        _PROGRAMS[key] = jax.jit(step)
        prog = _PROGRAMS[key]
    return prog


def _moments_choice(chunk: DNDarray, xa) -> tuple:
    """(mode, mesh) for one chunk's moments fold at the call boundary —
    the same gate as the statistics panel: pallas needs a local buffer or
    even split-0 shards, of rows no wider than ``moments.kernel_fits``
    admits; anything else folds through the one-pass XLA twin."""
    from ..core.kernels import dispatch_mode
    from ..core.kernels.moments import kernel_fits

    mode = dispatch_mode("moments_onepass")
    mesh = None
    if mode in ("pallas", "interpret"):
        p = chunk.comm.size
        if not kernel_fits(xa.shape[1]):
            mode = "xla"
        elif chunk.split == 0 and p > 1:
            if xa.shape[0] % p == 0:
                mesh = chunk.comm.mesh
            else:
                mode = "xla"
        elif chunk.split is not None and p > 1:
            mode = "xla"
    return mode, mesh


def _cov_program():
    prog = _PROGRAMS.get("cov")
    if prog is None:

        def step(xa, n_valid, count, mean, comoment):
            xs, valid, nb = _mask(xa, n_valid)
            mean_b = jnp.sum(xs, axis=0) / jnp.maximum(nb, 1.0)
            d = jnp.where(valid[:, None], xa - mean_b[None, :], 0.0)
            c_b = d.T @ d  # chunk co-moment: one MXU matmul, psum over ICI
            n = count + nb
            delta = mean_b - mean
            new_mean = mean + delta * (nb / jnp.maximum(n, 1.0))
            new_c = comoment + c_b + jnp.outer(delta, delta) * (
                count * nb / jnp.maximum(n, 1.0)
            )
            return new_mean, new_c

        _PROGRAMS["cov"] = jax.jit(step)
        prog = _PROGRAMS["cov"]
    return prog


def _hist_program(bins: int):
    key = ("hist", bins)
    prog = _PROGRAMS.get(key)
    if prog is None:

        def step(xa, n_valid, lo, hi, counts, bins):
            flat = xa.reshape(xa.shape[0], -1)
            valid = jnp.arange(flat.shape[0]) < n_valid
            v = flat.ravel()
            w = jnp.broadcast_to(valid[:, None], flat.shape).ravel()
            # numpy histogram semantics: left-closed uniform bins over
            # [lo, hi], right edge closed on the last bin only
            edges = jnp.linspace(lo, hi, bins + 1)
            idx = jnp.searchsorted(edges, v, side="right") - 1
            idx = jnp.where(v == edges[-1], bins - 1, idx)
            keep = w & (idx >= 0) & (idx < bins)
            add = jnp.where(keep, 1.0, 0.0).astype(counts.dtype)
            return counts.at[jnp.clip(idx, 0, bins - 1)].add(add)

        _PROGRAMS[key] = jax.jit(partial(step, bins=bins))
        prog = _PROGRAMS[key]
    return prog


class _StreamingBase:
    """Chunk capture shared by the estimators: first chunk pins the mesh
    placement for the finalized DNDarrays; every chunk contributes its
    padded buffer + logical row count."""

    def __init__(self):
        self._n = 0
        self._device = None
        self._comm = None

    @property
    def n(self) -> int:
        """Rows folded in so far."""
        return self._n

    def _capture(self, chunk: DNDarray):
        if not isinstance(chunk, DNDarray):
            raise TypeError(f"chunks must be DNDarrays, got {type(chunk)}")
        if self._comm is None:
            self._device = chunk.device
            self._comm = chunk.comm
        xa = chunk.larray
        xa = xa.astype(jnp.promote_types(xa.dtype, jnp.float32))
        if xa.ndim == 1:
            xa = xa[:, None]
        return xa, jnp.int32(chunk.gshape[0])

    def _require_data(self):
        if self._n == 0:
            raise RuntimeError("no chunks folded in yet (call update first)")

    def _wrap(self, arr) -> DNDarray:
        return DNDarray(arr, split=None, device=self._device, comm=self._comm)

    # -- cross-process merge (ROADMAP item 1 leftover) --------------------
    _COMBINE = None  # subclass: pure (tree_a, tree_b) -> tree on _state()

    def _state(self):  # subclass: pytree of jnp leaves (n travels int32)
        raise NotImplementedError

    def _set_state(self, state):  # subclass: inverse of _state()
        raise NotImplementedError

    def merge_processes(self):
        """Fold every process's state into the identical global state on
        every process via :func:`~heat_tpu.core.communication.tree_merge`
        — ``ceil(log2 P)`` ppermute rounds instead of allgathering P
        states. Collective: every process must call it after folding its
        own chunks (each must have folded at least one chunk, so state
        shapes agree). A single-process world is a no-op."""
        self._require_data()
        self._set_state(tree_merge(self._state(), type(self)._COMBINE))
        return self


class StreamingMoments(_StreamingBase):
    """Single-pass per-column mean/var/std (axis-0, like
    ``ht.mean(x, axis=0)`` / ``ht.var(x, axis=0, ddof=ddof)``)."""

    def __init__(self, ddof: int = 0):
        super().__init__()
        self.ddof = int(ddof)
        self._mean = None
        self._m2 = None

    def update(self, chunk: DNDarray) -> "StreamingMoments":
        xa, nv = self._capture(chunk)
        if self._mean is None:
            self._mean = jnp.zeros((xa.shape[1],), xa.dtype)
            self._m2 = jnp.zeros((xa.shape[1],), xa.dtype)
        from ..core.kernels import record_dispatch

        mode, mesh = _moments_choice(chunk, xa)
        record_dispatch("moments_onepass", mode)  # once per chunk fold
        self._mean, self._m2 = collective_lockstep(
            _moments_program(mode, mesh)(
                xa, nv, jnp.asarray(float(self._n), xa.dtype), self._mean, self._m2
            )
        )
        self._n += int(chunk.gshape[0])
        return self

    def merge(self, other: "StreamingMoments") -> "StreamingMoments":
        """Fold ``other``'s state into this one (pairwise combine)."""
        self._require_data()
        other._require_data()
        na, nb = float(self._n), float(other._n)
        n = na + nb
        delta = other._mean - self._mean
        self._m2 = self._m2 + other._m2 + delta * delta * (na * nb / n)
        self._mean = self._mean + delta * (nb / n)
        self._n += other._n
        return self

    _COMBINE = staticmethod(_combine_moments)

    def _state(self):
        return jnp.int32(self._n), self._mean, self._m2

    def _set_state(self, state):
        n, self._mean, self._m2 = state
        self._n = int(n)

    @property
    def mean(self) -> DNDarray:
        self._require_data()
        return self._wrap(self._mean)

    @property
    def var(self) -> DNDarray:
        self._require_data()
        return self._wrap(self._m2 / max(self._n - self.ddof, 1))

    @property
    def std(self) -> DNDarray:
        self._require_data()
        return self._wrap(jnp.sqrt(self._m2 / max(self._n - self.ddof, 1)))


class StreamingCov(_StreamingBase):
    """Single-pass covariance over samples-in-rows data — the streaming
    counterpart of ``ht.cov(x, rowvar=False, bias=bias, ddof=ddof)``
    (``ddof=None`` resolves to ``0 if bias else 1``, like the oracle)."""

    def __init__(self, bias: bool = False, ddof=None):
        super().__init__()
        if ddof is not None and ddof != int(ddof):
            raise ValueError("ddof must be integer")
        self.ddof = int(ddof) if ddof is not None else (0 if bias else 1)
        self._mean = None
        self._c = None

    def update(self, chunk: DNDarray) -> "StreamingCov":
        xa, nv = self._capture(chunk)
        if self._mean is None:
            f = xa.shape[1]
            self._mean = jnp.zeros((f,), xa.dtype)
            self._c = jnp.zeros((f, f), xa.dtype)
        self._mean, self._c = collective_lockstep(
            _cov_program()(
                xa, nv, jnp.asarray(float(self._n), xa.dtype), self._mean, self._c
            )
        )
        self._n += int(chunk.gshape[0])
        return self

    def merge(self, other: "StreamingCov") -> "StreamingCov":
        """Fold ``other``'s state into this one (pairwise combine)."""
        self._require_data()
        other._require_data()
        na, nb = float(self._n), float(other._n)
        n = na + nb
        delta = other._mean - self._mean
        self._c = self._c + other._c + jnp.outer(delta, delta) * (na * nb / n)
        self._mean = self._mean + delta * (nb / n)
        self._n += other._n
        return self

    _COMBINE = staticmethod(_combine_cov)

    def _state(self):
        return jnp.int32(self._n), self._mean, self._c

    def _set_state(self, state):
        n, self._mean, self._c = state
        self._n = int(n)

    @property
    def mean(self) -> DNDarray:
        self._require_data()
        return self._wrap(self._mean)

    @property
    def cov(self) -> DNDarray:
        self._require_data()
        return self._wrap(self._c / max(self._n - self.ddof, 1))


class StreamingHistogram(_StreamingBase):
    """Single-pass histogram over a FIXED finite range.

    Streaming can't discover the data's min/max before binning, so the
    range is explicit up front (``ht.histogram``'s in-memory default
    derives it from the full array — pass the same ``range=`` to both
    sides for the oracle comparison). Values outside the range are
    dropped, matching numpy."""

    def __init__(self, bins: int = 10, range=None):
        super().__init__()
        if range is None:
            raise ValueError(
                "StreamingHistogram needs an explicit finite range=(lo, hi): "
                "a single-pass estimator cannot derive it from the data"
            )
        lo, hi = float(range[0]), float(range[1])
        if not (lo < hi):
            raise ValueError(f"range must satisfy lo < hi, got {(lo, hi)}")
        self.bins = int(bins)
        self.range = (lo, hi)
        self._counts = None

    def update(self, chunk: DNDarray) -> "StreamingHistogram":
        xa, nv = self._capture(chunk)
        if self._counts is None:
            self._counts = jnp.zeros((self.bins,), jnp.float32)
        lo, hi = self.range
        self._counts = collective_lockstep(
            _hist_program(self.bins)(
                xa, nv, jnp.float32(lo), jnp.float32(hi), self._counts
            )
        )
        self._n += int(chunk.gshape[0])
        return self

    def merge(self, other: "StreamingHistogram") -> "StreamingHistogram":
        """Fold ``other``'s counts into this one (same bins and range)."""
        if (self.bins, self.range) != (other.bins, other.range):
            raise ValueError("cannot merge histograms with different binning")
        self._require_data()
        other._require_data()
        self._counts = self._counts + other._counts
        self._n += other._n
        return self

    _COMBINE = staticmethod(_combine_hist)

    def _state(self):
        return jnp.int32(self._n), self._counts

    def _set_state(self, state):
        n, self._counts = state
        self._n = int(n)

    @property
    def hist(self) -> DNDarray:
        """Bin counts, int-valued like ``ht.histogram``'s first output."""
        self._require_data()
        return self._wrap(self._counts.astype(jnp.int32))

    @property
    def bin_edges(self) -> DNDarray:
        lo, hi = self.range
        edges = jnp.linspace(lo, hi, self.bins + 1, dtype=jnp.float32)
        return DNDarray(edges, split=None, device=self._device, comm=self._comm)
