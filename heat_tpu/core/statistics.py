"""Statistical operations (reference ``heat/core/statistics.py``, 1997 LoC).

The reference implements parallel Welford moment-merging
(``__merge_moments``, ``statistics.py:1043``) and custom MPI argmax/argmin
ops over stacked (value, index) buffers (``statistics.py:1335-1404``).
Under XLA a single global ``jnp`` reduction over a sharded array compiles to
the identical local-partial + all-reduce schedule, so all of that machinery
disappears; what remains is axis/ddof bookkeeping and the unbiased
skew/kurtosis corrections.
"""
from __future__ import annotations

import weakref
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import _hooks, types
from . import _operations
from ._cache import ExecutableCache
from ._operations import (
    _binary_op,
    _local_op,
    _mask_padding,
    _neutral_value,
    _reduce_op,
    _reduced_shape,
    _reduced_split,
)
from .dndarray import DNDarray
from .stride_tricks import sanitize_axis

__all__ = [
    "argmax",
    "argmin",
    "average",
    "bincount",
    "bucketize",
    "cov",
    "digitize",
    "histc",
    "histogram",
    "kurtosis",
    "max",
    "nanmax",
    "nanmean",
    "nanmin",
    "maximum",
    "mean",
    "median",
    "min",
    "minimum",
    "percentile",
    "skew",
    "std",
    "var",
]


def argmax(x: DNDarray, axis=None, out=None, **kwargs) -> DNDarray:
    """Index of the maximum (reference ``statistics.py`` via MPI_ARGMAX)."""
    return _arg_reduce(jnp.argmax, x, axis, out)


def argmin(x: DNDarray, axis=None, out=None, **kwargs) -> DNDarray:
    """Index of the minimum (reference via MPI_ARGMIN)."""
    return _arg_reduce(jnp.argmin, x, axis, out)


def _arg_reduce(op, x, axis, out):
    # offer the call for lazy capture before the buffer read below can
    # force a pending operand (same slot protocol as the generic
    # dispatchers) — this is the tail of the standardize -> matmul ->
    # argmax predict pipeline, which must replay as ONE fused program
    if _operations._capture is not None and _operations._capture.active():
        res = _operations._capture.argreduce(op, x, axis, out)
        if res is not NotImplemented:
            return res
    if not isinstance(x, DNDarray):
        raise TypeError(f"expected x to be a DNDarray, but was {type(x)}")
    axis = sanitize_axis(x.shape, axis)
    arr = x.larray
    if x.padded:
        # padding can never win: fill it with the op's worst value
        fill = _neutral_value("min" if op is jnp.argmax else "max", arr.dtype)
        arr = _mask_padding(arr, x.gshape, x.split, fill)
    result = op(arr, axis=axis)
    if x.padded and axis is None and x.ndim > 1:
        # flat indices refer to the padded buffer; remap to logical layout
        coords = jnp.unravel_index(result, arr.shape)
        result = jnp.ravel_multi_index(coords, x.gshape, mode="clip")
    split = _reduced_split(x.split, axis if axis is not None else None, x.ndim, False)
    result = result.astype(jnp.int64)
    out_gshape = _reduced_shape(x.gshape, axis, False)
    if split is not None and tuple(result.shape) != out_gshape:
        res = DNDarray._from_buffer(result, out_gshape, types.int64, split, x.device, x.comm)
    else:
        res = DNDarray(
            result,
            gshape=out_gshape,
            dtype=types.int64,
            split=split,
            device=x.device,
            comm=x.comm,
        )
    if out is not None:
        from ._operations import _write_out

        return _write_out(out, res)
    return res


def average(x: DNDarray, axis=None, weights: Optional[DNDarray] = None, returned: bool = False):
    """Weighted average (reference ``statistics.py:189``)."""
    if weights is None:
        result = mean(x, axis)
        if returned:
            n = x.size if axis is None else np.prod([x.shape[a] for a in _axes(x, axis)])
            from . import factories

            return result, factories.full_like(result, float(n))
        return result
    axis_s = sanitize_axis(x.shape, axis)
    w = weights._logical() if isinstance(weights, DNDarray) else jnp.asarray(weights)
    xa = x._logical()
    if w.ndim != xa.ndim:
        if axis_s is None or isinstance(axis_s, tuple):
            raise TypeError("Axis must be specified when shapes of x and weights differ.")
        shape = [1] * xa.ndim
        shape[axis_s] = -1
        w = w.reshape(shape)
    wsum = jnp.sum(jnp.broadcast_to(w, xa.shape), axis=axis_s)
    # numpy parity: zero weight sums raise. Host-provided weights are
    # checked for free on their (small) host copy; device-resident
    # (DNDarray) weights pay one small fetch — average is an eager
    # analytics entry point, not a training-loop op.
    if not isinstance(weights, DNDarray) and isinstance(axis_s, (int, type(None))):
        # graftlint: host-sync - host-provided weights, checked on their host copy
        wnp = np.asarray(weights, dtype=np.float64).reshape(tuple(w.shape))
        if axis_s is None:
            zero = bool(wnp.sum() == 0)
        elif wnp.shape[axis_s] == xa.shape[axis_s]:
            zero = bool(np.any(wnp.sum(axis=axis_s) == 0))
        else:  # weights broadcast along the reduced axis
            zero = bool(np.any(wnp == 0))
    else:
        zero = bool(jnp.any(wsum == 0))
    if zero:
        raise ZeroDivisionError("Weights sum to zero, can't be normalized")
    result = jnp.sum(xa * w, axis=axis_s) / wsum
    split = _reduced_split(x.split, axis_s, x.ndim, False)
    res = DNDarray(result, dtype=types.canonical_heat_type(result.dtype), split=split, device=x.device, comm=x.comm)
    if returned:
        wres = DNDarray(jnp.broadcast_to(wsum, result.shape), split=split, device=x.device, comm=x.comm)
        return res, wres
    return res


def _axes(x, axis):
    if axis is None:
        return tuple(range(x.ndim))
    axis = sanitize_axis(x.shape, axis)
    return (axis,) if isinstance(axis, int) else axis


def bincount(x: DNDarray, weights=None, minlength: int = 0) -> DNDarray:
    """Count occurrences of each value (reference ``statistics.py:322``)."""
    w = weights._logical() if isinstance(weights, DNDarray) else weights
    result = jnp.bincount(x._logical(), weights=w, minlength=minlength)
    return DNDarray(result, dtype=types.canonical_heat_type(result.dtype), split=None, device=x.device, comm=x.comm)


def bucketize(input: DNDarray, boundaries, out_int32: bool = False, right: bool = False, out=None) -> DNDarray:
    """Index of the bucket each value falls into (reference
    ``statistics.py:393``)."""
    b = boundaries._logical() if isinstance(boundaries, DNDarray) else jnp.asarray(boundaries)
    # torch semantics: right=False -> first i with x <= b[i] (searchsorted
    # 'left'), right=True -> first i with x < b[i] ('right'); the flag was
    # inverted until the round-4 depth sweep compared against torch
    side = "right" if right else "left"
    idx_type = types.int32 if out_int32 else types.int64
    jt = idx_type.jax_type()
    return _local_op(lambda t: jnp.searchsorted(b, t, side=side).astype(jt), input, out=out, no_cast=True, out_dtype=idx_type)


def digitize(x: DNDarray, bins, right: bool = False) -> DNDarray:
    """Index of the bin each value belongs to (reference
    ``statistics.py:541``)."""
    b = bins._logical() if isinstance(bins, DNDarray) else jnp.asarray(bins)
    return _local_op(lambda t: jnp.digitize(t, b, right=right).astype(jnp.int64), x, no_cast=True, out_dtype=types.int64)


def cov(m: DNDarray, y: Optional[DNDarray] = None, rowvar: bool = True, bias: bool = False, ddof: Optional[int] = None) -> DNDarray:
    """Covariance matrix estimate (reference ``statistics.py:466``)."""
    if ddof is None:
        ddof = 0 if bias else 1
    x = m._logical()
    if x.ndim == 1:
        x = x[None, :]
    elif not rowvar and x.shape[0] != 1:
        x = x.T
    if y is not None:
        ya = y._logical()
        if ya.ndim == 1:
            ya = ya[None, :]
        elif not rowvar:
            ya = ya.T
        x = jnp.concatenate([x, ya], axis=0)
    avg = jnp.mean(x, axis=1, keepdims=True)
    fact = x.shape[1] - ddof
    xc = x - avg
    result = (xc @ xc.conj().T) / fact
    split = 0 if m.split is not None else None
    return DNDarray(jnp.squeeze(result), dtype=types.canonical_heat_type(result.dtype), split=split if result.ndim > 1 else None, device=m.device, comm=m.comm)


def histc(input: DNDarray, bins: int = 100, min: float = 0.0, max: float = 0.0, out=None) -> DNDarray:
    """Histogram with equal-width bins (torch-style; reference
    ``statistics.py:616``)."""
    arr = input._logical()
    lo, hi = float(min), float(max)
    if lo == 0.0 and hi == 0.0:
        lo, hi = float(jnp.min(arr)), float(jnp.max(arr))
    hist, _ = jnp.histogram(arr, bins=bins, range=(lo, hi))
    res = DNDarray(hist.astype(input.dtype.jax_type()), dtype=input.dtype, split=None, device=input.device, comm=input.comm)
    if out is not None:
        from ._operations import _write_out

        return _write_out(out, res)
    return res


def histogram(a: DNDarray, bins: int = 10, range=None, normed=None, weights=None, density=None):
    """numpy-style histogram (reference exposes torch histc; numpy parity
    added for convenience)."""
    hist, edges = jnp.histogram(a._logical(), bins=bins, range=range, density=density)
    return (
        DNDarray(hist, split=None, device=a.device, comm=a.comm),
        DNDarray(edges, split=None, device=a.device, comm=a.comm),
    )


def kurtosis(x: DNDarray, axis=None, unbiased: bool = True, Fischer: bool = True) -> DNDarray:
    """Kurtosis (reference ``statistics.py:727``; ``unbiased`` applies the
    sample-size correction, ``Fischer`` subtracts 3 — reference arg names).
    Moment merging is XLA's problem now."""
    axis_s = sanitize_axis(x.shape, axis)
    arr = x._logical().astype(jnp.promote_types(x.larray.dtype, jnp.float32))
    n = arr.size if axis_s is None else arr.shape[axis_s]
    mu = jnp.mean(arr, axis=axis_s, keepdims=True)
    m2 = jnp.mean((arr - mu) ** 2, axis=axis_s)
    m4 = jnp.mean((arr - mu) ** 4, axis=axis_s)
    g2 = m4 / (m2**2)
    if unbiased and n > 3:
        g2 = ((n - 1) / ((n - 2) * (n - 3))) * ((n + 1) * g2 - 3 * (n - 1)) + 3
    if Fischer:
        g2 = g2 - 3
    split = _reduced_split(x.split, axis_s, x.ndim, False)
    return DNDarray(g2, dtype=types.canonical_heat_type(g2.dtype), split=split, device=x.device, comm=x.comm)


def skew(x: DNDarray, axis=None, unbiased: bool = True) -> DNDarray:
    """Skewness (reference ``statistics.py:1676``; ``unbiased`` applies the
    Fisher-Pearson sample correction)."""
    axis_s = sanitize_axis(x.shape, axis)
    arr = x._logical().astype(jnp.promote_types(x.larray.dtype, jnp.float32))
    n = arr.size if axis_s is None else arr.shape[axis_s]
    mu = jnp.mean(arr, axis=axis_s, keepdims=True)
    m2 = jnp.mean((arr - mu) ** 2, axis=axis_s)
    m3 = jnp.mean((arr - mu) ** 3, axis=axis_s)
    g1 = m3 / (m2**1.5)
    if unbiased and n > 2:
        g1 = g1 * np.sqrt(n * (n - 1)) / (n - 2)
    split = _reduced_split(x.split, axis_s, x.ndim, False)
    return DNDarray(g1, dtype=types.canonical_heat_type(g1.dtype), split=split, device=x.device, comm=x.comm)


def _nan_propagating(op):
    """Wrap a reduction so NaN wins (torch/numpy semantics): the sharded
    cross-device max/min collective silently drops NaN (maximum(nan, x)
    resolves to x in the all-reduce combiner), so an explicit isnan
    reduction rides along — XLA fuses the sibling passes."""

    def run(arr, axis=None, keepdims=False, **kw):
        r = op(arr, axis=axis, keepdims=keepdims, **kw)
        if jnp.issubdtype(arr.dtype, jnp.floating):
            bad = jnp.any(jnp.isnan(arr), axis=axis, keepdims=keepdims)
            r = jnp.where(bad, jnp.asarray(jnp.nan, r.dtype), r)
        return r

    return run


# ONE closure per op, hoisted to module level: a fresh closure per call
# would make every ht.max/ht.min a cache miss in _jitted_reduce_cached
# (recompile each call, executables accumulating in the cache forever).
# Module-level identity keys the cache once; _cache_stable marks them as
# safe to cache despite being closures (see _operations._jitted_reduce).
_NANPROP_MAX = _nan_propagating(jnp.max)
_NANPROP_MIN = _nan_propagating(jnp.min)
_NANPROP_MAX._cache_stable = True
_NANPROP_MIN._cache_stable = True


def max(x: DNDarray, axis=None, out=None, keepdim=None, keepdims=None) -> DNDarray:
    """Maximum along axis (reference ``statistics.py:781``); NaN wins."""
    return _reduce_op(
        _NANPROP_MAX, x, axis=axis, out=out, keepdims=bool(keepdim or keepdims), neutral="min"
    )


def maximum(x1, x2, out=None) -> DNDarray:
    """Elementwise maximum (reference ``statistics.py``)."""
    return _binary_op(jnp.maximum, x1, x2, out=out)


def mean(x: DNDarray, axis=None, where=None) -> DNDarray:
    """Arithmetic mean (reference ``statistics.py:891`` — local moments +
    Allreduce + pairwise merging). Dispatches through the one-pass moments
    panel (see :func:`_moments_panel`): a following ``ht.std``/``ht.var``
    on the same buffer reuses the memoized (count, mean, M2) and costs
    zero additional data reads."""
    if where is not None and isinstance(x, DNDarray):
        return _where_moment(jnp.mean, x, axis, where, 0)
    if isinstance(x, DNDarray):
        axis_s = sanitize_axis(x.shape, axis)
        stats = _moments_panel(x, axis_s)
        if stats is not None:
            return _wrap_moment(x, axis_s, stats[1])
    return _reduce_op(jnp.mean, x, axis=axis)


def nanmax(x: DNDarray, axis=None, out=None, keepdim=None, keepdims=None) -> DNDarray:
    """Maximum ignoring NaNs (numpy extra beyond the reference)."""
    return _reduce_op(jnp.nanmax, x, axis=axis, out=out, keepdims=bool(keepdim or keepdims), neutral=("nan", "min"))


def nanmin(x: DNDarray, axis=None, out=None, keepdim=None, keepdims=None) -> DNDarray:
    """Minimum ignoring NaNs (numpy extra beyond the reference)."""
    return _reduce_op(jnp.nanmin, x, axis=axis, out=out, keepdims=bool(keepdim or keepdims), neutral=("nan", "max"))


def nanmean(x: DNDarray, axis=None, out=None, keepdim=None, keepdims=None) -> DNDarray:
    """Mean ignoring NaNs (numpy extra beyond the reference)."""
    return _reduce_op(jnp.nanmean, x, axis=axis, out=out, keepdims=bool(keepdim or keepdims), neutral=("nan", None))


def _streaming_percentile(chunks, q_host, axis, kd) -> DNDarray:
    """Single-pass approximate percentile over a ``ChunkIterator`` via a
    KLL sketch (rank error <= the sketch's ``eps``, ~1.4% at defaults)."""
    if axis is not None:
        raise ValueError(
            "streaming percentile/median folds all elements (axis=None "
            f"semantics); per-axis reduction is not supported, got axis={axis}"
        )
    if kd:
        raise ValueError("keepdim is not supported on the streaming path")
    from ..stream.sketch import KLLSketch

    sk = KLLSketch()
    for chunk in chunks:
        sk.update(chunk)
    return sk.percentile(q_host.tolist())


def _check_array_arg(x, name: str):
    """Reject non-DNDarray inputs with a message that names the streaming
    sketch path — a ``ChunkIterator`` is valid, anything else is not."""
    if not isinstance(x, DNDarray):
        raise TypeError(
            f"{name} expects a DNDarray (exact, in-memory) or a "
            "heat_tpu.stream.ChunkIterator (single-pass approximate KLL "
            f"sketch path), got {type(x).__name__}"
        )


def median(x: DNDarray, axis=None, keepdim: bool = False, keepdims=None) -> DNDarray:
    """Median (reference ``statistics.py:1017``, gather-based; when the
    reduced axis is the split axis the distributed-sort percentile path
    runs instead — O(n/P) memory, see :func:`percentile`). A
    ``ChunkIterator`` input streams through the KLL sketch instead
    (approximate, see ``docs/STREAMING.md``)."""
    kd = bool(keepdim or keepdims)
    from ..stream.chunked import ChunkIterator

    if isinstance(x, ChunkIterator):
        return _streaming_percentile(x, np.asarray(50.0), axis, kd)
    _check_array_arg(x, "median")
    axis_s = sanitize_axis(x.shape, axis)
    if _use_sorted_percentile(x, axis_s):
        result = _sorted_percentile(x, jnp.asarray(50.0), axis_s, "linear", kd)
        return DNDarray(result, dtype=types.canonical_heat_type(result.dtype), split=None, device=x.device, comm=x.comm)
    result = jnp.median(x._logical(), axis=axis_s, keepdims=kd)
    split = _reduced_split(x.split, axis_s, x.ndim, kd)
    return DNDarray(result, dtype=types.canonical_heat_type(result.dtype), split=split, device=x.device, comm=x.comm)


def min(x: DNDarray, axis=None, out=None, keepdim=None, keepdims=None) -> DNDarray:
    """Minimum along axis (reference ``statistics.py:1114``); NaN wins."""
    return _reduce_op(
        _NANPROP_MIN, x, axis=axis, out=out, keepdims=bool(keepdim or keepdims), neutral="max"
    )


def minimum(x1, x2, out=None) -> DNDarray:
    return _binary_op(jnp.minimum, x1, x2, out=out)


def _use_sorted_percentile(x: DNDarray, axis_s) -> bool:
    """True when the reduction runs along the split axis of a distributed,
    sortable array — the case where ``jnp.percentile`` on the logical view
    would all-gather O(n) to every device."""
    return (
        x.split is not None
        and x.comm.size > 1
        and not types.issubdtype(x.dtype, types.complexfloating)
        and (axis_s is None or axis_s == x.split)
    )


def _sorted_percentile(x: DNDarray, q_arr: jnp.ndarray, axis_s, method: str, kd: bool) -> jnp.ndarray:
    """Percentile via sort + O(q) takes, with numpy's exact semantics
    (q-dims first, float32/float64 compute, NaN propagates to every q,
    round-half-even tie-breaking for ``nearest``). The sort is the
    distributed transposition sort when the reduced axis is the split
    axis of a multi-device array, a local ``jnp.sort`` otherwise — one
    interpolation code path either way (``jnp.percentile``'s own
    ``nearest`` rounds ties differently from numpy, so it is not used)."""
    from . import manipulations as manip

    if axis_s is None and x.ndim > 1:
        xs, ax = manip.flatten(x), 0
    else:
        xs, ax = x, (0 if axis_s is None else axis_s)
    if xs.split == ax and xs.comm.size > 1:
        sv, _ = manip.sort(xs, axis=ax)
        arr = sv._logical()
    else:
        arr = jnp.sort(xs._logical(), axis=ax)
    n = arr.shape[ax]
    ct = jnp.float64 if arr.dtype == jnp.float64 else jnp.float32
    q = q_arr.astype(ct)
    # numpy's virtual-index arithmetic, exactly: q/100 is a float64 true
    # division, THEN cast to the array's inexact dtype (ints promote to
    # f64), then multiplied by (n-1) in that dtype. Evaluating q/100*(n-1)
    # all in f32 hit XLA's reciprocal rewrite (30/100*90 -> 26.999998,
    # selecting flat[26] where numpy takes flat[27], ADVICE r2); evaluating
    # it all in f64 diverges the other way for f32 arrays (numpy's f32 cast
    # makes 0.3 round UP, so 'higher' at q=30, n=91 takes flat[28]).
    idx_t = ct if jnp.issubdtype(arr.dtype, jnp.floating) else jnp.float64
    pos = (q_arr.astype(jnp.float64) / 100.0).astype(idx_t) * (n - 1)
    lo_i = jnp.clip(jnp.floor(pos).astype(jnp.int64), 0, n - 1)
    hi_i = jnp.clip(jnp.ceil(pos).astype(jnp.int64), 0, n - 1)
    take = lambda i: jnp.take(arr, i, axis=ax).astype(ct)
    if method == "lower":
        res = take(lo_i)
    elif method == "higher":
        res = take(hi_i)
    elif method == "nearest":
        res = take(jnp.clip(jnp.round(pos).astype(jnp.int64), 0, n - 1))
    else:
        vlo, vhi = take(lo_i), take(hi_i)
        if method == "midpoint":
            res = (vlo + vhi) / 2
        else:  # linear
            # gamma in the index dtype, cast to ct for the lerp (numpy casts
            # gamma to the array dtype before _lerp)
            w = (pos - jnp.floor(pos)).astype(ct)
            w = w.reshape((1,) * ax + q.shape + (1,) * (arr.ndim - 1 - ax))
            res = vlo + w * (vhi - vlo)
    # numpy layout: q-dims lead the reduced shape
    qn = q.ndim
    if qn and ax:
        perm = list(range(ax, ax + qn)) + list(range(ax)) + list(range(ax + qn, res.ndim))
        res = jnp.transpose(res, perm)
    # NaN propagates to every q (numpy partition semantics)
    if jnp.issubdtype(arr.dtype, jnp.floating):
        anynan = jnp.any(jnp.isnan(arr), axis=ax)  # psum'd over the split axis
        res = jnp.where(anynan.reshape((1,) * qn + anynan.shape), jnp.asarray(jnp.nan, ct), res)
    if kd:
        restore = (x.ndim * (1,)) if axis_s is None else None
        if restore is not None:
            res = res.reshape(tuple(q.shape) + restore)
        else:
            res = jnp.expand_dims(res, qn + ax)
    return res


def percentile(x: DNDarray, q, axis=None, out=None, interpolation: str = "linear", keepdim: bool = False, keepdims=None) -> DNDarray:
    """q-th percentile (reference ``statistics.py:1406``, gather-based).

    When the reduced axis is the split axis, the computation routes
    through the distributed transposition sort + O(q) element takes
    (:mod:`heat_tpu.parallel.dsort`) instead of ``jnp.percentile`` on the
    logical view, which would all-gather the full array to every device.

    A ``ChunkIterator`` input streams through the KLL sketch instead:
    single-pass, fixed memory, approximate within the sketch's rank-error
    bound (see ``docs/STREAMING.md``)."""
    kd = bool(keepdim or keepdims)
    q_arr = q._logical() if isinstance(q, DNDarray) else jnp.asarray(q)
    q_host = np.asarray(q_arr)  # graftlint: host-sync - O(q) scalars, validated eagerly
    # negated all-form so NaN q fails too, like numpy
    if q_host.size and not np.all((q_host >= 0) & (q_host <= 100)):
        raise ValueError("percentiles must be in the range [0, 100]")
    from ..stream.chunked import ChunkIterator

    if isinstance(x, ChunkIterator):
        res = _streaming_percentile(x, q_host, axis, kd)
        if out is not None:
            from ._operations import _write_out

            return _write_out(out, res)
        return res
    _check_array_arg(x, "percentile")
    axis_s = sanitize_axis(x.shape, axis)
    method = {"lower": "lower", "higher": "higher", "midpoint": "midpoint", "nearest": "nearest", "linear": "linear"}[interpolation]
    if (axis_s is None or isinstance(axis_s, int)) and not types.issubdtype(
        x.dtype, types.complexfloating
    ):
        result = _sorted_percentile(x, q_arr, axis_s, method, kd)
    else:  # tuple axis: jnp fallback (gather semantics, like the reference)
        result = jnp.percentile(x._logical().astype(jnp.float64 if x.larray.dtype == jnp.float64 else jnp.float32), q_arr, axis=axis_s, method=method, keepdims=kd)
    res = DNDarray(result, dtype=types.canonical_heat_type(result.dtype), split=None, device=x.device, comm=x.comm)
    if out is not None:
        from ._operations import _write_out

        return _write_out(out, res)
    return res


# --------------------------------------------------------------------------
# one-pass moments panel (kernels.moments dispatch)
#
# ht.mean + ht.std on the same buffer used to read the data three times
# (mean; std's own mean + centered pass). The panel computes (count, mean,
# M2) along the requested axis in ONE read — the pallas kernel on TPU, its
# raw-jnp shifted-sums twin under XLA — and memoizes the tiny result per
# buffer, so the second call of the pair costs zero data reads. mean /
# var(ddof) / std all finalize from the same three numbers.

_PANEL_PROGRAMS = ExecutableCache(maxsize=64)
# id(buffer) -> (weakref, mode, {axis_key: (count, mean, m2)}). Keyed by
# id() because jax Arrays are weakref-able but NOT hashable (elementwise
# __eq__); the death callback drops the slot, so a recycled id can never
# alias a dead buffer, and the identity re-check below guards the rest.
_PANELS: dict = {}
_PANELS_CAP = 32  # tiny entries (scalars + one (f,) row); bound per G002


def _axis_key(axis_s) -> str:
    return "all" if axis_s is None else str(axis_s)


def _panel_program(ndim: int, split, padded: bool, axis_s):
    """Jitted one-read shifted-sums moments program for 1-D/2-D buffers:
    ``s1 = Σ(x−x₀)`` and ``s2 = Σ(x−x₀)²`` fuse into a single XLA
    traversal (variance is shift-invariant), unlike the dependent
    ``mean → mean((x−mean)²)`` chain. Sharded operands compile to the
    local-partial + psum schedule automatically."""
    key = ("moments_panel", ndim, split, padded, _axis_key(axis_s))
    prog = _PANEL_PROGRAMS.get(key)
    if prog is not None:
        return prog

    def run(xa, n0, n1):
        x = xa.astype(jnp.promote_types(xa.dtype, jnp.float32))
        shift = x[(0,) * x.ndim]  # first element is always logically valid
        if padded:
            it = jax.lax.broadcasted_iota(jnp.int32, x.shape, split)
            nv = (n0, n1)[split] if x.ndim == 2 else n0
            xs = jnp.where(it < nv, x - shift, jnp.asarray(0.0, x.dtype))
        else:
            xs = x - shift
        if axis_s is None and x.ndim == 2:
            c = n0 * n1
            s1 = jnp.sum(xs)
            s2 = jnp.sum(xs * xs)
        else:
            ax = 0 if axis_s is None else axis_s
            c = n1 if (x.ndim == 2 and ax == 1) else n0
            s1 = jnp.sum(xs, axis=ax)
            s2 = jnp.sum(xs * xs, axis=ax)
        c = jnp.asarray(c, x.dtype)
        mean_ = shift + s1 / c
        m2 = jnp.maximum(s2 - s1 * s1 / c, 0.0)
        return c, mean_, m2

    _PANEL_PROGRAMS[key] = jax.jit(run)
    return _PANEL_PROGRAMS[key]


@jax.jit
def _panel_cols_merge(cnt, mean, m2):
    """Chan-merge equal-count per-column moments (the pallas kernel's
    output) into the whole-buffer moments: counts add, the grand mean is
    the column-mean average, and each column's M2 gains the between-column
    ``n·(mean_c − gmean)²`` term."""
    f = mean.shape[0]
    total = cnt * f
    gmean = jnp.mean(mean)
    dm = mean - gmean
    return total, gmean, jnp.sum(m2) + cnt * jnp.sum(dm * dm)


def _panel_kernel_stats(x: DNDarray, arr, interpret: bool):
    """Axis-0 and whole-buffer moments via the pallas kernel (one read),
    or None when the kernel's layout preconditions fail or its rows are
    wider than ``moments.kernel_fits`` admits (the caller then uses the
    XLA panel — never a second read of a memoized buffer)."""
    from .kernels import moments_local, moments_sharded
    from .kernels.moments import kernel_fits

    buf = arr if arr.ndim == 2 else arr.reshape(-1, 1)
    p = x.comm.size
    if not kernel_fits(buf.shape[1]):
        return None
    if x.split == 0 and p > 1:
        if buf.shape[0] % p:
            return None
        cnt, mean_, m2 = moments_sharded(
            buf, x.gshape[0], x.comm.mesh, interpret=interpret
        )
    elif x.split is None or p == 1:
        cnt, mean_, m2 = moments_local(buf, x.gshape[0], interpret=interpret)
    else:
        return None
    if arr.ndim == 2:
        return {"0": (cnt, mean_, m2), "all": _panel_cols_merge(cnt, mean_, m2)}
    # axis 0 of a 1-D array IS the whole buffer: serve both keys
    t = (cnt, mean_[0], m2[0])
    return {"all": t, "0": t}


def _moments_panel(x: DNDarray, axis_s):
    """(count, mean, M2) of ``x`` along ``axis_s`` from the one-pass
    panel, or None when the panel declines (ragged layouts, int/complex
    dtypes, >2-D, tuple axes, open lazy scopes, traced contexts — the
    caller falls back to ``_reduce_op``'s masked paths)."""
    if x.ndim not in (1, 2) or 0 in tuple(x.gshape):
        return None
    if axis_s is not None and not isinstance(axis_s, int):
        return None
    if getattr(x, "lcounts", None) is not None:
        return None
    if _operations._capture is not None and _operations._capture.active():
        return None  # lazy scope: _reduce_op's capture hook must see the call
    arr = x.larray
    if not isinstance(arr, jax.Array) or isinstance(arr, jax.core.Tracer):
        return None
    if _hooks.in_trace_safe():
        return None
    if arr.dtype not in (jnp.float32, jnp.float64):
        return None
    from .kernels import dispatch_mode, record_dispatch

    req_mode = dispatch_mode("moments_onepass")
    akey = _axis_key(axis_s)
    bid = id(arr)
    ent = _PANELS.get(bid)
    # entries key by the REQUESTED mode: a panel the kernel declined (and
    # the XLA program computed) must still hit while dispatch_mode keeps
    # answering 'pallas' — otherwise every declined axis recomputes and
    # re-creating the entry drops the buffer's other memoized axes
    if ent is not None and (ent[0]() is not arr or ent[1] != req_mode):
        ent = None
    if ent is not None and akey in ent[2]:
        # memo hit: zero data reads; report the mode that computed it
        record_dispatch("moments_onepass", ent[3].get(akey, req_mode))
        return ent[2][akey]
    entries = None
    mode = req_mode
    if (
        mode in ("pallas", "interpret")
        and arr.dtype == jnp.float32
        and (arr.ndim == 1 or axis_s in (None, 0))
    ):
        entries = _panel_kernel_stats(x, arr, interpret=(mode != "pallas"))
    if entries is None:
        mode = "xla"
        n0 = float(x.gshape[0])
        n1 = float(x.gshape[1]) if x.ndim == 2 else 1.0
        prog = _panel_program(arr.ndim, x.split, bool(x.padded), axis_s)
        entries = {akey: prog(arr, n0, n1)}
    record_dispatch("moments_onepass", mode)
    if ent is None:
        if len(_PANELS) >= _PANELS_CAP:
            _PANELS.pop(next(iter(_PANELS)))  # FIFO bound
        ent = (
            weakref.ref(arr, lambda _, bid=bid: _PANELS.pop(bid, None)),
            req_mode,
            {},
            {},
        )
        _PANELS[bid] = ent
    ent[2].update(entries)
    for k in entries:
        ent[3][k] = mode
    return ent[2][akey]


def _wrap_moment(x: DNDarray, axis_s, result) -> DNDarray:
    """Wrap a finalized moment like ``_reduce_op``'s tail: reduced split,
    reduced gshape, ``_from_buffer`` when the result keeps padded length."""
    out_split = _reduced_split(x.split, axis_s, x.ndim, False)
    dtype = types.canonical_heat_type(result.dtype)
    out_gshape = _reduced_shape(x.gshape, axis_s, False)
    if out_split is not None and tuple(result.shape) != tuple(out_gshape):
        return DNDarray._from_buffer(result, out_gshape, dtype, out_split, x.device, x.comm)
    return DNDarray(
        result, gshape=out_gshape, dtype=dtype, split=out_split,
        device=x.device, comm=x.comm,
    )


def _where_moment(op, x: DNDarray, axis, where, ddof: int) -> DNDarray:
    """``where=``-masked moments, decline-to-eager: a mask buffer cannot
    key the panel memo (jax Arrays are unhashable and the mask is
    arbitrary), so the masked reduction runs eagerly on the logical view —
    the same escape hatch as the lazy layer's unhashable-kwarg fallback."""
    axis_s = sanitize_axis(x.shape, axis)
    w = where._logical() if isinstance(where, DNDarray) else jnp.asarray(where)
    kw = {} if op is jnp.mean else {"ddof": ddof}
    result = op(
        x._logical(),
        axis=axis_s,
        where=jnp.broadcast_to(w.astype(bool), tuple(x.gshape)),
        **kw,
    )
    return _wrap_moment(x, axis_s, result)


def std(x: DNDarray, axis=None, ddof: int = 0, where=None, **kwargs) -> DNDarray:
    """Standard deviation (reference ``statistics.py:1784``).

    ``ddof`` and ``where=`` both route through the one-pass moments panel
    when they can; ``where=`` declines to the eager masked reduction."""
    if where is not None and isinstance(x, DNDarray):
        return _where_moment(jnp.std, x, axis, where, ddof)
    if isinstance(x, DNDarray):
        axis_s = sanitize_axis(x.shape, axis)
        stats = _moments_panel(x, axis_s)
        if stats is not None:
            c, _, m2 = stats
            return _wrap_moment(x, axis_s, jnp.sqrt(m2 / (c - ddof)))
    return _reduce_op(jnp.std, x, axis=axis, ddof=ddof)


def var(x: DNDarray, axis=None, ddof: int = 0, where=None, **kwargs) -> DNDarray:
    """Variance (reference ``statistics.py:1854``).

    ``ddof`` and ``where=`` both route through the one-pass moments panel
    when they can; ``where=`` declines to the eager masked reduction."""
    if where is not None and isinstance(x, DNDarray):
        return _where_moment(jnp.var, x, axis, where, ddof)
    if isinstance(x, DNDarray):
        axis_s = sanitize_axis(x.shape, axis)
        stats = _moments_panel(x, axis_s)
        if stats is not None:
            c, _, m2 = stats
            return _wrap_moment(x, axis_s, m2 / (c - ddof))
    return _reduce_op(jnp.var, x, axis=axis, ddof=ddof)
