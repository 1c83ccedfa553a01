"""Data-parallel NN wrapper (reference ``heat/nn/data_parallel.py``).

The reference wraps a ``torch.nn.Module`` and registers per-parameter
backward hooks that Iallreduce gradients, plus forward pre-hooks that wait
on the previous iteration's handles (``data_parallel.py:108-173,223-313``).
On TPU the entire hook machinery is unnecessary: with parameters replicated
and the batch sharded over the mesh, XLA inserts the gradient psum *inside*
the backward pass and overlaps it with remaining computation on ICI — the
non-blocking bucketed hooks, for free, at compile time.

:class:`DataParallel` therefore wraps a flax module (or a pure
``apply_fn``) and exposes a jitted ``train_step`` whose data sharding is
the ``split=0`` batch axis.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import random as ht_random
from ..core.communication import MeshCommunication, sanitize_comm
from ..core.dndarray import DNDarray


def _flatten_tree(prefix: str, tree) -> dict:
    """Pytree -> flat ``{prefix/keypath: numpy leaf}`` dict (host values)."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            # the leaf spans other processes (e.g. DASO's replica-stacked
            # params on a multi-host slow axis): gather the global value so
            # the host dict is complete — and identical — on every process
            from jax.experimental import multihost_utils

            leaf = multihost_utils.process_allgather(leaf, tiled=True)
        # graftflow: F006 - every rank walks the SAME pytree (same leaf
        # order), the allgather arm is gated on replicated sharding
        # metadata, and each per-leaf host read is symmetric
        out[prefix + jax.tree_util.keystr(path)] = np.asarray(jax.device_get(leaf))
    return out


def _load_tree(prefix: str, tree, d: dict):
    """Replace ``tree``'s leaves with the matching entries of ``d``
    (missing keys keep the live leaf; dtypes are preserved)."""

    def restore(path, leaf):
        key = prefix + jax.tree_util.keystr(path)
        if key not in d:
            return leaf
        return jnp.asarray(d[key], dtype=leaf.dtype)

    return jax.tree_util.tree_map_with_path(restore, tree)

__all__ = ["DataParallel", "DataParallelMultiGPU"]


class DataParallel:
    """Distributed data-parallel model wrapper (reference
    ``data_parallel.py:21``).

    Parameters
    ----------
    module : flax.linen.Module or callable
        The model. A flax module is initialized internally; a plain callable
        is treated as ``apply_fn(params, inputs)``.
    comm : MeshCommunication, optional
        Mesh to shard batches over. Positional order matches the reference
        signature (module, comm, optimizer) at ``data_parallel.py:52-57``,
        where ``MPI_WORLD`` was passed here.
    optimizer : optax.GradientTransformation or DataParallelOptimizer, optional
        If given, ``train_step`` also applies the update.
    blocking_parameter_updates : bool
        Accepted for reference-API parity. Both values compile to the same
        overlapped schedule (XLA fuses the psum into backward).

    Notes
    -----
    Like the reference (which seeds all ranks identically,
    ``data_parallel.py:108``), parameter initialization is deterministic
    and replicated across the mesh.
    """

    def __init__(
        self,
        module,
        comm: Optional[MeshCommunication] = None,
        optimizer=None,
        blocking_parameter_updates: bool = False,
        seed: int = 0,
    ):
        # tolerate the (module, optimizer, comm) order some callers use:
        # a communicator is never a gradient transformation and vice versa
        if comm is not None and not isinstance(comm, MeshCommunication) and (
            hasattr(comm, "update") or hasattr(comm, "transformation")
        ):
            comm, optimizer = (
                optimizer if isinstance(optimizer, MeshCommunication) else None,
                comm,
            )
        self.module = module
        self.comm = sanitize_comm(comm)
        self.blocking_parameter_updates = blocking_parameter_updates
        self._optimizer = None
        self._opt_state = None
        self.params = None
        self._seed = seed

        self._jitted_steps = {}
        self._last_loss = None  # previous step's device loss (dispatch fence)

        from ..optim.dp_optimizer import DataParallelOptimizer

        if optimizer is not None:
            if isinstance(optimizer, DataParallelOptimizer):
                self._optimizer = optimizer.transformation
                optimizer._bind(self)
            else:
                self._optimizer = optimizer

    # -- initialization -------------------------------------------------------
    def init(self, sample_input) -> Any:
        """Initialize replicated parameters (deterministic seed on every
        process, like reference ``data_parallel.py:108``)."""
        if isinstance(sample_input, DNDarray):
            sample_input = sample_input._logical()
        key = jax.random.PRNGKey(self._seed)
        if hasattr(self.module, "init"):
            self.params = self.module.init(key, sample_input)
        else:
            raise TypeError("module must be a flax module with .init, or set .params directly")
        if self._optimizer is not None:
            self._opt_state = self._optimizer.init(self.params)
        return self.params

    # -- forward --------------------------------------------------------------
    def __call__(self, inputs):
        """Forward pass on (possibly sharded) inputs."""
        from ..core._dispatch import fence_cpu_collectives

        # an in-flight train_step program must drain before another SPMD
        # program dispatches (CPU collective rendezvous, _dispatch.py)
        fence_cpu_collectives(self._last_loss)
        # _logical(): the padded buffer must never leak into user math —
        # a pad row would otherwise enter the forward as a phantom sample
        data = inputs._logical() if isinstance(inputs, DNDarray) else inputs
        if hasattr(self.module, "apply"):
            out = self.module.apply(self.params, data)
        else:
            out = self.module(self.params, data)
        if isinstance(inputs, DNDarray):
            return DNDarray(out, split=inputs.split, device=inputs.device, comm=inputs.comm)
        return out

    forward = __call__

    # -- training -------------------------------------------------------------
    def loss_and_grad(self, loss_fn: Callable, batch, labels) -> Tuple[jnp.ndarray, Any]:
        """Compute loss and (automatically psum'd) gradients.

        ``loss_fn(logits, labels) -> scalar``. Batch/labels may be sharded
        DNDarrays; gradients come out replicated (XLA inserts the
        all-reduce, the analogue of the reference's Iallreduce hooks).
        """
        xb = batch._logical() if isinstance(batch, DNDarray) else batch
        yb = labels._logical() if isinstance(labels, DNDarray) else labels

        def objective(params):
            if hasattr(self.module, "apply"):
                logits = self.module.apply(params, xb)
            else:
                logits = self.module(params, xb)
            return loss_fn(logits, yb)

        return jax.value_and_grad(objective)(self.params)

    def _build_step(self, loss_fn: Callable):
        """Jit the full (forward, backward, psum, update) step once.

        XLA fuses the gradient all-reduce into the backward pass and
        overlaps it on ICI — the compile-time analogue of the reference's
        non-blocking bucketed hooks. params/opt_state are donated.
        """
        import optax

        module = self.module
        optimizer = self._optimizer

        def step(params, opt_state, xb, yb):
            def objective(p):
                logits = module.apply(p, xb) if hasattr(module, "apply") else module(p, xb)
                return loss_fn(logits, yb)

            loss, grads = jax.value_and_grad(objective)(params)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        return jax.jit(step, donate_argnums=(0, 1))

    def train_step(self, loss_fn: Callable, batch, labels):
        """One optimization step; requires an optimizer at construction.

        Returns the loss as a DEVICE scalar — fetching it to host every
        batch would serialize training on a device→host sync per step;
        call ``float()``/``.item()`` only when the number is actually
        needed."""
        if self._optimizer is None:
            raise RuntimeError("DataParallel was constructed without an optimizer")
        key = id(loss_fn)
        if key not in self._jitted_steps:
            self._jitted_steps[key] = self._build_step(loss_fn)
        xb = batch._logical() if isinstance(batch, DNDarray) else batch
        yb = labels._logical() if isinstance(labels, DNDarray) else labels
        from ..core._dispatch import fence_cpu_collectives

        fence_cpu_collectives(self._last_loss)
        self.params, self._opt_state, loss = self._jitted_steps[key](
            self.params, self._opt_state, xb, yb
        )
        self._last_loss = loss
        return loss

    # -- resumable training ---------------------------------------------------
    def state_dict(self) -> dict:
        """Model + optimizer state as a flat dict of host numpy arrays
        (keys are pytree key-paths) plus JSON scalars — the checkpointable
        unit for a supervised ``fit``."""
        if self.params is None:
            raise RuntimeError("init must be called before state_dict")
        d = _flatten_tree("params", self.params)
        if self._opt_state is not None:
            d.update(_flatten_tree("opt", self._opt_state))
        d["seed"] = self._seed
        return d

    def load_state_dict(self, d: dict) -> "DataParallel":
        """Restore :meth:`state_dict` output into an initialized model
        (the live pytree structure provides the placement; values come
        from ``d``)."""
        if self.params is None:
            raise RuntimeError("init must be called before load_state_dict")
        self.params = _load_tree("params", self.params, d)
        if self._opt_state is not None:
            self._opt_state = _load_tree("opt", self._opt_state, d)
        self._last_loss = None
        return self

    def fit(
        self,
        loss_fn: Callable,
        batch,
        labels,
        n_steps: int,
        supervisor=None,
        steps_per_block: int = 8,
    ) -> "DataParallel":
        """Run ``n_steps`` of :meth:`train_step`.

        With ``supervisor`` the loop runs as a self-healing supervised
        step loop: one supervised step = ``steps_per_block`` train steps,
        and the block boundary is where the model state is checkpointed
        and restored. A ``version`` token in the state detects restores —
        when the supervisor rewinds, the checkpointed state is loaded
        back into the model before training resumes.
        """
        if self.params is None:
            self.init(batch)
        if supervisor is None:
            for _ in range(n_steps):
                self.train_step(loss_fn, batch, labels)
            return self
        if steps_per_block < 1:
            raise ValueError(f"steps_per_block must be >= 1, got {steps_per_block}")

        self._fit_version = 0
        state = dict(self.state_dict())
        state["step"] = 0
        state["version"] = 0

        def step_fn(st, data, blk):
            if st["version"] != self._fit_version:
                # this state came from a checkpoint, not the live model
                self.load_state_dict(st)
                self._fit_version = st["version"]
            n_do = min(steps_per_block, n_steps - st["step"])
            for _ in range(n_do):
                self.train_step(loss_fn, *data)
            new = dict(self.state_dict())
            new["step"] = st["step"] + n_do
            new["version"] = st["version"] + 1
            self._fit_version = new["version"]
            return new, new["step"] >= n_steps

        result = supervisor.run(step_fn, state, data=(batch, labels), label="nn.fit")
        if result.state is not None and result.state["version"] != self._fit_version:
            self.load_state_dict(result.state)
        return self

    # -- reference-API conveniences ------------------------------------------
    def eval(self):
        """No train/eval mode distinction for pure-function modules."""
        return self

    def train(self):
        return self


class DataParallelMultiGPU(DataParallel):
    """Reference ``data_parallel.py:314``: node-local torch-DDP + DASO
    global sync. On TPU there is no node-local/global split at this layer —
    the mesh covers all chips and DASO owns the hierarchy — so this is
    :class:`DataParallel` under the reference's name."""
