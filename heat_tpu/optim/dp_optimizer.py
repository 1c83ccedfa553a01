"""Data-parallel optimizers (reference ``heat/optim/dp_optimizer.py``).

Two pieces, as in the reference:

- :class:`DataParallelOptimizer` (reference ``dp_optimizer.py:834``): wraps
  any optax ``GradientTransformation`` with the step bookkeeping the
  reference kept for torch optimizers.
- :class:`DASO` (reference ``dp_optimizer.py:46``): hierarchical
  asynchronous data parallelism. The reference syncs node-local GPUs with
  torch-DDP every batch and runs staggered bf16 MPI Iallreduces across
  nodes every ``global_skip`` batches, applying results
  ``batches_to_wait`` batches later.

The TPU-native mapping of DASO keeps the defining property — **parameter
replicas diverge between global syncs**: parameters carry a leading
``nodes`` axis (one replica per slow-mesh group) sharded over the DCN mesh
axis. Each step vmaps the loss over that axis, so every group trains on its
own slice of the batch with gradients reduced only within the group (the
ICI fast axis, fused by XLA like the reference's node-local DDP). Every
``global_skip`` batches the replicas are averaged across the nodes axis in
**bfloat16** (one DCN all-reduce; the reference needed a custom MPI op for
bf16, ``dp_optimizer.py:21-44``) and mixed in ``batches_to_wait`` batches
later, reproducing the reference's delayed-update semantics.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..core.communication import MeshCommunication
from .utils import DetectMetricPlateau

__all__ = ["DataParallelOptimizer", "DASO"]


class DataParallelOptimizer:
    """Wraps an optax transformation for use with
    :class:`heat_tpu.nn.DataParallel` (reference ``dp_optimizer.py:834``)."""

    def __init__(self, transformation, blocking: bool = False):
        if not hasattr(transformation, "init") or not hasattr(transformation, "update"):
            raise TypeError("transformation must be an optax GradientTransformation")
        self.transformation = transformation
        self.blocking = blocking
        self._model = None
        self.batches_completed = 0

    def _bind(self, model) -> None:
        self._model = model

    def step(self, loss_fn: Callable, batch, labels):
        """One step through the bound model (reference kept per-batch
        bookkeeping in ``step``). The loss is returned as a device scalar;
        fetch with ``float()`` only when needed."""
        if self._model is None:
            raise RuntimeError("optimizer is not bound to a DataParallel model")
        loss = self._model.train_step(loss_fn, batch, labels)
        self.batches_completed += 1
        return loss

    def state_dict(self) -> dict:
        """Bookkeeping state (the wrapped transformation's state lives in
        the bound model's ``state_dict``)."""
        return {"batches_completed": self.batches_completed}

    def load_state_dict(self, d: dict) -> "DataParallelOptimizer":
        self.batches_completed = int(d.get("batches_completed", 0))
        return self

    def zero_grad(self) -> None:
        """No-op: JAX gradients are functional, never accumulated in place."""


class DASO:
    """Distributed Asynchronous and Selective Optimization (reference
    ``dp_optimizer.py:46``) on a 2-D ICI x DCN mesh.

    Usage::

        mesh = heat_tpu.parallel.make_hierarchical_mesh(n_slow=2)
        daso = DASO(optax.sgd(0.1), total_epochs=10)
        params = daso.init(params, mesh)        # adds the replica axis
        params, loss = daso.step(loss_and_grad_fn, params, batch, labels)
        ...
        final = daso.consolidated_params(params)  # average the replicas

    ``loss_and_grad_fn(per_group_params, *per_group_batch) -> (loss,
    grads)`` is written for ONE replica; DASO vmaps it over the nodes axis.
    """

    def __init__(
        self,
        local_optimizer,
        total_epochs: int,
        warmup_epochs: int = 4,
        cooldown_epochs: int = 4,
        scheduler=None,
        stability_level: float = 0.05,
        max_global_skips: int = 8,
        sending_chunk_size: int = 10_000_000,
        downcast_type=jnp.bfloat16,
        verbose: bool = False,
    ):
        self.local_optimizer = local_optimizer
        self.total_epochs = total_epochs
        self.warmup_epochs = warmup_epochs
        self.cooldown_epochs = cooldown_epochs
        self.stability = DetectMetricPlateau(patience=2, threshold=stability_level)
        self.max_global_skips = max_global_skips
        self.downcast_type = downcast_type
        self.verbose = verbose

        self._reset_schedule()
        self._opt_state = None
        self._mesh = None
        self._slow_axis = "nodes"
        self._param_shardings = None
        self._n_groups = 1
        self._step_fn = None
        self._avg_fn = None

    def _reset_schedule(self) -> None:
        """Schedule defaults, shared by construction and re-``init``."""
        self.global_skip = 4
        self.batches_to_wait = 1
        self.epoch = 0
        self._batch = 0
        self._pending = None  # (averaged replicas, apply_at_batch)
        self._last_loss = None  # previous step's device loss (dispatch fence)

    # -- setup ----------------------------------------------------------------
    def _replica_sharding(self, leaf_ndim: int):
        """Replica-stacked leaves: leading axis over the slow mesh axis,
        everything else replicated within the group (each fast-axis device
        holds its group's full replica, like the reference's per-GPU model
        copies under node-local DDP). On a mesh without the slow axis
        (n_groups == 1) the single replica is simply replicated."""
        from jax.sharding import NamedSharding, PartitionSpec

        lead = self._slow_axis if self._slow_axis in self._mesh.axis_names else None
        return NamedSharding(self._mesh, PartitionSpec(lead, *(None,) * (leaf_ndim - 1)))

    def _tree_shardings(self, tree):
        return jax.tree_util.tree_map(lambda p: self._replica_sharding(p.ndim), tree)

    def init(self, params, mesh, slow_axis: str = "nodes"):
        """Stack parameters into per-group replicas physically sharded over
        the slow axis and build the jitted step/average programs once."""
        self._mesh = mesh
        self._slow_axis = slow_axis
        # re-init on a new mesh must rebuild the step and drop ALL
        # carried-over schedule state from the previous run
        self._step_fn = None
        self._reset_schedule()
        self.stability.reset()
        n = mesh.shape.get(slow_axis, 1) if slow_axis in mesh.axis_names else 1
        self._n_groups = max(n, 1)
        down = self.downcast_type

        stacked = jax.tree_util.tree_map(
            lambda p: jnp.broadcast_to(p[None], (self._n_groups,) + p.shape), params
        )
        # pin replica r to slow-mesh group r — without this constraint XLA
        # may replicate the stack and the hierarchy is metadata only
        self._param_shardings = self._tree_shardings(stacked)
        stacked = jax.device_put(stacked, self._param_shardings)
        # opt state: moment leaves mirror the replica sharding; scalar
        # bookkeeping leaves (e.g. adam's count) must be explicitly
        # replicated over the WHOLE mesh or they land on one device and
        # clash with the mesh-wide params in the jitted step
        from jax.sharding import NamedSharding, PartitionSpec

        opt_state = jax.jit(self.local_optimizer.init)(stacked)
        self._opt_state = jax.device_put(
            opt_state,
            jax.tree_util.tree_map(
                lambda leaf: self._replica_sharding(leaf.ndim)
                if getattr(leaf, "ndim", 0) and leaf.shape[0] == self._n_groups
                else NamedSharding(mesh, PartitionSpec()),
                opt_state,
            ),
        )

        if self._n_groups == 1:
            # nothing to average across; keep the API uniform
            self._avg_fn = jax.jit(lambda reps: reps)
            return stacked

        # bf16 on the wire: the replica average is ONE explicit lax.pmean
        # over the slow (DCN) axis, written in bf16 inside a shard_map so
        # the collective itself carries the downcast dtype (the reference
        # needed a custom MPI op for exactly this, dp_optimizer.py:21-44)
        from jax import shard_map

        specs = jax.tree_util.tree_map(lambda s: s.spec, self._param_shardings)
        slow = slow_axis

        def avg_body(tree):
            return jax.tree_util.tree_map(
                lambda p: jax.lax.pmean(p.astype(down), slow).astype(p.dtype), tree
            )

        def avg(reps):
            return shard_map(avg_body, mesh=mesh, in_specs=(specs,), out_specs=specs)(reps)

        self._avg_fn = jax.jit(
            avg,
            in_shardings=(self._param_shardings,),
            out_shardings=self._param_shardings,
        )
        return stacked

    def _build_step(self, loss_and_grad_fn, n_args: int):
        import optax
        from jax.sharding import NamedSharding, PartitionSpec

        fast = tuple(a for a in self._mesh.axis_names if a != self._slow_axis)
        mesh = self._mesh
        slow = self._slow_axis if self._slow_axis in self._mesh.axis_names else None

        def step(params, opt_state, *batch):
            # split the global batch into one slice per replica group and
            # keep group g's rows on slow-row g, spread over the fast axis
            def regroup(b):
                g = b.reshape((self._n_groups, b.shape[0] // self._n_groups) + b.shape[1:])
                return jax.lax.with_sharding_constraint(
                    g, NamedSharding(mesh, PartitionSpec(slow, fast))
                )

            grouped = tuple(regroup(b) for b in batch)
            losses, grads = jax.vmap(loss_and_grad_fn)(params, *grouped)
            updates, opt_state = self.local_optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, jnp.mean(losses)

        # no in_shardings on the batches: a leading dim only divisible by
        # the group count (the documented contract) must stay accepted;
        # the with_sharding_constraint above pins the grouped layout
        opt_shardings = jax.tree_util.tree_map(lambda x: x.sharding, self._opt_state)
        return jax.jit(
            step,
            donate_argnums=(0, 1),
            in_shardings=(self._param_shardings, opt_shardings, *([None] * n_args)),
            out_shardings=(self._param_shardings, opt_shardings, None),
        )

    # -- phase logic (reference dp_optimizer.py:336) --------------------------
    def epoch_loss_logic(self, loss: float) -> None:
        """Adapt global_skip from the loss plateau. Phases follow the
        reference: warmup syncs every batch immediately, cooldown syncs
        every batch with skip 1; in between a plateau halves the skip, and
        a plateau at skip 1 resets it to ``max_global_skips`` (the
        reference's cycle, ``epoch_loss_logic:336``)."""
        if self.epoch < self.warmup_epochs:
            self.global_skip = 0
            self.batches_to_wait = 0
        elif self.epoch >= self.total_epochs - self.cooldown_epochs:
            self.global_skip = 1
            self.batches_to_wait = 0
        else:
            self.batches_to_wait = 1
            if self.global_skip == 0:
                self.global_skip = 4
            if self.stability.test_if_improving(loss):
                if self.global_skip <= 1:
                    self.global_skip = self.max_global_skips
                else:
                    self.global_skip //= 2
        self.epoch += 1

    # -- stepping -------------------------------------------------------------
    def step(self, loss_and_grad_fn: Callable, params, *batch):
        """One DASO step on replica-stacked ``params``.

        The leading batch dim must be divisible by the number of groups.
        """
        if self._avg_fn is None:
            raise RuntimeError("DASO.init must be called before step")
        if self._step_fn is None:
            self._step_fn = self._build_step(loss_and_grad_fn, len(batch))

        from ..core._dispatch import fence_cpu_collectives

        fence_cpu_collectives(self._last_loss)
        params, self._opt_state, loss = self._step_fn(params, self._opt_state, *batch)
        self._last_loss = loss

        # apply a pending delayed global average (reference
        # ``_gs_rcv_update_params:502``: received params are averaged with
        # the local ones that kept training in the meantime)
        if self._pending is not None and self._batch >= self._pending[1]:
            global_params = self._pending[0]
            params = jax.tree_util.tree_map(
                lambda p, g: (p + g.astype(p.dtype)) / 2.0, params, global_params
            )
            self._pending = None

        if self._n_groups > 1:
            skip = max(self.global_skip, 1)
            if self._batch % skip == 0:
                # the average is its own collective program: drain the step
                # program first, and fence on the average before the next
                # dispatch (CPU rendezvous, _dispatch.py)
                fence_cpu_collectives(loss)
                averaged = self._avg_fn(params)
                self._last_loss = (loss, averaged)
                if self.batches_to_wait > 0:
                    self._pending = (averaged, self._batch + self.batches_to_wait)
                else:
                    params = averaged

        self._batch += 1
        # the loss stays a device scalar: float(loss) here would block on a
        # device→host sync per step (the reference's .item() is an
        # MPI-local copy, ours waits for the device).
        # Callers fetch lazily when they actually need the number; the
        # whole step is transfer-free (asserted in test_nn_optim).
        return params, loss

    def state_dict(self, params=None) -> dict:
        """Schedule counters + optimizer state (+ the replica-stacked
        ``params`` when given) as a flat host dict, the checkpointable
        unit for a supervised DASO training loop. An in-flight delayed
        average (``_pending``) is intentionally NOT captured: on restore
        the replicas simply train until the next scheduled sync, which is
        within DASO's stale-update semantics anyway."""
        from ..nn.data_parallel import _flatten_tree

        d = {
            "global_skip": self.global_skip,
            "batches_to_wait": self.batches_to_wait,
            "epoch": self.epoch,
            "batch": self._batch,
        }
        if self._opt_state is not None:
            d.update(_flatten_tree("opt", self._opt_state))
        if params is not None:
            d.update(_flatten_tree("params", params))
        return d

    def load_state_dict(self, d: dict, params=None):
        """Restore :meth:`state_dict` output into an ``init``-ed DASO.
        Returns the restored replica-stacked params when ``params`` (a
        live tree supplying structure/placement) is given, else None."""
        from ..nn.data_parallel import _load_tree

        self.global_skip = int(d["global_skip"])
        self.batches_to_wait = int(d["batches_to_wait"])
        self.epoch = int(d["epoch"])
        self._batch = int(d["batch"])
        self._pending = None
        self._last_loss = None
        if self._opt_state is not None:
            # capture the live placement BEFORE swapping values in, then
            # re-put so restored leaves land exactly where the old ones were
            shardings = jax.tree_util.tree_map(lambda x: x.sharding, self._opt_state)
            self._opt_state = jax.device_put(
                _load_tree("opt", self._opt_state, d), shardings
            )
        if params is not None:
            restored = _load_tree("params", params, d)
            if self._param_shardings is not None:
                restored = jax.device_put(restored, self._param_shardings)
            return restored
        return None

    def consolidated_params(self, params):
        """Average the replicas into a single parameter tree (end of
        training)."""
        return jax.tree_util.tree_map(lambda p: jnp.mean(p, axis=0), params)

    def zero_grad(self) -> None:
        """No-op (functional gradients)."""

    def print0(self, *args, **kwargs) -> None:
        """reference ``dp_optimizer.py:687``"""
        if jax.process_index() == 0:
            print(*args, **kwargs)
