"""PSTrainStep — one fused SPMD program for dense + sparse tables.

The reference's hot loop does four round-trips per iteration: pull sparse
keys, pull dense weights, push sparse grads, push dense grads — each a zmq
hop through server threads (SURVEY.md §3.3). Here the whole iteration is ONE
jitted GSPMD program: shardings are annotated on the table state and batch,
and XLA inserts the collectives (all-gather for pulls, reduce-scatter for
dense pushes, gather/scatter collectives for embedding traffic) over ICI —
the "pick a mesh, annotate shardings, let the compiler insert collectives"
recipe (SURVEY.md §2.3; PAPERS.md arXiv 2004.13336 for the sharded weight
update).

User contract:
    loss_fn(dense_params, rows: dict[name, [B?, F?, dim]], batch) -> loss
    key_fns[name](batch) -> integer key array for that sparse table

The step differentiates through dense params and gathered rows, applies the
dense updater on the sharded flat vector and the row-wise sparse updater on
the touched slots — identical numerics to DenseTable.push /
SparseTable.push (shared ops in minips_tpu/ops/sparse_update.py).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from minips_tpu.parallel.mesh import DATA_AXIS
from minips_tpu.tables.dense import DenseTable, cast_floating
from minips_tpu.tables.sparse import SparseTable
from minips_tpu.utils import profiling as prof

PyTree = Any


class PSTrainStep:
    """Builds and runs the fused step; owns nothing — state stays in the
    tables, flowing through the jitted function with donation."""

    def __init__(
        self,
        loss_fn: Callable[..., jnp.ndarray],
        dense: Optional[DenseTable] = None,
        sparse: Optional[dict[str, SparseTable]] = None,
        key_fns: Optional[dict[str, Callable]] = None,
        compute_dtype: Optional[Any] = None,
        grad_scale: float = 1.0,
    ):
        """``compute_dtype`` (e.g. ``jnp.bfloat16``): run ``loss_fn`` in
        reduced precision — dense params, gathered sparse rows, and
        floating batch leaves are cast down before the loss, gradients are
        cast back to float32 before the sharded optimizer / row updates,
        and master table state stays float32 throughout (same contract as
        ``DenseTable.make_step(compute_dtype=...)``).

        ``grad_scale``: multiply all gradients by this constant before the
        updates while reporting the unscaled loss. The reference's server
        SUMS per-key contributions (``updater->Update`` adds each worker
        sample's gradient at full magnitude, SURVEY.md §3.3), so a
        batch-MEAN ``loss_fn`` underscales row updates by the batch size;
        ``grad_scale=batch_size`` restores per-sample update semantics
        (classic per-pair SGD, e.g. word2vec) without distorting the
        logged loss. Note: adagrad rows are invariant to any constant
        gradient scale (the accumulator normalizes it away up to eps), so
        this knob only changes SGD-updated tables and the dense path's
        scale-sensitive optimizers."""
        self.compute_dtype = (None if compute_dtype is None
                              else jnp.dtype(compute_dtype))
        if grad_scale <= 0:
            raise ValueError(f"grad_scale must be > 0, got {grad_scale}")
        self.grad_scale = float(grad_scale)
        self.loss_fn = loss_fn
        self.dense = dense
        self.sparse = sparse or {}
        self.key_fns = key_fns or {}
        if "dense" in self.sparse:
            raise ValueError(
                "'dense' is a reserved state key; rename the sparse table")
        missing = set(self.sparse) - set(self.key_fns)
        if missing:
            raise ValueError(f"sparse tables missing key_fns: {missing}")
        if dense is None and not self.sparse:
            raise ValueError("PSTrainStep needs a dense table and/or at "
                             "least one sparse table")
        self._mesh = (dense.mesh if dense is not None
                      else next(iter(self.sparse.values())).mesh)
        self._jit_step = self._build()
        self._staged = False    # its program's account is kept

    # ------------------------------------------------------------------ build
    def _collect_state(self) -> dict:
        state: dict = {}
        if self.dense is not None:
            state["dense"] = (self.dense.params, self.dense.opt_state)
        for name, t in self.sparse.items():
            state[name] = (t.emb, t.opt_state())
        return state

    def _restore_state(self, state: dict) -> None:
        if self.dense is not None:
            self.dense.params, self.dense.opt_state = state["dense"]
        for name, t in self.sparse.items():
            t.emb, opt = state[name]
            t.set_opt_state(opt)

    def _build(self):
        dense = self.dense
        sparse = dict(self.sparse)
        key_fns = dict(self.key_fns)
        loss_fn = self.loss_fn
        mesh = self._mesh
        cd = self.compute_dtype
        gscale = self.grad_scale

        def step(state, batch):
            # ----- pull phase (differentiable views of table state)
            with jax.named_scope(prof.PULL):
                if dense is not None:
                    p_flat, opt = state["dense"]
                cbatch = cast_floating(batch, cd)
                slots = {}
                rows = {}
                for name, t in sparse.items():
                    keys = key_fns[name](batch)
                    slots[name] = t.slots_of(keys)
                    rows[name] = state[name][0][slots[name]]

            def compute_loss(p_flat_in, rows_in):
                dp = (cast_floating(
                          dense._unravel(p_flat_in[: dense.num_keys]), cd)
                      if dense is not None else None)
                return loss_fn(dp, cast_floating(rows_in, cd),
                               cbatch).astype(jnp.float32)

            with jax.named_scope(prof.GRAD):
                if dense is not None:
                    loss, (g_flat, g_rows) = jax.value_and_grad(
                        compute_loss, argnums=(0, 1))(p_flat, rows)
                else:
                    loss, g_rows = jax.value_and_grad(
                        lambda rw: compute_loss(None, rw))(rows)
                if gscale != 1.0:
                    g_rows = jax.tree.map(lambda g: g * gscale, g_rows)
                    if dense is not None:
                        g_flat = g_flat * gscale

            new_state = dict(state)
            # ----- dense push: reduce-scatter + sharded optax update
            if dense is not None:
                with jax.named_scope(prof.PUSH_DENSE):
                    g_flat = jax.lax.with_sharding_constraint(
                        g_flat, NamedSharding(mesh, P(DATA_AXIS)))
                    updates, opt = dense.tx.update(g_flat, opt, p_flat)
                    new_state["dense"] = (
                        optax.apply_updates(p_flat, updates), opt)
            # ----- sparse pushes: row-wise updater on touched slots
            # (shared transition with SparseTable.push: t.row_update)
            for name, t in sparse.items():
                emb, opt = state[name]
                with jax.named_scope(prof.PUSH_SPARSE), \
                        jax.named_scope(name):
                    new_state[name] = t.row_update(emb, opt, slots[name],
                                                   g_rows[name])
            return new_state, loss

        step.__name__ = step.__qualname__ = prof.FUSED_STEP_FN
        # un-jitted pure transition, exposed for scan-chained microbenching
        # (bench.py chains K steps in one dispatch to defeat host overhead)
        self.step_fn_pure = step
        return jax.jit(step, donate_argnums=(0,))

    # -------------------------------------------------------------------- run
    def __call__(self, batch) -> float:
        """Run one fused step against the tables' live state. The batch
        should already be device_put with data-axis sharding (use
        ``shard_batch``)."""
        with prof.span(prof.STEP):
            with prof.span(prof.STEP_COLLECT):
                state = self._collect_state()
            with prof.span(prof.STEP_DISPATCH):
                if not self._staged or prof.stale(prof.FUSED_STEP_FN):
                    prof.stage(prof.FUSED_STEP_FN, self._jit_step, state,
                               batch)
                    self._staged = True
                new_state, loss = self._jit_step(state, batch)
            with prof.span(prof.STEP_RESTORE):
                self._restore_state(new_state)
        return loss

    def lower(self, batch):
        """The fused step lowered against the tables' live state — the
        program ``__call__`` runs, for inspection (collectives, kernels,
        memory) without executing it."""
        return self._jit_step.lower(self._collect_state(), batch)

    def shard_batch(self, batch: PyTree) -> PyTree:
        """device_put batch leaves sharded along the data axis (axis 0)."""
        sharding = NamedSharding(self._mesh, P(DATA_AXIS))
        with prof.span(prof.FEED):
            return jax.tree.map(
                lambda x: jax.device_put(jnp.asarray(x), sharding), batch)
