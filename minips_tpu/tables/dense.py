"""DenseTable — the KVTable + RangeManager + updater collapsed into data.

The reference's dense path is ``VectorStorage<Val>`` on server threads, a
``SimpleRangeManager`` contiguous key partition, and a server-side updater
applied at push (SURVEY.md §2 "KVTable storage", "SimpleRangeManager",
"Updaters"; §3.3 hot loop). TPU-first, all three collapse into one object:

- The table's key space 0..n-1 is a flat parameter vector, padded to ``P``
  and sharded in contiguous ranges across the mesh's ``data`` axis — the
  range partition *is* the ``PartitionSpec``.
- ``pull``  ≡ ``all_gather``  of the owner shards (SURVEY.md §2.3).
- ``push``  ≡ ``psum_scatter`` of worker grads into the owner shard followed
  by the optax updater on that shard — i.e. weight-update sharding
  (PAPERS.md, arXiv 2004.13336), which is exactly the PS server role.
- ``make_step`` fuses pull → grad → push → update into ONE jitted SPMD
  program so XLA overlaps the collectives with compute; this is the hot
  path replacing the reference's zmq round-trips (SURVEY.md §3.3).

Apps see parameters as a pytree: the table ravels any pytree template via
``jax.flatten_util.ravel_pytree``, so "keys" are positions in the raveled
vector — the same world view as the reference's integer key space.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from minips_tpu.parallel.mesh import DATA_AXIS, SHARD_TILE
from minips_tpu.parallel.partition import RangePartitioner
from minips_tpu.tables.updaters import (Adam8bitState, LearningRate,
                                        make_updater, masked_merge_adam8)
from minips_tpu.utils import profiling as prof

PyTree = Any


def cast_floating(tree: PyTree, dtype) -> PyTree:
    """Cast every floating leaf of ``tree`` to ``dtype`` (ints/bools pass
    through) — the shared mixed-precision downcast used by
    ``DenseTable.make_step`` and ``PSTrainStep`` so both paths keep the
    same contract. ``dtype=None`` is the identity."""
    if dtype is None:
        return tree
    dt = jnp.dtype(dtype)

    def down(x):
        return (x.astype(dt)
                if jnp.issubdtype(jnp.result_type(x), jnp.floating) else x)

    return jax.tree.map(down, tree)


class DenseTable:
    """A dense parameter table sharded across the mesh ``data`` axis."""

    @prof.span(prof.TABLE_INIT)
    def __init__(
        self,
        template: PyTree,
        mesh: Mesh,
        *,
        name: str = "dense0",
        updater: str = "sgd",
        lr: LearningRate = 0.1,
        grad_reduce: str = "mean",
        tx: Optional[optax.GradientTransformation] = None,
        updater_kwargs: Optional[dict] = None,
    ):
        if grad_reduce not in ("mean", "sum"):
            raise ValueError("grad_reduce must be 'mean' or 'sum'")
        self.name = name
        self.mesh = mesh
        self.grad_reduce = grad_reduce
        self.num_shards = mesh.shape[DATA_AXIS]

        flat, self._unravel = ravel_pytree(template)
        self.num_keys = int(flat.shape[0])
        kw = dict(updater_kwargs or {})
        # adam8's blockwise-quantized moments need whole blocks per shard
        # (one f32 scale per `block` contiguous elements); align the
        # range padding instead of erroring — padding keys are zeros with
        # zero grads, so they quantize to zero codes and never move
        align = int(kw.get("block", 256)) if updater == "adam8" else 1
        if self.num_shards > 1:
            # a shard that ends on the chip's tile is one the pull's
            # all-gather can place; a table on one shard has no
            # collective, and padding it would only make `full[:n]` a copy
            align = math.lcm(align, SHARD_TILE)
        self.partitioner = RangePartitioner(self.num_keys, self.num_shards,
                                            align=align)
        self.padded = self.partitioner.padded
        prof.counter(prof.TABLE_PAD_KEYS, self.padded - self.num_keys)
        self._shard_shape = (self.padded // self.num_shards,)
        # clip-by-global-norm must see the GLOBAL gradient, but the optax
        # transform runs on one owner shard inside shard_map — intercept
        # and apply it in the fused step with a cross-shard psum instead
        self._clip_norm = float(kw.pop("clip_norm", 0.0) or 0.0)
        if kw.get("decay_mask") is not None:
            # a params-shaped pytree mask (e.g. transformer.decay_mask)
            # travels the same ravel as the params; padding rows never
            # decay (they are zeros and must stay zeros)
            mflat, _ = ravel_pytree(kw["decay_mask"])
            if mflat.shape != flat.shape:
                raise ValueError(
                    f"decay_mask ravels to {mflat.shape}, params to "
                    f"{flat.shape} — the mask must be params-shaped")
            kw["decay_mask"] = (jnp.zeros(self.padded, flat.dtype)
                                .at[: self.num_keys].set(mflat))
        self.tx = tx if tx is not None else make_updater(updater, lr, **kw)

        self._pspec = P(DATA_AXIS)
        self._sharding = NamedSharding(mesh, self._pspec)
        # pad straight into the sharded layout: the padded copy never
        # exists whole on one device (the template itself is the caller's)
        self.params = jax.jit(
            lambda f: jnp.zeros(self.padded, f.dtype)
            .at[: self.num_keys].set(f),
            out_shardings=self._sharding)(flat)

        opt_state = jax.eval_shape(self.tx.init, self.params)
        a8 = [x for x in jax.tree.leaves(
                  opt_state, is_leaf=lambda l: isinstance(l, Adam8bitState))
              if isinstance(x, Adam8bitState)]
        block = a8[0].mu_q.shape[0] // a8[0].mu_s.shape[0] if a8 else 0
        if block and self._shard_shape[0] % block:
            raise ValueError(
                f"quantized opt state with block={block} does not align "
                f"with shard size {self._shard_shape[0]}: each contiguous "
                "range shard must hold whole blocks (use updater='adam8' "
                "so the table aligns its padding, or pick a block that "
                "divides the shard size)")
        self._opt_specs = self._opt_specs_tree(opt_state)
        opt_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), self._opt_specs,
            is_leaf=lambda x: isinstance(x, P))
        # Note: specs describe the *global* opt leaves; inside shard_map
        # sharded leaves have the per-shard shape.
        self.opt_state = jax.jit(
            self.tx.init, out_shardings=opt_shardings
        )(self.params)
        self.state = None       # see make_step(state=...)
        self._staged = None     # the step whose program step_inplace staged

    def _opt_specs_tree(self, opt_state) -> PyTree:
        """Spec tree for the opt state: params-length 1-D leaves range-
        shard; an ``Adam8bitState``'s OWN scale fields (``mu_s``/``nu_s``)
        are tagged structurally — by position in that state, never by
        shape inference (ADVICE r4 low: a foreign 1-D leaf that happens
        to length-match padded/block must stay replicated, or shard_map
        would silently hand its transform a slice). Scalars (adam's
        count) and everything else stay replicated. Works for
        updater='adam8' and for a user-supplied quantized tx alike."""
        def leaf_spec(leaf) -> P:
            if getattr(leaf, "ndim", None) == 1 \
                    and leaf.shape[0] == self.padded:
                return P(DATA_AXIS)
            return P()

        def node_spec(x):
            if isinstance(x, Adam8bitState):
                # codes are params-length (leaf rule would shard them
                # anyway); scales are tagged BECAUSE they are this
                # state's scales — contiguous range shards hold whole
                # blocks, so they slice in alignment with the codes
                return Adam8bitState(P(), P(DATA_AXIS), P(DATA_AXIS),
                                     P(DATA_AXIS), P(DATA_AXIS))
            return leaf_spec(x)  # the outer map decomposed other nodes

        return jax.tree.map(
            node_spec, opt_state,
            is_leaf=lambda x: isinstance(x, Adam8bitState))

    # ------------------------------------------------------------------ pull
    def pull(self) -> PyTree:
        """Full parameter pytree (all-gather of the owner shards).

        Reference: ``KVClientTable::Pull/Get`` over all keys (SURVEY.md §2
        "KVClientTable"). Under jit this is an all-gather on ICI; as a host
        call it just reads the (distributed) array.
        """
        return self._unravel(self.params[: self.num_keys])

    def pull_keys(self, keys: np.ndarray) -> jnp.ndarray:
        """Sparse read of a dense table (emulation/API-parity path)."""
        return self.params[jnp.asarray(keys)]

    # ------------------------------------------------------------------ push
    def push(self, grads: PyTree) -> None:
        """Apply a full-pytree gradient through the server-side updater.

        Reference: ``KVClientTable::Push/Add`` → server ``updater->Update``
        (SURVEY.md §3.3). The caller passes the already-reduced gradient
        (the engine's fused path reduces across workers itself).
        """
        gflat, _ = ravel_pytree(grads)
        self._push_flat(jnp.zeros(self.padded, gflat.dtype)
                        .at[: self.num_keys].set(gflat))

    def push_keys(self, keys: np.ndarray, vals: jnp.ndarray) -> None:
        """Sparse additive push into a dense table (emulation path).

        Per-key server semantics (SURVEY.md §3.3 ``updater->Update(keys,
        grads)``): only the pushed keys' parameters and elementwise
        optimizer state move; untouched keys are masked out so stateful
        updaters (adam/momentum) do not drift them. Scalar opt-state
        (e.g. adam's step count) still advances once per push.
        """
        keys = jnp.asarray(keys)
        flat = jnp.zeros(self.padded, self.params.dtype).at[keys].add(vals)
        mask = jnp.zeros(self.padded, self.params.dtype).at[keys].set(1.0)
        self.params, self.opt_state = self._jit_apply_masked(
            self.params, self.opt_state, flat, mask)

    def _push_flat(self, flat_grads: jnp.ndarray) -> None:
        self.params, self.opt_state = self._jit_apply(
            self.params, self.opt_state, flat_grads
        )

    def _make_apply(self, masked: bool):
        vec_shard = (self.padded // self.num_shards,)
        in_specs = (self._pspec, self._opt_specs, self._pspec) + (
            (self._pspec,) if masked else ())

        clip_norm = self._clip_norm

        def apply_shard(p_shard, opt_shard, g_shard, *mask):
            if clip_norm:
                # same cross-shard global-norm clip as the fused step —
                # a clip_norm kwarg must never be a silent no-op on the
                # push()/push_keys() paths
                sumsq = jax.lax.psum(jnp.sum(g_shard * g_shard),
                                     DATA_AXIS)
                g_shard = g_shard * jnp.minimum(
                    1.0, clip_norm * jax.lax.rsqrt(
                        jnp.maximum(sumsq, 1e-16)))
            updates, new_opt = self.tx.update(g_shard, opt_shard, p_shard)
            if masked:
                m = mask[0]
                updates = updates * m

                def restore(new, old):
                    # quantized moments restore at BLOCK granularity —
                    # an elementwise where() on the codes alone leaves
                    # them paired with recomputed scales (ADVICE r4
                    # medium: silent moment drift on untouched keys)
                    if isinstance(new, Adam8bitState):
                        return masked_merge_adam8(new, old, m)
                    return (jnp.where(m > 0, new, old)
                            if getattr(new, "shape", ()) == vec_shard
                            else new)

                new_opt = jax.tree.map(
                    restore, new_opt, opt_shard,
                    is_leaf=lambda x: isinstance(x, Adam8bitState))
            return optax.apply_updates(p_shard, updates), new_opt

        return jax.jit(
            jax.shard_map(apply_shard, mesh=self.mesh, in_specs=in_specs,
                          out_specs=(self._pspec, self._opt_specs)),
            donate_argnums=(0, 1))

    @functools.cached_property
    def _jit_apply(self):
        return self._make_apply(masked=False)

    @functools.cached_property
    def _jit_apply_masked(self):
        return self._make_apply(masked=True)

    # ------------------------------------------------------------- fused step
    def make_step(
        self,
        grad_fn: Callable[[PyTree, Any], tuple[jnp.ndarray, PyTree]],
        *,
        batch_spec: Optional[PyTree] = None,
        jit: bool = True,
        comm: str = "float32",
        accum: int = 1,
        compute_dtype: Optional[Any] = None,
        state: Optional[PyTree] = None,
    ):
        """Fuse pull → grad → push → update into one SPMD program.

        ``grad_fn(params_pytree, batch_shard) -> (loss, grads_pytree)`` runs
        per worker on its batch shard; the returned ``step(params, opt,
        batch) -> (params, opt, loss)`` is the TPU-native rewrite of one hot
        loop iteration (SURVEY.md §3.3): all-gather (pull), local grad
        (worker compute on MXU), psum_scatter (push), optax on the owner
        shard (server update). BSP is implicit — the collectives are the
        barrier (SURVEY.md §2 "BSPModel").

        ``comm`` compresses the two collectives' wire format ("bfloat16" or
        "int8"; EQuARX-style, see ops/quantized_comm.py). Params and the
        optimizer update stay float32 — only bytes-on-wire change.

        ``compute_dtype`` (e.g. ``jnp.bfloat16``) runs the worker math in
        reduced precision — the MXU-native mixed-precision recipe: float32
        master weights and optimizer update on the owner shard, with
        params AND floating batch leaves cast down before ``grad_fn`` and
        the gradients cast back up in the push, so the loss surface is
        evaluated in bf16 but the update path never loses master-weight
        precision. Composes with ``comm`` (wire) and ``accum`` (the f32
        microbatch fold).

        ``accum`` > 1 splits each shard's batch into that many microbatches
        and folds their grads in float32 under one ``lax.scan`` before the
        single push/update — effective batch grows ``accum``x while
        activation memory stays one microbatch's worth (one pull, one
        push, one optimizer step per call, so PS clock semantics are
        unchanged). The leading batch dim must divide by ``accum``.

        ``state`` is what a model carries from step to step beside its
        parameters and changes outside the gradient (an expert router's
        balancing bias): a pytree, replicated, never cast. The table keeps
        it as ``self.state``; ``grad_fn(params, batch, state) -> (loss,
        grads, state)`` hands on the next one, the same on every worker
        (it reduces over ``DATA_AXIS`` itself), and the step becomes
        ``step(params, opt, batch, state) -> (params, opt, loss, state)``
        (``step_inplace`` passes it through).
        """
        n, padded = self.num_keys, self.padded
        pad = padded - n
        num_workers = self.num_shards
        clip_norm = self._clip_norm
        unravel, tx, reduce = self._unravel, self.tx, self.grad_reduce
        bspec = batch_spec if batch_spec is not None else P(DATA_AXIS)
        if accum < 1:
            raise ValueError(f"accum must be >= 1, got {accum}")
        if state is not None and accum > 1:
            raise ValueError("a step-to-step state and accum > 1: which "
                             "microbatch's state is handed on is undefined")
        self.state = state
        from minips_tpu.ops.quantized_comm import (
            _check, quantized_all_gather, quantized_psum_scatter)
        _check(comm)  # eager: tracing happens on first step call

        cd = None if compute_dtype is None else jnp.dtype(compute_dtype)
        if cd is not None:
            user_grad_fn = grad_fn

            def grad_fn(params, batch, *state):  # noqa: F811 - a wrap
                # params arrive cast already: the pull phase casts them;
                # the gradients go back up in the push phase
                loss, grads, *state = user_grad_fn(
                    params, cast_floating(batch, cd), *state)
                return (loss.astype(jnp.float32), grads, *state)

        def to_wire(grads):
            # the push's own work on the workers' gradients: the cast back
            # up (one that is padded goes up after the pad) and the ravel
            # into the table's one vector
            if cd is not None and not pad:
                grads = cast_floating(grads, jnp.float32)
            return ravel_pytree(grads)[0]

        def _grads(params, batch, *state):
            # (loss, gradients, *state): the gradients as the workers' tree
            # where accum is 1 (the push ravels it), else the folded vector
            if accum == 1:
                return grad_fn(params, batch, *state)

            def to_micro(x):
                if x.shape[0] % accum:
                    raise ValueError(
                        f"batch dim {x.shape[0]} must divide by "
                        f"accum={accum}")
                return x.reshape((accum, x.shape[0] // accum) + x.shape[1:])

            micro = jax.tree.map(to_micro, batch)

            def fold(carry, mb):
                loss_sum, gsum = carry
                loss, grads = grad_fn(params, mb)
                return (loss_sum + loss, gsum + to_wire(grads)), None

            # fresh carries are axis-invariant but fold outputs vary
            # wherever params OR batch do (a replicated batch still yields
            # varying grads via the all-gathered params) — pcast keeps the
            # scan carry type fixed
            vma = frozenset()
            for leaf in jax.tree.leaves((params, batch)):
                vma = vma | jax.typeof(leaf).vma
            loss0, g0 = jnp.zeros((), jnp.float32), jnp.zeros(n)
            need = tuple(sorted(vma))
            if need:
                loss0 = jax.lax.pcast(loss0, need, to="varying")
                g0 = jax.lax.pcast(g0, need, to="varying")
            (loss_sum, gsum), _ = jax.lax.scan(fold, (loss0, g0), micro)
            if reduce == "sum":
                # sum-semantics grad_fns: microbatch sums add up to the
                # full-batch sum — averaging would scale grads by 1/accum
                return loss_sum, gsum
            return loss_sum / accum, gsum / accum

        def local_step(p_shard, opt_shard, batch, *state):
            with jax.named_scope(prof.PULL):
                full = quantized_all_gather(p_shard, DATA_AXIS, comm)
                params = cast_floating(unravel(full[:n]), cd)
            with jax.named_scope(prof.GRAD):
                loss, grads, *state = _grads(params, batch, *state)
            with jax.named_scope(prof.PUSH):
                gflat = to_wire(grads) if accum == 1 else grads
                if pad:
                    # the padding keys' zeros ravel in with the leaves, in
                    # the workers' dtype, and the cast comes last, where
                    # the collective folds it in: a pad of the float32
                    # vector is a pass over it of its own
                    gpad = jnp.concatenate(
                        [gflat, jnp.zeros(pad, gflat.dtype)]
                    ).astype(jnp.float32)
                else:
                    # nothing to pad (one shard): the trace such a step
                    # has always had, pinned in tests/test_olmo_hybrid.py
                    gpad = jnp.zeros(padded, gflat.dtype).at[:n].set(gflat)
                g_shard = quantized_psum_scatter(gpad, DATA_AXIS, comm)
                if reduce == "mean":
                    g_shard = g_shard / num_workers
                if clip_norm:
                    # global-norm clip across ALL shards (the optax
                    # transform would only see this shard's slice)
                    sumsq = jax.lax.psum(jnp.sum(g_shard * g_shard),
                                         DATA_AXIS)
                    g_shard = g_shard * jnp.minimum(
                        1.0, clip_norm * jax.lax.rsqrt(
                            jnp.maximum(sumsq, 1e-16)))
            with jax.named_scope(prof.UPDATE):
                updates, opt_shard = tx.update(g_shard, opt_shard, p_shard)
                p_shard = optax.apply_updates(p_shard, updates)
            return (p_shard, opt_shard, jax.lax.pmean(loss, DATA_AXIS),
                    *state)

        carried = () if state is None else (P(),)
        step = jax.shard_map(
            local_step,
            mesh=self.mesh,
            in_specs=(self._pspec, self._opt_specs, bspec) + carried,
            out_specs=(self._pspec, self._opt_specs, P()) + carried,
        )
        # a stable name for the program and its trace, whatever the
        # caller called its grad_fn
        step.__name__ = step.__qualname__ = prof.DENSE_STEP_FN
        if jit:
            step = jax.jit(step, donate_argnums=(0, 1))
        return step

    def step_inplace(self, step, batch) -> jnp.ndarray:
        """Run a fused step against the table's own state."""
        with prof.span(prof.STEP):
            if step is not self._staged or prof.stale(prof.DENSE_STEP_FN):
                # the step's first call, or its program was built anew
                prof.stage(prof.DENSE_STEP_FN, step, self.params,
                           self.opt_state, batch,
                           *(() if self.state is None else (self.state,)))
                self._staged = step
            if self.state is None:
                self.params, self.opt_state, loss = step(
                    self.params, self.opt_state, batch)
            else:
                self.params, self.opt_state, loss, self.state = step(
                    self.params, self.opt_state, batch, self.state)
        return loss

    # ------------------------------------------------------------- state I/O
    def state_dict(self) -> dict:
        """Host copies for checkpointing (params + opt state). Multi-host
        safe: non-addressable (cross-process sharded) leaves are fetched
        with a process allgather — a collective, so every process must
        call this together (the reference's Dump is likewise coordinated,
        SURVEY.md §3.5)."""
        from minips_tpu.parallel.cluster import host_copy

        out = {
            "params": host_copy(self.params),
            "opt_state": jax.tree.map(host_copy, self.opt_state),
        }
        if self.state is not None:
            out["state"] = jax.tree.map(host_copy, self.state)
        return out

    def global_arrays(self) -> dict:
        """The live (sharded) jax arrays, for coordinated multi-host
        checkpointing: hand these to orbax so every process writes only
        its addressable shards (no host gather, no full copy anywhere) —
        the globally-sharded checkpoint path (SURVEY.md §5.4)."""
        return {"params": self.params, "opt_state": self.opt_state}

    def _at_own_padding(self, new, cur: jax.Array) -> jax.Array:
        """A checkpoint's range-sharded vector laid out as this table's
        ``cur``. Where the lengths differ the padding was the writer's
        (another shard count, or a table from before shards ended on a
        tile): what covers keys ``< num_keys`` is kept and the tail is
        this table's own: zeros, or adam8's codes and scales that stand
        for zero. ``cur`` has one entry a key or one a block of keys."""
        if np.shape(new) != cur.shape:
            keep = -(-self.num_keys // (self.padded // cur.shape[0]))
            if np.ndim(new) != 1 or np.shape(new)[0] < keep:
                raise ValueError(
                    f"checkpoint leaf of shape {np.shape(new)} does not "
                    f"cover the {keep} entries this table's "
                    f"{self.num_keys} keys need")
            from minips_tpu.parallel.cluster import host_copy

            new = np.concatenate([np.asarray(new)[:keep],
                                  host_copy(cur[keep:])])
        return jax.device_put(jnp.asarray(new), cur.sharding)

    def load_state_dict(self, state: dict) -> None:
        self.params = self._at_own_padding(state["params"], self.params)
        if self.state is not None and "state" in state:
            self.state = jax.tree.unflatten(
                jax.tree.structure(self.state),
                [jnp.asarray(x) for x in jax.tree.leaves(state["state"])])
        # Graft by leaf order, not structure: a checkpoint roundtrip turns
        # optax's namedtuple states into plain lists, but leaf order is
        # deterministic either way.
        cur_leaves, treedef = jax.tree.flatten(self.opt_state)
        specs = jax.tree.leaves(self._opt_specs,
                                is_leaf=lambda x: isinstance(x, P))
        # A leafless opt state (sgd: all EmptyState) writes no npz entry at
        # all, so the key may be legitimately absent from the checkpoint.
        new_leaves = jax.tree.leaves(state.get("opt_state", ()))
        if len(cur_leaves) != len(new_leaves):
            raise ValueError(
                f"opt state leaf count mismatch: table has "
                f"{len(cur_leaves)}, checkpoint has {len(new_leaves)} "
                "(different updater?)")
        self.opt_state = jax.tree.unflatten(treedef, [
            self._at_own_padding(new, cur) if spec == self._pspec
            else jax.device_put(jnp.asarray(new), cur.sharding)
            for cur, new, spec in zip(cur_leaves, new_leaves, specs)
        ])
