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

The table keeps the template's leaves (``_Leaves``) and its updater's
state over them, on any number of shards. On ONE shard there is no range
to own and no wire: the vectors are stacked by length, so that a step
passes fewer arrays, and the step pulls by a cast and pushes by the cast
back. On several, each leaf whose shape divides into shards of whole
tiles of the chip is sharded on that dimension (``shard_dim``), and the
rest (gains, biases, narrow matrices) lie in one flat remainder,
range-sharded as the whole vector was; the step gathers and
reduce-scatters leaf by leaf, so that the compiler can run each
collective under the model's compute, with no ravel, unravel or pad of
the whole. The key-space vector is then a view (``params``,
``opt_state``), made by one jitted function when somebody reads it
(``ps.table.view``); the fused step never reads it. adam8's blockwise
moments need the flat vector, so that updater keeps it on any number of
shards.
"""

from __future__ import annotations

import functools
import math
import operator
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from minips_tpu.ops.quantized_comm import (quantized_all_gather,
                                           quantized_psum_scatter)
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


def _clip_by_global_norm(g: PyTree, clip_norm: float) -> PyTree:
    """Global-norm clip across ALL shards of ``g`` (the optax transform
    would only see this shard's slice), inside the table's shard_map."""
    sumsq = jax.lax.psum(functools.reduce(
        operator.add, [jnp.sum(x * x) for x in jax.tree.leaves(g)]),
        DATA_AXIS)
    scale = jnp.minimum(1.0, clip_norm * jax.lax.rsqrt(
        jnp.maximum(sumsq, 1e-16)))
    return jax.tree.map(lambda x: x * scale, g)


class _Stacks(tuple):
    """A table's parameters, or a part of its updater's state shaped as
    they are: ``_Leaves.groups``' leaves, each group's stacked on a
    leading axis on one shard; on several, each sharded leaf as it is and
    the flat remainder last."""


jax.tree_util.register_pytree_node(
    _Stacks, lambda s: (tuple(s), None), lambda _, xs: _Stacks(xs))


def _is_stacks(x) -> bool:
    return isinstance(x, _Stacks)


def _is_spec(x) -> bool:
    return isinstance(x, P)


def shard_dim(shape, shards: int) -> Optional[int]:
    """The dimension a leaf of ``shape`` is sharded on over ``shards``: the
    first that divides into shards of whole tiles of the chip (a multiple
    of 8 rows on the second-minor dimension, of 128 lanes on the minor
    one, any count on a dimension before them). None for a vector, and for
    a matrix narrower than one lane tile, which the chip pads to 128 lanes
    a row: such a leaf lies in the flat remainder, dense. A shard that
    ends off the tile is one the pull's all-gather cannot place: the
    compiler builds it as an all-reduce of zero-padded shards."""
    if len(shape) < 2 or shape[-1] < 128:
        return None
    for d, size in enumerate(shape):
        tile = {len(shape) - 1: 128, len(shape) - 2: 8}.get(d, 1)
        if size % shards == 0 and (size // shards) % tile == 0:
            return d
    return None


def _gather(x: jax.Array, dim: int, comm: str, dtype) -> jax.Array:
    """The pull of one part inside the step: its shards gathered on
    ``dim``, in ``dtype`` where that is given. The float32 wire gathers
    the cast (the cast commutes with the gather); a compressed wire
    gathers the part's shards raveled with ``dim`` leading."""
    if comm == "float32":
        return jax.lax.all_gather(x if dtype is None else x.astype(dtype),
                                  DATA_AXIS, axis=dim, tiled=True)
    m = jnp.moveaxis(x, dim, 0)
    full = quantized_all_gather(m.reshape(-1), DATA_AXIS, comm)
    full = jnp.moveaxis(full.reshape((-1,) + m.shape[1:]), 0, dim)
    return full if dtype is None else full.astype(dtype)


def _scatter(g: jax.Array, dim: int, comm: str) -> jax.Array:
    """The push of one part inside the step: the workers' float32
    gradients summed into this shard of ``dim``."""
    if comm == "float32":
        return jax.lax.psum_scatter(g, DATA_AXIS, scatter_dimension=dim,
                                    tiled=True)
    m = jnp.moveaxis(g, dim, 0)
    part = quantized_psum_scatter(m.reshape(-1), DATA_AXIS, comm)
    return jnp.moveaxis(part.reshape((-1,) + m.shape[1:]), 0, dim)


class _Leaves:
    """Where a table keeps its parameters, and its key space as a view of
    them. The key space is ``ravel_pytree``'s: the leaves in the tree's
    order, each raveled, in their common dtype.

    On one shard each array a step passes costs the host its dispatch, so
    the vectors (gains, biases, scales: half of a model's leaves) are
    stacked by length; every larger leaf stands alone, since a stack's
    gradient is one more array the step holds whole, and one the size of
    a model's matrices does not fit beside them. On ``shards`` > 1 each
    leaf ``shard_dim`` finds a dimension for stands alone, sharded on it
    (``dims``), and the rest are raveled in key order into one flat
    remainder, padded to shards that end on the chip's tile
    (``SHARD_TILE``) and range-sharded: ``groups`` holds the sharded
    leaves and then the remainder's."""

    def __init__(self, template: PyTree, shards: int = 1):
        leaves, self.treedef = jax.tree.flatten(template)
        self.dtypes = [jnp.result_type(x) for x in leaves]
        self.dtype = jnp.result_type(*self.dtypes)
        self.shapes = [tuple(np.shape(x)) for x in leaves]
        at = np.cumsum([0] + [math.prod(s) for s in self.shapes]).tolist()
        self.spans = list(zip(at[:-1], at[1:]))
        self.size = at[-1]
        self.shards = shards
        self.groups = []          # the leaves of each stack, in order
        if shards > 1:
            dims = [shard_dim(s, shards) for s in self.shapes]
            self.groups = [[i] for i, d in enumerate(dims) if d is not None]
            self.dims = [dims[g[0]] for g in self.groups]
            self.rest = [i for i, d in enumerate(dims) if d is None]
            self.rest_keys = sum(math.prod(self.shapes[i])
                                 for i in self.rest)
            self.rest_padded = RangePartitioner(
                self.rest_keys, shards, align=SHARD_TILE).padded
            if self.rest:
                self.groups.append(self.rest)
                self.dims.append(0)
            # the key space as the views hand it out: the padded vector a
            # range-sharded table on as many shards holds
            self.padded = RangePartitioner(self.size, shards,
                                           align=SHARD_TILE).padded
            ndims = [len(self.shapes[g[0]]) for g in self.groups]
            if self.rest:
                ndims[-1] = 1
            self.specs = _Stacks(
                P(*[DATA_AXIS if k == d else None for k in range(nd)])
                for nd, d in zip(ndims, self.dims))
            return
        vectors: dict = {}
        for i, s in enumerate(self.shapes):
            if len(s) > 1:
                self.groups.append([i])
            elif s in vectors:
                vectors[s].append(i)
            else:
                vectors[s] = [i]
                self.groups.append(vectors[s])
        self.padded = self.size
        self.specs = P()

    # lax, not jnp: a view traces these once a leaf, a step as well
    def _stack(self, xs: list) -> _Stacks:
        if self.shards > 1:
            parts = [xs[g[0]] for g in self.groups[:len(self.groups)
                                                   - bool(self.rest)]]
            if self.rest:
                flat = [jax.lax.reshape(xs[i], (math.prod(self.shapes[i]),))
                        for i in self.rest]
                pad = self.rest_padded - self.rest_keys
                if pad:
                    flat.append(jnp.zeros(pad, xs[self.rest[0]].dtype))
                parts.append(jax.lax.concatenate(flat, 0))
            return _Stacks(parts)
        return _Stacks(jax.lax.concatenate(
            [jax.lax.expand_dims(xs[i], (0,)) for i in g], 0)
            for g in self.groups)

    def _unstack(self, stacks: _Stacks) -> list:
        xs = [None] * len(self.shapes)
        if self.shards > 1:
            for g, x in zip(self.groups, stacks):
                xs[g[0]] = x
            if self.rest:
                at = 0
                for i in self.rest:
                    size = math.prod(self.shapes[i])
                    xs[i] = jax.lax.reshape(jax.lax.slice_in_dim(
                        stacks[-1], at, at + size), self.shapes[i])
                    at += size
            return xs
        for g, s in zip(self.groups, stacks):
            for k, i in enumerate(g):
                xs[i] = (jax.lax.index_in_dim(s, k, keepdims=False)
                         if len(g) > 1 else jax.lax.squeeze(s, (0,)))
        return xs

    def stack(self, tree: PyTree, dtype=None) -> _Stacks:
        """A params-shaped tree (the template, gradients, a mask) laid out
        as the table holds it, in ``dtype`` (None: the leaves' own)."""
        return self._stack([jnp.asarray(x, dtype)
                            for x in self.treedef.flatten_up_to(tree)])

    def tree(self, stacks: _Stacks, dtype=None) -> PyTree:
        """The workers' tree: each leaf in the template's dtype, or in
        ``dtype`` where the template's is floating."""
        def cast(x, d):
            return x.astype(dtype if dtype is not None and jnp.issubdtype(
                d, jnp.floating) else d)

        return self.treedef.unflatten(
            [cast(x, d) for x, d in zip(self._unstack(stacks), self.dtypes)])

    def ravel(self, stacks: _Stacks) -> jax.Array:
        """The key space: the vector ``ravel_pytree`` makes of the tree.
        The leaves of one shape are laid out row by row together: the chip
        keeps a matrix whose minor dimension is no multiple of 128 with its
        dimensions swapped, and one relayout a shape in place of one a
        leaf makes the program a seventh the size (my compile, PR 39:
        14.9 MB of code where 110 took 0.75 s to load in ``gpt2-xl``)."""
        xs = self._unstack(stacks)
        if self.shards > 1:
            # the range-sharded vector, padding keys' zeros and all, that a
            # table on the vector holds over as many shards
            return jnp.concatenate(
                [x.reshape(-1) for x in xs]
                + [jnp.zeros(self.padded - self.size, xs[0].dtype)])
        by_shape: dict = {}
        for i, x in enumerate(xs):
            by_shape.setdefault(x.shape, []).append(i)
        out = jnp.zeros(self.size, xs[0].dtype)
        for idx in by_shape.values():
            rows = jax.lax.reshape(jax.lax.concatenate(
                [jax.lax.expand_dims(xs[i], (0,)) for i in idx], 0),
                (len(idx), math.prod(xs[idx[0]].shape)))
            for k, i in enumerate(idx):
                out = jax.lax.dynamic_update_slice(
                    out, jax.lax.index_in_dim(rows, k, keepdims=False),
                    (self.spans[i][0],))
        return out

    def _split(self, flat: jax.Array) -> list:
        return [jax.lax.reshape(jax.lax.slice_in_dim(flat, a, b), s)
                for (a, b), s in zip(self.spans, self.shapes)]

    def unravel(self, flat: jax.Array) -> _Stacks:
        return self._stack(self._split(flat))

    def unravel_tree(self, flat: jax.Array) -> PyTree:
        """``ravel_pytree``'s ``unravel``."""
        return self.treedef.unflatten(
            [x.astype(d) for x, d in zip(self._split(flat), self.dtypes)])


def _needs_flat(updater: str, tx, layout: _Leaves) -> bool:
    """An updater whose state is blocks of contiguous keys (adam8's
    quantized moments) runs on the flat vector. A user's transform is
    asked for its state over the key space as a sharded table pads it."""
    if tx is None:
        return updater == "adam8"
    state = jax.eval_shape(
        tx.init, jax.ShapeDtypeStruct((layout.padded,), layout.dtype))
    return any(isinstance(x, Adam8bitState) for x in jax.tree.leaves(
        state, is_leaf=lambda x: isinstance(x, Adam8bitState)))


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

        # shapes only: no array is made
        layout = _Leaves(template, self.num_shards)
        self.num_keys = layout.size
        self._dtype = layout.dtype
        # the table keeps the leaves, unless its updater works on blocks
        # of contiguous keys
        self._leaves = None if _needs_flat(updater, tx, layout) else layout
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
        sharded = self._leaves is not None and self.num_shards > 1
        # the zero keys the table holds: a sharded table on its leaves
        # pads its flat remainder alone
        prof.counter(prof.TABLE_PAD_KEYS,
                     layout.rest_padded - layout.rest_keys if sharded
                     else self.padded - self.num_keys if self._leaves is None
                     else 0)
        if sharded:
            prof.counter(prof.TABLE_SHARDED_LEAVES,
                         len(layout.groups) - bool(layout.rest))
            prof.counter(prof.TABLE_FLAT_KEYS, layout.rest_keys)
        self._shard_shape = (self.padded // self.num_shards,)
        # clip-by-global-norm must see the GLOBAL gradient, but the optax
        # transform runs on one owner shard inside shard_map — intercept
        # and apply it in the fused step with a cross-shard psum instead
        self._clip_norm = float(kw.pop("clip_norm", 0.0) or 0.0)
        if kw.get("decay_mask") is not None:
            # a params-shaped pytree mask (e.g. transformer.decay_mask)
            # is held as the params are; padding rows never decay (they
            # are zeros and must stay zeros)
            mask = kw["decay_mask"]
            msize = sum(int(np.size(x)) for x in jax.tree.leaves(mask))
            if msize != self.num_keys:
                raise ValueError(
                    f"decay_mask ravels to ({msize},), params to "
                    f"({self.num_keys},) — the mask must be params-shaped")
            kw["decay_mask"] = (
                layout.stack(mask, layout.dtype) if self._leaves is not None
                else jnp.zeros(self.padded, self._dtype)
                .at[: self.num_keys].set(ravel_pytree(mask)[0]))
        self.tx = tx if tx is not None else make_updater(updater, lr, **kw)

        self._pspec = P(DATA_AXIS)
        self._sharding = NamedSharding(mesh, self._pspec)
        if self._leaves is not None:
            self._unravel = layout.unravel_tree
            # one shard: one device holds the whole, the same on every
            # worker, and the push's sum over workers makes it so; on
            # several each part of the state is sharded as the
            # parameters' part it stands beside
            self._state_spec = layout.specs
            self._opt_specs = P() if self.num_shards == 1 else jax.tree.map(
                lambda x: layout.specs if _is_stacks(x) else P(),
                jax.eval_shape(lambda t: self.tx.init(
                    layout.stack(t, layout.dtype)), template),
                is_leaf=_is_stacks)
            self._p, self._opt = jax.jit(
                lambda t: (lambda s: (s, self.tx.init(s)))(
                    layout.stack(t, layout.dtype)),
                out_shardings=self._on(
                    (self._state_spec, self._opt_specs)))(template)
        else:
            self._state_spec = self._pspec
            flat, self._unravel = ravel_pytree(template)
            # pad straight into the sharded layout: the padded copy never
            # exists whole on one device (the template is the caller's)
            self._p = jax.jit(
                lambda f: jnp.zeros(self.padded, f.dtype)
                .at[: self.num_keys].set(f),
                out_shardings=self._sharding)(flat)
            opt_state = jax.eval_shape(self.tx.init, self._p)
            a8 = [x for x in jax.tree.leaves(
                      opt_state,
                      is_leaf=lambda l: isinstance(l, Adam8bitState))
                  if isinstance(x, Adam8bitState)]
            block = a8[0].mu_q.shape[0] // a8[0].mu_s.shape[0] if a8 else 0
            if block and self._shard_shape[0] % block:
                raise ValueError(
                    f"quantized opt state with block={block} does not "
                    f"align with shard size {self._shard_shape[0]}: each "
                    "contiguous range shard must hold whole blocks (use "
                    "updater='adam8' so the table aligns its padding, or "
                    "pick a block that divides the shard size)")
            self._opt_specs = self._opt_specs_tree(opt_state)
            opt_shardings = jax.tree.map(
                lambda s: NamedSharding(mesh, s), self._opt_specs,
                is_leaf=lambda x: isinstance(x, P))
            # Note: specs describe the *global* opt leaves; inside
            # shard_map sharded leaves have the per-shard shape.
            self._opt = jax.jit(
                self.tx.init, out_shardings=opt_shardings)(self._p)
        self.state = None       # see make_step(state=...)
        self._staged = None     # the step whose program step_inplace staged

    def _on(self, specs) -> PyTree:
        """``specs``, a spec or a tree of them, as shardings on the mesh."""
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs,
                            is_leaf=_is_spec)

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

    # ------------------------------------------------------ the key space
    @property
    def step_state(self) -> tuple:
        """``(params, opt_state)`` as the fused step takes and hands them
        back: the table's parts (``_Leaves``) and its updater's state over
        them, or adam8's range-sharded vector and its state. For a caller
        that drives or lowers ``make_step``'s step itself, and sets what it
        hands back."""
        return self._p, self._opt

    @step_state.setter
    def step_state(self, state: tuple) -> None:
        self._p, self._opt = state

    def tree(self, p) -> PyTree:
        """The workers' tree of ``step_state``'s params, traceable: what a
        step that drives the table itself differentiates through."""
        if self._leaves is not None:
            return self._leaves.tree(p)
        return self._unravel(p[: self.num_keys])

    @property
    def params(self) -> jax.Array:
        """The key space 0..padded-1 as one range-sharded vector: the
        table's own (adam8), or a view of its leaves made on each read
        (``ps.table.view``). Assigning a vector sets the table."""
        if self._leaves is None:
            return self._p
        prof.counter(prof.TABLE_VIEW, 1)
        return self._ravel(jax.block_until_ready(self._p))

    @params.setter
    def params(self, flat: jax.Array) -> None:
        self._p = flat if self._leaves is None else self._unravel_keys(flat)

    @property
    def opt_state(self) -> PyTree:
        """The updater's state over the key space: each params-shaped part
        a vector in the same positions as ``params`` (a view on one
        shard, as ``params`` is; its other leaves are the table's own).
        Assigning such a state sets the table's."""
        if self._leaves is None:
            return self._opt
        prof.counter(prof.TABLE_VIEW, 1)
        return jax.tree.map(
            lambda x: self._ravel(x) if _is_stacks(x) else x,
            jax.block_until_ready(self._opt), is_leaf=_is_stacks)

    @opt_state.setter
    def opt_state(self, state: PyTree) -> None:
        if self._leaves is None:
            self._opt = state
            return
        nodes, treedef = jax.tree.flatten(self._opt, is_leaf=_is_stacks)
        leaves = jax.tree.leaves(state)
        if len(leaves) != len(nodes):
            raise ValueError(
                f"opt state of {len(leaves)} leaves for a table whose "
                f"updater keeps {len(nodes)}")
        self._opt = treedef.unflatten(
            [self._unravel_keys(x) if _is_stacks(node) else x
             for x, node in zip(leaves, nodes)])

    # A view waits for the step in flight: it is as large as the state it
    # reads, and the step holds its temporaries until it ends (ZAYA's
    # warm run died of a view beside its first step: my chip run, PR 39).
    # The params and each params-shaped part of the updater's state share
    # ONE program each way, so a process reads and loads it once.
    @functools.cached_property
    def _ravel(self):
        return jax.jit(self._leaves.ravel, out_shardings=self._sharding)

    @functools.cached_property
    def _unravel_keys(self):
        return jax.jit(self._leaves.unravel,
                       out_shardings=self._on(self._state_spec))

    @functools.cached_property
    def _pull_tree(self):
        return jax.jit(self._leaves.tree)

    # ------------------------------------------------------------------ pull
    def pull(self) -> PyTree:
        """Full parameter pytree (all-gather of the owner shards).

        Reference: ``KVClientTable::Pull/Get`` over all keys (SURVEY.md §2
        "KVClientTable"). Under jit this is an all-gather on ICI; as a host
        call it just reads the (distributed) array.
        """
        if self._leaves is not None:
            return self._pull_tree(jax.block_until_ready(self._p))
        return self.tree(self._p)

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
        if self._leaves is None:
            gflat, _ = ravel_pytree(grads)
            grads = (jnp.zeros(self.padded, gflat.dtype)
                     .at[: self.num_keys].set(gflat))
        self._p, self._opt = self._jit_apply(self._p, self._opt, grads)

    def push_keys(self, keys: np.ndarray, vals: jnp.ndarray) -> None:
        """Sparse additive push into a dense table (emulation path).

        Per-key server semantics (SURVEY.md §3.3 ``updater->Update(keys,
        grads)``): only the pushed keys' parameters and elementwise
        optimizer state move; untouched keys are masked out so stateful
        updaters (adam/momentum) do not drift them. Scalar opt-state
        (e.g. adam's step count) still advances once per push.
        """
        keys = jnp.asarray(keys)
        flat = jnp.zeros(self.padded, self._dtype).at[keys].add(vals)
        mask = jnp.zeros(self.padded, self._dtype).at[keys].set(1.0)
        self._p, self._opt = self._jit_apply_masked(
            self._p, self._opt, flat, mask)

    def _make_apply(self, masked: bool):
        vec_shard = self._shard_shape
        spec = self._state_spec
        in_specs = (spec, self._opt_specs, spec) + ((spec,) if masked else ())

        clip_norm = self._clip_norm

        def apply_shard(p_shard, opt_shard, g_shard, *mask):
            if clip_norm:
                # same cross-shard global-norm clip as the fused step —
                # a clip_norm kwarg must never be a silent no-op on the
                # push()/push_keys() paths
                g_shard = _clip_by_global_norm(g_shard, clip_norm)
            updates, new_opt = self.tx.update(g_shard, opt_shard, p_shard)
            if masked:
                m = mask[0]
                updates = jax.tree.map(jnp.multiply, updates, m)

                def restore(new, old):
                    # quantized moments restore at BLOCK granularity —
                    # an elementwise where() on the codes alone leaves
                    # them paired with recomputed scales (ADVICE r4
                    # medium: silent moment drift on untouched keys)
                    if isinstance(new, Adam8bitState):
                        return masked_merge_adam8(new, old, m)
                    if _is_stacks(new):
                        return jax.tree.map(
                            lambda a, b, k: jnp.where(k > 0, a, b),
                            new, old, m)
                    return (jnp.where(m > 0, new, old)
                            if getattr(new, "shape", ()) == vec_shard
                            else new)

                new_opt = jax.tree.map(
                    restore, new_opt, opt_shard,
                    is_leaf=lambda x: isinstance(x, (Adam8bitState,
                                                     _Stacks)))
            return optax.apply_updates(p_shard, updates), new_opt

        apply = jax.shard_map(apply_shard, mesh=self.mesh, in_specs=in_specs,
                              out_specs=(spec, self._opt_specs))
        L = self._leaves
        if L is not None:
            flat_apply = apply

            def apply(p, opt, g, *mask):
                # push hands the workers' tree, push_keys the key space
                return flat_apply(p, opt,
                                  L.unravel(g) if masked
                                  else L.stack(g, L.dtype),
                                  *map(L.unravel, mask))
        return jax.jit(apply, donate_argnums=(0, 1))

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
        shard (server update), on a table of several shards one of each a
        part, so that the compiler can run each under the model's compute.
        BSP is implicit — the collectives are the barrier (SURVEY.md §2
        "BSPModel").

        ``comm`` compresses the two collectives' wire format ("bfloat16" or
        "int8"; EQuARX-style, see ops/quantized_comm.py). Params and the
        optimizer update stay float32 — only bytes-on-wire change, part by
        part; a table on one shard has no wire, and nothing to compress.

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
        L = self._leaves        # the leaves (None: the vector)
        sharded = L is not None and num_workers > 1
        bspec = batch_spec if batch_spec is not None else P(DATA_AXIS)
        if accum < 1:
            raise ValueError(f"accum must be >= 1, got {accum}")
        if state is not None and accum > 1:
            raise ValueError("a step-to-step state and accum > 1: which "
                             "microbatch's state is handed on is undefined")
        # placed as the step hands it on: a state placed otherwise (a
        # router's bias made on the default device) builds the step twice
        self.state = None if state is None else jax.device_put(
            state, NamedSharding(self.mesh, P()))
        from minips_tpu.ops.quantized_comm import _check
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
            # into the table's one vector, which the leaves do not keep
            if L is not None:
                return cast_floating(grads, L.dtype)
            if cd is not None and not pad:
                grads = cast_floating(grads, jnp.float32)
            return ravel_pytree(grads)[0]

        def _grads(params, batch, *state):
            # (loss, gradients, *state): the gradients as the workers' tree
            # where accum is 1, else the folded vector, or the folded tree
            # on the leaves
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
                return (loss_sum + loss,
                        jax.tree.map(jnp.add, gsum, to_wire(grads))), None

            # fresh carries are axis-invariant but fold outputs vary
            # wherever params OR batch do (a replicated batch still yields
            # varying grads via the all-gathered params) — pcast keeps the
            # scan carry type fixed
            vma = frozenset()
            for leaf in jax.tree.leaves((params, batch)):
                vma = vma | jax.typeof(leaf).vma
            loss0 = jnp.zeros((), jnp.float32)
            g0 = jnp.zeros(n) if L is None else jax.tree.map(
                lambda x: jnp.zeros(x.shape, L.dtype), params)
            need = tuple(sorted(vma))
            if need:
                loss0 = jax.lax.pcast(loss0, need, to="varying")
                g0 = jax.tree.map(
                    lambda x: jax.lax.pcast(x, need, to="varying"), g0)
            (loss_sum, gsum), _ = jax.lax.scan(fold, (loss0, g0), micro)
            if reduce == "sum":
                # sum-semantics grad_fns: microbatch sums add up to the
                # full-batch sum — averaging would scale grads by 1/accum
                return loss_sum, gsum
            return loss_sum / accum, jax.tree.map(lambda g: g / accum, gsum)

        def local_step(p_shard, opt_shard, batch, *state):
            with jax.named_scope(prof.PULL):
                if sharded:
                    # leaf by leaf, each on its own dimension: the model's
                    # first layers wait for their own leaves alone
                    params = L.tree(_Stacks(
                        _gather(x, d, comm, cd)
                        for x, d in zip(p_shard, L.dims)), cd)
                elif L is not None:
                    # typed as the gathered vector is: varying over the
                    # workers (a model's custom gradient rules expect it)
                    params = cast_floating(L.tree(jax.lax.pcast(
                        p_shard, DATA_AXIS, to="varying")), cd)
                else:
                    full = quantized_all_gather(p_shard, DATA_AXIS, comm)
                    params = cast_floating(unravel(full[:n]), cd)
            with jax.named_scope(prof.GRAD):
                loss, grads, *state = _grads(params, batch, *state)
            with jax.named_scope(prof.PUSH):
                gflat = to_wire(grads) if accum == 1 else grads
                if sharded:
                    # leaf by leaf: a leaf's sum can start as soon as its
                    # gradient is made
                    g_shard = _Stacks(
                        _scatter(g, d, comm)
                        for g, d in zip(L.stack(gflat, L.dtype), L.dims))
                elif L is not None:
                    # the sum over the workers: one worker's, which the
                    # compiler drops
                    g_shard = jax.lax.psum(L.stack(gflat), DATA_AXIS)
                elif pad:
                    # the padding keys' zeros ravel in with the leaves, in
                    # the workers' dtype, and the cast comes last, where
                    # the collective folds it in: a pad of the float32
                    # vector is a pass over it of its own
                    gpad = jnp.concatenate(
                        [gflat, jnp.zeros(pad, gflat.dtype)]
                    ).astype(jnp.float32)
                else:
                    # nothing to pad: the trace such a step has always had
                    gpad = jnp.zeros(padded, gflat.dtype).at[:n].set(gflat)
                if L is None:
                    g_shard = quantized_psum_scatter(gpad, DATA_AXIS, comm)
                if reduce == "mean":
                    g_shard = jax.tree.map(lambda g: g / num_workers,
                                           g_shard)
                if clip_norm:
                    g_shard = _clip_by_global_norm(g_shard, clip_norm)
            with jax.named_scope(prof.UPDATE):
                updates, opt_shard = tx.update(g_shard, opt_shard, p_shard)
                p_shard = optax.apply_updates(p_shard, updates)
            return (p_shard, opt_shard, jax.lax.pmean(loss, DATA_AXIS),
                    *state)

        carried = () if state is None else (P(),)
        step = jax.shard_map(
            local_step,
            mesh=self.mesh,
            in_specs=(self._state_spec, self._opt_specs, bspec) + carried,
            out_specs=(self._state_spec, self._opt_specs, P()) + carried,
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
                prof.stage(prof.DENSE_STEP_FN, step, self._p, self._opt,
                           batch,
                           *(() if self.state is None else (self.state,)))
                self._staged = step
            if self.state is None:
                self._p, self._opt, loss = step(self._p, self._opt, batch)
            else:
                self._p, self._opt, loss, self.state = step(
                    self._p, self._opt, batch, self.state)
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
            self.state = jax.device_put(jax.tree.unflatten(
                jax.tree.structure(self.state),
                [jnp.asarray(x) for x in jax.tree.leaves(state["state"])]),
                NamedSharding(self.mesh, P()))
        # Graft by leaf order, not structure: a checkpoint roundtrip turns
        # optax's namedtuple states into plain lists, but leaf order is
        # deterministic either way.
        cur_leaves, treedef = jax.tree.flatten(self.opt_state)
        if self._leaves is None:
            keyed = [spec == self._pspec for spec in jax.tree.leaves(
                self._opt_specs, is_leaf=lambda x: isinstance(x, P))]
        else:
            keyed = [_is_stacks(x) for x in jax.tree.leaves(
                self._opt, is_leaf=_is_stacks)]
        # A leafless opt state (sgd: all EmptyState) writes no npz entry at
        # all, so the key may be legitimately absent from the checkpoint.
        new_leaves = jax.tree.leaves(state.get("opt_state", ()))
        if len(cur_leaves) != len(new_leaves):
            raise ValueError(
                f"opt state leaf count mismatch: table has "
                f"{len(cur_leaves)}, checkpoint has {len(new_leaves)} "
                "(different updater?)")
        self.opt_state = jax.tree.unflatten(treedef, [
            self._at_own_padding(new, cur) if k
            else jax.device_put(jnp.asarray(new), cur.sharding)
            for cur, new, k in zip(cur_leaves, new_leaves, keyed)
        ])
