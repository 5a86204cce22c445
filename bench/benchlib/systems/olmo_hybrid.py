"""The ``olmo_hybrid`` system under test: the program's own fused dense PS
step for the hybrid of linear-attention and full-attention layers
(``minips_tpu/models/olmo_hybrid.py``), built by the very function
``apps/lm_example.run`` builds it with on the dp layout
(``lm_example.model_dp_step``, which takes the model by the file's
``model_type``: one ``DenseTable`` with Adam, range-sharded over the cell's
chips, ``DenseTable.make_step`` over ``olmo_hybrid.grad_fn``), from the
cell's configuration file. The weights are the benchmark's, made on the
device from the seed; batches go through the same ``device_put`` onto the
data axis that the app's ``prep`` makes: one sequence a chip. The model
carries nothing from step to step and has no router; after the window its
observer is read once (``info``). A program without the model (the parent
of the PR that brought it) fails at the first import, before anything is
built.
"""

from __future__ import annotations

import math
import sys

import numpy as np

from benchlib import init, traffic
from benchlib.reference import olmo_hybrid_ref
from benchlib.systems import lm
from benchlib.systems.joyai import _not_written_to_the_compile_cache


def _unit(key, shape, xp):
    """Uniform draws in (0, 1), from the benchmark's generator."""
    return (init.leaf_values(key, shape, 1.0, xp=xp)
            / xp.float32(3.0 ** 0.5) + xp.float32(1.0)) * xp.float32(0.5)


def _leaf(name: str, shape, key, config: dict, xp):
    """A leaf's initial values: gains one; ``A_log`` the log of a uniform
    draw in (0, 16), ``dt_bias`` the inverse softplus of a log-uniform draw
    in (1e-3, 1e-1) (the Gated Delta Networks implementation's); the
    convolutions' taps of standard deviation 0.5; 0.02 elsewhere, the
    residual projections ``wo`` and ``w_down`` scaled down by sqrt(2 *
    layers)."""
    last = name.rsplit(".", 1)[-1]
    if last == "g":
        return xp.full(shape, 1.0, xp.float32)
    if last == "A_log":
        return xp.log(xp.float32(1e-3)
                      + xp.float32(16.0 - 1e-3) * _unit(key, shape, xp))
    if last == "dt_bias":
        lo, hi = math.log(1e-3), math.log(1e-1)
        dt = xp.exp(xp.float32(lo) + xp.float32(hi - lo)
                    * _unit(key, shape, xp))
        return dt + xp.log(-xp.expm1(-dt))
    scale = 0.02
    if last in ("wo", "w_down"):
        scale = 0.02 / (2.0 * int(config["num_hidden_layers"])) ** 0.5
    elif last.startswith("conv_"):
        scale = 0.5
    return init.leaf_values(key, shape, scale, xp=xp)


def make_params(struct, names, config: dict, keys, xp):
    """The benchmark's initial weights in the program's tree ``struct``
    (shapes only): leaf i draws from ``keys[i]``."""
    import jax
    leaves = [_leaf(name, s.shape, keys[i], config, xp)
              for i, (name, s) in enumerate(zip(names,
                                                jax.tree.leaves(struct)))]
    return jax.tree.unflatten(jax.tree.structure(struct), leaves)


class System(lm.System):
    """The ``lm`` adapter's feed, step and state readers (``host_batch``,
    ``step``, ``observe_grad`` / ``observe_delta``, ``to_host``, ``free``)
    over another model, other weights and another reference."""

    def __init__(self, cell, seed: int, phases):
        from minips_tpu.models import olmo_hybrid   # absent: fail at once
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        from minips_tpu.apps.lm_example import model_dp_step
        from minips_tpu.parallel.mesh import DATA_AXIS, make_mesh

        self.cell, self.seed = cell, int(seed)
        c, mix = cell.config, cell.traffic
        self.config, self.mix = c, mix
        with phases("batches"):
            self.pool = traffic.make_pool(mix, self.seed)
        with phases("tables"):
            mesh = make_mesh(cell.chips)     # the cell's chips, no more
            self._devices = tuple(mesh.devices.reshape(-1))
            m = olmo_hybrid.from_config(c)
            self.struct = jax.eval_shape(
                lambda: olmo_hybrid.init(jax.random.PRNGKey(0), m))
            paths = jax.tree_util.tree_flatten_with_path(self.struct)[0]
            self.names = [lm._leaf_name(p) for p, _ in paths]
            self.sizes = [int(np.prod(s.shape)) for _, s in paths]
            self._keys = lm.leaf_keys(self.names, self.seed)
            # every leaf laid over the cell's chips as the reference lays
            # it, so that no chip holds the whole tree (3.5 GiB), its
            # ravel and the copy the table pads at once
            spread = olmo_hybrid_ref.placement(self._devices)[0]
            self._make = jax.jit(
                lambda keys: make_params(self.struct, self.names, c, keys,
                                         jnp),
                out_shardings=jax.tree.map(lambda s: spread(s.shape),
                                           self.struct))
            self._sharding = NamedSharding(mesh, P(DATA_AXIS))
            self.model, self.table, self._step, self._stats = model_dp_step(
                c, mesh, self._make(self._keys),
                lm.System.put(self, self.host_batch(0)),
                updater=c["updater"], lr=float(c["lr"]))
        self.samples_per_step = traffic.samples_per_step(mix)
        self.tokens_per_step = traffic.tokens_per_step(mix)
        self.check_steps = 3
        self._observe = self._make_observers()
        self._last = None

    def put(self, batch: dict):
        self._last = super().put(batch)     # what the observer reads
        return self._last

    def _make_observers(self):
        import jax
        import jax.numpy as jnp
        sizes = self.sizes

        def delta(p, keys):
            # leaf by leaf, so that the start is never whole beside the
            # table: 3.7 GB of it would be
            p0 = make_params(self.struct, self.names, self.config, keys,
                             jnp)
            out, at = [], 0
            for x0, size in zip(jax.tree.leaves(p0), sizes):
                out.append(jnp.sqrt(jnp.sum(jnp.square(
                    p[at: at + size] - x0.reshape(-1)))))
                at += size
            return jnp.stack(out)

        return dict(super()._make_observers(), delta=jax.jit(delta))

    # ------------------------------------------------------------ the rest
    def info(self) -> dict:
        """Read once, after the window: the loss and the linear layers'
        decay, write strength and state of the last batch fed under the
        weights the window ended with."""
        import jax
        out = {"params": sum(self.sizes)}
        if self.table is None or self._last is None:
            return out
        st = jax.device_get(self._stats(self.table.pull(), self._last))
        out.update(lm_nll=float(st["lm_nll"]),
                   **{k: st[k].tolist() for k in (
                       "decay_mean", "beta_mean", "state_absmax")})
        print(f"olmo_hybrid after the window, by linear layer: mean decay "
              f"{out['decay_mean']}, mean write strength "
              f"{out['beta_mean']}, largest entry of a state "
              f"{out['state_absmax']}; lm.nll {out['lm_nll']:.6f}",
              file=sys.stderr)
        return out

    def free(self) -> None:
        self._last = None
        super().free()

    def reference(self, *, low=False, keep: float = 1.0,
                  fault=None) -> dict:
        batches = [self.host_batch(i) for i in range(self.check_steps)]
        with _not_written_to_the_compile_cache():
            return olmo_hybrid_ref.run(
                self.config, batches, lambda: self._make(self._keys),
                self.names, low=low, keep=keep, fault=fault,
                rows_per_block=int(self.config.get("reference_rows", 1)),
                devices=self._devices)


def build(cell, seed: int, phases) -> System:
    return System(cell, seed, phases)


def control_readings(sound: System, phases) -> dict:
    """The control's readings of the first steps of ``sound``'s cell and
    seed. The program has no path of its own below what the configuration
    states: the reference, put in its place, one step below each: bfloat16
    activations with fp8 matmul inputs where the configuration states
    bfloat16, the state and the decay in bfloat16 where it states
    float32. (``sound.reference(low="state")`` is the second alone: PERF.md
    section 6 has its reading at the cell's size.)"""
    return sound.reference(low=True)


# the planted faults, for bench/tools/check_faults.py: name -> the
# reference's arguments. A quarter of the batch is what chip 0 alone
# computes when the exchange between the cell's four chips is left out
# (bench/tests/test_correct.py plants the same by feeding every chip chip
# 0's shard)
FAULTS = {"fault_no_exchange": {"keep": 0.25},
          **{"fault_" + f: {"fault": f} for f in olmo_hybrid_ref.FAULTS}}
