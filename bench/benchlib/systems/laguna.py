"""The ``laguna`` system under test: the program's own fused dense PS step
for the window/full hybrid expert decoder (``minips_tpu/models/
laguna.py``), built by the very function ``apps/lm_example.run`` builds it
with on the dp layout (``lm_example.model_dp_step``, which takes the model
by the file's ``model_type``: one ``DenseTable`` with Adam,
``DenseTable.make_step`` over ``laguna.grad_fn``), from the cell's
configuration file. The weights are the benchmark's, made on the device
from the seed; batches go through the same ``device_put`` onto the data
axis that the app's ``prep`` makes, and the first of them is what the
builder starts the routers' balancing bias from. After the window the
observer is read once (``info``): the
assignments the held experts really got are what the expert layer's FLOPs
are counted from. A program without the model (the parent of the PR that
brought it) fails at the first import, before anything is built.
"""

from __future__ import annotations

import sys

import numpy as np

from benchlib import init, readstate, traffic
from benchlib.reference import laguna_ref
from benchlib.systems import lm
from benchlib.systems.joyai import _not_written_to_the_compile_cache


def _init(name: str, config: dict):
    """(constant, None) or (None, standard deviation) of a leaf's initial
    values: gains one; 0.02, the residual projections ``wo`` and
    ``w_down`` scaled down by sqrt(2 * layers)."""
    last = name.rsplit(".", 1)[-1]
    if last == "g":
        return 1.0, None
    if last in ("wo", "w_down"):
        return None, 0.02 / (2.0 * int(config["num_hidden_layers"])) ** 0.5
    return None, 0.02


def make_params(struct, names, config: dict, keys, xp):
    """The benchmark's initial weights in the program's tree ``struct``
    (shapes only): leaf i draws from ``keys[i]``."""
    import jax
    leaves = []
    for i, (name, s) in enumerate(zip(names, jax.tree.leaves(struct))):
        fill, scale = _init(name, config)
        leaves.append(xp.full(s.shape, fill, xp.float32) if scale is None
                      else init.leaf_values(keys[i], s.shape, scale, xp=xp))
    return jax.tree.unflatten(jax.tree.structure(struct), leaves)


class System(lm.System):
    """The ``lm`` adapter's feed, step and state readers (``host_batch``,
    ``step``, ``observe_grad`` / ``observe_delta``, ``to_host``, ``free``)
    over another model, other weights and another reference."""

    def __init__(self, cell, seed: int, phases):
        from minips_tpu.models import laguna     # absent: fail at once
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
            m = laguna.from_config(c)
            self.struct = jax.eval_shape(
                lambda: laguna.init(jax.random.PRNGKey(0), m))
            paths = jax.tree_util.tree_flatten_with_path(self.struct)[0]
            self.names = [lm._leaf_name(p) for p, _ in paths]
            self.sizes = [int(np.prod(s.shape)) for _, s in paths]
            self._keys = lm.leaf_keys(self.names, self.seed)
            self._make = jax.jit(lambda keys: make_params(
                self.struct, self.names, c, keys, jnp))
            self._sharding = NamedSharding(mesh, P(DATA_AXIS))
            self.model, self.table, self._step, self._stats = model_dp_step(
                c, mesh, self._make(self._keys),
                lm.System.put(self, self.host_batch(0)),
                updater=c["updater"], lr=float(c["lr"]))
        self.samples_per_step = traffic.samples_per_step(mix)
        self.tokens_per_step = traffic.tokens_per_step(mix)
        self.check_steps = 3
        self._observe = self._make_observers()
        self._last = self._ref_bias = None

    def put(self, batch: dict):
        self._last = super().put(batch)     # what the observer reads
        return self._last

    def _make_observers(self):
        import jax
        import jax.numpy as jnp
        n, sizes = sum(self.sizes), self.sizes

        def delta(p, keys):     # as lm's, over this model's weights
            p0 = make_params(self.struct, self.names, self.config, keys,
                             jnp)
            flat0 = jnp.concatenate([x.reshape(-1)
                                     for x in jax.tree.leaves(p0)])
            return readstate.segment_norms(p[:n] - flat0, sizes)

        return dict(super()._make_observers(), delta=jax.jit(delta))

    # ------------------------------------------------------------ the rest
    def info(self) -> dict:
        """Read once, after the window: the routing, the gates and the
        loss of the last batch fed under the weights the window ended
        with."""
        import jax
        out = {"params": sum(self.sizes)}
        if self.table is None or self._last is None:
            return out
        st = jax.device_get(self._stats(self.table.pull(), self._last,
                                        self.table.state))
        out.update(
            routed_tokens_held=int(st["tokens_held"].sum()),
            tokens_held=st["tokens_held"].tolist(),
            absent_share=st["absent_share"].tolist(),
            load_max_over_mean=st["load_max_over_mean"].tolist(),
            gate_mean=st["gate_mean"].tolist(), lm_nll=float(st["lm_nll"]))
        print(f"laguna routing after the window, by expert layer: "
              f"assignments of each held expert {out['tokens_held']}, share "
              f"routed to absent experts {out['absent_share']}, largest "
              f"load over the mean {out['load_max_over_mean']}; output "
              f"gate's mean by layer {out['gate_mean']}; lm.nll "
              f"{out['lm_nll']:.6f}", file=sys.stderr)
        return out

    def free(self) -> None:
        self._last = None
        super().free()

    def reference(self, *, low: bool = False, keep: float = 1.0,
                  fault=None) -> dict:
        batches = [self.host_batch(i) for i in range(self.check_steps)]
        # the sound reference starts its own balancing bias; the control
        # and the planted faults start from the reference's
        with _not_written_to_the_compile_cache():
            ref = laguna_ref.run(
                self.config, batches, lambda: self._make(self._keys),
                self.names, low=low, keep=keep, fault=fault,
                rows_per_block=int(self.config.get("reference_rows", 1)),
                bias=self._ref_bias)
        if not low and keep == 1.0 and fault is None:
            self._ref_bias = ref["bias"]
        return ref


def build(cell, seed: int, phases) -> System:
    return System(cell, seed, phases)


def control_readings(sound: System, phases) -> dict:
    """The control's readings of the first steps of ``sound``'s cell and
    seed. The program has no path of its own below bfloat16: the
    reference, put in its place, with bfloat16 activations and fp8 matmul
    inputs."""
    return sound.reference(low=True)


# the planted faults, for bench/tools/check_faults.py: name -> the
# reference's arguments (half a batch of one row is half its tokens)
FAULTS = {"fault_half_batch": {"keep": 0.5},
          **{"fault_" + f: {"fault": f} for f in laguna_ref.FAULTS}}
