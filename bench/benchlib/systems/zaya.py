"""The ``zaya`` system under test: the program's own fused dense PS step for
the ZAYA1-shaped decoder (``minips_tpu/models/zaya.py``), built by the very
function ``apps/lm_example.run`` builds it with on the dp layout
(``lm_example.zaya_dp_step``: one ``DenseTable`` with Adam,
``DenseTable.make_step`` over ``zaya.grad_fn``), from the cell's
configuration file. The weights are the benchmark's, made on the device
from the seed; batches go through the same ``device_put`` onto the data
axis that the app's ``prep`` makes, and the first of them is what the
builder starts the router's balancing bias from. After the window the
routing observer is read once (``info``): the tokens the held experts
really got are what the expert layer's FLOPs are counted from.
"""

from __future__ import annotations

import sys

import numpy as np

from benchlib import init, readstate, traffic
from benchlib.reference import zaya_ref
from benchlib.systems import lm

ONES = ("g", "k_temp")          # gains and the key temperature start at 1


def _init(name: str, config: dict):
    """(constant, None) or (None, standard deviation) of a leaf's initial
    values: 0.02, the residual projections scaled down by sqrt(2 *
    layers); the convolutions' taps 0.5 (depthwise) and head_dim^-0.5
    (inside a head), the router's MLP width^-0.5, so that every stage
    passes on what it gets at the same scale; the router's depth-averaging
    ``gamma`` starts at zero as the report has it."""
    last = name.rsplit(".", 1)[-1]
    if last in ONES:
        return 1.0, None
    if last == "gamma":
        return 0.0, None
    if last in ("wo", "w_down"):
        return None, 0.02 / (2.0 * int(config["num_hidden_layers"])) ** 0.5
    if last.endswith("_dw"):
        return None, 0.5
    if last.endswith("_hd"):
        return None, int(config["head_dim"]) ** -0.5
    if last in ("w1", "w2", "w3"):
        return None, int(config["router_hidden_size"]) ** -0.5
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
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        from minips_tpu.apps.lm_example import zaya_dp_step
        from minips_tpu.models import zaya
        from minips_tpu.parallel.mesh import DATA_AXIS, make_mesh

        self.cell, self.seed = cell, int(seed)
        c, mix = cell.config, cell.traffic
        self.config, self.mix = c, mix
        with phases("batches"):
            self.pool = traffic.make_pool(mix, self.seed)
        with phases("tables"):
            mesh = make_mesh(cell.chips)     # the cell's chips, no more
            self.struct = jax.eval_shape(lambda: zaya.init(
                jax.random.PRNGKey(0), zaya.from_config(c)))
            paths = jax.tree_util.tree_flatten_with_path(self.struct)[0]
            self.names = [lm._leaf_name(p) for p, _ in paths]
            self.sizes = [int(np.prod(s.shape)) for _, s in paths]
            self._keys = lm.leaf_keys(self.names, self.seed)
            self._make = jax.jit(lambda keys: make_params(
                self.struct, self.names, c, keys, jnp))
            self._sharding = NamedSharding(mesh, P(DATA_AXIS))
            self.model, self.table, self._step, self._stats = zaya_dp_step(
                c, mesh, self._make(self._keys),
                lm.System.put(self, self.host_batch(0)),
                updater=c["updater"], lr=float(c["lr"]))
            self._bias0 = self.table.state   # what the first step runs under
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

        return {"grad": jax.jit(lambda mu: readstate.segment_norms(
                    mu[:n] / (1 - readstate.ADAM_B1), sizes)),
                "delta": jax.jit(delta)}

    # ------------------------------------------------------------ the rest
    def _routing(self, params, batch, bias) -> dict:
        import jax
        return jax.device_get(self._stats(params, batch, bias))

    def info(self) -> dict:
        """Read once, after the window: the routing of the last batch fed
        under the weights the window ended with."""
        out = {"params": sum(self.sizes)}
        if self.table is None or self._last is None:
            return out
        st = self._routing(self.table.pull(), self._last, self.table.state)
        out.update(
            routed_tokens_held=int(st["tokens_held"].sum()),
            tokens_held=st["tokens_held"].tolist(),
            absent_share=st["absent_share"].tolist(),
            load_max_over_mean=st["load_max_over_mean"].tolist())
        print(f"zaya routing after the window, by layer: tokens of each "
              f"held expert {out['tokens_held']}, share routed to absent "
              f"experts {out['absent_share']}, largest load over the mean "
              f"{out['load_max_over_mean']}", file=sys.stderr)
        return out

    def free(self) -> None:
        self._last = None
        super().free()

    def reference(self, *, low: bool = False, keep: float = 1.0,
                  capacity=None) -> dict:
        batches = [self.host_batch(i) for i in range(self.check_steps)]
        # the sound reference starts its own balancing bias; the control
        # and the planted faults start from the reference's
        ref = zaya_ref.run(self.config, batches,
                           lambda: self._make(self._keys), self.names,
                           low=low, keep=keep, capacity=capacity,
                           rows_per_block=int(
                               self.config.get("reference_rows", 1)),
                           bias=self._ref_bias)
        if not low and keep == 1.0 and capacity is None:
            self._ref_bias = ref["bias"]
            self.flips = self._flips(batches[0], ref["expert"])
        return ref

    def _flips(self, batch, ref_expert) -> list:
        """Top-1 is a step function: how many tokens of step 1 choose
        another expert in the program than in the reference, by layer."""
        import jax
        import jax.numpy as jnp
        put = {"tokens": jax.device_put(jnp.asarray(batch["tokens"]),
                                        self._sharding)}
        mine = self._routing(self._make(self._keys), put,
                             self._bias0)["expert"]
        flips = [int(np.sum(a != b)) for a, b in zip(mine, ref_expert)]
        print(f"zaya: tokens of step 1 whose expert differs from the "
              f"reference's, by layer: {flips} of {mine.shape[1]}",
              file=sys.stderr)
        return flips


def build(cell, seed: int, phases) -> System:
    return System(cell, seed, phases)


def control_readings(sound: System, phases) -> dict:
    """The control's readings of the first steps of ``sound``'s cell and
    seed. The program has no path of its own below bfloat16: the
    reference, put in its place, with bfloat16 activations and fp8 matmul
    inputs."""
    return sound.reference(low=True)


# the planted faults, for bench/tools/check_faults.py: name -> the
# reference's arguments
FAULTS = {"fault_half_batch": {"keep": 0.5},
          "fault_capacity_drops": {"capacity": 1.0}}
