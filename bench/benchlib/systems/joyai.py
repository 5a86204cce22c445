"""The ``joyai`` system under test: the program's own fused dense PS step
for the latent-attention expert decoder (``minips_tpu/models/mla_moe.py``),
built by the very function ``apps/lm_example.run`` builds it with on the dp
layout (``lm_example.model_dp_step``, which takes the model by the file's
``model_type``: one ``DenseTable`` with Adam, ``DenseTable.make_step`` over
``mla_moe.grad_fn``), from the cell's configuration file. The weights are
the benchmark's, made on the device from the seed; batches go through the
same ``device_put`` onto the data axis that the app's ``prep`` makes, and
the first of them is what the builder starts the routers' balancing bias
from. After the window the observer is read once (``info``): the
assignments the held experts really got are what the expert layer's FLOPs
are counted from. A program without the model (the parent of the PR that
brought it) fails at the first import, before anything is built.
"""

from __future__ import annotations

import contextlib
import sys

import numpy as np

from benchlib import init, readstate, traffic
from benchlib.reference import joyai_ref
from benchlib.systems import lm


def _init(name: str, config: dict):
    """(constant, None) or (None, standard deviation) of a leaf's initial
    values: gains one; 0.02, the residual projections ``wo`` and
    ``w_down`` scaled down by sqrt(2 * blocks), the prediction module's
    block counted."""
    last = name.rsplit(".", 1)[-1]
    if last == "g":
        return 1.0, None
    if last in ("wo", "w_down"):
        blocks = int(config["num_hidden_layers"]) \
            + int(config.get("num_nextn_predict_layers", 0))
        return None, 0.02 / (2.0 * blocks) ** 0.5
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


@contextlib.contextmanager
def _not_written_to_the_compile_cache():
    """The reference's programs are compiled and not kept: its float32
    program is an 89 MB entry, and with the step (63 MB) and the observer
    (16 MB) beside it a run's programs pass the 192 MiB that the chip
    machine's persistent cache holds, so that every run of the cell would
    evict what the next one needs, set-up included (PERF.md section 6,
    PR 33). What a run measures stays warm; the reference, which no
    metric times, compiles again."""
    import jax
    name = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, name)
    jax.config.update(name, 1e9)
    try:
        yield
    finally:
        jax.config.update(name, before)


class System(lm.System):
    """The ``lm`` adapter's feed, step and state readers (``host_batch``,
    ``step``, ``observe_grad`` / ``observe_delta``, ``to_host``, ``free``)
    over another model, other weights and another reference."""

    def __init__(self, cell, seed: int, phases):
        from minips_tpu.models import mla_moe     # absent: fail at once
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
            m = mla_moe.from_config(c)
            self.struct = jax.eval_shape(
                lambda: mla_moe.init(jax.random.PRNGKey(0), m))
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

        return {"grad": jax.jit(lambda mu: readstate.segment_norms(
                    mu[:n] / (1 - readstate.ADAM_B1), sizes)),
                "delta": jax.jit(delta)}

    # ------------------------------------------------------------ the rest
    def info(self) -> dict:
        """Read once, after the window: the routing and the two losses of
        the last batch fed under the weights the window ended with."""
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
            lm_nll=float(st["lm_nll"]), mtp_nll=float(st["mtp_nll"]))
        print(f"joyai routing after the window, by expert layer (the "
              f"prediction module's last): assignments of each held expert "
              f"{out['tokens_held']}, share routed to absent experts "
              f"{out['absent_share']}, largest load over the mean "
              f"{out['load_max_over_mean']}; lm.nll {out['lm_nll']:.6f}, "
              f"mtp.nll {out['mtp_nll']:.6f}", file=sys.stderr)
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
            ref = joyai_ref.run(self.config, batches,
                                lambda: self._make(self._keys), self.names,
                                low=low, keep=keep, fault=fault,
                                rows_per_block=int(
                                    self.config.get("reference_rows", 1)),
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
# reference's arguments
FAULTS = {"fault_half_batch": {"keep": 0.5},
          **{"fault_" + f: {"fault": f} for f in joyai_ref.FAULTS}}
