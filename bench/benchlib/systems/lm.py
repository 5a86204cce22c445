"""The ``lm`` system under test: the program's own fused dense PS step for
the decoder-only LM, built as ``apps/lm_example.run`` builds it on the dp
layout: ``models/transformer.init``'s tree in one ``DenseTable`` with Adam,
``DenseTable.make_step`` over ``transformer.grad_fn`` with flash
attention, remat, a chunked head and bfloat16 worker math. The weights are
the benchmark's, made on the device from the seed; batches go through the
same ``device_put`` onto the data axis that the app's ``prep`` makes.
"""

from __future__ import annotations

import functools
import gc

import numpy as np

from benchlib import init, readstate, traffic
from benchlib.reference import gpt2_ref


def _leaf_name(path) -> str:
    return ".".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _scale(name: str, config: dict) -> float | None:
    """Standard deviation of a leaf's initial values: GPT-2's 0.02, the
    residual projections scaled down by sqrt(2 * layers); None for a
    LayerNorm leaf (gain one, bias zero)."""
    last = name.rsplit(".", 1)[-1]
    if last in ("g", "b"):
        return None
    if last in ("proj", "mlp_out"):
        return 0.02 / (2.0 * int(config["n_layer"])) ** 0.5
    return 0.01 if last == "pos_emb" else 0.02


def leaf_keys(names, seed: int) -> np.ndarray:
    """One 32-bit key a leaf, from the seed: an argument of the jitted
    generator, so that every seed runs the same program."""
    return np.array([init.seed_key(seed, 1000 + i)
                     for i in range(len(names))], np.uint32)


def make_params(struct, names, config: dict, keys, xp):
    """The benchmark's initial weights in the program's tree ``struct``
    (shapes only): leaf i draws from ``keys[i]``."""
    import jax
    leaves = []
    for i, (name, s) in enumerate(zip(names, jax.tree.leaves(struct))):
        scale = _scale(name, config)
        if scale is None:
            fill = 1.0 if name.endswith(".g") else 0.0
            leaves.append(xp.full(s.shape, fill, xp.float32))
        else:
            leaves.append(init.leaf_values(keys[i], s.shape, scale, xp=xp))
    return jax.tree.unflatten(jax.tree.structure(struct), leaves)


class System:
    def __init__(self, cell, seed: int, phases):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        from minips_tpu.models import transformer as tfm
        from minips_tpu.parallel.mesh import DATA_AXIS, make_mesh
        from minips_tpu.tables.dense import DenseTable

        self.cell, self.seed = cell, int(seed)
        c, mix = cell.config, cell.traffic
        self.config, self.mix = c, mix
        model = dict(vocab=int(c["vocab_size"]), dim=int(c["n_embd"]),
                     heads=int(c["n_head"]), depth=int(c["n_layer"]),
                     max_len=int(c["n_positions"]))
        with phases("tables"):
            mesh = make_mesh(cell.chips)     # the cell's chips, no more
            self.struct = jax.eval_shape(
                lambda: tfm.init(jax.random.PRNGKey(0), **model))
            paths = jax.tree_util.tree_flatten_with_path(self.struct)[0]
            self.names = [_leaf_name(p) for p, _ in paths]
            self.sizes = [int(np.prod(s.shape)) for _, s in paths]
            self._keys = leaf_keys(self.names, self.seed)
            self._make = jax.jit(lambda keys: make_params(
                self.struct, self.names, c, keys, jnp))
            params = self._make(self._keys)
            self.table = DenseTable(params, mesh, name="lm",
                                    updater=c["updater"],
                                    lr=float(c["lr"]))
            del params
            remat = c["remat"]
            self._step = self.table.make_step(
                functools.partial(tfm.grad_fn, heads=model["heads"],
                                  attn_impl=c["attn"],
                                  remat=True if remat == "full" else remat,
                                  head_chunk=int(c["head_chunk"])),
                batch_spec=P(DATA_AXIS), accum=1,
                compute_dtype=jnp.dtype(c["compute_dtype"]),
                comm="float32")
            self._sharding = NamedSharding(mesh, P(DATA_AXIS))
        with phases("batches"):
            self.pool = traffic.make_pool(mix, self.seed)
        self.samples_per_step = traffic.samples_per_step(mix)
        self.tokens_per_step = traffic.tokens_per_step(mix)
        self.check_steps = 3
        self._observe = self._make_observers()

    # ------------------------------------------------------------ the feed
    def host_batch(self, i: int) -> dict:
        return traffic.batch_of(self.pool, i)

    def put(self, batch: dict):
        import jax
        import jax.numpy as jnp
        return {"tokens": jax.device_put(jnp.asarray(batch["tokens"]),
                                         self._sharding)}

    def step(self, batch):
        return self.table.step_inplace(self._step, batch)

    # ------------------------------------------------- reading the state
    def _make_observers(self):
        import jax
        import jax.numpy as jnp
        n = sum(self.sizes)

        sizes = self.sizes

        def delta(p, keys):
            p0 = make_params(self.struct, self.names, self.config, keys,
                             jnp)
            flat0 = jnp.concatenate([x.reshape(-1)
                                     for x in jax.tree.leaves(p0)])
            return readstate.segment_norms(p[:n] - flat0, sizes)

        return {"grad": jax.jit(lambda mu: readstate.segment_norms(
                    mu[:n] / (1 - readstate.ADAM_B1), sizes)),
                "delta": jax.jit(delta)}

    def observe_grad(self):
        return self._observe["grad"](
            readstate.adam_mu(self.table.opt_state))

    def observe_delta(self):
        return self._observe["delta"](self.table.params, self._keys)

    def to_host(self, obs) -> dict:
        return {n: float(v) for n, v in zip(self.names, np.asarray(obs))}

    # ------------------------------------------------------------ the rest
    def info(self) -> dict:
        return {"params": sum(self.sizes)}

    def free(self) -> None:
        self.table = self._step = None
        gc.collect()

    def reference(self, *, low: bool = False, keep: float = 1.0) -> dict:
        batches = [self.host_batch(i) for i in range(self.check_steps)]
        params0 = self._make(self._keys)
        return gpt2_ref.run(self.config, batches, params0, self.names,
                            low=low, keep=keep,
                            rows_per_block=int(
                                self.config.get("reference_rows", 2)))


def build(cell, seed: int, phases) -> System:
    return System(cell, seed, phases)


def control_readings(sound: System, phases) -> dict:
    """The control's readings of the first steps of ``sound``'s cell and
    seed. The program has no path of its own below bfloat16: the
    reference, put in its place, with bfloat16 activations and fp8 matmul
    inputs."""
    return sound.reference(low=True)
