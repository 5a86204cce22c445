"""The ``deepfm`` system under test: the program's own fused PS step for
Wide&Deep/DeepFM, assembled as ``apps/wide_deep_example.build`` assembles
it (two hashed ``SparseTable``s, a ``DenseTable`` for the deep tower, one
``PSTrainStep``), at the configuration's sizes. The tables' values and the
tower's weights are the benchmark's, made from the seed; the batches come
through the program's own feed, ``PSTrainStep.shard_batch``.
"""

from __future__ import annotations

import gc

import numpy as np

from benchlib import init, readstate, traffic
from benchlib.reference import deepfm_ref

STREAMS = {"wide": 1, "emb": 2, "deep": 3}


def deep_template(config: dict, seed: int) -> dict:
    """The tower's initial weights, host arrays: He-scaled weights, zero
    biases, in the program's ``{w<i>, b<i>}`` layout."""
    sizes = ((int(config["num_dense"])
              + int(config["num_cat"]) * int(config["embedding_dim"]),)
             + tuple(config["hidden"]) + (1,))
    out = {}
    for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:])):
        out[f"w{i}"] = init.leaf_values(
            init.seed_key(seed, STREAMS["deep"] * 100 + i), (a, b),
            (2.0 / a) ** 0.5)
        out[f"b{i}"] = np.zeros((b,), np.float32)
    return out


class System:
    def __init__(self, cell, seed: int, phases, *, control: bool = False):
        import jax.numpy as jnp
        from minips_tpu.models import wide_deep as wd_model
        from minips_tpu.parallel.mesh import make_mesh
        from minips_tpu.tables.dense import DenseTable
        from minips_tpu.tables.sparse import SparseTable
        from minips_tpu.train.ps_step import PSTrainStep

        self.cell, self.seed = cell, int(seed)
        c, mix = cell.config, cell.traffic
        self.config, self.mix = c, mix
        S, k = int(c["num_slots"]), int(c["embedding_dim"])
        with phases("tables"):
            mesh = make_mesh(cell.chips)     # the cell's chips, no more
            common = dict(updater=c["sparse_updater"],
                          lr=float(c["sparse_lr"]),
                          adagrad_init=float(c["adagrad_init"]))
            self.wide = SparseTable(S, 1, mesh, name="wide", init_scale=0.0,
                                    salt=int(c["wide_salt"]), **common)
            self.emb = SparseTable(S, k, mesh, name="emb", init_scale=0.0,
                                   salt=int(c["emb_salt"]), **common)
            # the benchmark's own values, in the table's sharded layout
            for t, name in ((self.wide, "wide"), (self.emb, "emb")):
                scale = float(c[f"{name}_init_scale"])
                if scale:
                    sharding = t.emb.sharding
                    t.emb = None
                    t.emb = init.fill_table(self.seed, STREAMS[name], S,
                                            t.dim, scale, sharding)
            self.deep0 = deep_template(c, self.seed)
            self.deep = DenseTable(
                {n: jnp.asarray(v) for n, v in self.deep0.items()}, mesh,
                name="deep", updater=c["dense_updater"],
                lr=float(c["dense_lr"]))

            def loss_fn(deep_params, rows, batch):
                return wd_model.loss(rows["wide"], rows["emb"], deep_params,
                                     batch, use_fm=True)

            # control: the program's own lower-precision path
            self.ps = PSTrainStep(
                loss_fn, dense=self.deep,
                sparse={"wide": self.wide, "emb": self.emb},
                key_fns={"wide": lambda b: b["cat"],
                         "emb": lambda b: b["cat"]},
                compute_dtype=jnp.bfloat16 if control else None)
        with phases("batches"):
            self.pool = traffic.make_pool(mix, self.seed)
        self.samples_per_step = traffic.samples_per_step(mix)
        self.tokens_per_step = 0
        self.check_steps = 3
        self._leaves = sorted(self.deep0)         # ravel order of a dict
        self._sizes = [int(self.deep0[n].size) for n in self._leaves]
        self._observe = self._make_observers()
        self._slots = self._expected_slots()

    # ------------------------------------------------------------ the feed
    def host_batch(self, i: int) -> dict:
        return traffic.batch_of(self.pool, i)

    def put(self, batch: dict):
        return self.ps.shard_batch(batch)

    def step(self, batch):
        return self.ps(batch)

    # ------------------------------------------------- reading the state
    def _expected_slots(self) -> dict:
        """Per table: the slots the reference expects step 1, and steps
        1 to 3, to touch: unique, padded to a fixed length with a mask, so
        every seed compiles the same observer."""
        S = int(self.config["num_slots"])
        out = {}
        for name in ("wide", "emb"):
            salt = int(self.config[f"{name}_salt"])
            per = [deepfm_ref.hash_slots(self.host_batch(i)["cat"], S, salt)
                   .reshape(-1) for i in range(self.check_steps)]
            for tag, arr in (("first", per[0]),
                             ("all", np.concatenate(per))):
                u = np.unique(arr)
                pad = np.zeros(arr.size, np.int32)
                pad[: u.size] = u
                valid = np.zeros(arr.size, np.float32)
                valid[: u.size] = 1.0
                out[name, tag] = (pad, valid)
        return out

    def _make_observers(self):
        import jax
        import jax.numpy as jnp
        lr = float(self.config["sparse_lr"])

        def sparse(emb, accum, slots, valid, key32, scale):
            rows0 = init.uniform_rows(key32, slots, emb.shape[1], scale,
                                      xp=jnp) if scale else 0.0
            d = (emb[slots] - rows0) * valid[:, None]
            # Adagrad's step is -lr*G/(sqrt(accum')+eps): G from the state
            g = -d * (jnp.sqrt(accum[slots]) + 1e-10) / lr
            return jnp.sqrt(jnp.sum(g * g)), jnp.sqrt(jnp.sum(d * d)), g

        sizes = self._sizes
        self._flat0 = np.concatenate([self.deep0[n].reshape(-1)
                                      for n in self._leaves])
        return {
            "sparse": jax.jit(sparse, static_argnums=(5,)),
            "dense_grad": jax.jit(lambda mu: readstate.segment_norms(
                mu / (1 - readstate.ADAM_B1), sizes)),
            "dense_delta": jax.jit(lambda p, p0: readstate.segment_norms(
                p[: p0.size] - p0, sizes)),
        }

    def _read_sparse(self, tag: str, which: int) -> dict:
        out = {}
        for name, t in (("wide", self.wide), ("emb", self.emb)):
            slots, valid = self._slots[name, tag]
            out[name] = self._observe["sparse"](
                t.emb, t.accum, slots, valid,
                init.seed_key(self.seed, STREAMS[name]),
                float(self.config[f"{name}_init_scale"]))[which]
        return out

    def observe_grad(self) -> dict:
        """After step 1: the first gradient's norm per leaf, from the
        updaters' state. Device scalars; read them after the window."""
        out = self._read_sparse("first", 0)
        out["deep"] = self._observe["dense_grad"](
            readstate.adam_mu(self.deep.opt_state))
        return out

    def observe_rows(self) -> dict:
        """After step 1: the first gradient of each table row by row, over
        the slots the step touched (sorted, zero beyond them)."""
        return self._read_sparse("first", 2)

    def observe_delta(self) -> dict:
        """After the last check step: the norm of each leaf's change."""
        out = self._read_sparse("all", 1)
        out["deep"] = self._observe["dense_delta"](self.deep.params,
                                                   self._flat0)
        return out

    def to_host(self, obs: dict) -> dict:
        out = {k: float(v) for k, v in obs.items() if k != "deep"}
        for n, v in zip(self._leaves, np.asarray(obs["deep"])):
            out[f"deep.{n}"] = float(v)
        return out

    # ------------------------------------------------------------ the rest
    def info(self) -> dict:
        c, mix = self.config, self.mix
        return {"rows_per_step": int(mix["batch"]) * int(c["num_cat"]),
                "table_state_bytes": sum(
                    int(x.nbytes) for t in (self.wide, self.emb)
                    for x in (t.emb, t.accum))}

    def free(self) -> None:
        self.ps = self.wide = self.emb = self.deep = None
        gc.collect()

    def reference(self, *, keep: float = 1.0) -> dict:
        batches = [self.host_batch(i) for i in range(self.check_steps)]
        out = deepfm_ref.run(
            self.config, batches, self.seed, self.deep0,
            lambda name, rows, dim, scale: init.uniform_rows(
                init.seed_key(self.seed, STREAMS[name]), rows, dim, scale),
            keep=keep)
        # the reference's rows in the order the program's were read in;
        # a slot the reference never touched has gradient zero there
        for name, (slots, g) in out["rows"].items():
            want, valid = self._slots[name, "first"]
            at = np.minimum(np.searchsorted(slots, want), slots.size - 1)
            hit = (slots[at] == want) & (valid > 0)
            out["rows"][name] = np.where(hit[:, None], g[at], 0.0)
        return out


def build(cell, seed: int, phases, **kw) -> System:
    return System(cell, seed, phases, **kw)


def control_readings(sound: System, phases) -> dict:
    """The control's readings of the first steps of ``sound``'s cell and
    seed: the program with its own lower-precision path switched on,
    ``PSTrainStep(compute_dtype=bfloat16)``: rows, batch, FM term,
    first-order term and loss bfloat16 where the configuration states
    float32. ``sound`` has let go of its tables by now."""
    from benchlib import harness
    system = System(sound.cell, sound.seed, phases, control=True)
    got = harness.first_readings(system)
    system.free()
    return got
