"""DeepFM with a hashed sparse table, plain: the reference of the
``deepfm`` system. It imports nothing of the program and takes nothing the
program made: the table's initial rows come from the benchmark's own
counter-based generator, worked out for the touched rows alone.

Model (Guo et al., arXiv:1703.04247, section 3): logit = first-order sum
of per-id scalar weights + FM second-order term over the field embeddings
+ an MLP over [numeric fields ; flattened embeddings]; loss = mean binary
cross-entropy. Ids are hashed onto ``num_slots`` rows by the multiply-shift
hash the configuration states. Push: duplicate rows' gradients are summed,
then row-wise Adagrad; the MLP takes Adam.

Precision as the configuration states it: the tables, their gathered
rows, the first-order and FM terms, the loss and both updaters float32;
the MLP bfloat16. The control is no path of this file: it is the program
with its own lower-precision path switched on (PERF.md).

The table is held compactly: only the rows the three steps touch.
"""

from __future__ import annotations

import numpy as np

HASH_MULT = np.uint32(2654435761)


def hash_slots(keys: np.ndarray, num_slots: int, salt: int) -> np.ndarray:
    k = np.asarray(keys).astype(np.uint32)
    h = (k * HASH_MULT) ^ (k >> np.uint32(16)) ^ np.uint32(salt)
    return (h & np.uint32(num_slots - 1)).astype(np.int64)


def _tower(deep, x):
    """The MLP in the precision the configuration states: bfloat16 inputs,
    weights as used, results, bias add and ReLU (each matmul accumulates
    in float32 inside); the float32 masters are rounded here."""
    import jax.numpy as jnp
    bf = jnp.bfloat16
    n = len(deep) // 2
    h = x.astype(bf)
    for i in range(n):
        h = jnp.dot(h, deep[f"w{i}"].astype(bf)) + deep[f"b{i}"].astype(bf)
        if i < n - 1:
            h = jnp.maximum(h, 0)
    return h[:, 0].astype(jnp.float32)


def _loss(wide_rows, emb_rows, deep, dense, y):
    import jax.numpy as jnp
    B = emb_rows.shape[0]
    first = jnp.sum(wide_rows[..., 0], axis=-1)
    s = jnp.sum(emb_rows, axis=1)
    s2 = jnp.sum(emb_rows * emb_rows, axis=1)
    fm = 0.5 * jnp.sum(s * s - s2, axis=-1)
    z = first + fm + _tower(
        deep, jnp.concatenate([dense, emb_rows.reshape(B, -1)], axis=-1))
    return jnp.mean(jnp.logaddexp(0.0, z) - y * z)


def _adagrad(rows, acc, idx, grads, lr, eps=1e-10):
    """Sum duplicates, then Adagrad on the compact table: a row nobody
    touched gets a zero gradient and stays as it is."""
    import jax.numpy as jnp
    G = jnp.zeros_like(rows).at[idx.reshape(-1)].add(
        grads.reshape(-1, rows.shape[1]).astype(jnp.float32))
    acc = acc + G * G
    return rows - lr * G / (jnp.sqrt(acc) + eps), acc, G


def _adam(p, m, v, g, t, lr, b1=0.9, b2=0.999, eps=1e-8):
    import jax.numpy as jnp
    g = g.astype(jnp.float32)
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    return p - lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t))
                                           + eps), m, v


def _norm(x):
    import jax.numpy as jnp
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def _compact(slot_lists):
    """The touched slots, unique and sorted, padded to the fixed length of
    all ids (so every seed runs programs of one shape; the padding repeats
    the last slot and is never indexed), and each step's indices into it.
    """
    flat = np.concatenate([s.reshape(-1) for s in slot_lists])
    u = np.unique(flat)
    padded = np.full(flat.size, u[-1], np.int64)
    padded[: u.size] = u
    return padded, [np.searchsorted(u, s).astype(np.int32)
                    for s in slot_lists]


def run(config: dict, batches: list, seed: int, deep0: dict, table_init,
        *, keep: float = 1.0) -> dict:
    """Follow ``len(batches)`` steps from the seed; returns ``loss`` (one
    per step), ``grad`` (norm of the first gradient per leaf, duplicates
    summed, as the updater gets it), ``rows`` (per table: the touched
    slots, sorted, and that first gradient row by row) and ``delta`` (norm
    of each leaf's change after the last step).
    ``table_init(stream, rows, dim, scale)``
    is the benchmark's generator. ``keep`` < 1 plants the fault of a step
    that leaves part of its batch out and takes the mean over the rest:
    0.5 is half of the batch left out, 1/chips the exchange between chips
    left out (each chip alone with its own shard).
    """
    import jax
    import jax.numpy as jnp
    S, k = int(config["num_slots"]), int(config["embedding_dim"])
    lr_s = float(config["sparse_lr"])
    lr_d = float(config["dense_lr"])
    acc0 = float(config["adagrad_init"])
    if keep < 1.0:
        batches = [{k_: v[: int(v.shape[0] * keep)] for k_, v in b.items()}
                   for b in batches]
    sl_w = [hash_slots(b["cat"], S, int(config["wide_salt"]))
            for b in batches]
    sl_e = [hash_slots(b["cat"], S, int(config["emb_salt"]))
            for b in batches]
    uw, idx_w = _compact(sl_w)
    ue, idx_e = _compact(sl_e)
    wide = jnp.asarray(table_init("wide", uw, 1,
                                  float(config["wide_init_scale"])))
    emb = jnp.asarray(table_init("emb", ue, k,
                                 float(config["emb_init_scale"])))
    wide0, emb0 = wide, emb
    acc_w, acc_e = jnp.full_like(wide, acc0), jnp.full_like(emb, acc0)
    deep0 = {n: jnp.asarray(v, jnp.float32) for n, v in deep0.items()}
    deep = dict(deep0)
    mu = {n: jnp.zeros_like(v) for n, v in deep.items()}
    nu = {n: jnp.zeros_like(v) for n, v in deep.items()}
    out = {"loss": [], "grad": {}, "delta": {}}

    @jax.jit
    def step(wide, acc_w, emb, acc_e, deep, mu, nu, iw, ie, dense, y, t):
        loss, (gw, ge, gd) = jax.value_and_grad(
            lambda w_, e_, d_: _loss(w_, e_, d_, dense, y),
            argnums=(0, 1, 2))(wide[iw], emb[ie], deep)
        wide, acc_w, Gw = _adagrad(wide, acc_w, iw, gw, lr_s)
        emb, acc_e, Ge = _adagrad(emb, acc_e, ie, ge, lr_s)
        new = {n: _adam(deep[n], mu[n], nu[n], gd[n], t, lr_d)
               for n in deep}
        grads = {"wide": _norm(Gw), "emb": _norm(Ge),
                 **{f"deep.{n}": _norm(gd[n]) for n in deep}}
        return (wide, acc_w, emb, acc_e, {n: v[0] for n, v in new.items()},
                {n: v[1] for n, v in new.items()},
                {n: v[2] for n, v in new.items()}, loss, grads,
                {"wide": Gw, "emb": Ge})

    for t, b in enumerate(batches, 1):
        (wide, acc_w, emb, acc_e, deep, mu, nu, loss, grads, G) = step(
            wide, acc_w, emb, acc_e, deep, mu, nu,
            jnp.asarray(idx_w[t - 1]), jnp.asarray(idx_e[t - 1]),
            jnp.asarray(b["dense"]), jnp.asarray(b["y"]),
            jnp.float32(t))
        out["loss"].append(float(loss))
        if t == 1:
            out["grad"] = {n: float(v) for n, v in grads.items()}
            out["rows"] = {"wide": (uw, np.asarray(G["wide"])),
                           "emb": (ue, np.asarray(G["emb"]))}
    delta = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: _norm(x - y), a, b))(
        {"wide": wide, "emb": emb, **{f"deep.{n}": deep[n] for n in deep}},
        {"wide": wide0, "emb": emb0,
         **{f"deep.{n}": deep0[n] for n in deep0}})
    out["delta"] = {n: float(v) for n, v in delta.items()}
    return out
