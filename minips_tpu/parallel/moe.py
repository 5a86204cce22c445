"""Expert parallelism — top-k MoE FFN over a mesh axis (Switch top-1 default,
GShard-style top-2+ via ``k_top``).

Beyond parity (the reference has no expert parallelism, SURVEY.md §2.2).
Completes the framework's parallelism set (dp / sp ring attention / tp /
pp / ep), all expressed the same way: shard_map over named mesh axes with
explicit collectives.

Mechanics (Switch Transformer shape, public recipe): a linear router picks
each token's top-1 expert; tokens are packed into per-expert capacity
slots (earliest-first, overflow dropped — the standard fixed-shape trick,
since TPU programs need static shapes); an ``all_to_all`` ships slots to
the devices that own the experts (``E`` experts sharded over the axis),
each device runs its local experts' FFN on its slots, a second
``all_to_all`` ships results back, and outputs are combined weighted by
the router probability. Gradients flow through both all_to_alls and the
dispatch/combine einsums; the router gets trained through the combine
weights (straight-through on the top-1 choice, as in Switch).

``moe_apply_dense`` is the unsharded oracle: identical numerics (including
capacity drops) computed without collectives, used by tests and usable on
one device.

``moe_apply_dropless`` is the other kind of expert layer: no capacity, no
drops, no one-hot dispatch tensor. The caller routes; the layer is told
which experts of all it holds, sorts the tokens by expert, runs grouped
matrix products over the experts held and scatters back. It is what a
chip's share of a larger expert layer computes (models/zaya.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from minips_tpu.parallel.mesh import pcast_varying
from minips_tpu.utils import profiling as prof


def init_moe(key, num_experts: int, dim: int, hidden: int):
    """Router + stacked expert FFN weights ([E, ...] — shard dim 0 for EP)."""
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = dim ** -0.5
    return {
        "router": jax.random.normal(k1, (dim, num_experts)) * scale_in,
        "w_in": jax.random.normal(k2, (num_experts, dim, hidden)) * scale_in,
        "w_out": jax.random.normal(k3, (num_experts, hidden, dim))
                 * hidden ** -0.5,
    }


def _dispatch_combine(x, router_w, num_experts: int, capacity: int,
                      k_top: int = 1):
    """Route [N, D] tokens to their top-``k_top`` experts: returns
    (dispatch [N, E, C] f32 {0,1}, combine [N, E, C] f32 gate-weighted,
    frac [E], mean_p [E]) — the last two are the raw load-balancing
    statistics for ``_aux_loss``.

    ``k_top=1`` is Switch; ``k_top=2`` is the GShard shape. Capacity slots
    are assigned rank-major (every token's primary choice queues before
    any secondary choice), so when capacity binds, primary routes survive
    preferentially. Gates are the raw softmax probabilities of the chosen
    experts (no top-k renormalization) — for k=1 this is exactly Switch's
    straight-through combine weight."""
    N = x.shape[0]
    logits = x @ router_w                              # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert = jax.lax.top_k(probs, k_top)         # [N, k] each
    onehots = jax.nn.one_hot(expert, num_experts)      # [N, k, E]
    # queue position per (token, choice) within its expert, earliest-first
    # across a rank-major flattening: [k*N, E]
    flat = onehots.transpose(1, 0, 2).reshape(k_top * N, num_experts)
    pos = (jnp.cumsum(flat, axis=0) * flat).astype(jnp.int32) - 1
    keep = (pos >= 0) & (pos < capacity)               # -1 = not routed
    slot = jax.nn.one_hot(pos, capacity)               # [kN, E, C]
    disp = (slot * keep[..., None]).reshape(k_top, N, num_experts,
                                            capacity)
    dispatch = jnp.sum(disp, axis=0)                   # [N, E, C]
    combine = jnp.sum(disp * gate.T[:, :, None, None], axis=0)
    # Switch aux load-balancing statistics: fraction of tokens whose
    # PRIMARY route is each expert and mean router prob per expert.
    # Returned raw (not yet combined) so the distributed path can pmean
    # them BEFORE the product — mean-of-products would differ from the
    # global loss.
    frac = jnp.mean(onehots[:, 0], axis=0)
    mean_p = jnp.mean(probs, axis=0)
    return dispatch, combine, frac, mean_p


def _aux_loss(frac, mean_p, num_experts):
    """E * sum_e(frac_e * mean_prob_e) — minimized at uniform routing."""
    return num_experts * jnp.sum(frac * mean_p)


def _expert_ffn(w_in, w_out, x, compute_dtype):
    """x: [E_local, C', D] through each local expert's GELU MLP."""
    h = jax.nn.gelu(jnp.einsum(
        "ecd,edh->ech", x.astype(compute_dtype), w_in.astype(compute_dtype)))
    return jnp.einsum("ech,ehd->ecd", h,
                      w_out.astype(compute_dtype)).astype(jnp.float32)


def moe_apply_dense(params, x, *, capacity: int,
                    compute_dtype=jnp.bfloat16, k_top: int = 1):
    """Unsharded oracle: [N, D] -> ([N, D], aux_loss). Matches the
    distributed path exactly whenever capacity does not bind; when it
    does, drop patterns differ (one global queue per expert here vs one
    queue per (expert, source device) there)."""
    E = params["router"].shape[1]
    dispatch, combine, frac, mean_p = _dispatch_combine(
        x, params["router"], E, capacity, k_top)
    slots = jnp.einsum("nec,nd->ecd", dispatch, x)     # [E, C, D]
    out_slots = _expert_ffn(params["w_in"], params["w_out"], slots,
                            compute_dtype)
    return (jnp.einsum("nec,ecd->nd", combine, out_slots),
            _aux_loss(frac, mean_p, E))


def moe_apply_local(params_local, x_local, *, axis_name: str,
                    capacity: int, compute_dtype=jnp.bfloat16,
                    k_top: int = 1):
    """Expert-parallel MoE — call INSIDE shard_map with tokens sharded
    [N_local, D] over ``axis_name``, router replicated, and w_in/w_out
    sharded on their expert dim (``ep_specs``). ``capacity`` is per-expert
    per-source-device. Returns ([N_local, D], aux_loss pmean'd).

    Like the other parallel schedules, take grads OUTSIDE the shard_map.
    """
    k = jax.lax.axis_size(axis_name)
    E = params_local["router"].shape[1]
    e_local = params_local["w_in"].shape[0]
    if e_local * k != E:
        raise ValueError(f"router knows {E} experts but {k} devices hold "
                         f"{e_local} each")
    dispatch, combine, frac, mean_p = _dispatch_combine(
        x_local, params_local["router"], E, capacity, k_top)
    slots = jnp.einsum("nec,nd->ecd", dispatch, x_local)   # [E, C, D]
    # ship: expert block e_blk of every device -> device owning those
    # experts; receive my experts' slots from every source device
    slots = slots.reshape(k, e_local, capacity, -1)
    recv = jax.lax.all_to_all(slots, axis_name, split_axis=0,
                              concat_axis=0, tiled=False)  # [k, eL, C, D]
    # fold source-device axis into the slot axis for the local FFN
    mine = recv.transpose(1, 0, 2, 3).reshape(e_local, k * capacity, -1)
    out = _expert_ffn(params_local["w_in"], params_local["w_out"], mine,
                      compute_dtype)
    # ship results back along the inverse route
    out = out.reshape(e_local, k, capacity, -1).transpose(1, 0, 2, 3)
    back = jax.lax.all_to_all(out, axis_name, split_axis=0,
                              concat_axis=0, tiled=False)  # [k, eL, C, D]
    out_slots = back.reshape(E, capacity, -1)
    y = jnp.einsum("nec,ecd->nd", combine, out_slots)
    # global aux loss: average the statistics across shards BEFORE the
    # product so it equals the dense oracle's loss exactly
    aux = _aux_loss(jax.lax.pmean(frac, axis_name),
                    jax.lax.pmean(mean_p, axis_name), E)
    return y, aux


def ep_specs(axis_name: str = "data"):
    """PartitionSpec pytree for ``moe_apply_local``'s params."""
    from jax.sharding import PartitionSpec as P

    return {"router": P(), "w_in": P(axis_name), "w_out": P(axis_name)}


# ------------------------------------------------ dropless, a chip's share
@jax.custom_vjp
def _permute(x, perm, inv):
    """``x[perm]`` for a permutation whose inverse is ``inv``: the
    cotangent is a gather by the inverse, never a scatter."""
    return x[perm]


def _permute_fwd(x, perm, inv):
    return x[perm], (perm, inv)


def _permute_bwd(res, g):
    perm, inv = res
    return g[inv], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def dropless_dispatch(expert, held: tuple[int, int]):
    """Sort ``expert`` [N] (ids over ALL experts) for a worker that holds
    the experts ``held = (lo, hi)``: returns (order, inverse, group_sizes
    [hi - lo]). ``order`` is stable, tokens of held experts first in
    expert order, tokens of absent experts last; no shape depends on the
    routing and no token is dropped."""
    lo, hi = held
    n_held = hi - lo
    local = expert.astype(jnp.int32) - lo
    key = jnp.where((local >= 0) & (local < n_held), local, n_held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    n = expert.shape[0]
    inverse = jnp.zeros(n, jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    sizes = jnp.sum(key[:, None] == jnp.arange(n_held)[None, :], axis=0,
                    dtype=jnp.int32)
    return order, inverse, sizes


def _grouped_rows(n: int, sizes):
    """[n, 1] bool: the rows that the groups ``sizes`` cover."""
    return jnp.arange(n, dtype=jnp.int32)[:, None] < jnp.sum(sizes)


def _grouped_swiglu(experts, xs, sizes, grouped_rows, compute_dtype):
    """``(silu(xs w_gate) * (xs w_up)) w_down`` over rows ``xs`` sorted by
    expert, ``sizes`` rows an expert held: three grouped products
    (``jax.lax.ragged_dot``); the rows beyond the groups' sum (not in
    ``grouped_rows``) stay zero."""

    def live(a):
        return jnp.where(grouped_rows, a, jnp.zeros((), a.dtype))

    def grouped(a, w):
        # the TPU's grouped-matmul kernel leaves the rows beyond the
        # groups' sum as it found them, in the product and (through its
        # transpose) in the cotangent: both sides are masked
        return live(jax.lax.ragged_dot(live(a), w.astype(compute_dtype),
                                       sizes))

    g = grouped(xs, experts["w_gate"])
    u = grouped(xs, experts["w_up"])
    act = jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
    return grouped(act.astype(compute_dtype), experts["w_down"])


def _window_sizes(sizes, lo, r: int):
    """The share of every held expert's group (``sizes`` rows each, one
    group after another) that lies in the rows [lo, lo + r)."""
    ends = jnp.cumsum(sizes)
    return jnp.clip(jnp.minimum(ends, lo + r) - jnp.maximum(ends - sizes, lo),
                    0, r)


def _window(experts, xs, g, sz, compute_dtype):
    """One window's rows ``xs`` [r, D] (sorted by expert, ``sz`` of each)
    through the experts, times their gates ``g`` [r]: [r, D] float32."""
    with jax.named_scope(prof.LM_MOE_DISPATCH):
        rows = _grouped_rows(xs.shape[0], sz)
    with jax.named_scope(prof.LM_MOE_EXPERTS):
        ys = _grouped_swiglu(experts, xs, sz, rows, compute_dtype)
    with jax.named_scope(prof.LM_MOE_COMBINE):
        return ys.astype(jnp.float32) * g[:, None]


def _live_windows(sizes, r: int, body, carry):
    """``body(window's first row, carry)`` over the windows of ``r`` rows
    that hold an assignment to a held expert: as many turns as the routing
    asks for and no more (a loop whose length no shape depends on). Under
    ``shard_map`` every worker runs its own loop: the carries vary where
    the routing does."""
    total = jnp.sum(sizes)
    vma = tuple(jax.typeof(total).vma)
    return jax.lax.while_loop(
        lambda c: c[0] < total,
        lambda c: (c[0] + r, body(c[0], c[1])),
        jax.tree.map(lambda t: pcast_varying(t, vma),
                     (jnp.zeros((), jnp.int32), carry)))[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _windows(experts, xc, gates, token, sizes, compute_dtype):
    """Sum over the live windows of the sorted assignments, a window as
    many rows as ``xc`` has: row i of a window is token ``token[i]``'s row
    of ``xc`` through its expert, times ``gates[i]``, added onto that
    token: [N, D] float32."""
    r = xc.shape[0]

    def body(lo, y):
        tok = jax.lax.dynamic_slice(token, (lo,), (r,))
        with jax.named_scope(prof.LM_MOE_DISPATCH):
            xs = xc[tok]
        ys = _window(experts, xs, jax.lax.dynamic_slice(gates, (lo,), (r,)),
                     _window_sizes(sizes, lo, r), compute_dtype)
        with jax.named_scope(prof.LM_MOE_COMBINE):
            return y.at[tok].add(ys)

    return _live_windows(sizes, r, body, jnp.zeros(xc.shape, jnp.float32))


def _windows_fwd(experts, xc, gates, token, sizes, compute_dtype):
    return (_windows(experts, xc, gates, token, sizes, compute_dtype),
            (experts, xc, gates, token, sizes))


def _windows_bwd(compute_dtype, res, dy):
    # the forward's loop again, window by window: a window's products are
    # made a second time and transposed while they are live, so nothing of
    # a window outlives it but its sums (float32; cast once, at the end)
    experts, xc, gates, token, sizes = res
    r = xc.shape[0]
    f32 = lambda t: jax.tree.map(                       # noqa: E731
        lambda a: jnp.zeros(a.shape, jnp.float32), t)

    def body(lo, carry):
        d_experts, d_xc, d_gates = carry
        tok = jax.lax.dynamic_slice(token, (lo,), (r,))
        g = jax.lax.dynamic_slice(gates, (lo,), (r,))
        sz = _window_sizes(sizes, lo, r)
        with jax.named_scope(prof.LM_MOE_DISPATCH):
            xs = xc[tok]
        _, vjp = jax.vjp(lambda e, x, g: _window(e, x, g, sz, compute_dtype),
                         experts, xs, g)
        with jax.named_scope(prof.LM_MOE_COMBINE):
            de, dxs, dg = vjp(dy[tok])
        with jax.named_scope(prof.LM_MOE_DISPATCH):
            d_xc = d_xc.at[tok].add(dxs.astype(jnp.float32))
        return (jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                             d_experts, de),
                d_xc, jax.lax.dynamic_update_slice(d_gates, dg, (lo,)))

    d_experts, d_xc, d_gates = _live_windows(
        sizes, r, body, (f32(experts), f32(xc), f32(gates)))
    return (jax.tree.map(lambda d, w: d.astype(w.dtype), d_experts, experts),
            d_xc.astype(xc.dtype), d_gates, None, None)


_windows.defvjp(_windows_fwd, _windows_bwd)


def _dropless_topk(experts, x, expert, gate, held, compute_dtype):
    """``moe_apply_dropless`` for ``expert`` / ``gate`` [N, k]. The N * k
    assignments are sorted by expert, those of held experts first; only
    that head of the order is worked on, in windows of N rows, as many as
    there are tokens: a window gathers its tokens' rows (never k copies of
    every token at once), runs the grouped products, and adds each row,
    times its gate, onto its token, so that a token which several held
    experts chose gets their sum. The loop ends with the held assignments
    (``_windows``: one turn unless the held experts take more than N of
    the N * k, k at the most): the work follows the routing, no shape
    does, and nothing is dropped."""
    n, k = expert.shape
    with jax.named_scope(prof.LM_MOE_DISPATCH):
        order, inverse, sizes = dropless_dispatch(expert.reshape(n * k), held)
        token = order // k
        gates = _permute(gate.reshape(n * k).astype(jnp.float32), order,
                         inverse)
    # one set of varying axes for the inputs, the loops' carries and the
    # cotangents (a custom_vjp's rule returns cotangents of the primals'
    # own types); where the weights vary over fewer, this pcast's
    # transpose is the psum their gradient needs
    vma = tuple(jax.typeof(token).vma)
    experts, xc, gates = jax.tree.map(
        lambda t: pcast_varying(t, vma),
        (experts, x.astype(compute_dtype), gates))
    return _windows(experts, xc, gates, token, sizes, compute_dtype)


def moe_apply_dropless(experts, x, expert, gate, *, held: tuple[int, int],
                       compute_dtype=jnp.bfloat16):
    """The part of a gated-SiLU expert layer that the experts held here
    give: [N, D] -> [N, D] float32.

    ``experts`` holds the stacks ``w_gate``, ``w_up`` [n_held, D, F] and
    ``w_down`` [n_held, F, D] of the experts ``held = (lo, hi)`` out of
    however many the router knows; ``expert`` [N] is each token's choice
    over ALL of them and ``gate`` [N] its weight (top-1), or [N, k] each
    for k choices a token. A token gets ``gate * (silu(x w_gate) * (x
    w_up)) w_down`` of every chosen expert that is held, summed, and 0 of
    one that lives elsewhere: what the absent experts would add is another
    worker's part (on one chip there is no exchange, and nothing stands in
    for one). Top-1: the tokens are sorted by expert and go through three
    grouped products (``jax.lax.ragged_dot``); rows beyond the groups' sum
    are kept zero. Top-k: the same over the sorted assignments, N rows at a
    time (``_dropless_topk``)."""
    n_held = held[1] - held[0]
    if experts["w_gate"].shape[0] != n_held:
        raise ValueError(f"held {held} names {n_held} experts but the "
                         f"stacks hold {experts['w_gate'].shape[0]}")
    if expert.ndim == 2:
        return _dropless_topk(experts, x, expert, gate, held, compute_dtype)
    with jax.named_scope(prof.LM_MOE_DISPATCH):
        order, inverse, sizes = dropless_dispatch(expert, held)
        xs = _permute(x.astype(compute_dtype), order, inverse)
        rows = _grouped_rows(x.shape[0], sizes)
    with jax.named_scope(prof.LM_MOE_EXPERTS):
        ys = _grouped_swiglu(experts, xs, sizes, rows, compute_dtype)
    with jax.named_scope(prof.LM_MOE_COMBINE):
        ys = ys.astype(jnp.float32) * gate.astype(jnp.float32)[order][:, None]
        return _permute(ys, inverse, order)
