"""Reading an updater's state without copying it: what the output check
takes from the program after a step."""

from __future__ import annotations

ADAM_B1 = 0.9


def adam_mu(opt_state):
    """The first-moment vector of an optax Adam state, wherever the chain
    keeps it. After one step from zero it is (1 - b1) times the gradient
    the updater got."""
    import jax
    states = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]
    return states[0].mu


def segment_norms(flat, sizes):
    """The norm of each leaf of a raveled vector, leaves of ``sizes`` in
    ravel order."""
    import jax.numpy as jnp
    out, at = [], 0
    for n in sizes:
        out.append(jnp.sqrt(jnp.sum(jnp.square(flat[at: at + n]))))
        at += n
    return jnp.stack(out)
