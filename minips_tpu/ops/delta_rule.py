"""The gated delta rule (Gated Delta Networks, arXiv:2412.06464), chunked,
forward and backward.

Per head, with q, k of ``Dk`` channels and v of ``Dv``, a log-decay ``g``
<= 0 and a write strength ``beta`` a token, the state ``S`` [Dk, Dv]
starts at zero for every sequence and moves token by token::

    S~  = exp(g_t) S_(t-1)
    u_t = beta_t (v_t - S~^T k_t)
    S_t = S~ + k_t u_t^T              o_t = S_t^T q_t

(``S_t = (I - beta_t k_t k_t^T) exp(g_t) S_(t-1) + beta_t k_t v_t^T``).
:func:`delta_rule_recurrence` is that and nothing else. What a step runs is
the chunked form of the paper's section 3, :func:`gated_delta_rule`: with
``gamma`` the running sum of ``g`` inside a chunk of C tokens and
``Gamma_ij = exp(gamma_i - gamma_j)``::

    A = strict_lower(diag(beta) (K K^T * Gamma))
    [W | U] = (I + A)^-1 diag(beta) [K * exp(gamma) | V]
    per chunk, one after another:
      V' = U - W S
      O  = (Q * exp(gamma)) S + lower(Q K^T * Gamma) V'
      S <- exp(gamma_C) S + (K * exp(gamma_C - gamma))^T V'

Everything above "per chunk" is a batch of dense products over chunks and
heads (``_chunk_terms``); the last three lines are a ``lax.scan`` of T / C
steps over batched products. No exponent is ever positive: ``gamma`` only
enters as ``exp(gamma_i)``, ``exp(gamma_i - gamma_j)`` for j <= i and
``exp(gamma_C - gamma_i)``, so a strong decay underflows to the zero it
means and nothing is divided by it.

The backward pass (a ``jax.custom_vjp``) is the same scan run from the end:
it keeps the inputs and the state at the start of every chunk, recomputes
the chunk terms (under ``jax.vjp``, which then carries their cotangents
back to q, k, v, g and beta) and V', and walks the chunks backwards with
the state's cotangent as its carry. The output and the kept states carry
checkpoint names (``profiling.GDN_RESIDUALS``): a block checkpoint whose
policy saves both does not run the forward scan a second time.

Precision: ``g``, ``beta``, ``gamma``, every ``exp``, the triangular solve
(by substitution: the inverse is never formed), W, U and the state are
float32; the products take their inputs in q's dtype
(bfloat16 in a training step, float32 at highest precision in the tests)
and accumulate in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from minips_tpu.parallel.mesh import pcast_varying
from minips_tpu.utils import profiling as prof

CHUNK = 64


def delta_rule_recurrence(q, k, v, g, beta):
    """The token-by-token rule in float32: q, k [B, T, H, Dk], v
    [B, T, H, Dv], g and beta [B, T, H] -> o [B, T, H, Dv] float32."""
    f32 = lambda x: jnp.moveaxis(x.astype(jnp.float32), 1, 0)  # noqa: E731
    hi = jax.lax.Precision.HIGHEST

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t,
                                               precision=hi))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=hi)

    B, _, H, Dk = q.shape
    S0 = jnp.zeros((B, H, Dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(token, S0, tuple(f32(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _vma(*xs):
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def _mm(eq, a, b, dtype):
    """A chunk product: inputs in ``dtype``, float32 out."""
    precision = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
    return jnp.einsum(eq, a.astype(dtype), b.astype(dtype),
                      precision=precision,
                      preferred_element_type=jnp.float32)


def _chunks(x, chunk):
    """[B, T, H, ..] -> [N, B, H, C, ..]: what the scan walks."""
    B, T, H = x.shape[:3]
    x = x.reshape((B, T // chunk, chunk, H) + x.shape[3:])
    return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)


def _unchunk(x):
    """[N, B, H, C, ..] -> [B, T, H, ..]."""
    x = jnp.moveaxis(jnp.moveaxis(x, 0, 1), 2, 3)
    B, N, C, H = x.shape[:4]
    return x.reshape((B, N * C, H) + x.shape[4:])


def _chunk_terms(q, k, v, g, beta, dtype):
    """The part with no order among chunks, on [N, B, H, C, ..] arrays:
    (W, U, Q * exp(gamma), lower(Q K^T * Gamma), K * exp(gamma_C - gamma),
    exp(gamma_C)), float32."""
    C = q.shape[-2]
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    gamma = jnp.cumsum(g, axis=-1)
    rows = jnp.arange(C)
    lower = rows[:, None] >= rows[None, :]
    Gam = jnp.exp(jnp.where(lower, gamma[..., :, None] - gamma[..., None, :],
                            -jnp.inf))
    A = jnp.where(rows[:, None] > rows[None, :],
                  beta[..., :, None] * _mm("...id,...jd->...ij", k, k, dtype)
                  * Gam, 0.0)
    eg = jnp.exp(gamma)[..., None]
    b = beta[..., None]
    rhs = jnp.concatenate([b * eg * k.astype(jnp.float32),
                           b * v.astype(jnp.float32)], -1)
    wu = jax.lax.linalg.triangular_solve(
        A + jnp.eye(C, dtype=jnp.float32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    Dk = k.shape[-1]
    a_qk = jnp.where(lower, _mm("...id,...jd->...ij", q, k, dtype) * Gam,
                     0.0)
    last = gamma[..., -1:]
    return (wu[..., :Dk], wu[..., Dk:], q.astype(jnp.float32) * eg, a_qk,
            k.astype(jnp.float32) * jnp.exp(last - gamma)[..., None],
            jnp.exp(last[..., 0]))


def _scan_forward(terms, dtype, vma):
    """The chunks in order: (O [N, B, H, C, Dv], the state at the start of
    every chunk [N, B, H, Dk, Dv], the state after the last)."""
    W, U = terms[0], terms[1]

    def step(S, x):
        W, U, qg, a_qk, k_dec, e = x
        v_new = U - _mm("bhck,bhkv->bhcv", W, S, dtype)
        o = _mm("bhck,bhkv->bhcv", qg, S, dtype) \
            + _mm("bhcj,bhjv->bhcv", a_qk, v_new, dtype)
        S_next = e[..., None, None] * S \
            + _mm("bhck,bhcv->bhkv", k_dec, v_new, dtype)
        return S_next, (o, S)

    S0 = pcast_varying(jnp.zeros(W.shape[1:3] + (W.shape[-1], U.shape[-1]),
                                 jnp.float32), vma)
    last, (o, states) = jax.lax.scan(step, S0, terms)
    return o, states, last


def _scan_backward(terms, states, d_o, dtype):
    """The chunks from the end: the cotangents of ``terms``."""

    def step(dS, x):
        (W, U, qg, a_qk, k_dec, e), S, do = x
        v_new = U - _mm("bhck,bhkv->bhcv", W, S, dtype)
        dv_new = _mm("bhcj,bhcv->bhjv", a_qk, do, dtype) \
            + _mm("bhck,bhkv->bhcv", k_dec, dS, dtype)
        d_terms = (-_mm("bhcv,bhkv->bhck", dv_new, S, dtype),     # W
                   dv_new,                                        # U
                   _mm("bhcv,bhkv->bhck", do, S, dtype),          # Q exp
                   _mm("bhcv,bhjv->bhcj", do, v_new, dtype),      # a_qk
                   _mm("bhcv,bhkv->bhck", v_new, dS, dtype),      # K dec
                   jnp.sum(dS * S, axis=(-2, -1)))                # exp(g_C)
        dS = _mm("bhck,bhcv->bhkv", qg, do, dtype) + e[..., None, None] * dS \
            - _mm("bhck,bhcv->bhkv", W, dv_new, dtype)
        return dS, d_terms

    dS0 = pcast_varying(jnp.zeros(states.shape[1:], jnp.float32),
                        _vma(states, d_o))
    _, d_terms = jax.lax.scan(step, dS0, (terms, states, d_o), reverse=True)
    return d_terms


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gdn(q, k, v, g, beta, chunk):
    return _gdn_fwd(q, k, v, g, beta, chunk)[0]


def chunk_states(q, k, v, g, beta, chunk: int = CHUNK):
    """The forward pass with what it passes through: (o [B, T, H, Dv] in
    v's dtype, the state at the start of every chunk [T / chunk, B, H, Dk,
    Dv], the state after the last token), the states float32. Not
    differentiated: :func:`gated_delta_rule` is."""
    with jax.named_scope(prof.LM_LINATTN_SCAN):
        parts = tuple(_chunks(x, chunk) for x in (q, k, v, g, beta))
        terms = _chunk_terms(*parts, q.dtype)
        o, states, last = _scan_forward(terms, q.dtype,
                                        _vma(q, k, v, g, beta))
        return _unchunk(o).astype(v.dtype), states, last


def _gdn_fwd(q, k, v, g, beta, chunk):
    o, states, _ = chunk_states(q, k, v, g, beta, chunk)
    B, T, H, Dv = v.shape
    # named before they part into output and residuals, as the flash
    # kernel's are: a policy that saves both leaves the rematted forward
    # no reader of the scan
    o = checkpoint_name(o.reshape(B, T, H * Dv),
                        prof.GDN_OUT).reshape(B, T, H, Dv)
    states = checkpoint_name(states, prof.GDN_STATES)
    return o, (q, k, v, g, beta, states)


def _gdn_bwd(chunk, res, d_o):
    q, k, v, g, beta, states = res
    with jax.named_scope(prof.LM_LINATTN_SCAN):
        parts = tuple(_chunks(x, chunk) for x in (q, k, v, g, beta))
        terms, pull = jax.vjp(
            lambda *p: _chunk_terms(*p, q.dtype), *parts)
        d_terms = _scan_backward(terms, states, _chunks(d_o, chunk), q.dtype)
        return tuple(_unchunk(d).astype(x.dtype)
                     for d, x in zip(pull(d_terms), (q, k, v, g, beta)))


_gdn.defvjp(_gdn_fwd, _gdn_bwd)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = CHUNK):
    """q, k [B, T, H, Dk] (normalised and scaled by the caller), v
    [B, T, H, Dv], g [B, T, H] the log-decay (<= 0) and beta [B, T, H] the
    write strength -> o [B, T, H, Dv] in v's dtype. Every sequence starts
    from a zero state; T must divide by ``chunk``."""
    if q.shape[1] % chunk:
        raise ValueError(f"gated_delta_rule: {q.shape[1]} tokens do not "
                         f"divide into chunks of {chunk}")
    vma = _vma(q, k, v, g, beta)
    q, k, v, g, beta = (pcast_varying(x, vma) for x in (q, k, v, g, beta))
    return _gdn(q, k, v, g.astype(jnp.float32), beta.astype(jnp.float32),
                chunk)
