"""Decoder-only transformer LM — the rebuild's long-context model family.

The reference has no attention models at all (SURVEY.md §2.2: configs are
LR/MLP/MF/W&D/w2v), so this family is beyond parity: it exists to exercise
the framework's first-class long-context path — causal ring attention
(``parallel/ring_attention.py``) with the sequence axis sharded across the
mesh — inside the same PS machinery (DenseTable fused step) every other
model uses.

Functional plain-dict params like the other model files, so the whole LM
lives in one DenseTable. Matmuls run bfloat16 on the MXU with float32
params; pre-LN blocks, learned positional embeddings, GELU MLP, weight-tied
output head.

Two attention modes, numerically identical:
- ``apply(params, tokens)`` — single-program causal attention (any device).
- ``apply_sp(params, tokens_local, shift, axis_name)`` — call under
  ``shard_map`` with tokens sharded along the sequence axis; attention runs
  as a ring over ``axis_name`` and positional embeddings are indexed by the
  shard's global offset ``shift``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from minips_tpu.parallel.mesh import DATA_AXIS, pcast_varying
from minips_tpu.utils import profiling as prof
from minips_tpu.parallel.ring_attention import (
    reference_attention,
    ring_attention_local,
)


def init(key, *, vocab: int = 256, dim: int = 64, heads: int = 4,
         depth: int = 2, max_len: int = 1024, mlp_mult: int = 4,
         kv_heads: int = None, rope: bool = False):
    """``kv_heads < heads`` builds a grouped-query model (1 = MQA): the
    K/V projection emits ``kv_heads`` heads that every group of
    ``heads // kv_heads`` q-heads shares — the projection weights, the
    attention K/V activations, and (under sp) the ring's ppermute wire
    all shrink by the group factor. ``None``/``heads`` keeps the classic
    fused [dim, 3, dim] qkv layout (same param tree as before GQA).

    ``rope=True`` replaces the learned positional table with rotary
    embeddings (:func:`rope_rotate` on Q/K inside every attention call):
    no ``pos_emb`` params, no ``max_len`` sequence cap — the long-context
    positional scheme (``max_len`` is ignored)."""
    if dim % heads:
        raise ValueError(f"dim {dim} not divisible by heads {heads}")
    gqa = kv_heads is not None and kv_heads != heads
    if gqa and (kv_heads < 1 or heads % kv_heads):
        raise ValueError(f"kv_heads {kv_heads} must be >= 1 and divide "
                         f"heads {heads}")
    hd = dim // heads
    if rope and hd % 2:
        raise ValueError(f"rope needs an even head dim (dim/heads = {hd})")
    ks = iter(jax.random.split(key, 2 + depth))
    scale = dim ** -0.5
    params = {
        "tok_emb": jax.random.normal(next(ks), (vocab, dim)) * scale,
        "ln_f": {"g": jnp.ones(dim), "b": jnp.zeros(dim)},
        "blocks": [],
    }
    if not rope:
        params["pos_emb"] = (jax.random.normal(next(ks), (max_len, dim))
                             * scale)
    else:
        next(ks)  # burn the key so rope=True doesn't reshuffle block init
    for _ in range(depth):
        kq, kp, ki, ko, kk = jax.random.split(next(ks), 5)
        blk = {
            "ln1": {"g": jnp.ones(dim), "b": jnp.zeros(dim)},
            "ln2": {"g": jnp.ones(dim), "b": jnp.zeros(dim)},
            "proj": jax.random.normal(kp, (dim, dim)) * scale,
            "mlp_in": jax.random.normal(ki, (dim, mlp_mult * dim)) * scale,
            "mlp_out": jax.random.normal(ko, (mlp_mult * dim, dim))
                       * (mlp_mult * dim) ** -0.5,
        }
        if gqa:
            # split layout: full-width Q, narrow fused KV ([dim, 2, kv
            # width], axis 1 = (k, v)); head dim contiguous in the last
            # axis so TP shards both at head boundaries
            blk["wq"] = jax.random.normal(kq, (dim, dim)) * scale
            blk["wkv"] = (jax.random.normal(kk, (dim, 2, kv_heads * hd))
                          * scale)
        else:
            # one [dim, 3, dim] tensor, axis 1 = (q, k, v); the last dim
            # is the head dim (heads contiguous), so tensor parallelism
            # can shard it at head boundaries
            blk["qkv"] = jax.random.normal(kq, (dim, 3, dim)) * scale
        params["blocks"].append(blk)
    return params


def _ln(x, p):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * p["g"] + p["b"]


def _dropout(x, rate, key):
    """Inverted dropout; identity when rate is 0 or no key is given
    (eval). ``rate`` is static, ``key`` traced."""
    if not rate or key is None:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


def _block(h, blk, heads, attn_fn, compute_dtype, psum_axis=None,
           ffn_fn=None, dropout=0.0, rng=None):
    """One pre-LN block. With ``psum_axis`` the block runs Megatron-style
    tensor parallel under shard_map: qkv/mlp_in arrive sharded on their
    OUTPUT feature dim (this device computes heads/k heads and hidden/k
    MLP units), proj/mlp_out on their INPUT dim, and the two row-parallel
    matmuls' partial products are psum'd before each residual add —
    activations stay replicated, two collectives per block.

    ``ffn_fn(blk, x_2d [B*T, D]) -> (y_2d, aux)`` replaces the dense MLP
    (the MoE variant); the dense path reports aux 0. Returns (h, aux)."""
    B, T, _ = h.shape
    tp = 1 if psum_axis is None else jax.lax.axis_size(psum_axis)
    local_heads = heads // tp
    from jax.ad_checkpoint import checkpoint_name
    with jax.named_scope(prof.LM_ATTN):
        x = _ln(h, blk["ln1"]).astype(compute_dtype)
        # q/k/v stay in compute_dtype: the flash kernel runs its dots at
        # the input dtype's MXU rate with f32 accumulation, so a bf16 run
        # keeps bf16 VMEM/HBM traffic end-to-end (upcasting here doubled
        # both and forced f32-rate attention matmuls)
        if "wkv" in blk:
            # grouped-query layout: full-width Q, narrow fused KV; the
            # attention impls map q-head h onto kv head h // g themselves
            q = x @ blk["wq"].astype(compute_dtype)
            kv = jnp.einsum("btd,dce->btce", x,
                            blk["wkv"].astype(compute_dtype))
            # same checkpoint names as the fused layout, so every remat
            # policy ("hybrid_qkv" saves the projections) works unchanged
            q = checkpoint_name(q, "qkv")
            kv = checkpoint_name(kv, "qkv")
            hd = q.shape[-1] // local_heads
            local_kv = kv.shape[-1] // hd
            q = q.reshape(B, T, local_heads, hd)
            k = kv[:, :, 0].reshape(B, T, local_kv, hd)
            v = kv[:, :, 1].reshape(B, T, local_kv, hd)
        else:
            qkv = jnp.einsum("btd,dce->btce", x,
                             blk["qkv"].astype(compute_dtype))
            # named so "hybrid_qkv" can save it — with qkv, attn_out and
            # mlp_hidden all resident, backward recomputes only the
            # attention output projection (2 of 24 D^2-units per block)
            qkv = checkpoint_name(qkv, "qkv")
            q, k, v = (qkv[:, :, i] for i in range(3))
            hd = q.shape[-1] // local_heads
            q = q.reshape(B, T, local_heads, hd)
            k = k.reshape(B, T, local_heads, hd)
            v = v.reshape(B, T, local_heads, hd)
        a = attn_fn(q, k, v).reshape(B, T, -1)
    return _block_tail(h, blk, a, compute_dtype, psum_axis, ffn_fn,
                       dropout, rng)


def _block_tail(h, blk, a, compute_dtype, psum_axis=None, ffn_fn=None,
                dropout=0.0, rng=None):
    """Everything after attention — output projection + residual, then
    MLP (or ``ffn_fn``) + residual. ONE implementation shared by the
    training block above and the KV-cached decode block
    (models/decode.py), so the block math cannot drift between them."""
    from jax.ad_checkpoint import checkpoint_name

    B, T, _ = h.shape
    # named for selective remat: remat="attn" saves this tensor (and, on
    # the kernel path, the forward kernel's own residuals, which carry
    # their names from ops/flash_attention.py), so the backward never
    # re-runs the attention itself (the priciest recompute per byte:
    # flash kernels + T^2 math) while everything else still recomputes
    with jax.named_scope(prof.LM_ATTN):
        a = checkpoint_name(a, "attn_out")
        att = (a.astype(compute_dtype)
               @ blk["proj"].astype(compute_dtype)).astype(jnp.float32)
        if psum_axis is not None:
            att = jax.lax.psum(att, psum_axis)
        if dropout and rng is not None:   # GPT-style residual dropout
            att = _dropout(att, dropout, jax.random.fold_in(rng, 0))
        h = h + att
    with jax.named_scope(prof.LM_MLP):
        if ffn_fn is not None:
            D = h.shape[-1]
            y, aux = ffn_fn(blk, _ln(h, blk["ln2"]).reshape(B * T, D))
            return h + y.reshape(B, T, D), aux
        x = _ln(h, blk["ln2"]).astype(compute_dtype)
        z = x @ blk["mlp_in"].astype(compute_dtype)
        # the [B*T, 4D] PRE-gelu tensor is the bulk of a block's
        # activation memory; the "hybrid" policies save it (with attn_out)
        # so backward skips the expensive up-projection recompute while
        # still shedding the dots-policy tensors that blow HBM at batch
        # 32. It must be the pre-activation: gelu's VJP reads its input,
        # so saving gelu(z) would force the up-projection to be
        # recomputed anyway.
        z = checkpoint_name(z, "mlp_hidden")
        x = jax.nn.gelu(z)
        m = (x @ blk["mlp_out"].astype(compute_dtype)).astype(jnp.float32)
        if psum_axis is not None:
            m = jax.lax.psum(m, psum_axis)
        if dropout and rng is not None:
            m = _dropout(m, dropout, jax.random.fold_in(rng, 1))
        return h + m, 0.0


def _forward(params, tokens, pos, heads, attn_fn, compute_dtype,
             psum_axis=None, apply_blocks=None, ffn_fn=None, remat=False,
             head=True, dropout=0.0, rng=None):
    """Returns (logits, total aux loss) — aux is nonzero only for MoE
    ``ffn_fn`` blocks; the plain ``apply*`` wrappers drop it. ``remat``
    wraps each block in ``jax.checkpoint`` so the backward pass recomputes
    block activations instead of storing them — the standard HBM-for-FLOPs
    trade that long-context training needs."""
    if "pos_emb" in params:
        # static check: jax clamps out-of-range indices silently, so an
        # oversized sequence would reuse the last positional embedding row
        # for every tail position instead of erroring
        max_len = params["pos_emb"].shape[0]
        if pos.shape[0] > max_len:
            raise ValueError(f"sequence length {pos.shape[0]} exceeds the "
                             f"model's max_len {max_len}")
        with jax.named_scope(prof.LM_EMBED):
            h = params["tok_emb"][tokens] + params["pos_emb"][pos]
    else:
        # rope model: positions enter through the attention rotation
        # (below); no table, no sequence-length cap
        with jax.named_scope(prof.LM_EMBED):
            h = params["tok_emb"][tokens]
        if attn_fn is not None:
            attn_fn = _rope_wrap(attn_fn, pos)
    if not 0.0 <= dropout < 1.0:
        raise ValueError(f"dropout rate {dropout} outside [0, 1)")
    if dropout and apply_blocks is not None:
        # the parallel-schedule path replaces the sequential layer loop,
        # so the per-block residual dropout below would be silently
        # skipped — only embedding dropout would apply, and a library
        # caller would under-regularize without noticing (lm_example
        # guards this at the CLI; the library must refuse too, like the
        # adamw-on-tp/pp/ep refusals)
        raise ValueError("dropout > 0 is not supported on parallel-"
                         "schedule (apply_blocks) paths: per-block "
                         "residual dropout lives in the sequential loop")
    aux_total = 0.0
    if dropout and rng is not None:   # embedding dropout (GPT-style)
        h = _dropout(h, dropout, jax.random.fold_in(rng, 2 ** 20))
    if apply_blocks is not None:
        # parallel schedules (e.g. the GPipe pipeline) replace the
        # sequential layer loop but share embedding/head/LN code
        h = apply_blocks(h)
    else:
        block_fn = _block
        if remat:
            # dropout (7) is static config like its neighbours; the rng
            # key (8) is a traced array and replays exactly in recompute
            block_fn = jax.checkpoint(
                _block, static_argnums=(2, 3, 4, 5, 6, 7),
                policy=_remat_policy(remat))
        for i, blk in enumerate(params["blocks"]):
            blk_rng = (jax.random.fold_in(rng, i)
                       if dropout and rng is not None else None)
            h, aux = block_fn(h, blk, heads, attn_fn, compute_dtype,
                              psum_axis, ffn_fn, dropout, blk_rng)
            aux_total = aux_total + aux
    with jax.named_scope(prof.LM_HEAD):
        h = _ln(h, params["ln_f"])
        if not head:  # chunked-CE path applies the tied head itself
            return h, aux_total
        # weight-tied head
        logits = (h.astype(compute_dtype)
                  @ params["tok_emb"].T.astype(compute_dtype)
                  ).astype(jnp.float32)
    return logits, aux_total


def _remat_policy(remat):
    """Rematerialization spectrum for the block checkpoint — the
    FLOPs↔HBM dial (SURVEY brief: jax.checkpoint to trade FLOPs for
    memory). Every mode but ``True`` also keeps what the flash forward
    kernel leaves for its backward kernel (``prof.FLASH_RESIDUALS``:
    ``out`` and the row logsumexp ``lse``, named in
    ``ops/flash_attention.py``), so with ``attn_impl="flash"`` the
    kernel runs once a block and step, not again for the backward; off
    the kernel path those names do not occur and nothing more is kept.

    - ``True``  — save only block inputs; backward recomputes the whole
      block, the flash forward kernel included (max memory savings, +1/3
      executed FLOPs).
    - ``"attn"`` — additionally save each block's attention output
      (checkpoint_name above): the backward re-runs the matmuls but never
      the attention itself. Costs one [B, T, D] compute_dtype tensor
      (bf16 in the default mixed-precision run) per block; on the kernel
      path that tensor is the kernel's ``out``, plus ``lse``, one
      float32 a query row and head.
    - ``"dots"`` — save what the MXU made: every matmul output and the
      kernel's ``out`` (the product p v; with the reference attention the
      same policy keeps that dot itself) with its ``lse``; recompute only
      elementwise (LN/gelu/softmax): near-zero recompute, the memory win
      is only the elementwise intermediates.
    - ``"hybrid"`` — save attn_out + the [B*T, 4D] pre-gelu mlp_hidden:
      backward recomputes only qkv + the attention output projection
      (~8 of 24 D^2-units per block, ~1.1x total FLOPs) at a fraction
      of dots' residency — for batch sizes where dots spills HBM.
    - ``"hybrid_qkv"`` — hybrid plus the qkv tensor: recompute drops to
      the attention output projection alone (~1.03x) for +3 D-units of
      residency.
    """
    if remat is True:
        return None
    policies = jax.checkpoint_policies
    if remat == "dots":
        return policies.save_from_both_policies(
            policies.checkpoint_dots,
            policies.save_only_these_names(*prof.FLASH_RESIDUALS))
    names = {"attn": ("attn_out",),
             "hybrid": ("attn_out", "mlp_hidden"),
             "hybrid_qkv": ("attn_out", "mlp_hidden", "qkv")}.get(remat)
    if names is None:
        raise ValueError(f"unknown remat mode {remat!r} "
                         "(expected True/False, 'attn', 'dots', 'hybrid' "
                         "or 'hybrid_qkv')")
    return policies.save_only_these_names(*names, *prof.FLASH_RESIDUALS)


def decay_mask(params):
    """Params-shaped 0/1 pytree for AdamW's decoupled weight decay: decay
    matrices (ndim >= 2 — projections, embeddings), never LayerNorm
    gains/biases (the standard rule). Feed to
    ``DenseTable(updater="adamw", updater_kwargs={"decay_mask": ...})``,
    which ravels it alongside the params."""
    return jax.tree.map(
        lambda x: jnp.full(x.shape, float(jnp.ndim(x) >= 2), x.dtype),
        params)


def rope_rotate(x, pos, theta: float = 10000.0):
    """Rotary position embedding: rotate half-split head-dim pairs of
    ``x`` [B, T, H, hd] by angles ``pos · theta^(-2i/hd)`` (``pos`` [T],
    GLOBAL positions — the sp path passes each shard's offset range, so
    K rows are rotated at their home shard before the ring moves them).
    Angles/trig run in f32; the product drops back to x.dtype so bf16
    runs keep bf16-rate attention dots."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]      # [T, half]
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1)


def _rope_wrap(attn_fn, pos):
    """Attention wrapper applying RoPE to Q and K (never V). Works for
    any head layout — GQA's narrow K rotates the same way."""
    return lambda q, k, v: attn_fn(rope_rotate(q, pos),
                                   rope_rotate(k, pos), v)


def _attn_fn(attn_impl: str, window=None):
    """Causal attention implementation by name: ``reference`` (full [T, T]
    scores, XLA-fused) or ``flash`` (ops/flash_attention.py — Pallas kernel
    on TPU, exact blockwise scan elsewhere; O(T) memory either way).
    ``window`` W: the query at t sees the keys t - W < s <= t alone (the
    windowed kernels; ``reference`` masks its full scores)."""
    from minips_tpu.ops.flash_attention import (blockwise_attention,
                                                flash_attention)
    if attn_impl == "flash":
        return lambda q, k, v: flash_attention(q, k, v, causal=True,
                                               window=window)
    if attn_impl != "reference":
        raise ValueError(f"unknown attn_impl {attn_impl!r} "
                         "(expected 'reference' or 'flash')")
    if window is not None:      # one block of keys: the full scores
        return lambda q, k, v: blockwise_attention(
            q, k, v, causal=True, block_k=k.shape[1], window=window)
    return lambda q, k, v: reference_attention(q, k, v, causal=True)


def apply(params, tokens, *, heads=4, compute_dtype=jnp.bfloat16,
          remat=False, attn_impl="reference", dropout=0.0, rng=None):
    """Logits [B, T, vocab]; plain causal attention in one program.
    ``heads`` is static model structure, not table state — pass the value
    used at ``init``. ``remat=True`` recomputes block activations in the
    backward pass (jax.checkpoint) to cut peak HBM on long sequences.
    ``attn_impl="flash"`` swaps in the fused O(T)-memory attention.
    ``dropout`` (with an ``rng`` key) enables GPT-style embedding +
    residual dropout — train-time only; omit both at eval."""
    T = tokens.shape[1]
    return _forward(params, tokens, jnp.arange(T), heads,
                    _attn_fn(attn_impl), compute_dtype, remat=remat,
                    dropout=dropout, rng=rng)[0]


def apply_sp(params, tokens_local, shift, *, heads=4, axis_name=DATA_AXIS,
             compute_dtype=jnp.bfloat16, remat=False,
             attn_impl="reference"):
    """Sequence-parallel logits for a local token shard [B, T_local].

    Call inside ``shard_map``: ``shift`` is this shard's global sequence
    offset (``axis_index * T_local``); full params, sharded activations —
    sequence parallelism in its pure form. Two SP strategies x two
    attention impls (``attn_impl``):

    - ``"reference"`` / ``"flash"`` — causal RING over ``axis_name``
      (K/V rotate via ppermute; flash = the offset-masked kernel per
      ring step). O(T/N) K/V memory; any head count.
    - ``"a2a"`` / ``"a2a_flash"`` — ALL-TO-ALL re-shard to head groups
      with the full sequence local (parallel/a2a_attention.py, Ulysses
      lineage): two collectives total, attention fully local (a2a_flash
      = the fused kernel at full rate, no ring bookkeeping). Needs
      ``heads`` divisible by the axis size. RoPE rotates before the
      exchange, so positions stay correct.
    """
    T_local = tokens_local.shape[1]
    pos = shift + jnp.arange(T_local)
    if attn_impl == "flash":
        from minips_tpu.ops.flash_attention import (
            ring_flash_attention_local)

        attn = lambda q, k, v: ring_flash_attention_local(  # noqa: E731
            q, k, v, axis_name=axis_name, causal=True)
    elif attn_impl == "reference":
        attn = lambda q, k, v: ring_attention_local(  # noqa: E731
            q, k, v, axis_name=axis_name, causal=True)
    elif attn_impl in ("a2a", "a2a_flash"):
        from minips_tpu.parallel.a2a_attention import a2a_attention_local

        inner = None
        if attn_impl == "a2a_flash":
            from minips_tpu.ops.flash_attention import flash_attention

            inner = flash_attention  # causal/scale threaded by a2a
        attn = lambda q, k, v: a2a_attention_local(  # noqa: E731
            q, k, v, axis_name=axis_name, causal=True, inner=inner)
    else:
        raise ValueError(f"unknown attn_impl {attn_impl!r} (expected "
                         "'reference', 'flash', 'a2a', or 'a2a_flash')")
    return _forward(params, tokens_local, pos, heads, attn,
                    compute_dtype, remat=remat)[0]


def sp_train_wiring(heads, T_local, axis_name=DATA_AXIS,
                    attn_impl="reference"):
    """``(grad_fn, batch_spec)`` for SEQUENCE-parallel training through
    ``DenseTable.make_step``: the batch is ``{"inp", "tgt"}`` of [B, T]
    tokens sharded on the sequence axis; each shard computes its local
    loss at its global shift and ring attention stitches the sequence.
    One wiring shared by ``lm_example --layout sp`` and the multi-host
    lm path (apps/multihost_example.py) so the shift/reduce semantics
    cannot drift between them."""
    from jax.sharding import PartitionSpec as P

    def sp_grad(p, b):
        def shard_loss(p_, inp, tgt):
            shift = jax.lax.axis_index(axis_name) * T_local
            return loss_sp(p_, inp, tgt, shift, heads=heads,
                           reduce="local", attn_impl=attn_impl)
        return jax.value_and_grad(shard_loss)(p, b["inp"], b["tgt"])

    return sp_grad, {"inp": P(None, axis_name), "tgt": P(None, axis_name)}


def apply_tp(params, tokens, *, heads=4, axis_name="model",
             compute_dtype=jnp.bfloat16):
    """Megatron-style tensor-parallel logits — call INSIDE shard_map with
    block weights sharded per ``tp_specs`` (qkv/mlp_in column-parallel,
    proj/mlp_out row-parallel; embeddings/LN replicated). Activations are
    replicated across the ``axis_name`` axis; two psums per block.

    For training, take ``value_and_grad`` OUTSIDE the shard_map (of a loss
    that closes over the shard_map call): shard_map's transpose inserts the
    Megatron conjugate-operator reductions automatically. Raw local grads
    taken inside would mis-reduce the replicated params
    (tests/test_tensor_parallel.py::test_tp_composes_with_dp).
    """
    tp = jax.lax.axis_size(axis_name)
    if heads % tp:
        raise ValueError(f"heads {heads} not divisible by tensor-parallel "
                         f"size {tp} (head-boundary sharding)")
    blk0 = params["blocks"][0]
    if "wkv" in blk0:
        # params arrive SHARDED here: wkv's local width must still be a
        # whole number of kv heads, else the head-boundary sharding split
        # a kv head across model shards
        hd = params["tok_emb"].shape[1] // heads
        local_w = blk0["wkv"].shape[2]
        if local_w % hd:
            raise ValueError(
                f"GQA kv_heads {local_w * tp // hd} not divisible by "
                f"tensor-parallel size {tp} (each shard needs whole kv "
                f"heads)")
    T = tokens.shape[1]
    return _forward(params, tokens, jnp.arange(T), heads,
                    lambda q, k, v: reference_attention(q, k, v, causal=True),
                    compute_dtype, psum_axis=axis_name)[0]


def tp_specs(params, axis_name="model"):
    """PartitionSpec pytree for ``apply_tp``: shard each block's qkv and
    mlp_in on their output feature dim, proj and mlp_out on their input
    dim; replicate embeddings and layernorms."""
    from jax.sharding import PartitionSpec as P

    def one_block(blk):
        out = {
            "ln1": jax.tree.map(lambda _: P(), blk["ln1"]),
            "ln2": jax.tree.map(lambda _: P(), blk["ln2"]),
            "proj": P(axis_name, None),
            "mlp_in": P(None, axis_name),
            "mlp_out": P(axis_name, None),
        }
        if "wkv" in blk:   # GQA: both projections column-parallel at
            out["wq"] = P(None, axis_name)         # head boundaries
            out["wkv"] = P(None, None, axis_name)
        else:
            out["qkv"] = P(None, None, axis_name)
        return out

    return {
        "tok_emb": P(),
        **({"pos_emb": P()} if "pos_emb" in params else {}),
        "ln_f": jax.tree.map(lambda _: P(), params["ln_f"]),
        "blocks": [one_block(b) for b in params["blocks"]],
    }


def apply_pp(params, tokens, *, heads=4, axis_name="model",
             num_microbatches=4, compute_dtype=jnp.bfloat16):
    """GPipe pipeline-parallel logits — call INSIDE shard_map with
    ``params["blocks"]`` STACKED (parallel/pipeline.stack_layers) and its
    leading depth axis sharded over ``axis_name``; embeddings/LN
    replicated (see ``pp_specs``). The batch splits into
    ``num_microbatches`` that flow through the stages via ppermute.

    Like ``apply_tp``, take grads OUTSIDE the shard_map.
    """
    from minips_tpu.parallel.pipeline import gpipe

    B, T = tokens.shape
    if B % num_microbatches:
        raise ValueError(f"batch {B} not divisible into "
                         f"{num_microbatches} microbatches")
    blocks_local = params["blocks"]  # leading depth axis, local slice
    attn = lambda q, k, v: reference_attention(  # noqa: E731
        q, k, v, causal=True)
    if "pos_emb" not in params:   # rope: _forward's wrap can't reach the
        attn = _rope_wrap(attn, jnp.arange(T))   # stage closure, wrap here

    def stage_fn(x):
        def one(hc, blk):
            h2, _ = _block(hc, blk, heads, attn, compute_dtype)
            return h2, None
        return jax.lax.scan(one, x, blocks_local)[0]

    def piped_blocks(h):
        h_mb = h.reshape(num_microbatches, B // num_microbatches, T, -1)
        return gpipe(stage_fn, h_mb, axis_name=axis_name).reshape(B, T, -1)

    return _forward(params, tokens, jnp.arange(T), heads, None,
                    compute_dtype, apply_blocks=piped_blocks)[0]


def pp_specs(params_stacked, axis_name="model"):
    """PartitionSpec pytree for ``apply_pp``: shard every stacked block
    leaf on its leading depth axis; replicate everything else."""
    from jax.sharding import PartitionSpec as P

    return {
        "tok_emb": P(),
        **({"pos_emb": P()} if "pos_emb" in params_stacked else {}),
        "ln_f": jax.tree.map(lambda _: P(), params_stacked["ln_f"]),
        "blocks": jax.tree.map(lambda _: P(axis_name),
                               params_stacked["blocks"]),
    }


def init_moe_lm(key, *, vocab: int = 256, dim: int = 64, heads: int = 4,
                depth: int = 2, max_len: int = 1024, num_experts: int = 8,
                expert_hidden: int = 256, kv_heads: int = None,
                rope: bool = False):
    """LM variant whose FFNs are Switch-style MoE layers (parallel/moe.py):
    same attention as ``init`` (incl. grouped-query via ``kv_heads``),
    each block's MLP replaced by router + stacked expert weights. Use with
    ``apply_ep`` under shard_map (experts sharded over the data axis) or
    with moe_apply_dense on one device."""
    from minips_tpu.parallel.moe import init_moe

    k_base, k_moe = jax.random.split(key)
    base = init(k_base, vocab=vocab, dim=dim, heads=heads, depth=depth,
                max_len=max_len, mlp_mult=1, kv_heads=kv_heads, rope=rope)
    ks = jax.random.split(k_moe, depth)
    for i, blk in enumerate(base["blocks"]):
        del blk["mlp_in"], blk["mlp_out"]
        blk["moe"] = init_moe(ks[i], num_experts, dim, expert_hidden)
    return base


def apply_moe_dense(params, tokens, *, heads=4, capacity: int,
                    compute_dtype=jnp.bfloat16, k_top: int = 1):
    """Single-program MoE-LM logits (oracle / one device):
    returns (logits, total aux loss)."""
    from minips_tpu.parallel.moe import moe_apply_dense

    return _forward(
        params, tokens, jnp.arange(tokens.shape[1]), heads,
        lambda q, k, v: reference_attention(q, k, v, causal=True),
        compute_dtype,
        ffn_fn=lambda blk, x: moe_apply_dense(
            blk["moe"], x, capacity=capacity, compute_dtype=compute_dtype,
            k_top=k_top))


def apply_ep(params, tokens_local, *, heads=4, axis_name=DATA_AXIS,
             capacity: int, compute_dtype=jnp.bfloat16, k_top: int = 1):
    """Expert-parallel MoE-LM logits — call INSIDE shard_map with the
    batch sharded over ``axis_name``, attention weights replicated, and
    each block's expert stacks sharded per ``ep_lm_specs``. Attention runs
    data-parallel per shard; every FFN's tokens fan out to the experts by
    all_to_all. Grads OUTSIDE the shard_map, like the other schedules."""
    from minips_tpu.parallel.moe import moe_apply_local

    return _forward(
        params, tokens_local, jnp.arange(tokens_local.shape[1]), heads,
        lambda q, k, v: reference_attention(q, k, v, causal=True),
        compute_dtype,
        ffn_fn=lambda blk, x: moe_apply_local(
            blk["moe"], x, axis_name=axis_name, capacity=capacity,
            compute_dtype=compute_dtype, k_top=k_top))


def ep_lm_specs(params, axis_name=DATA_AXIS):
    """PartitionSpec pytree for ``apply_ep``: expert stacks sharded over
    the axis, everything else replicated."""
    from jax.sharding import PartitionSpec as P

    from minips_tpu.parallel.moe import ep_specs

    def one_block(blk):
        out = {
            "ln1": jax.tree.map(lambda _: P(), blk["ln1"]),
            "ln2": jax.tree.map(lambda _: P(), blk["ln2"]),
            "proj": P(),
            "moe": ep_specs(axis_name),
        }
        # attention projections replicate either layout (fused or GQA)
        for name in ("qkv", "wq", "wkv"):
            if name in blk:
                out[name] = P()
        return out

    return {
        "tok_emb": P(),
        **({"pos_emb": P()} if "pos_emb" in params else {}),
        "ln_f": jax.tree.map(lambda _: P(), params["ln_f"]),
        "blocks": [one_block(b) for b in params["blocks"]],
    }


@jax.named_scope(prof.LM_HEAD)
def nll(logits, targets):
    """Mean next-token negative log-likelihood — the one cross-entropy
    shared by every layout (full/sp/tp/pp)."""
    logp = jax.nn.log_softmax(logits)
    return jnp.mean(
        -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0])


def _head_chunks(h, targets, chunk):
    """[B, T, ..] -> [T / chunk, B, chunk, ..]: what the head's scan walks."""
    B, T, D = h.shape
    n = T // chunk
    return (jnp.moveaxis(h.reshape(B, n, chunk, D), 1, 0),
            jnp.moveaxis(targets.reshape(B, n, chunk), 1, 0))


def _chunk_logp(hc, w, tc):
    """One chunk's log-probabilities (float32, from a product in ``w``'s
    dtype) and its summed NLL: :func:`nll`'s reduction, not yet averaged."""
    logp = jax.nn.log_softmax((hc.astype(w.dtype) @ w.T).astype(jnp.float32))
    return logp, -jnp.take_along_axis(logp, tc[..., None], axis=-1).sum()


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _nll_chunked(h, tok_emb, targets, chunk, compute_dtype):
    w = tok_emb.astype(compute_dtype)

    def body(acc, xt):
        return acc + _chunk_logp(xt[0], w, xt[1])[1], None

    total, _ = jax.lax.scan(
        body, pcast_varying(jnp.zeros((), jnp.float32), jax.typeof(h).vma),
        _head_chunks(h, targets, chunk))
    return total / targets.size


def _nll_chunked_fwd(h, tok_emb, targets, chunk, compute_dtype):
    # the loss is a scalar, so a chunk's dlogits = (softmax - onehot) / (B T)
    # is known while its logits are live: dh and dW are formed in the same
    # loop, in the dtypes autodiff would give them, and the backward rule
    # only scales them by the scalar cotangent
    w = tok_emb.astype(compute_dtype)
    vma = jax.typeof(h).vma
    vocab = jnp.arange(tok_emb.shape[0])

    def body(carry, xt):
        acc, dw = carry
        hc, tc = xt
        logp, nll_sum = _chunk_logp(hc, w, tc)
        dlogits = ((jnp.exp(logp) - (tc[..., None] == vocab))
                   / targets.size).astype(compute_dtype)
        dw = dw + jnp.einsum("bcv,bcd->vd", dlogits,
                             hc.astype(compute_dtype)).astype(dw.dtype)
        return (acc + nll_sum, dw), (dlogits @ w).astype(hc.dtype)

    (total, dw), dhs = jax.lax.scan(
        body, (pcast_varying(jnp.zeros((), jnp.float32), vma),
               pcast_varying(jnp.zeros_like(tok_emb), vma)),
        _head_chunks(h, targets, chunk))
    return total / targets.size, (jnp.moveaxis(dhs, 0, 1).reshape(h.shape),
                                  dw)


def _nll_chunked_bwd(chunk, compute_dtype, res, g):
    return tuple(x * g.astype(x.dtype) for x in res) + (None,)


_nll_chunked.defvjp(_nll_chunked_fwd, _nll_chunked_bwd)


@jax.named_scope(prof.LM_HEAD)
def nll_chunked(h, tok_emb, targets, chunk, compute_dtype=jnp.bfloat16):
    """Tied-head projection + cross-entropy, scanned over sequence chunks
    so the full ``[B, T, vocab]`` f32 logits tensor NEVER exists: each
    chunk's logits die inside its scan step. At bench shapes (B=64, T=1024,
    V=16384) that tensor is 4.3 GB of f32 each way. Under differentiation
    the same ONE loop also forms the chunk's ``dlogits``, ``dh`` and its
    share of ``dW`` while the logits are live (a ``jax.custom_vjp``), so a
    training step runs the three vocabulary-sized products a chunk that
    its mathematics needs and nothing is computed twice; the residuals are
    ``dh`` ``[B, T, D]`` and ``dW`` ``[V, D]``, which a backward pass holds
    at that point anyway. Not differentiated it is one product a chunk.
    Numerics: identical reduction tree to :func:`nll` per chunk, summed in
    f32; products in ``compute_dtype``, ``dW`` summed over the chunks in
    ``tok_emb``'s dtype: oracle-equality tested in
    tests/test_transformer.py."""
    if h.shape[1] % chunk:
        raise ValueError(
            f"seq len {h.shape[1]} must divide by head chunk {chunk}")
    # one set of varying axes for the inputs, the scan's carries and the
    # cotangents (a custom_vjp's rule must return cotangents of the
    # primals' own types); where an input varies over fewer, this pcast's
    # transpose is the psum its gradient needs
    vma = frozenset().union(*(jax.typeof(x).vma
                              for x in (h, tok_emb, targets)))
    return _nll_chunked(pcast_varying(h, vma), pcast_varying(tok_emb, vma),
                        targets, chunk, compute_dtype)


def loss(params, batch, *, heads=4, compute_dtype=jnp.bfloat16,
         attn_impl="reference", remat=False, head_chunk=0, dropout=0.0):
    """Next-token cross-entropy; batch = {"tokens": [B, T+1] int32}.
    ``remat=True`` recomputes block activations in the backward pass —
    activation memory stops scaling with depth, the standard trade for
    fitting larger models (SURVEY brief: jax.checkpoint to trade FLOPs
    for HBM). ``head_chunk > 0`` computes the tied head + CE in sequence
    chunks of that size (:func:`nll_chunked`) so the [B, T, vocab] logits
    never materialize. ``dropout > 0`` reads the step's PRNG key from
    ``batch["rng"]`` (the fused step is pure, so randomness must ride the
    batch) and raises if it is absent.

    ``batch["rng"]`` contract: a RAW uint32 key array — ``[2]`` (one key,
    replicated), or ``[W, 2]`` fed through shard_map with ``batch_spec
    P(DATA_AXIS)`` so each worker's shard sees its own ``[1, 2]`` slice
    (distinct masks per worker). New-style typed keys
    (``jax.random.key``) are rejected: a typed ``[W]`` stack would bypass
    the per-worker slice below and silently broadcast one mask."""
    toks = batch["tokens"]
    rng = batch.get("rng")
    if dropout and rng is None:
        raise ValueError('dropout > 0 needs a per-step key in '
                         'batch["rng"] (the fused step is pure)')
    if dropout and jnp.issubdtype(rng.dtype, jax.dtypes.prng_key):
        # only when the rng will actually be consumed: an eval call
        # (dropout=0) reusing a training batch dict must not start
        # rejecting a key it never reads
        raise TypeError('batch["rng"] must be a RAW uint32 key array '
                        '([2] or [W, 2] via jax.random.PRNGKey), not a '
                        'typed jax.random.key array: the per-worker '
                        '[W, 2] slicing below cannot see typed-key '
                        'stacks and would silently reuse one mask')
    if rng is not None and rng.ndim == 2:
        # per-WORKER keys sharded over the data axis (a [W, 2] stack fed
        # with batch_spec P(DATA_AXIS)): each shard sees its [1, 2] slice
        # — distinct dropout masks per worker, not one replicated pattern
        if dropout and rng.shape[-1] != 2:
            raise ValueError(f'batch["rng"] 2-D stack must be [W, 2] raw '
                             f'uint32 keys, got {rng.shape}')
        rng = rng[0]
    if head_chunk:
        T = toks.shape[1] - 1
        h, _ = _forward(params, toks[:, :-1], jnp.arange(T), heads,
                        _attn_fn(attn_impl), compute_dtype, remat=remat,
                        head=False, dropout=dropout, rng=rng)
        return nll_chunked(h, params["tok_emb"], toks[:, 1:], head_chunk,
                           compute_dtype)
    logits = apply(params, toks[:, :-1], heads=heads,
                   compute_dtype=compute_dtype, attn_impl=attn_impl,
                   remat=remat, dropout=dropout, rng=rng)
    return nll(logits, toks[:, 1:])


def grad_fn(params, batch, *, heads=4, attn_impl="reference", remat=False,
            head_chunk=0, dropout=0.0):
    l, g = jax.value_and_grad(
        lambda p, b: loss(p, b, heads=heads, attn_impl=attn_impl,
                          remat=remat, head_chunk=head_chunk,
                          dropout=dropout))(params, batch)
    return l, g


def loss_sp(params, tokens_local, targets_local, shift, *, heads=4,
            axis_name=DATA_AXIS, compute_dtype=jnp.bfloat16,
            reduce="pmean", attn_impl="reference"):
    """Per-shard next-token loss over the shard's tokens.

    ``reduce="pmean"`` returns the global mean loss (standalone use — take
    ``jax.grad`` OUTSIDE the shard_map). ``reduce="local"`` returns the
    shard-local mean: required when differentiating INSIDE shard_map under
    ``DenseTable.make_step``, whose psum_scatter + 1/N already averages the
    per-shard grads — a pmean here would double-scale them by 1/N.
    """
    logits = apply_sp(params, tokens_local, shift, heads=heads,
                      axis_name=axis_name, compute_dtype=compute_dtype,
                      attn_impl=attn_impl)
    local = nll(logits, targets_local)
    if reduce == "local":
        return local
    return jax.lax.pmean(local, axis_name)
