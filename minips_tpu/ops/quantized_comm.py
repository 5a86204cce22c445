"""Quantized collectives for the PS data plane (EQuARX-style, PAPERS.md).

The fused PS step's traffic is two bandwidth-bound collectives per
iteration: all-gather of the sharded parameter vector (pull) and
reduce-scatter of the gradient (push) — SURVEY.md §2.3. On ICI these are
wire-limited, so shrinking bytes-on-wire converts directly into step time;
"EQuARX: quantized all-reduce in XLA" (PAPERS.md) reports ~2x collective
speedup at negligible quality cost with dynamic block quantization. This
module is the same idea expressed at the JAX level, usable inside
``shard_map``:

- ``comm="bfloat16"``: cast → collective → cast. 2x traffic cut; the safe
  default to try first.
- ``comm="int8"``: symmetric per-shard dynamic quantization (max-abs scale
  per contiguous shard chunk), 4x traffic cut. The reduce-scatter becomes
  all-to-all of int8 chunks + local dequantized f32 accumulation, so
  precision loss stays per-hop bounded: sums accumulate in f32, never int8.

Accuracy contract (tests/test_quantized_comm.py): int8 round-trip error is
bounded by scale/2 per element (≈0.4% of the chunk max), and end-to-end LR
training converges to the f32 loss within noise.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

VALID = ("float32", "bfloat16", "int8")


# --------------------------------------------------------------- host codec
# The per-ROW absmax int8 codec for the host PS wire (train/sharded_ps.py):
# the numpy twin of the blockwise device codec below, with the row (not a
# 256-element block) as the scale unit — PS frames already move row-major
# key slices, so one f32 scale per row is the natural framing. Both the
# push leg (gradients, stochastic rounding) and the pull leg (weights,
# nearest rounding) of the sharded PS speak this codec; it lives here so
# the device collectives and the host wire share one quantization home.

def quantize_rows_int8(rows: np.ndarray,
                       rng: np.random.Generator | None = None
                       ) -> tuple[np.ndarray, np.ndarray]:
    """Per-row absmax int8. With ``rng``, rounding is STOCHASTIC (round
    to floor with probability 1-frac, up with probability frac), making
    the codec UNBIASED: E[decode(encode(g))] = g — quantization noise
    averages out across steps instead of accumulating as drift, which is
    why the gradient push wire needs no error-feedback residual (EF
    would require a residual the size of the FULL table on every pusher,
    breaking the sharded PS's 1/N-memory-per-process claim).

    With ``rng=None``, rounding is round-to-NEAREST — the pull-wire mode
    for weights: deterministic, so every puller of an unchanged row
    decodes identical bytes, and half the worst-case per-element error.

    Returns ``(codes int8 [n, dim], scale f32 [n])``; decode is
    ``codes * scale[:, None]``. All-zero rows get scale 0."""
    rows = np.asarray(rows, np.float32)
    scale = (np.abs(rows).max(axis=1) / 127.0).astype(np.float32)
    safe = np.where(scale > 0, scale, 1.0).astype(np.float32)
    x = rows / safe[:, None]
    if rng is None:
        codes = np.rint(x)
    else:
        low = np.floor(x)
        codes = low + (rng.random(rows.shape) < (x - low))
    return np.clip(codes, -127, 127).astype(np.int8), scale


def dequantize_rows_int8(codes: np.ndarray,
                         scale: np.ndarray) -> np.ndarray:
    return codes.astype(np.float32) * scale[:, None]

# ------------------------------------- sparse top-k + blockwise host codec
# The compressed push wire's two levers (SparCML + EQuARX, PAPERS.md):
# magnitude top-k ROW selection over the owner-split gradient (ship the
# mass, not the touch set) and blockwise absmax quantization at 8 or 4
# bits (one f32 scale per HOST_BLOCK flattened elements — the numpy twin
# of the device codec's ``_quantize_blocks`` below, block size tunable).
# The pusher keeps ``g - decode(encode(g))`` plus every unselected row in
# its error-feedback residual store (train/sharded_ps.ResidualStore), so
# unlike the per-row int8 codec above, BIASED nearest rounding is sound
# here: the bias is measured and re-shipped, never accumulated.

HOST_BLOCK = 64  # default blockwise-scale unit for the host topk wire
                 # (f32-scale overhead = 4/HOST_BLOCK bytes per element;
                 # at 64 that is 1/16 the 8-bit code stream)


def topk_rows(rows: np.ndarray, *, mass: float = 0.9,
              frac_cap: float = 0.5) -> np.ndarray:
    """SORTED indices of the smallest row set capturing ``mass`` of the
    squared-L2 gradient mass, capped at ``ceil(frac_cap * n)`` rows —
    'k adaptive to the touched set': a zipf push whose summed hot rows
    dominate selects a few rows; a flat push selects up to the cap and
    leaves the rest to error feedback. Deterministic (stable sort);
    always selects at least one row of a nonzero gradient."""
    n = rows.shape[0]
    if n == 0:
        return np.empty(0, np.int64)
    mag = np.einsum("ij,ij->i", rows, rows, dtype=np.float64)
    total = float(mag.sum())
    cap = max(1, int(np.ceil(frac_cap * n)))
    if total <= 0.0:
        return np.arange(min(1, n), dtype=np.int64)
    order = np.argsort(-mag, kind="stable")
    k = int(np.searchsorted(np.cumsum(mag[order]), mass * total)) + 1
    return np.sort(order[: min(k, cap)])


def _block_grid(flat: np.ndarray, block: int) -> tuple[np.ndarray, int]:
    """Zero-pad a flat f32 array up to a block multiple and view it
    ``[nb, block]`` (zeros never move an absmax)."""
    L = flat.size
    nb = -(-L // block) if L else 0
    pad = nb * block - L
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, np.float32)])
    return flat.reshape(nb, block), L


def quantize_blockwise(rows: np.ndarray, bits: int, *,
                       block: int = HOST_BLOCK,
                       rng: np.random.Generator | None = None
                       ) -> tuple[np.ndarray, np.ndarray]:
    """Blockwise absmax quantization of ``[n, dim]`` f32 rows flattened
    row-major: one f32 scale per ``block`` elements, codes at 8 bits
    (int8 stream) or 4 bits (two codes per byte, uint8 stream, offset
    +8 so the sign needs no second pass). ``rng`` selects stochastic
    rounding (unbiased); None is round-to-nearest (deterministic — the
    serve-plane refresh mode, where every replica must decode the same
    bytes). Returns ``(codes, scales f32 [nb])``."""
    if bits not in (4, 8):
        raise ValueError("blockwise codec supports 4 or 8 bits")
    levels = 127 if bits == 8 else 7
    flat = np.ascontiguousarray(rows, np.float32).reshape(-1)
    grid, L = _block_grid(flat, block)
    scale = (np.abs(grid).max(axis=1) / levels).astype(np.float32)
    safe = np.where(scale > 0, scale, 1.0).astype(np.float32)
    x = grid / safe[:, None]
    if rng is None:
        q = np.rint(x)
    else:
        low = np.floor(x)
        q = low + (rng.random(x.shape) < (x - low))
    q = np.clip(q, -levels, levels).astype(np.int8).reshape(-1)[:L]
    if bits == 8:
        return q, scale
    u = (q.astype(np.int16) + 8).astype(np.uint8)  # 1..15, 0 unused
    if u.size % 2:
        u = np.concatenate([u, np.zeros(1, np.uint8)])
    return (u[0::2] << 4) | u[1::2], scale


def dequantize_blockwise(codes: np.ndarray, scales: np.ndarray,
                         n: int, dim: int, bits: int, *,
                         block: int = HOST_BLOCK) -> np.ndarray:
    """Inverse of :func:`quantize_blockwise` back to ``[n, dim]`` f32."""
    L = n * dim
    if bits == 8:
        q = np.frombuffer(codes, np.int8)[:L].astype(np.float32)
    else:
        packed = np.frombuffer(codes, np.uint8)
        u = np.empty(packed.size * 2, np.uint8)
        u[0::2] = packed >> 4
        u[1::2] = packed & 0x0F
        q = (u[:L].astype(np.int16) - 8).astype(np.float32)
    grid, _ = _block_grid(q, block)
    out = grid * np.asarray(scales, np.float32)[:, None]
    return out.reshape(-1)[:L].reshape(n, dim)


def blockwise_stream_bytes(n: int, dim: int, bits: int,
                           block: int = HOST_BLOCK) -> tuple[int, int]:
    """(code bytes, scale bytes) of the blockwise stream for ``n`` rows —
    the one size formula encoder, decoder, and frame validators share."""
    L = n * dim
    nb = -(-L // block) if L else 0
    code = L if bits == 8 else -(-L // 2)
    return code, 4 * nb


# ------------------------------------------- sorted-run key delta codec
# The other half of the index-stream bill (ROADMAP item 5's "cheap
# adjacent win"): the topk push wire ships SORTED unique keys (np.unique
# upstream, topk_rows returns sorted positions), and a hot zipf working
# set is near-contiguous in key space — so the gaps between adjacent
# keys fit a byte where the absolute keys need 2-8. Encode the first
# key absolute (i64) and the rest as unsigned run deltas at the
# narrowest width the largest gap fits. Strictly-increasing input only
# (deltas >= 1 by construction after dedup); the encoder is the one
# place that checks, so a caller with unsorted keys must sort first.

def delta_stream_bytes(n: int, dw: int) -> int:
    """Byte size of the delta key stream for ``n`` keys at delta width
    ``dw`` — shared by encoder and frame validators."""
    return 0 if n == 0 else 8 + (n - 1) * dw


def encode_key_deltas(keys: np.ndarray) -> tuple[int, bytes]:
    """Delta-encode strictly-increasing int64 ``keys``: 8-byte i64 base
    + ``n-1`` gaps at the narrowest unsigned width ∈ {1, 2, 4, 8} that
    fits the largest gap. Returns ``(delta_width, stream)``."""
    keys = np.ascontiguousarray(keys, np.int64)
    n = keys.size
    if n == 0:
        return 1, b""
    if n == 1:
        return 1, keys.tobytes()
    gaps = np.diff(keys)
    if gaps.min() <= 0:
        raise ValueError("delta key codec requires strictly "
                         "increasing keys")
    top = int(gaps.max())
    dw = 1 if top <= 0xFF else 2 if top <= 0xFFFF \
        else 4 if top <= 0xFFFFFFFF else 8
    dt = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}[dw]
    return dw, keys[:1].tobytes() + gaps.astype(dt).tobytes()


def decode_key_deltas(buf, n: int, dw: int) -> np.ndarray:
    """Inverse of :func:`encode_key_deltas` back to int64 keys."""
    if n == 0:
        return np.empty(0, np.int64)
    base = np.frombuffer(buf[:8], np.int64)
    if n == 1:
        return base.copy()
    dt = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}[dw]
    gaps = np.frombuffer(buf[8:8 + (n - 1) * dw], dt).astype(np.int64)
    out = np.empty(n, np.int64)
    out[0] = base[0]
    np.cumsum(gaps, out=out[1:])
    out[1:] += base[0]
    return out


BLOCK = 256  # int8 quantization block: one f32 scale per 256 elements
             # (1.6% wire overhead). Per-BLOCK scales matter because a
             # raveled model mixes magnitudes (layernorm ~1.0, attention
             # weights ~0.005); one scale per shard would flush the small
             # tensors to zero.


def _check(comm: str) -> None:
    if comm not in VALID:
        raise ValueError(f"comm must be one of {VALID}, got {comm!r}")


def _quantize_blocks(x: jnp.ndarray, block: int = BLOCK):
    """[..., L] f32 → (int8 [..., nb, block], f32 scales [..., nb]).
    L is zero-padded up to a block multiple."""
    L = x.shape[-1]
    nb = -(-L // block)
    pad = nb * block - L
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    xb = x.reshape(*x.shape[:-1], nb, block)
    scale = jnp.maximum(jnp.max(jnp.abs(xb), axis=-1), 1e-30) / 127.0
    q = jnp.round(xb / scale[..., None]).astype(jnp.int8)
    return q, scale


def _dequantize_blocks(q: jnp.ndarray, scale: jnp.ndarray,
                       length: int) -> jnp.ndarray:
    """Inverse of ``_quantize_blocks`` over the last two dims."""
    x = (q.astype(jnp.float32) * scale[..., None])
    return x.reshape(*x.shape[:-2], -1)[..., :length]


def quantized_all_gather(x: jnp.ndarray, axis_name: str,
                         comm: str = "float32") -> jnp.ndarray:
    """All-gather a [shard] f32 vector as ``comm`` dtype; returns f32
    [n * shard] (tiled). int8 sends one f32 scale per BLOCK alongside."""
    _check(comm)
    if comm == "float32":
        return jax.lax.all_gather(x, axis_name, tiled=True)
    if comm == "bfloat16":
        g = jax.lax.all_gather(x.astype(jnp.bfloat16), axis_name, tiled=True)
        return g.astype(jnp.float32)
    shard = x.shape[0]
    q, scale = _quantize_blocks(x)
    qs = jax.lax.all_gather(q, axis_name, tiled=False)      # [n, nb, block]
    ss = jax.lax.all_gather(scale, axis_name, tiled=False)  # [n, nb]
    return _dequantize_blocks(qs, ss, shard).reshape(-1)


def a2a_reduce(chunks: jnp.ndarray, axis_name: str,
               comm: str, *, block: int = BLOCK
               ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The compressed REDUCE leg, shared by the pull/push plane and the
    CollectiveSSP sync wire: ship ``[n, c]`` per-destination chunks via
    all-to-all (same bytes on wire as a reduce-scatter ring) in the
    compressed dtype and accumulate in f32 after decompression — the
    cross-worker sum NEVER runs compressed, so error stays per-hop
    bounded instead of growing with worker count. Returns ``(reduced_c,
    sent)``: my reduced chunk and exactly what I contributed AFTER
    compression (the error-feedback hook: residual = input − sent)."""
    c = chunks.shape[1]
    if comm == "bfloat16":
        sent = chunks.astype(jnp.bfloat16).astype(jnp.float32)
        recv = jax.lax.all_to_all(chunks.astype(jnp.bfloat16), axis_name,
                                  split_axis=0, concat_axis=0, tiled=False)
        return jnp.sum(recv.astype(jnp.float32), axis=0), sent
    q, scale = _quantize_blocks(chunks, block)              # [n, nb, block]
    sent = _dequantize_blocks(q, scale, c)
    # chunk j of every device -> device j; received rows are the n devices'
    # contributions to MY chunk
    q_recv = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                                tiled=False)
    s_recv = jax.lax.all_to_all(scale, axis_name, split_axis=0,
                                concat_axis=0, tiled=False)
    return jnp.sum(_dequantize_blocks(q_recv, s_recv, c), axis=0), sent


def gather_broadcast(chunk: jnp.ndarray, axis_name: str,
                     comm: str) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The compressed REPLICATE leg: all-gather my ``[c]`` chunk in the
    compressed dtype; every participant dequantizes the SAME bytes, so
    the assembled ``[n*c]`` result is bitwise identical everywhere.
    Returns ``(full, gap)`` with ``gap = chunk − what the others will
    decode of it`` — the second compression's error, which the chunk
    owner can fold into its error-feedback residual so BOTH legs'
    bias is compensated, not just the reduce leg's."""
    c = chunk.shape[0]
    if comm == "bfloat16":
        low = chunk.astype(jnp.bfloat16)
        g = jax.lax.all_gather(low, axis_name, tiled=False)
        return g.astype(jnp.float32).reshape(-1), \
            chunk - low.astype(jnp.float32)
    q, s = _quantize_blocks(chunk[None, :])
    decoded = _dequantize_blocks(q, s, c)[0]
    qg = jax.lax.all_gather(q, axis_name, tiled=False)
    sg = jax.lax.all_gather(s, axis_name, tiled=False)
    return _dequantize_blocks(qg[:, 0], sg[:, 0], c).reshape(-1), \
        chunk - decoded


def quantized_psum_scatter(gpad: jnp.ndarray, axis_name: str,
                           comm: str = "float32", *,
                           block: int = BLOCK) -> jnp.ndarray:
    """Reduce-scatter a [n * shard] f32 gradient to this device's [shard]
    chunk, summing over the axis (compressed modes via
    :func:`a2a_reduce`). ``block`` is the absmax scale unit — the mesh
    data plane (train/mesh_plane.py) passes the host wire's block size
    here so the collective tier and the compressed-wire tier are one
    codec with two transports (EQuARX, PAPERS.md)."""
    _check(comm)
    if comm == "float32":
        return jax.lax.psum_scatter(gpad, axis_name, tiled=True)
    n = jax.lax.axis_size(axis_name)
    reduced, _ = a2a_reduce(gpad.reshape(n, -1), axis_name, comm,
                            block=block)
    return reduced


def quantized_psum_scatter_ef(gpad: jnp.ndarray, axis_name: str,
                              comm: str = "float32", *,
                              block: int = BLOCK
                              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`quantized_psum_scatter` with the error-feedback hook kept:
    also returns this device's compression RESIDUAL — input minus what
    :func:`a2a_reduce` actually shipped after quantization, reshaped to
    ``gpad``'s layout so the caller can fold it into its next
    contribution (the leader-side ResidualStore contract,
    train/sharded_ps.py, now shared by the mesh plane's blk8 reduce
    leg). ``float32`` ships exactly, so its residual is exact zeros —
    one signature, the caller never branches on the codec."""
    _check(comm)
    if comm == "float32":
        return (jax.lax.psum_scatter(gpad, axis_name, tiled=True),
                jnp.zeros_like(gpad))
    n = jax.lax.axis_size(axis_name)
    chunks = gpad.reshape(n, -1)
    reduced, sent = a2a_reduce(chunks, axis_name, comm, block=block)
    return reduced, (chunks - sent).reshape(gpad.shape)
