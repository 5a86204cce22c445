"""In-mesh collective data plane for the sharded PS (``MINIPS_MESH=1``).

The third data plane next to the zmq/native/shm HOST wire (comm/bus.py):
instead of routing per-owner key slices over sockets or rings, the whole
gang lives on one device mesh and exchanges owner-split rows with XLA
collectives — the retrieval target's endgame (SNIPPETS.md header,
ROADMAP item 1) and the bridge between the host-wire PS and the
fused-SPMD numbers (bench r02's ~915k samples/sec/chip vs the wire
path's control-plane rates):

- **server state is pjit-sharded**: each table's rows AND its updater
  state (adagrad accumulator, adam moments/steps) live as device arrays
  range-sharded across the mesh's ``shard`` axis
  (``NamedSharding(mesh, P("shard"))``) — the updater step itself runs
  sharded per "Automatic Cross-Replica Sharding of Weight Update in
  Data-Parallel Training" (PAPERS.md): no replicated optimizer math, no
  host round-trip on the hot path;
- **push ≡ reduce-scatter**: each logical rank's dense row-space
  contribution rides a ``shard_map``-level ``psum_scatter`` that sums
  across ranks and leaves every device exactly its owned row range;
- **pull ≡ all-gather**: the updated owner shards reassemble on every
  device with one ``all_gather`` fused into the same XLA program;
- **BSP/SSP gate the collective, not wire frames**: the plane keeps a
  DEVICE-SIDE clock vector (one entry per logical rank); pull admission
  is the shared ``consistency.gate.admits`` predicate evaluated against
  ``min`` of that vector — the same clk−s bound as the owner-side park
  on the wire planes, and under BSP the apply wave is the barrier;
- **optional quantized tier** (``comm="blk8"``): the reduce leg runs
  ``ops.quantized_comm.quantized_psum_scatter`` — quantize to blockwise
  absmax int8 codes, exchange, dequantize-ACCUMULATE in f32
  (EQuARX-style), sharing the blockwise codec with the PR9 compressed
  host wire so there is one compression story with two transports.

Semantics vs the wire planes (the consistency contract survives the
transport swap):

- Pushes DEPOSIT into a per-rank dense row-space buffer (duplicate keys
  coalesced exactly like the wire's client-side dedup: per-dim f64
  bincount, rounded once to f32 — bitwise the frame the wire would
  ship). An APPLY WAVE — one jitted program: reduce-scatter, sharded
  updater, all-gather — fires when every live rank has a deposit, when
  a depositing rank pulls (read-your-own-writes), and at every
  ``tick``/``finalize`` (so a rank's step-k pushes are in the shared
  state BEFORE its clock reads k — the wire's per-link-FIFO staleness
  argument, enforced by program order instead of frame order).
- BSP + sgd is BITWISE-equal to the zmq wire path (the
  ``run_bsp_lockstep`` drill pins it): a wave with one push per rank
  applies ``w -= lr * Σ_r g_r`` where cross-rank zeros are exact, i.e.
  exactly the per-push server apply.
- Stateful updaters apply ONE step per wave to each touched row (adam
  stays lazy via a reduced touch mask): when two ranks hit the same row
  in one wave the gradients sum before the update — gradient
  aggregation semantics, vs the wire's update-per-frame. Same
  fixed-point family, documented divergence (docs/architecture.md).

Development and tier-1 run on CPU via the repo's established
``--xla_force_host_platform_device_count`` pattern (tests/conftest.py);
real meshes swap the device list, nothing else.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

import numpy as np

from minips_tpu.consistency.gate import RETIRED_CLOCK, admits
from minips_tpu.obs import flight as _fl
from minips_tpu.obs import window as _ow
from minips_tpu.obs.hist import Log2Histogram, summarize_counts

MESH_AXIS = "shard"
VALID_MESH_COMM = ("float32", "blk8")
# BSP tick-flush grace: how long a ticking rank lets the eager full
# wave fire before solo-flushing its own deposits (see
# MeshPlane._flush_rank_locked) — generous vs a step, invisible vs the
# gate timeout
_BSP_FLUSH_GRACE = 0.05

__all__ = ["MeshPlane", "MeshRank", "MeshTable", "MeshAggregator",
           "resolve_plane", "resolve_deposit", "MESH_AXIS",
           "VALID_MESH_COMM"]


def resolve_plane(plane: Optional[str]) -> str:
    """The data-plane selection rule every entrypoint shares (same
    explicit-wins-over-env convention as ``make_bus``): an explicit
    ``plane`` wins, else ``MINIPS_MESH`` (any value but ''/'0') selects
    the in-mesh collective plane, else the host wire."""
    if plane:
        if plane not in ("wire", "mesh"):
            raise ValueError(f"plane must be 'wire' or 'mesh', "
                             f"got {plane!r}")
        return plane
    env = os.environ.get("MINIPS_MESH", "").strip()
    return "mesh" if env not in ("", "0") else "wire"


def resolve_deposit(deposit: Optional[str] = None) -> str:
    """Deposit-buffer selection, same explicit-wins-over-env rule:
    ``dense`` stages pushes in the pre-stacked ``[n, padded, dim]``
    host buffers; ``sparse`` stages COO (keys, rows) streams and
    densifies ON DEVICE with a segment-sum scatter inside the wave —
    an embedding-table-sized key space with a small touched set stops
    materializing host buffers that scale with ``num_rows``.
    ``MINIPS_MESH_SPARSE`` (any value but ''/'0') selects sparse."""
    if deposit:
        if deposit not in ("dense", "sparse"):
            raise ValueError(f"mesh deposit must be 'dense' or "
                             f"'sparse', got {deposit!r}")
        return deposit
    env = os.environ.get("MINIPS_MESH_SPARSE", "").strip()
    return "sparse" if env not in ("", "0") else "dense"


def _padded(rows: int, shards: int) -> int:
    return shards * (-(-max(rows, 1) // shards))


class MeshTable:
    """One pjit-sharded KVTable + updater state on the plane's mesh,
    with per-logical-rank deposit buffers. All mutation runs under the
    plane lock; rank-facing entrypoints take the rank explicitly (the
    :class:`MeshRank` handle binds it)."""

    def __init__(self, plane: "MeshPlane", name: str, num_rows: int,
                 dim: int, *, updater: str = "sgd", lr: float = 0.05,
                 adagrad_init: float = 0.1, eps: Optional[float] = None,
                 beta1: float = 0.9, beta2: float = 0.999):
        if updater not in ("sgd", "adagrad", "adam"):
            raise ValueError(
                "mesh-plane updater must be 'sgd', 'adagrad' or 'adam'")
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        self.plane = plane
        self.name = name
        self.num_rows = int(num_rows)
        self.dim = int(dim)
        self.updater = updater
        self.lr = float(lr)
        # same defaults as the wire table (train/sharded_ps.py), which
        # themselves match the ops/sparse_update.py oracles
        self.eps = float((1e-8 if updater == "adam" else 1e-10)
                         if eps is None else eps)
        self.beta1 = float(beta1)
        self.beta2 = float(beta2)
        n = plane.num_ranks
        self.padded = _padded(self.num_rows, n)
        self.shard_rows = self.padded // n
        self._row_sh = NamedSharding(plane.mesh, P(MESH_AXIS))
        # the rank axis of the stacked deposits shards the same way: each
        # device holds exactly its own logical rank's contribution —
        # data-parallel layout in, range-sharded state out
        self._stack_sh = NamedSharding(plane.mesh, P(MESH_AXIS))
        z = jnp.zeros((self.padded, self.dim), jnp.float32)
        self._w = jax.device_put(z, self._row_sh)
        self._acc = (jax.device_put(
            jnp.full((self.padded, self.dim), float(adagrad_init),
                     jnp.float32), self._row_sh)
            if updater == "adagrad" else None)
        if updater == "adam":
            self._m = jax.device_put(z, self._row_sh)
            self._v = jax.device_put(z, self._row_sh)
            self._steps = jax.device_put(
                jnp.zeros(self.padded, jnp.int32), self._row_sh)
        else:
            self._m = self._v = self._steps = None
        self.deposit = plane.deposit
        if self.deposit == "sparse":
            # sparse device waves: deposits stage as per-rank COO
            # (keys, rows) streams and densify ON DEVICE with a
            # segment-sum scatter inside the wave — host staging
            # scales with the TOUCHED set, not ``num_rows`` (the
            # embedding-table shape PR 11 carried as headroom)
            self._gbuf = None
            self._tstack = None
            self._ckeys: Optional[list] = [[] for _ in range(n)]
            self._cvals: Optional[list] = [[] for _ in range(n)]
            self.peak_deposit_bytes = 0
        else:
            # per-rank host deposit buffers, PRE-STACKED: the wave's
            # input is this one [n, padded, dim] array (each rank
            # deposits into its row — clean ranks contribute exact
            # zeros), so a wave pays one device_put and zero stacking
            # copies
            self._gbuf = np.zeros((n, self.padded, self.dim), np.float32)
            self._tstack = (np.zeros((n, self.padded), np.float32)
                            if updater == "adam" else None)
            self._ckeys = self._cvals = None
            self.peak_deposit_bytes = self._gbuf.nbytes + (
                self._tstack.nbytes if self._tstack is not None else 0)
        self._dirty = [False] * n
        # the replicated pull mirror: the wave's fused all-gather output,
        # host-resident (and read-only: pull_all serves VIEWS — the
        # mirror is REPLACED per wave, never mutated, so an outstanding
        # view stays a valid snapshot) so reads between waves are plain
        # numpy indexing
        self._mirror = np.zeros((self.padded, self.dim), np.float32)
        self._mirror.setflags(write=False)
        self.waves = 0
        self.rows_pushed = 0
        self.rows_pulled = 0
        # collective traffic accounting (the MESH analog of wire bytes):
        # what the reduce-scatter + all-gather move per wave, summed over
        # ranks — ring cost (n-1)/n of the buffer each way, codes+scales
        # for the blk8 tier (blockwise_stream_bytes is the shared bill)
        self.collective_bytes = 0
        # blk8 error feedback (plane.mesh_ef): each device's quantization
        # residual from the reduce leg — input minus what a2a_reduce
        # actually shipped — retained host-side and folded into the next
        # wave's contribution, with an exact-f32 repayment wave at
        # finalize: the wire ResidualStore's fold/flush contract
        # (train/sharded_ps.py) on the collective transport. Born as a
        # DEVICE array (stack-sharded zeros): between waves it is the
        # wave's own device output, and a host-side [n, padded, dim]
        # zeros block would charge sparse mode a dense host buffer it
        # exists to avoid
        self._rbuf = (jax.device_put(
            jnp.zeros((n, self.padded, self.dim), jnp.float32),
            self._stack_sh) if plane.mesh_ef else None)
        self._fence_fn = None  # exact repayment program, built lazily
        self.ef_waves = 0        # waves that folded + re-captured resid
        self.ef_fence_waves = 0  # exact repayment waves (finalize)
        self.sparse_waves = 0    # waves that densified on device
        self._wave_fns: dict = {}  # sparse: one program per L bucket
        self._wave_len = 8         # grow-only L (compile-thrash guard)
        self._wave_fn = (self._build_wave_fn()
                         if self.deposit == "dense" else None)

    # ------------------------------------------------------------ wave
    def _build_wave_fn(self, *, exact: bool = False,
                       sparse_len: Optional[int] = None):
        """One jitted XLA program per table — THE collective data plane:
        reduce-scatter the stacked rank deposits (push), run the updater
        on the owner shard (sharded server math — no replicated
        optimizer state), all-gather the new rows (pull). The signature
        varies by updater so only real state is donated; the updater
        math mirrors the wire table's numpy updaters op for op
        (sharded_ps._update_block/_adam_rows).

        ``sparse_len=L`` swaps the dense ``[n, padded, dim]`` deposit
        input for COO streams (``[n, L]`` keys + ``[n, L, dim]`` rows,
        sentinel key = ``padded`` → dropped): each device densifies ITS
        rank's stream with a segment-sum scatter before the identical
        reduce leg — one cached program per power-of-two L bucket."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from minips_tpu.ops.quantized_comm import (
            quantized_psum_scatter, quantized_psum_scatter_ef)

        dim = self.dim
        lr = np.float32(self.lr)
        eps = np.float32(self.eps)
        b1 = np.float32(self.beta1)
        b2 = np.float32(self.beta2)
        one_m_b1 = np.float32(1) - b1
        one_m_b2 = np.float32(1) - b2
        comm = "float32" if exact else self.plane.comm
        block = self.plane.block
        # EF only rides the lossy leg: the exact (fence) program ships
        # f32 and must NOT re-capture a residual — it repays one
        ef = bool(self.plane.mesh_ef and comm == "blk8")
        upd = self.updater
        S = P(MESH_AXIS)

        def _reduce(g_mine):
            # g_mine [padded, dim]: my rank's full-row-space contribution;
            # the reduce-scatter leaves me the summed rows I own. Second
            # return is this device's compression residual (EF mode) —
            # what the quantizer did NOT ship, folded into the next wave
            if comm == "float32":
                return jax.lax.psum_scatter(
                    g_mine, MESH_AXIS, scatter_dimension=0,
                    tiled=True), None
            if ef:
                red, resid = quantized_psum_scatter_ef(
                    g_mine.reshape(-1), MESH_AXIS, comm="int8",
                    block=block)
                return red.reshape(-1, dim), resid.reshape(g_mine.shape)
            red = quantized_psum_scatter(
                g_mine.reshape(-1), MESH_AXIS, comm="int8", block=block)
            return red.reshape(-1, dim), None

        def _out(full, resid):
            # resid rides out stacked over the shard axis ([1,...] per
            # device -> [n,...]); non-EF programs keep the bare-full
            # output shape so their jitted artifacts are untouched
            return (full, resid[None]) if ef else full

        if upd == "sgd":
            def body(w, g_stack):
                g, resid = _reduce(g_stack[0])
                w = w - lr * g
                full = jax.lax.all_gather(w, MESH_AXIS, axis=0,
                                          tiled=True)
                return (w,), _out(full, resid)
            n_state = 1
        elif upd == "adagrad":
            def body(w, acc, g_stack):
                g, resid = _reduce(g_stack[0])
                acc = acc + g * g
                w = w - lr * g / (jnp.sqrt(acc) + eps)
                full = jax.lax.all_gather(w, MESH_AXIS, axis=0,
                                          tiled=True)
                return (w, acc), _out(full, resid)
            n_state = 2
        else:
            def body(w, m, v, steps, g_stack, t_stack):
                # lazy adam: the touch-mask reduce keeps untouched rows'
                # moments and step counters frozen, matching the wire's
                # per-key server semantics (sharded_ps._adam_rows)
                g, resid = _reduce(g_stack[0])
                t = jax.lax.psum_scatter(
                    t_stack[0], MESH_AXIS, scatter_dimension=0,
                    tiled=True)
                mask = t > 0
                mcol = mask[:, None]
                steps = steps + mask.astype(jnp.int32)
                m = jnp.where(mcol, b1 * m + one_m_b1 * g, m)
                v = jnp.where(mcol, b2 * v + one_m_b2 * (g * g), v)
                tf = steps.astype(jnp.float32)[:, None]
                bc1 = np.float32(1) - b1 ** tf
                bc2 = np.float32(1) - b2 ** tf
                w = jnp.where(
                    mcol,
                    w - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps), w)
                full = jax.lax.all_gather(w, MESH_AXIS, axis=0,
                                          tiled=True)
                if ef:
                    # rows NO rank touched this wave skip the update
                    # entirely — shipped mass for them is discarded by
                    # the where, so the residual keeps the FULL input
                    # (nothing landed), not input - sent; without this
                    # a residual-only row would leak its mass
                    mask_full = jax.lax.all_gather(
                        mask, MESH_AXIS, axis=0, tiled=True)
                    resid = jnp.where(mask_full[:, None], resid,
                                      g_stack[0])
                return (w, m, v, steps), _out(full, resid)
            n_state = 4

        if ef:
            # the retained residual stays a DEVICE array between waves
            # (r_stack, last input): folding on device instead of a
            # host-side _gbuf + _rbuf add keeps the wave's hot path
            # free of a full-buffer device->host->device round trip
            # per wave — the residual only ever crosses to the host
            # for the one-time fence and the stats probe
            inner = body
            if upd == "adam":
                def body(w, m, v, steps, g_stack, t_stack, r_stack):
                    return inner(w, m, v, steps, g_stack + r_stack,
                                 t_stack)
            elif upd == "adagrad":
                def body(w, acc, g_stack, r_stack):
                    return inner(w, acc, g_stack + r_stack)
            else:
                def body(w, g_stack, r_stack):
                    return inner(w, g_stack + r_stack)

        if sparse_len is not None:
            # COO front end: densify my rank's staged stream on device
            # (scatter-add; the sentinel key == padded is out of range
            # and mode="drop" discards it), then run the identical
            # dense body — adam's touch mask is the scatter of ones
            # over the same keys, so semantics are byte-for-byte the
            # dense path's
            padded = self.padded

            def _densify(k, v):
                return jnp.zeros((padded, dim), jnp.float32
                                 ).at[k].add(v, mode="drop")

            def _touch(k):
                return jnp.zeros((padded,), jnp.float32
                                 ).at[k].add(1.0, mode="drop")

            dense_body = body
            if upd == "adam":
                def body(w, m, v, steps, k_stack, v_stack, *rest):
                    g = _densify(k_stack[0], v_stack[0])
                    t = _touch(k_stack[0])
                    return dense_body(w, m, v, steps, g[None], t[None],
                                      *rest)
            elif upd == "adagrad":
                def body(w, acc, k_stack, v_stack, *rest):
                    g = _densify(k_stack[0], v_stack[0])
                    return dense_body(w, acc, g[None], *rest)
            else:
                def body(w, k_stack, v_stack, *rest):
                    g = _densify(k_stack[0], v_stack[0])
                    return dense_body(w, g[None], *rest)
            n_in = n_state + 2 + (1 if ef else 0)
        else:
            n_in = (n_state + (2 if upd == "adam" else 1)
                    + (1 if ef else 0))
        # check_vma/check_rep off: the all-gathered output is replicated
        # by construction, but older checkers cannot infer it through
        # the quantized a2a path
        mapped = jax.shard_map(
            body, mesh=self.plane.mesh, in_specs=(S,) * n_in,
            out_specs=((S,) * n_state, ((P(), S) if ef else P())),
            check_vma=False)
        return jax.jit(mapped, donate_argnums=tuple(range(n_state)))

    def _deposit(self, rank: int, keys: np.ndarray,
                 grads: np.ndarray) -> None:
        """Coalesce duplicates via THE shared client-side dedup kernel
        (sharded_ps.sum_duplicate_keys — the bitwise-parity drill
        depends on both planes summing identically), then accumulate
        into the rank's buffer."""
        from minips_tpu.train.sharded_ps import sum_duplicate_keys

        keys = np.asarray(keys, np.int64)
        grads = np.asarray(grads, np.float32).reshape(keys.size, self.dim)
        if keys.size and (keys.min() < 0 or keys.max() >= self.num_rows):
            raise ValueError("push keys outside the table's key space")
        uniq, summed, _ = sum_duplicate_keys(keys, grads, self.dim)
        if self._ckeys is not None:
            # sparse: stage the deduped COO slice; cross-deposit
            # duplicates coalesce on device (two-term f32 adds are
            # commutative, so the wave equals the dense accumulate)
            self._ckeys[rank].append(np.asarray(uniq, np.int64))
            self._cvals[rank].append(
                np.ascontiguousarray(summed, np.float32))
        else:
            np.add.at(self._gbuf[rank], uniq, summed)
            if self._tstack is not None:
                self._tstack[rank][uniq] = 1.0
        self._dirty[rank] = True
        self.rows_pushed += keys.size

    def _deposit_dense(self, rank: int, grad: np.ndarray) -> None:
        grad = np.asarray(grad, np.float32).reshape(-1, self.dim)
        if grad.shape[0] != self.num_rows:
            raise ValueError(
                f"push_dense expects [{self.num_rows}, {self.dim}]")
        if self._ckeys is not None:
            # a dense push touches every row — COO staging degrades to
            # the full key list (dense workloads should run deposit=
            # dense; the sparse plane stays correct, not clever)
            self._ckeys[rank].append(
                np.arange(self.num_rows, dtype=np.int64))
            self._cvals[rank].append(
                np.ascontiguousarray(grad, np.float32))
        else:
            self._gbuf[rank, : self.num_rows] += grad
            if self._tstack is not None:
                self._tstack[rank, : self.num_rows] = 1.0
        self._dirty[rank] = True
        self.rows_pushed += self.num_rows

    def _wave_locked(self, *, fence: bool = False) -> None:
        """One apply wave: ship the pre-stacked deposits (clean ranks
        contribute exact zeros), reduce-scatter + sharded update +
        all-gather in one jitted program, refresh the pull mirror, zero
        the dirty rows. EF mode folds the retained residual into the
        input and re-captures the wave's new residual; ``fence=True``
        swaps in the exact-f32 program (built lazily — the repayment
        wave at finalize, after which the residual is zero by
        construction). Caller holds the plane lock."""
        import jax

        if self._ckeys is not None and not fence:
            self._wave_sparse_locked()
            return
        t_wave0 = time.monotonic()
        n = self.plane.num_ranks
        ef = self._rbuf is not None
        g_in = self._gbuf
        if ef and fence:
            # the exact program has no r_stack input — fold the
            # residual on the host for this one-time repayment wave.
            # Sparse mode densifies any still-staged COO here too (the
            # fence is the one wave that MUST see a dense input — the
            # honest limit the architecture doc states): at finalize
            # the per-rank flushes already drained the stages, so this
            # is normally residual-only
            if self._ckeys is not None:
                g_in = np.zeros((n, self.padded, self.dim), np.float32)
                for r in range(n):
                    for k, v in zip(self._ckeys[r], self._cvals[r]):
                        np.add.at(g_in[r], k, v)
                g_in += np.asarray(self._rbuf)
            else:
                g_in = self._gbuf + np.asarray(self._rbuf)
        t_in = self._tstack
        fn = self._wave_fn
        extra = ()
        if ef and not fence:
            # residual rides as a device-resident input (a no-op put
            # when it is last wave's output, already stack-sharded)
            extra = (jax.device_put(self._rbuf, self._stack_sh),)
        if fence:
            if self._fence_fn is None:
                self._fence_fn = self._build_wave_fn(exact=True)
            fn = self._fence_fn
            if ef and self.updater == "adam":
                # the fence repays residual as a real (exact) push:
                # residual-only rows must pass the lazy-adam touch mask,
                # exactly like the wire's f32 residual fence arrives as
                # a normal push frame and advances server state
                mass = (np.abs(g_in).sum(axis=-1) > 0
                        ).astype(np.float32)
                t_in = (mass if t_in is None
                        else np.maximum(t_in, mass))
        g_stack = jax.device_put(g_in, self._stack_sh)
        if self.updater == "sgd":
            (self._w,), out = fn(self._w, g_stack, *extra)
        elif self.updater == "adagrad":
            (self._w, self._acc), out = fn(self._w, self._acc,
                                           g_stack, *extra)
        else:
            t_stack = jax.device_put(t_in, self._stack_sh)
            (self._w, self._m, self._v, self._steps), out = \
                fn(self._w, self._m, self._v, self._steps,
                   g_stack, t_stack, *extra)
        if ef and not fence:
            full, resid = out
            self._rbuf = resid  # stays on device until fence/stats
            self.ef_waves += 1
        else:
            full = out
            if ef:
                # repaid: reset to device-born zeros (explicit shape —
                # sparse mode has no _gbuf to zeros_like)
                import jax.numpy as jnp
                self._rbuf = jax.device_put(
                    jnp.zeros((n, self.padded, self.dim), jnp.float32),
                    self._stack_sh)
                self.ef_fence_waves += 1
        mirror = np.asarray(full)
        mirror.setflags(write=False)
        self._mirror = mirror
        for r in range(self.plane.num_ranks):
            if self._dirty[r]:
                if self._gbuf is not None:
                    self._gbuf[r].fill(0.0)
                    if self._tstack is not None:
                        self._tstack[r].fill(0.0)
                else:
                    self._ckeys[r].clear()
                    self._cvals[r].clear()
                self._dirty[r] = False
        self.waves += 1
        self.collective_bytes += self._wave_bytes()
        # the step-phase observable: one wave = one collective program
        # dispatch; its duration hist feeds the plane's windowed layer
        self.plane.hist_wave.record_s(time.monotonic() - t_wave0)

    def _wave_sparse_locked(self) -> None:
        """Sparse apply wave: pack each rank's staged COO stream into
        ``[n, L]`` keys + ``[n, L, dim]`` rows (pad slots carry the
        sentinel key ``padded`` — out of range, dropped by the device
        scatter's ``mode="drop"``), densify ON DEVICE with a
        segment-sum scatter, then run the identical reduce/update/
        gather body. ``L`` rounds up to a power of two so recompiles
        stay O(log max-touched); peak host bytes are the staged slices
        plus these stacks — they scale with the TOUCHED set, never
        ``num_rows``. Caller holds the plane lock."""
        import jax

        t_wave0 = time.monotonic()
        n = self.plane.num_ranks
        ef = self._rbuf is not None
        counts = [sum(k.size for k in self._ckeys[r]) for r in range(n)]
        need = max(max(counts), 1)
        # MONOTONIC stack length: grow-only, so a touched-set count
        # that oscillates across waves reuses ONE compiled program
        # instead of ping-ponging between L buckets (each bucket is a
        # fresh XLA compile — worth 10-100ms, easily dwarfing the wave)
        L = self._wave_len
        while L < need:
            L *= 2
        self._wave_len = L
        k_stack = np.full((n, L), self.padded, np.int32)
        v_stack = np.zeros((n, L, self.dim), np.float32)
        for r in range(n):
            o = 0
            for k, v in zip(self._ckeys[r], self._cvals[r]):
                k_stack[r, o:o + k.size] = k
                v_stack[r, o:o + k.size] = v
                o += k.size
        staged = sum(k.nbytes + v.nbytes
                     for r in range(n)
                     for k, v in zip(self._ckeys[r], self._cvals[r]))
        self.peak_deposit_bytes = max(
            self.peak_deposit_bytes,
            staged + k_stack.nbytes + v_stack.nbytes)
        fn = self._wave_fns.get(L)
        if fn is None:
            fn = self._wave_fns[L] = self._build_wave_fn(sparse_len=L)
        ks = jax.device_put(k_stack, self._stack_sh)
        vs = jax.device_put(v_stack, self._stack_sh)
        extra = ()
        if ef:
            extra = (jax.device_put(self._rbuf, self._stack_sh),)
        if self.updater == "sgd":
            (self._w,), out = fn(self._w, ks, vs, *extra)
        elif self.updater == "adagrad":
            (self._w, self._acc), out = fn(self._w, self._acc,
                                           ks, vs, *extra)
        else:
            (self._w, self._m, self._v, self._steps), out = \
                fn(self._w, self._m, self._v, self._steps,
                   ks, vs, *extra)
        if ef:
            full, resid = out
            self._rbuf = resid
            self.ef_waves += 1
        else:
            full = out
        mirror = np.asarray(full)
        mirror.setflags(write=False)
        self._mirror = mirror
        for r in range(n):
            if self._dirty[r]:
                self._ckeys[r].clear()
                self._cvals[r].clear()
                self._dirty[r] = False
        self.waves += 1
        self.sparse_waves += 1
        self.collective_bytes += self._wave_bytes()
        self.plane.hist_wave.record_s(time.monotonic() - t_wave0)

    def _wave_bytes(self) -> int:
        """Collective bytes one wave moves, summed over ranks: ring
        reduce-scatter + ring all-gather each move (n-1)/n of the buffer
        per rank; the blk8 reduce leg ships codes + blockwise scales
        (the shared ``blockwise_stream_bytes`` bill) instead of f32."""
        from minips_tpu.ops.quantized_comm import blockwise_stream_bytes

        n = self.plane.num_ranks
        full = self.padded * self.dim * 4
        gather = (n - 1) * full  # (n-1)/n per rank, n ranks
        if self.plane.comm == "blk8":
            code, scale = blockwise_stream_bytes(
                self.padded, self.dim, 8, self.plane.block)
            reduce = (n - 1) * (code + scale)
        else:
            reduce = (n - 1) * full
        return reduce + gather

    # ------------------------------------------------------- rank-facing
    def push(self, rank: int, keys: np.ndarray,
             grads: np.ndarray) -> None:
        plane = self.plane
        with plane._cond:
            self._deposit(rank, keys, grads)
            plane._maybe_wave_locked(self)

    def push_dense(self, rank: int, grad: np.ndarray) -> None:
        plane = self.plane
        with plane._cond:
            self._deposit_dense(rank, grad)
            plane._maybe_wave_locked(self)

    def pull(self, rank: int, keys: np.ndarray) -> np.ndarray:
        keys = np.asarray(keys, np.int64)
        if keys.size and (keys.min() < 0
                          or keys.max() >= self.num_rows):
            # same contract as the wire plane (a misrouted pull is
            # refused, never served): without this a padding row or a
            # numpy-wrapped negative index would silently read zeros
            raise ValueError("pull keys outside the table's key space")
        plane = self.plane
        with plane._cond:
            plane._admit_locked(rank)
            if self._dirty[rank]:  # read-your-own-writes: flush first
                self._wave_locked()
            self.rows_pulled += keys.size
            return self._mirror[keys].copy()

    def pull_all(self, rank: int) -> np.ndarray:
        """Full-table read: a READ-ONLY view of the current pull mirror
        (waves REPLACE the mirror, never mutate it, so the view is a
        stable snapshot — and the full-table hot path pays zero copy,
        exactly the all-gather-once-per-wave story)."""
        plane = self.plane
        with plane._cond:
            plane._admit_locked(rank)
            if self._dirty[rank]:
                self._wave_locked()
            self.rows_pulled += self.num_rows
            return self._mirror[: self.num_rows]

    def load_dense(self, w: np.ndarray) -> None:
        """Install a full [num_rows, dim] weight table (drill/checkpoint
        seeding) — re-sharded onto the mesh, mirror refreshed."""
        import jax
        import jax.numpy as jnp

        w = np.asarray(w, np.float32).reshape(self.num_rows, self.dim)
        padded = np.zeros((self.padded, self.dim), np.float32)
        padded[: self.num_rows] = w
        with self.plane._cond:
            self._w = jax.device_put(jnp.asarray(padded), self._row_sh)
            padded.setflags(write=False)
            self._mirror = padded

    def shard_slice(self, rank: int) -> np.ndarray:
        """Rank ``rank``'s owner rows of the CURRENT table (mirror read)
        — the per-rank final-state view the lockstep drill compares
        against the wire tables' local shards."""
        with self.plane._cond:
            lo = rank * self.shard_rows
            hi = min(lo + self.shard_rows, self.num_rows)
            return self._mirror[lo:hi].copy()

    def ef_stats(self) -> Optional[dict]:
        """blk8 error-feedback accounting — None when EF is off (the
        off-vs-idle convention every wire stats block keeps); resident
        rows are the residual mass currently awaiting its next fold."""
        if self._rbuf is None:
            return None
        return {
            "folded_waves": int(self.ef_waves),
            "fence_waves": int(self.ef_fence_waves),
            "resident_rows": int(
                (np.abs(self._rbuf).sum(axis=-1) > 0).sum()),
        }

    def local_bytes(self) -> int:
        """Device bytes of table + updater state PER SHARD — the same
        ~1/N claim as the wire table's local_bytes."""
        n = self.shard_rows * self.dim * 4
        if self._acc is not None:
            n += self.shard_rows * self.dim * 4
        if self._m is not None:
            n += 2 * self.shard_rows * self.dim * 4 + self.shard_rows * 4
        return n


class MeshRank:
    """A logical rank's handle on the plane: the per-rank API surface
    the wire path spreads across (ShardedTable, ShardedPSTrainer)."""

    def __init__(self, plane: "MeshPlane", rank: int):
        self.plane = plane
        self.rank = rank
        self.tables = _RankTables(plane, rank)

    @property
    def clock(self) -> int:
        return int(self.plane._clk_host[self.rank])

    @property
    def staleness(self) -> float:
        return self.plane.staleness

    def tick(self, *, wait: bool = True) -> None:
        self.plane.tick(self.rank, wait=wait)

    def finalize(self, timeout: float = 30.0) -> None:
        self.plane.finalize(self.rank, timeout=timeout)


class _RankTables:
    def __init__(self, plane, rank):
        self._plane, self._rank = plane, rank

    def __getitem__(self, name: str) -> "_BoundTable":
        return _BoundTable(self._plane.tables[name], self._rank)

    def __iter__(self):
        return iter(self._plane.tables)


class _BoundTable:
    """MeshTable with the rank argument bound — pull/push read like the
    wire ShardedTable's client surface."""

    def __init__(self, table: MeshTable, rank: int):
        self._t, self._r = table, rank

    def __getattr__(self, item):
        return getattr(self._t, item)

    def pull(self, keys):
        return self._t.pull(self._r, keys)

    def pull_all(self):
        return self._t.pull_all(self._r)

    def push(self, keys, grads):
        self._t.push(self._r, keys, grads)

    def push_dense(self, grad):
        self._t.push_dense(self._r, grad)


class MeshPlane:
    """The gang: one process, ``num_ranks`` logical ranks mapped onto
    ``num_ranks`` mesh devices, tables sharded across all of them.

    Construction order: ``MeshPlane(...)`` → ``add_table(...)`` per
    table → ``rank(r)`` handles for the worker threads. BSP/SSP comes
    from ``staleness`` exactly like the wire trainer's; the gate is the
    shared ``admits`` predicate over the plane's device-side clock
    vector."""

    def __init__(self, num_ranks: int, *, staleness: float = 0.0,
                 comm: str = "float32", block: Optional[int] = None,
                 deposit: Optional[str] = None, devices=None,
                 gate_timeout: float = 60.0):
        if comm not in VALID_MESH_COMM:
            raise ValueError(f"mesh comm must be one of "
                             f"{VALID_MESH_COMM}, got {comm!r}")
        if staleness < 0:
            raise ValueError("staleness must be >= 0")
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        from minips_tpu.ops.quantized_comm import HOST_BLOCK

        devs = list(devices) if devices is not None else list(jax.devices())
        if len(devs) < num_ranks:
            raise ValueError(
                f"mesh plane needs {num_ranks} devices, have "
                f"{len(devs)} — set "
                f"--xla_force_host_platform_device_count on CPU")
        self.num_ranks = int(num_ranks)
        self.staleness = float(staleness)
        self.comm = comm
        # the quantized tier defaults to the HOST wire's block size:
        # one codec (blockwise absmax), two transports
        self.block = int(HOST_BLOCK if block is None else block)
        # deposit buffer shape: dense pre-stacked host buffers vs COO
        # staging + on-device segment-sum densify (sparse device waves)
        self.deposit = resolve_deposit(deposit)
        # error feedback on the blk8 reduce leg (default ON): each
        # device retains its quantization residual and folds it into
        # the next wave — unbiased in the limit, exact repayment at
        # finalize. MINIPS_MESH_EF=0 is the kill switch (A/B arm);
        # float32 ships exactly, nothing to feed back
        self.mesh_ef = (comm == "blk8"
                        and os.environ.get("MINIPS_MESH_EF",
                                           "1").strip() != "0")
        self.gate_timeout = float(gate_timeout)
        self.mesh = Mesh(np.array(devs[: self.num_ranks]), (MESH_AXIS,))
        self._rep_sh = NamedSharding(self.mesh, P())
        self.tables: dict[str, MeshTable] = {}
        self._cond = threading.Condition(threading.RLock())
        # the device-side clock vector: pull admission and the SSP gate
        # evaluate min() of THIS array (gate.admits, the one predicate)
        # int32 on device (x64 is off repo-wide); RETIRED_CLOCK = 2^30
        # fits with headroom
        self._clk_dev = jax.device_put(
            jnp.zeros(self.num_ranks, jnp.int32), self._rep_sh)
        self._clk_host = np.zeros(self.num_ranks, np.int64)
        self._retired = np.zeros(self.num_ranks, bool)
        self.gate_waits = 0
        self.max_skew_seen = 0
        # ---- observability: always-on step-PHASE histograms (apply-
        # wave duration, tick-gate blocked time) + the windowed layer
        # over them — the mesh plane's analog of the wire trainer's
        # hist/window blocks; MINIPS_OBS=0 disables the window only
        # (the tax arm), the hists are as free as the wire's
        self.hist_wave = Log2Histogram()
        self.hist_gate = Log2Histogram()
        self.obs_window = _ow.maybe_build()
        if self.obs_window is not None:
            self.obs_window.register_hist(
                "wave", lambda: self.hist_wave.snapshot())
            self.obs_window.register_hist(
                "gate", lambda: self.hist_gate.snapshot())
            self.obs_window.register_counter(
                "waves", lambda: sum(t.waves
                                     for t in self.tables.values()))
            self.obs_window.register_counter(
                "collective_bytes",
                lambda: sum(t.collective_bytes
                            for t in self.tables.values()))

    # ------------------------------------------------------------- setup
    def add_table(self, name: str, num_rows: int, dim: int,
                  **kwargs) -> MeshTable:
        if name in self.tables:
            raise ValueError(f"table {name!r} already exists")
        t = MeshTable(self, name, num_rows, dim, **kwargs)
        self.tables[name] = t
        return t

    def rank(self, r: int) -> MeshRank:
        if not 0 <= r < self.num_ranks:
            raise ValueError(f"rank {r} out of range")
        return MeshRank(self, r)

    # -------------------------------------------------------- gang logic
    def _global_min(self) -> int:
        """min of the clock vector — the freshness certificate the
        admission predicate runs on (the mesh analog of
        ClockGossip.global_min). Reads the host mirror: it is updated
        in lockstep with the device vector under the plane lock
        (bitwise the same values), and the gate wait loops poll this
        every iteration — a jitted device reduction per poll would put
        dispatch churn on the admission hot path for no information.
        Once the poll passes, admission CERTIFIES against the device
        vector (:meth:`_device_min` — one dispatch per admission, not
        per poll), so the predicate's final word is device state."""
        return int(self._clk_host.min())

    def _device_min(self) -> int:
        """min of the DEVICE-side clock vector — the authoritative
        replicated copy every clock write updates under the plane
        lock; the admission certificate reads THIS."""
        return int(self._clk_dev.min())

    def clocks(self) -> np.ndarray:
        """Host copy of the device-side clock vector (tests/obs)."""
        return np.asarray(self._clk_dev)

    def _maybe_wave_locked(self, table: MeshTable) -> None:
        """Fire the apply wave eagerly once every live rank deposited —
        the full wave is the natural BSP barrier and keeps the state
        fresh without waiting for the tick boundary."""
        live = [r for r in range(self.num_ranks) if not self._retired[r]]
        if live and all(table._dirty[r] for r in live):
            table._wave_locked()
            self._cond.notify_all()

    def _admit_locked(self, rank: int) -> bool:
        """Pull admission: wait until ``admits(min(clock_vec), clk, s)``
        — the owner-side park rule. The host mirror screens each poll;
        the admission that actually serves is certified against the
        DEVICE clock vector."""
        clk = int(self._clk_host[rank])
        if not admits(self._global_min(), clk, self.staleness):
            self.gate_waits += 1
            deadline = time.monotonic() + self.gate_timeout
            while not admits(self._global_min(), clk, self.staleness):
                left = deadline - time.monotonic()
                if left <= 0:
                    raise TimeoutError(
                        f"mesh plane gate timed out at clock {clk} "
                        f"(global_min={self._global_min()}, "
                        f"staleness={self.staleness})")
                self._cond.wait(timeout=min(0.2, left))
        if not admits(self._device_min(), clk, self.staleness):
            # cannot happen while mirror and device update under one
            # lock — but the predicate's final word is device state,
            # so a torn update surfaces as a loud refusal, not a
            # silently-early read
            raise RuntimeError(
                "mesh clock mirror ahead of the device vector "
                f"({self._clk_host.tolist()} vs {self.clocks().tolist()})")
        return True

    def _flush_rank_locked(self, rank: int) -> None:
        """Flush rank ``rank``'s deposits ahead of a clock advance.
        Under BSP every live rank deposits every step, so a solo flush
        here would triple the wave count (one per rank's tick instead
        of one full wave per step — measured 2-3x off the fused bench):
        give the eager full wave a short grace to fire first (peers'
        pushes run while we cond-wait), then flush whatever is left —
        correctness (pushes before clock) never depends on the grace."""
        if not any(t._dirty[rank] for t in self.tables.values()):
            return
        if self.staleness == 0:
            deadline = time.monotonic() + _BSP_FLUSH_GRACE
            while any(t._dirty[rank] for t in self.tables.values()):
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                self._cond.wait(timeout=min(0.01, left))
        for t in self.tables.values():
            if t._dirty[rank]:
                t._wave_locked()
                self._cond.notify_all()

    def tick(self, rank: int, *, wait: bool = True) -> None:
        """Clock boundary: flush the rank's deposits (an apply wave —
        its step-k pushes enter the shared state BEFORE the clock reads
        k), advance the device-side clock vector, then gate
        (BSP/SSP/ASP rule) unless ``wait=False`` (single-threaded
        drivers gate at pull admission instead)."""
        poison_args = None
        try:
            with self._cond:
                self._flush_rank_locked(rank)
                new = int(self._clk_host[rank]) + 1
                self._clk_host[rank] = new
                self._clk_dev = self._clk_dev.at[rank].set(new)
                self._cond.notify_all()
                if rank == 0 and self.obs_window is not None:
                    # one roll per full clock (rank 0's boundary): the
                    # plane's windowed intervals track steps like the
                    # wire trainer's tick-time roll
                    self.obs_window.roll()
                # skew is recorded in EVERY mode (ASP and wait=False
                # included) — the observable must not go vacuous just
                # because the gate does not block
                self.max_skew_seen = max(self.max_skew_seen,
                                         new - self._global_min())
                if not wait or self.staleness == float("inf"):
                    return
                threshold = new - int(self.staleness)
                t_gate0 = time.monotonic()
                if self._global_min() < threshold:
                    self.gate_waits += 1
                deadline = time.monotonic() + self.gate_timeout
                try:
                    while self._global_min() < threshold:
                        left = deadline - time.monotonic()
                        if left <= 0:
                            poison_args = {
                                "rank": rank, "clock": new,
                                "global_min": self._global_min(),
                                "staleness": self.staleness}
                            raise TimeoutError(
                                f"mesh plane gate timed out at clock "
                                f"{new} "
                                f"(global_min={self._global_min()}, "
                                f"staleness={self.staleness})")
                        self._cond.wait(timeout=min(0.2, left))
                finally:
                    self.hist_gate.record_s(time.monotonic() - t_gate0)
                if self._device_min() < threshold:  # certify: device
                    raise RuntimeError(
                        "mesh clock mirror ahead of the device vector "
                        f"({self._clk_host.tolist()} vs "
                        f"{self.clocks().tolist()})")
        except TimeoutError:
            # the dump is file I/O: it must not run under the plane
            # lock (every other rank's tick would block behind it —
            # the same outside-the-lock rule comm/reliable.py keeps)
            if poison_args is not None:
                _fl.poison("mesh_gate_deadline", poison_args)
            raise

    def finalize(self, rank: int, timeout: float = 30.0) -> None:
        """Flush, retire (the shared RETIRED_CLOCK sentinel so nobody
        gates on a finished rank), and barrier until every rank
        finalized — after which pull/pull_all return identical rows for
        every rank (there is only ONE state; the barrier guarantees it
        contains everyone's mass)."""
        poison_args = None
        try:
            with self._cond:
                for t in self.tables.values():
                    if t._dirty[rank]:
                        t._wave_locked()
                self._retired[rank] = True
                self._clk_host[rank] = RETIRED_CLOCK
                self._clk_dev = self._clk_dev.at[rank].set(
                    RETIRED_CLOCK)
                if self._retired.all():
                    # LAST rank out repays the blk8 EF residual with one
                    # exact-f32 fence wave per table that still holds
                    # mass — nobody deposits after this point, and the
                    # finalize barrier below means every rank returns
                    # AFTER the repayment refreshed the mirror: no
                    # gradient mass is stranded in the residual at exit
                    # (the wire ResidualStore's fence contract)
                    for t in self.tables.values():
                        if (t._rbuf is not None
                                and np.any(t._rbuf)):
                            t._wave_locked(fence=True)
                self._cond.notify_all()
                deadline = time.monotonic() + timeout
                while not self._retired.all():
                    left = deadline - time.monotonic()
                    if left <= 0:
                        missing = [r for r in range(self.num_ranks)
                                   if not self._retired[r]]
                        poison_args = {"rank": rank,
                                       "missing": missing}
                        raise TimeoutError(
                            f"mesh finalize: ranks {missing} never "
                            "retired")
                    self._cond.wait(timeout=min(0.2, left))
        except TimeoutError:
            if poison_args is not None:  # dump OUTSIDE the plane lock
                _fl.poison("mesh_finalize_deadline", poison_args)
            raise

    def stats(self) -> dict:
        return {
            "plane": "mesh",
            "comm": self.comm,
            "block": self.block if self.comm == "blk8" else None,
            "deposit": self.deposit,
            "ranks": self.num_ranks,
            "devices": len(self.mesh.devices.ravel()),
            "waves": {n: t.waves for n, t in self.tables.items()},
            # peak host bytes the deposit stage held (dense: the fixed
            # pre-stacked buffers; sparse: the high-water COO staging)
            "peak_deposit_bytes": {n: t.peak_deposit_bytes
                                   for n, t in self.tables.items()},
            "sparse_waves": sum(t.sparse_waves
                                for t in self.tables.values()),
            "collective_bytes": sum(t.collective_bytes
                                    for t in self.tables.values()),
            # blk8 reduce-leg error feedback: None when off
            # (float32 plane or MINIPS_MESH_EF=0), per-table
            # fold/fence/resident accounting when armed
            "ef": ({n: t.ef_stats()
                    for n, t in self.tables.items()}
                   if self.mesh_ef else None),
            "gate_waits": self.gate_waits,
            # step-phase hists + windowed layer, the wire trainer's
            # hist/window done-line convention ({"count": 0} idle,
            # None = window layer off)
            "hist": {"wave_ms": summarize_counts(
                         self.hist_wave.snapshot()),
                     "gate_ms": summarize_counts(
                         self.hist_gate.snapshot())},
            "window": (self.obs_window.record()
                       if self.obs_window is not None else None),
        }


class MeshAggregator:
    """The hier leader's in-host reduce backend (``MINIPS_HIER``
    ``agg=mesh``): member contributions deposit as per-slot COO
    streams, and ONE device program — segment-sum densify per slot,
    then a reduce-scatter over the mesh axis (blk8 quantized tier with
    error-feedback residual out, or exact f32) — produces the
    aggregate the leader ships cross-host. This swaps PR 16's
    host-side per-owner f64 dedup loop for XLA collectives while the
    CROSS-host leg (one topk8/topk4 ``psH`` frame per owner) is
    untouched: the reduce-scatter never leaves the host's mesh, so
    cross-host bytes are identical by construction.

    Degenerate meshes (fewer than 2 usable devices, or
    ``MINIPS_HIER_MESH_DEVS=1``) reduce on the host via THE shared
    dedup kernel in the exact deposit order the f64 path uses —
    bitwise-equal to ``agg=host`` (the stamp-folding test pins it).
    The ``reduce()`` residual return feeds the leader's ResidualStore
    so the unbiased-flush contract holds end-to-end."""

    def __init__(self, num_rows: int, dim: int, *, slots: int,
                 comm: str = "blk8", block: Optional[int] = None,
                 devices=None):
        if comm not in VALID_MESH_COMM:
            raise ValueError(f"aggregator comm must be one of "
                             f"{VALID_MESH_COMM}, got {comm!r}")
        import jax

        from minips_tpu.ops.quantized_comm import HOST_BLOCK

        self.num_rows = int(num_rows)
        self.dim = int(dim)
        self.block = int(HOST_BLOCK if block is None else block)
        devs = (list(devices) if devices is not None
                else list(jax.devices()))
        m = min(int(slots), len(devs))
        cap = os.environ.get("MINIPS_HIER_MESH_DEVS", "").strip()
        if cap:
            m = min(m, max(int(cap), 1))
        self.m = max(m, 1)
        # one usable device -> nothing to reduce-scatter ACROSS: the
        # degenerate tier is the host dedup kernel, and it reports
        # comm=float32 because that is what it ships (exactly)
        self.comm = comm if self.m >= 2 else "float32"
        self.reduces = 0
        self.rows_reduced = 0
        self.collective_bytes = 0
        self.peak_stage_bytes = 0
        self._staged: list = [[] for _ in range(self.m)]
        self._order: list = []  # (slot-stream flattening) deposit order
        self._L = 8             # grow-only stack length (see reduce())
        if self.m >= 2:
            from jax.sharding import Mesh
            self.mesh = Mesh(np.array(devs[: self.m]), (MESH_AXIS,))
            self.padded = _padded(self.num_rows, self.m)
            self._fns: dict = {}
        else:
            self.mesh = None
            self.padded = self.num_rows
            self._fns = None

    def _build_reduce_fn(self, L: int):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from minips_tpu.ops.quantized_comm import \
            quantized_psum_scatter_ef

        padded, dim = self.padded, self.dim
        comm = "int8" if self.comm == "blk8" else "float32"
        block = self.block
        S = P(MESH_AXIS)

        def body(k_stack, v_stack):
            # densify my slot's COO stream (sentinel key == padded is
            # dropped), then reduce-scatter across slots — the same
            # one-signature EF collective the mesh plane's wave runs:
            # float32 returns exact zeros for the residual, so the
            # caller never branches on the codec
            dense = jnp.zeros((padded, dim), jnp.float32
                              ).at[k_stack[0]].add(v_stack[0],
                                                   mode="drop")
            red, resid = quantized_psum_scatter_ef(
                dense.reshape(-1), MESH_AXIS, comm=comm, block=block)
            return red.reshape(-1, dim), resid.reshape(padded, dim)[None]

        mapped = jax.shard_map(
            body, mesh=self.mesh, in_specs=(S, S), out_specs=(S, S),
            check_vma=False)
        return jax.jit(mapped)

    def deposit(self, slot: int, keys: np.ndarray,
                grads: np.ndarray) -> None:
        """Stage one member contribution. ``slot`` is the member's
        index within the host group (wrapped onto the mesh)."""
        keys = np.asarray(keys, np.int64)
        grads = np.asarray(grads, np.float32).reshape(keys.size,
                                                      self.dim)
        if keys.size == 0:
            return
        if keys.min() < 0 or keys.max() >= self.num_rows:
            raise ValueError("aggregator keys outside the key space")
        self._staged[slot % self.m].append((keys, grads))
        self._order.append((keys, grads))

    def reduce(self):
        """Run the reduce over everything staged since the last call.

        Returns ``(keys, rows, resid_keys, resid_rows)``: the touched
        keys with their aggregated rows, plus the quantizer's residual
        (what the blk8 exchange did NOT ship) for the leader's
        ResidualStore. Exact tiers return empty residuals."""
        if not self._order:
            return (np.zeros(0, np.int64),
                    np.zeros((0, self.dim), np.float32),
                    np.zeros(0, np.int64),
                    np.zeros((0, self.dim), np.float32))
        from minips_tpu.train.sharded_ps import sum_duplicate_keys

        empty_r = (np.zeros(0, np.int64),
                   np.zeros((0, self.dim), np.float32))
        if self.m < 2:
            # host tier: concat in deposit order, THE shared f64 dedup
            # kernel — bitwise what agg=host would have shipped
            ks = np.concatenate([k for k, _ in self._order])
            gs = np.concatenate([g for _, g in self._order])
            self._staged = [[] for _ in range(self.m)]
            self._order = []
            k, g, _ = sum_duplicate_keys(ks, gs, self.dim)
            if k.size and not np.all(k[1:] >= k[:-1]):
                # the kernel keeps the ORIGINAL pairing when nothing
                # coalesced — reduce() contracts SORTED keys (callers
                # searchsorted into them), so restore the order the
                # dedup branch would have produced
                order = np.argsort(k, kind="stable")
                k, g = k[order], g[order]
            self.reduces += 1
            self.rows_reduced += int(k.size)
            return (k, g) + empty_r
        import jax

        counts = [sum(k.size for k, _ in s) for s in self._staged]
        need = max(max(counts), 1)
        # grow-only L: per-flush contribution counts jitter, and every
        # fresh L bucket is a fresh XLA compile — monotonic growth
        # keeps steady state on ONE compiled program
        L = self._L
        while L < need:
            L *= 2
        self._L = L
        k_stack = np.full((self.m, L), self.padded, np.int32)
        v_stack = np.zeros((self.m, L, self.dim), np.float32)
        for s in range(self.m):
            o = 0
            for k, v in self._staged[s]:
                k_stack[s, o:o + k.size] = k
                v_stack[s, o:o + k.size] = v
                o += k.size
        staged_bytes = sum(k.nbytes + g.nbytes for k, g in self._order)
        self.peak_stage_bytes = max(
            self.peak_stage_bytes,
            staged_bytes + k_stack.nbytes + v_stack.nbytes)
        touched = np.unique(np.concatenate(
            [k for k, _ in self._order]))
        self._staged = [[] for _ in range(self.m)]
        self._order = []
        fn = self._fns.get(L)
        if fn is None:
            fn = self._fns[L] = self._build_reduce_fn(L)
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        sh = NamedSharding(self.mesh, P(MESH_AXIS))
        agg, resid = fn(jax.device_put(k_stack, sh),
                        jax.device_put(v_stack, sh))
        agg = np.asarray(agg)          # [padded, dim], owner-reassembled
        rows = agg[touched]
        if self.comm == "blk8":
            resid_total = np.asarray(resid).sum(axis=0)
            rk = np.flatnonzero(
                np.abs(resid_total).sum(axis=1) > 0)
            rk = rk[rk < self.num_rows]
            rrows = resid_total[rk]
            from minips_tpu.ops.quantized_comm import \
                blockwise_stream_bytes
            code, scale = blockwise_stream_bytes(
                self.padded, self.dim, 8, self.block)
            self.collective_bytes += (self.m - 1) * (code + scale)
        else:
            rk, rrows = empty_r
            self.collective_bytes += (
                (self.m - 1) * self.padded * self.dim * 4)
        self.reduces += 1
        self.rows_reduced += int(touched.size)
        return touched, rows, np.asarray(rk, np.int64), rrows

    def stats(self) -> dict:
        return {
            "backend": "mesh" if self.m >= 2 else "host-degenerate",
            "slots": self.m,
            "comm": self.comm,
            "reduces": int(self.reduces),
            "rows_reduced": int(self.rows_reduced),
            "collective_bytes": int(self.collective_bytes),
            "peak_stage_bytes": int(self.peak_stage_bytes),
        }
