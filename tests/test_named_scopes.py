"""The named phases inside the jitted steps: ``jax.named_scope`` lands in
each HLO instruction's ``op_name``, which is what a trace's reduction
(utils/trace_analysis.py) reads. Proved here from the compiled module's
text on virtual CPU devices, and from the jaxpr for the Pallas kernels'
names."""

from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from minips_tpu.utils import profiling as prof
from minips_tpu.utils.trace_analysis import phase_of
from tests.conftest import jaxpr_eqns as _eqns
from tests.conftest import pallas_call_names as _pallas_names


def _instructions(text: str) -> list[tuple[str, str]]:
    """(opcode, op_name) of every instruction of a compiled module that
    carries an op_name."""
    out = []
    for line in text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        op = re.search(r" ([a-z][a-z0-9\-]*)\(", line.split("metadata=")[0])
        if m and op:
            out.append((op.group(1), m.group(1)))
    return out


def _phases_of(instrs, *opcodes) -> set:
    return {phase_of(scope)[0] for op, scope in instrs
            if any(op.startswith(o) for o in opcodes)}


@pytest.fixture(scope="module")
def dense_lm_text(mesh4):
    from minips_tpu.models import transformer as tfm
    from minips_tpu.tables.dense import DenseTable

    params = tfm.init(jax.random.PRNGKey(0), vocab=128, dim=32, heads=2,
                      depth=2, max_len=32)
    table = DenseTable(params, mesh4, updater="adam", lr=1e-3)
    step = table.make_step(
        functools.partial(tfm.grad_fn, heads=2, remat="dots",
                          head_chunk=8),
        compute_dtype=jnp.bfloat16)
    batch = {"tokens": jnp.zeros((8, 33), jnp.int32)}
    return step.lower(table.params, table.opt_state,
                      batch).compile().as_text()


def test_dense_step_has_a_stable_program_name(dense_lm_text):
    assert dense_lm_text.startswith("HloModule jit_" + prof.DENSE_STEP_FN)


def test_dense_step_all_gather_is_under_pull(dense_lm_text):
    got = _phases_of(_instructions(dense_lm_text), "all-gather")
    assert got == {prof.PULL}


def test_dense_step_push_collective_is_under_push(dense_lm_text):
    got = _phases_of(_instructions(dense_lm_text), "reduce-scatter",
                     "all-reduce")
    assert prof.PUSH in got
    # the loss's pmean is the one collective outside the four phases
    assert got <= {prof.PUSH, None}


def test_dense_step_optimizer_ops_are_under_update(dense_lm_text):
    scopes = [s for _, s in _instructions(dense_lm_text)
              if phase_of(s)[0] == prof.UPDATE]
    assert any("sqrt" in s or "rsqrt" in s for s in scopes)   # Adam's
    assert not any(prof.GRAD in s for s in scopes)


def test_dense_step_model_phases_are_inside_grad(dense_lm_text):
    seen = set()
    for _, scope in _instructions(dense_lm_text):
        phase, part = phase_of(scope)
        if phase in (prof.LM_HEAD, prof.LM_ATTN, prof.LM_MLP,
                     prof.LM_EMBED):
            assert prof.GRAD in scope, scope
            seen.add((phase, part))
    for phase in (prof.LM_HEAD, prof.LM_ATTN, prof.LM_MLP):
        assert (phase, "fwd") in seen and (phase, "bwd") in seen
    # remat "dots" runs a block's forward again for its backward
    assert (prof.LM_ATTN, "remat") in seen or (prof.LM_MLP, "remat") in seen


def test_the_chunked_heads_one_loop_is_wholly_under_the_heads_scope(
        dense_lm_text):
    """``nll_chunked``'s forward and backward rules are a custom_vjp's:
    the one loop that forms logits, dh and dW, and everything in its body,
    reads ``lm.head``; no second loop, nothing run again for a backward."""
    loops = [line for line in dense_lm_text.splitlines()
             if re.search(r" while\(", line)]
    assert len(loops) == 1
    assert _phases_of(_instructions(loops[0]), "while") == {prof.LM_HEAD}
    body = re.search(r"body=(%[\w.\-]+)", loops[0]).group(1)
    start = dense_lm_text.index("\n" + body + " ")
    inside = _instructions(
        dense_lm_text[start:dense_lm_text.index("\n}", start)])
    assert any(op == "dot" for op, _ in inside)
    assert {phase_of(scope) for op, scope in inside
            if op != "constant"} == {(prof.LM_HEAD, "fwd")}
    assert not any(phase_of(scope) == (prof.LM_HEAD, "remat")
                   for _, scope in _instructions(dense_lm_text))


def test_phase_of_takes_the_innermost_phase_and_the_pass():
    assert phase_of("jit(ps_dense_step)/ps.grad/jvp(lm.attn)/dot_general") \
        == (prof.LM_ATTN, "fwd")
    assert phase_of("ps.grad/transpose(jvp(lm.head))/mul") \
        == (prof.LM_HEAD, "bwd")
    assert phase_of("jit(s)/ps.grad/transpose(jvp(ps.grad))/jvp()/"
                    "checkpoint/rematted_computation/lm.mlp/mul") \
        == (prof.LM_MLP, "remat")
    assert phase_of("jit(s)/ps.push.sparse/emb/sparse.adagrad_sorted/"
                    "sparse.dedup/sort") == (prof.SPARSE_DEDUP, "fwd")
    assert phase_of("jit(s)/ps.push.dense/add") == (prof.PUSH_DENSE, "fwd")
    assert phase_of("jit(s)/ps.pushy/add") == (None, "fwd")
    assert phase_of("") == (None, "fwd")


@pytest.fixture()
def fused_text(mesh4, monkeypatch):
    from minips_tpu.ops import sparse_update
    from minips_tpu.tables.dense import DenseTable
    from minips_tpu.tables.sparse import SparseTable
    from minips_tpu.train.ps_step import PSTrainStep

    # the sort-dedup strategy, which the size of a real table selects
    monkeypatch.setattr(sparse_update, "DENSE_ACCUM_MAX_ELEMS", 0)
    emb = SparseTable(256, 4, mesh4, name="emb", updater="adagrad")
    dense = DenseTable({"w": jnp.ones(4)}, mesh4, updater="adam", lr=1e-3)

    def loss_fn(dp, rows, b):
        return jnp.mean((rows["emb"] @ dp["w"] - b["y"]) ** 2)

    ps = PSTrainStep(loss_fn, dense=dense, sparse={"emb": emb},
                     key_fns={"emb": lambda b: b["ids"]})
    batch = ps.shard_batch({"ids": np.arange(16, dtype=np.int32) % 7,
                            "y": np.ones(16, np.float32)})
    return ps.lower(batch).compile().as_text()


def test_fused_step_names_its_phases_and_the_sparse_ops(fused_text):
    assert fused_text.startswith("HloModule jit_" + prof.FUSED_STEP_FN)
    scopes = [s for _, s in _instructions(fused_text)]
    phases = {phase_of(s)[0] for s in scopes}
    assert {prof.PULL, prof.GRAD, prof.PUSH_DENSE, prof.SPARSE_DEDUP,
            prof.SPARSE_ADAGRAD_SORTED} <= phases
    # a table's push is one scope with the table's name inside
    sparse = [s for s in scopes if prof.PUSH_SPARSE + "/emb/" in s]
    assert any(prof.SPARSE_DEDUP in s for s in sparse)
    assert any(prof.SPARSE_ADAGRAD_SORTED in s for s in sparse)
    assert any(op == "sort" and prof.SPARSE_DEDUP in s
               for op, s in _instructions(fused_text))
    assert any(op.startswith("gather") and phase_of(s)[0] == prof.PULL
               for op, s in _instructions(fused_text))


@pytest.mark.parametrize("fn, prefer_dense, want", [
    ("row_adagrad", True, prof.SPARSE_ADAGRAD_DENSE),
    ("row_adagrad", False, prof.SPARSE_ADAGRAD_SORTED),
    ("row_adam", True, prof.SPARSE_ADAM_DENSE),
    ("row_adam", False, prof.SPARSE_ADAM_SORTED),
])
def test_each_row_update_strategy_has_its_own_scope(fn, prefer_dense, want):
    from minips_tpu.ops import sparse_update

    emb = jnp.zeros((32, 4))
    slots = jnp.arange(8, dtype=jnp.int32) % 5
    grads = jnp.ones((8, 4))
    if fn == "row_adagrad":
        args = (emb, emb + 0.1, slots, grads, 0.1)
    else:
        args = (emb, emb, emb, jnp.zeros(32, jnp.int32), slots, grads, 0.1)
    f = jax.jit(functools.partial(getattr(sparse_update, fn),
                                  prefer_dense=prefer_dense))
    phases = {phase_of(s)[0]
              for _, s in _instructions(f.lower(*args).compile().as_text())}
    assert want in phases
    assert (prof.SPARSE_DEDUP in phases) == (not prefer_dense)


def test_the_chunked_heads_backward_rule_is_under_the_heads_scope():
    """A custom_vjp's backward rule runs outside the Python scope of its
    call: the one multiply that scales ``dW`` ``[vocab, dim]`` by the
    cotangent must still read ``transpose(jvp(lm.head))``."""
    from minips_tpu.models import transformer as tfm

    p = tfm.init(jax.random.PRNGKey(0), vocab=48, dim=32, heads=2,
                 depth=1, max_len=16)    # no other [48, 32] in the model
    batch = {"tokens": jnp.zeros((2, 17), jnp.int32)}
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, s: s * tfm.loss(q, batch, heads=2, head_chunk=4)))(p, 3.0)
    scopes = [str(e.source_info.name_stack) for e in _eqns(jaxpr.jaxpr)
              if e.primitive.name == "mul"
              and e.outvars[0].aval.shape == (48, 32)]
    assert len(scopes) == 1
    assert phase_of(scopes[0]) == (prof.LM_HEAD, "bwd")


def test_the_two_flash_kernels_carry_their_names():
    from minips_tpu.ops.flash_attention import flash_attention

    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True,
                               block_q=64, block_k=64).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
    assert _pallas_names(jaxpr.jaxpr) == [
        prof.FLASH_FWD, prof.FLASH_BWD]


@pytest.mark.parametrize("remat, rematted", [
    ("dots", []), (True, [prof.FLASH_FWD] * 2)])
def test_flash_kernels_keep_name_and_scope_with_residuals_named(
        remat, rematted):
    """``attn_roofline`` and ``trace_analysis`` find the kernels by name
    (or opcode) under ``lm.attn``: naming the forward's residuals takes no
    kernel out of the scope and renames none. Under ``"dots"`` no kernel
    is in a rematted forward; under ``True`` the forward kernel is."""
    from minips_tpu.models import transformer as tfm
    from minips_tpu.ops.flash_attention import flash_attention

    p = tfm.init(jax.random.PRNGKey(0), vocab=32, dim=32, heads=2,
                 depth=2, max_len=128)
    toks = jnp.zeros((2, 128), jnp.int32)

    def loss(q):
        out, _ = tfm._forward(
            q, toks, jnp.arange(128), 2,
            lambda *a: flash_attention(*a, causal=True, interpret=True),
            jnp.float32, remat=remat)
        return out.sum()

    kernels = [(str(e.params["name"]),
                phase_of(str(e.source_info.name_stack)))
               for e in _eqns(jax.make_jaxpr(jax.grad(loss))(p).jaxpr)
               if e.primitive.name == "pallas_call"]
    assert {name for name, _ in kernels} == {
        prof.FLASH_FWD, prof.FLASH_BWD}
    assert {phase for _, (phase, _) in kernels} == {prof.LM_ATTN}
    assert [n for n, (_, part) in kernels if part == "remat"] == rematted
    assert set(prof.FLASH_RESIDUALS).isdisjoint(prof.KERNELS)


def test_the_gather_kernel_carries_its_name():
    from minips_tpu.ops import pallas_kernels

    emb = jnp.ones((64, 128), jnp.float32)
    slots = jnp.arange(16, dtype=jnp.int32)
    jaxpr = jax.make_jaxpr(functools.partial(
        pallas_kernels.gather_rows, interpret=True))(emb, slots)
    assert _pallas_names(jaxpr.jaxpr) == [prof.GATHER_ROWS]


def test_names_are_defined_in_profiling_only():
    """Call sites and the reduction import the names; a literal copy
    elsewhere in the package could drift."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(
        prof.__file__)))
    names = [getattr(prof, k) for k in dir(prof)
             if k.isupper() and isinstance(getattr(prof, k), str)
             and re.fullmatch(r"[a-z_]+(\.[a-z_]+)+|flash_\w+|ps_\w+_step",
                              getattr(prof, k))]
    assert prof.STEP in names and prof.FLASH_BWD in names
    quoted = re.compile("|".join(
        r"[\"']" + re.escape(n) + r"[\"']" for n in names))
    offenders = []
    for d, _, files in os.walk(root):
        for name in files:
            path = os.path.join(d, name)
            if name.endswith(".py") and path != prof.__file__:
                with open(path) as f:
                    if quoted.search(f.read()):
                        offenders.append(os.path.relpath(path, root))
    assert offenders == []
