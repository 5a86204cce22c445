"""Checkpoint/recovery: disk roundtrip of tables + clocks (SURVEY.md §5.4)."""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from minips_tpu.ckpt.checkpoint import Checkpointer, _flatten, _unflatten
from minips_tpu.consistency import SSP
from minips_tpu.tables.dense import DenseTable
from minips_tpu.tables.sparse import SparseTable
from minips_tpu.utils import profiling


def test_flatten_unflatten_roundtrip():
    tree = {"a": {"b": np.arange(3)}, "c": [np.ones(2), {"d": np.zeros(1)}],
            "e": None}
    back = _unflatten({k: v for k, v in _flatten(tree).items()})
    assert back["e"] is None
    np.testing.assert_array_equal(back["a"]["b"], np.arange(3))
    np.testing.assert_array_equal(back["c"][0], np.ones(2))
    np.testing.assert_array_equal(back["c"][1]["d"], np.zeros(1))


def _trained_tables(mesh, updater="adam"):
    dense = DenseTable({"w": jnp.zeros(8)}, mesh, updater=updater, lr=0.1)
    sparse = SparseTable(64, 4, mesh, updater="adagrad", lr=0.1, seed=7)
    for _ in range(3):
        dense.push({"w": jnp.arange(8.0)})
        sparse.push(jnp.array([1, 2, 3]), jnp.ones((3, 4)))
    return dense, sparse


def test_disk_roundtrip_resumes_identically(mesh8, tmp_path):
    """After restore, further identical pushes must produce identical state
    (i.e. optimizer state incl. adam moments/adagrad accum survived)."""
    d1, s1 = _trained_tables(mesh8)
    ck = Checkpointer(str(tmp_path), {"d": d1, "s": s1})
    ck.save(step=3)

    d2, s2 = _trained_tables(mesh8)  # fresh tables, same shapes
    # diverge d2 so restore provably overwrites
    d2.push({"w": jnp.ones(8) * 100})
    ck2 = Checkpointer(str(tmp_path), {"d": d2, "s": s2})
    assert ck2.restore() == 3

    for t in (d1, d2):
        t.push({"w": jnp.arange(8.0)})
    s1.push(jnp.array([2, 3]), jnp.ones((2, 4)))
    s2.push(jnp.array([2, 3]), jnp.ones((2, 4)))
    np.testing.assert_allclose(np.asarray(d2.params), np.asarray(d1.params),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s2.emb), np.asarray(s1.emb),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s2.accum), np.asarray(s1.accum),
                               rtol=1e-6)


def test_updater_mismatch_rejected(mesh8, tmp_path):
    d1, _ = _trained_tables(mesh8, updater="adam")
    Checkpointer(str(tmp_path), {"d": d1}).save(step=1)
    d_sgd = DenseTable({"w": jnp.zeros(8)}, mesh8, updater="sgd", lr=0.1)
    with pytest.raises(ValueError, match="leaf count mismatch"):
        Checkpointer(str(tmp_path), {"d": d_sgd}).restore()


def test_controller_clocks_roundtrip(mesh8, tmp_path):
    d, s = _trained_tables(mesh8)
    c = SSP(4, staleness=2)
    c.clock(0); c.clock(0); c.clock(1)
    Checkpointer(str(tmp_path), {"d": d}, {"t": c}).save(step=9)
    c2 = SSP(4, staleness=2)
    ck = Checkpointer(str(tmp_path), {"d": d}, {"t": c2})
    assert ck.restore() == 9
    assert c2.tracker.snapshot() == [2, 1, 0, 0]


def test_gc_keeps_newest(mesh8, tmp_path):
    d, _ = _trained_tables(mesh8)
    ck = Checkpointer(str(tmp_path), {"d": d}, keep=2)
    for s in (1, 2, 3, 4):
        ck.save(step=s)
    assert ck.list_steps() == [3, 4]


def test_async_save(mesh8, tmp_path):
    d, s = _trained_tables(mesh8)
    ck = Checkpointer(str(tmp_path), {"d": d, "s": s}, async_save=True)
    ck.save(step=5)
    ck.wait()
    assert ck.list_steps() == [5]
    ck2 = Checkpointer(str(tmp_path), {"d": d, "s": s})
    assert ck2.restore() == 5


def test_partial_tmp_dir_ignored(mesh8, tmp_path):
    """A crash mid-save (leftover .tmp dir) must not break restore."""
    d, _ = _trained_tables(mesh8)
    ck = Checkpointer(str(tmp_path), {"d": d})
    ck.save(step=1)
    os.makedirs(str(tmp_path / "step_0000000002.tmp"))
    assert ck.list_steps() == [1]
    assert ck.restore() == 1


def test_restore_walks_back_past_torn_checkpoint(mesh8, tmp_path, capfd):
    """Fail-slow PR satellite: a TORN newest checkpoint — truncated
    npz, corrupt manifest, or a missing table file — is skipped with a
    loud warning and ``restore()`` walks back to the newest VALID step
    instead of crashing the relaunch. The live tables stay untouched
    by the failed candidate (validate-before-apply)."""
    d, s = _trained_tables(mesh8)
    ck = Checkpointer(str(tmp_path), {"d": d, "s": s})
    ck.save(step=1)
    ck.save(step=2)
    ck.save(step=3)
    # tear step 3: truncate its npz mid-file (the crash-mid-write shape
    # the atomic rename cannot protect against — e.g. disk-full after
    # publish, or bit rot)
    p3 = tmp_path / "step_0000000003" / "d.npz"
    raw = p3.read_bytes()
    p3.write_bytes(raw[: len(raw) // 2])
    d2, s2 = _trained_tables(mesh8)
    ck2 = Checkpointer(str(tmp_path), {"d": d2, "s": s2})
    before = profiling.snapshot()[1].get(profiling.CKPT_SKIP_TORN, (0, 0))
    assert ck2.restore() == 2
    err = capfd.readouterr().err
    assert "skipping torn checkpoint" in err and "step_3" in err
    # ... and on record in the program's ring: one skip, value the step
    after = profiling.snapshot()[1][profiling.CKPT_SKIP_TORN]
    assert (after[0] - before[0], after[1] - before[1]) == (1, 3)
    # an EXPLICIT step keeps strict semantics: asking for the torn one
    # raises instead of silently substituting an older step
    with pytest.raises(Exception):
        ck2.restore(step=3)
    # corrupt manifest on the next-newest: walk back twice
    (tmp_path / "step_0000000002" / "manifest.json").write_text("{tor")
    d3, s3 = _trained_tables(mesh8)
    assert Checkpointer(str(tmp_path), {"d": d3, "s": s3}).restore() == 1
    # a missing table file is a torn checkpoint too
    os.remove(str(tmp_path / "step_0000000001" / "d.npz"))
    d4, s4 = _trained_tables(mesh8)
    with pytest.raises(FileNotFoundError, match="every candidate"):
        Checkpointer(str(tmp_path), {"d": d4, "s": s4}).restore()


def test_sgd_roundtrip_leafless_opt_state(mesh8, tmp_path):
    """sgd's opt state has zero leaves (EmptyStates), so no 'opt_state' key
    lands in the npz at all — restore must tolerate the absent key."""
    d1 = DenseTable({"w": jnp.zeros(8)}, mesh8, updater="sgd", lr=0.1)
    d1.push({"w": jnp.ones(8)})
    Checkpointer(str(tmp_path), {"d": d1}).save(step=1)
    d2 = DenseTable({"w": jnp.zeros(8)}, mesh8, updater="sgd", lr=0.1)
    assert Checkpointer(str(tmp_path), {"d": d2}).restore() == 1
    np.testing.assert_allclose(np.asarray(d2.params), np.asarray(d1.params),
                               rtol=1e-6)


class TestOrbaxBackend:
    """Same contract as the native backend, through orbax.checkpoint."""

    @pytest.fixture(autouse=True)
    def _require_orbax(self):
        pytest.importorskip("orbax.checkpoint")

    def test_roundtrip_resumes_identically(self, mesh8, tmp_path):
        from minips_tpu.ckpt.orbax_backend import make_checkpointer

        d1, s1 = _trained_tables(mesh8)
        ck = make_checkpointer(str(tmp_path), {"d": d1, "s": s1},
                               backend="orbax")
        ck.save(step=3)
        ck.wait()

        d2, s2 = _trained_tables(mesh8)
        d2.push({"w": jnp.ones(8) * 100})      # diverge; restore overwrites
        ck2 = make_checkpointer(str(tmp_path), {"d": d2, "s": s2},
                                backend="orbax")
        assert ck2.restore() == 3
        for t in (d1, d2):
            t.push({"w": jnp.arange(8.0)})
        np.testing.assert_allclose(np.asarray(d2.params),
                                   np.asarray(d1.params), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(s2.emb), np.asarray(s1.emb),
                                   rtol=1e-6)
        ck.close()
        ck2.close()

    def test_keep_and_list_steps(self, mesh8, tmp_path):
        from minips_tpu.ckpt.orbax_backend import make_checkpointer

        d, s = _trained_tables(mesh8)
        ck = make_checkpointer(str(tmp_path), {"d": d}, keep=2,
                               backend="orbax")
        for step in (1, 2, 3):
            ck.save(step=step)
        ck.wait()
        assert ck.list_steps() == [2, 3]
        ck.close()

    def test_clocks_roundtrip(self, mesh8, tmp_path):
        from minips_tpu.ckpt.orbax_backend import make_checkpointer

        d, _ = _trained_tables(mesh8)
        ctl = SSP(staleness=2, num_workers=3)
        for w in range(3):
            ctl.clock(w)
        ctl.clock(0)
        ck = make_checkpointer(str(tmp_path), {"d": d},
                               {"ssp": ctl}, backend="orbax")
        ck.save(step=5)
        ck.wait()
        ctl2 = SSP(staleness=2, num_workers=3)
        ck2 = make_checkpointer(str(tmp_path), {"d": d},
                                {"ssp": ctl2}, backend="orbax")
        assert ck2.restore() == 5
        assert ctl2.state_dict() == ctl.state_dict()
        ck.close()
        ck2.close()

    def test_factory_default_is_native(self, mesh8, tmp_path, monkeypatch):
        from minips_tpu.ckpt.checkpoint import Checkpointer
        from minips_tpu.ckpt.orbax_backend import make_checkpointer

        monkeypatch.delenv("MINIPS_CKPT_BACKEND", raising=False)
        d, _ = _trained_tables(mesh8)
        ck = make_checkpointer(str(tmp_path), {"d": d})
        assert isinstance(ck, Checkpointer)
        with pytest.raises(ValueError, match="unknown checkpoint backend"):
            make_checkpointer(str(tmp_path), {"d": d}, backend="bogus")


def test_resume_replays_exact_data_stream(mesh8, tmp_path):
    """Interrupt-at-k + resume must reproduce the uninterrupted run's final
    params EXACTLY: TrainLoop fast-forwards the BatchIterator to the global
    step, so the resumed run consumes the same batches in the same order."""
    from minips_tpu.data.loader import BatchIterator
    from minips_tpu.models import lr as lr_model
    from minips_tpu.train.loop import TrainLoop

    rng = np.random.default_rng(0)
    data = {"x": rng.normal(size=(256, 16)).astype(np.float32),
            "y": rng.integers(0, 2, size=256).astype(np.float32)}

    def make():
        t = DenseTable(lr_model.init(16), mesh8, updater="adagrad", lr=0.3)
        s = t.make_step(lr_model.grad_fn_dense)
        return t, (lambda b: t.step_inplace(
            s, {k: jnp.asarray(v) for k, v in b.items()}))

    t1, f1 = make()  # uninterrupted: 10 steps
    TrainLoop(f1, BatchIterator(data, 32, seed=3), log_every=0).run(10)

    t2, f2 = make()  # interrupted at 6...
    ck = Checkpointer(str(tmp_path), {"w": t2})
    TrainLoop(f2, BatchIterator(data, 32, seed=3), checkpointer=ck,
              checkpoint_every=6, log_every=0).run(6)
    t3, f3 = make()  # ...resumed for the remaining 4
    start = Checkpointer(str(tmp_path), {"w": t3}).restore()
    assert start == 6
    TrainLoop(f3, BatchIterator(data, 32, seed=3), step_offset=start,
              log_every=0).run(10 - start)

    np.testing.assert_array_equal(np.asarray(t3.params),
                                  np.asarray(t1.params))


def test_orbax_restores_checkpoint_predating_layout_record(mesh8, tmp_path):
    """A pre-'layout' orbax checkpoint (hashed table) must still restore:
    the template is pruned to the saved keys so StandardRestore never sees
    the missing entry (code-review round 2 regression)."""
    pytest.importorskip("orbax.checkpoint")
    from minips_tpu.ckpt.orbax_backend import make_checkpointer
    from minips_tpu.tables.sparse import SparseTable

    s1 = SparseTable(64, 2, mesh8, updater="sgd", lr=0.5)
    s1.push(jnp.array([3]), jnp.ones((1, 2)))
    ck = make_checkpointer(str(tmp_path), {"s": s1}, backend="orbax")
    # simulate a legacy checkpoint: drop 'layout' from what gets saved
    orig = s1.state_dict

    def legacy_state_dict():
        st = orig()
        st.pop("layout")
        return st

    s1.state_dict = legacy_state_dict
    ck.save(step=1)
    ck.wait()
    ck.close()

    s2 = SparseTable(64, 2, mesh8, updater="sgd", lr=0.5, init_scale=0.0)
    ck2 = make_checkpointer(str(tmp_path), {"s": s2}, backend="orbax")
    assert ck2.restore() == 1  # hashed table: legacy tolerance
    np.testing.assert_allclose(np.asarray(s2.emb), np.asarray(s1.emb))
    ck2.close()

    # an identity table must still REFUSE the layout-less checkpoint
    s3 = SparseTable(64, 2, mesh8, updater="sgd", identity=True)
    ck3 = make_checkpointer(str(tmp_path), {"s": s3}, backend="orbax")
    with pytest.raises(ValueError, match="predates layout"):
        ck3.restore()
    ck3.close()


def test_sparse_layout_mismatch_rejected_but_salt_ignored_on_identity(
        mesh8, tmp_path):
    from minips_tpu.ckpt.checkpoint import Checkpointer
    from minips_tpu.tables.sparse import SparseTable

    t = SparseTable(64, 2, mesh8, identity=True, salt=0)
    Checkpointer(str(tmp_path), {"s": t}).save(step=1)
    # identity path never reads salt → differing salt must restore fine
    t2 = SparseTable(64, 2, mesh8, identity=True, salt=7)
    Checkpointer(str(tmp_path), {"s": t2}).restore()
    # but hashed vs identity is a real layout change → refuse
    t3 = SparseTable(64, 2, mesh8, identity=False)
    with pytest.raises(ValueError, match="layout"):
        Checkpointer(str(tmp_path), {"s": t3}).restore()


def test_legacy_checkpoint_refused_for_nonzero_salt(mesh8, tmp_path):
    from minips_tpu.ckpt.checkpoint import Checkpointer
    from minips_tpu.tables.sparse import SparseTable

    t = SparseTable(64, 2, mesh8, salt=3)
    orig = t.state_dict
    t.state_dict = lambda: {k: v for k, v in orig().items()
                            if k != "layout"}
    Checkpointer(str(tmp_path), {"s": t}).save(step=1)
    t2 = SparseTable(64, 2, mesh8, salt=7)
    with pytest.raises(ValueError, match="predates layout"):
        Checkpointer(str(tmp_path), {"s": t2}).restore()


def test_cross_backend_convert_native_to_orbax_and_back(mesh8, tmp_path):
    """VERDICT r1 #10: native save → orbax restore (via convert) and vice
    versa are lossless, including optimizer state — the two backends stay
    honestly drop-in. Post-restore push parity proves the state is live,
    not just byte-equal."""
    pytest.importorskip("orbax.checkpoint")
    from minips_tpu.ckpt import convert_checkpoint
    from minips_tpu.ckpt.orbax_backend import make_checkpointer

    d1, s1 = _trained_tables(mesh8)
    Checkpointer(str(tmp_path / "nat"), {"d": d1, "s": s1}).save(step=5)

    # native → orbax: migrate through scratch tables, then restore into
    # FRESH tables purely from the orbax copy
    dm, sm = _trained_tables(mesh8)
    assert convert_checkpoint(
        str(tmp_path / "nat"), str(tmp_path / "orb"), {"d": dm, "s": sm},
        src_backend="native", dst_backend="orbax") == 5
    d2, s2 = _trained_tables(mesh8)
    d2.push({"w": jnp.ones(8) * 50})  # diverge; restore must overwrite
    ck = make_checkpointer(str(tmp_path / "orb"), {"d": d2, "s": s2},
                           backend="orbax")
    assert ck.restore() == 5
    ck.close()
    np.testing.assert_allclose(np.asarray(d2.params), np.asarray(d1.params),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s2.emb), np.asarray(s1.emb),
                               rtol=1e-6)

    # orbax → native, restored into fresh tables again
    dn, sn = _trained_tables(mesh8)
    assert convert_checkpoint(
        str(tmp_path / "orb"), str(tmp_path / "nat2"), {"d": dn, "s": sn},
        src_backend="orbax", dst_backend="native") == 5
    d3, s3 = _trained_tables(mesh8)
    Checkpointer(str(tmp_path / "nat2"), {"d": d3, "s": s3}).restore()
    # optimizer state survived BOTH hops: identical further pushes give
    # identical state (adam moments / adagrad accumulators intact)
    for d, s in ((d1, s1), (d3, s3)):
        d.push({"w": jnp.arange(8.0)})
        s.push(jnp.array([2, 3]), jnp.ones((2, 4)))
    np.testing.assert_allclose(np.asarray(d3.params), np.asarray(d1.params),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s3.emb), np.asarray(s1.emb),
                               rtol=1e-6)


def test_prune_above_deletes_newer_steps(tmp_path):
    """prune_above removes dead-incarnation checkpoints so a later resume
    negotiation can never land on a mixed-incarnation step."""
    from minips_tpu.ckpt.checkpoint import Checkpointer

    class T:
        def __init__(self):
            self.v = np.zeros(4, np.float32)

        def state_dict(self):
            return {"v": self.v}

        def load_state_dict(self, s):
            self.v = s["v"]

    ck = Checkpointer(str(tmp_path), {"t": T()}, keep=0)
    for s in (5, 10, 15, 20):
        ck.save(s)
    assert ck.list_steps() == [5, 10, 15, 20]
    assert ck.prune_above(10) == [15, 20]
    assert ck.list_steps() == [5, 10]
    assert ck.prune_above(10) == []  # idempotent
    ck.restore(10)  # the kept step still restores
