"""The jax_compat quarantine's CI contract (satellite): the manifest of
pre-existing jax-version failures (tests/jax_compat_failures.txt) may
only SHRINK — fixing a test deletes its line; a new failure must never
hide behind the marker. The ceiling below is the seed count measured
the day the quarantine landed; anyone deleting lines should lower it
to match (it is an upper bound, so forgetting merely loosens nothing
that matters — adding a line is what it catches)."""

from __future__ import annotations

import subprocess
import sys

from tests.conftest import load_jax_compat_manifest

# the manifest may never grow past the count below; it only moves
# down. The version shims that emptied most of it are gone (the code
# now calls jax.shard_map / jax.lax.pcast / jax.typeof directly); the 2
# entries left XPASS on the installed jax (ROADMAP D0 removes them).
SEED_FAILURE_COUNT = 2


def test_manifest_only_shrinks():
    entries = load_jax_compat_manifest()
    assert len(entries) <= SEED_FAILURE_COUNT, (
        f"jax_compat manifest grew to {len(entries)} entries "
        f"(seed ceiling {SEED_FAILURE_COUNT}): a NEW failure is a "
        "regression to fix, never a line to quarantine")


def test_manifest_has_no_duplicates_and_sane_nodeids():
    entries = load_jax_compat_manifest()
    assert entries, "manifest missing or empty — quarantine disarmed"
    assert len(entries) == len(set(entries)), "duplicate manifest lines"
    for e in entries:
        assert e.startswith("tests/") and "::" in e, (
            f"manifest line is not a pytest nodeid: {e!r}")


def test_manifest_entries_match_collected_tests():
    """Every manifest FILE must still exist and collect — a deleted or
    renamed test leaves a dead manifest line that silently shrinks the
    quarantine's coverage claim. (File-level check: a full collection
    here would re-pay the suite's import cost.)"""
    import os

    here = os.path.dirname(__file__)
    files = {e.split("::", 1)[0] for e in load_jax_compat_manifest()}
    for f in sorted(files):
        assert os.path.exists(os.path.join(here, "..", f)), (
            f"manifest names a test file that no longer exists: {f}")


def test_quarantined_test_reports_xfail_not_failed():
    """End-to-end: running ONE manifested test under the tier-1 flags
    reports xfailed (clean signal), not failed."""
    entries = load_jax_compat_manifest()
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", entries[0], "-q",
         "-p", "no:cacheprovider", "--no-header", "-rxX"],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-2000:]
    assert "xfailed" in out or "xpassed" in out, out[-2000:]
