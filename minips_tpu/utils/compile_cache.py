"""Persistent XLA compilation cache — one function, one placement rule.

JAX ships a content-addressed persistent cache keyed on (HLO, jaxlib
version, backend, flags, and the cache directory's own path). Every
single-process entry point (``apps.common.app_main``, ``bench.py``,
``chip_smoke.py``, ``tests/conftest.py``) calls
:func:`enable_compile_cache` once, before its first compile.

Placement: if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own reading of
it stands and nothing here sets another directory — the machine's
operator decides where compiled programs live (and whether they outlive
the run). Otherwise the cache is ``<checkout>/.jax_cache``: one fixed,
git-ignored path inside the tree, never a home directory, a temp name, a
pid, a rank or a time — the path is part of the cache key, so a
directory that moves never hits.

RANKS OF A MULTI-PROCESS JOB RUN CACHE-LESS (round-5 finding, attempted
twice — do not try a third time without new evidence). Attempt 1: CPU
launcher children sharing a cache hung intermittently on warm reads with
XLA logging ``cpu_aot_loader ... could lead to execution errors such as
SIGILL``. Attempt 2: host-fingerprint-scoped directories — the wd
collective smokes ran 2.5x slower and the bsp leg reproducibly died on
Gloo's 30s rendezvous deadline (``GetKeyValue() timed out``): with every
tiny program paying a serialize+write, the ranks' arrival at their first
collective skews past the deadline. So a process that the launcher
started as one of several ranks gets ``None`` here, and
``launch.child_env`` drops ``JAX_COMPILATION_CACHE_DIR`` from such
ranks' environment so JAX does not arm the cache on its own.
"""

from __future__ import annotations

import os

IN_CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache; returns the directory
    in effect, or None in a rank of a multi-process job (see module
    docstring)."""
    if int(os.environ.get("MINIPS_NUM_PROCS") or 1) > 1:
        return None
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = IN_CHECKOUT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # JAX's defaults skip sub-second compiles; the test suite's cost is a
    # long tail of 1-10s CPU compiles and a chip call's is two large
    # steps, so cache everything (a tier-1 run leaves ~30 MB)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
