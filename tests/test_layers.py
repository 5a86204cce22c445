"""The line between the two systems in ``minips_tpu`` (docs/architecture.md,
the drawing at its top): the fused path (core) never imports the wire
fleet; the fleet imports the core.

The checks: a static walk of every core file's imports, lazy ones inside
functions included (no fleet module; and, for the core's lower half, nothing
of its upper half); one fresh interpreter that imports the package and the
two chip apps and must not have loaded the fleet; and the package's
top-level names, which all come from the core.
"""

import ast
import json
import pathlib
import subprocess
import sys

import pytest

PKG = pathlib.Path(__file__).resolve().parent.parent / "minips_tpu"

# a core "package" is a directory, or the named modules of one that the
# fleet shares (consistency/gate.py and the wire trainers are fleet)
CORE = {
    "parallel": ["parallel"],
    "ops": ["ops"],
    "tables": ["tables"],
    "models": ["models"],
    "utils": ["utils"],
    "data": ["data"],
    "ckpt": ["ckpt"],
    "core": ["core"],
    "consistency": ["consistency/__init__.py", "consistency/controllers.py",
                    "consistency/tracker.py"],
    "train": ["train/__init__.py", "train/ps_step.py", "train/loop.py"],
}

FLEET = (
    "minips_tpu.comm", "minips_tpu.obs", "minips_tpu.balance",
    "minips_tpu.serve", "minips_tpu.tenant", "minips_tpu.launch",
    "minips_tpu.apps", "minips_tpu.consistency.gate",
    "minips_tpu.train.sharded_ps", "minips_tpu.train.mesh_plane",
    "minips_tpu.train.ssp_spmd", "minips_tpu.train.cssp_ps",
    "minips_tpu.train.ssp_trainer", "zmq",
)

# "<file relative to minips_tpu/>: <module>" -> why it may cross. Empty:
# the core tells the fleet nothing.
ALLOWED: dict[str, str] = {}


def _under(module: str, roots) -> bool:
    """``module`` is one of ``roots`` or lies inside one."""
    return any(module == r or module.startswith(r + ".") for r in roots)


def _is_fleet(module: str) -> bool:
    return _under(module, FLEET)


def _files(entries):
    for e in entries:
        p = PKG / e
        yield from (sorted(p.rglob("*.py")) if p.is_dir() else [p])


def _imports(path: pathlib.Path):
    """Every module a file imports, absolute: ``import a.b``, ``from a
    import b`` (both ``a`` and ``a.b``: ``b`` may be a submodule), and
    relative forms resolved against the file's own package."""
    here = ["minips_tpu", *path.relative_to(PKG).parts[:-1]]
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = here[:len(here) - node.level + 1]
                base = ".".join(up + ([base] if base else []))
            yield base
            for a in node.names:
                yield f"{base}.{a.name}"


@pytest.mark.parametrize("package", sorted(CORE))
def test_core_imports_no_fleet(package):
    files = list(_files(CORE[package]))
    assert files, package
    crossing = []
    for f in files:
        rel = f.relative_to(PKG).as_posix()
        for mod in _imports(f):
            if _is_fleet(mod) and f"{rel}: {mod}" not in ALLOWED:
                crossing.append(f"{rel}: {mod}")
    assert not crossing, (
        f"core package {package!r} imports the fleet:\n  "
        + "\n  ".join(sorted(set(crossing))))


# the drawing's second arrow: the lower half of the core (kernels, mesh,
# loaders, utilities) imports nothing of the upper half
LOWER = ("ops", "parallel", "data", "utils")
UPPER = tuple("minips_tpu." + u for u in
              ("models", "tables", "train", "core", "ckpt", "consistency"))


@pytest.mark.parametrize("package", LOWER)
def test_core_lower_half_imports_nothing_above(package):
    crossing = sorted({
        f"{f.relative_to(PKG).as_posix()}: {mod}"
        for f in _files(CORE[package]) for mod in _imports(f)
        if _under(mod, UPPER)})
    assert not crossing, "\n  ".join(crossing)


LOADED_BY_THE_FUSED_PATH_NEVER = (
    "zmq", "minips_tpu.comm", "minips_tpu.obs", "minips_tpu.balance",
    "minips_tpu.serve", "minips_tpu.tenant", "minips_tpu.launch",
    "minips_tpu.train.sharded_ps", "minips_tpu.train.mesh_plane",
    "minips_tpu.train.ssp_spmd",
)


_PROBE = """
import json, sys, types
import minips_tpu
names = {k: (v.__name__ if isinstance(v, types.ModuleType)
             else getattr(v, "__module__", None))
         for k, v in vars(minips_tpu).items() if not k.startswith("_")}
import minips_tpu.apps.lm_example, minips_tpu.apps.wide_deep_example
print(json.dumps({"names": names, "modules": sorted(sys.modules)}))
"""


@pytest.fixture(scope="module")
def fresh_interpreter():
    """What a fresh interpreter holds: the public names ``import
    minips_tpu`` binds with the module each comes from, and then
    ``sys.modules`` once the two apps that the chip cells and
    ``chip_smoke.py`` start are imported too. A subprocess, because this
    one has long since imported the fleet for other tests."""
    out = subprocess.run([sys.executable, "-c", _PROBE],
                         capture_output=True, text=True, timeout=300,
                         cwd=str(PKG.parent))
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", LOADED_BY_THE_FUSED_PATH_NEVER)
def test_fused_path_loads_no_fleet(fresh_interpreter, name):
    loaded = [m for m in fresh_interpreter["modules"] if _under(m, [name])]
    assert not loaded, f"the fused path loaded {loaded}"


TOP_LEVEL = (
    "Config", "TableConfig", "TrainConfig", "Engine", "Info", "MLTask",
    "ASP", "BSP", "SSP", "make_controller", "make_mesh", "DenseTable",
    "cast_floating", "SparseTable", "TrainLoop", "PSTrainStep",
    "StreamingAUC", "auc_exact", "evaluate_auc", "MetricsLogger", "cluster",
)


def test_top_level_names_come_from_the_core(fresh_interpreter):
    names = fresh_interpreter["names"]
    assert not set(TOP_LEVEL) - set(names), set(TOP_LEVEL) - set(names)
    core = ["minips_tpu." + p for p in CORE]
    for name, home in names.items():
        assert home and _under(home, core) and not _is_fleet(home), (
            name, home)
