"""The dependency direction of the fabric's modules.

What a node computes (``core/alu.py``) and the arc layout every executor
shares (``core/plan.py``) sit below the executors: the compiler's passes
import them without the engine or the kernels, and no kernel reaches up
into the engine.
"""
import ast
import os
import subprocess
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parent.parent / "src"
ENGINE = "repro.core.engine"


def _imports(path: Path):
    """(module, names) of every import statement in a source file,
    relative imports resolved against its package."""
    pkg = path.relative_to(SRC).with_suffix("").parts[:-1]
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name, ()
        elif isinstance(node, ast.ImportFrom):
            base = pkg[:len(pkg) - node.level + 1] if node.level else ()
            mod = ".".join((*base, *(node.module or "").split(".")))
            names = tuple(a.name for a in node.names)
            yield mod.strip("."), names
            for n in names:         # ``from repro.core import engine``
                yield f"{mod.strip('.')}.{n}", ()


def test_semantics_plan_and_passes_load_no_executor():
    code = ("import sys, repro.core.alu, repro.core.plan, repro.core.passes; "
            f"print([m for m in ({ENGINE!r}, 'repro.kernels.dataflow_fire') "
            "if m in sys.modules])")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        filter(None, (str(SRC), os.environ.get("PYTHONPATH")))))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"


def test_no_kernel_imports_the_engine():
    bad = [p.name for p in (SRC / "repro" / "kernels").glob("*.py")
           if any(m == ENGINE for m, _ in _imports(p))]
    assert not bad


def test_no_module_imports_private_engine_names():
    bad = [(str(p.relative_to(SRC)), n)
           for p in (SRC / "repro").rglob("*.py")
           for m, names in _imports(p) if m == ENGINE
           for n in names if n.startswith("_")]
    assert not bad
