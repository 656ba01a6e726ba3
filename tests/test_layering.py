"""The arrows of the system point one way: entry points, tools, the
benchmark and the tests import the package, and the package imports none
of them."""
import ast
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ABOVE = {"tools", "bench", "benchmarks", "chip_smoke", "tests"}


def test_package_imports_nothing_above_it():
    found = []
    for dirpath, _dirs, files in os.walk(os.path.join(ROOT, "mmlspark_tpu")):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            with open(path) as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                if isinstance(node, ast.Import):
                    tops = [a.name.split(".")[0] for a in node.names]
                elif isinstance(node, ast.ImportFrom) and node.level == 0:
                    tops = [(node.module or "").split(".")[0]]
                else:
                    continue
                found += [f"{os.path.relpath(path, ROOT)}:{node.lineno} "
                          f"imports {t}" for t in tops if t in ABOVE]
    assert found == []
