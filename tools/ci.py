"""CI entry point: lint + sharded test matrix with flaky retries.

Reference: the pipeline's style gate and sharded test matrix
(pipeline.yaml:41 scalastyle; :332-415 — per-package test jobs with
20-minute budgets and flaky-retry).  One command runs the same thing
anywhere:

    python tools/ci.py lint [--json] [--full]
                                            # style gate + graftlint
                                            # (incremental --changed mode
                                            # by default; --full scans
                                            # the whole tree)
    python tools/ci.py metrics-lint         # M001/M002 alias (graftlint G3)
    python tools/ci.py fleet-smoke          # gateway kill/revive soak
    python tools/ci.py obs-soak             # telemetry plane: kill ->
                                            # alert -> autoscale ->
                                            # incident -> resolve
    python tools/ci.py flow-soak            # graftflow runtime chaos soak
    python tools/ci.py dist-soak            # elastic multi-host: kill a
                                            # pod host mid-epoch, shrink,
                                            # resume on survivors
    python tools/ci.py parity-3d            # 3D-mesh trainer == single-
                                            # device losses (8-dev mesh)
    python tools/ci.py sanitize [--json]    # all soaks under GRAFTSAN=1
                                            # (tools/graftsan runtime
                                            # concurrency sanitizer)
    python tools/ci.py test [--shards N] [--shard K] [--retries R]
    python tools/ci.py all                  # lint + every shard

Lint runs two layers with zero dependencies: a built-in AST style
linter (syntax, unused imports, bare except, mutable default args —
ruff replaces it when installed), then **graftlint**
(tools/graftlint/, docs/static_analysis.md): jit-purity hazards (G1,
now tracked through the cross-module call graph), lock discipline
(G2), registry drift incl. the old metrics-lint M001/M002 (G3),
resource hygiene (G4), and SPMD/sharding hazards (G5 "shardlint":
axis-literal hygiene, rule-table shadowing/coverage, use-after-donate),
gated by the checked-in baseline tools/graftlint_baseline.json.
graftlint runs in --changed mode (findings filtered to the git diff;
automatic full scan when the analyzer or a registry surface changed)
and always drops a SARIF 2.1.0 artifact (graftlint.sarif, override
with GRAFTLINT_SARIF) for diff-annotation tooling.

Sharding assigns test FILES round-robin over sorted order, so shard
membership is deterministic across machines; a failed shard reruns once
(--retries) and only an honest second failure fails the job.
"""
from __future__ import annotations

import argparse
import ast
import glob
import os
import re
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools import graftlint as _graftlint            # noqa: E402
from tools.graftlint import core as _gl_core         # noqa: E402
from tools.graftlint import g3_registry as _g3       # noqa: E402

LINT_TARGETS = ("mmlspark_tpu", "tests", "tools", "examples",
                "__graft_entry__.py")


# ---------------------------------------------------------------- lint

def _py_files():
    out = []
    for t in LINT_TARGETS:
        p = os.path.join(ROOT, t)
        if os.path.isfile(p):
            out.append(p)
        else:
            out.extend(sorted(glob.glob(os.path.join(p, "**", "*.py"),
                                        recursive=True)))
    return out


class _Lint(ast.NodeVisitor):
    """Minimal high-signal linter: unused imports (F401), bare except
    (E722), mutable default args (B006)."""

    def __init__(self, src: str, path: str):
        self.src = src
        self.path = path
        self.problems: list = []
        self.imported: dict = {}  # name -> lineno

    def visit_Import(self, node):
        for a in node.names:
            name = (a.asname or a.name).split(".")[0]
            self.imported[name] = node.lineno

    def visit_ImportFrom(self, node):
        if node.module == "__future__":
            return
        for a in node.names:
            if a.name == "*":
                continue
            self.imported[a.asname or a.name] = node.lineno

    def visit_ExceptHandler(self, node):
        if node.type is None:
            self.problems.append(
                (node.lineno, "E722 bare 'except:' — name the exception"))
        self.generic_visit(node)

    def visit_FunctionDef(self, node, _async=False):
        for d in node.args.defaults + [
                d for d in node.args.kw_defaults if d is not None]:
            if isinstance(d, (ast.List, ast.Dict, ast.Set)):
                self.problems.append(
                    (d.lineno, "B006 mutable default argument"))
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def finish(self):
        import re

        # an import is "used" if its name occurs as a whole word anywhere
        # else in the source (attribute chains, decorators, __all__
        # strings, doctests); word boundaries so 'np' never matches 'jnp'
        is_init = os.path.basename(self.path) == "__init__.py"
        lines = self.src.splitlines()
        for name, lineno in self.imported.items():
            if is_init or name.startswith("_"):
                continue  # re-export surface / deliberate side-effect
            pat = re.compile(r"\b%s\b" % re.escape(name))
            uses = len(pat.findall(self.src))
            uses -= len(pat.findall(lines[lineno - 1]))
            if uses <= 0:
                self.problems.append(
                    (lineno, f"F401 '{name}' imported but unused"))
        return sorted(self.problems)


# -------------------------------------------------------- metrics lint
# The M001/M002 implementation moved into tools/graftlint/g3_registry.py
# (rule ids preserved).  These shims keep the historical surface —
# tests monkeypatch _py_files / _declared_metric_names, and
# test_device_obs pins _sanitize_metric_name against the exposition
# module — and `metrics_lint()` keeps its exact output contract.

_METRIC_CALL = _g3._METRIC_CALL
_METRIC_CALL_BARE = _g3._METRIC_CALL_BARE
_TELEMETRY_IMPORT = _g3._TELEMETRY_IMPORT
_PROM_BAD = _g3._PROM_BAD


def _declared_metric_names():
    """DECLARED_METRICS keys parsed out of metrics.py's dict literal via
    AST — importing mmlspark_tpu here would pull jax into every lint."""
    return _g3.declared_metric_names(ROOT)


def _sanitize_metric_name(name: str) -> str:
    return _g3.sanitize_metric_name(name)


def metrics_lint() -> int:
    """Thin alias over graftlint's G3 metric checks: instrumented names
    must resolve against DECLARED_METRICS (M001, exact or declared
    prefix; f-strings by literal prefix) and no two declared names may
    sanitize to the same Prometheus name (M002)."""
    declared = _declared_metric_names()
    collisions = _g3.collision_findings(declared)
    for f in collisions:
        print(f"{f.path}: {f.rule} {f.message}")
    # same scope as graftlint's DEFAULT_TARGETS: tests/ is out — lint
    # fixtures embed deliberately-undeclared names the regex pass would
    # flag inside their string literals
    tests_dir = os.path.join(ROOT, "tests") + os.sep
    files = [_gl_core.load_source(p, ROOT) for p in _py_files()
             if not p.startswith(tests_dir)]
    m001 = _g3.metric_findings(files, declared)
    for f in m001:
        print(f"{f.path}:{f.line}: {f.rule} {f.message}")
    failures = len(m001) + len(collisions)
    if failures:
        print(f"metrics-lint: {failures} problem(s) "
              f"({len(collisions)} sanitize collision(s))")
    else:
        print("metrics-lint: all instrumented names declared, "
              "no sanitize collisions")
    return 1 if failures else 0


def graftlint_lint(json_out: bool = False, changed_only: bool = True,
                   sarif_out: str = None) -> int:
    """Run the full graftlint pass set against the checked-in baseline
    (tools/graftlint_baseline.json): any non-baselined finding — or a
    stale baseline entry — fails.

    `changed_only` is graftlint's --changed incremental mode (the
    default here): the whole tree is still analyzed — the cross-module
    call graph is whole-program — but findings are reported for the
    git-changed file set, falling back to the full report when the
    analyzer or a registry surface changed.  `sarif_out` additionally
    writes a SARIF 2.1.0 artifact (for diff annotation); the
    GRAFTLINT_SARIF env var overrides the default path."""
    res = _graftlint.run_with_baseline(ROOT, changed_only=changed_only)
    print(_gl_core.format_findings(res, json_out=json_out))
    sarif_out = sarif_out or os.environ.get(
        "GRAFTLINT_SARIF", os.path.join(ROOT, "graftlint.sarif"))
    try:
        with open(sarif_out, "w", encoding="utf-8") as f:
            f.write(_gl_core.format_sarif(res))
            f.write("\n")
        print(f"graftlint: SARIF artifact -> "
              f"{os.path.relpath(sarif_out, ROOT)}")
    except OSError as e:
        print(f"graftlint: could not write SARIF artifact: {e}")
    return 0 if not (res.new or res.stale) else 1


def lint(json_out: bool = False, full: bool = False) -> int:
    style_rc = _style_lint()
    graft_rc = graftlint_lint(json_out=json_out,
                              changed_only=not full)
    return style_rc or graft_rc


def _style_lint() -> int:
    if shutil.which("ruff"):
        return subprocess.call(["ruff", "check", ROOT])
    failures = 0
    for path in _py_files():
        with open(path, encoding="utf-8") as f:
            src = f.read()
        try:
            tree = ast.parse(src, filename=path)
        except SyntaxError as e:
            print(f"{path}:{e.lineno}: E999 {e.msg}")
            failures += 1
            continue
        linter = _Lint(src, path)
        linter.visit(tree)
        for lineno, msg in linter.finish():
            print(f"{os.path.relpath(path, ROOT)}:{lineno}: {msg}")
            failures += 1
    if failures:
        print(f"lint: {failures} problem(s)")
    else:
        print(f"lint: {len(_py_files())} files clean (builtin AST linter)")
    return 1 if failures else 0


# ---------------------------------------------------------------- test

def shard_files(n_shards: int):
    """Deterministic round-robin assignment of test files to shards."""
    files = sorted(
        os.path.basename(p)
        for p in glob.glob(os.path.join(ROOT, "tests", "test_*.py")))
    shards = [[] for _ in range(n_shards)]
    for i, f in enumerate(files):
        shards[i % n_shards].append(f)
    return shards


def run_shard(files, retries: int, timeout_s: int) -> bool:
    cmd = [sys.executable, "-m", "pytest", "-x", "-q"] + [
        os.path.join("tests", f) for f in files]
    for attempt in range(retries + 1):
        note = f" (retry {attempt})" if attempt else ""
        print(f"== shard: {len(files)} files{note}")
        try:
            rc = subprocess.call(cmd, cwd=ROOT, timeout=timeout_s)
        except subprocess.TimeoutExpired:
            print(f"shard timed out after {timeout_s}s")
            rc = 1
        if rc == 0:
            return True
    return False


def test(n_shards: int, shard: int, retries: int, timeout_s: int) -> int:
    shards = shard_files(n_shards)
    run = ([shards[shard]] if shard >= 0 else shards)
    ok = all(run_shard(files, retries, timeout_s) for files in run if files)
    return 0 if ok else 1


def fleet_smoke(timeout_s: int = 300) -> int:
    """Run the fleet kill/revive soak (tools/fleet_soak.py) as a smoke
    job: 2 replicas behind the gateway, a scripted mid-traffic kill, the
    exactly-once + eject/reinstate assertions.  CPU backend so the job
    runs on any CI machine."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join("tools", "fleet_soak.py"), "--json"]
    try:
        rc = subprocess.call(cmd, cwd=ROOT, env=env, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        print(f"fleet-smoke timed out after {timeout_s}s")
        return 1
    print("fleet-smoke:", "OK" if rc == 0 else f"FAILED (rc={rc})")
    return rc


def obs_soak(timeout_s: int = 300) -> int:
    """Run the observability-plane soak (tools/fleet_soak.py --obs):
    kill a replica mid-traffic, assert the availability SLO alert fires
    within one fast burn window, the AutoscaleController provisions a
    replacement, the flight recorder dumps an incident bundle, and the
    alert resolves — under the fleet exactly-once audit.  CPU backend so
    the job runs on any CI machine."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join("tools", "fleet_soak.py"),
           "--obs", "--json"]
    try:
        rc = subprocess.call(cmd, cwd=ROOT, env=env, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        print(f"obs-soak timed out after {timeout_s}s")
        return 1
    print("obs-soak:", "OK" if rc == 0 else f"FAILED (rc={rc})")
    return rc


def train_smoke(timeout_s: int = 300) -> int:
    """Run the training-reliability soak (tools/train_soak.py) as a
    smoke job: seeded NaN batches + mid-epoch kill + on-disk checkpoint
    corruption, survived with a bit-exact no-fault parity check.  CPU
    backend so the job runs on any CI machine."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join("tools", "train_soak.py"), "--json"]
    try:
        rc = subprocess.call(cmd, cwd=ROOT, env=env, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        print(f"train-soak timed out after {timeout_s}s")
        return 1
    print("train-soak:", "OK" if rc == 0 else f"FAILED (rc={rc})")
    return rc


def parity_3d(timeout_s: int = 600) -> int:
    """Run tools/parity3d.py on the virtual 8-device CPU mesh: the
    composed (data x tensor x pipe) 3D GSPMD train step must reproduce
    the single-device loss trajectory (2 steps, bf16 atol) for every
    swept layout.  The cheap CI proof that a sharding-rule or pipeline-
    schedule change didn't silently alter the math."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=8")
               .strip())
    cmd = [sys.executable, os.path.join("tools", "parity3d.py")]
    try:
        rc = subprocess.call(cmd, cwd=ROOT, env=env, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        print(f"parity-3d timed out after {timeout_s}s")
        return 1
    print("parity-3d:", "OK" if rc == 0 else f"FAILED (rc={rc})")
    return rc


def flow_soak(timeout_s: int = 300) -> int:
    """Run the graftflow runtime soak (tools/chaos_soak.py --flow) as a
    smoke job: seeded faults at every registered flow.* point, bounded-
    intake shed, intake-reap + mid-graph deadline expiry, with the
    0-lost/0-dup/ordered ledger reconciled against the telemetry
    snapshot.  CPU backend so the job runs on any CI machine."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join("tools", "chaos_soak.py"),
           "--flow", "--json"]
    try:
        rc = subprocess.call(cmd, cwd=ROOT, env=env, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        print(f"flow-soak timed out after {timeout_s}s")
        return 1
    print("flow-soak:", "OK" if rc == 0 else f"FAILED (rc={rc})")
    return rc


def dist_soak(timeout_s: int = 420) -> int:
    """Run the elastic multi-host soak (tools/dist_soak.py): the
    in-process lease-expiry shrink (8→6 device mesh, exactly-once
    ledger, parity with an uninterrupted reference) plus a real
    3-process pod with one host SIGKILLed mid-epoch — survivors
    quarantine, adopt the shrunken membership epoch, resume from the
    last verified checkpoint, and their per-host telemetry endpoints
    federate into one fleet view.  CPU backend so the job runs on any
    CI machine."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join("tools", "dist_soak.py"),
           "--json"]
    try:
        rc = subprocess.call(cmd, cwd=ROOT, env=env, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        print(f"dist-soak timed out after {timeout_s}s")
        return 1
    print("dist-soak:", "OK" if rc == 0 else f"FAILED (rc={rc})")
    return rc


def sanitize(timeout_s: int = 300, json_out: bool = False) -> int:
    """Run every soak under the runtime concurrency sanitizer
    (tools/graftsan, GRAFTSAN=1): chaos_soak --flow / --gateway /
    --dist, fleet_soak, train_soak, dist_soak.  Each job fails on any
    unsuppressed S-rule
    finding (lockset race S101, lock-order cycle S201, credit/EOF leak
    S301, leaked fault-point arm S302) not excused by the checked-in —
    and deliberately empty — tools/graftsan_baseline.json."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", GRAFTSAN="1")
    jobs = [
        ("chaos-flow", [os.path.join("tools", "chaos_soak.py"), "--flow"]),
        ("chaos-gateway", [os.path.join("tools", "chaos_soak.py"),
                           "--gateway"]),
        ("fleet", [os.path.join("tools", "fleet_soak.py")]),
        ("obs", [os.path.join("tools", "fleet_soak.py"), "--obs"]),
        ("train", [os.path.join("tools", "train_soak.py")]),
        ("chaos-dist", [os.path.join("tools", "chaos_soak.py"), "--dist"]),
        ("dist", [os.path.join("tools", "dist_soak.py")]),
    ]
    failures = 0
    for name, cmd in jobs:
        full = [sys.executable] + cmd + (["--json"] if json_out else [])
        print(f"== sanitize: {name}")
        try:
            rc = subprocess.call(full, cwd=ROOT, env=env,
                                 timeout=timeout_s)
        except subprocess.TimeoutExpired:
            print(f"sanitize[{name}] timed out after {timeout_s}s")
            rc = 1
        if rc != 0:
            failures += 1
        print(f"sanitize[{name}]:", "OK" if rc == 0 else f"FAILED (rc={rc})")
    print("sanitize:", "OK" if not failures
          else f"{failures} job(s) FAILED")
    return 1 if failures else 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("command", choices=["lint", "metrics-lint", "test",
                                        "fleet-smoke",
                                        "obs-soak", "train-soak",
                                        "flow-soak", "dist-soak",
                                        "parity-3d", "sanitize", "all"])
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--shard", type=int, default=-1,
                    help="run only this shard index (CI matrix job)")
    ap.add_argument("--retries", type=int, default=1)
    ap.add_argument("--timeout", type=int, default=1200,
                    help="per-shard budget, seconds (pipeline.yaml's 20min)")
    ap.add_argument("--json", action="store_true",
                    help="lint: machine-readable graftlint output")
    ap.add_argument("--full", action="store_true",
                    help="lint: disable graftlint's --changed "
                         "incremental mode (report the whole tree)")
    args = ap.parse_args(argv)
    if args.command == "lint":
        return lint(json_out=args.json, full=args.full)
    if args.command == "metrics-lint":
        return metrics_lint()
    if args.command == "fleet-smoke":
        return fleet_smoke()
    if args.command == "obs-soak":
        return obs_soak()
    if args.command == "train-soak":
        return train_smoke()
    if args.command == "flow-soak":
        return flow_soak()
    if args.command == "dist-soak":
        return dist_soak()
    if args.command == "parity-3d":
        return parity_3d()
    if args.command == "sanitize":
        return sanitize(json_out=args.json)
    if args.command == "test":
        return test(args.shards, args.shard, args.retries, args.timeout)
    rc = lint()
    return rc or test(args.shards, args.shard, args.retries, args.timeout)


if __name__ == "__main__":
    sys.exit(main())
