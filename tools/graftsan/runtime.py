"""graftsan runtime: locksets, the lock-order graph, and flow audits.

Everything execution-time lives here; reporting/baseline glue is in
report.py and the public install()/uninstall() surface in __init__.py.

Three analyses, all deterministic given a deterministic schedule:

* **S101 — Eraser-style lockset races.**  `SanLock`/`SanRLock` record
  per-thread held-lock sets; every `#: guarded-by` annotated field of
  the adopted classes gets a data-descriptor shim that runs the Eraser
  state machine (Virgin -> Exclusive -> Shared/Shared-Modified) and
  intersects the candidate lockset on each access.  A shared, written
  field whose candidate set goes empty is a race: the report carries
  the access site/stack of BOTH conflicting accesses.
* **S201 — lock-order cycles.**  Acquiring lock B while holding lock A
  adds edge A->B to the global acquisition-order graph (one stack
  captured per new edge).  The moment an edge closes a cycle the report
  fires — no hang required — naming both acquisition stacks.
* **S301/S302 — conservation audits.**  FlowGraph registers its credit
  semaphores through the `core.flow._SAN` observer hook; at clean EOF
  every hop must have released exactly what it acquired (a leak names
  the stage), EOF markers must not be duplicated past the
  one-per-worker re-put contract, and at audit time no `flow.*` fault
  point may still be armed.

The disabled path costs nothing: uninstalled, production code builds
plain `threading.Lock`s (utils/sync.py returns them directly) and the
only residue is the `_SAN is None` branch at flow's credit hops.
"""
from __future__ import annotations

import os
import sys
import threading
import traceback
from _thread import allocate_lock as _raw_lock
from typing import Any, Dict, List, Optional, Tuple

from tools.graftlint.core import Finding

__all__ = ["SanLock", "SanRLock", "STATE", "S_RULE_DOCS",
           "shim_guarded_fields", "unshim_guarded_fields",
           "FlowObserver", "audit_flow", "audit_fault_points",
           "short_stack"]

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

S_RULE_DOCS: Dict[str, str] = {
    "S101": "lockset race: a guarded-by field was accessed by multiple "
            "threads and its candidate lockset went empty",
    "S201": "lock-order inversion: a new acquisition edge closed a "
            "cycle in the global lock-order graph",
    "S301": "credit/EOF conservation violated: a flow graph reached "
            "EOF with unreleased credits or duplicated EOF markers",
    "S302": "a flow.* fault point was still armed at audit time (the "
            "soak's arm() never disarmed)",
}


def _rel(path: str) -> str:
    """Repo-relative '/'-separated path for findings; out-of-tree files
    (stdlib lock sites) keep their basename so baseline keys stay
    stable across interpreter prefixes."""
    try:
        rel = os.path.relpath(path, ROOT)
    except ValueError:
        rel = os.path.basename(path)
    if rel.startswith(".."):
        rel = os.path.basename(path)
    return rel.replace(os.sep, "/")


def short_stack(skip: int = 2, limit: int = 8) -> str:
    """Compact one-line stack summary: 'file:line in fn <- ...', newest
    first, graftsan's own frames dropped."""
    frames = traceback.extract_stack(sys._getframe(skip), limit=limit)
    parts = []
    for fr in reversed(frames):
        if os.sep + "graftsan" + os.sep in fr.filename:
            continue
        parts.append(f"{_rel(fr.filename)}:{fr.lineno} in {fr.name}")
    return " <- ".join(parts[:5]) or "<no frames>"


# ---------------------------------------------------------------------------
# Global sanitizer state.  One raw (never-instrumented) mutex guards it;
# sanitizer internals never acquire a product lock while holding it, so
# it is a strict leaf in the lock hierarchy and cannot deadlock.
# ---------------------------------------------------------------------------
class _State:
    def __init__(self):
        # a raw (never-instrumented) _thread lock guards everything
        # below; plain comments, not `#: guarded-by` grammar — the
        # sanitizer must never shim its own state
        self.lock = _raw_lock()
        self.enabled = False  # SanLock/shim fast-path flag (GIL-atomic)
        self.findings: List[Finding] = []
        self.seen: set = set()
        # dedupe key per finding, index-parallel to `findings` so
        # take_findings can forget consumed keys (a hazard a test has
        # asserted on and removed may be deliberately re-provoked later)
        self.finding_keys: List[str] = []
        # lock-order graph: from_uid -> {to_uid: (stack, thread_name)}
        self.edges: Dict[int, Dict[int, Tuple[str, str]]] = {}
        # uid -> (name, file, line): only locks that ever nested
        self.lock_meta: Dict[int, Tuple[str, str, int]] = {}
        self.reported_pairs: set = set()
        # flow graph audit records, keyed id(graph)
        self.flow_graphs: Dict[int, dict] = {}
        self.uid_counter = 0
        self.test_mark = 0  # findings index at begin_test()

    def next_uid(self) -> int:
        with self.lock:
            self.uid_counter += 1
            return self.uid_counter

    def add_finding(self, key: str, finding: Finding) -> bool:
        """Record once per dedupe key; returns True when newly added."""
        with self.lock:
            if key in self.seen:
                return False
            self.seen.add(key)
            self.findings.append(finding)
            self.finding_keys.append(key)
            return True

    def reset(self):
        with self.lock:
            self.findings.clear()
            self.finding_keys.clear()
            self.seen.clear()
            self.edges.clear()
            self.lock_meta.clear()
            self.reported_pairs.clear()
            self.flow_graphs.clear()
            self.test_mark = 0


STATE = _State()
_TLS = threading.local()  # .held: {lock_uid: reentry_count}, ordered


def _held() -> Dict[int, int]:
    held = getattr(_TLS, "held", None)
    if held is None:
        held = _TLS.held = {}
    return held


# ---------------------------------------------------------------------------
# Suppression checking against source lines (runtime findings can't ride
# graftlint's whole-file pass; same grammar, '# graftsan: disable=SXXX'
# on the line or the line directly above, via graftlint's shared core).
# ---------------------------------------------------------------------------
_SF_CACHE: Dict[str, Any] = {}


def suppressed_at(path: str, line: int, rule: str) -> bool:
    from tools.graftlint.core import SourceFile

    if not path or line <= 0:
        return False
    sf = _SF_CACHE.get(path)
    if sf is None:
        try:
            with open(path, encoding="utf-8") as f:
                src = f.read()
        except OSError:
            return False
        sf = _SF_CACHE[path] = SourceFile(path, _rel(path), src,
                                          marker="graftsan")
    return sf.suppressed(rule, line)


# ---------------------------------------------------------------------------
# S201: the lock-order graph
# ---------------------------------------------------------------------------
def _find_path(src: int, dst: int) -> Optional[List[int]]:
    """DFS for a path src ->* dst in the edge graph (STATE.lock held)."""
    stack = [(src, [src])]
    visited = {src}
    while stack:
        node, path = stack.pop()
        if node == dst:
            return path
        for nxt in STATE.edges.get(node, ()):
            if nxt not in visited:
                visited.add(nxt)
                stack.append((nxt, path + [nxt]))
    return None


def _note_acquire_edges(lock: "SanLock", held: Dict[int, int]) -> None:
    """Record held->acquiring edges; fire S201 the moment the SECOND
    direction of any pair (any cycle) is observed — no hang required."""
    stack = None
    with STATE.lock:
        for h_uid in list(held):
            tos = STATE.edges.setdefault(h_uid, {})
            if lock.uid in tos:
                continue
            if stack is None:
                stack = short_stack(skip=4)
            tos[lock.uid] = (stack, threading.current_thread().name)
            STATE.lock_meta.setdefault(
                lock.uid, (lock.name, lock.site[0], lock.site[1]))
            # cycle: is the reverse direction already reachable?
            path = _find_path(lock.uid, h_uid)
            if path is None:
                continue
            pair = frozenset((h_uid, lock.uid))
            if pair in STATE.reported_pairs:
                continue
            STATE.reported_pairs.add(pair)
            self_meta = STATE.lock_meta.get(
                lock.uid, (lock.name,) + lock.site)
            held_meta = STATE.lock_meta.get(h_uid, ("<lock>", "", 0))
            rev_stack, rev_thread = STATE.edges.get(
                path[0], {}).get(path[1], ("<unknown>", "?"))
            finding = Finding(
                rule="S201",
                path=_rel(held_meta[1]) if held_meta[1] else "<unknown>",
                line=held_meta[2],
                symbol=f"{held_meta[0]}<->{self_meta[0]}",
                message=(
                    f"lock-order cycle: {held_meta[0]!r} -> "
                    f"{self_meta[0]!r} acquired here [{threading.current_thread().name}: "
                    f"{stack}] but {self_meta[0]!r} -> ... -> "
                    f"{held_meta[0]!r} was already observed "
                    f"[{rev_thread}: {rev_stack}]"),
                hint="pick one acquisition order (or suppress at a "
                     "lock's creation site with '# graftsan: "
                     "disable=S201' and a justification)")
            key = f"S201::{finding.symbol}"
            if STATE.seen.__contains__(key):
                continue
            # suppression: either lock's creation line may carry the
            # disable
            suppress = False
            for name, f, ln in (self_meta, held_meta):
                if f and suppressed_at(f, ln, "S201"):
                    suppress = True
            if not suppress:
                STATE.seen.add(key)
                STATE.findings.append(finding)
                STATE.finding_keys.append(key)


# ---------------------------------------------------------------------------
# SanLock / SanRLock: drop-in instrumented mutexes
# ---------------------------------------------------------------------------
class SanLock:
    """Instrumented `threading.Lock` stand-in: tracks the per-thread
    held-lock set (feeding S101 locksets) and the global acquisition-
    order graph (S201).  Installed two ways: utils/sync.make_lock gives
    NAMED locks at the adopted construction sites, and the install()
    monkeypatch of `threading.Lock` catches everything else (queue
    mutexes, Events, Conditions) created while the sanitizer is live."""

    _KIND = "Lock"

    def __init__(self, name: Optional[str] = None, _depth: int = 1):
        self._inner = self._make_inner()
        self.uid = STATE.next_uid()
        try:
            frame = sys._getframe(_depth)
            self.site = (frame.f_code.co_filename, frame.f_lineno)
        except ValueError:
            self.site = ("", 0)
        self.name = name or (
            f"{_rel(self.site[0])}:{self.site[1]}" if self.site[0]
            else f"lock#{self.uid}")

    @staticmethod
    def _make_inner():
        return _raw_lock()

    # -- tracking ------------------------------------------------------
    def _track_acquire(self):
        held = _held()
        n = held.get(self.uid)
        if n is not None:
            held[self.uid] = n + 1
            return
        if held and STATE.enabled:
            _note_acquire_edges(self, held)
        held[self.uid] = 1

    def _track_release(self):
        held = _held()
        n = held.get(self.uid)
        if n is None:
            return  # released by a non-owner thread: nothing to untrack
        if n <= 1:
            del held[self.uid]
        else:
            held[self.uid] = n - 1

    # -- the lock protocol --------------------------------------------
    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        got = self._inner.acquire(blocking, timeout)
        if got:
            self._track_acquire()
        return got

    def release(self) -> None:
        self._track_release()
        self._inner.release()

    def locked(self) -> bool:
        return self._inner.locked()

    def __enter__(self) -> bool:
        return self.acquire()

    def __exit__(self, *exc) -> None:
        self.release()

    def _at_fork_reinit(self):  # pragma: no cover - fork paths only
        self._inner = self._make_inner()
        _TLS.held = {}

    def __repr__(self) -> str:
        return f"<{type(self).__name__} {self.name!r} uid={self.uid}>"


class SanRLock(SanLock):
    """Instrumented `threading.RLock` stand-in; additionally speaks the
    `_release_save`/`_acquire_restore`/`_is_owned` protocol so
    `threading.Condition` keeps full reentrant semantics on top."""

    _KIND = "RLock"

    @staticmethod
    def _make_inner():
        return threading._PyRLock() if not hasattr(
            threading, "_CRLock") or threading._CRLock is None \
            else threading._CRLock()

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        got = self._inner.acquire(blocking, timeout)
        if got:
            self._track_acquire()
        return got

    # Condition protocol: _release_save fully releases however deep the
    # reentry is; carry our own held count through the opaque state so
    # _acquire_restore rebuilds the lockset exactly
    def _release_save(self):
        count = _held().pop(self.uid, 1)
        return (self._inner._release_save(), count)

    def _acquire_restore(self, state):
        inner_state, count = state
        self._inner._acquire_restore(inner_state)
        held = _held()
        if held and STATE.enabled and self.uid not in held:
            _note_acquire_edges(self, held)
        held[self.uid] = count

    def _is_owned(self):
        return self._inner._is_owned()


# ---------------------------------------------------------------------------
# S101: guarded-field shims (the Eraser lockset state machine)
# ---------------------------------------------------------------------------
class _FieldState:
    __slots__ = ("state", "tid", "lockset", "last")

    def __init__(self, tid: int, last: tuple):
        self.state = "exclusive"   # virgin collapses into first access
        self.tid = tid
        self.lockset: Optional[set] = None
        self.last = last           # (site, thread name, 'write'|'read')


class GuardedField:
    """Data descriptor shimmed over one `#: guarded-by` annotated
    attribute: stores the value at its ordinary `__dict__` key (so
    uninstall is just descriptor removal) and runs the Eraser check on
    every access while the sanitizer is enabled."""

    def __init__(self, cls: type, attr: str, lock_attr: str,
                 decl_file: str, decl_line: int):
        self.cls = cls
        self.attr = attr
        self.lock_attr = lock_attr
        self.decl_file = decl_file
        self.decl_line = decl_line

    # -- descriptor protocol ------------------------------------------
    def __get__(self, obj, objtype=None):
        if obj is None:
            return self
        try:
            val = obj.__dict__[self.attr]
        except KeyError:
            raise AttributeError(
                f"{type(obj).__name__!s} object has no attribute "
                f"{self.attr!r}") from None
        if STATE.enabled:
            self._access(obj, write=False)
        return val

    def __set__(self, obj, value):
        if STATE.enabled:
            self._access(obj, write=True)
        obj.__dict__[self.attr] = value

    def __delete__(self, obj):
        if STATE.enabled:
            self._access(obj, write=True)
        obj.__dict__.pop(self.attr, None)

    # -- Eraser --------------------------------------------------------
    def _access(self, obj, write: bool):
        # Instances whose declared guard is a PLAIN lock predate
        # install() (module singletons like utils.faults.FAULTS) — their
        # critical sections are invisible to the lockset tracker, so
        # every access would look lockless.  Skip them: only instances
        # built after install (monkeypatched Lock or make_lock adoption)
        # carry SanLocks and can be checked without false positives.
        guard = obj.__dict__.get(self.lock_attr)
        if not isinstance(guard, SanLock):
            return
        tid = threading.get_ident()
        held = frozenset(_held())
        try:
            frame = sys._getframe(2)
            site = f"{_rel(frame.f_code.co_filename)}:{frame.f_lineno}"
        except ValueError:
            site = "<unknown>"
        cur = (site, threading.current_thread().name,
               "write" if write else "read")
        with STATE.lock:
            states = obj.__dict__.get("__graftsan_fields__")
            if states is None:
                states = {}
                obj.__dict__["__graftsan_fields__"] = states
            st = states.get(self.attr)
            if st is None:
                states[self.attr] = _FieldState(tid, cur)
                return
            if st.state == "reported":
                return
            if st.state == "exclusive":
                if tid == st.tid:
                    st.last = cur
                    return
                # second thread: the field is truly shared from here on
                st.lockset = set(held)
                st.state = "shared_mod" if write else "shared"
            else:
                st.lockset &= held
                if write:
                    st.state = "shared_mod"
            empty = st.state == "shared_mod" and not st.lockset
            prev = st.last
            st.last = cur
            if not empty:
                return
            st.state = "reported"
        self._report(prev, cur)

    def _report(self, prev: tuple, cur: tuple):
        if suppressed_at(self.decl_file, self.decl_line, "S101"):
            return
        finding = Finding(
            rule="S101",
            path=_rel(self.decl_file),
            line=self.decl_line,
            symbol=f"{self.cls.__name__}.{self.attr}",
            message=(
                f"lockset race on {self.cls.__name__}.{self.attr} "
                f"(guarded-by self.{self.lock_attr}): candidate lockset "
                f"empty after {cur[2]} at {cur[0]} [thread {cur[1]}, "
                f"stack {short_stack(skip=3)}] conflicting with "
                f"{prev[2]} at {prev[0]} [thread {prev[1]}]"),
            hint=f"hold self.{self.lock_attr} on every access, or "
                 f"suppress on the annotation line with '# graftsan: "
                 f"disable=S101' and a justification")
        STATE.add_finding(f"S101::{self.cls.__name__}.{self.attr}",
                          finding)


def _guarded_decls(cls: type) -> List[Tuple[str, str, int]]:
    """(attr, lock_attr, decl_line) for every `#: guarded-by self.X`
    annotation in the class's __init__ — graftlint G2's grammar, read
    from the live class's source so tools and product can't drift."""
    import ast
    import inspect

    from tools.graftlint.g2_locks import GUARDED_BY

    try:
        src = inspect.getsource(cls)
        base_line = cls.__dict__.get("__graftsan_srcline__") or \
            inspect.getsourcelines(cls)[1]
    except (OSError, TypeError):
        return []
    try:
        tree = ast.parse(__import__("textwrap").dedent(src))
    except SyntaxError:
        return []
    lines = __import__("textwrap").dedent(src).splitlines()
    out: List[Tuple[str, str, int]] = []
    node = tree.body[0]
    if not isinstance(node, ast.ClassDef):
        return []
    for child in node.body:
        if isinstance(child, ast.FunctionDef) and child.name == "__init__":
            for stmt in ast.walk(child):
                if not isinstance(stmt, (ast.Assign, ast.AnnAssign,
                                         ast.AugAssign)):
                    continue
                targets = (stmt.targets if isinstance(stmt, ast.Assign)
                           else [stmt.target])
                for t in targets:
                    if not (isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"):
                        continue
                    line = lines[stmt.lineno - 1] \
                        if stmt.lineno <= len(lines) else ""
                    m = GUARDED_BY.search(line)
                    if m is None and stmt.lineno >= 2:
                        above = lines[stmt.lineno - 2].strip()
                        if above.startswith("#"):
                            m = GUARDED_BY.search(above)
                    if m:
                        out.append((t.attr, m.group(1),
                                    base_line + stmt.lineno - 1))
    return out


def shim_guarded_fields(cls: type) -> List[str]:
    """Install GuardedField descriptors for every annotated attribute of
    `cls`; returns the shimmed attribute names.  Skips classes with
    __slots__ (no instance dict to store through) and fields whose
    annotation line carries `# graftsan: disable=S101`."""
    if "__slots__" in cls.__dict__:
        return []
    try:
        import inspect

        decl_file = inspect.getsourcefile(cls) or ""
    except TypeError:
        return []
    shimmed = []
    for attr, lock_attr, line in _guarded_decls(cls):
        if attr in cls.__dict__:   # already shimmed, or a class default
            continue
        if suppressed_at(decl_file, line, "S101"):
            continue
        setattr(cls, attr, GuardedField(cls, attr, lock_attr,
                                        decl_file, line))
        shimmed.append(attr)
    return shimmed


def unshim_guarded_fields(cls: type) -> None:
    for attr, val in list(cls.__dict__.items()):
        if isinstance(val, GuardedField):
            delattr(cls, attr)


# ---------------------------------------------------------------------------
# S301/S302: flow credit + fault-point conservation
# ---------------------------------------------------------------------------
class FlowObserver:
    """The `core.flow._SAN` hook target.  FlowGraph tells it about
    construction (creation site for suppression), credit traffic, EOF
    marker enqueues, and clean EOF; audit_flow() turns the ledger into
    S301 findings."""

    def on_graph(self, graph) -> None:
        try:
            frame = sys._getframe(2)
            site = (frame.f_code.co_filename, frame.f_lineno)
        except ValueError:
            site = ("", 0)
        names = [s.name for s in graph.stages] + ["out"]
        rec = {
            "label": graph._label,
            "site": site,
            "names": names,
            "budgets": list(graph._budgets),
            "workers": [s.workers for s in graph.stages],
            "credits": {id(c): [names[i], 0, 0]  # name, acq, rel
                        for i, c in enumerate(graph._credits)},
            "eof": [0] * len(graph._budgets),
            "clean_eof": False,
            "audited": False,
        }
        with STATE.lock:
            STATE.flow_graphs[id(graph)] = rec
            # hold the credit objects so id() keys can't be reused
            rec["_pins"] = list(graph._credits)
            self._by_credit = getattr(self, "_by_credit", {})
            for c in graph._credits:
                self._by_credit[id(c)] = rec

    def _credit(self, credits, delta_acq: int, delta_rel: int) -> None:
        by = getattr(self, "_by_credit", None)
        if not by:
            return
        rec = by.get(id(credits))
        if rec is None:
            return
        with STATE.lock:
            row = rec["credits"].get(id(credits))
            if row is not None:
                row[1] += delta_acq
                row[2] += delta_rel

    def on_credit_acquire(self, credits) -> None:
        self._credit(credits, 1, 0)

    def on_credit_release(self, credits) -> None:
        self._credit(credits, 0, 1)

    def on_eof(self, graph, idx: int) -> None:
        with STATE.lock:
            rec = STATE.flow_graphs.get(id(graph))
            if rec is not None and idx < len(rec["eof"]):
                rec["eof"][idx] += 1

    def on_graph_eof(self, graph) -> None:
        """Clean end-of-stream observed by the consumer: every credit
        must be home.  Audited immediately — this is the moment the
        parity contract holds by construction."""
        with STATE.lock:
            rec = STATE.flow_graphs.get(id(graph))
            if rec is None:
                return
            rec["clean_eof"] = True
        _audit_graph_record(rec)


def _audit_graph_record(rec: dict) -> None:
    if rec["audited"] or not rec["clean_eof"]:
        return
    rec["audited"] = True
    site_file, site_line = rec["site"]
    leaks = []
    for cid, (name, acq, rel) in sorted(rec["credits"].items(),
                                        key=lambda kv: kv[1][0]):
        if acq != rel:
            leaks.append((name, acq, rel))
    dup_eof = []
    for i, n in enumerate(rec["eof"]):
        # contract: 1 arrival from upstream + one re-put per worker of
        # the stage that pops it; the out hop has no workers re-putting.
        # Fewer is a worker still parked (benign at audit time); MORE is
        # a duplicated end-of-stream marker.
        workers = rec["workers"][i] if i < len(rec["workers"]) else 0
        if n > workers + 1:
            dup_eof.append((rec["names"][i], n, workers + 1))
    if not leaks and not dup_eof:
        return
    if site_file and suppressed_at(site_file, site_line, "S301"):
        return
    for name, acq, rel in leaks:
        finding = Finding(
            rule="S301",
            path=_rel(site_file) if site_file else "<unknown>",
            line=site_line,
            symbol=f"{rec['label']}.{name}",
            message=(
                f"credit leak in {rec['label']!r} stage {name!r}: "
                f"{acq} acquired vs {rel} released at clean EOF "
                f"(budget {rec['budgets'][rec['names'].index(name)]})"),
            hint="every _put_into must be balanced by a release when "
                 "the item leaves the stage; suppress at the graph "
                 "construction site with '# graftsan: disable=S301'")
        STATE.add_finding(f"S301::{rec['label']}.{name}::credit", finding)
    for name, n, want in dup_eof:
        finding = Finding(
            rule="S301",
            path=_rel(site_file) if site_file else "<unknown>",
            line=site_line,
            symbol=f"{rec['label']}.{name}",
            message=(
                f"EOF-slot accounting violated in {rec['label']!r} hop "
                f"{name!r}: {n} EOF enqueues, contract allows {want} "
                f"(1 + one re-put per worker)"),
            hint="an EOF marker was forwarded twice — check the "
                 "reorder buffer's _eof_sent latch")
        STATE.add_finding(f"S301::{rec['label']}.{name}::eof", finding)


def audit_flow() -> None:
    """End-of-run sweep: audit every clean-EOF graph not yet audited
    (on_graph_eof normally got there first; this catches graphs whose
    consumer never drained to EOF but that were registered clean)."""
    with STATE.lock:
        recs = list(STATE.flow_graphs.values())
    for rec in recs:
        _audit_graph_record(rec)


def audit_fault_points() -> None:
    """S302: no `flow.*` fault point may still be armed when the soak
    or test ends — a leaked arm() poisons every later run's schedule."""
    try:
        from mmlspark_tpu.utils.faults import FAULTS
    except Exception:
        return
    with FAULTS._lock:
        plan = FAULTS._plan
        armed = sorted(p for p in (plan.rules if plan else ())
                       if p.startswith("flow."))
    if not armed:
        return
    finding = Finding(
        rule="S302",
        path="mmlspark_tpu/utils/faults.py",
        line=0,
        symbol="FaultInjector.arm",
        message=(
            f"flow fault point(s) still armed at audit time: "
            f"{', '.join(armed)} — the arming context manager never "
            f"exited"),
        hint="arm plans with 'with FAULTS.arm(plan):' so disarm is "
             "structural")
    STATE.add_finding(f"S302::{','.join(armed)}", finding)
