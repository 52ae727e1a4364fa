"""The nine repo-specific graft-lint checkers (ISSUEs 7 + 15).

Each rule encodes a defect class a human reviewer actually caught —
the PR 7 set (thread-safety, host-sync, atomic-write, env-sync,
metrics-hygiene, memory-hygiene) works at the source level; the ISSUE
15 tier (use-after-donate, retrace-hazard, gate-hygiene)
guards the jit/program boundary where the bug class moved after PR 10
made the training step one opaque donated program.  The checker
docstrings name the incidents.  All checkers are AST-based and
conservative — a miss is recoverable (the sanitizer, the program
auditor, or a review catches it), a false-positive storm kills the
gate.
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .core import FileCtx, Finding, PKG_DIR, REPO_ROOT

_ENV_RE = re.compile(r"^(MXNET_|MXT_)[A-Z0-9_]+$")
_ENV_DOC_RE = re.compile(r"\b((?:MXNET|MXT)_[A-Z0-9_]+)")


# one dotted-call-name resolver for the whole package: dataflow.py owns
# it (the def-use pass needs it without importing this heavier module)
from .dataflow import call_name as _call_name  # noqa: E402


def _const_str(node) -> Optional[str]:
    return node.value if isinstance(node, ast.Constant) \
        and isinstance(node.value, str) else None


# ---------------------------------------------------------------------------
# 1. thread-safety
# ---------------------------------------------------------------------------
class _ClassInfo:
    def __init__(self, node: ast.ClassDef):
        self.node = node
        self.methods: Dict[str, ast.FunctionDef] = {}
        self.locks: Dict[str, bool] = {}      # attr -> reentrant?
        self.worker_entries: Set[str] = set()
        # attr -> [(side, method, node, frozenset(held))]
        self.writes: Dict[str, list] = {}
        self.init_only: Set[str] = set()


_LOCK_CTORS = {
    "threading.Lock": False, "threading.RLock": True,
    "Lock": False, "RLock": True,
    # the sanitizer factories (mxnet_tpu.analysis.sanitizer)
    "make_lock": False, "make_rlock": True,
    "_san.make_lock": False, "_san.make_rlock": True,
    "sanitizer.make_lock": False, "sanitizer.make_rlock": True,
}
_COND_CTORS = {"threading.Condition", "Condition", "make_condition",
               "_san.make_condition", "sanitizer.make_condition"}


def _lock_ctor_reentrant(call: ast.Call) -> Optional[bool]:
    """None = not a lock construction; else the reentrancy of the lock
    bound by this call (Condition counts as its inner lock)."""
    name = _call_name(call.func)
    if name in _LOCK_CTORS:
        return _LOCK_CTORS[name]
    if name in _COND_CTORS or name.endswith(".Condition"):
        # an explicit reentrant= kwarg or inner lock wins; a BARE
        # Condition() defaults to an RLock (threading.Condition's
        # documented default), so it IS reentrant
        for kw in call.keywords:
            if kw.arg == "reentrant" and isinstance(kw.value, ast.Constant):
                return bool(kw.value.value)
        for a in call.args:
            if isinstance(a, ast.Call):
                inner = _lock_ctor_reentrant(a)
                if inner is not None:
                    return inner
        return True


class ThreadSafetyChecker:
    """Classes that spawn ``threading.Thread`` must guard shared mutable
    attributes with a held lock (the PR 6 hung-future reviews), and a
    non-reentrant lock must not be re-acquirable on the same thread
    (the PR 5 SIGTERM-mid-save deadlock class).

    Flags (a) ``self.attr = ...`` rebinds reachable from BOTH the worker
    thread and non-worker methods with no common must-held lock, and
    (b) acquisition of ``self.X`` while a path already holds ``self.X``
    and X is non-reentrant.  ``__init__`` writes are construction
    (happens-before ``Thread.start``), never flagged.
    """

    name = "thread-safety"
    _MAX_DEPTH = 12

    def check_file(self, ctx: FileCtx) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ClassDef):
                out.extend(self._check_class(ctx, node))
        return out

    # -- per-class analysis --------------------------------------------------
    def _check_class(self, ctx: FileCtx, cls: ast.ClassDef) -> List[Finding]:
        info = _ClassInfo(cls)
        for item in cls.body:
            if isinstance(item, ast.FunctionDef):
                info.methods[item.name] = item
        # pass 1: lock attrs + worker entries (Thread(target=...))
        local_workers: List[ast.FunctionDef] = []
        for mname, m in info.methods.items():
            local_defs = {n.name: n for n in ast.walk(m)
                          if isinstance(n, ast.FunctionDef) and n is not m}
            for n in ast.walk(m):
                if isinstance(n, ast.Assign) and isinstance(n.value, ast.Call):
                    re_ent = _lock_ctor_reentrant(n.value)
                    if re_ent is not None:
                        for t in n.targets:
                            if isinstance(t, ast.Attribute) and \
                                    isinstance(t.value, ast.Name) and \
                                    t.value.id == "self":
                                info.locks[t.attr] = re_ent
                if isinstance(n, ast.Call) and \
                        _call_name(n.func).endswith("Thread"):
                    for kw in n.keywords:
                        if kw.arg != "target":
                            continue
                        v = kw.value
                        if isinstance(v, ast.Attribute) and \
                                isinstance(v.value, ast.Name) and \
                                v.value.id == "self":
                            info.worker_entries.add(v.attr)
                        elif isinstance(v, ast.Name) and v.id in local_defs:
                            # closure worker (predictor._poll): analyze
                            # the local def as worker-side code
                            local_workers.append(local_defs[v.id])
        if not info.worker_entries and not local_workers:
            return []
        qual = cls.name
        reentry: List[Finding] = []
        sink: list = []   # (attr, side, method, node, held)
        seen: Set[tuple] = set()

        # pass 2: walk methods with must-held lock tracking
        def walk(fn: ast.FunctionDef, held: frozenset, side: str,
                 chain: Tuple[str, ...]):
            if len(chain) >= self._MAX_DEPTH or \
                    (fn.name, held, side) in seen:
                return
            seen.add((fn.name, held, side))
            for stmt in fn.body:
                visit(fn, stmt, held, side, chain + (fn.name,))

        def visit(fn, stmt, held, side, chain):
            if isinstance(stmt, ast.With):
                new_held = set(held)
                for item in stmt.items:
                    e = item.context_expr
                    if isinstance(e, ast.Attribute) and \
                            isinstance(e.value, ast.Name) and \
                            e.value.id == "self" and e.attr in info.locks:
                        if e.attr in held and not info.locks[e.attr]:
                            reentry.append(ctx.finding(
                                self.name, e,
                                f"non-reentrant lock 'self.{e.attr}' is "
                                f"re-acquired on a thread that already "
                                f"holds it (path: {' -> '.join(chain)}) "
                                f"— guaranteed deadlock; use an RLock "
                                f"or restructure",
                                symbol=f"{qual}.{fn.name}"))
                        new_held.add(e.attr)
                for s in stmt.body:
                    visit(fn, s, frozenset(new_held), side, chain)
                return
            if isinstance(stmt, (ast.If, ast.For, ast.While)):
                for s in list(stmt.body) + list(stmt.orelse):
                    visit(fn, s, held, side, chain)
                return
            if isinstance(stmt, ast.Try):
                for s in (list(stmt.body) + list(stmt.orelse)
                          + list(stmt.finalbody)
                          + [h for hh in stmt.handlers for h in hh.body]):
                    visit(fn, s, held, side, chain)
                return
            # attribute rebinds + self-method calls in plain statements
            for node in ast.walk(stmt):
                if isinstance(node, ast.Assign):
                    for t in node.targets:
                        record_write(fn, t, node, held, side)
                elif isinstance(node, ast.AugAssign):
                    record_write(fn, node.target, node, held, side)
                elif isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Attribute) and \
                        isinstance(node.func.value, ast.Name) and \
                        node.func.value.id == "self":
                    callee = info.methods.get(node.func.attr)
                    if callee is not None and callee.name != fn.name:
                        walk(callee, held, side, chain)

        def record_write(fn, target, node, held, side):
            if fn.name == "__init__":
                return
            if isinstance(target, ast.Attribute) and \
                    isinstance(target.value, ast.Name) and \
                    target.value.id == "self":
                sink.append((target.attr, side, fn.name, node, held))

        worker_names = set(info.worker_entries)
        for m in sorted(worker_names):
            if m in info.methods:
                walk(info.methods[m], frozenset(), "worker", ())
        for lw in local_workers:
            walk(lw, frozenset(), "worker", ())
        worker_reached = {s[2] for s in sink if s[1] == "worker"}
        seen.clear()
        for mname, m in info.methods.items():
            if mname == "__init__" or mname in worker_names:
                continue
            walk(m, frozenset(), "caller", ())

        # pass 3: write/write conflicts without a common must-held lock
        findings: List[Finding] = list(reentry)
        by_attr: Dict[str, list] = {}
        for attr, side, method, node, held in sink:
            by_attr.setdefault(attr, []).append((side, method, node, held))
        for attr, rows in sorted(by_attr.items()):
            if attr in info.locks:
                continue
            w = [r for r in rows if r[0] == "worker"]
            c = [r for r in rows if r[0] == "caller"
                 and r[1] not in worker_reached]
            if not w or not c:
                continue
            common = None
            for _, _, _, held in w + c:
                common = set(held) if common is None else common & set(held)
            if common:
                continue
            _, method, node, held = (c + w)[0]
            others = sorted({f"{qual}.{m}" for _, m, _, _ in w})
            findings.append(ctx.finding(
                self.name, node,
                f"attribute 'self.{attr}' is written both from the "
                f"worker thread ({', '.join(others)}) and from "
                f"{qual}.{method} with no common lock held — guard "
                f"both writes with one of "
                f"{sorted(info.locks) or ['a lock']}",
                symbol=f"{qual}.{method}"))
        return findings


# ---------------------------------------------------------------------------
# 2. host-sync
# ---------------------------------------------------------------------------
_SYNC_ATTRS = {"asnumpy", "asscalar", "item", "block_until_ready",
               "wait_to_read", "wait_to_write"}
_SYNC_CALLS = {"np.asarray", "_np.asarray", "numpy.asarray",
               "np.array", "_np.array"}


class HostSyncChecker:
    """No device→host synchronization inside ``@analysis.hot_path``
    functions or functions handed to ``jax.jit`` (the round-2/round-4
    dispatch-count regressions, caught statically).

    A ``.asnumpy()`` / ``float(nd)`` / ``np.asarray`` /
    ``block_until_ready`` on a hot path stalls the PJRT pipeline and
    turns O(1)-dispatch steps back into blocking ones.  The check is
    transitive over same-file calls (``self.m()`` and module-level
    functions) from every hot entry.
    """

    name = "host-sync"
    _MAX_DEPTH = 16

    def check_file(self, ctx: FileCtx) -> List[Finding]:
        funcs: Dict[str, ast.FunctionDef] = {}   # qualified name -> def
        methods: Dict[str, Dict[str, ast.FunctionDef]] = {}
        hot: List[Tuple[str, ast.FunctionDef, Optional[str]]] = []

        def collect(node, cls: Optional[str]):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.ClassDef):
                    collect(child, child.name)
                elif isinstance(child, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                    qual = f"{cls}.{child.name}" if cls else child.name
                    funcs[qual] = child
                    if cls:
                        methods.setdefault(cls, {})[child.name] = child
                    else:
                        methods.setdefault("", {})[child.name] = child
                    for dec in child.decorator_list:
                        dn = _call_name(dec) if not isinstance(dec, ast.Call) \
                            else _call_name(dec.func)
                        if dn.split(".")[-1] == "hot_path" or \
                                dn in ("jax.jit", "_jax.jit"):
                            hot.append((qual, child, cls))
                    collect(child, cls)

        collect(ctx.tree, None)
        # functions passed to jax.jit(...) positionally are hot entries
        jit_args: Set[str] = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) and \
                    _call_name(node.func) in ("jax.jit", "_jax.jit"):
                for a in node.args[:1]:
                    if isinstance(a, ast.Name):
                        jit_args.add(a.id)
                    elif isinstance(a, ast.Attribute) and \
                            isinstance(a.value, ast.Name) and \
                            a.value.id == "self":
                        jit_args.add(a.attr)
        hot_quals = {q for q, _, _ in hot}
        for qual, fn in funcs.items():
            if fn.name in jit_args and qual not in hot_quals:
                cls = qual.rsplit(".", 1)[0] if "." in qual else None
                hot.append((qual, fn, cls))

        out: List[Finding] = []
        for qual, fn, cls in hot:
            seen: Set[str] = set()
            self._scan(ctx, fn, cls, (qual,), methods, seen, out)
        return out

    @staticmethod
    def _host_math(node) -> bool:
        """int/float of host-static expressions is not a device sync:
        numpy/math shape arithmetic (int(np.prod(shape))), env/config
        parsing (float(getenv(...))), and ``x.shape[i]`` accesses."""
        if isinstance(node, ast.Call):
            cn = _call_name(node.func)
            root = cn.split(".")[0]
            leaf = cn.split(".")[-1]
            return root in ("np", "_np", "numpy", "math", "len",
                            "builtins") or \
                leaf in ("getenv", "get", "len", "float", "int")
        if isinstance(node, ast.Subscript):
            v = node.value
            return isinstance(v, ast.Attribute) and \
                v.attr in ("shape", "sizes", "strides", "buckets")
        return False

    def _scan(self, ctx, fn, cls, chain, methods, seen, out):
        key = chain[-1]
        if key in seen or len(chain) > self._MAX_DEPTH:
            return
        seen.add(key)
        entry = chain[0]
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            cn = _call_name(node.func)
            sync = None
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _SYNC_ATTRS and not node.args:
                sync = f".{node.func.attr}()"
            elif cn in _SYNC_CALLS:
                sync = f"{cn}(...)"
            elif cn in ("float", "int") and node.args and isinstance(
                    node.args[0], (ast.Call, ast.Subscript)) and \
                    not self._host_math(node.args[0]):
                # float(x.sum()) — a device value materialized to host.
                # Bare names are skipped (float(scale) on a python
                # scalar is everywhere), as is numpy/math shape
                # arithmetic (int(np.prod(shape)) is host-static).
                sync = f"{cn}(<expr>)"
            if sync is not None:
                via = "" if len(chain) == 1 else \
                    f" (via {' -> '.join(chain)})"
                out.append(ctx.finding(
                    self.name, node,
                    f"device->host sync {sync} reachable from hot path "
                    f"'{entry}'{via} — hot paths must stay async "
                    f"(move the read off-path, use metrics gauges, or "
                    f"suppress with justification)"))
                continue
            # transitive: self.m() within the class, bare f() in module
            if isinstance(node.func, ast.Attribute) and \
                    isinstance(node.func.value, ast.Name) and \
                    node.func.value.id == "self" and cls:
                callee = methods.get(cls, {}).get(node.func.attr)
                if callee is not None:
                    self._scan(ctx, callee, cls,
                               chain + (f"{cls}.{callee.name}",),
                               methods, seen, out)
            elif isinstance(node.func, ast.Name):
                callee = methods.get("", {}).get(node.func.id)
                if callee is not None:
                    self._scan(ctx, callee, None,
                               chain + (callee.name,), methods, seen,
                               out)


# ---------------------------------------------------------------------------
# 3. atomic-write
# ---------------------------------------------------------------------------
_EXEMPT_FILES = ("mxnet_tpu/base.py", "mxnet_tpu/checkpoint/layout.py")
_WRITE_CALLS = {"np.savez", "_np.savez", "np.savez_compressed",
                "_np.savez_compressed", "np.save", "_np.save",
                "json.dump", "_json.dump"}


class AtomicWriteChecker:
    """Persistent files must be written crash-atomically: via
    ``base.atomic_write``, ``checkpoint/layout.py``, or the
    tmp-then-``os.replace`` idiom in the same function (the PR 5 review
    found five writers that could leave torn files; this pins the fix).

    Flags ``open(path, 'w'/'wb'/'a')``, ``np.savez``, ``json.dump`` in
    any other context.  A function that also calls ``os.replace`` (or
    ``atomic_write``) is using the idiom and passes.
    """

    name = "atomic-write"

    def check_file(self, ctx: FileCtx) -> List[Finding]:
        if ctx.relpath.endswith(_EXEMPT_FILES):
            return []
        # map each function to whether it uses the atomic idiom
        out: List[Finding] = []
        funcs = [n for n in ast.walk(ctx.tree)
                 if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        covered: List[Tuple[int, int, bool]] = []
        for fn in funcs:
            atomic = False
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    cn = _call_name(node.func)
                    if cn in ("os.replace", "os.rename") or \
                            cn.split(".")[-1] == "atomic_write":
                        atomic = True
                        break
            covered.append((fn.lineno,
                            getattr(fn, "end_lineno", fn.lineno), atomic))

        def in_atomic_fn(line: int) -> bool:
            # innermost enclosing function wins
            best = None
            for lo, hi, atomic in covered:
                if lo <= line <= hi and \
                        (best is None or lo > best[0]):
                    best = (lo, atomic)
            return best[1] if best else False

        # names bound to in-memory buffers: np.save(buf)/json.dump(.., buf)
        # into a BytesIO/StringIO is not a persistent write
        membuf: set = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Call):
                vn = _call_name(node.value.func)
                if vn.split(".")[-1] in ("BytesIO", "StringIO"):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            membuf.add(t.id)

        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            cn = _call_name(node.func)
            mode = None
            if cn == "open" or cn.endswith(".open") and cn != "os.open":
                mode = "r"
                if len(node.args) >= 2:
                    mode = _const_str(node.args[1]) or ""
                for kw in node.keywords:
                    if kw.arg == "mode":
                        mode = _const_str(kw.value) or ""
                base = mode.replace("b", "").replace("t", "") \
                           .replace("+", "")
                if base not in ("w", "a", "x"):
                    continue
            elif cn not in _WRITE_CALLS:
                continue
            else:
                # np.save(buf, ...) / json.dump(obj, buf): in-memory
                # targets are exempt (position of the file arg differs
                # by callee; any BytesIO/StringIO name among the args
                # qualifies)
                if any(isinstance(a, ast.Name) and a.id in membuf
                       for a in node.args):
                    continue
            if in_atomic_fn(node.lineno):
                continue
            what = f"open(..., '{mode}')" if mode else f"{cn}(...)"
            out.append(ctx.finding(
                self.name, node,
                f"{what} writes a persistent file non-atomically — a "
                f"crash mid-write leaves a torn file.  Use "
                f"base.atomic_write / checkpoint.layout, or write to a "
                f"same-dir tmp and os.replace"))
        return out


# ---------------------------------------------------------------------------
# 4. env-sync
# ---------------------------------------------------------------------------
# roots searched for the docs→code direction: variables honored outside
# the python package (native runtime, harness scripts) or read through
# helpers the AST pass can't follow still count as read.  The package
# itself is included so a PARTIAL scan (one file) never turns every
# documented variable into a "stale row".  Paths are repo-relative.
_ENV_EXTRA_ROOTS = ("mxnet_tpu", "src", "tools", "chip_smoke.py",
                    "__graft_entry__.py", "tests", "tests_tpu", "example")
_ENV_DOC = os.path.join("docs", "env_var.md")


class EnvVarSyncChecker:
    """Every ``MXNET_*`` / ``MXT_*`` variable the package reads must be
    documented in docs/env_var.md, and every documented variable must
    be read somewhere (package, native runtime, or harness) — the PR
    1-6 reviews each found knobs that shipped undocumented.

    Reads are detected as ``os.environ.get/[]/setdefault``,
    ``os.getenv`` and ``base.getenv`` calls with a literal name.
    """

    name = "env-sync"

    def __init__(self, doc_path: Optional[str] = None,
                 extra_roots: Sequence[str] = _ENV_EXTRA_ROOTS):
        self.doc_path = doc_path or os.path.join(REPO_ROOT, _ENV_DOC)
        self.extra_roots = extra_roots
        self._reads: List[Tuple[str, FileCtx, ast.AST]] = []
        self._indirect: Set[str] = set()

    def check_file(self, ctx: FileCtx) -> List[Finding]:
        for node in ast.walk(ctx.tree):
            name = self._read_name(node)
            if name and _ENV_RE.match(name):
                self._reads.append((name, ctx, node))
            elif isinstance(node, ast.Call):
                # indirection reads: a literal env name handed to a
                # helper (parse_bucket_env("MXNET_SERVE_BUCKETS")).
                # Counts for the docs→code direction only — the
                # code→docs direction stays strict on direct reads.
                for a in node.args:
                    s = _const_str(a)
                    if s and _ENV_RE.match(s):
                        self._indirect.add(s)
        return []

    @staticmethod
    def _read_name(node) -> Optional[str]:
        if isinstance(node, ast.Call):
            cn = _call_name(node.func)
            if cn in ("os.environ.get", "environ.get", "os.getenv",
                      "getenv", "_base.getenv", "base.getenv",
                      "os.environ.setdefault", "environ.setdefault") \
                    and node.args:
                return _const_str(node.args[0])
        if isinstance(node, ast.Subscript):
            base = _call_name(node.value)
            if base in ("os.environ", "environ"):
                sl = node.slice
                if isinstance(sl, ast.Index):  # py<3.9 compat shape
                    sl = sl.value
                return _const_str(sl)
        return None

    def _doc_tokens(self) -> Set[str]:
        try:
            with open(self.doc_path, encoding="utf-8") as f:
                return set(_ENV_DOC_RE.findall(f.read()))
        except OSError:
            return set()

    def finalize(self) -> List[Finding]:
        documented = self._doc_tokens()
        out: List[Finding] = []
        read_names: Set[str] = set()
        doc_rel = os.path.relpath(self.doc_path, REPO_ROOT) \
            .replace(os.sep, "/")
        reported: Set[str] = set()
        for name, ctx, node in self._reads:
            read_names.add(name)
            if name in documented or name in reported:
                continue   # one finding per variable, at its first read
            reported.add(name)
            out.append(ctx.finding(
                self.name, node,
                f"env var '{name}' is read here but not documented in "
                f"{doc_rel} — add a row (name, default, meaning)"))
        # docs -> code: documented vars nobody reads anywhere
        undocumented_side = documented - read_names - self._indirect
        if undocumented_side:
            extra_text = self._extra_corpus()
            for name in sorted(undocumented_side):
                if name in extra_text:
                    continue
                out.append(Finding(
                    rule=self.name, path=doc_rel, line=1, col=0,
                    symbol=name,
                    message=f"env var '{name}' is documented in "
                            f"{doc_rel} but never read by the package, "
                            f"native runtime, or harness — stale row?"))
        return out

    def _extra_corpus(self) -> str:
        chunks: List[str] = []
        for root in self.extra_roots:
            p = os.path.join(REPO_ROOT, root)
            if os.path.isfile(p):
                try:
                    with open(p, encoding="utf-8",
                              errors="ignore") as f:
                        chunks.append(f.read())
                except OSError:
                    pass
                continue
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = [d for d in dirnames
                               if d not in ("__pycache__", ".git")]
                for fname in filenames:
                    if not fname.endswith((".py", ".cc", ".h", ".sh")):
                        continue
                    try:
                        with open(os.path.join(dirpath, fname),
                                  encoding="utf-8",
                                  errors="ignore") as f:
                            chunks.append(f.read())
                    except OSError:
                        pass
        return "\n".join(chunks)


# ---------------------------------------------------------------------------
# 5. metrics-hygiene
# ---------------------------------------------------------------------------
class MetricsHygieneChecker:
    """Metric names, label VALUES, and flight-recorder phase names must
    come from bounded sets — an f-string / %-format / .format() value
    is unbounded cardinality (the PR 6 per-tenant series leak: every
    distinct string becomes a forever-живая time series in the registry
    and the scrape; ISSUE 8 extends the same rule to ``phase_span``
    names, each of which is a forever-entry in ``flight.summary()`` and
    an EWMA slot in the slow-phase watchdog).

    Flags dynamic strings passed as label kwargs to ``.inc/.set/.dec``
    on ALL-CAPS metric objects, non-literal metric names in
    ``Counter/Gauge/Histogram`` constructions, and dynamically built
    phase names passed to ``phase_span(...)``.  ``type(e).__name__``
    and plain variables are allowed — bounded sets routed through a
    variable are the normal idiom; string BUILDING at the call site is
    the defect.
    """

    name = "metrics-hygiene"

    @staticmethod
    def _is_metric_recv(node: ast.Attribute) -> bool:
        v = node.value
        last = v.attr if isinstance(v, ast.Attribute) else \
            v.id if isinstance(v, ast.Name) else ""
        return bool(last) and last == last.upper() and \
            any(c.isalpha() for c in last)

    @staticmethod
    def _dynamic_str(node) -> Optional[str]:
        if isinstance(node, ast.JoinedStr):
            return "f-string"
        if isinstance(node, ast.BinOp) and isinstance(
                node.op, (ast.Add, ast.Mod)):
            for side in (node.left, node.right):
                if _const_str(side) is not None or \
                        isinstance(side, ast.JoinedStr):
                    return "string concatenation/%-format"
        if isinstance(node, ast.Call):
            cn = _call_name(node.func)
            if cn.endswith(".format"):
                return ".format()"
            if cn == "str" and node.args and not isinstance(
                    node.args[0], ast.Constant):
                return "str(<expr>)"
        return None

    def check_file(self, ctx: FileCtx) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            # label values on metric mutators
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr in ("inc", "set", "dec") and \
                    self._is_metric_recv(node.func):
                for kw in node.keywords:
                    if kw.arg is None:
                        continue
                    why = self._dynamic_str(kw.value)
                    if why:
                        out.append(ctx.finding(
                            self.name, kw.value,
                            f"label '{kw.arg}' gets a dynamically built "
                            f"value ({why}) — label values must come "
                            f"from a bounded set or the metric's "
                            f"cardinality is unbounded (fold/bound the "
                            f"value first; see Counter.fold_label)"))
            # metric names at construction
            cn = _call_name(node.func)
            if cn.split(".")[-1] in ("Counter", "Gauge", "Histogram") \
                    and node.args:
                name_arg = node.args[0]
                if _const_str(name_arg) is None and \
                        self._dynamic_str(name_arg):
                    out.append(ctx.finding(
                        self.name, name_arg,
                        "metric name is dynamically built — names must "
                        "be literal so the registry and dashboards are "
                        "enumerable"))
            # flight-recorder phase names (ISSUE 8): phase_span("x"),
            # flight.record("x", ...) — every distinct name is an
            # unbounded entry in flight.summary() + a watchdog EWMA
            # slot.  `phase_span` (and the primitive's other names,
            # `span` / `trace_span`) match under ANY receiver
            # (x.phase_span / profiler.phase_span / bare: only a built
            # string argument is flagged, so re.Match.span() is safe);
            # `record` is too generic, so it stays allowlisted to
            # flight-ish bases (other aliases escape — conservative by
            # design, a miss is recoverable)
            last = cn.split(".")[-1]
            if (last in ("phase_span", "trace_span", "span")
                    or (last == "record"
                        and cn.split(".")[0] in ("record", "flight",
                                                 "_flight", "fl"))) and \
                    node.args:
                name_arg = node.args[0]
                why = self._dynamic_str(name_arg)
                if why:
                    out.append(ctx.finding(
                        self.name, name_arg,
                        f"flight-recorder phase name is dynamically "
                        f"built ({why}) — phase names must come from a "
                        f"bounded literal set (unbounded phase "
                        f"cardinality grows flight.summary() and the "
                        f"watchdog EWMA table forever; put the varying "
                        f"part in labels=... instead)"))
            # program-introspection names (ISSUE 13): note_program /
            # note_jit program names and named_scope / layer_scope
            # layer names are forever-entries in the program registry
            # and the known-scope set — the PR 6/PR 8 cardinality
            # class.  `named_scope`/`layer_scope`/`note_program`/
            # `note_jit` are distinctive enough to match under ANY
            # receiver; a varying-but-bounded qualifier belongs in
            # note_program's label= (which is checked too — pass a
            # bounded helper's result like bucket_label, never build
            # the string at the call site).
            if last in ("note_program", "note_jit", "named_scope",
                        "layer_scope") and node.args:
                name_arg = node.args[0]
                why = self._dynamic_str(name_arg)
                if why:
                    out.append(ctx.finding(
                        self.name, name_arg,
                        f"program/layer name is dynamically built "
                        f"({why}) — note_program/named_scope names must "
                        f"come from a bounded set (each distinct name "
                        f"is a forever-entry in the program registry / "
                        f"known-scope table; use note_program's label= "
                        f"with a bounded helper for the varying part)"))
                if last in ("note_program", "note_jit"):
                    for kw in node.keywords:
                        if kw.arg == "label":
                            why = self._dynamic_str(kw.value)
                            if why:
                                out.append(ctx.finding(
                                    self.name, kw.value,
                                    f"note_program label is dynamically "
                                    f"built ({why}) — labels must come "
                                    f"from a bounded set (e.g. the "
                                    f"bucket lattice via bucket_label)"))
            # run-journal / goodput-ledger names (ISSUE 16): every
            # distinct journal.emit event name is a grep key operators
            # and the offline reporter enumerate, and every
            # goodput.attribute reason is a row in the badput class list
            # + a mxnet_badput_seconds_total label — the same
            # unbounded-cardinality class as phase names.  `emit` and
            # `attribute` are too generic for any-receiver matching,
            # so they stay allowlisted to journal-/goodput-ish bases
            # (the same conservative posture as `record` above).
            if ((last == "emit"
                 and cn.split(".")[0] in ("journal", "_journal", "jr"))
                or (last == "attribute"
                    and cn.split(".")[0] in ("goodput", "_goodput",
                                             "gp"))) and node.args:
                name_arg = node.args[0]
                why = self._dynamic_str(name_arg)
                if why:
                    out.append(ctx.finding(
                        self.name, name_arg,
                        f"journal event / badput reason is dynamically "
                        f"built ({why}) — event names and goodput "
                        f"classes must come from a bounded literal set "
                        f"(each distinct name is a forever grep key in "
                        f"the run journal and a "
                        f"mxnet_badput_seconds_total label; put the "
                        f"varying part in the entry's fields instead)"))
        return out


class MemoryHygieneChecker:
    """Device-array creation must stay attributable (ISSUE 9): a
    ``jax.device_put`` whose result the HBM ledger can never see is a
    buffer the OOM post-mortem reports as untagged — the exact
    dark-bytes class the ledger exists to eliminate.

    A ``device_put`` call site passes when any of:

      * its result feeds an ``NDArray(...)`` construction in the same
        expression — NDArray.__init__ ledger-registers the wrapper;
      * it sits lexically inside a ``with memory_scope("tag")`` block
        (any receiver: ``memory_scope`` / ``_mem.memory_scope``);
      * its RESULT flows into a ledger call in the same function: the
        name the device_put is assigned to is later an argument to
        ``register``/``register_nd``/``register_host``/
        ``note_compiled``/``._set_data``/``NDArray(...)`` — the
        "ledger-registered helper" idiom (predictor ``_to_dev``).
        Per-VALUE on purpose: a function that registers one buffer
        does not whitelist its other device_puts (an unrelated
        ``_set_data`` elsewhere in the function must not hide a
        retained, never-registered copy);
      * the file IS the ledger (``observability/``).

    Transient device→device redistribution (mesh placement in
    ``parallel/``, eager sp-op staging) carries justified inline
    suppressions — same policy as every other rule.
    """

    name = "memory-hygiene"

    _REGISTER_FNS = ("register", "register_nd", "register_host",
                     "note_compiled", "_set_data")

    @staticmethod
    def _last_name(func) -> str:
        """Terminal name of a call target, tolerant of subscripted
        receivers (``self.arg_dict[k]._set_data`` -> ``_set_data``,
        which ``_call_name`` gives up on)."""
        if isinstance(func, ast.Attribute):
            return func.attr
        return _call_name(func).split(".")[-1]

    @staticmethod
    def _is_device_put(node: ast.Call) -> bool:
        return MemoryHygieneChecker._last_name(node.func) == "device_put"

    @classmethod
    def _is_register_call(cls, func) -> bool:
        last = cls._last_name(func)
        if last not in cls._REGISTER_FNS:
            return False
        if last != "register":
            return True
        # a bare `.register` is everywhere (atexit, base.Registry, the
        # ops registry) — only a ledger receiver whitelists device_puts
        if isinstance(func, ast.Attribute):
            recv = _call_name(func.value).split(".")[-1]
            return recv in ("memory", "_memory", "_mem")
        return False

    @staticmethod
    def _in_memory_scope(node, parents) -> bool:
        cur = parents.get(node)
        while cur is not None:
            if isinstance(cur, ast.With):
                for item in cur.items:
                    ce = item.context_expr
                    if isinstance(ce, ast.Call) and _call_name(
                            ce.func).split(".")[-1] == "memory_scope":
                        return True
            cur = parents.get(cur)
        return False

    @classmethod
    def _feeds_registered_call(cls, node, parents) -> bool:
        """Nested (transitively) inside an NDArray(...) construction or
        a ledger-register/_set_data call's argument list."""
        cur = parents.get(node)
        while cur is not None:
            if isinstance(cur, ast.Call):
                if cls._last_name(cur.func).endswith("NDArray") or \
                        cls._is_register_call(cur.func):
                    return True
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return False
            cur = parents.get(cur)
        return False

    @classmethod
    def _result_reaches_register(cls, node, parents) -> bool:
        """Per-VALUE helper idiom: the name(s) the device_put's
        enclosing assignment binds are later an argument to a ledger
        register / ``_set_data`` / ``NDArray(...)`` call in the same
        function.  A value that escapes through a lambda or is never
        name-bound is opaque to this — suppress with justification."""
        stmt, fn, p = None, None, parents.get(node)
        while p is not None:
            if isinstance(p, ast.Lambda):
                return False
            if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fn = p
                break
            if stmt is None and isinstance(
                    p, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                stmt = p
            p = parents.get(p)
        if fn is None or stmt is None:
            return False
        targets = stmt.targets if isinstance(stmt, ast.Assign) \
            else [stmt.target]
        names = {sub.id for t in targets for sub in ast.walk(t)
                 if isinstance(sub, ast.Name)}
        if not names:
            return False
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call):
                continue
            if not (cls._is_register_call(sub.func)
                    or cls._last_name(sub.func).endswith("NDArray")):
                continue
            for arg in list(sub.args) + [kw.value for kw in sub.keywords]:
                if any(isinstance(n, ast.Name) and n.id in names
                       for n in ast.walk(arg)):
                    return True
        return False

    def check_file(self, ctx: FileCtx) -> List[Finding]:
        rel = ctx.relpath.replace("\\", "/")
        if "/observability/" in rel or rel.startswith("observability/"):
            return []
        out: List[Finding] = []
        parents: Dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(ctx.tree):
            for child in ast.iter_child_nodes(parent):
                parents[child] = parent
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call) or not self._is_device_put(node):
                continue
            if self._feeds_registered_call(node, parents):
                continue
            if self._in_memory_scope(node, parents):
                continue
            if self._result_reaches_register(node, parents):
                continue
            out.append(ctx.finding(
                self.name, node,
                "device_put outside a memory_scope / ledger-registered "
                "helper — the resulting buffer is invisible to the HBM "
                "ledger (untagged in memory.report() and the OOM "
                "post-mortem).  Wrap the creation in `with "
                "memory_scope(\"<tag>\")`, register the result "
                "(memory.register), or route it through NDArray"))
        return out


# ---------------------------------------------------------------------------
# 7. use-after-donate (ISSUE 15)
# ---------------------------------------------------------------------------
class UseAfterDonateChecker:
    """No read of a value previously passed through a donated jit call
    position (the PR 10 "the failed call may have consumed donated
    buffers" class, PR 12's donation-safe retry, PR 14's
    transient-device-copy double-count — jax reports these as an opaque
    "Array has been deleted" at some LATER access, far from the
    dispatch that killed the buffer).

    Runs the ``analysis.dataflow`` def-use pass per function: donating
    callables are recognized by construction (``jax.jit(...,
    donate_argnums=...)``), through same-file factories
    (``_build_fn``-style returns) and the ``lookup_program`` cache;
    rebinds / ``del`` / the supervisor-restore idioms
    (``*restore*`` / ``_load_init`` / ``set_states_bytes`` /
    ``readmit`` / ``_set_data``) kill the taint, as does the
    scatter-update restore idiom ``x = x.at[ids].set(...)`` (ISSUE 20:
    the whole-step embedding update rebinds the donated table to the
    functional scatter result in the same statement, so the RHS read
    is the aliasing flow, not a stale use).  The MXNET_SANITIZE
    runtime twin (``sanitizer.poison_donated``) raises a typed
    ``DonatedBufferError`` for whatever escapes the static net.
    """

    name = "use-after-donate"

    def check_file(self, ctx: FileCtx) -> List[Finding]:
        from . import dataflow as _df
        factories = _df.donating_factories(ctx.tree)
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            for use in _df.analyze_donation(node, factories):
                out.append(ctx.finding(
                    self.name, use.node,
                    f"'{use.name}' was passed through a donated "
                    f"argument of {use.callee}(...) at line "
                    f"{use.donated_line} — its buffer belongs to XLA "
                    f"now and this read sees a deleted array.  Rebind "
                    f"the name from the program's outputs, or restore "
                    f"from host copies before reusing it"))
        return out


# ---------------------------------------------------------------------------
# 8. retrace-hazard (ISSUE 15)
# ---------------------------------------------------------------------------
#: files allowed to construct jit programs — the compile chokepoints
#: program introspection instruments (executor, CachedOp, FusedUpdater,
#: whole-step, serving) plus the op/kernel registries whose jits are
#: module-lifetime singletons.  Everything else building a program is a
#: retrace hazard until reviewed (suppress/baseline with justification).
_JIT_CHOKEPOINTS = (
    "mxnet_tpu/executor.py",
    "mxnet_tpu/gluon/block.py",
    "mxnet_tpu/gluon/wholestep.py",
    # the scanned K-step superstep: same chokepoint discipline as the
    # whole step (programs cached via FusedUpdater.lookup_program keyed
    # on (policy, opt, K, ...), captured via introspect.note_jit)
    "mxnet_tpu/autotune/superstep.py",
    "mxnet_tpu/gluon/parameter.py",
    "mxnet_tpu/optimizer.py",
    "mxnet_tpu/serving/predictor.py",
    # continuous-batching decode: ONE module-lifetime jit closure per
    # engine, AOT-compiled per (slots, pages) lattice key in
    # precompile() and captured via note_program("decode_step")
    "mxnet_tpu/serving/decode.py",
    "mxnet_tpu/predictor.py",
    "mxnet_tpu/module/module.py",
    "mxnet_tpu/ops/registry.py",
    "mxnet_tpu/kvstore.py",
    "mxnet_tpu/parallel/collectives.py",
    "mxnet_tpu/parallel/data_parallel.py",
    "mxnet_tpu/symbol/symbol.py",
    "mxnet_tpu/symbol/graph.py",
    "mxnet_tpu/ndarray/sparse.py",
    "mxnet_tpu/image.py",
    "mxnet_tpu/rtc.py",
    "mxnet_tpu/export.py",
)


class RetraceHazardChecker:
    """Compiled-program identity must be stable (the
    FUSED_DTYPE_RECOMPILES class: a silent retrace/fallback re-pays XLA
    compilation on a hot path, or — worse — silently reuses a program
    traced for different semantics).  Its shapes:

      * ``jax.jit(f)(x)`` — jit-then-call in one expression builds a
        fresh program cache per evaluation: every call recompiles;
      * ``jax.jit`` inside a loop body — one program per iteration;
      * ``shard_map(...)`` that is not the argument of a ``jax.jit`` —
        bound eagerly it compiles its body's primitives one by one at
        every call;
      * ``jax.jit`` call sites outside the blessed compile chokepoints
        (``_JIT_CHOKEPOINTS``) — programs built where introspection /
        dispatch-count gates can't see them;
      * unstable/unhashable values in a dispatch-stability cache key:
        list/set/dict displays (unhashable — a TypeError at best) and
        ``id(...)`` (a recycled address aliases a NEW object onto a
        dead entry's program — the ``_PLAN_UID`` incident) in any
        ``lookup_program(key, ...)`` argument or a local ``key``
        assignment feeding one.
    """

    name = "retrace-hazard"

    @staticmethod
    def _scope_of(node, parents):
        """Nearest enclosing function (or None = module scope) — cache
        keys resolve per-scope so an unrelated local named ``key`` in
        another function can never shadow a blessed one."""
        cur = parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                return cur
            cur = parents.get(cur)
        return None

    def check_file(self, ctx: FileCtx) -> List[Finding]:
        out: List[Finding] = []
        parents: Dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(ctx.tree):
            for child in ast.iter_child_nodes(parent):
                parents[child] = parent
        blessed = any(ctx.relpath.endswith(p) for p in _JIT_CHOKEPOINTS)
        # (scope, name) -> value expr, scoped to the enclosing function
        key_exprs: Dict[tuple, ast.AST] = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                scope = self._scope_of(node, parents)
                key_exprs[(scope, node.targets[0].id)] = node.value
            if not isinstance(node, ast.Call):
                continue
            cn = _call_name(node.func)
            if cn in ("jax.jit", "_jax.jit"):
                if not blessed:
                    out.append(ctx.finding(
                        self.name, node,
                        "jax.jit call site outside the blessed compile "
                        "chokepoints — programs built here escape "
                        "introspection capture and the dispatch-count "
                        "gates.  Route through an existing chokepoint "
                        "(executor / CachedOp / FusedUpdater / "
                        "whole-step / serving), or suppress with the "
                        "caching story written down"))
                inner = parents.get(node)
                if isinstance(inner, ast.Call) and inner.func is node:
                    out.append(ctx.finding(
                        self.name, node,
                        "jax.jit(f)(...) — jit-then-call in one "
                        "expression builds a fresh program cache per "
                        "evaluation, so EVERY call recompiles.  Bind "
                        "the jitted callable once and reuse it"))
                cur = parents.get(node)
                while cur is not None:
                    if isinstance(cur, (ast.FunctionDef,
                                        ast.AsyncFunctionDef,
                                        ast.Lambda)):
                        break
                    if isinstance(cur, (ast.For, ast.While)):
                        out.append(ctx.finding(
                            self.name, node,
                            "jax.jit constructed inside a loop — one "
                            "fresh program (and XLA compile) per "
                            "iteration.  Hoist the jit out of the "
                            "loop"))
                        break
                    cur = parents.get(cur)
            elif cn.split(".")[-1] == "shard_map":
                outer = parents.get(node)
                if not (isinstance(outer, ast.Call) and node in outer.args
                        and _call_name(outer.func) in ("jax.jit",
                                                       "_jax.jit")):
                    out.append(ctx.finding(
                        self.name, node,
                        "bare shard_map(...) — bound on concrete arrays "
                        "it runs its body primitive by primitive and "
                        "compiles each one anew at EVERY call (the "
                        "sequence-parallel KV decode: 2,383 compiles of "
                        "105 signatures for 11 positions).  Build "
                        "jax.jit(shard_map(...)) once per (mesh, specs) "
                        "and reuse it"))
            elif _call_name(node.func).split(".")[-1] == \
                    "lookup_program" and node.args:
                key = node.args[0]
                if isinstance(key, ast.Name):
                    scope = self._scope_of(node, parents)
                    key = key_exprs.get((scope, key.id), key)
                out.extend(self._check_key(ctx, key))
        return out

    def _check_key(self, ctx: FileCtx, key) -> List[Finding]:
        out: List[Finding] = []
        # displays/comprehensions immediately coerced hashable —
        # tuple(<genexp>) / frozenset([...]) — are the NORMAL key idiom
        coerced: Set[ast.AST] = set()
        for sub in ast.walk(key):
            if isinstance(sub, ast.Call) and _call_name(sub.func) in (
                    "tuple", "frozenset") and sub.args:
                coerced.add(sub.args[0])
        for sub in ast.walk(key):
            if sub in coerced:
                continue
            if isinstance(sub, (ast.List, ast.Set, ast.Dict,
                                ast.ListComp, ast.SetComp, ast.DictComp,
                                ast.GeneratorExp)):
                out.append(ctx.finding(
                    self.name, sub,
                    "unhashable value (list/set/dict display) inside a "
                    "program cache key — the dispatch-stability lookup "
                    "raises TypeError or, tuple()-coerced elsewhere, "
                    "drifts.  Use tuples of hashables"))
            elif isinstance(sub, ast.Call) and \
                    _call_name(sub.func) == "id":
                out.append(ctx.finding(
                    self.name, sub,
                    "id(...) inside a program cache key — a recycled "
                    "address aliases a NEW object onto a dead entry's "
                    "compiled program (the _PLAN_UID incident).  Use a "
                    "process-unique counter stamped on the object"))
        return out


# ---------------------------------------------------------------------------
# 9. gate-hygiene (ISSUE 15)
# ---------------------------------------------------------------------------
class GateHygieneChecker:
    """Every documented ``MXNET_*=0`` kill-switch must reduce its hooks
    to ONE module-global boolean test before any other work — the
    overhead contract PRs 1 (metrics), 8 (flight), 9 (memory ledger),
    12 (supervise) and 13 (introspect) each re-promised in prose; this
    rule machine-checks it.

    A gate is a module-level ``ENABLED = getenv("MXNET_...", ...)``.
    Two violation shapes:

      * **buried guard** — a function whose body contains the
        early-return guard (``if not ENABLED: return``) anywhere but
        as its first statement, with effectful work (calls, control
        flow) before it: the disabled path no longer costs one boolean
        test;
      * **per-call env re-read** — a function body re-reading the
        gate's env var through ``getenv``/``os.environ`` instead of
        testing the module global: an env lookup + string parse per
        call on a path the contract says costs one flag test (and a
        mid-run ``export`` silently half-toggles the subsystem —
        enable()/disable() and the global stay authoritative).
    """

    name = "gate-hygiene"

    def __init__(self):
        # env var -> (module relpath) for every gate seen this run
        self._gates: Dict[str, str] = {}
        # (relpath, lineno, col, symbol-less env, suppressed) of
        # in-function getenv reads, resolved in finalize once every
        # module's gates are known.  Primitives only — holding the
        # FileCtx here would pin every swept file's source + AST in
        # memory for the whole run
        self._fn_reads: List[Tuple[str, int, int, str, bool]] = []

    @staticmethod
    def _gate_env(node) -> Optional[str]:
        """Env name when ``node`` is ``ENABLED = getenv("MXNET_X", ..)``
        (bool()-wrapped and AnnAssign forms included)."""
        if isinstance(node, ast.Assign):
            targets = node.targets
            value = node.value
        elif isinstance(node, ast.AnnAssign):
            targets = [node.target]
            value = node.value
        else:
            return None
        if not any(isinstance(t, ast.Name) and t.id == "ENABLED"
                   for t in targets):
            return None
        if isinstance(value, ast.Call) and \
                _call_name(value.func) == "bool" and value.args:
            value = value.args[0]
        if isinstance(value, ast.Call) and \
                _call_name(value.func).split(".")[-1] in (
                    "getenv", "get") and value.args:
            name = _const_str(value.args[0])
            if name and _ENV_RE.match(name):
                return name
        return None

    @staticmethod
    def _is_gate_guard(stmt, gate_names: Set[str]) -> bool:
        """``if not ENABLED: return/yield/pass`` (possibly
        ``not ENABLED or ...``) at statement level."""
        if not isinstance(stmt, ast.If):
            return False
        test = stmt.test
        candidates = [test]
        if isinstance(test, ast.BoolOp) and isinstance(test.op, ast.Or):
            candidates = list(test.values)
        hit = False
        for c in candidates:
            if isinstance(c, ast.UnaryOp) and isinstance(c.op, ast.Not):
                inner = c.operand
                key = inner.attr if isinstance(inner, ast.Attribute) \
                    else inner.id if isinstance(inner, ast.Name) else ""
                if key in gate_names:
                    hit = True
        if not hit:
            return False
        return all(isinstance(s, (ast.Return, ast.Pass, ast.Expr))
                   for s in stmt.body)

    @staticmethod
    def _effectful(stmt) -> bool:
        """Work the disabled path would pay before reaching the guard."""
        if isinstance(stmt, (ast.With, ast.For, ast.While, ast.Try)):
            return True
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call):
                return True
        return False

    def check_file(self, ctx: FileCtx) -> List[Finding]:
        gate_envs: Dict[str, str] = {}
        for stmt in ctx.tree.body:
            env = self._gate_env(stmt)
            if env:
                gate_envs[env] = "ENABLED"
                self._gates[env] = ctx.relpath
        out: List[Finding] = []
        gate_names = {"ENABLED"}
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # record in-function env re-reads for finalize
                for sub in ast.walk(node):
                    name = EnvVarSyncChecker._read_name(sub)
                    if name:
                        ln = getattr(sub, "lineno", 0)
                        self._fn_reads.append(
                            (ctx.relpath, ln,
                             getattr(sub, "col_offset", 0), name,
                             ctx.suppressed(self.name, ln)))
                if not gate_envs:
                    continue
                body = node.body
                start = 0
                if body and isinstance(body[0], ast.Expr) and \
                        isinstance(body[0].value, ast.Constant):
                    start = 1  # docstring
                for i, stmt in enumerate(body):
                    if not self._is_gate_guard(stmt, gate_names):
                        continue
                    if i == start:
                        break
                    if any(self._effectful(p) for p in body[start:i]):
                        out.append(ctx.finding(
                            self.name, stmt,
                            f"kill-switch guard 'if not ENABLED' is "
                            f"buried behind other work in "
                            f"'{node.name}' — the disabled path must "
                            f"cost ONE module-global boolean test "
                            f"(move the guard to the first "
                            f"statement)"))
                    break
        return out

    def finalize(self) -> List[Finding]:
        out: List[Finding] = []
        reported: Set[Tuple[str, int]] = set()
        for relpath, line, col, env, suppressed in self._fn_reads:
            gate_mod = self._gates.get(env)
            if gate_mod is None or suppressed:
                continue
            where = (relpath, line)
            if where in reported:
                continue
            reported.add(where)
            out.append(Finding(
                rule=self.name, path=relpath, line=line, col=col,
                message=f"'{env}' is re-read from the environment "
                        f"inside a function, but it is the "
                        f"module-global kill-switch gate of "
                        f"{gate_mod} — test that module's ENABLED "
                        f"flag instead (one boolean test; env is "
                        f"parsed once at import)"))
        return out


# ---------------------------------------------------------------------------
def registry() -> Dict[str, type]:
    return {
        ThreadSafetyChecker.name: ThreadSafetyChecker,
        HostSyncChecker.name: HostSyncChecker,
        AtomicWriteChecker.name: AtomicWriteChecker,
        EnvVarSyncChecker.name: EnvVarSyncChecker,
        MetricsHygieneChecker.name: MetricsHygieneChecker,
        MemoryHygieneChecker.name: MemoryHygieneChecker,
        UseAfterDonateChecker.name: UseAfterDonateChecker,
        RetraceHazardChecker.name: RetraceHazardChecker,
        GateHygieneChecker.name: GateHygieneChecker,
    }


ALL_RULES = tuple(registry())
