"""Static lock-discipline pass (ISSUE 9 tentpole, rule family ``lock-*``).

Builds the whole-repo lock-acquisition graph from the AST and checks it
three ways:

* ``lock-hierarchy`` — an acquisition edge (lock A held while blocking-
  acquiring lock B) whose declared ranks are not strictly ascending
  (lockdep.RANKS). This is the static mirror of the runtime sanitizer:
  it sees paths no test happens to thread through.
* ``lock-cycle`` — a cycle among UNRANKED locks (plain
  ``threading.Lock`` attributes outside the named hierarchy): A→B and
  B→A edges mean two call paths disagree about order — the ABBA
  precondition.
* ``lock-blocking`` — a blocking operation (device transfer, file I/O,
  sleep, subprocess, bus broadcast, queue/thread waits) performed while
  a BOOKKEEPING lock is held. Locks marked ``coarse`` in the hierarchy
  (the engine's paged lock, the native build lock) serialize device
  work by design and are exempt; everything else holding up a blocking
  call stalls every thread contending for pure bookkeeping — exactly
  the PR 7 async-spill bug class.

How lock identity is resolved (repo-native, heuristic on purpose):

* ``self.<attr> = named_lock("name"[, rlock=...])`` — the name IS the
  identity; rank/coarse come from the declared hierarchy.
* ``self.<attr> = threading.Lock()/RLock()`` — identity
  ``ClassName.<attr>``; unranked (participates in cycles only).
* Acquisitions are ``with <expr>`` blocks and ``<expr>.acquire()``
  calls where ``<expr>`` resolves to a known lock: ``self._lock``,
  a local aliased from an attribute (``st = self.sessions`` →
  ``st.lock``), or a constructor-typed attribute chain
  (``self.sessions = SessionStore(...)`` → ``self.sessions.lock``).
  ``acquire(blocking=False)`` try-acquires are exempt from hierarchy
  checks, same as at runtime.
* Call edges: ``self.m()``, ``<typed-var>.m()``, module functions, and
  cross-module ``module.fn()`` within the package, followed to a
  bounded depth so a blocking call two frames below an acquisition is
  still attributed to it.

Suppression is inline only: ``# qlint: allow[lock-blocking] reason`` on
the blocking line or on the ``with`` line that takes the lock.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Optional

from quoracle_tpu.analysis import lockdep
from quoracle_tpu.analysis.common import Finding, SourceModule

MAX_CALL_DEPTH = 4

# Blocking-call patterns: dotted-suffix match against the rendered call
# target. Kept explicit and small — a curated list beats a clever one
# for a repo-native tool.
BLOCKING_SUFFIXES: dict = {
    "jax.device_get": "device transfer (host sync)",
    "jax.device_put": "device transfer",
    "jax.block_until_ready": "device sync",
    "block_until_ready": "device sync",
    "np.savez": "file I/O",
    "np.savez_compressed": "file I/O",
    "np.save": "file I/O",
    "np.load": "file I/O",
    "json.dump": "file I/O",
    "time.sleep": "sleep",
    "subprocess.run": "subprocess",
    "subprocess.check_call": "subprocess",
    "subprocess.check_output": "subprocess",
    "subprocess.Popen": "subprocess",
    "shutil.copyfile": "file I/O",
    "os.replace": "file I/O (rename)",
    "os.listdir": "directory scan",
    "os.utime": "file I/O",
}
# attribute-call NAMES that block regardless of receiver (method calls
# whose receiver type we can't resolve)
BLOCKING_METHOD_NAMES: dict = {
    "broadcast": "bus broadcast (runs subscriber handlers)",
    "device_get": "device transfer (host sync)",
    "device_put": "device transfer",
    "savez": "file I/O",
    "sleep": "sleep",
}
# .join()/.wait() block only on synchronization receivers — os.path.join
# and str.join must not match.
_WAITISH_RECEIVERS = ("thread", "queue", "_q", "proc", "event", "wake",
                      "stop", "future", "fut", "sem", "cond", "barrier")
# open() is only blocking-relevant when its result is written/read —
# treat any open() under a lock as I/O.
BLOCKING_BARE_NAMES: dict = {
    "open": "file I/O",
}
# Receiver names for which .get/.put are queue waits, not dict access.
QUEUEISH = ("queue", "_q", "spill_q", "_queue")

# Attribute types the constructor heuristic can't see (assigned from a
# parameter or attached after construction). Repo-native hints — the
# price of a resolver that needs no imports or type checker.
KNOWN_ATTR_TYPES: dict = {
    ("SessionStore", "tier"): "TierManager",
    ("SessionStore", "prefix_cache"): "RadixPrefixCache",
    ("TierManager", "store"): "SessionStore",
    ("TierManager", "disk"): "DiskPrefixStore",
    ("TierManager", "host"): "HostPageStore",
    ("ContinuousBatcher", "engine"): "GenerateEngine",
    ("GenerateEngine", "sessions"): "SessionStore",
    ("BatchedSpeculator", "target"): "GenerateEngine",
    ("BatchedSpeculator", "draft"): "GenerateEngine",
    ("RadixPrefixCache", "store"): "SessionStore",
    ("TierManager", "prefixd"): "PrefixdClient",
    ("PrefixdClient", "transport"): "Transport",
    ("FabricPeer", "handoff"): "KVHandoff",
}


def _dotted(node: ast.AST) -> Optional[str]:
    """Render a Name/Attribute chain as 'a.b.c' (None when dynamic)."""
    parts: list = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


@dataclasses.dataclass
class LockInfo:
    key: str                 # "name:<hier name>" or "attr:Class.attr"
    display: str             # what findings print
    rank: Optional[int]      # None = unranked
    coarse: bool
    reentrant: bool


@dataclasses.dataclass
class Acquisition:
    lock: LockInfo
    line: int
    blocking: bool           # False for acquire(blocking=False)


@dataclasses.dataclass
class FuncInfo:
    module: SourceModule
    qualname: str            # "Class.method" or "function"
    cls: Optional[str]
    node: ast.AST
    # direct (acquisition, body-statements) pairs and call sites are
    # derived lazily by the analyzer walk


class _ClassIndex:
    """Per-module class table: lock attributes + attribute types."""

    def __init__(self) -> None:
        self.locks: dict = {}        # (cls, attr) -> LockInfo
        self.attr_types: dict = {}   # (cls, attr) -> class name
        self.classes: dict = {}      # cls name -> {method name -> FuncInfo}
        self.functions: dict = {}    # module-level fn name -> FuncInfo
        self.class_module: dict = {}  # cls name -> module rel path


def _lock_from_assign(value: ast.AST, cls: Optional[str],
                      attr: str) -> Optional[LockInfo]:
    """LockInfo for `<target> = named_lock(...)/threading.Lock()` RHS."""
    if not isinstance(value, ast.Call):
        return None
    target = _dotted(value.func)
    if target is None:
        return None
    if target.endswith("named_lock"):
        if value.args and isinstance(value.args[0], ast.Constant) \
                and isinstance(value.args[0].value, str):
            name = value.args[0].value
            rlock = any(kw.arg == "rlock"
                        and isinstance(kw.value, ast.Constant)
                        and bool(kw.value.value)
                        for kw in value.keywords)
            return LockInfo(
                key=f"name:{name}", display=name,
                rank=lockdep.RANKS.get(name),
                coarse=name in lockdep.COARSE, reentrant=rlock)
        return None
    if target in ("threading.Lock", "threading.RLock"):
        owner = cls or "<module>"
        return LockInfo(
            key=f"attr:{owner}.{attr}", display=f"{owner}.{attr}",
            rank=None, coarse=False,
            reentrant=target.endswith("RLock"))
    return None


def build_index(modules: list) -> _ClassIndex:
    idx = _ClassIndex()
    for mod in modules:
        for node in mod.tree.body:
            if isinstance(node, ast.ClassDef):
                methods: dict = {}
                for sub in node.body:
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                        fi = FuncInfo(mod, f"{node.name}.{sub.name}",
                                      node.name, sub)
                        methods[sub.name] = fi
                        _scan_self_assigns(idx, node.name, sub)
                idx.classes[node.name] = methods
                idx.class_module[node.name] = mod.rel
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                idx.functions[node.name] = FuncInfo(
                    mod, node.name, None, node)
            elif isinstance(node, ast.Assign):
                # module-level lock: _build_lock = named_lock(...)
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        info = _lock_from_assign(node.value, None, tgt.id)
                        if info is not None:
                            idx.locks[("<module>:" + mod.rel, tgt.id)] = \
                                info
    return idx


def _scan_self_assigns(idx: _ClassIndex, cls: str, fn: ast.AST) -> None:
    """self.<attr> = named_lock/threading.Lock/KnownClass(...) sites."""
    for node in ast.walk(fn):
        if not isinstance(node, ast.Assign):
            continue
        for tgt in node.targets:
            if (isinstance(tgt, ast.Attribute)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id == "self"):
                info = _lock_from_assign(node.value, cls, tgt.attr)
                if info is not None:
                    idx.locks[(cls, tgt.attr)] = info
                elif isinstance(node.value, ast.Call):
                    ctor = _dotted(node.value.func)
                    if ctor is not None:
                        idx.attr_types[(cls, tgt.attr)] = \
                            ctor.rsplit(".", 1)[-1]


class _FunctionAnalysis:
    """Locks acquired + blocking calls + call sites of ONE function, each
    tagged with the acquisition stack active at that point."""

    def __init__(self) -> None:
        # (lock, line, blocking-acquire) of every direct acquisition,
        # with the locks held at that point (outermost first)
        self.acq_edges: list = []    # (held: tuple[LockInfo], acq, line, blocking)
        self.blocking: list = []     # (held: tuple[LockInfo], target, why, line)
        self.calls: list = []        # (held: tuple[LockInfo], callee_key, line)
        # summary for transitive propagation: what this function does
        # with NO locks held by its caller is still relevant — the
        # caller's held set prefixes ours.


class LockPass:
    def __init__(self, modules: list):
        self.modules = modules
        self.idx = build_index(modules)
        for (cls, attr), t in KNOWN_ATTR_TYPES.items():
            if cls in self.idx.classes and t in self.idx.classes:
                self.idx.attr_types.setdefault((cls, attr), t)
        self.analyses: dict = {}     # qualname key -> _FunctionAnalysis
        self.findings: list = []
        self._local_types_stack: list = []

    # -- lock expression resolution -------------------------------------

    def _resolve_lock(self, expr: ast.AST, fi: FuncInfo,
                      local_types: dict) -> Optional[LockInfo]:
        dotted = _dotted(expr)
        if dotted is None:
            return None
        parts = dotted.split(".")
        # module-level lock name
        if len(parts) == 1:
            return self.idx.locks.get(
                ("<module>:" + fi.module.rel, parts[0]))
        base, attr = parts[0], parts[-1]
        if len(parts) == 2:
            if base == "self" and fi.cls is not None:
                info = self.idx.locks.get((fi.cls, attr))
                if info is not None:
                    return info
                return None
            # typed local: st.lock where st: SessionStore
            t = local_types.get(base)
            if t is not None:
                return self.idx.locks.get((t, attr))
            return None
        if len(parts) == 3 and base == "self" and fi.cls is not None:
            # self.sessions.lock → type of self.sessions
            t = self.idx.attr_types.get((fi.cls, parts[1]))
            if t is not None:
                return self.idx.locks.get((t, attr))
        return None

    def _local_types(self, fi: FuncInfo) -> dict:
        """var name -> class name, from assignments + annotations."""
        types: dict = {}
        for node in ast.walk(fi.node):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                var = node.targets[0].id
                v = node.value
                if isinstance(v, ast.Call):
                    ctor = _dotted(v.func)
                    if ctor is not None:
                        cname = ctor.rsplit(".", 1)[-1]
                        if cname in self.idx.classes:
                            types[var] = cname
                elif isinstance(v, ast.Attribute):
                    d = _dotted(v)
                    if d is not None and d.startswith("self.") \
                            and fi.cls is not None:
                        t = self.idx.attr_types.get(
                            (fi.cls, d.split(".")[1]))
                        if t is not None:
                            types[var] = t
            elif isinstance(node, ast.arg) and node.annotation is not None:
                ann = node.annotation
                if isinstance(ann, ast.Constant) \
                        and isinstance(ann.value, str):
                    d = ann.value              # forward ref: a: "A"
                else:
                    d = _dotted(ann)
                if d is not None:
                    cname = d.strip("'\"").rsplit(".", 1)[-1]
                    if cname in self.idx.classes:
                        types[node.arg] = cname
        # well-known parameter conventions in this repo
        argnames = [a.arg for a in getattr(fi.node.args, "args", [])]
        for conv, cname in (("store", "SessionStore"),
                            ("st", "SessionStore"),
                            ("engine", "GenerateEngine"),
                            ("sess", "_Session")):
            if conv in argnames and conv not in types \
                    and cname in self.idx.classes:
                types[conv] = cname
        return types

    # -- per-function walk ----------------------------------------------

    def analyze_function(self, fi: FuncInfo) -> _FunctionAnalysis:
        key = f"{fi.module.rel}:{fi.qualname}"
        cached = self.analyses.get(key)
        if cached is not None:
            return cached
        fa = _FunctionAnalysis()
        self.analyses[key] = fa
        local_types = self._local_types(fi)
        body = getattr(fi.node, "body", [])
        self._walk(body, fi, local_types, fa, held=())
        return fa

    def _walk(self, stmts: list, fi: FuncInfo, local_types: dict,
              fa: _FunctionAnalysis, held: tuple) -> None:
        for stmt in stmts:
            self._walk_stmt(stmt, fi, local_types, fa, held)

    def _walk_stmt(self, stmt: ast.AST, fi: FuncInfo, local_types: dict,
                   fa: _FunctionAnalysis, held: tuple) -> None:
        if isinstance(stmt, ast.With):
            inner = held
            for item in stmt.items:
                info = self._resolve_lock(item.context_expr, fi,
                                          local_types)
                if info is not None:
                    fa.acq_edges.append((inner, info, stmt.lineno, True))
                    if not any(h.key == info.key for h in inner):
                        inner = inner + (info,)
                else:
                    # non-lock context manager: its constructor may
                    # itself block (``with np.load(path) as z:``)
                    for sub in ast.walk(item.context_expr):
                        self._visit_expr(sub, fi, local_types, fa, held)
            self._walk(stmt.body, fi, local_types, fa, inner)
            return
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # nested defs execute later; analyze with empty held set via
            # their own FuncInfo only if module-level — skip here.
            return
        # expression-level scan (calls, .acquire())
        for node in ast.walk(stmt) if not isinstance(
                stmt, (ast.If, ast.For, ast.While, ast.Try,
                       ast.AsyncFor, ast.AsyncWith)) else [stmt]:
            if isinstance(node, (ast.If, ast.While)):
                for sub in ast.walk(node.test):
                    self._visit_expr(sub, fi, local_types, fa, held)
                self._walk(node.body, fi, local_types, fa, held)
                self._walk(node.orelse, fi, local_types, fa, held)
                return
            if isinstance(node, (ast.For, ast.AsyncFor)):
                for sub in ast.walk(node.iter):
                    self._visit_expr(sub, fi, local_types, fa, held)
                self._walk(node.body, fi, local_types, fa, held)
                self._walk(node.orelse, fi, local_types, fa, held)
                return
            if isinstance(node, ast.Try):
                self._walk(node.body, fi, local_types, fa, held)
                for h in node.handlers:
                    self._walk(h.body, fi, local_types, fa, held)
                self._walk(node.orelse, fi, local_types, fa, held)
                self._walk(node.finalbody, fi, local_types, fa, held)
                return
            if isinstance(node, ast.AsyncWith):
                self._walk(node.body, fi, local_types, fa, held)
                return
            self._visit_expr(node, fi, local_types, fa, held)

    def _visit_expr(self, node: ast.AST, fi: FuncInfo, local_types: dict,
                    fa: _FunctionAnalysis, held: tuple) -> None:
        if not isinstance(node, ast.Call):
            return
        target = _dotted(node.func)
        if target is None:
            return
        parts = target.split(".")
        # .acquire() on a lock
        if parts[-1] == "acquire" and len(parts) > 1:
            lock_expr = node.func.value  # type: ignore[attr-defined]
            info = self._resolve_lock(lock_expr, fi, local_types)
            if info is not None:
                blocking = True
                for kw in node.keywords:
                    if kw.arg == "blocking" \
                            and isinstance(kw.value, ast.Constant) \
                            and kw.value.value is False:
                        blocking = False
                if node.args and isinstance(node.args[0], ast.Constant) \
                        and node.args[0].value is False:
                    blocking = False
                fa.acq_edges.append((held, info, node.lineno, blocking))
                return
        # blocking call? Recorded even with no lock held HERE — a caller
        # may hold one (the transitive propagation filters on the
        # combined held set).
        why = self._blocking_reason(target, parts)
        if why is not None:
            fa.blocking.append((held, target, why, node.lineno))
        # call edge for transitive propagation
        callee = self._callee_key(target, parts, fi, local_types)
        if callee is not None:
            fa.calls.append((held, callee, node.lineno))

    def _blocking_reason(self, target: str, parts: list) -> Optional[str]:
        for suffix, why in BLOCKING_SUFFIXES.items():
            if target == suffix or target.endswith("." + suffix):
                return why
        if len(parts) == 1:
            return BLOCKING_BARE_NAMES.get(parts[0])
        name = parts[-1]
        if name in BLOCKING_METHOD_NAMES:
            return BLOCKING_METHOD_NAMES[name]
        recv = parts[-2].lower()
        if name in ("join", "wait") and any(
                w in recv for w in _WAITISH_RECEIVERS):
            return "thread/queue wait"
        if name in ("get", "put") and any(
                q in recv for q in QUEUEISH):
            return "queue wait"
        return None

    def _callee_key(self, target: str, parts: list, fi: FuncInfo,
                    local_types: dict) -> Optional[tuple]:
        """(cls | None, method) for calls we can resolve in-repo."""
        name = parts[-1]
        if len(parts) == 1:
            if name in self.idx.functions:
                return (None, name)
            return None
        base = parts[0]
        if base == "self" and fi.cls is not None and len(parts) == 2:
            if name in self.idx.classes.get(fi.cls, ()):
                return (fi.cls, name)
            return None
        t = local_types.get(base)
        if t is not None and len(parts) == 2:
            if name in self.idx.classes.get(t, ()):
                return (t, name)
        if base == "self" and fi.cls is not None and len(parts) == 3:
            t = self.idx.attr_types.get((fi.cls, parts[1]))
            if t is not None and name in self.idx.classes.get(t, ()):
                return (t, name)
        return None

    def _func_for(self, key: tuple) -> Optional[FuncInfo]:
        cls, name = key
        if cls is None:
            return self.idx.functions.get(name)
        return self.idx.classes.get(cls, {}).get(name)

    # -- transitive effects ---------------------------------------------

    def _effects(self, fi: FuncInfo, depth: int,
                 seen: frozenset) -> tuple:
        """(acquires, blocking) this function performs with NO locks held
        by the caller, transitively: acquires = [(lock, line, blocking,
        via)], blocking = [(target, why, line, via)]. ``via`` is the
        call-path suffix for messages."""
        key = f"{fi.module.rel}:{fi.qualname}"
        if key in seen or depth > MAX_CALL_DEPTH:
            return ((), ())
        seen = seen | {key}
        fa = self.analyze_function(fi)
        acquires: list = []
        blocking: list = []
        for held, info, line, blk in fa.acq_edges:
            acquires.append((held, info, line, blk, fi))
        for held, target, why, line in fa.blocking:
            blocking.append((held, target, why, line, fi))
        for held, callee, line in fa.calls:
            sub = self._func_for(callee)
            if sub is None:
                continue
            sub_acq, sub_blk = self._effects(sub, depth + 1, seen)
            for h2, info, l2, blk, src in sub_acq:
                acquires.append((held + h2, info, l2, blk, src))
            for h2, target, why, l2, src in sub_blk:
                # propagate even lock-free callee blocking: an OUTER
                # frame may combine it with a held lock
                blocking.append((held + h2, target, why, l2, src))
        return (tuple(acquires), tuple(blocking))

    # -- the pass --------------------------------------------------------

    def run(self) -> list:
        edges: dict = {}          # (outer key, inner key) -> witness
        for mod in self.modules:
            for cls, methods in (
                    (c, m) for c, m in self.idx.classes.items()
                    if self.idx.class_module.get(c) == mod.rel):
                for fi in methods.values():
                    self._check_function(fi, edges)
            for fname, fi in self.idx.functions.items():
                if fi.module is mod:
                    self._check_function(fi, edges)
        self._check_cycles(edges)
        return self.findings

    def _check_function(self, fi: FuncInfo, edges: dict) -> None:
        acquires, blocking = self._effects(fi, 0, frozenset())
        mod = fi.module
        for held, info, line, blk, src in acquires:
            for h in held:
                if h.key == info.key:
                    continue          # re-entrant
                ekey = (h.key, info.key)
                if ekey not in edges:
                    edges[ekey] = (h, info, src, line)
                if not blk:
                    continue          # try-acquire: exempt (runtime rule)
                if h.rank is not None and info.rank is not None \
                        and h.rank >= info.rank:
                    f = Finding(
                        "lock-hierarchy", src.module.rel, line,
                        src.qualname,
                        f"acquires {info.display!r} (rank {info.rank}) "
                        f"while holding {h.display!r} (rank {h.rank}); "
                        f"declared order requires strictly descending "
                        f"the hierarchy")
                    if not src.module.allowed("lock-hierarchy", line):
                        self._add(f)
        for held, target, why, line, src in blocking:
            # only bookkeeping locks count; coarse locks exempt
            fine = [h for h in held if not h.coarse]
            if not fine:
                continue
            f = Finding(
                "lock-blocking", src.module.rel, line, src.qualname,
                f"{why}: {target}() while holding "
                f"{', '.join(repr(h.display) for h in fine)}")
            if not src.module.allowed("lock-blocking", line):
                self._add(f)

    def _check_cycles(self, edges: dict) -> None:
        """Cycle detection over UNRANKED lock keys (ranked locks are
        already linearized by lock-hierarchy)."""
        graph: dict = {}
        for (a, b), (ha, hb, src, line) in edges.items():
            if ha.rank is None or hb.rank is None:
                graph.setdefault(a, set()).add(b)
        # DFS cycle detection
        WHITE, GRAY, BLACK = 0, 1, 2
        color = {k: WHITE for k in graph}
        stack: list = []
        reported: set = set()

        def dfs(u: str) -> None:
            color[u] = GRAY
            stack.append(u)
            for v in graph.get(u, ()):
                if color.get(v, WHITE) == GRAY:
                    cyc = tuple(stack[stack.index(v):] + [v])
                    if frozenset(cyc) not in reported:
                        reported.add(frozenset(cyc))
                        ha, hb, src, line = edges[(u, v)]
                        self._add(Finding(
                            "lock-cycle", src.module.rel, line,
                            src.qualname,
                            "lock-order cycle: "
                            + " -> ".join(
                                k.split(":", 1)[1] for k in cyc)))
                elif color.get(v, WHITE) == WHITE and v in graph:
                    dfs(v)
            stack.pop()
            color[u] = BLACK

        for k in sorted(graph):
            if color[k] == WHITE:
                dfs(k)

    def _add(self, f: Finding) -> None:
        """Dedupe by site: one blocking call reached from N entry points
        is one finding (the held-set in the message is the first seen)."""
        key = (f.rule, f.path, f.line, f.symbol)
        if not hasattr(self, "_seen_sites"):
            self._seen_sites: set = set()
        if key in self._seen_sites:
            return
        self._seen_sites.add(key)
        self.findings.append(f)


def run(modules: list) -> list:
    """Entry point: findings for the lock-discipline pass."""
    return LockPass(modules).run()
