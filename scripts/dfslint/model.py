"""Phase 1 of the interprocedural analyzer: the whole-repo model.

The r08 rules are single-function AST visitors; three review rounds
each hand-caught a bug class they structurally cannot see (the r13
ManifestStore resurrection race, the r15 staging-buffer
recycle-while-in-flight aliasing, client/handler wire drift). This
module builds the facts those bug classes are *about*, once per run,
shared by every pass:

- a **module-qualified call graph** over the walked sources (imports,
  same-module calls, ``self.method`` calls, and ``self.attr.method``
  calls through constructor-/annotation-derived attribute types);
- an **execution-context inference**: every function is classified on
  the lattice ``{} ⊂ {loop} | {worker} ⊂ {loop, worker}`` — seeded
  from ``async def`` (loop), executor/thread dispatch sites
  (``asyncio.to_thread``, ``run_in_executor``, ``pool.submit``,
  ``Thread(target=…)`` → worker), loop-marshalled callbacks
  (``call_soon_threadsafe``, ``add_done_callback`` → loop), and
  executor *trampolines* (a function whose parameter reaches a
  dispatch site — ``AsyncChunkStore._run`` — seeds its call sites'
  callable arguments as worker entry points), then propagated along
  sync call edges to a fixed point;
- a **symbol table of ``self.<attr>`` accesses**: per (class, attr),
  every read/write with the set of lock-ish ``with`` guards held at
  the access — the facts DFS008's affinity-race check joins against
  the context classification;
- the set of functions that **return borrowed buffer views**
  (``memoryview``/``unpack_chunks``-derived), so DFS009 can follow a
  view through one call without type inference.

Everything here is a best-effort lexical approximation — unresolvable
calls simply contribute no edge, and an unknown context is the empty
set (which no rule fires on). That bias is deliberate: phase 2 rules
must only fire on facts the model actually established.
"""

from __future__ import annotations

import ast
import dataclasses
import re

from scripts.dfslint.core import Project, SourceFile, dotted, scope_nodes

LOOP = "loop"
WORKER = "worker"

# `with <expr>:` guards treated as locks. Wider than DFS003's _LOCKISH
# on purpose: the store layer names its ordering mutexes `_dir_mu` (a
# lock a shard directory, taken as `self._dir_mu[k]`) / `_delta_mu` /
# `_mu` and the model must see them as guards, not as unprotected
# accesses.
LOCKISH = re.compile(
    r"(lock|mutex|mtx|cond|sem(aphore)?|(^|_)mu$|(^|_)cv$)",
    re.IGNORECASE)

# executor dispatch shapes: (callable-position args, target= keyword)
_THREAD_SEED_ATTRS = frozenset({"submit"})
# callables marshalled BACK to the event loop from anywhere. NOT
# add_done_callback: on a concurrent.futures future the callback runs
# on the POOL WORKER thread (store/aio.py uses exactly those), so
# seeding it loop would invert DFS003/DFS008's analysis — unknown
# context is the honest classification there.
_LOOP_CALLBACK_ATTRS = frozenset({"call_soon_threadsafe", "call_soon"})

# mutating method names: a call `self.x.append(...)` is a WRITE to the
# shared structure behind `self.x`, not a read
_MUTATOR_ATTRS = frozenset({
    "add", "append", "appendleft", "extend", "insert", "remove",
    "discard", "pop", "popleft", "popitem", "clear", "update",
    "setdefault", "put_nowait", "push", "rotate",
})

# calls that return a borrowed view of an existing buffer
_VIEW_CALLS = frozenset({"memoryview", "unpack_chunks"})
_VIEW_METHODS = frozenset({"toreadonly", "cast", "getbuffer"})


@dataclasses.dataclass
class FuncInfo:
    """One function/method/lambda in the walked project."""

    uid: str                 # "<rel>:<qualname>:<lineno>" — unique
    src: SourceFile
    node: ast.AST            # FunctionDef | AsyncFunctionDef | Lambda
    name: str
    cls: str | None          # nearest enclosing ClassDef name
    is_async: bool
    params: tuple[str, ...]
    ctx: set = dataclasses.field(default_factory=set)
    callees: set = dataclasses.field(default_factory=set)  # uids
    returns_view: bool = False

    @property
    def qual(self) -> str:
        return f"{self.cls}.{self.name}" if self.cls else self.name


@dataclasses.dataclass(frozen=True)
class AttrAccess:
    """One ``self.<attr>`` touch inside a method."""

    cls: str
    attr: str
    kind: str                # "read" | "write"
    fn: FuncInfo
    node: ast.AST
    locks: frozenset        # lock-ish guard names held at the access


def _param_names(node: ast.AST) -> tuple[str, ...]:
    a = getattr(node, "args", None)
    if a is None:
        return ()
    names = [p.arg for p in (*a.posonlyargs, *a.args, *a.kwonlyargs)]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return tuple(names)


def lock_names(expr: ast.AST) -> str | None:
    """Guard name for a ``with <expr>`` item when it is lock-ish.
    Handles plain names (``self._lock``), factory calls
    (``self._lock_for(fid)``, ``threading.Lock()``), and subscripts of
    lock arrays (``self._mu[i]`` — the striped-lock idiom)."""
    base = expr
    if isinstance(base, ast.Call):
        base = base.func
    if isinstance(base, ast.Subscript):
        base = base.value
    name = dotted(base)
    if name and LOCKISH.search(name.split(".")[-1]):
        return name
    return None


class ProjectModel:
    """The phase-1 facts. Build once via :func:`build_model`; every
    phase-2 rule reads it (``Project.model`` caches it)."""

    def __init__(self, project: Project) -> None:
        self.project = project
        self.functions: dict[str, FuncInfo] = {}
        # resolution tables
        self._by_module_func: dict[tuple[str, str], FuncInfo] = {}
        self._by_class_method: dict[tuple[str, str], FuncInfo] = {}
        self._attr_types: dict[tuple[str, str], str] = {}
        self._imports: dict[str, dict[str, str]] = {}
        self._fn_of_node: dict[tuple[int, int], FuncInfo] = {}
        # per-function name -> nested FuncInfo (computed once: the
        # per-call ast.walk search was quadratic on runtime.py)
        self._nested: dict[str, dict[str, FuncInfo]] = {}
        # callee uid -> [(caller uid, locks held at the call site)] —
        # feeds the inherited-lock fixed point (the `*_locked` caller-
        # holds-the-lock convention becomes a model fact)
        self._call_sites: dict[str, list[tuple[str, frozenset]]] = {}
        self._inherited_locks: dict[str, frozenset] = {}
        # per-function Call nodes in scope (filled by the edge pass)
        self._calls_of: dict[str, list[ast.Call]] = {}
        self._view_stmt_cache: dict[str, list[ast.AST]] = {}
        # per-function locals known to OWN their buffer (assigned from
        # bytes()/bytearray()): a memoryview over one is not borrowed
        self._owned_vars: dict[str, set[str]] = {}
        self.accesses: dict[tuple[str, str], list[AttrAccess]] = {}
        self._build()

    # ---- construction ------------------------------------------------- #

    @staticmethod
    def _module_of(rel: str) -> str:
        mod = rel[:-3] if rel.endswith(".py") else rel
        mod = mod.replace("/", ".")
        return mod[:-9] if mod.endswith(".__init__") else mod

    def _build(self) -> None:
        for src in self.project.files:
            if src.tree is None:
                continue
            self._collect_functions(src)
            self._collect_imports(src)
        pending: list[tuple[FuncInfo, ast.Attribute, ast.AST]] = []
        for src in self.project.files:
            if src.tree is None:
                continue
            pending.extend(self._collect_attr_assigns(src))
        self._resolve_attr_types(pending)
        seeds: list[tuple[FuncInfo, str]] = []
        for src in self.project.files:
            if src.tree is None:
                continue
            seeds.extend(self._collect_edges_and_seeds(src))
        # trampolines: a fn whose param reaches a dispatch site makes
        # every callable argument at its call sites a worker entry
        seeds.extend(self._trampoline_seeds())
        self._propagate(seeds)
        self._compute_inherited_locks()
        self._collect_accesses()
        self._compute_returns_view()

    def _collect_functions(self, src: SourceFile) -> None:
        mod = self._module_of(src.rel)
        fns = src.nodes(ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        created: list[FuncInfo] = []
        # pass 1: create + register every FuncInfo (the node index is
        # grouped by TYPE, so a nested sync def can precede its async
        # parent — enclosing-scope lookups must wait for pass 2)
        for node in fns:
            name = getattr(node, "name", "<lambda>")
            cls = None
            cur = src.parents.get(node)
            while cur is not None:
                if isinstance(cur, ast.ClassDef):
                    cls = cur.name
                    break
                cur = src.parents.get(cur)
            fi = FuncInfo(
                uid=f"{src.rel}:{src.qualname(node)}.{name}"
                    f":{node.lineno}",
                src=src, node=node, name=name, cls=cls,
                is_async=isinstance(node, ast.AsyncFunctionDef),
                params=_param_names(node))
            if fi.is_async:
                fi.ctx.add(LOOP)
            self.functions[fi.uid] = fi
            self._fn_of_node[(id(src), id(node))] = fi
            created.append(fi)
        # pass 2: nesting + name tables (every function resolvable now)
        for fi in created:
            if isinstance(fi.node, ast.Lambda):
                continue
            encl = self._enclosing_fn(src, fi.node)
            if encl is not None:
                self._nested.setdefault(encl.uid, {})[fi.name] = fi
            parent = src.parents.get(fi.node)
            if isinstance(parent, ast.Module):
                self._by_module_func.setdefault((mod, fi.name), fi)
            elif isinstance(parent, ast.ClassDef) \
                    and src.parents.get(parent) is not None:
                self._by_class_method[(parent.name, fi.name)] = fi

    def _collect_imports(self, src: SourceFile) -> None:
        table: dict[str, str] = {}
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    table[alias.asname or alias.name.split(".")[0]] = \
                        alias.name
            elif isinstance(node, ast.ImportFrom) and node.module \
                    and node.level == 0:
                for alias in node.names:
                    table[alias.asname or alias.name] = \
                        f"{node.module}.{alias.name}"
        self._imports[src.rel] = table

    def _known_class(self, name: str | None) -> str | None:
        if name is None:
            return None
        last = name.split(".")[-1]
        return last if any(last == c for c, _ in self._by_class_method) \
            else None

    def _collect_attr_assigns(self, src: SourceFile
                              ) -> list[tuple[FuncInfo, ast.Attribute,
                                              ast.AST]]:
        """Every ``self.…x = value`` site, for the attr-type pass."""
        out: list[tuple[FuncInfo, ast.Attribute, ast.AST]] = []
        for node in src.nodes(ast.Assign, ast.AnnAssign):
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                t, value = node.targets[0], node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                t, value = node.target, node.value
            else:
                continue
            if not isinstance(t, ast.Attribute):
                continue
            fn = self._enclosing_fn(src, node)
            if fn is None or fn.cls is None:
                continue
            out.append((fn, t, value))
        return out

    def _resolve_attr_types(self, pending: list) -> None:
        """``self.x = SomeClass(...)`` and ``self.x = <param annotated
        SomeClass>`` pin the attribute's class, so ``self.x.m()``
        resolves module-qualified instead of by name-guess. Chained
        targets resolve through already-known types to a fixed point —
        the runtime's seam wiring (``self.store.chunks.index =
        IndexPlane(...)``) types ``ChunkStore.index`` even though the
        assignment lives in another class and another file."""
        for _ in range(4):
            progressed = False
            for fn, t, value in pending:
                owner = self._owner_class(fn, t.value)
                if owner is None or (owner, t.attr) in self._attr_types:
                    continue
                cls_name = None
                if isinstance(value, ast.Call):
                    cls_name = self._known_class(dotted(value.func))
                elif isinstance(value, ast.Name):
                    if fn is not None:
                        ann = self._param_annotation(fn, value.id)
                        cls_name = self._known_class(ann)
                elif isinstance(value, ast.Attribute):
                    got = self._owner_class(fn, value.value)
                    if got is not None:
                        cls_name = self._attr_types.get(
                            (got, value.attr))
                if cls_name:
                    self._attr_types[(owner, t.attr)] = cls_name
                    progressed = True
            if not progressed:
                break

    def _owner_class(self, fn: FuncInfo, expr: ast.AST) -> str | None:
        """Class owning the attribute at the END of a ``self.a.b…``
        chain (``self`` → the method's own class; each hop through the
        attr-type table)."""
        chain = dotted(expr)
        if chain is None or fn.cls is None:
            return None
        parts = chain.split(".")
        if parts[0] != "self":
            return None
        cls = fn.cls
        for attr in parts[1:]:
            cls = self._attr_types.get((cls, attr))
            if cls is None:
                return None
        return cls

    @staticmethod
    def _param_annotation(fn: FuncInfo, pname: str) -> str | None:
        a = getattr(fn.node, "args", None)
        if a is None:
            return None
        for p in (*a.posonlyargs, *a.args, *a.kwonlyargs):
            if p.arg == pname and p.annotation is not None:
                return dotted(p.annotation)
        return None

    def _enclosing_fn(self, src: SourceFile,
                      node: ast.AST) -> FuncInfo | None:
        cur = src.parents.get(node)
        while cur is not None:
            fi = self._fn_of_node.get((id(src), id(cur)))
            if fi is not None:
                return fi
            cur = src.parents.get(cur)
        return None

    # ---- call/target resolution ---------------------------------------- #

    def resolve_call(self, src: SourceFile, fn: FuncInfo | None,
                     call_func: ast.AST) -> FuncInfo | None:
        """Best-effort resolution of a call expression to a FuncInfo."""
        # self.method(...) / self.attr.method(...)
        if isinstance(call_func, ast.Attribute):
            chain = dotted(call_func)
            if chain and chain.startswith("self.") and fn is not None \
                    and fn.cls is not None:
                parts = chain.split(".")
                cls: str | None = fn.cls
                for attr in parts[1:-1]:
                    cls = self._attr_types.get((cls, attr))
                    if cls is None:
                        return None
                return self._by_class_method.get((cls, parts[-1]))
            # mod.func(...) via imports
            if chain:
                head, _, tail = chain.partition(".")
                imp = self._imports.get(src.rel, {}).get(head)
                if imp is not None and "." not in tail:
                    return self._by_module_func.get((imp, tail)) \
                        or self._by_class_method.get(
                            (imp.split(".")[-1], tail))
            return None
        if isinstance(call_func, ast.Name):
            name = call_func.id
            # nested function in the lexically-enclosing chain
            cur = fn
            while cur is not None:
                got = self._nested.get(cur.uid, {}).get(name)
                if got is not None:
                    return got
                cur = self._enclosing_fn(src, cur.node)
            mod = self._module_of(src.rel)
            got = self._by_module_func.get((mod, name))
            if got is not None:
                return got
            imp = self._imports.get(src.rel, {}).get(name)
            if imp is not None:
                pmod, _, pname = imp.rpartition(".")
                return self._by_module_func.get((pmod, pname))
        return None

    def _resolve_target(self, src: SourceFile, fn: FuncInfo | None,
                        expr: ast.AST) -> FuncInfo | None:
        """A callable ARGUMENT (dispatch target / callback): a lambda,
        a local/nested/module function name, or ``self.method``."""
        if isinstance(expr, ast.Lambda):
            return self._fn_of_node.get((id(src), id(expr)))
        return self.resolve_call(src, fn, expr)

    def dispatch_targets(self, src: SourceFile, node: ast.Call
                         ) -> tuple[list[ast.AST], list[ast.AST]]:
        """(worker-seeded exprs, loop-seeded exprs) for one call."""
        workers: list[ast.AST] = []
        loops: list[ast.AST] = []
        name = dotted(node.func)
        if name == "asyncio.to_thread" and node.args:
            workers.append(node.args[0])
        elif isinstance(node.func, ast.Attribute):
            attr = node.func.attr
            if attr == "run_in_executor" and len(node.args) >= 2:
                workers.append(node.args[1])
            elif attr in _THREAD_SEED_ATTRS and node.args:
                workers.append(node.args[0])
            elif attr == "Thread":
                kw = next((k.value for k in node.keywords
                           if k.arg == "target"), None)
                if kw is not None:
                    workers.append(kw)
            elif attr in _LOOP_CALLBACK_ATTRS and node.args:
                loops.append(node.args[0])
        if name in ("threading.Thread", "Thread"):
            kw = next((k.value for k in node.keywords
                       if k.arg == "target"), None)
            if kw is not None:
                workers.append(kw)
        return workers, loops

    def _collect_edges_and_seeds(self, src: SourceFile
                                 ) -> list[tuple[FuncInfo, str]]:
        """One pass over the file's Call nodes: each call belongs to
        its IMMEDIATE enclosing function (the same not-into-nested-
        scopes semantics scope_nodes gives, without re-walking every
        function subtree)."""
        seeds: list[tuple[FuncInfo, str]] = []
        for n in src.nodes(ast.Call):
            fi = self._enclosing_fn(src, n)
            if fi is not None:
                self._calls_of.setdefault(fi.uid, []).append(n)
            workers, loops = self.dispatch_targets(src, n)
            for expr in workers:
                tgt = self._resolve_target(src, fi, expr)
                if tgt is not None:
                    seeds.append((tgt, WORKER))
            for expr in loops:
                tgt = self._resolve_target(src, fi, expr)
                if tgt is not None:
                    seeds.append((tgt, LOOP))
            if workers or loops or fi is None:
                continue  # dispatch, not a same-context call edge
            callee = self.resolve_call(src, fi, n.func)
            if callee is not None:
                fi.callees.add(callee.uid)
                self._call_sites.setdefault(callee.uid, []).append(
                    (fi.uid, self._locks_held(src, n, fi.node)))
        return seeds

    def _trampoline_seeds(self) -> list[tuple[FuncInfo, str]]:
        """``AsyncChunkStore._run(pool, fn)`` shape: ``fn`` (a param)
        reaches ``run_in_executor`` — possibly via a nested def that
        calls it — so callable args at ``_run``'s call sites run on
        worker threads."""
        tramp: dict[str, set[str]] = {}
        for fi in self.functions.values():
            if isinstance(fi.node, ast.Lambda) or not fi.params:
                continue
            dispatched: set[str] = set()
            for n in self._calls_of.get(fi.uid, ()):
                workers, _ = self.dispatch_targets(fi.src, n)
                for expr in workers:
                    if isinstance(expr, ast.Name):
                        dispatched.add(expr.id)
            if not dispatched:
                continue
            params = set(fi.params)
            hit = dispatched & params
            for name, nested in self._nested.get(fi.uid, {}).items():
                if name in dispatched:
                    called = {c.func.id
                              for c in self._calls_of.get(nested.uid, ())
                              if isinstance(c.func, ast.Name)}
                    hit |= called & params
            if hit:
                tramp[fi.uid] = hit
        if not tramp:
            return []
        seeds: list[tuple[FuncInfo, str]] = []
        for fi in self.functions.values():
            src = fi.src
            for n in self._calls_of.get(fi.uid, ()):
                callee = self.resolve_call(src, fi, n.func)
                if callee is None or callee.uid not in tramp:
                    continue
                pnames = tramp[callee.uid]
                # positional args map onto the callee's params
                # (skipping its leading self for bound-method calls)
                params = list(callee.params)
                if params and params[0] == "self":
                    params = params[1:]
                for i, arg in enumerate(n.args):
                    if i < len(params) and params[i] in pnames:
                        tgt = self._resolve_target(src, fi, arg)
                        if tgt is not None:
                            seeds.append((tgt, WORKER))
                for kw in n.keywords:
                    if kw.arg in pnames:
                        tgt = self._resolve_target(src, fi, kw.value)
                        if tgt is not None:
                            seeds.append((tgt, WORKER))
        return seeds

    def _propagate(self, seeds: list[tuple[FuncInfo, str]]) -> None:
        work: list[FuncInfo] = []
        for fi, ctx in seeds:
            if ctx not in fi.ctx:
                fi.ctx.add(ctx)
            work.append(fi)
        work.extend(fi for fi in self.functions.values() if fi.ctx)
        while work:
            fi = work.pop()
            for uid in fi.callees:
                callee = self.functions.get(uid)
                if callee is None:
                    continue
                add = fi.ctx - callee.ctx
                if callee.is_async:
                    # an async callee always runs on the loop; a worker
                    # caller cannot await it, so worker never crosses
                    add = add & {LOOP}
                if add:
                    callee.ctx |= add
                    work.append(callee)

    # ---- symbol table -------------------------------------------------- #

    def _compute_inherited_locks(self) -> None:
        """Locks a function can RELY on its callers holding: the
        intersection, over every resolved call site, of the locks held
        lexically at the site plus the caller's own inherited set — the
        ``_flush_wal_locked`` convention (callee runs with the store
        lock held) established as a fact instead of trusted by name.
        A function with no resolved call sites inherits nothing."""
        inh: dict[str, frozenset] = {}
        for _ in range(8):
            changed = False
            for callee, sites in self._call_sites.items():
                new = None
                for caller, locks in sites:
                    held = locks | inh.get(caller, frozenset())
                    new = held if new is None else (new & held)
                new = new or frozenset()
                if inh.get(callee, frozenset()) != new:
                    inh[callee] = new
                    changed = True
            if not changed:
                break
        self._inherited_locks = inh

    def inherited_locks(self, fn: FuncInfo) -> frozenset:
        return self._inherited_locks.get(fn.uid, frozenset())

    def callers_of(self, fn: FuncInfo) -> list[FuncInfo]:
        """Every function with a resolved call site into ``fn``."""
        return [self.functions[c]
                for c, _ in self._call_sites.get(fn.uid, [])
                if c in self.functions]

    def _locks_held(self, src: SourceFile, node: ast.AST,
                    stop: ast.AST) -> frozenset:
        held: set[str] = set()
        cur = src.parents.get(node)
        while cur is not None and cur is not stop:
            if isinstance(cur, (ast.With, ast.AsyncWith)):
                for item in cur.items:
                    name = lock_names(item.context_expr)
                    if name:
                        held.add(name)
            cur = src.parents.get(cur)
        return frozenset(held)

    def _collect_accesses(self) -> None:
        for src in self.project.files:
            if src.tree is None:
                continue
            for n in src.nodes(ast.Attribute):
                if not (isinstance(n.value, ast.Name)
                        and n.value.id == "self"):
                    continue
                fi = self._enclosing_fn(src, n)
                if fi is None or fi.cls is None:
                    continue
                acc = self._classify_access(src, n)
                if acc is None:
                    continue
                attr, kind, anchor = acc
                held = self._locks_held(src, anchor, fi.node) \
                    | self.inherited_locks(fi)
                self.accesses.setdefault((fi.cls, attr), []).append(
                    AttrAccess(fi.cls, attr, kind, fi, anchor, held))

    def _classify_access(self, src: SourceFile, n: ast.AST
                         ) -> tuple[str, str, ast.AST] | None:
        if isinstance(n, ast.Attribute) and isinstance(n.value, ast.Name) \
                and n.value.id == "self":
            parent = src.parents.get(n)
            if isinstance(n.ctx, (ast.Store, ast.Del)):
                return n.attr, "write", n
            if isinstance(parent, ast.AugAssign) and parent.target is n:
                return n.attr, "write", n
            # self.x[k] = v / del self.x[k]
            if isinstance(parent, ast.Subscript) \
                    and isinstance(parent.ctx, (ast.Store, ast.Del)):
                return n.attr, "write", n
            # self.x.append(...) and friends mutate the structure
            if isinstance(parent, ast.Attribute) \
                    and parent.attr in _MUTATOR_ATTRS:
                gp = src.parents.get(parent)
                if isinstance(gp, ast.Call) and gp.func is parent:
                    return n.attr, "write", n
            return n.attr, "read", n
        return None

    # ---- view-returning functions -------------------------------------- #

    def _compute_returns_view(self) -> None:
        # only functions that actually return something participate
        returners = []
        for fi in self.functions.values():
            if isinstance(fi.node, ast.Lambda):
                continue
            rets = [n for n in scope_nodes(fi.node)
                    if isinstance(n, ast.Return) and n.value is not None]
            if rets:
                returners.append((fi, rets))
        changed = True
        while changed:
            changed = False
            for fi, rets in returners:
                if fi.returns_view:
                    continue
                views = view_vars(self, fi)
                if any(is_view_expr(self, fi, r.value, views)
                       for r in rets):
                    fi.returns_view = True
                    changed = True

    def fn_for(self, src: SourceFile, node: ast.AST) -> FuncInfo | None:
        return self._fn_of_node.get((id(src), id(node)))


# ---- shared view dataflow (used by the model and DFS009) -------------- #

# self-attribute names that denote POOLED/recycled buffers: a view over
# one is only valid until the pool recycles it (the r15 bug class). The
# naming heuristic is the same contract as DFS003's lock regex: name
# pooled buffers like pooled buffers.
POOLED_ATTR = re.compile(r"(staging|pool|scratch|recycl|spare|arena)",
                         re.IGNORECASE)


def is_view_source_call(model: ProjectModel, fn: FuncInfo,
                        call: ast.Call, views: set[str]) -> bool:
    name = dotted(call.func)
    if name in _VIEW_CALLS or (
            name and name.split(".")[-1] in _VIEW_CALLS):
        if name and name.split(".")[-1] == "memoryview" and call.args:
            return _borrowed_base(call.args[0], views,
                                  model._owned_vars.get(fn.uid, set()))
        return True
    if isinstance(call.func, ast.Attribute):
        if call.func.attr in _VIEW_METHODS:
            return True
        # one interprocedural hop: a call to a function the model
        # knows returns a view
    resolved = model.resolve_call(fn.src, fn, call.func)
    return resolved is not None and resolved.returns_view


def _borrowed_base(expr: ast.AST, views: set[str],
                   owned: set[str] = frozenset()) -> bool:
    """Is ``memoryview(expr)`` a view over memory this function does
    NOT own? Owned: a fresh local ``bytes``/``bytearray`` (inline or a
    local name assigned from one — ``owned`` is the dataflow set
    ``view_vars`` maintains) or a plain ``self.<attr>`` buffer —
    UNLESS the attr name marks it pooled (staging/pool/scratch/…),
    where recycling is the whole point."""
    if isinstance(expr, ast.Call):
        name = dotted(expr.func)
        if name in ("bytes", "bytearray"):
            return False
        return True
    chain = dotted(expr)
    if chain and chain.startswith("self."):
        return bool(POOLED_ATTR.search(chain))
    if isinstance(expr, ast.Name):
        # param or local of unknown provenance: borrowed — unless the
        # forward pass saw it assigned from a fresh bytes/bytearray
        return expr.id not in owned
    if isinstance(expr, ast.Subscript):
        return _borrowed_base(expr.value, views, owned)
    return True


def is_view_expr(model: ProjectModel, fn: FuncInfo, expr: ast.AST,
                 views: set[str]) -> bool:
    """Does ``expr`` evaluate to a borrowed view (given the known
    view-variable set)?"""
    if isinstance(expr, ast.Await):
        return is_view_expr(model, fn, expr.value, views)
    if isinstance(expr, ast.Name):
        return expr.id in views
    if isinstance(expr, ast.Call):
        return is_view_source_call(model, fn, expr, views)
    if isinstance(expr, ast.Subscript):
        return is_view_expr(model, fn, expr.value, views)
    if isinstance(expr, ast.Attribute):
        # v.obj / v.field — views of views only via the known methods
        return False
    if isinstance(expr, ast.IfExp):
        return is_view_expr(model, fn, expr.body, views) \
            or is_view_expr(model, fn, expr.orelse, views)
    return False


def view_vars(model: ProjectModel, fn: FuncInfo) -> set[str]:
    """Names bound to borrowed views inside ``fn`` (forward pass in
    line order; a later rebind to a copy — ``v = bytes(v)`` — clears
    the mark)."""
    views: set[str] = set()
    if isinstance(fn.node, ast.Lambda):
        return views
    # live reference: is_view_expr consults it mid-pass via the model
    owned = model._owned_vars.setdefault(fn.uid, set())
    owned.clear()
    stmts = model._view_stmt_cache.get(fn.uid)
    if stmts is None:
        stmts = sorted((n for n in scope_nodes(fn.node)
                        if isinstance(n, (ast.Assign, ast.AnnAssign,
                                          ast.For, ast.AsyncFor))),
                       key=lambda n: (n.lineno, n.col_offset))
        model._view_stmt_cache[fn.uid] = stmts
    for st in stmts:
        if isinstance(st, (ast.For, ast.AsyncFor)):
            if is_view_expr(model, fn, st.iter, views):
                for t in ast.walk(st.target):
                    if isinstance(t, ast.Name):
                        views.add(t.id)
            continue
        value = st.value
        if value is None:
            continue
        targets = st.targets if isinstance(st, ast.Assign) else [st.target]
        is_view = is_view_expr(model, fn, value, views)
        owns = isinstance(value, ast.Call) \
            and dotted(value.func) in ("bytes", "bytearray")
        for t in targets:
            if isinstance(t, ast.Name):
                (views.add if is_view else views.discard)(t.id)
                (owned.add if owns else owned.discard)(t.id)
            elif isinstance(t, (ast.Tuple, ast.List)) and is_view:
                # unpacking a view-producing call (unpack_chunks pairs,
                # conn.reply() triples): every bound name may borrow
                for sub in ast.walk(t):
                    if isinstance(sub, ast.Name):
                        views.add(sub.id)
    return views


def build_model(project: Project) -> ProjectModel:
    """Build (or return the cached) phase-1 model for ``project``."""
    cached = getattr(project, "_model", None)
    if cached is None:
        cached = ProjectModel(project)
        project._model = cached
    return cached
