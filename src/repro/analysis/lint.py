"""AST lint pass over ``src/repro`` enforcing the project's runtime invariants.

Rules (see ROADMAP.md "Runtime invariants" for rationale):

* **RT001 wallclock** — calls to ``time.time``/``time.monotonic``/
  ``datetime.now`` in sim-reachable modules.  Virtual time must flow
  through injected ``time_fn``/clock parameters; a wall-clock read on a
  sim path silently breaks determinism and replay.  Live-only modules
  (``launch/``, ``chaos/live.py``, ``distributed/fault.py``) are
  allowlisted; other sites need ``# repro: allow-wallclock(<reason>)``.
  ``time.perf_counter`` is *not* flagged: it is the live-path duration
  idiom and never doubles as a timestamp.  Bare references (e.g. the
  ``time_fn=time.monotonic`` injection default) are not calls and are
  allowed — injection is exactly the sanctioned pattern.
* **RT002 unbounded** — ``deque()`` without ``maxlen`` and append-only
  log lists (``self.<x>_log = []`` in ``__init__``).  Every long-lived
  log in this repo is bounded (``log_cap`` + dropped counters); work
  queues that are drained each tick carry
  ``# repro: allow-unbounded(<reason>)``.
* **RT003 unseeded** — ``random.*`` / ``np.random.*`` global-state calls.
  Determinism is the substrate of every benchmark compare gate; all
  randomness goes through seeded ``random.Random(seed)`` /
  ``np.random.default_rng(seed)`` instances.  ``jax.random`` key
  threading is exempt (explicitly seeded by construction).
* **RT004 span-schema** — span emission call sites
  (``tracer.decision(KIND, ...)``, ``add_span(tid, KIND, ...)``,
  ``spans=[(KIND, t0, t1, attrs)]``, ``tracer.batch(t0, t1, ...)``)
  checked against
  ``repro.obs.trace.SCHEMA``: the kind must exist and the required
  attributes must be present in the literal attrs.  Catches
  schema drift at lint time instead of in a Perfetto viewer.
* **RT005 thread-hygiene** — ``Thread(...)`` without an explicit
  ``daemon`` flag, ``.wait()`` with no timeout inside a loop, and bare
  ``except:``.  A non-daemon thread wedges interpreter exit; an
  untimed wait in a loop is unkillable unless every setter is audited
  (sites that *are* audited carry ``# repro: allow-wait(<reason>)``).
* **RT006 guarded-by** — attributes annotated ``# guarded-by: _lock``
  that are written in a method body which never enters a
  ``with self._lock`` block.  The static shadow of
  :mod:`repro.analysis.guards`; catches the common case without
  running anything.

Suppression: ``# repro: allow-<alias>(<reason>)`` on the offending
line.  A pragma with an empty reason, or one that suppresses nothing,
is itself a finding (RT000) — stale pragmas rot.
"""
from __future__ import annotations

import ast
import dataclasses
import io
import os
import re
import tokenize
from typing import Dict, List, Optional, Sequence, Set, Tuple

# ---------------------------------------------------------------------------
# Rule registry

RULES: Dict[str, str] = {
    "RT000": "pragma hygiene: malformed/unused allow-pragma or empty reason",
    "RT001": "wall-clock call in sim-reachable module (inject time_fn instead)",
    "RT002": "unbounded growth: deque() without maxlen / append-only log list",
    "RT003": "unseeded randomness: random.*/np.random.* global-state call",
    "RT004": "span emission does not match repro.obs.trace.SCHEMA",
    "RT005": "thread hygiene: non-daemon Thread / untimed wait in loop / bare except",
    "RT006": "guarded-by attribute written without entering its lock",
}

# pragma alias -> rule it suppresses
PRAGMA_ALIASES: Dict[str, str] = {
    "wallclock": "RT001",
    "unbounded": "RT002",
    "unseeded": "RT003",
    "span": "RT004",
    "thread": "RT005",
    "wait": "RT005",
    "guard": "RT006",
}

# Modules (paths relative to the lint root, '/'-separated) that are
# live-only by construction: they exist to touch the real clock.
WALLCLOCK_ALLOWLIST: Tuple[str, ...] = (
    "launch/",
    "chaos/live.py",
    "distributed/fault.py",
)

_WALLCLOCK_TIME_ATTRS = {"time", "monotonic", "time_ns", "monotonic_ns"}
_WALLCLOCK_DT_ATTRS = {"now", "utcnow", "today"}
_LOG_NAME_RE = re.compile(r"(^|_)(log|logs|history|events)($|_)")
_PRAGMA_RE = re.compile(r"#\s*repro:\s*allow-([a-z0-9_-]+)\s*(?:\(([^)]*)\))?")
_GUARDED_BY_RE = re.compile(r"#\s*guarded-by:\s*([A-Za-z_][A-Za-z0-9_]*)")

# Seeded constructors that make the *result* deterministic; calling these
# is the sanctioned way to obtain randomness.
_RANDOM_MODULE_OK = {"Random", "SystemRandom", "getstate", "setstate", "seed"}
_NP_RANDOM_OK = {"Generator", "RandomState", "default_rng", "SeedSequence",
                 "PCG64", "Philox"}


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"


def format_findings(findings: Sequence[Finding]) -> str:
    lines = [f.render() for f in findings]
    lines.append(f"{len(findings)} finding(s)")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Comment scanning (pragmas + guarded-by annotations)


def _scan_comments(source: str):
    """Return (pragmas, guarded) keyed by line number.

    pragmas: line -> list of (alias, reason, used-flag-list)
    guarded: line -> lock attribute name
    """
    pragmas: Dict[int, List[List]] = {}
    guarded: Dict[int, str] = {}
    try:
        toks = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in toks:
            if tok.type != tokenize.COMMENT:
                continue
            line = tok.start[0]
            for m in _PRAGMA_RE.finditer(tok.string):
                alias, reason = m.group(1), (m.group(2) or "").strip()
                pragmas.setdefault(line, []).append([alias, reason, False])
            m = _GUARDED_BY_RE.search(tok.string)
            if m:
                guarded[line] = m.group(1)
    except tokenize.TokenError:
        pass
    return pragmas, guarded


# ---------------------------------------------------------------------------
# Span schema resolution helpers


def _load_schema():
    """SCHEMA and {CONSTANT_NAME: span_name} from repro.obs.trace."""
    try:
        from repro.obs import trace as _trace
    except Exception:  # pragma: no cover - lint must run without jax etc.
        return {}, {}
    schema = dict(getattr(_trace, "SCHEMA", {}))
    consts = {
        name: val
        for name, val in vars(_trace).items()
        if name.isupper() and isinstance(val, str)
    }
    return schema, consts


def _names_tracer(node: ast.AST) -> bool:
    """``tracer`` or ``<x>.tracer``: the receiver of a span call."""
    return ((isinstance(node, ast.Name) and node.id == "tracer")
            or (isinstance(node, ast.Attribute) and node.attr == "tracer"))


def _resolve_kind(node: ast.AST, consts: Dict[str, str]) -> Optional[str]:
    """Span-kind expression -> span name string, or None if unresolvable."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.Attribute) and node.attr in consts:
        return consts[node.attr]
    if isinstance(node, ast.Name) and node.id in consts:
        return consts[node.id]
    return None


def _dict_literal_keys(node: ast.AST) -> Optional[Set[str]]:
    if isinstance(node, ast.Dict):
        keys = set()
        for k in node.keys:
            if k is None:  # **spread: can't see inside
                return None
            if isinstance(k, ast.Constant) and isinstance(k.value, str):
                keys.add(k.value)
            else:
                return None
        return keys
    if isinstance(node, ast.Constant) and node.value is None:
        return set()
    return None


# ---------------------------------------------------------------------------
# The per-file visitor


class _FileLint(ast.NodeVisitor):
    def __init__(self, rel: str, source: str, schema, consts,
                 wallclock_allowed: bool):
        self.rel = rel
        self.schema = schema
        self.consts = consts
        self.wallclock_allowed = wallclock_allowed
        self.findings: List[Finding] = []
        self.pragmas, self.guarded_comments = _scan_comments(source)

        # import aliases
        self.time_names: Set[str] = set()
        self.datetime_mod_names: Set[str] = set()
        self.datetime_cls_names: Set[str] = set()
        self.random_names: Set[str] = set()
        self.from_random_fns: Set[str] = set()
        self.np_names: Set[str] = set()
        self.jax_names: Set[str] = set()
        self.threading_names: Set[str] = set()
        self.thread_cls_names: Set[str] = set()
        self.deque_names: Set[str] = set()
        self.collections_names: Set[str] = set()

        # structural state
        self._loop_depth = 0
        self._class_stack: List[str] = []
        self._func_stack: List[ast.AST] = []
        self._local_dicts: List[Dict[str, Set[str]]] = []
        # class name -> {attr: lock} collected from __init__ comments
        self._guarded_attrs: Dict[str, Dict[str, str]] = {}

    # -- reporting ----------------------------------------------------------

    def _emit(self, rule: str, line: int, message: str) -> None:
        for entry in self.pragmas.get(line, ()):
            alias, _reason, _ = entry
            if PRAGMA_ALIASES.get(alias) == rule:
                entry[2] = True  # mark used
                return
        self.findings.append(Finding(rule, self.rel, line, message))

    def finish(self) -> List[Finding]:
        # RT000: every pragma must be used and carry a reason.
        for line, entries in sorted(self.pragmas.items()):
            for alias, reason, used in entries:
                if alias not in PRAGMA_ALIASES:
                    self.findings.append(Finding(
                        "RT000", self.rel, line,
                        f"unknown pragma alias 'allow-{alias}' "
                        f"(known: {', '.join(sorted(PRAGMA_ALIASES))})"))
                    continue
                if not used:
                    self.findings.append(Finding(
                        "RT000", self.rel, line,
                        f"pragma 'allow-{alias}' suppresses nothing on this "
                        "line — remove it"))
                    continue
                if not reason:
                    self.findings.append(Finding(
                        "RT000", self.rel, line,
                        f"pragma 'allow-{alias}' needs a reason: "
                        f"# repro: allow-{alias}(<why this is safe>)"))
        self.findings.sort(key=lambda f: (f.line, f.rule))
        return self.findings

    # -- imports ------------------------------------------------------------

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            name = a.asname or a.name.split(".")[0]
            if a.name == "time" or a.name.startswith("time."):
                self.time_names.add(name)
            elif a.name == "datetime":
                self.datetime_mod_names.add(name)
            elif a.name == "random":
                self.random_names.add(name)
            elif a.name in ("numpy", "numpy.random"):
                self.np_names.add(name)
            elif a.name == "jax" or a.name.startswith("jax."):
                self.jax_names.add(name)
            elif a.name == "threading":
                self.threading_names.add(name)
            elif a.name == "collections":
                self.collections_names.add(name)
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        mod = node.module or ""
        for a in node.names:
            name = a.asname or a.name
            if mod == "datetime" and a.name == "datetime":
                self.datetime_cls_names.add(name)
            elif mod == "time":
                if a.name in _WALLCLOCK_TIME_ATTRS:
                    self.time_names.add(name)  # flagged as bare-call below
            elif mod == "random":
                self.from_random_fns.add(name)
            elif mod == "threading" and a.name == "Thread":
                self.thread_cls_names.add(name)
            elif mod == "collections" and a.name == "deque":
                self.deque_names.add(name)
            elif mod in ("numpy", "numpy.random") and a.name == "random":
                self.np_names.add(name)
        self.generic_visit(node)

    # -- structure tracking --------------------------------------------------

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._class_stack.append(node.name)
        self._collect_guarded(node)
        self.generic_visit(node)
        cls = self._class_stack.pop()
        self._check_guarded_writes(node, self._guarded_attrs.get(cls, {}))

    def _visit_func(self, node) -> None:
        self._func_stack.append(node)
        self._local_dicts.append({})
        self.generic_visit(node)
        self._local_dicts.pop()
        self._func_stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def _visit_loop(self, node) -> None:
        self._loop_depth += 1
        self.generic_visit(node)
        self._loop_depth -= 1

    visit_While = _visit_loop
    visit_For = _visit_loop

    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        if node.type is None:
            self._emit("RT005", node.lineno,
                       "bare 'except:' swallows KeyboardInterrupt/SystemExit "
                       "— catch Exception at most")
        self.generic_visit(node)

    def visit_Assign(self, node: ast.Assign) -> None:
        # track `name = {...literal...}` for RT004 attrs resolution
        if (self._local_dicts and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            keys = _dict_literal_keys(node.value)
            scope = self._local_dicts[-1]
            if keys is not None:
                scope[node.targets[0].id] = keys
            else:
                scope.pop(node.targets[0].id, None)
        self._check_unbounded_log_list(node)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            shim = ast.Assign(targets=[node.target], value=node.value)
            ast.copy_location(shim, node)
            self._check_unbounded_log_list(shim)
        self.generic_visit(node)

    # -- RT002: unbounded log lists -----------------------------------------

    def _check_unbounded_log_list(self, node: ast.Assign) -> None:
        if not (self._class_stack and self._func_stack):
            return
        fn = self._func_stack[-1]
        if getattr(fn, "name", "") != "__init__":
            return
        for tgt in node.targets:
            if (isinstance(tgt, ast.Attribute)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id == "self"
                    and isinstance(node.value, ast.List)
                    and not node.value.elts
                    and _LOG_NAME_RE.search(tgt.attr)):
                self._emit("RT002", node.lineno,
                           f"append-only log list 'self.{tgt.attr} = []' — "
                           "use collections.deque(maxlen=...) with a dropped "
                           "counter")

    # -- guarded-by (RT006) --------------------------------------------------

    def _collect_guarded(self, cls: ast.ClassDef) -> None:
        attrs: Dict[str, str] = {}
        for item in cls.body:
            if not (isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and item.name == "__init__"):
                continue
            for stmt in ast.walk(item):
                if not isinstance(stmt, (ast.Assign, ast.AnnAssign)):
                    continue
                lock = self.guarded_comments.get(stmt.lineno)
                if not lock:
                    continue
                targets = (stmt.targets if isinstance(stmt, ast.Assign)
                           else [stmt.target])
                for tgt in targets:
                    if (isinstance(tgt, ast.Attribute)
                            and isinstance(tgt.value, ast.Name)
                            and tgt.value.id == "self"):
                        attrs[tgt.attr] = lock
        if attrs:
            self._guarded_attrs[cls.name] = attrs

    @staticmethod
    def _written_attrs(fn: ast.AST) -> Dict[str, int]:
        """self-attributes stored to in fn body -> first write line."""
        out: Dict[str, int] = {}

        def note(attr_node: ast.AST) -> None:
            tgt = attr_node
            # unwrap subscript stores: self.x[k] = v
            while isinstance(tgt, ast.Subscript):
                tgt = tgt.value
            if (isinstance(tgt, ast.Attribute)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id == "self"):
                out.setdefault(tgt.attr, tgt.lineno)

        for stmt in ast.walk(fn):
            if isinstance(stmt, ast.Assign):
                for t in stmt.targets:
                    note(t)
            elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
                note(stmt.target)
            elif isinstance(stmt, ast.Delete):
                for t in stmt.targets:
                    note(t)
        return out

    @staticmethod
    def _locks_entered(fn: ast.AST) -> Set[str]:
        locks: Set[str] = set()
        for stmt in ast.walk(fn):
            if isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    expr = item.context_expr
                    if (isinstance(expr, ast.Attribute)
                            and isinstance(expr.value, ast.Name)
                            and expr.value.id == "self"):
                        locks.add(expr.attr)
        return locks

    def _check_guarded_writes(self, cls: ast.ClassDef,
                              attrs: Dict[str, str]) -> None:
        if not attrs:
            return
        for item in cls.body:
            if not isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if item.name == "__init__":
                continue
            writes = self._written_attrs(item)
            entered = self._locks_entered(item)
            for attr, line in sorted(writes.items(), key=lambda kv: kv[1]):
                lock = attrs.get(attr)
                if lock and lock not in entered:
                    self._emit("RT006", line,
                               f"'{cls.name}.{item.name}' writes "
                               f"'self.{attr}' (guarded-by {lock}) without "
                               f"entering 'with self.{lock}'")

    # -- calls: RT001 / RT002-deque / RT003 / RT004 / RT005 ------------------

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        if isinstance(fn, ast.Attribute):
            self._check_attr_call(node, fn)
        elif isinstance(fn, ast.Name):
            self._check_name_call(node, fn)
        self.generic_visit(node)

    def _check_attr_call(self, node: ast.Call, fn: ast.Attribute) -> None:
        base = fn.value
        # RT001: time.time() / time.monotonic()
        if (isinstance(base, ast.Name) and base.id in self.time_names
                and fn.attr in _WALLCLOCK_TIME_ATTRS):
            if not self.wallclock_allowed:
                self._emit("RT001", node.lineno,
                           f"'{base.id}.{fn.attr}()' in sim-reachable module "
                           "— inject a time_fn/clock instead")
        # RT001: datetime.now()/utcnow()/today(), incl. datetime.datetime.now()
        if fn.attr in _WALLCLOCK_DT_ATTRS:
            is_dt = (isinstance(base, ast.Name)
                     and base.id in self.datetime_cls_names)
            is_dt = is_dt or (isinstance(base, ast.Attribute)
                              and base.attr == "datetime"
                              and isinstance(base.value, ast.Name)
                              and base.value.id in self.datetime_mod_names)
            if is_dt and not self.wallclock_allowed:
                self._emit("RT001", node.lineno,
                           f"'datetime.{fn.attr}()' in sim-reachable module "
                           "— inject a time_fn/clock instead")
        # RT002: collections.deque() without maxlen
        if (fn.attr == "deque" and isinstance(base, ast.Name)
                and base.id in self.collections_names):
            self._check_deque(node)
        # RT003: random.* / np.random.*
        if isinstance(base, ast.Name) and base.id in self.random_names:
            if fn.attr not in _RANDOM_MODULE_OK:
                self._emit("RT003", node.lineno,
                           f"'{base.id}.{fn.attr}()' uses the global RNG — "
                           "thread a seeded random.Random(seed) instead")
        if (isinstance(base, ast.Attribute) and base.attr == "random"
                and isinstance(base.value, ast.Name)):
            root = base.value.id
            if root in self.np_names and fn.attr not in _NP_RANDOM_OK:
                self._emit("RT003", node.lineno,
                           f"'{root}.random.{fn.attr}()' uses numpy's global "
                           "RNG — use np.random.default_rng(seed)")
            # jax.random.* is exempt: keys are seeded by construction
        # RT004: tracer.decision(KIND, ...) / add_span(tid, KIND, ...)
        if fn.attr in ("decision", "add_span"):
            self._check_span_call(node, fn.attr)
        if fn.attr in ("request", "finish_request"):
            self._check_spans_kwarg(node)
        if fn.attr == "batch" and _names_tracer(base):
            self._check_kw_attrs(node, "batch", "'tracer.batch()'")
        # RT005: Thread without daemon via threading.Thread(...)
        if (fn.attr == "Thread" and isinstance(base, ast.Name)
                and base.id in self.threading_names):
            self._check_thread(node)
        # RT005: .wait() with no timeout inside a loop
        if (fn.attr == "wait" and self._loop_depth > 0
                and not node.args and not node.keywords):
            self._emit("RT005", node.lineno,
                       "'.wait()' without timeout inside a loop — pass a "
                       "timeout so the loop can observe shutdown")

    def _check_name_call(self, node: ast.Call, fn: ast.Name) -> None:
        if fn.id in self.deque_names:
            self._check_deque(node)
        if fn.id in self.thread_cls_names:
            self._check_thread(node)
        if fn.id in self.from_random_fns:
            self._emit("RT003", node.lineno,
                       f"'{fn.id}()' (from random import ...) uses the "
                       "global RNG — thread a seeded random.Random(seed)")
        if fn.id in self.time_names and fn.id in _WALLCLOCK_TIME_ATTRS:
            if not self.wallclock_allowed:
                self._emit("RT001", node.lineno,
                           f"'{fn.id}()' (from time import ...) in "
                           "sim-reachable module — inject a time_fn/clock")

    def _check_deque(self, node: ast.Call) -> None:
        has_maxlen = any(kw.arg == "maxlen" for kw in node.keywords)
        has_maxlen = has_maxlen or len(node.args) >= 2
        if not has_maxlen:
            self._emit("RT002", node.lineno,
                       "'deque()' without maxlen — bound it or pragma it as "
                       "a drained work queue")

    def _check_thread(self, node: ast.Call) -> None:
        if not any(kw.arg == "daemon" for kw in node.keywords):
            self._emit("RT005", node.lineno,
                       "Thread(...) without explicit daemon= — background "
                       "threads must be daemonized (or deliberately not, "
                       "with a pragma)")

    # -- RT004 helpers -------------------------------------------------------

    def _span_required(self, kind_node: ast.AST, line: int,
                       what: str) -> Optional[Tuple[str, Tuple[str, ...]]]:
        if not self.schema:
            return None
        kind = _resolve_kind(kind_node, self.consts)
        if kind is None:
            return None  # dynamic kind: out of static reach
        if kind not in self.schema:
            self._emit("RT004", line,
                       f"{what} emits unknown span kind '{kind}' — add it to "
                       "repro.obs.trace.SCHEMA first")
            return None
        return kind, tuple(self.schema[kind])

    def _check_span_call(self, node: ast.Call, method: str) -> None:
        kind_idx = 0 if method == "decision" else 1
        if len(node.args) <= kind_idx:
            return
        res = self._span_required(node.args[kind_idx], node.lineno,
                                  f"'{method}()'")
        if res is None:
            return
        self._check_kw_attrs(node, res[0], f"'{method}({res[0]})'")

    def _check_kw_attrs(self, node: ast.Call, kind: str, what: str) -> None:
        required = self.schema.get(kind, ())
        if any(kw.arg is None for kw in node.keywords):
            return  # **attrs: can't see inside
        present = {kw.arg for kw in node.keywords}
        missing = [a for a in required if a not in present]
        if missing:
            self._emit("RT004", node.lineno,
                       f"{what} missing required attr(s) "
                       f"{missing} per SCHEMA")

    def _check_spans_kwarg(self, node: ast.Call) -> None:
        for kw in node.keywords:
            if kw.arg != "spans" or not isinstance(kw.value, ast.List):
                continue
            for elt in kw.value.elts:
                if not isinstance(elt, ast.Tuple) or len(elt.elts) != 4:
                    continue
                res = self._span_required(elt.elts[0], elt.lineno,
                                          "spans=[...] entry")
                if res is None:
                    continue
                kind, required = res
                if not required:
                    continue
                keys = self._attrs_keys(elt.elts[3])
                if keys is None:
                    continue  # unresolvable attrs expression
                missing = [a for a in required if a not in keys]
                if missing:
                    self._emit("RT004", elt.lineno,
                               f"spans entry '{kind}' missing required "
                               f"attr(s) {missing} per SCHEMA")

    def _attrs_keys(self, node: ast.AST) -> Optional[Set[str]]:
        keys = _dict_literal_keys(node)
        if keys is not None:
            return keys
        if isinstance(node, ast.Name) and self._local_dicts:
            return self._local_dicts[-1].get(node.id)
        return None


# ---------------------------------------------------------------------------
# Entry points


def lint_file(path: str, rel: Optional[str] = None,
              schema_pair=None) -> List[Finding]:
    rel = (rel or os.path.basename(path)).replace(os.sep, "/")
    with open(path, "r", encoding="utf-8") as fh:
        source = fh.read()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as exc:
        return [Finding("RT000", rel, exc.lineno or 0,
                        f"file does not parse: {exc.msg}")]
    if schema_pair is None:
        schema_pair = _load_schema()
    schema, consts = schema_pair
    allowed = any(
        rel == p or (p.endswith("/") and rel.startswith(p))
        for p in WALLCLOCK_ALLOWLIST)
    visitor = _FileLint(rel, source, schema, consts, allowed)
    visitor.visit(tree)
    return visitor.finish()


def default_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_tree(root: Optional[str] = None) -> List[Finding]:
    root = os.path.abspath(root or default_root())
    findings: List[Finding] = []
    schema_pair = _load_schema()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames
                             if d not in ("__pycache__", ".git"))
        for fname in sorted(filenames):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            if rel.startswith("analysis/"):
                continue  # the toolkit itself names the patterns it hunts
            findings.extend(lint_file(path, rel, schema_pair))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings
