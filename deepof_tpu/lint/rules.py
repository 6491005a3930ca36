"""The five graftlint rules (DESIGN.md "Static analysis").

Each rule encodes a project invariant that previously lived in reviewer
vigilance; every one of them has at least one shipped-and-later-fixed
defect behind it (see the per-rule docstrings). Rules are pure
functions over one parsed file — no cross-file state beyond the two
jax-free schema imports (`obs.registry`, `core.config`).
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Iterator

from .core import Finding, FileContext, rule
from ..obs import registry as obs_registry

# --------------------------------------------------------------------
# rule: counter-registry
# --------------------------------------------------------------------


def _literal_stat_keys(tree: ast.AST) -> Iterator[tuple[str, ast.AST]]:
    """(key, node) for every string-literal stats-dict WRITE with a
    linted prefix: dict-literal keys and `d["key"] = ...` subscript
    assignments. Reads (`.get("serve_x")`, membership tuples) are
    deliberately not matched — the registry polices what gets WRITTEN
    into a stats block; the merge paths are registry-driven and have no
    per-key read lists left to drift."""
    prefixes = obs_registry.LINTED_PREFIXES
    for node in ast.walk(tree):
        if isinstance(node, ast.Dict):
            for key in node.keys:
                if (isinstance(key, ast.Constant)
                        and isinstance(key.value, str)
                        and key.value.startswith(prefixes)):
                    yield key.value, key
        elif isinstance(node, ast.Assign):
            for tgt in node.targets:
                if (isinstance(tgt, ast.Subscript)
                        and isinstance(tgt.slice, ast.Constant)
                        and isinstance(tgt.slice.value, str)
                        and tgt.slice.value.startswith(prefixes)):
                    yield tgt.slice.value, tgt.slice


@rule("counter-registry",
      "every serve_*/fleet_*/elastic_*/data_*/fault_* stats key written "
      "anywhere must be declared in obs/registry.py")
def counter_registry(ctx: FileContext) -> Iterator[Finding]:
    """PRs 4/6/7/9/10/11 each hand-patched a merge list after a new
    counter silently missed the heartbeat/analyze/tail/scrape surface.
    The merge paths are now driven from obs/registry.py, so the ONE
    remaining way to lose a counter is writing a key the registry does
    not know — which is exactly what this rule makes a CI failure."""
    if ctx.path.endswith(("obs/registry.py", "obs\\registry.py")):
        return  # the schema's own declarations are not "writes"
    for key, node in _literal_stat_keys(ctx.tree):
        if obs_registry.lookup(key) is None:
            yield Finding(
                "counter-registry", ctx.path, node.lineno, node.col_offset,
                f"stats key {key!r} is not declared in obs/registry.py — "
                "register it (name, merge kind, owner) so the fleet "
                "scrape and analyze/tail merges pick it up")


# --------------------------------------------------------------------
# rule: config-key
# --------------------------------------------------------------------

#: methods legal on any frozen config dataclass
_CONFIG_METHODS = frozenset(("replace",))


def _config_schema():
    """{class name -> {field -> nested class name | None}} for the whole
    config tree, resolved once from the real dataclasses (so this rule
    can never drift from core/config.py)."""
    import typing

    from ..core import config as config_mod
    from ..resilience.faults import FaultConfig

    classes: dict[str, type] = {"FaultConfig": FaultConfig}
    for name in dir(config_mod):
        obj = getattr(config_mod, name)
        if isinstance(obj, type) and dataclasses.is_dataclass(obj):
            classes[name] = obj
    schema: dict[str, dict[str, str | None]] = {}
    for cname, cls in classes.items():
        hints = typing.get_type_hints(cls)
        fields: dict[str, str | None] = {}
        for f in dataclasses.fields(cls):
            hint = hints.get(f.name)
            fields[f.name] = (hint.__name__
                              if isinstance(hint, type)
                              and dataclasses.is_dataclass(hint) else None)
        schema[cname] = fields
    return schema


_SCHEMA_CACHE: dict | None = None


def _schema() -> dict:
    global _SCHEMA_CACHE
    if _SCHEMA_CACHE is None:
        _SCHEMA_CACHE = _config_schema()
    return _SCHEMA_CACHE


def _annotation_class(node: ast.AST | None, schema: dict) -> str | None:
    """Config class named by an annotation: `ExperimentConfig`,
    `"ExperimentConfig"`, `X | None`, `Optional[X]`."""
    if node is None:
        return None
    if isinstance(node, ast.Name) and node.id in schema:
        return node.id
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        name = node.value.strip().split(".")[-1]
        return name if name in schema else None
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.BitOr):
        return (_annotation_class(node.left, schema)
                or _annotation_class(node.right, schema))
    if isinstance(node, ast.Subscript):  # Optional[X]
        return _annotation_class(node.slice, schema)
    if isinstance(node, ast.Attribute):  # config.ExperimentConfig
        return node.attr if node.attr in schema else None
    return None


def _chain(node: ast.Attribute) -> tuple[ast.AST, list[str]]:
    """Attribute chain -> (base node, [attr names outermost-last])."""
    attrs: list[str] = []
    cur: ast.AST = node
    while isinstance(cur, ast.Attribute):
        attrs.append(cur.attr)
        cur = cur.value
    attrs.reverse()
    return cur, attrs


def _resolve_chain(start: str, attrs: list[str],
                   schema: dict) -> tuple[str | None, str | None]:
    """Walk `attrs` from config class `start`.

    Returns (error_attr, final_class): error_attr is the first attr
    that is not a field (None = chain valid); final_class is the config
    class the full chain lands on (None when it ends at a leaf field or
    a method)."""
    cls: str | None = start
    for a in attrs:
        if cls is None:
            return None, None  # past a leaf: not ours to judge
        fields = schema[cls]
        if a in fields:
            cls = fields[a]
        elif a in _CONFIG_METHODS or a.startswith("__"):
            return None, None
        else:
            return a, None
    return None, cls


class _ConfigScope(ast.NodeVisitor):
    """Per-function validation scope: parameter/alias roots + chain
    checks. Nested defs share the parent's roots (closures read them)."""

    def __init__(self, ctx: FileContext, schema: dict,
                 roots: dict[str, str], self_attrs: dict[str, str]):
        self.ctx = ctx
        self.schema = schema
        self.roots = dict(roots)        # local name -> config class
        self.self_attrs = self_attrs    # self.<attr> -> config class
        self.findings: list[Finding] = []
        self._seen: set[int] = set()

    # ------------------------------------------------- chain resolution
    def _root_class(self, base: ast.AST,
                    attrs: list[str]) -> tuple[str | None, list[str]]:
        """(config class, remaining attrs) for a chain's base."""
        if isinstance(base, ast.Name):
            cls = self.roots.get(base.id)
            if cls is not None:
                return cls, attrs
        if (isinstance(base, ast.Name) and base.id == "self" and attrs):
            cls = self.self_attrs.get(attrs[0])
            if cls is not None:
                return cls, attrs[1:]
        return None, attrs

    def _check(self, node: ast.Attribute) -> tuple[str | None, bool]:
        """Validate one full chain; returns (final config class, known)
        and records a finding on the first unknown field."""
        base, attrs = _chain(node)
        for sub in ast.walk(node):
            if isinstance(sub, ast.Attribute):
                self._seen.add(id(sub))
        cls, attrs = self._root_class(base, attrs)
        if cls is None:
            return None, False
        bad, final = _resolve_chain(cls, attrs, self.schema)
        if bad is not None:
            self.findings.append(Finding(
                "config-key", self.ctx.path, node.lineno, node.col_offset,
                f"{cls}.{'.'.join(attrs)}: {bad!r} is not a declared "
                f"field on the config path (typo'd config access would "
                "silently read nothing at runtime)"))
        return final, True

    # ------------------------------------------------------- visitors
    def visit_Attribute(self, node: ast.Attribute) -> None:
        if id(node) not in self._seen:
            self._check(node)
        self.generic_visit(node)

    def visit_Assign(self, node: ast.Assign) -> None:
        # alias tracking: `sc = cfg.serve.session` makes `sc` a root
        self.generic_visit(node)
        final: str | None = None
        known = False
        if isinstance(node.value, ast.Attribute):
            final, known = (self._final_of(node.value))
        elif isinstance(node.value, ast.Name):
            final = self.roots.get(node.value.id)
            known = final is not None
        for tgt in node.targets:
            if isinstance(tgt, ast.Name):
                if final is not None:
                    self.roots[tgt.id] = final
                elif known is False and tgt.id in self.roots:
                    del self.roots[tgt.id]  # rebound to something else
            elif (isinstance(tgt, ast.Attribute)
                  and isinstance(tgt.value, ast.Name)
                  and tgt.value.id == "self" and final is not None):
                self.self_attrs[tgt.attr] = final

    def _final_of(self, node: ast.Attribute) -> tuple[str | None, bool]:
        base, attrs = _chain(node)
        cls, attrs = self._root_class(base, attrs)
        if cls is None:
            return None, False
        bad, final = _resolve_chain(cls, attrs, self.schema)
        return (final, True) if bad is None else (None, True)


def _collect_roots(fn: ast.AST, schema: dict) -> dict[str, str]:
    """Config-typed roots from a function's signature: annotations win;
    the bare names `cfg`/`config` and `<section>_cfg` are conventions
    this codebase follows everywhere."""
    roots: dict[str, str] = {}
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return roots
    section_classes = {f"{name}_cfg": cls
                       for name, cls in schema["ExperimentConfig"].items()
                       if cls is not None}
    args = list(fn.args.posonlyargs) + list(fn.args.args) \
        + list(fn.args.kwonlyargs)
    for a in args:
        cls = _annotation_class(a.annotation, schema)
        if cls is not None:
            roots[a.arg] = cls
        elif a.annotation is None:
            if a.arg in ("cfg", "config"):
                roots[a.arg] = "ExperimentConfig"
            elif a.arg in section_classes:
                roots[a.arg] = section_classes[a.arg]
    return roots


def _self_attr_aliases(cls_node: ast.ClassDef,
                       schema: dict) -> dict[str, str]:
    """{self.<attr> -> config class} from every `self.x = <chain>`
    assignment in the class (two-pass: methods may be defined before
    __init__'s aliases lexically)."""
    out: dict[str, str] = {}
    for method in cls_node.body:
        if not isinstance(method, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        roots = _collect_roots(method, schema)
        for node in ast.walk(method):
            if not isinstance(node, ast.Assign):
                continue
            final: str | None = None
            if isinstance(node.value, ast.Name):
                final = roots.get(node.value.id)
            elif isinstance(node.value, ast.Attribute):
                base, attrs = _chain(node.value)
                if isinstance(base, ast.Name) and base.id in roots:
                    bad, fin = _resolve_chain(roots[base.id], attrs, schema)
                    final = fin if bad is None else None
            if final is None:
                continue
            for tgt in node.targets:
                if (isinstance(tgt, ast.Attribute)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id == "self"):
                    out[tgt.attr] = final
    return out


@rule("config-key",
      "attribute access on config dataclasses must resolve to a "
      "declared field")
def config_key(ctx: FileContext) -> Iterator[Finding]:
    """`config_from_dict` rejects typo'd KEYS at load time, but a typo'd
    READ (`cfg.serve.sesion.ttl_s`) only explodes when the line runs —
    which for error paths is production. This rule resolves every
    attribute chain rooted at a config-typed name against the real
    dataclass tree, so the typo is a lint finding, not a 3 a.m.
    AttributeError."""
    schema = _schema()

    def lint_function(fn, extra_roots, self_attrs):
        roots = {**extra_roots, **_collect_roots(fn, schema)}
        scope = _ConfigScope(ctx, schema, roots, self_attrs)
        for stmt in fn.body:
            scope.visit(stmt)
        return scope.findings

    for node in ctx.tree.body if isinstance(ctx.tree, ast.Module) else []:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield from lint_function(node, {}, {})
        elif isinstance(node, ast.ClassDef):
            self_attrs = _self_attr_aliases(node, schema)
            for method in node.body:
                if isinstance(method,
                              (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield from lint_function(method, {}, self_attrs)


# --------------------------------------------------------------------
# rule: determinism
# --------------------------------------------------------------------

#: module subtrees under the determinism contract (derive_batch_rng's
#: bit-identical-stream pin, PRs 2/4/8): path fragments relative to the
#: PACKAGE root — matched against the path from the `deepof_tpu/`
#: segment on, never against the checkout prefix (a repo cloned under
#: /data/... must not put every file in scope).
_DETERMINISM_SCOPES = (
    "/data/", "/models/", "/losses/", "/ops/", "/train/step.py",
)


def _package_relative(path: str) -> str | None:
    """The path from the `deepof_tpu/` package segment on (leading
    slash kept so scope fragments anchor on directory boundaries), or
    None for files outside the package — the determinism contract is
    package-internal by definition."""
    norm = path.replace("\\", "/")
    idx = norm.rfind("/deepof_tpu/")
    if idx >= 0:
        return norm[idx:]
    if norm.startswith("deepof_tpu/"):
        return "/" + norm
    return None

#: seeded constructors: legal when called WITH at least one argument
_SEEDED_CTORS = frozenset(("RandomState", "default_rng", "Generator",
                           "Random", "SeedSequence", "PRNGKey", "key"))


def _dotted(node: ast.AST) -> str | None:
    """`a.b.c` -> "a.b.c" for plain name/attribute chains."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


@rule("determinism",
      "no unseeded random.*/np.random.*/time.time() in data/models/"
      "losses/ops/train-step modules")
def determinism(ctx: FileContext) -> Iterator[Finding]:
    """The pinned contract: the sample/augment stream is bit-identical
    for any worker count and any elastic
    re-shard (derive_batch_rng). One module-level `np.random.shuffle`
    or `time.time()`-derived seed silently voids all of it. Only the
    contract-bearing module subtrees are in scope; obs/timing helpers
    (`time.perf_counter`, `time.monotonic`) are always legal."""
    rel = _package_relative(ctx.path)
    if rel is None or not any(s in rel for s in _DETERMINISM_SCOPES):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        name = _dotted(node.func)
        if name is None:
            continue
        if name in ("time.time", "time.time_ns"):
            yield Finding(
                "determinism", ctx.path, node.lineno, node.col_offset,
                f"{name}() in a determinism-scoped module: wall-clock "
                "values void the bit-identical-stream contract (use "
                "time.perf_counter/monotonic for durations, or seed "
                "from config)")
            continue
        parts = name.split(".")
        unseeded = None
        if parts[0] == "random" and len(parts) == 2:
            unseeded = parts[1] not in _SEEDED_CTORS or not (
                node.args or node.keywords)
        elif (len(parts) >= 3 and parts[-3] in ("np", "numpy")
              and parts[-2] == "random"):
            unseeded = parts[-1] not in _SEEDED_CTORS or not (
                node.args or node.keywords)
        if unseeded:
            yield Finding(
                "determinism", ctx.path, node.lineno, node.col_offset,
                f"unseeded {name}() in a determinism-scoped module: "
                "draw from a derive_batch_rng-derived RandomState (or "
                "seed explicitly) so the stream stays bit-identical "
                "for any worker count")


# --------------------------------------------------------------------
# rule: jit-purity
# --------------------------------------------------------------------

_JIT_NAMES = frozenset(("jit", "pjit", "eval_shape"))
_JIT_ATTRS = frozenset(("jit", "pjit", "eval_shape", "scan"))


def _is_jit_expr(node: ast.AST) -> bool:
    """`jit` / `pjit` / `jax.jit` / `jax.lax.scan` / ... as a bare
    expression (no call parens)."""
    if isinstance(node, ast.Name):
        return node.id in _JIT_NAMES
    return (isinstance(node, ast.Attribute) and node.attr in _JIT_ATTRS
            and (_dotted(node) or "").split(".")[0] in ("jax", "lax"))


def _jit_callees(tree: ast.AST) -> Iterator[tuple[ast.AST, ast.AST]]:
    """(jit-like site, traced-function node) pairs, covering BOTH forms
    this repo uses: the call form `jax.jit(fn)` / `lax.scan(fn, ...)`
    and the decorator form `@jax.jit` / `@partial(jax.jit, ...)` —
    the latter is the dominant idiom in the model/ops code, and a rule
    that misses it would pass exactly the prints it advertises to
    catch."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and node.args \
                and _is_jit_expr(node.func):
            yield node, node.args[0]
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _is_jit_expr(dec):
                    yield dec, node  # @jax.jit
                elif isinstance(dec, ast.Call):
                    if _is_jit_expr(dec.func):
                        yield dec, node  # @jax.jit(static_argnums=...)
                    elif ((_dotted(dec.func) or "").split(".")[-1]
                          == "partial" and dec.args
                          and _is_jit_expr(dec.args[0])):
                        yield dec, node  # @partial(jax.jit, ...)


def _impure_statements(fn_node: ast.AST) -> Iterator[tuple[ast.AST, str]]:
    """(node, what) for prints, file opens, and module-global mutation
    inside a traced function body (nested defs included)."""
    global_names: set[str] = set()
    for node in ast.walk(fn_node):
        if isinstance(node, ast.Global):
            global_names.update(node.names)
    for node in ast.walk(fn_node):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
            if node.func.id == "print":
                yield node, "calls print()"
            elif node.func.id == "open":
                yield node, "opens a file"
        elif isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for tgt in targets:
                if isinstance(tgt, ast.Name) and tgt.id in global_names:
                    yield node, f"mutates module global {tgt.id!r}"


@rule("jit-purity",
      "functions passed to jit/pjit/lax.scan/eval_shape must not "
      "print, open files, or mutate module globals")
def jit_purity(ctx: FileContext) -> Iterator[Finding]:
    """Side effects in traced code run ONCE, at trace time, then never
    again — a print inside a jitted step 'works' in the first dispatch
    and silently vanishes for the rest of the run (and a mutated
    global desynchronizes retrace decisions across processes). Only
    statically resolvable callees (same-module defs, lambdas) are
    checked; `jax.debug.print` is the supported escape hatch."""
    # module-level function table for resolving Name references
    defs: dict[str, ast.AST] = {}
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, node)
    for call, arg in _jit_callees(ctx.tree):
        target: ast.AST | None = None
        label = ""
        if isinstance(arg, (ast.FunctionDef, ast.AsyncFunctionDef)):
            target, label = arg, arg.name  # decorator form
        elif isinstance(arg, ast.Lambda):
            target, label = arg, "lambda"
        elif isinstance(arg, ast.Name) and arg.id in defs:
            target, label = defs[arg.id], arg.id
        if target is None:
            continue
        for node, what in _impure_statements(target):
            yield Finding(
                "jit-purity", ctx.path, node.lineno, node.col_offset,
                f"traced function {label!r} (passed to jit-like call at "
                f"line {call.lineno}) {what}: side effects in traced "
                "code run once at trace time and never again (use "
                "jax.debug.print / host_callback, or hoist the effect)")


# --------------------------------------------------------------------
# rule: lock-discipline
# --------------------------------------------------------------------

_LOCK_CTORS = frozenset(("Lock", "RLock", "Condition"))


def _lock_attrs(cls_node: ast.ClassDef) -> set[str]:
    """self.<attr> names assigned a threading.Lock/RLock/Condition
    anywhere in the class."""
    out: set[str] = set()
    for node in ast.walk(cls_node):
        if not isinstance(node, ast.Assign):
            continue
        v = node.value
        ctor = None
        if isinstance(v, ast.Call):
            if isinstance(v.func, ast.Attribute):
                ctor = v.func.attr
            elif isinstance(v.func, ast.Name):
                ctor = v.func.id
        if ctor not in _LOCK_CTORS:
            continue
        for tgt in node.targets:
            if (isinstance(tgt, ast.Attribute)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id == "self"):
                out.add(tgt.attr)
    return out


def _spawns_thread(cls_node: ast.ClassDef) -> bool:
    for node in ast.walk(cls_node):
        if isinstance(node, ast.Call):
            name = _dotted(node.func)
            if name in ("threading.Thread", "Thread") \
                    or (name or "").endswith(".Thread"):
                return True
    return False


def _self_writes(method: ast.AST, locks: set[str]):
    """(attr, node, locked) for every `self.<attr> = ...` /
    `self.<attr> += ...` in the method, where `locked` means the write
    is lexically inside a `with self.<lock>:` block."""

    def walk(node: ast.AST, locked: bool):
        if isinstance(node, ast.With):
            holds = any(
                isinstance(item.context_expr, ast.Attribute)
                and isinstance(item.context_expr.value, ast.Name)
                and item.context_expr.value.id == "self"
                and item.context_expr.attr in locks
                for item in node.items)
            for child in node.body:
                walk(child, locked or holds)
            return
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for tgt in targets:
                if (isinstance(tgt, ast.Attribute)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id == "self"):
                    yield_list.append((tgt.attr, node, locked))
        for child in ast.iter_child_nodes(node):
            walk(child, locked)

    yield_list: list = []
    walk(method, False)
    return yield_list


@rule("lock-discipline",
      "in thread-spawning classes, self attributes written from "
      "multiple methods must be written under the class lock")
def lock_discipline(ctx: FileContext) -> Iterator[Finding]:
    """The PR 10 torn-heartbeat race in one rule: a class that spawns a
    thread AND owns a lock has declared its mutable state shared;
    a `self._x` written from two different methods (one of them on the
    spawned thread) without the lock is a data race — GIL atomicity
    does not cover read-modify-write or multi-field invariants.
    Writes in __init__ are exempt (they happen before the thread
    exists). Deliberate lock-free handoffs (atomic rebinds, Events)
    carry a waiver with the reason."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.ClassDef):
            continue
        locks = _lock_attrs(node)
        if not locks or not _spawns_thread(node):
            continue
        writes_by_attr: dict[str, list] = {}
        for method in node.body:
            if not isinstance(method,
                              (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if method.name == "__init__":
                continue
            for attr, wnode, locked in _self_writes(method, locks):
                if attr in locks:
                    continue
                writes_by_attr.setdefault(attr, []).append(
                    (method.name, wnode, locked))
        for attr, writes in writes_by_attr.items():
            methods = {m for m, _, _ in writes}
            if len(methods) < 2:
                continue
            for mname, wnode, locked in writes:
                if not locked:
                    yield Finding(
                        "lock-discipline", ctx.path, wnode.lineno,
                        wnode.col_offset,
                        f"{node.name}.{mname} writes self.{attr} outside "
                        f"the class lock, but self.{attr} is also "
                        f"written by "
                        f"{sorted(methods - {mname}) or [mname]} — in a "
                        "thread-spawning class that is a data race "
                        "(hold the lock, or waive with the reason the "
                        "lock-free write is safe)")
