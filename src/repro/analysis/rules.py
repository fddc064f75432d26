"""The jaxlint rule catalog — each rule encodes a bug class this repo has
actually had (or explicitly guards against).  See the module docstring of
``repro.analysis`` for how to add one.

R000 suppression-without-justification  accepted risk must say why
R001 prng-key-reuse                     same key consumed twice
R002 host-sync-in-hot-loop              the PR-5 per-metric sync class
R003 mutable-closure-capture            the PR-2 NFT frozen-reference class
R004 python-control-flow-on-tracer      if/while on jnp-derived values
R005 donated-buffer-reuse               read-after-donate is a dead buffer
R006 recompile-hazard                   unhashable statics / jit-in-loop
R007 blocking-drain-in-dispatch-loop    sync on the just-dispatched step
"""
from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from repro.analysis.core import Finding, Module, Rule, register_rule, \
    rule_ids
from repro.analysis.scopes import FuncInfo, ScopeGraph, _donated_positions, \
    _enclosing_class, last_name, root_name, shallow_walk

# namespaces whose calls produce device values / tracers
_DEVICE_ROOTS = {"jnp", "lax", "pl"}
# jax.<first-attr> members that do NOT produce device values
_JAX_HOST = {"device_get", "tree_util", "tree", "debug", "config",
             "devices", "local_devices", "device_count", "make_mesh",
             "local_device_count", "default_backend", "make_jaxpr",
             "eval_shape", "ShapeDtypeStruct", "block_until_ready",
             "profiler", "sharding", "clear_caches", "tree_map",
             "tree_leaves", "tree_structure", "tree_flatten",
             "tree_unflatten"}
# array-method reductions: inside a traced scope, calling one on anything
# yields a tracer whatever the receiver is
_ARRAY_REDUCERS = {"any", "all", "sum", "mean", "max", "min", "prod",
                   "argmax", "argmin"}
# attribute reads that are static at trace time
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size"}

_SYNC_BUILTINS = {"float", "int", "bool", "complex"}
_SYNC_METHODS = {"item", "tolist"}

_SAMPLERS = {
    "normal", "uniform", "bernoulli", "randint", "bits", "categorical",
    "choice", "permutation", "gumbel", "exponential", "laplace", "logistic",
    "truncated_normal", "beta", "gamma", "dirichlet", "poisson",
    "rademacher", "cauchy", "multivariate_normal", "orthogonal", "ball",
    "loggamma", "maxwell", "split",
}
_KEY_PRODUCERS = {"PRNGKey", "key", "split", "fold_in", "wrap_key_data",
                  "clone"}
_UNHASHABLE_LITERALS = (ast.Dict, ast.List, ast.Set, ast.ListComp,
                        ast.SetComp, ast.DictComp, ast.GeneratorExp)


def _attr_chain(expr: ast.expr) -> List[str]:
    """["jax", "random", "normal"] for ``jax.random.normal``."""
    parts: List[str] = []
    while isinstance(expr, ast.Attribute):
        parts.append(expr.attr)
        expr = expr.value
    if isinstance(expr, ast.Name):
        parts.append(expr.id)
    return list(reversed(parts))


def _is_jax_random(func: ast.expr, name: str) -> bool:
    """Does ``func`` denote ``jax.random.<name>`` (or ``random.<name>``
    via ``from jax import random`` / ``jr.<name>``)?"""
    chain = _attr_chain(func)
    if not chain or chain[-1] != name:
        return False
    if chain[0] in ("np", "numpy", "nprandom"):
        return False
    return "random" in chain[:-1] or chain[0] == "jr"


def _device_call_kind(call: ast.Call) -> Optional[str]:
    """"dev" for a device-value-producing call, "fetched" for
    ``jax.device_get`` (host values, but straight off a transfer)."""
    chain = _attr_chain(call.func)
    if not chain:
        return None
    if chain[0] == "jax":
        if len(chain) >= 2 and chain[1] == "device_get":
            return "fetched"
        if len(chain) >= 2 and chain[1] in _JAX_HOST:
            return None
        return "dev"
    if chain[0] in _DEVICE_ROOTS:
        return "dev"
    return None


def _target_names(target: ast.expr) -> List[str]:
    return [e.id for e in ast.walk(target) if isinstance(e, ast.Name)]


def _terminates(stmts: List[ast.stmt]) -> bool:
    """Does control flow definitely leave this statement list?"""
    return any(isinstance(s, (ast.Return, ast.Raise, ast.Break,
                              ast.Continue)) for s in stmts)


def _local_names(fi: FuncInfo) -> Set[str]:
    """Parameter + locally-bound names of a function (shallow)."""
    node = fi.node
    names: Set[str] = set(fi.params)
    a = node.args
    for extra in ([a.vararg] if a.vararg else []) + \
                 ([a.kwarg] if a.kwarg else []) + list(a.kwonlyargs):
        names.add(extra.arg if not isinstance(extra, str) else extra)
    for n in shallow_walk(node):
        if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            tgts = n.targets if isinstance(n, ast.Assign) else [n.target]
            for t in tgts:
                names.update(_target_names(t))
        elif isinstance(n, (ast.For, ast.AsyncFor)):
            names.update(_target_names(n.target))
        elif isinstance(n, ast.comprehension):
            names.update(_target_names(n.target))
        elif isinstance(n, ast.withitem) and n.optional_vars is not None:
            names.update(_target_names(n.optional_vars))
        elif isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.ClassDef)):
            names.add(n.name)
        elif isinstance(n, ast.ExceptHandler) and n.name:
            names.add(n.name)
    return names


# =========================================================================
@register_rule
class R000SuppressionHygiene(Rule):
    id = "R000"
    name = "suppression-without-justification"
    rationale = ("a `# jaxlint: disable=` without a reason hides risk "
                 "silently; audits need the why next to the what")

    def check(self, module: Module, graph: ScopeGraph) -> Iterator[Finding]:
        known = set(rule_ids())
        for sup in module.suppressions:
            snippet = module.lines[sup.line - 1].strip() \
                if sup.line <= len(module.lines) else ""
            if not sup.rules:
                yield Finding(self.id, module.rel, sup.line, 0,
                              "jaxlint suppression names no rule ids "
                              "(expected `# jaxlint: disable=R0xx — "
                              "<reason>`)", snippet)
                continue
            bad = [r for r in sup.rules if r not in known]
            if bad:
                yield Finding(self.id, module.rel, sup.line, 0,
                              f"jaxlint suppression names unknown rule "
                              f"id(s) {bad}", snippet)
            if not sup.reason:
                yield Finding(self.id, module.rel, sup.line, 0,
                              f"jaxlint suppression of "
                              f"{','.join(sup.rules)} has no justification "
                              "— write `# jaxlint: disable=R0xx — "
                              "<reason>`", snippet)


# =========================================================================
@register_rule
class R001PrngKeyReuse(Rule):
    id = "R001"
    name = "prng-key-reuse"
    rationale = ("the same PRNG key consumed by two samplers yields "
                 "identical \"random\" draws — split/fold_in first")

    def check(self, module: Module, graph: ScopeGraph) -> Iterator[Finding]:
        for fi in graph.module_functions(module):
            if isinstance(fi.node, ast.Lambda):
                continue
            yield from self._check_func(module, fi)

    def _check_func(self, module: Module, fi: FuncInfo
                    ) -> Iterator[Finding]:
        key_names: Set[str] = {p for p in fi.params
                               if "key" in p.lower() or "rng" in p.lower()}
        counts: Dict[str, int] = {}
        reported: Set[int] = set()
        findings: List[Finding] = []

        def is_key_producer(value: ast.expr) -> bool:
            if isinstance(value, ast.Call):
                tail = last_name(value.func)
                if tail in _KEY_PRODUCERS and \
                        _is_jax_random(value.func, tail):
                    return True
            if isinstance(value, ast.Subscript):
                inner = value.value
                if isinstance(inner, ast.Name) and inner.id in key_names:
                    return True                  # rows of a key batch
            return False

        def consume(call: ast.Call) -> None:
            tail = last_name(call.func)
            if tail not in _SAMPLERS or not _is_jax_random(call.func, tail):
                return
            if not call.args:
                return
            arg = call.args[0]
            if isinstance(arg, ast.Name) and arg.id in key_names:
                counts[arg.id] = counts.get(arg.id, 0) + 1
                if counts[arg.id] > 1 and id(call) not in reported:
                    reported.add(id(call))
                    findings.append(self.finding(
                        module, call,
                        f"PRNG key `{arg.id}` is consumed again by "
                        f"jax.random.{tail} without an intervening "
                        "split/fold_in — identical draws"))

        def scan_calls(node: ast.AST) -> None:
            # shallow_walk yields descendants only — the expression itself
            # may already be the consuming Call
            if isinstance(node, ast.Call):
                consume(node)
            for n in shallow_walk(node):
                if isinstance(n, ast.Call):
                    consume(n)

        def exec_stmts(stmts: List[ast.stmt]) -> None:
            for s in stmts:
                if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                    continue
                if isinstance(s, ast.If):
                    scan_calls(s.test)
                    base = dict(counts)
                    exec_stmts(s.body)
                    after_body = dict(counts)
                    counts.clear()
                    counts.update(base)
                    exec_stmts(s.orelse)
                    if _terminates(s.orelse):
                        counts.clear()
                        counts.update(base)
                    if not _terminates(s.body):
                        # branch merge: max (a terminating branch — e.g.
                        # `if how == "uniform": return uniform(key)` —
                        # never reaches the fall-through consumption)
                        for k, v in after_body.items():
                            counts[k] = max(counts.get(k, 0), v)
                    continue
                if isinstance(s, (ast.For, ast.While)):
                    scan_calls(s.iter if isinstance(s, ast.For) else s.test)
                    exec_stmts(s.body)    # twice: loop-carried reuse
                    exec_stmts(s.body)
                    exec_stmts(s.orelse)
                    continue
                if isinstance(s, ast.Try):
                    exec_stmts(s.body)
                    for h in s.handlers:
                        exec_stmts(h.body)
                    exec_stmts(s.orelse)
                    exec_stmts(s.finalbody)
                    continue
                if isinstance(s, ast.With):
                    for item in s.items:
                        scan_calls(item.context_expr)
                    exec_stmts(s.body)
                    continue
                if isinstance(s, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                    if s.value is not None:
                        scan_calls(s.value)
                    produced = s.value is not None and \
                        is_key_producer(s.value)
                    tgts = (s.targets if isinstance(s, ast.Assign)
                            else [s.target])
                    for t in tgts:
                        for name in _target_names(t):
                            counts[name] = 0     # reassignment resets
                            if produced:
                                key_names.add(name)
                    continue
                scan_calls(s)

        exec_stmts(fi.node.body)
        yield from findings


# =========================================================================
@register_rule
class R002HostSyncInHotLoop(Rule):
    id = "R002"
    name = "host-sync-in-hot-loop"
    rationale = ("per-value float()/.item()/device_get in a step loop "
                 "serializes host/device round-trips (the PR-5 class: ~8 "
                 "syncs per train step) — fetch once, convert once")

    #: conversions of device/fetched values inside one loop body are only
    #: flagged from this count on (a single fetch per iteration is the
    #: sanctioned pattern; the bug class is per-METRIC fan-out)
    LOOP_SYNC_THRESHOLD = 2

    def check(self, module: Module, graph: ScopeGraph) -> Iterator[Finding]:
        for fi in graph.module_functions(module):
            if graph.is_traced(fi) or isinstance(fi.node, ast.Lambda):
                continue
            yield from self._check_body(module, graph, fi, fi.node.body)
        yield from self._check_body(module, graph, None, module.tree.body)

    # ------------------------------------------------------------------
    def _check_body(self, module: Module, graph: ScopeGraph,
                    fi: Optional[FuncInfo], body: List[ast.stmt]
                    ) -> Iterator[Finding]:
        env: Dict[str, str] = {}           # name -> "dev" | "fetched"
        findings: List[Finding] = []
        reported: Set[int] = set()

        def kind_of(e: ast.expr) -> Optional[str]:
            if isinstance(e, ast.Name):
                return env.get(e.id)
            if isinstance(e, ast.Call):
                k = _device_call_kind(e)
                if k:
                    return k
                tgts = graph.resolve_call(e, module, fi)
                if any(graph.is_traced(t) for t in tgts):
                    return "dev"          # direct call into a jitted scope
                if isinstance(e.func, ast.Attribute):
                    return kind_of(e.func.value)   # m.items(), x.copy()
                return None
            if isinstance(e, ast.Attribute):
                if e.attr in _STATIC_ATTRS:
                    return None
                return kind_of(e.value)
            if isinstance(e, ast.Subscript):
                return kind_of(e.value)
            if isinstance(e, ast.BinOp):
                return _max_kind(kind_of(e.left), kind_of(e.right))
            if isinstance(e, ast.UnaryOp):
                return kind_of(e.operand)
            if isinstance(e, (ast.Tuple, ast.List)):
                k = None
                for el in e.elts:
                    k = _max_kind(k, kind_of(el))
                return k
            if isinstance(e, ast.IfExp):
                return _max_kind(kind_of(e.body), kind_of(e.orelse))
            if isinstance(e, ast.Starred):
                return kind_of(e.value)
            return None

        def contains_dev_call(e: ast.expr) -> bool:
            return any(isinstance(n, ast.Call)
                       and _device_call_kind(n) == "dev"
                       for n in ast.walk(e))

        def sync_candidates(node: ast.AST):
            """(call, arg_expr, what) for sync-shaped calls under node."""
            for n in shallow_walk(node):
                if not isinstance(n, ast.Call):
                    continue
                if isinstance(n.func, ast.Name) \
                        and n.func.id in _SYNC_BUILTINS \
                        and len(n.args) == 1:
                    yield n, n.args[0], n.func.id + "()"
                elif isinstance(n.func, ast.Attribute) \
                        and n.func.attr in _SYNC_METHODS and not n.args:
                    yield n, n.func.value, "." + n.func.attr + "()"
                elif isinstance(n.func, ast.Attribute) \
                        and n.func.attr in ("asarray", "array") \
                        and root_name(n.func) in ("np", "numpy") \
                        and n.args:
                    yield n, n.args[0], "np." + n.func.attr + "()"

        def flag(call: ast.Call, msg: str) -> None:
            if id(call) not in reported:
                reported.add(id(call))
                findings.append(self.finding(module, call, msg))

        def check_stmt_syncs(s: ast.stmt, in_loop: bool) -> None:
            # direct sync fused onto device compute: flagged anywhere
            for call, arg, what in sync_candidates(s):
                if contains_dev_call(arg):
                    flag(call, f"{what} on a freshly computed device value "
                               "forces an extra host sync — compute from "
                               "an already-fetched array (np) or keep it "
                               "on device")

        def loop_syncs(loop: ast.stmt) -> None:
            """Per-value conversions + repeated device_gets in one loop."""
            tainted: List[Tuple[ast.Call, str, str]] = []
            for call, arg, what in sync_candidates(loop):
                k = kind_of(arg)
                if k is None and contains_dev_call(arg):
                    k = "dev"
                if k is not None:
                    tainted.append((call, what, k))
            if len(tainted) >= self.LOOP_SYNC_THRESHOLD:
                for call, what, k in tainted:
                    origin = ("on a device value" if k == "dev" else
                              "on an already-fetched value")
                    flag(call, f"{what} {origin} inside a hot loop — "
                               f"{len(tainted)} per-value host conversions "
                               "per iteration; fetch the whole pytree with "
                               "ONE jax.device_get and convert at the "
                               "transfer site")
            gets = [n for n in shallow_walk(loop)
                    if isinstance(n, ast.Call)
                    and _device_call_kind(n) == "fetched"]
            if len(gets) >= 2:
                for g in gets:
                    flag(g, f"{len(gets)} jax.device_get transfers per "
                            "loop iteration — batch them into one "
                            "device_get of a tuple/dict")

        def walk_stmts(stmts: List[ast.stmt], in_loop: bool) -> None:
            for s in stmts:
                if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                    continue
                if isinstance(s, (ast.For, ast.While)):
                    if isinstance(s, ast.For):
                        k = kind_of(s.iter)
                        if k:
                            for name in _target_names(s.target):
                                env[name] = k
                    walk_stmts(s.body, True)   # pass 1: establish taint
                    if in_loop is False:
                        loop_syncs(s)          # ...then scan the loop
                    check_stmt_syncs(s, True)
                    walk_stmts(s.body, True)   # pass 2: loop-carried
                    walk_stmts(s.orelse, in_loop)
                    continue
                check_stmt_syncs(s, in_loop)
                if in_loop:
                    # nested-loop bodies re-checked with taint present
                    pass
                if isinstance(s, (ast.Assign, ast.AnnAssign)):
                    if s.value is not None:
                        k = kind_of(s.value)
                        tgts = (s.targets if isinstance(s, ast.Assign)
                                else [s.target])
                        for t in tgts:
                            for name in _target_names(t):
                                if k:
                                    env[name] = k
                                else:
                                    env.pop(name, None)
                elif isinstance(s, ast.If):
                    walk_stmts(s.body, in_loop)
                    walk_stmts(s.orelse, in_loop)
                elif isinstance(s, ast.Try):
                    walk_stmts(s.body, in_loop)
                    for h in s.handlers:
                        walk_stmts(h.body, in_loop)
                    walk_stmts(s.orelse, in_loop)
                    walk_stmts(s.finalbody, in_loop)
                elif isinstance(s, ast.With):
                    walk_stmts(s.body, in_loop)

        walk_stmts(body, False)
        yield from findings


def _max_kind(a: Optional[str], b: Optional[str]) -> Optional[str]:
    order = {None: 0, "fetched": 1, "dev": 2}
    return a if order[a] >= order[b] else b


# =========================================================================
@register_rule
class R003MutableClosureCapture(Rule):
    id = "R003"
    name = "mutable-closure-capture"
    rationale = ("jit bakes closure-captured values in as trace-time "
                 "constants: later mutations are invisible (the PR-2 NFT "
                 "frozen-reference bug) — thread them as arguments")

    def check(self, module: Module, graph: ScopeGraph) -> Iterator[Finding]:
        for fi in graph.module_functions(module):
            if not graph.is_traced(fi):
                continue
            yield from self._check_self_reads(module, graph, fi)
            if fi.parent is not None:
                yield from self._check_nonlocal(module, graph, fi)

    def _check_self_reads(self, module: Module, graph: ScopeGraph,
                          fi: FuncInfo) -> Iterator[Finding]:
        cls_name = _enclosing_class(fi)
        if not cls_name:
            return
        own_writes: Set[str] = set()
        reads: List[Tuple[str, ast.Attribute]] = []
        seen_attrs: Set[str] = set()
        for n in shallow_walk(fi.node):
            if isinstance(n, ast.Attribute) and \
                    isinstance(n.value, ast.Name) and n.value.id == "self":
                if isinstance(n.ctx, ast.Store):
                    own_writes.add(n.attr)
                elif n.attr not in seen_attrs:
                    seen_attrs.add(n.attr)
                    reads.append((n.attr, n))
        for attr, node in reads:
            if attr in own_writes:
                continue       # this function IS the mutation site
            writers = graph.family_attr_writers(cls_name, attr)
            writers -= {"__init__", "__post_init__", fi.name}
            if writers:
                yield self.finding(
                    module, node,
                    f"traced scope `{fi.qualname}` reads `self.{attr}`, "
                    f"which {sorted(writers)} mutate after __init__ — jit "
                    "captures the trace-time value as a constant and "
                    "never sees the update; pass it as an argument "
                    "(update_extras-style)")

    def _check_nonlocal(self, module: Module, graph: ScopeGraph,
                        fi: FuncInfo) -> Iterator[Finding]:
        local = _local_names(fi)
        explicit_nonlocal: Set[str] = {
            name for n in shallow_walk(fi.node)
            if isinstance(n, ast.Nonlocal) for name in n.names}
        free_reads: Dict[str, ast.Name] = {}
        for n in shallow_walk(fi.node):
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load) \
                    and n.id not in local \
                    and n.id not in explicit_nonlocal \
                    and n.id not in free_reads:
                free_reads[n.id] = n
        parent = fi.parent
        def_line = fi.node.lineno
        while parent is not None:
            for n in shallow_walk(parent.node):
                if not isinstance(n, (ast.Assign, ast.AugAssign)):
                    continue
                if n.lineno <= def_line:
                    continue
                tgts = (n.targets if isinstance(n, ast.Assign)
                        else [n.target])
                for t in tgts:
                    for name in _target_names(t):
                        if name in free_reads and name != fi.name:
                            yield self.finding(
                                module, free_reads.pop(name),
                                f"traced closure `{fi.qualname}` captures "
                                f"`{name}`, reassigned at line {n.lineno} "
                                "after the definition — the trace keeps "
                                "the old value; pass it as an argument")
            parent = parent.parent


# =========================================================================
@register_rule
class R004PythonControlFlowOnTracer(Rule):
    id = "R004"
    name = "python-control-flow-on-tracer"
    rationale = ("`if`/`while` on a jnp-derived value inside a traced "
                 "scope raises at trace time (or silently specializes) — "
                 "use lax.cond/lax.select/jnp.where")

    def check(self, module: Module, graph: ScopeGraph) -> Iterator[Finding]:
        for fi in graph.module_functions(module):
            if not graph.is_traced(fi) or isinstance(fi.node, ast.Lambda):
                continue
            yield from self._check_func(module, fi)

    def _check_func(self, module: Module, fi: FuncInfo
                    ) -> Iterator[Finding]:
        env: Set[str] = set()
        findings: List[Finding] = []
        reported: Set[int] = set()

        def tainted(e: ast.expr) -> bool:
            if isinstance(e, ast.Name):
                return e.id in env
            if isinstance(e, ast.Call):
                if _device_call_kind(e) == "dev":
                    return True
                if isinstance(e.func, ast.Attribute) \
                        and e.func.attr in _ARRAY_REDUCERS \
                        and tainted(e.func.value):
                    return True
                return False
            if isinstance(e, ast.Attribute):
                if e.attr in _STATIC_ATTRS:
                    return False
                return tainted(e.value)
            if isinstance(e, ast.Subscript):
                return tainted(e.value)
            if isinstance(e, ast.Compare):
                if all(isinstance(op, (ast.Is, ast.IsNot)) for op in e.ops):
                    return False           # `is None` checks are static
                return tainted(e.left) or any(tainted(c)
                                              for c in e.comparators)
            if isinstance(e, ast.BoolOp):
                return any(tainted(v) for v in e.values)
            if isinstance(e, ast.BinOp):
                return tainted(e.left) or tainted(e.right)
            if isinstance(e, ast.UnaryOp):
                return tainted(e.operand)
            if isinstance(e, (ast.Tuple, ast.List)):
                return any(tainted(el) for el in e.elts)
            if isinstance(e, ast.IfExp):
                return tainted(e.body) or tainted(e.orelse)
            return False

        def flag(node: ast.AST, what: str) -> None:
            if id(node) not in reported:
                reported.add(id(node))
                findings.append(self.finding(
                    module, node,
                    f"Python `{what}` on a traced (jnp-derived) value "
                    f"inside traced scope `{fi.qualname}` — this "
                    "concretizes a tracer; use lax.cond / lax.while_loop "
                    "/ jnp.where"))

        def walk_stmts(stmts: List[ast.stmt]) -> None:
            for s in stmts:
                if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                    continue
                if isinstance(s, (ast.Assign, ast.AnnAssign)):
                    if s.value is not None:
                        is_t = tainted(s.value)
                        tgts = (s.targets if isinstance(s, ast.Assign)
                                else [s.target])
                        for t in tgts:
                            for name in _target_names(t):
                                (env.add if is_t else env.discard)(name)
                elif isinstance(s, ast.AugAssign):
                    if tainted(s.value):
                        env.update(_target_names(s.target))
                elif isinstance(s, ast.If):
                    if tainted(s.test):
                        flag(s, "if")
                    walk_stmts(s.body)
                    walk_stmts(s.orelse)
                elif isinstance(s, ast.While):
                    if tainted(s.test):
                        flag(s, "while")
                    walk_stmts(s.body)
                    walk_stmts(s.body)
                elif isinstance(s, ast.For):
                    walk_stmts(s.body)
                    walk_stmts(s.body)
                    walk_stmts(s.orelse)
                elif isinstance(s, ast.Try):
                    walk_stmts(s.body)
                    for h in s.handlers:
                        walk_stmts(h.body)
                    walk_stmts(s.orelse)
                    walk_stmts(s.finalbody)
                elif isinstance(s, ast.With):
                    walk_stmts(s.body)
                # assert/return/expr: only if/while are the hazard

        walk_stmts(fi.node.body)
        yield from findings


# =========================================================================
@register_rule
class R005DonatedBufferReuse(Rule):
    id = "R005"
    name = "donated-buffer-reuse"
    rationale = ("an argument passed through a donate_argnums position is "
                 "deallocated by XLA — reading it afterwards returns "
                 "garbage or raises")

    def check(self, module: Module, graph: ScopeGraph) -> Iterator[Finding]:
        donators = self._class_donators(module, graph)
        for fi in graph.module_functions(module):
            if isinstance(fi.node, ast.Lambda):
                continue
            yield from self._check_func(module, graph, fi, donators)

    # which `self.<attr>` / names hold donating jitted callables
    def _donating_call(self, call: ast.Call, module: Module,
                       graph: ScopeGraph, fi: Optional[FuncInfo]
                       ) -> Optional[Set[int]]:
        if last_name(call.func) in ("jit", "pjit"):
            pos = _donated_positions(call)
            return pos or None
        for target in graph.resolve_call(call, module, fi):
            pos = graph.wrapper_donates.get(id(target.node))
            if pos:
                return pos
        return None

    def _class_donators(self, module: Module, graph: ScopeGraph
                        ) -> Dict[str, Dict[str, Set[int]]]:
        out: Dict[str, Dict[str, Set[int]]] = {}
        for fi in graph.module_functions(module):
            if isinstance(fi.node, ast.Lambda) or fi.class_name is None:
                continue
            for n in shallow_walk(fi.node):
                if isinstance(n, ast.Assign) and \
                        isinstance(n.value, ast.Call):
                    pos = self._donating_call(n.value, module, graph, fi)
                    if not pos:
                        continue
                    for t in n.targets:
                        if isinstance(t, ast.Attribute) and \
                                isinstance(t.value, ast.Name) and \
                                t.value.id == "self":
                            out.setdefault(fi.class_name, {})[t.attr] = pos
        return out

    def _check_func(self, module: Module, graph: ScopeGraph, fi: FuncInfo,
                    donators: Dict[str, Dict[str, Set[int]]]
                    ) -> Iterator[Finding]:
        local_don: Dict[str, Set[int]] = {}
        donated: Dict[str, Tuple[ast.Call, str]] = {}  # expr-src -> origin
        findings: List[Finding] = []

        def call_donates(call: ast.Call) -> Optional[Set[int]]:
            # donation happens when a donating CALLABLE is invoked — the
            # `jax.jit(fn, donate_argnums=...)` constructor itself donates
            # nothing
            f = call.func
            if isinstance(f, ast.Name) and f.id in local_don:
                return local_don[f.id]
            if isinstance(f, ast.Attribute) and \
                    isinstance(f.value, ast.Name) and f.value.id == "self" \
                    and fi.class_name:
                for cname in graph.family(fi.class_name):
                    hit = donators.get(cname, {}).get(f.attr)
                    if hit:
                        return hit
            if isinstance(f, ast.Call):       # jax.jit(g, donate...)(x)
                return self._donating_call(f, module, graph, fi)
            return None

        def expr_src(e: ast.expr) -> Optional[str]:
            if isinstance(e, ast.Name):
                return e.id
            if isinstance(e, ast.Attribute) and \
                    isinstance(e.value, ast.Name):
                return f"{e.value.id}.{e.attr}"
            return None

        def walk_stmts(stmts: List[ast.stmt],
                       donated: Dict[str, Tuple[ast.Call, str]]) -> None:
            for s in stmts:
                if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                    continue
                # a with-statement's body runs once, in line: it is walked
                # by the recursion below, so only its items belong here
                own = ([n for item in s.items for n in shallow_walk(item)]
                       if isinstance(s, ast.With) else list(shallow_walk(s)))
                # 1. reads of already-donated buffers in this statement
                for n in own:
                    if isinstance(n, (ast.Name, ast.Attribute)) and \
                            isinstance(getattr(n, "ctx", None), ast.Load):
                        src = expr_src(n)
                        if src in donated:
                            call, label = donated.pop(src)
                            findings.append(self.finding(
                                module, n,
                                f"`{src}` was donated to `{label}` (its "
                                "buffer may already be deallocated) — "
                                "reading it afterwards is invalid; use "
                                "the returned value or drop the "
                                "donation"))
                # 2. does this statement donate something?  (a Return's
                # donation can never be read afterwards — skip it)
                new_donations: List[Tuple[str, ast.Call, str]] = []
                for n in (() if isinstance(s, ast.Return) else own):
                    if not isinstance(n, ast.Call):
                        continue
                    pos = call_donates(n)
                    if not pos:
                        continue
                    label = ".".join(_attr_chain(n.func)) or "jitted call"
                    for p in sorted(pos):
                        if p < len(n.args):
                            src = expr_src(n.args[p])
                            if src:
                                new_donations.append((src, n, label))
                # 3. track donating-callable bindings + reassignments
                if isinstance(s, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                    tgts = (s.targets if isinstance(s, ast.Assign)
                            else [s.target])
                    assigned = set()
                    for t in tgts:
                        src = expr_src(t)
                        if src:
                            assigned.add(src)
                            donated.pop(src, None)
                        for name in _target_names(t):
                            donated.pop(name, None)
                    value = getattr(s, "value", None)
                    if isinstance(value, ast.Call):
                        pos = self._donating_call(value, module, graph, fi)
                        if pos:
                            for t in tgts:
                                if isinstance(t, ast.Name):
                                    local_don[t.id] = pos
                    for src, call, label in new_donations:
                        if src not in assigned:
                            donated[src] = (call, label)
                else:
                    for src, call, label in new_donations:
                        donated[src] = (call, label)
                # recurse, branch-local copies for If
                if isinstance(s, ast.If):
                    d1, d2 = dict(donated), dict(donated)
                    walk_stmts(s.body, d1)
                    walk_stmts(s.orelse, d2)
                    donated.update(d1)
                    donated.update(d2)
                elif isinstance(s, (ast.For, ast.While)):
                    walk_stmts(s.body, donated)
                    walk_stmts(s.orelse, donated)
                elif isinstance(s, ast.Try):
                    walk_stmts(s.body, donated)
                    for h in s.handlers:
                        walk_stmts(h.body, donated)
                    walk_stmts(s.orelse, donated)
                    walk_stmts(s.finalbody, donated)
                elif isinstance(s, ast.With):
                    walk_stmts(s.body, donated)

        walk_stmts(fi.node.body, donated)
        yield from findings


# =========================================================================
@register_rule
class R006RecompileHazard(Rule):
    id = "R006"
    name = "recompile-hazard"
    rationale = ("dict/list literals flowing into static_argnums/names "
                 "(unhashable -> TypeError or retrace-per-call) and "
                 "jax.jit built inside a loop both defeat the compile "
                 "cache")

    def check(self, module: Module, graph: ScopeGraph) -> Iterator[Finding]:
        statics = self._static_map(module, graph)
        for fi in graph.module_functions(module):
            if isinstance(fi.node, ast.Lambda):
                continue
            yield from self._check_func(module, graph, fi, statics)
        yield from self._check_jit_in_loop(module, None, module.tree.body,
                                           graph)

    # map: id(FuncInfo.node) -> (static positions, static names)
    def _static_map(self, module: Module, graph: ScopeGraph
                    ) -> Dict[int, Tuple[Set[int], Set[str]]]:
        out: Dict[int, Tuple[Set[int], Set[str]]] = {}
        for fi in graph.module_functions(module):
            if isinstance(fi.node, ast.Lambda):
                continue
            for dec in getattr(fi.node, "decorator_list", []):
                if not isinstance(dec, ast.Call):
                    continue
                target = dec
                if last_name(dec.func) == "partial" and dec.args \
                        and last_name(dec.args[0]) in ("jit", "pjit"):
                    target = dec
                elif last_name(dec.func) not in ("jit", "pjit"):
                    continue
                pos, names = _static_spec(target)
                if pos or names:
                    out[id(fi.node)] = (pos, names)
        return out

    def _check_func(self, module: Module, graph: ScopeGraph, fi: FuncInfo,
                    statics: Dict[int, Tuple[Set[int], Set[str]]]
                    ) -> Iterator[Finding]:
        unhashable: Set[str] = set()
        for n in shallow_walk(fi.node):
            if isinstance(n, ast.Assign) and \
                    isinstance(n.value, _UNHASHABLE_LITERALS):
                for t in n.targets:
                    unhashable.update(_target_names(t))

        def is_unhashable(e: ast.expr) -> bool:
            return isinstance(e, _UNHASHABLE_LITERALS) or (
                isinstance(e, ast.Name) and e.id in unhashable)

        for n in shallow_walk(fi.node):
            if not isinstance(n, ast.Call):
                continue
            # (a) literal static spec on a direct jit call with args known
            if last_name(n.func) in ("jit", "pjit"):
                pos, names = _static_spec(n)
                _ = pos, names     # positions checked at call sites below
            # (b) call sites of statically-decorated functions
            for target in graph.resolve_call(n, module, fi):
                spec = statics.get(id(target.node))
                if not spec:
                    continue
                s_pos, s_names = spec
                for kw in n.keywords:
                    if kw.arg in s_names and is_unhashable(kw.value):
                        yield self.finding(
                            module, n,
                            f"unhashable value for static arg "
                            f"`{kw.arg}` of `{target.name}` — every call "
                            "re-traces (or raises TypeError); pass a "
                            "hashable (tuple/frozen) value")
                for p in s_pos:
                    if p < len(n.args) and is_unhashable(n.args[p]):
                        yield self.finding(
                            module, n,
                            f"unhashable value in static_argnums position "
                            f"{p} of `{target.name}` — every call "
                            "re-traces (or raises TypeError)")
        yield from self._check_jit_in_loop(module, fi, fi.node.body, graph)

    def _check_jit_in_loop(self, module: Module, fi: Optional[FuncInfo],
                           body: List[ast.stmt], graph: ScopeGraph
                           ) -> Iterator[Finding]:
        if fi is not None and graph.is_traced(fi):
            return
        for s in body:
            for n in shallow_walk(s):
                if isinstance(n, (ast.For, ast.While)):
                    for inner in shallow_walk(n):
                        if isinstance(inner, ast.Call) \
                                and last_name(inner.func) in ("jit", "pjit") \
                                and _attr_chain(inner.func)[0] in ("jax",
                                                                   "jit",
                                                                   "pjit"):
                            yield self.finding(
                                module, inner,
                                "jax.jit called inside a loop builds a "
                                "fresh callable (and re-traces) every "
                                "iteration — hoist the jit out of the "
                                "loop")


def _static_spec(call: ast.Call) -> Tuple[Set[int], Set[str]]:
    pos: Set[int] = set()
    names: Set[str] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            v = kw.value
            if isinstance(v, ast.Tuple):
                pos |= {e.value for e in v.elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, int)}
            elif isinstance(v, ast.Constant) and isinstance(v.value, int):
                pos.add(v.value)
        elif kw.arg == "static_argnames":
            v = kw.value
            if isinstance(v, (ast.Tuple, ast.List)):
                names |= {e.value for e in v.elts
                          if isinstance(e, ast.Constant)
                          and isinstance(e.value, str)}
            elif isinstance(v, ast.Constant) and isinstance(v.value, str):
                names.add(v.value)
    return pos, names


@register_rule
class R007BlockingDrainInDispatchLoop(Rule):
    id = "R007"
    name = "blocking-drain-in-dispatch-loop"
    rationale = ("device_get/block_until_ready/float() on the output of "
                 "the jit step dispatched in the SAME loop iteration "
                 "serializes host and device (the pre-pipeline TrainLoop "
                 "shape) — buffer results and drain them a step late")

    def check(self, module: Module, graph: ScopeGraph) -> Iterator[Finding]:
        class_disp = self._class_dispatchers(module, graph)
        for fi in graph.module_functions(module):
            if graph.is_traced(fi) or isinstance(fi.node, ast.Lambda):
                continue
            yield from self._check_func(module, graph, fi, class_disp)

    # ---------------------------------------------------------- dispatchers
    def _dispatching_ctor(self, call: ast.Call, module: Module,
                          graph: ScopeGraph, fi: Optional[FuncInfo]) -> bool:
        """Does evaluating ``call`` build a jit-dispatching callable —
        ``jax.jit(...)`` / ``pjit(...)`` directly, or any function of the
        ``distributed.jit_*`` wrapper layer (ScopeGraph already knows which
        functions trace a parameter)?"""
        if last_name(call.func) in ("jit", "pjit"):
            return True
        return any(graph.wrapper_positions.get(id(t.node))
                   for t in graph.resolve_call(call, module, fi))

    def _class_dispatchers(self, module: Module, graph: ScopeGraph
                           ) -> Dict[str, Set[str]]:
        """class -> ``self.<attr>``s holding jitted callables."""
        out: Dict[str, Set[str]] = {}
        for fi in graph.module_functions(module):
            if isinstance(fi.node, ast.Lambda) or fi.class_name is None:
                continue
            for n in shallow_walk(fi.node):
                if not (isinstance(n, ast.Assign)
                        and isinstance(n.value, ast.Call)
                        and self._dispatching_ctor(n.value, module, graph,
                                                   fi)):
                    continue
                for t in n.targets:
                    if isinstance(t, ast.Attribute) and \
                            isinstance(t.value, ast.Name) and \
                            t.value.id == "self":
                        out.setdefault(fi.class_name, set()).add(t.attr)
        return out

    def _local_dispatchers(self, module: Module, graph: ScopeGraph,
                           fi: FuncInfo) -> Set[str]:
        """Names in ``fi`` bound to jitted callables."""
        out: Set[str] = set()
        for n in shallow_walk(fi.node):
            if isinstance(n, ast.Assign) and isinstance(n.value, ast.Call) \
                    and self._dispatching_ctor(n.value, module, graph, fi):
                out.update(t.id for t in n.targets
                           if isinstance(t, ast.Name))
        return out

    # ------------------------------------------------------------ the walk
    def _check_func(self, module: Module, graph: ScopeGraph, fi: FuncInfo,
                    class_disp: Dict[str, Set[str]]) -> Iterator[Finding]:
        local_disp = self._local_dispatchers(module, graph, fi)
        findings: List[Finding] = []
        reported: Set[int] = set()

        def is_dispatch(call: ast.Call) -> bool:
            f = call.func
            if isinstance(f, ast.Name) and f.id in local_disp:
                return True
            if isinstance(f, ast.Attribute) and \
                    isinstance(f.value, ast.Name) and f.value.id == "self" \
                    and fi.class_name:
                if any(f.attr in class_disp.get(c, ())
                       for c in graph.family(fi.class_name)):
                    return True
            if isinstance(f, ast.Call):        # jax.jit(g)(x)
                return self._dispatching_ctor(f, module, graph, fi)
            tgts = graph.resolve_call(call, module, fi)
            return bool(tgts) and any(graph.is_traced(t) for t in tgts)

        def dispatched_in(e: ast.expr, hot: Set[str]) -> bool:
            return any(
                (isinstance(n, ast.Name) and n.id in hot)
                or (isinstance(n, ast.Call) and is_dispatch(n))
                for n in ast.walk(e))

        def sync_shapes(s: ast.stmt):
            """(call, drained_expr, label) for blocking fetches under s."""
            for n in shallow_walk(s):
                if not isinstance(n, ast.Call):
                    continue
                chain = _attr_chain(n.func)
                if chain and chain[-1] == "device_get" \
                        and chain[0] == "jax" and n.args:
                    yield n, n.args[0], "jax.device_get()"
                elif chain and chain[-1] == "block_until_ready":
                    if chain[0] == "jax" and n.args:
                        yield n, n.args[0], "jax.block_until_ready()"
                    elif not n.args and isinstance(n.func, ast.Attribute):
                        yield n, n.func.value, ".block_until_ready()"
                elif isinstance(n.func, ast.Name) \
                        and n.func.id in _SYNC_BUILTINS and len(n.args) == 1:
                    yield n, n.args[0], n.func.id + "()"
                elif isinstance(n.func, ast.Attribute) \
                        and n.func.attr in _SYNC_METHODS and not n.args:
                    yield n, n.func.value, "." + n.func.attr + "()"

        def targets_of(s: ast.stmt) -> List[str]:
            if isinstance(s, ast.Assign):
                names: List[str] = []
                for t in s.targets:
                    if isinstance(t, ast.Name):
                        names.append(t.id)
                    elif isinstance(t, (ast.Tuple, ast.List)):
                        names.extend(e.id for e in t.elts
                                     if isinstance(e, ast.Name))
                return names
            if isinstance(s, ast.AnnAssign) and \
                    isinstance(s.target, ast.Name) and s.value is not None:
                return [s.target.id]
            return []

        def handle(stmts: List[ast.stmt], hot: Set[str],
                   in_loop: bool) -> None:
            for s in stmts:
                if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                    continue
                if isinstance(s, (ast.For, ast.While, ast.AsyncFor)):
                    inner = set(hot)
                    handle(s.body, inner, True)
                    handle(s.orelse, set(hot), in_loop)
                    continue
                if isinstance(s, ast.If):
                    a, b = set(hot), set(hot)
                    handle(s.body, a, in_loop)
                    handle(s.orelse, b, in_loop)
                    hot |= a | b
                    continue
                if isinstance(s, (ast.With, ast.AsyncWith)):
                    handle(s.body, hot, in_loop)
                    continue
                if isinstance(s, ast.Try):
                    handle(s.body, hot, in_loop)
                    for h in s.handlers:
                        handle(h.body, set(hot), in_loop)
                    handle(s.orelse, hot, in_loop)
                    handle(s.finalbody, hot, in_loop)
                    continue
                if in_loop:
                    for call, arg, label in sync_shapes(s):
                        if id(call) in reported:
                            continue
                        if dispatched_in(arg, hot):
                            reported.add(id(call))
                            findings.append(self.finding(
                                module, call,
                                f"{label} on the output of the jit step "
                                "dispatched this iteration blocks until "
                                "the device finishes — dispatch runs "
                                "ahead only if results are buffered and "
                                "drained >=1 step late (deque) or after "
                                "the loop"))
                    names = targets_of(s)
                    hot.difference_update(names)
                    value = getattr(s, "value", None)
                    if names and isinstance(value, ast.Call) \
                            and is_dispatch(value):
                        hot.update(names)

        handle(fi.node.body, set(), False)
        yield from findings
