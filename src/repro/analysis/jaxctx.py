"""Shared JAX-context analysis over one module's AST.

Answers the one question every rule needs: *which code is traced?* A traced
context is source that runs at jit-trace time — inside a jit-decorated
function, a `lax` control-flow body, or (this repo's convention) an engine
protocol method that solvers call from inside their jitted cores. Host
Python there is not "slow", it is a different semantics: `np.asarray`
forces a sync, `float()` breaks the trace, a bf16 multiply silently fixes
the accumulation dtype.

Detection is static and name-based (no imports are resolved):

  * decorator forms: ``@jax.jit``, ``@jit``, ``@partial(jax.jit, ...)``,
    ``@functools.partial(jax.jit, ...)``;
  * wrapping forms: ``g = jax.jit(f)`` marks ``f`` (and records ``g`` as a
    jit-wrapped name);
  * control-flow bodies: any function NAME passed as an argument to
    ``lax.scan`` / ``while_loop`` / ``fori_loop`` / ``cond`` / ``switch`` /
    ``map`` / ``shard_map`` / ``checkpoint`` / ``remat`` / ``vmap`` /
    ``pmap`` / ``grad`` — conservative: a function
    handed to a jax combinator is assumed traced;
  * contract methods: names listed in `traced_methods` (the engine
    protocol) defined inside a class body;
  * nesting: every function lexically inside a traced function is traced.

`donated` maps function names jitted with ``donate_argnums`` to the donated
positional indices — the JL005 input.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field

__all__ = ["JaxContext", "analyze", "TRACING_COMBINATORS"]

# callables that trace a function argument when handed one by name
TRACING_COMBINATORS = frozenset({
    "scan", "while_loop", "fori_loop", "cond", "switch", "map",
    "shard_map", "jit", "checkpoint", "remat",
    "vmap", "pmap", "grad", "value_and_grad", "custom_jvp", "custom_vjp",
})


def _is_jit_ref(node: ast.AST) -> bool:
    """`jit` or `<anything>.jit` (jax.jit, jax.experimental... )."""
    if isinstance(node, ast.Name):
        return node.id == "jit"
    return isinstance(node, ast.Attribute) and node.attr == "jit"


def _is_partial_ref(node: ast.AST) -> bool:
    if isinstance(node, ast.Name):
        return node.id == "partial"
    return isinstance(node, ast.Attribute) and node.attr == "partial"


def _jit_call(node: ast.AST) -> ast.Call | None:
    """The Call node if `node` is `jax.jit(...)` or `partial(jax.jit, ...)`."""
    if not isinstance(node, ast.Call):
        return None
    if _is_jit_ref(node.func):
        return node
    if _is_partial_ref(node.func) and node.args and _is_jit_ref(node.args[0]):
        return node
    return None


def _donate_positions(call: ast.Call) -> tuple[int, ...]:
    """donate_argnums value of a jit call, () if absent/undecidable."""
    for kw in call.keywords:
        if kw.arg in ("donate_argnums", "donate_argnames"):
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return (v.value,)
            if isinstance(v, (ast.Tuple, ast.List)):
                out = []
                for el in v.elts:
                    if isinstance(el, ast.Constant) and \
                            isinstance(el.value, int):
                        out.append(el.value)
                return tuple(out)
    return ()


AnyFunc = (ast.FunctionDef, ast.AsyncFunctionDef)


@dataclass
class FuncInfo:
    node: ast.FunctionDef | ast.AsyncFunctionDef
    parent: "FuncInfo | None"        # enclosing function, if any
    in_class: bool                   # defined directly in a class body
    traced: bool = False


@dataclass
class JaxContext:
    functions: list[FuncInfo] = field(default_factory=list)
    # function name -> donated positional indices (jit(donate_argnums=...))
    donated: dict[str, tuple[int, ...]] = field(default_factory=dict)
    jit_wrapped_names: set[str] = field(default_factory=set)
    _by_node: dict[int, FuncInfo] = field(default_factory=dict)

    def info(self, node: ast.AST) -> FuncInfo | None:
        return self._by_node.get(id(node))

    def is_traced(self, node: ast.AST) -> bool:
        fi = self.info(node)
        return fi is not None and fi.traced

    def traced_roots(self):
        """Traced functions with no traced ancestor: walking each yields
        every traced statement exactly once."""
        for fi in self.functions:
            if not fi.traced:
                continue
            p = fi.parent
            while p is not None and not p.traced:
                p = p.parent
            if p is None:
                yield fi.node


class _Collector(ast.NodeVisitor):
    def __init__(self, ctx: JaxContext, traced_methods: tuple[str, ...]):
        self.ctx = ctx
        self.traced_methods = traced_methods
        self.func_stack: list[FuncInfo] = []
        self.class_depth = 0
        self.combinator_args: set[str] = set()

    # -- structure ---------------------------------------------------------
    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.class_depth += 1
        self.generic_visit(node)
        self.class_depth -= 1

    def _visit_func(self, node) -> None:
        fi = FuncInfo(node=node,
                      parent=self.func_stack[-1] if self.func_stack else None,
                      in_class=self.class_depth > 0 and not self.func_stack)
        self.ctx.functions.append(fi)
        self.ctx._by_node[id(node)] = fi
        # decorator-traced?
        for dec in node.decorator_list:
            jc = _jit_call(dec) if isinstance(dec, ast.Call) else None
            if _is_jit_ref(dec) or jc is not None:
                fi.traced = True
                if jc is not None:
                    pos = _donate_positions(jc)
                    if pos:
                        self.ctx.donated[node.name] = pos
        if fi.in_class and node.name in self.traced_methods:
            fi.traced = True
        self.func_stack.append(fi)
        self.generic_visit(node)
        self.func_stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    # -- wrapping / combinator calls --------------------------------------
    def visit_Assign(self, node: ast.Assign) -> None:
        jc = _jit_call(node.value)
        if jc is not None:
            for t in node.targets:
                if isinstance(t, ast.Name):
                    self.ctx.jit_wrapped_names.add(t.id)
            pos = _donate_positions(jc)
            if pos:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        self.ctx.donated[t.id] = pos
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        name = node.func.attr if isinstance(node.func, ast.Attribute) \
            else node.func.id if isinstance(node.func, ast.Name) else None
        if name in TRACING_COMBINATORS:
            for a in node.args:
                if isinstance(a, ast.Name):
                    self.combinator_args.add(a.id)
        self.generic_visit(node)


def analyze(tree: ast.Module,
            traced_methods: tuple[str, ...] = ()) -> JaxContext:
    """Compute the JaxContext for one parsed module."""
    ctx = JaxContext()
    col = _Collector(ctx, traced_methods)
    col.visit(tree)
    # name-based marks: functions passed to combinators or wrapped by jit
    marked = col.combinator_args | ctx.jit_wrapped_names | \
        set(ctx.donated)
    for fi in ctx.functions:
        if fi.node.name in marked:
            fi.traced = True
    # donated names that are jit-wrapped assignments keep their positions;
    # decorator-donated functions were recorded during the walk
    # nesting: anything inside a traced function is traced
    changed = True
    while changed:
        changed = False
        for fi in ctx.functions:
            if not fi.traced and fi.parent is not None and fi.parent.traced:
                fi.traced = True
                changed = True
    return ctx
