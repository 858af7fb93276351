"""``jit-purity``: no host effects reachable inside jitted step code.

The ``[G×P]`` consensus step compiles to one XLA program. Anything
impure a traced function touches — wall clocks, Python RNG, env reads,
host callbacks — either silently bakes a trace-time constant into every
execution (``time.time()`` at trace time is *one* number forever) or
drags a host round-trip into the hot loop. The op-definition census
(``parallel/scaling.py``) checks the *compiled* program
for stray collectives at runtime; this rule is its static complement —
the impurity never lands on a branch CI didn't trace.

Mechanics: a pre-pass over the whole package collects jit *roots* —
function names appearing in ``jax.jit(f)``, ``jax.jit(partial(f, ...))``
or under a ``@jax.jit`` / ``@functools.partial(jax.jit, ...)``
decorator. Within each ``ops/`` module, the rule walks the local
name-level call graph from those roots and flags forbidden calls in any
reachable function. Name-level reachability is deliberately
over-approximate for helpers shared with host-side code — a helper that
must stay host-impure belongs outside ``ops/``.
"""

from __future__ import annotations

import ast

from .astutil import dotted_name
from .callgraph import callee_names, local_functions
from .findings import Finding

FORBIDDEN_CALLS = {
    "time.time": "wall clock freezes to a trace-time constant",
    "time.monotonic": "wall clock freezes to a trace-time constant",
    "time.perf_counter": "wall clock freezes to a trace-time constant",
    "time.sleep": "host sleep inside a traced function",
    "random.random": "Python RNG is trace-time-frozen; use jax.random",
    "random.randint": "Python RNG is trace-time-frozen; use jax.random",
    "random.choice": "Python RNG is trace-time-frozen; use jax.random",
    "os.getenv": "env read freezes to a trace-time constant",
    "os.environ.get": "env read freezes to a trace-time constant",
    "jax.debug.callback": "host callback in the step's hot loop",
    "jax.pure_callback": "host callback in the step's hot loop",
    "jax.experimental.io_callback": "host callback in the step's hot loop",
    "io_callback": "host callback in the step's hot loop",
    "np.random.seed": "host RNG state mutation at trace time",
}

FORBIDDEN_PREFIXES = {
    "np.random.": "host-side numpy RNG is trace-time-frozen; use jax.random",
    "numpy.random.": "host-side numpy RNG is trace-time-frozen; use "
                     "jax.random",
}

FORBIDDEN_SUBSCRIPTS = {
    "os.environ": "env read freezes to a trace-time constant",
}


def collect_jit_roots(trees: dict[str, ast.Module]) -> set[str]:
    """Function names jitted anywhere in the scanned tree."""
    roots: set[str] = set()

    def jitted_arg(call: ast.Call) -> None:
        for arg in call.args:
            if isinstance(arg, ast.Name):
                roots.add(arg.id)
            elif isinstance(arg, ast.Attribute):
                roots.add(arg.attr)
            elif isinstance(arg, ast.Call):
                # jax.jit(partial(step, ...)) / jax.jit(functools.partial(...))
                inner = dotted_name(arg.func) or ""
                if inner.rsplit(".", 1)[-1] == "partial" and arg.args:
                    first = arg.args[0]
                    if isinstance(first, ast.Name):
                        roots.add(first.id)
                    elif isinstance(first, ast.Attribute):
                        roots.add(first.attr)

    for tree in trees.values():
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                name = dotted_name(node.func) or ""
                if name.rsplit(".", 1)[-1] == "jit":
                    jitted_arg(node)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for deco in node.decorator_list:
                    deco_name = dotted_name(
                        deco.func if isinstance(deco, ast.Call) else deco) or ""
                    tail = deco_name.rsplit(".", 1)[-1]
                    if tail == "jit":
                        roots.add(node.name)
                    elif (tail == "partial" and isinstance(deco, ast.Call)
                          and deco.args):
                        inner = dotted_name(deco.args[0]) or ""
                        if inner.rsplit(".", 1)[-1] == "jit":
                            roots.add(node.name)
    return roots


# The name-level resolution machinery this rule pioneered now lives in
# callgraph.py (local_functions / callee_names), where the package-wide
# async call graph builds on the same over-approximation.


def check_jit_purity(tree: ast.Module, path: str,
                     jit_roots: set[str]) -> list[Finding]:
    if "/ops/" not in f"/{path}":
        return []
    local = local_functions(tree)
    reachable: set[str] = set()
    frontier = [name for name in local if name in jit_roots]
    while frontier:
        name = frontier.pop()
        if name in reachable:
            continue
        reachable.add(name)
        frontier.extend(c for c in callee_names(local[name])
                        if c in local and c not in reachable)
    findings: list[Finding] = []
    for name in sorted(reachable):
        fn = local[name]
        for node in ast.walk(fn):
            why = None
            culprit = None
            if isinstance(node, ast.Call):
                called = dotted_name(node.func) or ""
                if called in FORBIDDEN_CALLS:
                    why, culprit = FORBIDDEN_CALLS[called], called
                else:
                    for prefix, reason in FORBIDDEN_PREFIXES.items():
                        if called.startswith(prefix):
                            why, culprit = reason, called
            elif isinstance(node, ast.Subscript):
                sub = dotted_name(node.value) or ""
                if sub in FORBIDDEN_SUBSCRIPTS:
                    why, culprit = FORBIDDEN_SUBSCRIPTS[sub], sub
            if why:
                findings.append(Finding(
                    rule="jit-purity", path=path, line=node.lineno,
                    message=(f"`{culprit}` reachable from jitted step "
                             f"function `{name}` — {why}"),
                    symbol=name))
    return findings
