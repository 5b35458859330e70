"""jit-purity: jitted callables stay pure.

A callable handed to ``jax.jit`` / ``shard_map`` is traced once per
shape signature and replayed as compiled XLA: any host-side effect in
its body either runs only at trace time (logging, stats counters, fault
hooks — silently NOT per batch, which is worse than failing) or
materializes a tracer and breaks/stalls compilation (``float()`` /
``int()`` / ``np.asarray`` on traced values, host clocks).  The
engine's steps therefore keep every effect — fault checks, EmitStats /
IngestStats increments, logging, wall-clock reads — on the host side of
the step boundary.

The rule finds ``jax.jit(...)`` / ``shard_map(...)`` call sites (incl.
``self.jax.jit`` receivers), resolves the
callable argument to a function definition, and reports banned
constructs anywhere in the resolved body:

- host clocks: ``time.time`` / ``time.monotonic`` / ``time.perf_counter``
  / ``datetime.now``
- logging / printing: any call on a ``log`` / ``logger`` / ``logging``
  receiver, bare ``print``
- fault hooks: ``.check(...)`` on a fault-injector receiver
  (``fi`` / ``faults`` / ``fault_injector`` / ``injector``)
- stats counters: writes to a ``*.stats.*`` attribute chain
- tracer materialization: ``np.asarray`` / ``np.array`` /
  ``jax.device_get``, and bare ``float()`` / ``int()`` / ``bool()`` on
  a non-literal argument

Resolution is lexical (same module, enclosing scopes outward) when the
rule runs without a ``ProjectIndex``; with one — the normal
whole-program run — the callable argument additionally resolves through
the import map (``from .steps import scan_step``), through
``self.``/``cls.`` method dispatch along the MRO, and into other
modules, and the scan follows project-resolved **helper calls**
transitively: everything the jitted callable calls is traced with it,
so a ``time.time()`` two hops away in another file is the same bug as
one written inline.  Findings on a helper are attributed to the
helper's own file and scope.  Callables/edges the project cannot
resolve statically (arbitrary object attributes, container lookups)
are skipped — conservative, never guessed.
"""

from __future__ import annotations

import ast
from typing import Iterable, Iterator, List, Optional, Set, Tuple

from ..framework import Finding, Rule, register
from ..index import ModuleIndex

JIT_NAMES = {"jax.jit", "jit"}
SHARD_NAMES = {"shard_map", "jax.shard_map"}

_CLOCKS = {"time.time", "time.monotonic", "time.perf_counter",
           "time.perf_counter_ns", "datetime.now", "datetime.datetime.now"}
_LOG_RECEIVERS = {"log", "logger", "logging"}
_FAULT_RECEIVERS = {"fi", "faults", "fault_injector", "injector"}
_MATERIALIZERS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
                  "jax.device_get"}
_CASTS = {"float", "int", "bool"}


def jit_call_sites(index: ModuleIndex) -> List[Tuple[ast.Call, ast.AST]]:
    """(call, callable-arg) for every jit/shard_map wrapping site."""
    out = []
    for call in index.calls():
        name = index.dotted(call.func)
        is_wrapper = name in JIT_NAMES or name in SHARD_NAMES
        if is_wrapper and call.args:
            out.append((call, call.args[0]))
    return out


def resolve_callable(index: ModuleIndex, site: ast.Call,
                     arg: ast.AST) -> Optional[ast.AST]:
    """The function definition a jit argument refers to, searching the
    enclosing scopes outward; None when not statically resolvable
    within the module (the project layer picks those up)."""
    if isinstance(arg, ast.Lambda):
        return arg
    if isinstance(arg, ast.Call):
        # functools.partial(f, ...) / shard_map(f, ...): recurse on the
        # wrapped callable
        if arg.args:
            return resolve_callable(index, site, arg.args[0])
        return None
    if not isinstance(arg, ast.Name):
        return None
    scope = index.qualname(site)
    parts = scope.split(".") if scope != "<module>" else []
    while True:
        qual = ".".join(parts + [arg.id]) if parts else arg.id
        fn = index.functions.get(qual)
        if fn is not None:
            return fn
        if not parts:
            return None
        parts.pop()


def resolve_callable_project(project, index: ModuleIndex, site: ast.Call,
                             arg: ast.AST
                             ) -> Optional[Tuple[ModuleIndex, ast.AST]]:
    """Cross-module fallback when lexical resolution fails: plain names
    through the import map, ``self.``/``cls.`` methods through the MRO,
    dotted receivers into their defining module."""
    if isinstance(arg, ast.Call):
        if arg.args:
            return resolve_callable_project(project, index, site, arg.args[0])
        return None
    hit = project._resolve_value(index, site, arg)
    if hit is None:
        return None
    return (hit[0], hit[1])


def impure_constructs(index: ModuleIndex, fn: ast.AST
                      ) -> List[Tuple[int, str]]:
    """(line, description) for every banned construct in a jitted
    callable's subtree (nested local defs are traced too, so the whole
    subtree counts)."""
    hits: List[Tuple[int, str]] = []
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            name = index.dotted(node.func)
            if name is None:
                continue
            base = name.split(".")[0]
            leaf = name.split(".")[-1]
            if name in _CLOCKS:
                hits.append((node.lineno, f"host clock {name}()"))
            elif base in _LOG_RECEIVERS and base != leaf:
                hits.append((node.lineno, f"logging call {name}()"))
            elif name == "print":
                hits.append((node.lineno, "print()"))
            elif leaf == "check" and base in _FAULT_RECEIVERS:
                hits.append((node.lineno, f"fault hook {name}()"))
            elif name in _MATERIALIZERS:
                hits.append((node.lineno, f"tracer materialization {name}()"))
            elif name in _CASTS and node.args and \
                    not isinstance(node.args[0], ast.Constant):
                hits.append((node.lineno,
                             f"tracer materialization {name}() on a "
                             "non-literal"))
        elif isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                tname = index.dotted(t) if isinstance(t, ast.Attribute) \
                    else None
                if tname and "stats" in tname.split(".")[:-1]:
                    hits.append((t.lineno,
                                 f"stats counter write {tname}"))
    return hits


@register
class JitPurityRule(Rule):
    name = "jit-purity"
    description = (
        "host clock / logging / fault hook / stats counter / tracer "
        "materialization inside a callable passed to jax.jit or shard_map")

    #: transitive helper-following cap per jitted root
    MAX_HELPER_DEFS = 50

    def begin(self):
        # (rel, scope, line) already reported — one helper reached from
        # jit sites in several modules is one finding
        self._reported: Set[Tuple[str, str, int]] = set()

    def check(self, index: ModuleIndex) -> Iterable[Finding]:
        reported = getattr(self, "_reported", None)
        if reported is None:
            reported = self._reported = set()
        for site, arg in jit_call_sites(index):
            fn = resolve_callable(index, site, arg)
            fn_idx = index
            if fn is None and self.project is not None:
                hit = resolve_callable_project(self.project, index, site, arg)
                if hit is not None:
                    fn_idx, fn = hit
            if fn is None:
                continue
            for d_idx, d_fn in self._traced_defs(fn_idx, fn):
                d_qual = d_idx.def_qualname(d_fn)
                for line, what in impure_constructs(d_idx, d_fn):
                    key = (d_idx.rel, d_qual, line)
                    if key in reported:
                        continue  # same fn jitted/reached repeatedly
                    reported.add(key)
                    inline = d_idx is fn_idx and d_fn is fn
                    yield Finding(
                        rule=self.name,
                        rel=d_idx.rel,
                        line=line,
                        scope=d_qual,
                        message=(
                            f"{what} inside a jitted callable"
                            + ("" if inline else
                               " (helper reached from a jitted callable)")
                            + " — effects run at trace time only (or "
                            "break tracing); hoist to the host side of "
                            "the step boundary, or allowlist with a "
                            "justification"),
                    )

    def _traced_defs(self, fn_idx: ModuleIndex, fn: ast.AST
                     ) -> Iterator[Tuple[ModuleIndex, ast.AST]]:
        """The jitted callable plus — in project mode — every
        project-resolved helper its body (transitively) calls: they are
        all traced together."""
        yield (fn_idx, fn)
        if self.project is None:
            return
        visited: Set[Tuple[int, int]] = {(id(fn_idx), id(fn))}
        work: List[Tuple[ModuleIndex, ast.AST]] = [(fn_idx, fn)]
        while work and len(visited) <= self.MAX_HELPER_DEFS:
            cur_idx, cur_fn = work.pop()
            for node in ast.walk(cur_fn):
                if not isinstance(node, ast.Call):
                    continue
                hit = self.project.resolve_call(cur_idx, node)
                if hit is None:
                    continue
                t_idx, t_fn, _fq = hit
                key = (id(t_idx), id(t_fn))
                if key in visited:
                    continue
                visited.add(key)
                work.append((t_idx, t_fn))
                yield (t_idx, t_fn)
