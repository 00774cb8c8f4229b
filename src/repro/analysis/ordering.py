"""Pass 1b — lock-acquisition ordering.

Builds the directed lock-acquisition graph across every analyzed class
(Manager/ResultCache/SharedStore/SocketBackend/…): an edge
``A -> B`` means some code path acquires ``B`` while holding ``A``, either
lexically (``with A: ... with B:``) or one call level deep (``with A:
self.x.m()`` where ``m`` acquires ``B`` — ``self.x``'s class resolved from
its constructor assignment).  Any cycle in the graph is a potential
deadlock and is reported.

Codes:
  O301  lock-ordering cycle
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Dict, FrozenSet, List, Optional, Set, Tuple

from .core import Finding, SourceFile
from .lockmodel import ClassModel, HeldWalker, ModuleModel, collect_module

__all__ = ["run", "build_edges"]

PASS_ID = "ordering"


@dataclasses.dataclass(frozen=True)
class Edge:
    src_lock: str
    dst_lock: str
    rel: str
    line: int
    where: str


def _direct_acquisitions(
    mod: ModuleModel, cls: Optional[ClassModel], fn: ast.FunctionDef
) -> Set[str]:
    """Locks this function itself acquires via ``with`` (class/module locks
    only — heuristic local/obj locks don't participate in the graph)."""
    w = HeldWalker(mod, cls, fn)
    for _ in w.walk():
        pass
    return {
        lid
        for _, lid, _ in w.acquisitions
        if not lid.startswith(("local.", "obj."))
    }


def _resolve_call(
    mod: ModuleModel,
    cls: Optional[ClassModel],
    call: ast.Call,
    registry: Dict[str, Tuple[ModuleModel, ClassModel]],
) -> Optional[Tuple[ModuleModel, Optional[ClassModel], ast.FunctionDef]]:
    fn = call.func
    if isinstance(fn, ast.Name):
        target = mod.functions.get(fn.id)
        if target is not None:
            return mod, None, target
        return None
    if not isinstance(fn, ast.Attribute):
        return None
    base = fn.value
    if isinstance(base, ast.Name) and base.id == "self" and cls is not None:
        target = cls.methods.get(fn.attr)
        if target is not None:
            return mod, cls, target
        return None
    # self.X.m() with self.X = ClassName(...) and ClassName analyzed
    if (
        isinstance(base, ast.Attribute)
        and isinstance(base.value, ast.Name)
        and base.value.id == "self"
        and cls is not None
    ):
        type_name = cls.attr_types.get(base.attr)
        if type_name and type_name in registry:
            tmod, tcls = registry[type_name]
            target = tcls.methods.get(fn.attr)
            if target is not None:
                return tmod, tcls, target
    return None


def build_edges(
    mods: List[ModuleModel],
    registry: Optional[Dict[str, Tuple[ModuleModel, ClassModel]]] = None,
) -> List[Edge]:
    if registry is None:
        registry = {}
        for m in mods:
            for cls in m.classes.values():
                registry.setdefault(cls.name, (m, cls))
    edges: List[Edge] = []

    def record(held: FrozenSet[str], lid: str, rel: str, line: int, where: str) -> None:
        for h in held:
            if h.startswith(("local.", "obj.")) or lid.startswith(("local.", "obj.")):
                continue
            if h != lid:
                edges.append(Edge(h, lid, rel, line, where))

    for mod in mods:
        fns: List[Tuple[Optional[ClassModel], ast.FunctionDef]] = [
            (None, fn) for fn in mod.functions.values()
        ]
        for cls in mod.classes.values():
            fns.extend((cls, m) for m in cls.methods.values())
        for cls, fn in fns:
            where = f"{cls.name}.{fn.name}" if cls else fn.name
            w = HeldWalker(mod, cls, fn)
            calls: List[Tuple[ast.Call, FrozenSet[str]]] = []
            for node, held in w.walk():
                if isinstance(node, ast.Call) and held:
                    calls.append((node, held))
            for held, lid, node in w.acquisitions:
                record(held, lid, mod.src.rel, node.lineno, where)
            for call, held in calls:
                resolved = _resolve_call(mod, cls, call, registry)
                if resolved is None:
                    continue
                tmod, tcls, target = resolved
                for lid in _direct_acquisitions(tmod, tcls, target):
                    record(held, lid, mod.src.rel, call.lineno, where)
    return edges


def _cycles(edges: List[Edge]) -> List[List[str]]:
    graph: Dict[str, Set[str]] = {}
    for e in edges:
        graph.setdefault(e.src_lock, set()).add(e.dst_lock)
        graph.setdefault(e.dst_lock, set())
    # Tarjan SCC
    index: Dict[str, int] = {}
    low: Dict[str, int] = {}
    onstack: Set[str] = set()
    stack: List[str] = []
    sccs: List[List[str]] = []
    counter = [0]

    def strongconnect(v: str) -> None:
        index[v] = low[v] = counter[0]
        counter[0] += 1
        stack.append(v)
        onstack.add(v)
        for wnode in graph[v]:
            if wnode not in index:
                strongconnect(wnode)
                low[v] = min(low[v], low[wnode])
            elif wnode in onstack:
                low[v] = min(low[v], index[wnode])
        if low[v] == index[v]:
            comp = []
            while True:
                wnode = stack.pop()
                onstack.discard(wnode)
                comp.append(wnode)
                if wnode == v:
                    break
            sccs.append(comp)

    for v in sorted(graph):
        if v not in index:
            strongconnect(v)
    out = []
    for comp in sccs:
        if len(comp) > 1:
            out.append(sorted(comp))
        elif comp[0] in graph[comp[0]]:  # self-loop: re-acquire A under A
            out.append(comp)
    return out


def run_project(mods: List[ModuleModel]) -> List[Finding]:
    edges = build_edges(mods)
    findings: List[Finding] = []
    for cycle in _cycles(edges):
        members = set(cycle)
        sites = [
            e for e in edges if e.src_lock in members and e.dst_lock in members
        ]
        site = min(sites, key=lambda e: (e.rel, e.line))
        detail = "; ".join(
            f"{e.src_lock}->{e.dst_lock} at {e.rel}:{e.line} ({e.where})"
            for e in sites[:4]
        )
        findings.append(
            Finding(
                PASS_ID,
                "O301",
                site.rel,
                site.line,
                f"lock-ordering cycle {' -> '.join(cycle + [cycle[0]])}: {detail}",
                "cycle:" + "->".join(cycle),
            )
        )
    return findings


def run(src: SourceFile, mod: Optional[ModuleModel] = None) -> List[Finding]:
    return run_project([mod or collect_module(src)])
