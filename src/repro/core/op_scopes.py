"""Which work function each operation of a compiled step program belongs to.

``PersistentRuntime._lk_step`` runs every work function inside
``jax.named_scope(f"lk.op.{name}")``, so the ops it traces carry that scope
in their HLO ``op_name``. XLA also adds ops of its own (copies, layout
changes) with no metadata. An op is attributed by two rules, in order:

1. its ``op_name`` holds ``lk.op.<name>``: it belongs to work function
   ``<name>``;
2. it lies in a computation reached from one branch of a conditional,
   where every scoped op reached from that branch names the same work
   function: the branch is that work function's, and so is every op in
   it, scoped or not.

Every other op belongs to the runtime: the step's own plumbing around the
work functions (the opcode switch, the carries, the ack record).
"""
from __future__ import annotations

import re

SCOPE_PREFIX = "lk.op."

_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|"
    r"false_computation|branch_computations|called_computations)="
    r"(\{[^}]*\}|%?[\w.\-]+)")
_BRANCHES = re.compile(
    r"\b(?:branch_computations=(\{[^}]*\})|"
    r"(?:true|false)_computation=(%?[\w.\-]+))")
_NAME = re.compile(r"%?([\w.\-]+)")


def work_scope(op_name: str) -> str:
    """The ``lk.op.<name>`` component of an ``op_name`` ("" if none)."""
    for part in op_name.split("/"):
        if part.startswith(SCOPE_PREFIX):
            return part
    return ""


def _names(ref: str) -> list[str]:
    return _NAME.findall(ref.strip("{}"))


def attribute(hlo_text: str) -> tuple[str, dict[str, tuple[str, str]]]:
    """(module name, {instruction name: (scope, op_name)}) of one HLO
    module's text, as ``Compiled.as_text()`` prints it. ``scope`` is the
    ``lk.op.<name>`` the instruction belongs to by the module's rules,
    or "" for the runtime; ``op_name`` is its metadata ("" if none)."""
    module = ""
    comp = None
    instrs: dict[str, list[tuple[str, str]]] = {}
    callees: dict[str, set[str]] = {}
    branches: list[list[str]] = []
    for line in hlo_text.splitlines():
        if not module:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
        if line.startswith("}"):
            comp = None
            continue
        if comp is None:
            m = _COMPUTATION.match(line)
            if m:
                comp = m.group(1)
                instrs[comp] = []
                callees[comp] = set()
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        instrs[comp].append((m.group(1), op.group(1) if op else ""))
        for ref in _CALLS.findall(line):
            callees[comp].update(_names(ref))
        found = [n for a, b in _BRANCHES.findall(line)
                 for n in _names(a or b)]
        if found:
            branches.append(found)

    reach: dict[str, frozenset] = {}

    def reachable(c: str) -> frozenset:
        if c not in reach:
            reach[c] = frozenset()          # guards a malformed cycle
            out = {c}
            for d in callees.get(c, ()):
                out |= reachable(d)
            reach[c] = frozenset(out)
        return reach[c]

    comp_scope: dict[str, str] = {}
    for group in branches:
        for b in group:
            held = {work_scope(op) for c in reachable(b)
                    for _, op in instrs.get(c, ())} - {""}
            if len(held) == 1:
                scope = held.pop()
                for c in reachable(b):
                    comp_scope.setdefault(c, scope)
    out = {}
    for c, rows in instrs.items():
        for name, op in rows:
            out[name] = (work_scope(op) or comp_scope.get(c, ""), op)
    return module, out
