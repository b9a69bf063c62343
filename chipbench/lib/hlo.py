"""Which HLO instructions of a compiled program ran under a name scope.

Device ops in a profiler trace carry the name of the HLO instruction that
ran (a fusion, a scatter, a collective), not the ``jax.named_scope`` it
was traced under.  The scope survives in the optimized HLO, as the
``op_name`` metadata of each instruction: a fusion carries its root's
``op_name``, and the instructions inside its fused computation keep their
own.  The rule this module applies:

* an instruction is **in** the scope when every ``op_name`` it and its
  fused computations carry has the scope as a path component;
* **out** when none has;
* **mixed** when XLA fused instructions from both sides into one op;
* **unnamed** when it carries no ``op_name`` at all (copies, bitcasts).

Control-flow instructions (while, conditional, call) only contain other
ops, so they are left out of the map.
"""
from __future__ import annotations

import re

_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_CONTAINERS = {"while", "conditional", "call", "parameter", "tuple",
               "get-tuple-element", "constant"}

IN, OUT, MIXED, UNNAMED = "in", "out", "mixed", "unnamed"


def _opcode(rhs: str) -> str:
    # rhs: "<shape> opcode(operands), attrs"; tuple shapes hold spaces
    depth, i = 0, 0
    for i, ch in enumerate(rhs):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            break
    m = re.match(r"\s*([\w\-]+)\(", rhs[i:])
    return m.group(1) if m else ""


def parse(hlo_text: str) -> dict:
    """``{computation: [(name, opcode, op_names, callees)]}``."""
    comps: dict = {}
    cur = None
    for line in hlo_text.splitlines():
        if cur is None:
            m = _COMP.match(line)
            if m and "=" not in line.split("(")[0]:
                cur = comps.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rhs = m.groups()
        names = [n for n in _OP_NAME.findall(rhs) if "/" in n]
        cur.append((name, _opcode(rhs), names, _CALLS.findall(rhs)))
    return comps


def classify(hlo_text: str, scope: str) -> dict:
    """``{instruction name: (opcode, IN | OUT | MIXED | UNNAMED, op_name)}``
    for every instruction of the module that can run as a device op;
    ``op_name`` is the instruction's own (a fusion's root's), or ``""``."""
    comps = parse(hlo_text)
    by_name = {}
    for instrs in comps.values():
        for name, opcode, names, callees in instrs:
            by_name[name] = (opcode, names, callees)
    memo: dict = {}

    def names_of(comp: str) -> set:
        if comp not in memo:
            memo[comp] = set()
            acc = set()
            for _, _, names, callees in comps.get(comp, ()):
                acc.update(names)
                for c in callees:
                    acc |= names_of(c)
            memo[comp] = acc
        return memo[comp]

    def inside(op_name: str) -> bool:
        return scope in op_name.split("/")

    out = {}
    for name, (opcode, names, callees) in by_name.items():
        if opcode in _CONTAINERS:
            continue
        every = set(names)
        for c in callees:
            every |= names_of(c)
        if not every:
            cls = UNNAMED
        else:
            hits = sum(inside(n) for n in every)
            cls = IN if hits == len(every) else OUT if hits == 0 else MIXED
        out[name] = (opcode, cls, names[0] if names else "")
    return out
