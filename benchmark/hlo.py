"""Reading the compiled step's HLO text: which instruction is what.

A device trace names its events after HLO instructions (``fusion.12``,
``all-reduce-start.3``). Nothing in the program names its regions yet, so
the split a trace allows is by what XLA's own text says of each
instruction: a fusion or op that holds a ``dot`` or a ``convolution`` (on a
TPU a dot is often written as a convolution), a Mosaic custom call, a
collective, the rest.
"""

from __future__ import annotations

import re

MATMUL, MOSAIC, COLLECTIVE, OTHER = "matmul", "mosaic", "collective", "other"

_COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                   "collective-permute", "all-to-all", "collective-broadcast")
_MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")


def _instruction(line: str):
    """``(name, opcode, rest)`` of an instruction line, or ``None``."""
    s = line.strip()
    if s.startswith("ROOT "):
        s = s[5:]
    if " = " not in s:
        return None
    name, rhs = s.split(" = ", 1)
    name = name.lstrip("%")
    if not re.fullmatch(r"[\w.\-]+", name):
        return None
    if rhs.startswith("("):  # a tuple shape: skip to its closing bracket
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rhs = rhs[i + 1:].lstrip()
                break
    else:
        rhs = rhs.split(" ", 1)[1] if " " in rhs else ""
    m = re.match(r"([\w\-]+)\(", rhs)
    return (name, m.group(1), rhs) if m else None


def computations(text: str) -> dict[str, list[tuple[str, str, str]]]:
    """Computation name -> its instructions as ``(name, opcode, rest)``."""
    out: dict[str, list] = {}
    current = None
    for line in text.splitlines():
        if current is None:
            m = _HEADER.match(line)
            if m and "->" in line:
                current = out.setdefault(m.group(1), [])
            continue
        if line.strip() == "}":
            current = None
            continue
        ins = _instruction(line)
        if ins is not None:
            current.append(ins)
    return out


def _is_collective(opcode: str) -> bool:
    return opcode.startswith(_COLLECTIVE_OPS)


def categorize(text: str) -> dict[str, str]:
    """Instruction name -> ``matmul`` | ``mosaic`` | ``collective`` |
    ``other``, for every instruction of every computation of the module.
    A fusion or an async wrapper takes the category of what it calls."""
    comps = computations(text)

    def direct(opcode: str, rest: str) -> str:
        if opcode in ("dot", "convolution"):
            return MATMUL
        if opcode == "custom-call" and _MOSAIC_TARGET in rest:
            return MOSAIC
        if _is_collective(opcode):
            return COLLECTIVE
        return OTHER

    holds: dict[str, str] = {}
    for cname, instrs in comps.items():
        kinds = {direct(op, rest) for _, op, rest in instrs}
        holds[cname] = next(
            (k for k in (COLLECTIVE, MOSAIC, MATMUL) if k in kinds), OTHER
        )

    cats: dict[str, str] = {}
    for instrs in comps.values():
        for name, opcode, rest in instrs:
            cat = direct(opcode, rest)
            if cat == OTHER and (opcode == "fusion"
                                 or opcode.startswith("async-")):
                called = _CALLS.search(rest)
                if called:
                    cat = holds.get(called.group(1), OTHER)
            cats[name] = cat
    return cats


_OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_names(text: str) -> dict[str, str]:
    """Instruction name -> the source it was traced from (the ``op_name``
    of its metadata, without the ``jit(...)/`` prefix), where it has one:
    what tells a reader of a breakdown which ``fusion.4113`` is."""
    out = {}
    for instrs in computations(text).values():
        for name, _, rest in instrs:
            m = _OP_NAME.search(rest)
            if m:
                out[name] = re.sub(r"^jit\([^)]*\)/", "", m.group(1))
    return out


def count_ops(text: str, opcodes: tuple[str, ...]) -> int:
    """Instructions of the module whose opcode is one of ``opcodes``."""
    return sum(op in opcodes
               for instrs in computations(text).values()
               for _, op, _ in instrs)


def all_reduce_count(text: str) -> int:
    """All-reduces in the compiled step; an asynchronous pair counts once."""
    return count_ops(text, ("all-reduce", "all-reduce-start"))


def mosaic_call_count(text: str) -> int:
    return sum(op == "custom-call" and _MOSAIC_TARGET in rest
               for instrs in computations(text).values()
               for _, op, rest in instrs)


def module_name(text: str) -> str:
    m = re.search(r"^HloModule\s+([\w.\-]+)", text, re.M)
    return m.group(1) if m else ""
