"""Named parts of the fused replay step, and their map onto the compiled
program.

The step functions of :mod:`repro.core.replay` wrap their work in
:func:`jax.named_scope`\\ s named in :data:`STEP_SCOPES`.  A named scope
changes only the ``op_name`` metadata of the HLO it produces, so the
compiled program is the same with or without it; a profiler trace of the
device names each executed op by its HLO instruction, and
:func:`op_scopes` maps those instructions back to the step's parts.

The map has to come from the program that ran: inside
``with recording():`` every runner call made through :func:`run` keeps the
optimized HLO text of its compiled program under the runner's name.
Outside it, :func:`run` costs one check of a module global per call.
"""

from __future__ import annotations

import contextlib
import functools
import re
from collections import Counter
from typing import Dict, Iterator, Optional

import jax

#: the step's parts, as the ``jax.named_scope`` names the program uses
STEP_SCOPES = ("lfb", "transport", "media", "flash", "telemetry",
               "collective")
#: instructions with an ``op_name`` that holds no step scope: the scan's
#: own slicing and stacking, and everything outside the loop
LOOP = "loop"
#: instructions with no ``op_name`` at all, in themselves or (fusions) in
#: their fused computation
UNNAMED = "unnamed"

_kept: Optional[Dict[str, str]] = None


def scoped(name: str):
    """Decorator: trace the function inside ``jax.named_scope(name)``.  A
    fresh scope per call (one named_scope object used as a decorator keeps
    its state on itself, so it cannot be entered twice at once)."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


@contextlib.contextmanager
def recording() -> Iterator[Dict[str, str]]:
    """Keep, for each runner called through :func:`run` inside the block,
    the optimized HLO text of its first compiled program, keyed by the
    runner's name; yields that dict."""
    global _kept
    prev, _kept = _kept, {}
    try:
        yield _kept
    finally:
        _kept = prev


def run(runner, *args):
    """``runner(*args)``; inside :func:`recording`, first keep the compiled
    program's HLO text (lowering and compiling it again is then part of
    the call)."""
    if _kept is not None and runner.__name__ not in _kept:
        _kept[runner.__name__] = runner.lower(*args).compile().as_text()
    return runner(*args)


_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
# the opcode is the first word after the type that opens a bracket: layout
# marks such as ``T(128)`` never follow a space
_INSTR = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s+=\s+.*?\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")


def _innermost(op_name: str) -> str:
    """The innermost step scope of an ``op_name`` path; a compiler that
    merged instructions joins their paths with ``;``, and the most common
    scope among them wins."""
    found = Counter()
    for path in op_name.split(";"):
        for part in reversed(path.split("/")):
            if part in STEP_SCOPES:
                found[part] += 1
                break
    return found.most_common(1)[0][0] if found else LOOP


def op_scopes(hlo_text: str, infer: bool = True) -> Dict[str, str]:
    """``{instruction name: scope}`` over an optimized HLO module's text,
    fused computations and conditional branches included.

    An instruction takes the innermost :data:`STEP_SCOPES` name on its
    ``op_name`` path, or :data:`LOOP` where the path holds none.  A fusion
    with no ``op_name`` takes the scope of its fused computation's root,
    or else the most common scope of that computation's named
    instructions.  What is left has no ``op_name`` at all: the compiler
    made it (a copy, or one half of a split 64-bit op).  With ``infer``
    such an instruction takes the most common scope of its operands,
    else of its users; an asynchronous copy (``copy-start``/``copy-done``)
    looks at its users first, since the core waits on it to launch them.
    Ties go to :data:`LOOP` where it is among them.  What is still left is
    :data:`UNNAMED`."""
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, str] = {}
    opcode: Dict[str, str] = {}
    operands: Dict[str, list] = {}
    users: Dict[str, list] = {}
    comps: Dict[str, list] = {}
    roots: Dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
            comps[comp] = []
            continue
        m = _INSTR.match(line)
        if not m or comp is None:
            continue
        name = m.group(2)
        opcode[name] = m.group(3)
        comps[comp].append(name)
        if m.group(1):
            roots[comp] = name
        head, _, meta = line.partition(", metadata={")
        on = _OP_NAME.search(meta)
        own[name] = _innermost(on.group(1)) if on else None
        c = _CALLS.search(head)
        if c:
            calls[name] = c.group(1)
        operands[name] = list(dict.fromkeys(_REF.findall(head[m.end():])))
        for ref in operands[name]:
            users.setdefault(ref, []).append(name)

    scope: Dict[str, str] = {n: s for n, s in own.items() if s is not None}

    def fused(name: str, depth: int = 0) -> Optional[str]:
        body = comps.get(calls.get(name, ""), [])
        root = roots.get(calls.get(name, ""))
        if root is not None:
            s = scope.get(root) or (fused(root, depth + 1)
                                    if depth < 8 else None)
            if s is not None:
                return s
        return _vote(scope.get(n) for n in body)

    for name in own:
        if name not in scope and name in calls:
            s = fused(name)
            if s is not None:
                scope[name] = s

    while infer:
        found = {}
        for name in own:
            if name in scope:
                continue
            sides = (users, operands) if opcode[name] in _ASYNC_COPY \
                else (operands, users)
            for side in sides:
                s = _vote(scope.get(n) for n in side.get(name, ()))
                if s is not None:
                    found[name] = s
                    break
        scope.update(found)
        infer = bool(found)
    return {n: scope.get(n, UNNAMED) for n in own}


_ASYNC_COPY = ("copy-start", "copy-done")


def _vote(scopes) -> Optional[str]:
    """The most common of the scopes given (``None`` ones skipped), with
    ties to :data:`LOOP` where it is among them."""
    got = Counter(s for s in scopes if s is not None)
    if not got:
        return None
    top = max(got.values())
    best = [s for s, v in got.items() if v == top]
    return LOOP if LOOP in best else best[0]
