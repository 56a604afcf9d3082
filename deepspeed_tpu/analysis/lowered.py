"""Which kernels a lowered program holds, read from its StableHLO text.

A Pallas kernel compiled for the chip lowers to a ``stablehlo.custom_call
@tpu_custom_call`` carrying its ``kernel_name``; the interpreter and every
XLA reference path lower to plain ops. So the text of a lowered module
(``jitted.lower(...).as_text()``, or the files ``jax_dump_ir_to`` writes) says
which implementation a dispatch site actually traced — independent of any
flag the dispatching code keeps about itself. ``chip_smoke.py`` prints and
asserts on this.
"""

import re
from dataclasses import dataclass
from typing import Dict, List, Set

_STRING = re.compile(r'"[^"]*"')
_FUNC = re.compile(r"func\.func\s+(?:public\s+|private\s+)?@([\w.$-]+)")
_CALL = re.compile(r"\bcall\s+@([\w.$-]+)")
_KERNEL = re.compile(r'@tpu_custom_call\b.*?kernel_name\s*=\s*"([^"]*)"')
_MAIN_INT_ARG = re.compile(r"tensor<((?:\d+x){2,})i32>")


@dataclass
class MosaicCall:
    kernel: str          # the pallas_call's name
    count: int           # call sites in the module (one per unrolled layer)
    in_loop: bool        # at least one site runs inside a stablehlo.while body


def _match_brace(text: str, open_idx: int) -> int:
    """Index just past the ``}`` closing the ``{`` at ``open_idx`` (``text``
    must already have its string literals blanked)."""
    depth = 0
    for i in range(open_idx, len(text)):
        c = text[i]
        if c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
            if depth == 0:
                return i + 1
    raise ValueError("unbalanced braces in lowered module text")


def _functions(blank: str) -> Dict[str, slice]:
    """Body span of every ``func.func`` (the printer keeps a signature on one
    line, so the body opens at that line's last brace)."""
    out = {}
    for m in _FUNC.finditer(blank):
        open_idx = blank.rindex("{", m.start(), blank.index("\n", m.start()))
        out[m.group(1)] = slice(open_idx, _match_brace(blank, open_idx))
    return out


def _while_bodies(blank: str, span: slice) -> List[slice]:
    """``do { ... }`` regions of every ``stablehlo.while`` inside ``span``."""
    bodies = []
    pos = span.start
    while True:
        w = blank.find("stablehlo.while", pos, span.stop)
        if w < 0:
            return bodies
        cond_open = blank.index("{", blank.index("cond", w))
        cond_end = _match_brace(blank, cond_open)
        do_open = blank.index("{", blank.index("do", cond_end))
        do_end = _match_brace(blank, do_open)
        bodies.append(slice(do_open, do_end))
        pos = w + 1              # nested whiles are found by the same scan


def mosaic_calls(text: str) -> List[MosaicCall]:
    """Every Mosaic (compiled Pallas) kernel in a lowered StableHLO module,
    with how many call sites it has and whether any sits inside a loop body
    (directly, or through functions the body calls)."""
    # kernel names live inside string literals, so find them on the raw text
    # and keep positions; structure (braces, calls) is read off a copy whose
    # literals are blanked — backend_config JSON is full of braces
    blank = _STRING.sub(lambda m: '"' + " " * (len(m.group()) - 2) + '"', text)
    funcs = _functions(blank)
    sites = [(m.start(), m.group(1)) for m in _KERNEL.finditer(text)]

    def owner(pos: int) -> str:
        for name, sp in funcs.items():
            if sp.start <= pos < sp.stop:
                return name
        raise ValueError("custom call outside any function")

    direct: Dict[str, List[str]] = {name: [] for name in funcs}
    for pos, kernel in sites:
        direct[owner(pos)].append(kernel)
    callees = {name: set(_CALL.findall(blank[sp])) & set(funcs)
               for name, sp in funcs.items()}

    def reach(name: str, seen: Set[str]) -> Set[str]:
        if name in seen:
            return set()
        seen.add(name)
        got = set(direct[name])
        for c in callees[name]:
            got |= reach(c, seen)
        return got

    looped: Set[str] = set()
    for name, sp in funcs.items():
        for body in _while_bodies(blank, sp):
            looped |= {k for pos, k in sites if body.start <= pos < body.stop}
            for c in set(_CALL.findall(blank[body])) & set(funcs):
                looped |= reach(c, set())
    counts: Dict[str, int] = {}
    for _, kernel in sites:
        counts[kernel] = counts.get(kernel, 0) + 1
    return [MosaicCall(k, n, k in looped) for k, n in sorted(counts.items())]


def main_int_arg_shapes(text: str) -> List[str]:
    """Shapes of ``@main``'s int32 arguments of rank >= 2 (``"1x64"``, …) —
    the first is a serving prefill's padded prompt bucket."""
    m = re.search(r"func\.func\s+public\s+@main\((.*?)\)\s*->", text, re.S)
    sig = m.group(1) if m else ""
    return [s.rstrip("x") for s in _MAIN_INT_ARG.findall(_STRING.sub('""', sig))]
