"""Which named scope a compiled program's instructions came from.

A device trace names an operation by its HLO instruction (`%fusion.412`,
`%copy.3`); only a Pallas kernel's custom call carries the scope it was
called under in its own name. The optimised HLO text keeps, for every
instruction, the `op_name` it was traced under
(`jit(step_paged)/.../layer_0/attn_0/mla.attend/dot_general`), so the map
from instruction to scope is read from there — for a fusion, the scope of
the instruction the compiler took its metadata from.
"""
from __future__ import annotations

import re
from typing import Dict

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?"
    r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")


def instruction_scopes(hlo_text: str) -> Dict[str, str]:
    """{instruction name (no `%`): op_name} over every computation of an
    optimised HLO module's text; instructions without metadata are left
    out."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


__all__ = ["instruction_scopes"]
