"""Device ops to graph nodes: the scope of each compiled HLO instruction.

The executor traces every graph node under ``jax.named_scope(f"n{id}.{op}")``
and every fused chain region under ``region.<id>+<id>...``; the workload's
postprocess head runs under ``head``.  XLA keeps the scope path in each
instruction's ``op_name`` metadata through optimization, so the optimized
HLO of a bucket executable says which node every device op belongs to —
including the pads, copies and layout changes XLA adds around a kernel.
A profiler trace names device ops by instruction (``%pad.17``) inside the
module that ran them (``jit__run(...)``); :func:`op_scopes` is the map
that charges them to nodes.  Built from the compiled text, once per
executable: nothing runs per call.
"""

from __future__ import annotations

import re
from typing import Mapping

#: The scope of an instruction whose op_name names no node, region or head
#: (weight prefetches, ops of arguments no map names).
NO_SCOPE = "none"
_SCOPE = re.compile(r"n\d+\.\w+|region\.\d+(?:\+\d+)*|head")
_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_INSTR = re.compile(r"^\s*(?:ROOT )?%([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(op_name: str) -> str:
    """The innermost node, region or head scope in an ``op_name`` path
    (``jit(_run)/n3.packed_conv_pool/jit(chain_conv)/pallas_call`` →
    ``n3.packed_conv_pool``), else :data:`NO_SCOPE`."""
    found = NO_SCOPE
    for part in op_name.split("/"):
        if _SCOPE.fullmatch(part):
            found = part
    return found


def op_scopes(hlo_text: str, args: Mapping[str, str] | None = None
              ) -> dict[str, dict[str, str]]:
    """``{module name: {instruction name: scope}}`` of one compiled
    module's text (``jax.stages.Compiled.as_text()``).  Instruction names
    are unique within a module, not across modules.

    An op XLA adds on an argument (a layout copy of the input or of a
    weight) carries the argument's path as its ``op_name`` (``x``,
    ``arrays['3']['w_packed']``) and no scope; ``args`` maps such a path
    prefix to the scope of the node that reads it."""
    m = _MODULE.search(hlo_text)
    if m is None:
        raise ValueError("not an HLO module's text")
    args = args or {}
    out: dict[str, str] = {}
    for line in hlo_text.splitlines():
        ins = _INSTR.match(line)
        if ins is None:
            continue
        op = _OP_NAME.search(line)
        scope = scope_of(op.group(1)) if op else NO_SCOPE
        if scope == NO_SCOPE and op:
            name = op.group(1).replace("\\'", "'")   # HLO text escapes '
            scope = next((sc for prefix, sc in args.items()
                          if name == prefix or name.startswith(prefix + "[")),
                         NO_SCOPE)
        out[ins.group(1)] = scope
    return {m.group(1): out}
