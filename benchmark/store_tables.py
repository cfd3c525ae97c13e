"""Device time a capture spends on whole per-node store tables outside
the program's store scopes.

The program's ``stores_read`` and ``stores_write`` scopes hold the
gathers, sets and scatter-add it wrote. What the compiler adds to make
them possible carries no scope: where the device keeps a ``[rows, dim]``
float32 store column-major, the compiled step copies the whole table to
a row-major temporary before the first gather and back after the last
write (PR 30 on the v5e: four copies of 535 MB, 9.65 of 10.34 ms a
step). Those ops are found here by what they produce: an op of no scope
whose result has the stores' shape, ``f32[num_nodes + 1, dim]`` (a
gather's or a scatter's result of that shape rides its scope and is not
counted twice). They are part of ``step.unscoped_ms`` too.

Imports nothing of the program.
"""

from __future__ import annotations

import re

from benchmark import scopes

_RESULT = r"^%?([\w.\-]+) = f32\[{rows},{dim}\]"


def layout_copy_ms(ctx):
    """Device ms per traced step, fullest chip, of unscoped ops that
    produce a store-shaped table; 0.0 where the step has none. None
    without a capture and its HLO text, or where the program names no
    store scope (a program from before them: every store op is unscoped
    there, and the split would be a guess)."""
    ms = scopes.step_scope_ms(ctx)
    if not ms or not (ms.get("stores_read") or ms.get("stores_write")):
        return None
    if ctx.capture is None:
        return None
    table = scopes.scope_table(scopes.hlo_path_for(ctx.xplane_path))
    result = re.compile(_RESULT.format(
        rows=ctx.cfg["graph"]["num_nodes"] + 1, dim=ctx.cfg["dim"]))
    ns = 0.0
    for name, start, end in ctx.capture.fullest().events:
        m = result.match(name)
        if m and table.get(m.group(1), scopes.UNSCOPED) == scopes.UNSCOPED:
            ns += end - start
    return ns * 1e-6 / ctx.trace_steps
