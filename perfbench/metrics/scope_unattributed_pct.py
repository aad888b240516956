"""Share of the device's self time in the traced window that no scope
reaches: instructions the scope map lacks (every Mosaic call, renamed by the
loader) or whose ``op_name`` names no phase. 100 when the traced program has
no scope map."""

from ..harness.scope_reduce import unattributed_pct


def read(ctx):
    return unattributed_pct(ctx)
