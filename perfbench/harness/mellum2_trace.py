"""Device time of what the ``mellum`` trunk adds, from the run's trace: the
sliding-window attention kernels and the causal ones by their instruction
names (``%flash_window_*`` against ``%flash_causal_*``: the two kinds of
attention layer run two kernel families), the TPU's grouped matmuls by
theirs, the expert layer's parts by the program's own scopes. The file
reading, the join to the program's scope map and the routing counter are
``joyai_trace``'s. Under another configuration's program (no
``sliding_attention`` layer in the cell's configuration), without that map or
without these kernels, every function here returns ``None`` and raises
nothing.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional

from .joyai_trace import (CAUSAL_KERNELS, GROUPED_KERNELS, _whole,
                          expert_part, load_named, trace_file)
from .joyai_trace import held_per_step  # noqa: F401 - the readers' counter
from .scope_reduce import module_name
from .trace_reduce import clip, self_seconds

WINDOW_KERNELS = re.compile(r"^%flash_window_(fwd|bwd)")
EXPERT_PARTS = ("router", "dispatch", "experts", "combine")


def label(name: str, op_name: Optional[str]) -> str:
    if WINDOW_KERNELS.match(name):
        return "window_kernels"
    if CAUSAL_KERNELS.match(name):
        return "causal_kernels"
    if GROUPED_KERNELS.match(name):
        return "experts"
    return expert_part(op_name, 0) or "rest"     # no leading dense layer


def reduce(ops, modules, window, steps: int, scope_map_of) -> Optional[dict]:
    """Milliseconds a step and chip by ``label``. ``None`` when no operation
    ran."""
    lo, hi = window
    per_chip: List[dict] = []
    for chip, events in sorted(ops.items()):
        events = clip(events, lo, hi)
        if not events:
            continue
        mods = sorted(clip(modules.get(chip, []), lo, hi),
                      key=lambda ev: ev[1])
        starts = [m[1] for m in mods]
        labelled = []
        for name, s, e in events:
            at = bisect.bisect_right(starts, s) - 1
            program = module_name(mods[at][0]) \
                if at >= 0 and e <= mods[at][2] else ""
            op_name = scope_map_of(program).get(name) if program else None
            labelled.append((label(name, op_name) + "|", s, e))
        per_chip.append(self_seconds(labelled))
    if not per_chip or not steps:
        return None
    to_ms = 1e-6 / steps / len(per_chip)
    out: Dict[str, float] = {}
    for sums in per_chip:
        for key, ns in sums.items():
            key = key.rstrip("|")
            out[key] = out.get(key, 0.0) + ns * to_ms
    return out


def layers(ctx, kind: str) -> int:
    """How many layers of ``kind`` the cell's configuration has."""
    cfg = ctx["cell"].config if "cell" in ctx else {}
    return list(cfg.get("layer_types", [])).count(kind)


def table(ctx) -> Optional[dict]:
    """The reduction of the run's trace, made once and kept in ``ctx``;
    ``None`` under a configuration with no sliding-window layer."""
    if "mellum2_table" in ctx:
        return ctx["mellum2_table"]
    found = None
    path, steps = trace_file(ctx), ctx.get("trace_steps")
    if path and steps and layers(ctx, "sliding_attention"):
        try:
            from ml_recipe_tpu.metrics.trace import scope_map
        except ImportError:
            scope_map = None
        ops, modules = load_named(path)
        maps: Dict[str, dict] = {}

        def scope_map_of(program):
            if program not in maps:
                maps[program] = scope_map(program) if scope_map else {}
            return maps[program]

        window = ctx["trace"].window() if ctx.get("trace") is not None \
            else _whole(modules, ops)
        found = reduce(ops, modules, window, steps, scope_map_of)
        if found is not None:
            from .result import note

            note(mellum2_table=found, mellum2_scope_maps={
                name: len(m) for name, m in maps.items()})
    ctx["mellum2_table"] = found
    return found


def part_ms(ctx, *parts) -> Optional[float]:
    """Summed ms a step of the named rows; ``None`` when the trace holds none
    of them (a program without these kernels or scopes)."""
    found = table(ctx)
    if not found or not any(p in found for p in parts):
        return None
    return sum(found.get(p, 0.0) for p in parts)


def core_roofline_pct(ctx, kind: str, part: str) -> Optional[float]:
    """100 x the least time the chip could take for the cores of the traced
    steps' layers of ``kind`` (the larger of FLOPs over the bf16 peak and
    bytes over the HBM peak, from shapes, permitted pairs only:
    ``flops_mellum2``) over the time the kernels under ``part`` took."""
    from . import flops_mellum2
    from .flops import roofline_seconds

    took_ms = part_ms(ctx, part)
    if not took_ms or not ctx.get("trace_shapes"):
        return None
    cfg = ctx["cell"].config
    least = 0.0
    for rows, seq in ctx["trace_shapes"]:
        rows_chip = rows / ctx["chips"]
        least += layers(ctx, kind) * roofline_seconds(
            flops_mellum2.core_flops(cfg, kind, rows_chip, seq,
                                     train=ctx["train"]),
            flops_mellum2.core_bytes(cfg, rows_chip, seq, train=ctx["train"]),
            ctx["peaks"])[0]
    return 100.0 * least / (took_ms * 1e-3 * ctx["trace_steps"])
