"""Device time of what the ``lfm2_moe`` trunk adds, from the run's trace: the
causal attention kernels and the TPU's grouped matmuls by their instruction
names, the expert layer's parts and the convolution operator's by the
program's own scopes (``conv`` the module, ``short_conv`` the gating and taps
inside it). The file reading, the join to the program's scope map and the
routing counter are ``joyai_trace``'s; under a program without that map, or
without these kernels and scopes, every function here returns ``None`` and
raises nothing.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional

from .joyai_trace import (CAUSAL_KERNELS, GROUPED_KERNELS, _whole,
                          expert_part, load_named, trace_file)
from .joyai_trace import held_per_step  # noqa: F401 - the readers' counter
from .scope_reduce import bare, components, module_name
from .trace_reduce import clip, self_seconds

EXPERT_PARTS = ("router", "dispatch", "experts", "combine")


def conv_part(op_name: Optional[str]):
    """``'short_conv'`` for an operation of the gating and taps, ``'conv'``
    for the rest of a convolution operator (its projections), else
    ``None``."""
    if not op_name:
        return None
    names = [bare(c) for c in components(op_name)[:-1]]
    if "conv" not in names:
        return None
    return "short_conv" if "short_conv" in names else "conv"


def label(name: str, op_name: Optional[str], first_expert_layer: int) -> str:
    if CAUSAL_KERNELS.match(name):
        return "causal_kernels"
    if GROUPED_KERNELS.match(name):
        return "experts"
    return (conv_part(op_name) or expert_part(op_name, first_expert_layer)
            or "rest")


def reduce(ops, modules, window, steps: int, scope_map_of,
           first_expert_layer: int) -> Optional[dict]:
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
            labelled.append(
                (label(name, op_name, first_expert_layer) + "|", s, e))
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


def table(ctx) -> Optional[dict]:
    """The reduction of the run's trace, made once and kept in ``ctx``."""
    if "lfm2_table" in ctx:
        return ctx["lfm2_table"]
    found = None
    path, steps = trace_file(ctx), ctx.get("trace_steps")
    if path and steps:
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
        found = reduce(ops, modules, window, steps, scope_map_of,
                       int(ctx["cell"].config.get("num_dense_layers", 0)))
        if found is not None:
            from .result import note

            note(lfm2_table=found, lfm2_scope_maps={
                name: len(m) for name, m in maps.items()})
    ctx["lfm2_table"] = found
    return found


def part_ms(ctx, *parts) -> Optional[float]:
    """Summed ms a step of the named rows; ``None`` when the trace holds none
    of them (a program without these kernels or scopes)."""
    found = table(ctx)
    if not found or not any(p in found for p in parts):
        return None
    return sum(found.get(p, 0.0) for p in parts)


def conv_layers(ctx) -> int:
    return ctx["cell"].config.get("layer_types", []).count("conv")


def attention_layers(ctx) -> int:
    return ctx["cell"].config.get("layer_types", []).count("full_attention")


def expert_layers(ctx) -> int:
    cfg = ctx["cell"].config
    return len(cfg.get("layer_types", [])) - cfg.get("num_dense_layers", 0)
