"""Device time of what the ``olmo_hybrid`` trunk adds, from the run's trace:
the causal attention kernels by their instruction names, and the
linear-attention operator's parts by the program's own scopes
(``linear_attention`` the module; ``gated_delta`` the scan forward and
backward, ``qkv_conv`` the taps, SiLU and l2 norms, ``gated_norm`` the gated
RMSNorm inside it; the module's own row is its projections). The file
reading and the join to the program's scope map are ``joyai_trace``'s. Under
another configuration's program (no ``linear_attention`` layer in the cell's
configuration), without that map or without these scopes, every function here
returns ``None`` and raises nothing.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional

from .joyai_trace import CAUSAL_KERNELS, _whole, load_named, trace_file
from .scope_reduce import bare, components, module_name
from .trace_reduce import clip, self_seconds

MODULE = "linear_attention"
PARTS = ("gated_delta", "qkv_conv", "gated_norm")


def linear_part(op_name: Optional[str]):
    """``'gated_delta'`` / ``'qkv_conv'`` / ``'gated_norm'`` for an operation
    under that scope of a linear-attention operator, ``'linear_attention'``
    for the rest of the operator (its projections), else ``None``."""
    if not op_name:
        return None
    names = [bare(c) for c in components(op_name)[:-1]]
    if MODULE not in names:
        return None
    return next((p for p in PARTS if p in names), MODULE)


def label(name: str, op_name: Optional[str]) -> str:
    if CAUSAL_KERNELS.match(name):
        return "causal_kernels"
    return linear_part(op_name) or "rest"


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


def scan_layers(ctx) -> int:
    cfg = ctx["cell"].config if "cell" in ctx else {}
    return list(cfg.get("layer_types", [])).count(MODULE)


def attention_layers(ctx) -> int:
    return ctx["cell"].config.get("layer_types", []).count("full_attention")


def table(ctx) -> Optional[dict]:
    """The reduction of the run's trace, made once and kept in ``ctx``;
    ``None`` under a configuration with no linear-attention layer."""
    if "olmo_hybrid_table" in ctx:
        return ctx["olmo_hybrid_table"]
    found = None
    path, steps = trace_file(ctx), ctx.get("trace_steps")
    if path and steps and scan_layers(ctx):
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

            note(olmo_hybrid_table=found, olmo_hybrid_scope_maps={
                name: len(m) for name, m in maps.items()})
    ctx["olmo_hybrid_table"] = found
    return found


def part_ms(ctx, *parts) -> Optional[float]:
    """Summed ms a step of the named rows; ``None`` when the trace holds none
    of them (a program without these kernels or scopes)."""
    found = table(ctx)
    if not found or not any(p in found for p in parts):
        return None
    return sum(found.get(p, 0.0) for p in parts)
