"""Device time of what the ``joyai_llm_flash`` trunk adds, from the run's
trace: the two-width causal attention kernels by their instruction names, and
the expert layer's parts by the program's own scopes.

``trace_reduce.load`` renames every Mosaic call to ``%tpu_custom_call.0``, so
neither a kernel's name nor its scope can be had from the ``Trace`` in
``ctx``. This module reads the same ``.xplane.pb`` again and keeps each
event's instruction name (``%flash_causal_fwd.3``, ``%ragged-dot-none.7``:
what stands before `` = ``), then joins it to the program's scope map
(``ml_recipe_tpu.metrics.trace.scope_map``) as ``scope_reduce`` does. Under a
program without that map, or without these kernels and scopes (the parent of
the PR that added them), every function here returns ``None`` and raises
nothing.
"""

from __future__ import annotations

import bisect
import glob
import re
from typing import Dict, List, Optional

from .manifest import CACHE_DIR
from .scope_reduce import bare, components, module_name
from .trace_reduce import (DEVICE_PLANE, MODULE_LINE, OP_LINE, clip,
                           self_seconds)

CAUSAL_KERNELS = re.compile(r"^%flash_causal_(fwd|bwd)")
# what the TPU compiler makes of ``jax.lax.ragged_dot``: its own grouped-matmul
# kernel and that kernel's tile metadata. The rewrite drops the operation's
# scope (``op_name="ragged-dot-none"``), so these are told by name; nothing
# else in the program is a ragged dot.
GROUPED_KERNELS = re.compile(r"^%ragged-dot")
EXPERT_PARTS = ("router", "dispatch", "experts", "shared_expert", "combine")
_LAYER = re.compile(r"^layer_(\d+)$")


def trace_file(ctx) -> Optional[str]:
    """The traced stretch's file: ``ctx['trace_file']`` (tests), else the
    newest one the runner's profiler left for this cell."""
    if ctx.get("trace_file"):
        return ctx["trace_file"]
    if ctx.get("trace") is None or "cell" not in ctx:
        return None
    found = sorted(glob.glob(str(
        CACHE_DIR / "trace" / ctx["cell"].name / "plugins" / "profile" / "*"
        / "*.xplane.pb")))
    return found[-1] if found else None


def load_named(path: str):
    """``(ops, modules)``: chip -> events ``(instruction, start, end)``."""
    from jax.profiler import ProfileData

    ops: Dict[int, list] = {}
    modules: Dict[int, list] = {}
    for plane in ProfileData.from_file(str(path)).planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            into = {OP_LINE: ops, MODULE_LINE: modules}.get(line.name)
            if into is None:
                continue
            events = into.setdefault(int(m.group(1)), [])
            for ev in line.events:
                name = ev.name if into is modules \
                    else ev.name.split(" = ", 1)[0]
                events.append((name, ev.start_ns,
                               ev.start_ns + ev.duration_ns))
    return ops, modules


def expert_part(op_name: Optional[str], first_expert_layer: int):
    """``'experts'`` etc. for an operation under an expert layer's ``mlp``
    (``'other'`` where none of the five parts is on its path), else
    ``None``."""
    if not op_name:
        return None
    parts = components(op_name)
    for at, component in enumerate(parts[:-1]):
        layer = _LAYER.match(component)     # ``bare`` would eat the index
        if layer and int(layer.group(1)) >= first_expert_layer \
                and parts[at + 1] == "mlp":
            below = [bare(c) for c in parts[at + 2:]]
            return next((p for p in EXPERT_PARTS if p in below), "other")
    return None


def reduce(ops, modules, window, steps: int, scope_map_of,
           first_expert_layer: int) -> Optional[dict]:
    """Milliseconds a step and chip: ``causal_kernels``, and the expert
    layer's self time by part. ``None`` when no operation ran."""
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
            if CAUSAL_KERNELS.match(name):
                label = "causal_kernels"
            elif GROUPED_KERNELS.match(name):
                label = "experts"
            else:
                label = expert_part(
                    scope_map_of(program).get(name) if program else None,
                    first_expert_layer) or "rest"
            labelled.append((label + "|", s, e))
        per_chip.append(self_seconds(labelled))
    if not per_chip or not steps:
        return None
    to_ms = 1e-6 / steps / len(per_chip)
    out: Dict[str, float] = {}
    for sums in per_chip:
        for label, ns in sums.items():
            key = label.rstrip("|")
            out[key] = out.get(key, 0.0) + ns * to_ms
    return out


def table(ctx) -> Optional[dict]:
    """The reduction of the run's trace, made once and kept in ``ctx``."""
    if "joyai_table" in ctx:
        return ctx["joyai_table"]
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
                       int(ctx["cell"].config.get("first_k_dense_replace", 0)))
        if found is not None:
            from .result import note

            note(joyai_table=found, joyai_scope_maps={
                name: len(m) for name, m in maps.items()})
    ctx["joyai_table"] = found
    return found


def _whole(modules, ops):
    events = [e for m in modules.values() for e in m] or [
        e for o in ops.values() for e in o]
    if not events:
        return 0.0, 0.0
    return min(e[1] for e in events), max(e[2] for e in events)


def part_ms(ctx, *parts) -> Optional[float]:
    """Summed ms a step of the named rows; ``None`` when the trace holds none
    of them (a program without these kernels or scopes)."""
    found = table(ctx)
    if not found or not any(p in found for p in parts):
        return None
    return sum(found.get(p, 0.0) for p in parts)


def held_per_step(ctx) -> Optional[float]:
    """Assignments to held experts a step (summed over the expert layers),
    the median of the telemetry stretch; ``None`` where the program has no
    such counter."""
    reg = ctx.get("telemetry")
    series = reg.get("train_moe_held_assignments") if reg is not None else None
    if series is None or not series.count:
        return None
    return series.quantile(0.5)
