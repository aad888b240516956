"""Device time by the program's own scopes: the window's ``XLA Ops`` events
joined, by instruction name, to the scope map the program registers
(``ml_recipe_tpu.metrics.trace.scope_map``: instruction -> ``op_name``, the
``jax.named_scope`` path of the operation, for a fusion that of its root).

An event is counted for its self time (``trace_reduce.self_seconds``: a
``%while`` for its bookkeeping, not for its body), looked up in the map of
the program whose ``XLA Modules`` event contains it, and sorted twice:

phase   ``bwd`` if a component of the ``op_name`` starts with ``transpose(``,
        else ``fwd`` if one starts with ``jvp(``, else ``update`` if one is a
        step phase of the trainer (``STEP_PHASES``), else unattributed; so is
        an instruction the map lacks. A Mosaic call is one of those: the
        loader renamed it to ``%tpu_custom_call.0`` (``trace_reduce.short``).
block   the first of ``BLOCKS`` that a component matches, whatever the phase;
        in the update phase the step phase itself.

``fwd + bwd + update + unattributed`` is the device's self time in the
window. With no map for the traced program everything is unattributed: the
times read 0.0 and the coverage 100.0, which is what such a run knows.
"""

from __future__ import annotations

import bisect
import re
import time
from typing import Callable, Dict, List, Optional, Tuple

from .result import note
from .trace_reduce import Trace, clip, kind, self_seconds

STEP_PHASES = ("grad_accumulate", "grad_reduce", "grad_clip", "optimizer",
               "step_metrics")
PHASES = ("fwd", "bwd", "update")
UNATTRIBUTED = "unattributed"
# block -> the module or scope names that mean it, in the order they are tried
BLOCKS = (
    ("flash_fwd", ("flash_fwd",)),
    ("flash_bwd", ("flash_bwd",)),
    ("attention", ("attention",)),
    ("mlp", ("mlp",)),
    ("embeddings", ("embeddings",)),
    ("layer_norm", ("layer_norm", "LayerNorm", "FusedLayerNorm")),
    ("dropout", ("Dropout",)),
    ("heads", ("position_outputs", "classifier", "reg_start", "reg_end",
               "pooler")),
    ("loss", ("loss",)),
)
MOSAIC = "%tpu_custom_call.0"       # what trace_reduce.short() left of a name
_MODULE_ID = re.compile(r"\(\d+\)$")
_WRAPPED = re.compile(r"^(?:\w+\()+([^()]*)\)+$")   # transpose(jvp(X)) -> X
_AUTO_INDEX = re.compile(r"_\d+$")                  # Dropout_0 -> Dropout


def components(op_name: str) -> List[str]:
    """The ``/``-separated scopes of an ``op_name`` (of the first one where
    XLA merged several with ``;``)."""
    return op_name.split(";", 1)[0].split("/")


def bare(component: str) -> str:
    """``loss`` of ``transpose(jvp(loss))``, ``Dropout`` of ``Dropout_0``."""
    wrapped = _WRAPPED.match(component)
    return _AUTO_INDEX.sub("", wrapped.group(1) if wrapped else component)


def classify(op_name: Optional[str]) -> Optional[Tuple[str, str, str]]:
    """``(phase, block, part)`` of an operation, or ``None`` when no rule
    reaches it. ``part`` is the sub-module right under the block (``query``,
    ``layer_norm``, ``Dropout_0``) or ``-``."""
    if not op_name:
        return None
    parts = components(op_name)
    if any(c.startswith("transpose(") for c in parts):
        phase = "bwd"
    elif any(c.startswith("jvp(") for c in parts):
        phase = "fwd"
    elif any(c in STEP_PHASES for c in parts):
        phase = "update"
    else:
        return None
    names = [bare(c) for c in parts]
    if phase == "update":       # the innermost step phase
        at = max(i for i, c in enumerate(parts) if c in STEP_PHASES)
        return phase, parts[at], "-"
    for block, spellings in BLOCKS:
        for at, name in enumerate(names):
            if name in spellings:
                below = parts[at + 1:-1]    # the last one is the primitive
                part = below[0] if below and "(" not in below[0] else "-"
                return phase, block, part
    return phase, "other", "-"


def module_name(event_name: str) -> str:
    """``jit_train_step`` of ``jit_train_step(9701493265859229110)``."""
    return _MODULE_ID.sub("", event_name)


def _labelled(ops, modules, scope_map_of: Callable[[str], Dict[str, str]]):
    """The events renamed to what they are counted under: the kind of
    operation, then phase, block and part, or ``unattributed``, program and
    instruction. Every label ends in ``|`` so that ``trace_reduce.kind``
    leaves it whole."""
    modules = sorted(modules, key=lambda ev: ev[1])
    starts = [m[1] for m in modules]
    out = []
    for name, s, e in ops:
        at = bisect.bisect_right(starts, s) - 1
        program = module_name(modules[at][0]) \
            if at >= 0 and e <= modules[at][2] else ""
        found = classify(scope_map_of(program).get(name)) if program else None
        label = (kind(name), *(found or (UNATTRIBUTED, program, name)))
        out.append(("|".join(label) + "|", s, e))
    return out


def _top(sums: Dict[tuple, float], n: Optional[int] = None) -> List[list]:
    heavy = sorted(sums.items(), key=lambda kv: -kv[1])
    return [list(key) + [ms] for key, ms in heavy[:n]]


def reduce(trace: Trace, steps: int,
           scope_map_of: Callable[[str], Dict[str, str]],
           top: int = 10) -> Optional[dict]:
    """Milliseconds a step, averaged over the chips that ran anything, by
    phase, by block, by (phase, block, part) and, for the heaviest, by kind
    of operation within those; the heaviest unattributed instructions; the
    share of device time no scope reaches."""
    lo, hi = trace.window()
    per_chip: List[Dict[str, float]] = []
    for chip, ops in sorted(trace.device_ops.items()):
        ops = clip(ops, lo, hi)
        if not ops:
            continue
        modules = clip(trace.device_modules.get(chip, []), lo, hi)
        by_label = self_seconds(_labelled(ops, modules, scope_map_of))
        device_ns = sum(self_seconds(ops).values())
        if abs(sum(by_label.values()) - device_ns) > 1e-3 * device_ns:
            raise AssertionError(
                f"chip {chip}: the scopes hold {sum(by_label.values())} ns "
                f"of {device_ns} ns of device self time")
        per_chip.append(by_label)
    if not per_chip or not steps:
        return None
    to_ms = 1e-6 / steps / len(per_chip)
    phases = dict.fromkeys(PHASES + (UNATTRIBUTED,), 0.0)
    blocks: Dict[str, float] = {}
    rows: Dict[tuple, float] = {}
    kinds: Dict[tuple, float] = {}
    loose: Dict[tuple, float] = {}

    def add(sums, key, ms):
        sums[key] = sums.get(key, 0.0) + ms

    for by_label in per_chip:
        for label, ns in by_label.items():
            op_kind, phase, *rest = label.split("|")[:-1]
            ms = ns * to_ms
            phases[phase] += ms
            if phase == UNATTRIBUTED:
                add(loose, tuple(rest), ms)
            else:
                add(blocks, rest[0], ms)
                add(rows, (phase, *rest), ms)
                add(kinds, (op_kind, phase, *rest), ms)
    device_ms = sum(phases.values())
    return {
        "device_ms_step": device_ms,
        "phases": phases,
        "blocks": dict(sorted(blocks.items(), key=lambda kv: -kv[1])),
        "unattributed_pct": 100.0 * phases[UNATTRIBUTED] / device_ms
        if device_ms else 100.0,
        "mosaic_ms_step": sum(ms for key, ms in loose.items()
                              if key[-1] == MOSAIC),
        "table": _top(rows),
        "kinds": _top(kinds, 4 * top),
        "unattributed_top": _top(loose, top),
        "chips": len(per_chip), "steps": steps,
    }


# -- what the readers under metrics/ call -------------------------------------------

def table(ctx: dict) -> Optional[dict]:
    """The reduction of the run's trace, made once (kept in ``ctx``) and
    printed once on an earlier line. ``None`` without a trace, and under a
    program that has no scope map to ask for (one older than the map)."""
    if "scope_table" in ctx:
        return ctx["scope_table"]
    trace, steps = ctx.get("trace"), ctx.get("trace_steps")
    if trace is None or not steps:
        return None
    try:
        from ml_recipe_tpu.metrics.trace import scope_map
    except ImportError:
        return None
    t0 = time.perf_counter()
    programs = {module_name(m[0]) for mods in trace.device_modules.values()
                for m in mods}
    maps = {name: scope_map(name) for name in sorted(programs)}
    seconds = time.perf_counter() - t0
    found = reduce(trace, steps, lambda name: maps.get(name, {}))
    if found is not None:
        found["programs"] = {name: len(m) for name, m in maps.items()}
        found["scope_map_s"] = seconds
        note(scope_table=found)
    ctx["scope_table"] = found
    return found


def phase_ms(ctx: dict, phase: str) -> Optional[float]:
    found = table(ctx)
    return found["phases"][phase] if found else None


def block_ms(ctx: dict, block: str) -> Optional[float]:
    found = table(ctx)
    return found["blocks"].get(block, 0.0) if found else None


def unattributed_pct(ctx: dict) -> Optional[float]:
    found = table(ctx)
    return found["unattributed_pct"] if found else None
