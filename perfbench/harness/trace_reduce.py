"""From a profiler trace (``.xplane.pb``) to numbers: device busy and idle,
per-kernel sums, collective time and its exposed part, idle gaps named by
what the host was doing, the heaviest device operations.

Reads the file with ``jax.profiler.ProfileData`` alone. The arithmetic works
on plain lists of ``(name, start_ns, end_ns)`` so that it can be checked on
hand-made events as well as on the recorded trace in ``fixtures/``.

What a TPU trace of this installation looks like (read by hand, PR 22):
one plane ``/device:TPU:<n>`` per chip. Its line ``XLA Ops`` holds one event
per executed HLO operation, named by the operation's whole HLO text
(``%fusion.12 = bf16[...] fusion(...)``: kept here up to the `` = ``), nested
where an operation contains others (a ``%while`` spans its body), and without
any category or FLOP statistic. A Pallas kernel is a custom call whose text
says ``custom_call_target="tpu_custom_call"``; it carries no kernel name and
is not always called ``%tpu_custom_call.<n>``, so it is renamed to that here. ``XLA Modules`` holds one event per executed
program (``jit_train_step(<id>)``); ``Async XLA Ops`` holds copies and slices
in flight and is not compute. Host threads are lines of the plane
``/host:CPU``: the benchmark's own ``TraceAnnotation``s (``bench:*``) are on
the line ``python``, and ``PJRT_LoadedExecutable_Execute`` marks a dispatch.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]          # name, start_ns, end_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")
HOST_KEEP = ("bench:", "PJRT_LoadedExecutable_Execute")
# what the host was doing, by the annotation that covers most of a gap
GAP_NAMES = (
    ("bench:loader_next", "waiting for data"),
    ("bench:request_wait", "waiting for requests"),
    ("PJRT_LoadedExecutable_Execute", "dispatch"),
)
WINDOW_OPEN, WINDOW_CLOSE = "bench:window_open", "bench:window_close"


@dataclasses.dataclass
class Trace:
    device_ops: Dict[int, List[Event]]        # chip -> events of its op line
    device_modules: Dict[int, List[Event]]
    host: List[Event]                         # kept host events, any thread
    # "markers": between the benchmark's own marks (a server's stretch);
    # "modules": from the start of the first executed program to the end of
    # the last (whole steps: the filling and draining of an epoch's pipeline
    # at the edges of a traced stretch are not the steady state)
    window_from: str = "markers"

    def window(self) -> Tuple[float, float]:
        """[open, close] in ns."""
        opens = [e[1] for e in self.host if e[0] == WINDOW_OPEN]
        closes = [e[2] for e in self.host if e[0] == WINDOW_CLOSE]
        mods = [e for m in self.device_modules.values() for e in m]
        if self.window_from == "modules" and mods:
            return min(e[1] for e in mods), max(e[2] for e in mods)
        if opens and closes:
            return min(opens), max(closes)
        starts = [e[1] for ops in self.device_ops.values() for e in ops]
        ends = [e[2] for ops in self.device_ops.values() for e in ops]
        if not starts:
            return 0.0, 0.0
        return min(starts), max(ends)


MOSAIC = 'custom_call_target="tpu_custom_call"'


def short(name: str) -> str:
    """``%fusion.12`` of ``%fusion.12 = bf16[...] fusion(...)``; a Mosaic
    kernel is ``%tpu_custom_call.<n>`` whatever XLA named it."""
    head = name.split(" = ", 1)[0]
    if MOSAIC in name and not head.startswith("%tpu_custom_call"):
        return "%tpu_custom_call.0"
    return head


def kind(name: str) -> str:
    """``%fusion`` of ``%fusion.12``."""
    return re.sub(r"[.\d]+$", "", name)


def load(path: str, window_from: str = "markers") -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    device_ops: Dict[int, List[Event]] = {}
    device_modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops = device_ops.setdefault(chip, [])
                    for ev in line.events:
                        ops.append((short(ev.name), ev.start_ns,
                                    ev.start_ns + ev.duration_ns))
                elif line.name == MODULE_LINE:
                    mods = device_modules.setdefault(chip, [])
                    for ev in line.events:
                        mods.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_KEEP):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    return Trace(device_ops, device_modules, host, window_from)


# -- interval arithmetic ---------------------------------------------------------

def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Tuple[float, float]]) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a: List[Tuple[float, float]],
             b: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The parts of ``a`` (a union) not covered by ``b`` (a union)."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float):
    return subtract([(lo, hi)], busy)


# -- the reductions ----------------------------------------------------------------

def busy_idle(trace: Trace) -> Optional[dict]:
    """Seconds an operation ran on the device (union of the op intervals
    inside the window, averaged over the chips that ran any) and the window's
    length. ``None`` when no operation ran on a device."""
    lo, hi = trace.window()
    per_chip = []
    for ops in trace.device_ops.values():
        b = total(union((s, e) for _, s, e in clip(ops, lo, hi)))
        if b > 0:
            per_chip.append(b)
    if not per_chip or hi <= lo:
        return None
    busy_s = sum(per_chip) / len(per_chip) / 1e9
    window_s = (hi - lo) / 1e9
    return {"busy_s": busy_s, "window_s": window_s, "chips": len(per_chip),
            "idle_pct": 100.0 * (1.0 - busy_s / window_s)}


def kernel_seconds(trace: Trace, pattern: str) -> float:
    """Device seconds of the operations whose name matches ``pattern``,
    inside the window, averaged over the chips."""
    rx = re.compile(pattern)
    lo, hi = trace.window()
    sums = [sum(e - s for n, s, e in clip(ops, lo, hi) if rx.search(n))
            for ops in trace.device_ops.values()]
    return (sum(sums) / len(sums) / 1e9) if sums else 0.0


def leaves(ops: List[Event]) -> List[Event]:
    """The operations that contain no other: a ``%while`` that spans its
    body is bookkeeping, not work running beside what it contains."""
    out: List[Event] = []
    ordered = sorted(ops, key=lambda ev: (ev[1], -ev[2]))
    for i, (n, s, e) in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is None or nxt[1] >= e:
            out.append((n, s, e))
    return out


def collectives(trace: Trace) -> Optional[dict]:
    """Per chip, averaged: seconds in collective operations, and the part of
    them during which no other operation ran on that chip (on this
    installation the operations of a chip's ``XLA Ops`` line run one after
    another, so a collective that shows there is exposed for all its time;
    one the compiler made asynchronous shows only its short start and done)."""
    lo, hi = trace.window()
    totals, exposed = [], []
    for ops in trace.device_ops.values():
        ops = leaves(clip(ops, lo, hi))
        coll = union((s, e) for n, s, e in ops if COLLECTIVE.search(n))
        if not coll:
            continue
        compute = union((s, e) for n, s, e in ops if not COLLECTIVE.search(n))
        totals.append(total(coll))
        exposed.append(total(subtract(coll, compute)))
    if not totals:
        return None
    return {"collective_s": sum(totals) / len(totals) / 1e9,
            "exposed_s": sum(exposed) / len(exposed) / 1e9,
            "calls": sum(1 for ops in trace.device_ops.values()
                         for n, s, e in clip(ops, lo, hi)
                         if COLLECTIVE.search(n)) / max(len(totals), 1)}


def _gap_name(gap: Tuple[float, float], host: List[Event]) -> str:
    best, best_cover = "other", 0.0
    for prefix, label in GAP_NAMES:
        cover = total(union(
            (max(s, gap[0]), min(e, gap[1])) for n, s, e in host
            if n.startswith(prefix) and e > gap[0] and s < gap[1]))
        if cover > best_cover and cover >= 0.5 * (gap[1] - gap[0]):
            best, best_cover = label, cover
    return best


def self_seconds(ops: List[Event]) -> Dict[str, float]:
    """Device nanoseconds by kind of operation, each operation counted for
    the time no operation nested in it ran (a ``%while`` for its own
    bookkeeping, not for its body)."""
    out: Dict[str, float] = {}
    stack: List[Tuple[str, float]] = []
    for n, s, e in sorted(ops, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]] -= e - s
        k = kind(n)
        out[k] = out.get(k, 0.0) + (e - s)
        stack.append((k, e))
    return out


def breakdown(trace: Trace, top: int = 10) -> dict:
    """``device_ops``: the kinds of operation that took most device time
    (self time summed over the window, first chip). ``idle_gaps``: idle
    seconds by what the host was doing, then the longest single gaps."""
    lo, hi = trace.window()
    if not trace.device_ops or hi <= lo:
        return {"device_ops": [], "idle_gaps": []}
    chip = min(trace.device_ops)
    ops = clip(trace.device_ops[chip], lo, hi)
    heavy = sorted(self_seconds(ops).items(), key=lambda kv: -kv[1])[:top]
    idle = gaps(union((s, e) for _, s, e in ops), lo, hi)
    host = clip(trace.host, lo, hi)
    # name only the gaps that matter: the longest 200 hold nearly all idle time
    idle = sorted(idle, key=lambda g: g[0] - g[1])
    named = [(_gap_name(g, host), g) for g in idle[:200]]
    by_cause: Dict[str, float] = {}
    for name, g in named:
        by_cause[name] = by_cause.get(name, 0.0) + (g[1] - g[0])
    rest = total(idle[200:])
    if rest:
        by_cause["short gaps"] = rest
    out = [[name, secs / 1e9]
           for name, secs in sorted(by_cause.items(), key=lambda kv: -kv[1])]
    for name, g in named[: max(0, top - len(out))]:
        out.append([f"longest: {name}", (g[1] - g[0]) / 1e9])
    return {"device_ops": [[n, secs / 1e9] for n, secs in heavy],
            "idle_gaps": out[:top]}
