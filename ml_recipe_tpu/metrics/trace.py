"""The span plane: one record of what the host did, three readers of it.

``span(name, cat=, args=)`` and ``complete(name, t0, t1)`` are the program's
one way to say "the host spent this interval on that". Every span goes three
ways:

- into ``jax.profiler.TraceAnnotation("mlrt:<cat>:<name>", **args)`` for its
  duration, so that under any profiler session (``XplaneWindow``'s, a
  benchmark's) the program's phases lie on the host lines of the same
  ``.xplane.pb`` as the device's operations, stamped by the profiler's own
  clock; without a session the annotation is inert;
- into a bounded process-wide record (``recent()``), installed tracer or
  not: ``(name, cat, t0, t1, thread, parent, args)`` with ``parent`` the
  enclosing span on that thread and ``args`` carrying ``step=<global_step>``
  for the spans of a step. The set-up gauges and the step clock of
  ``train/telemetry.py`` read it;
- to the installed :class:`TraceWriter`, if any (``--trace_spans``): Chrome
  trace-event JSON for Perfetto (https://ui.perfetto.dev), an operator's
  view. Spans cover the host side of both planes:

  - training: ``setup`` (``init_model``, ``init_datasets``,
    ``trainer_init``, ``preflight`` > ``preflight_attempt``, ``first_step``),
    ``compile`` (``trace`` / ``lower`` / ``backend``, from ``jax.monitoring``
    through ``utils/platform.py``) and, a step, ``train``: ``data_wait`` ->
    ``place`` -> ``dispatch`` -> ``consume`` (+ ``step``: dispatch to the
    step's boundary), ``after_epoch``, ``checkpoint_*``;
  - serving: ``admission`` -> ``queue`` -> ``flush`` -> ``device`` ->
    ``span_reduce`` -> ``respond``, keyed by request id in ``args``.

The module-level ``install``/``current``/``span`` trio mirrors the
watchdog's process-global pattern so deep call sites (engine batcher
thread, prefetch worker) need no handle threading. A span costs two clock
reads, one annotation and one ``deque.append``.

The record's and the writer's timestamps are ``time.perf_counter()``
readings (Chrome trace ``ts`` values are relative microseconds, so a
monotonic interval clock is the correct source, and the wall clock is not);
the profiler stamps its copy itself.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import os
import re
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, NamedTuple, Optional

from .artifacts import atomic_write_json, wall_now

logger = logging.getLogger(__name__)

# bound memory on multi-day runs: the newest events win (the tail of a run
# is what an operator debugging it actually loads)
_MAX_EVENTS = 200_000


class TraceWriter:
    """Thread-safe Chrome trace-event collector.

    ``complete(name, t0, t1)`` records a span from explicit
    ``perf_counter`` readings (for call sites that timed the interval
    themselves, e.g. queue wait reconstructed from an enqueue stamp);
    ``span(name)`` is the context-manager spelling. ``tid`` defaults to the
    calling thread so Perfetto lays concurrent planes out on separate
    tracks.
    """

    def __init__(self, path: str, *, process_name: str = "ml_recipe_tpu"):
        self.path = os.fspath(path)
        self.origin = time.perf_counter()
        # wall-clock anchor of the perf_counter origin: scripts/
        # merge_traces.py aligns per-host trace files onto one timeline
        # with it (an EVENT stamp, so the wall clock is the right source)
        self.origin_unix = wall_now()
        self._events: List[Dict[str, Any]] = []
        self._dropped = 0
        self._lock = threading.Lock()
        self._pid = os.getpid()
        self._meta = process_name

    # -- clock -----------------------------------------------------------------

    def now(self) -> float:
        """Current ``perf_counter`` reading (callers stamp intervals with
        this so explicit ``complete`` calls share the writer's clock)."""
        return time.perf_counter()

    def _us(self, t: float) -> float:
        return (t - self.origin) * 1e6

    # -- event emission --------------------------------------------------------

    def _append(self, event: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._events) >= _MAX_EVENTS:
                # drop the OLDEST half once, keeping the recent window
                self._dropped += len(self._events) // 2
                self._events = self._events[len(self._events) // 2:]
            self._events.append(event)

    def complete(
        self,
        name: str,
        t0: float,
        t1: float,
        *,
        cat: str = "host",
        tid: Optional[int] = None,
        args: Optional[Dict[str, Any]] = None,
    ) -> None:
        """One complete-duration event from two ``perf_counter`` readings."""
        event: Dict[str, Any] = {
            "name": name,
            "ph": "X",
            "ts": self._us(t0),
            "dur": max(0.0, (t1 - t0) * 1e6),
            "pid": self._pid,
            "tid": tid if tid is not None else threading.get_ident() % (1 << 31),
            "cat": cat,
        }
        if args:
            event["args"] = args
        self._append(event)

    def instant(self, name: str, *, cat: str = "host",
                args: Optional[Dict[str, Any]] = None) -> None:
        event: Dict[str, Any] = {
            "name": name,
            "ph": "i",
            "ts": self._us(time.perf_counter()),
            "pid": self._pid,
            "tid": threading.get_ident() % (1 << 31),
            "cat": cat,
            "s": "p",  # process-scoped instant
        }
        if args:
            event["args"] = args
        self._append(event)

    @contextlib.contextmanager
    def span(self, name: str, *, cat: str = "host",
             args: Optional[Dict[str, Any]] = None):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.complete(name, t0, time.perf_counter(), cat=cat, args=args)

    # -- serialization ---------------------------------------------------------

    def flush(self) -> str:
        """Write the collected events as Chrome trace JSON; returns the
        path. Atomic (tmp + rename) so a capture killed mid-write never
        leaves a half-JSON behind; safe to call repeatedly (checkpointing
        the trace as a long run progresses)."""
        with self._lock:
            events = list(self._events)
            dropped = self._dropped
        doc = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "producer": "ml_recipe_tpu.metrics.trace",
                "dropped_events": dropped,
                "process_name": self._meta,
                # wall anchor of ts==0 on this writer's clock, for the
                # cross-host alignment in scripts/merge_traces.py
                "origin_unix": self.origin_unix,
            },
        }
        return atomic_write_json(self.path, doc)

    def close(self) -> str:
        path = self.flush()
        logger.info(f"Trace spans written to {path} (load in Perfetto).")
        return path

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)


# -- process-global instance (deep call sites: engine, prefetch worker) --------

_active: Optional[TraceWriter] = None


def install(tracer: Optional[TraceWriter]) -> Optional[TraceWriter]:
    """Install (or clear, with None) the process-global tracer."""
    global _active
    _active = tracer
    return tracer


def current() -> Optional[TraceWriter]:
    return _active


# -- the always-on record --------------------------------------------------------

# spans kept a category, the newest win. A category has a record of its own,
# so that a week of step spans cannot push the set-up spans out
_RECORD_MAX = 32768


class SpanRecord(NamedTuple):
    name: str
    cat: str
    t0: float                   # time.perf_counter() readings
    t1: float
    thread: int
    parent: Optional[str]       # "<cat>:<name>" of the enclosing span
    args: Optional[Dict[str, Any]]

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


_records: Dict[str, deque] = {}
_records_lock = threading.Lock()
_open = threading.local()       # .stack: this thread's open spans, innermost last
_annotation_type = None


def _keep(record: SpanRecord) -> None:
    kept = _records.get(record.cat)
    if kept is None:
        with _records_lock:
            kept = _records.setdefault(record.cat, deque(maxlen=_RECORD_MAX))
    kept.append(record)


def recent(cat: Optional[str] = None) -> List[SpanRecord]:
    """The recorded spans of ``cat`` (of every category with None), by start."""
    with _records_lock:
        kept = (list(_records.values()) if cat is None
                else [_records.get(cat) or deque()])
    # (deque.copy() is atomic; iterating one that another thread appends to
    # is not)
    return sorted((r for records in kept for r in records.copy()),
                  key=lambda r: r.t0)


def clear_record() -> None:
    """Forget every recorded span (tests)."""
    with _records_lock:
        _records.clear()


def _enclosing() -> Optional[str]:
    """``<cat>:<name>`` of this thread's innermost open span."""
    stack = getattr(_open, "stack", None)
    return f"{stack[-1].cat}:{stack[-1].name}" if stack else None


def _annotation(cat: str, name: str, args: Optional[Dict[str, Any]]):
    global _annotation_type
    if _annotation_type is None:    # jax only when the first span opens
        from jax.profiler import TraceAnnotation

        _annotation_type = TraceAnnotation
    if not args:
        return _annotation_type(f"mlrt:{cat}:{name}")
    return _annotation_type(f"mlrt:{cat}:{name}", **{
        k: v for k, v in args.items() if isinstance(v, (int, float, str))})


class span:
    """``with span("dispatch", cat="train", args={"step": n}) as s:`` — the
    interval goes to the profiler, the record and the installed tracer;
    ``s.t0`` / ``s.t1`` are its ``perf_counter`` readings afterwards, and
    ``s.args`` may still be filled in inside the block."""

    __slots__ = ("name", "cat", "args", "t0", "t1", "_parent", "_annotation")

    def __init__(self, name: str, *, cat: str = "host",
                 args: Optional[Dict[str, Any]] = None):
        self.name, self.cat, self.args = name, cat, args
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "span":
        self._parent = _enclosing()
        _open.__dict__.setdefault("stack", []).append(self)
        self._annotation = _annotation(self.cat, self.name, self.args)
        self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc_info) -> None:
        self.t1 = time.perf_counter()
        self._annotation.__exit__(*exc_info)
        stack = _open.stack
        while stack and stack.pop() is not self:
            pass    # a span left open below (a generator dropped mid-span)
        thread = threading.get_ident()
        _keep(SpanRecord(self.name, self.cat, self.t0, self.t1, thread,
                         self._parent, self.args))
        tracer = _active
        if tracer is not None:
            tracer.complete(self.name, self.t0, self.t1, cat=self.cat,
                            tid=thread % (1 << 31), args=self.args)


def complete(name: str, t0: float, t1: float, *, cat: str = "host",
             tid: Optional[int] = None,
             args: Optional[Dict[str, Any]] = None) -> None:
    """An interval that cannot nest (timed by its caller, or ending on another
    step than it began): the record and the installed tracer get it, the
    profiler does not."""
    _keep(SpanRecord(name, cat, t0, t1,
                     tid if tid is not None else threading.get_ident(),
                     _enclosing(), args))
    tracer = _active
    if tracer is not None:
        tracer.complete(name, t0, t1, cat=cat, tid=tid, args=args)


def instant(name: str, *, cat: str = "host",
            args: Optional[Dict[str, Any]] = None) -> None:
    tracer = _active
    if tracer is not None:
        tracer.instant(name, cat=cat, args=args)


# -- wall-time profiling decorator (the legacy utils.profiler surface) ---------


def time_profiler(fun):
    """Log a function call's wall time AND emit it as a trace span.

    This is the reference-parity ``time_profiler`` decorator
    (``utils.profiler`` keeps the public name as a thin shim), migrated
    onto the span plane: ``_train``/``_test`` and every other decorated
    unit are ``cat="profile"`` intervals of the record and, with a tracer
    installed, of the same Perfetto timeline as the step/checkpoint spans;
    the historical log line is emitted either way.
    """

    @functools.wraps(fun)
    def _profiled_func(*args, **kwargs):
        start = time.perf_counter()
        try:
            return fun(*args, **kwargs)
        finally:
            end = time.perf_counter()
            complete(fun.__name__, start, end, cat="profile")
            logger.info(
                f"Execution of {fun.__name__} took {end - start:.3f} sec."
            )

    return _profiled_func


# -- scope map (device time by the program's own scopes) -----------------------
#
# A device trace of this installation names an ``XLA Ops`` event by the
# instruction's HLO text without its metadata, so the trace alone cannot say
# which part of the program a ``%fusion.12`` is. The optimized HLO text can:
# every instruction carries ``metadata={op_name="jit(train_step)/optimizer/
# add"}``, the ``jax.named_scope`` path of the operation it came from (for a
# fusion, of its root instruction). ``parse_scope_map`` reads that text into
# instruction name -> op_name; the table below holds, per program, a thunk
# that yields the text when somebody asks, so that a run nobody traces never
# lowers, extracts or parses anything. Two things the names depend on:
# ``utils/platform.configure_compile_cache`` keeps whole scope paths in
# ``op_name`` (its comment says how), and metadata is no part of the compile
# cache's key, so an executable read from the cache carries the names of the
# process that compiled it.

_COMPUTATION = re.compile(r"^(?:ENTRY )?(%?[\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?(%?[\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_FUSED = re.compile(r"\bfusion\(.*\bcalls=(%?[\w.\-]+)")


def parse_scope_map(hlo_text: str) -> Dict[str, str]:
    """Instruction name (``%fusion.12``, as it stands before ``" = "``) ->
    ``op_name``, over every computation of an optimized HLO module except the
    insides of fused computations: those instructions never run as events of
    their own. Instruction names are unique in a module."""
    computations: Dict[str, Dict[str, str]] = {}
    fused = set()
    current: Optional[Dict[str, str]] = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = computations.setdefault(head.group(1), {})
            continue
        instruction = _INSTRUCTION.match(line)
        if instruction is None or current is None:
            continue
        called = _FUSED.search(line)
        if called:
            fused.add(called.group(1))
        op_name = _OP_NAME.search(line)
        if op_name:
            current[instruction.group(1)] = op_name.group(1)
    out: Dict[str, str] = {}
    for name, instructions in computations.items():
        if name not in fused:
            out.update(instructions)
    return out


_programs: Dict[str, tuple] = {}      # name -> (text_source, on_map)
_scope_maps: Dict[str, Dict[str, str]] = {}
_scope_lock = threading.Lock()


def register_program(name: str,
                     text_source: Callable[[], Optional[str]],
                     on_map: Optional[Callable[[], None]] = None) -> None:
    """Remember how to get the optimized HLO text of the program the trace's
    ``XLA Modules`` line calls ``name`` (``jit_train_step``, without the id).
    ``text_source`` is called at most once, by the first ``scope_map(name)``,
    and ``on_map`` once after it, when the map can be read; registering again
    (a rebuilt step) replaces the entry and its map."""
    with _scope_lock:
        _programs[name] = (text_source, on_map)
        _scope_maps.pop(name, None)


def registered_programs() -> List[str]:
    with _scope_lock:
        return sorted(_programs)


def scope_map(name: str) -> Dict[str, str]:
    """Instruction name -> op_name of program ``name``; ``{}`` when nothing
    is registered under it or its text cannot be had."""
    with _scope_lock:
        found = _scope_maps.get(name)
        entry = _programs.get(name)
    if found is not None or entry is None:
        return found or {}
    text_source, on_map = entry
    try:
        text = text_source()
    except Exception:  # noqa: BLE001 - a capture's extra, never its failure
        logger.warning(f"No HLO text for program {name}: its device events "
                       f"stay unattributed.", exc_info=True)
        text = None
    parsed = parse_scope_map(text) if text else {}
    with _scope_lock:
        kept = _programs.get(name) is entry
        if kept:
            _scope_maps[name] = parsed
    if kept and on_map is not None:
        on_map()
    return parsed


# the two-width causal family (``ops/flash_causal.py``) names its backward by
# how it ran: one ``flash_causal_bwd`` a call where the row's dq stays in
# VMEM, else ``flash_causal_bwd_dq`` + ``flash_causal_bwd_dkv``
_CAUSAL_BWD = re.compile(r"^%flash_causal_bwd(_dq)?(?:\.\d+)?$")


def causal_backward_calls(name: str) -> Dict[str, int]:
    """``{"fused": n, "split": m}``: the causal attention backward calls of
    program ``name`` that are the one fused kernel, and those that are the
    dq / dk-dv pair. Read from the instruction names of a program compiled for
    the chip (on the CPU the kernels are interpreted and leave no call)."""
    counts = {"fused": 0, "split": 0}
    for instruction in scope_map(name):
        call = _CAUSAL_BWD.match(instruction)
        if call:
            counts["split" if call.group(1) else "fused"] += 1
    return counts


# the expert layers' grouped matmuls (``ops/grouped_matmul.py``) by the form
# that ran: the repo's Mosaic kernels, or the grouped-matmul kernel the TPU
# compiler makes of ``jax.lax.ragged_dot`` (off the TPU neither leaves a call)
_GROUPED_MATMUL = re.compile(
    r"^%(?:(grouped_matmul_(?:fwd|drows|dweights))|ragged-dot-none)"
    r"(?:\.\d+)?$")


def grouped_matmul_calls(name: str) -> Dict[str, int]:
    """``{"kernel": n, "ragged_dot": m}``: the grouped matmul calls of
    program ``name`` in each form, read as ``causal_backward_calls`` reads
    its kernels (a loop's body counts once: mellum2's step program holds 56
    calls, 14 a layer: the first chunk's 6 as kernels, a granule's 8 as
    ``ragged_dot``)."""
    counts = {"kernel": 0, "ragged_dot": 0}
    for instruction in scope_map(name):
        call = _GROUPED_MATMUL.match(instruction)
        if call:
            counts["kernel" if call.group(1) else "ragged_dot"] += 1
    return counts


# the expert layers' token-side walks as kernels (``ops/token_rows.py``): a
# ``combine``'s forward or a ``dispatch``'s backward is a sum, a ``combine``'s
# weight gradient a dot; the XLA walks leave no instruction of their own
_TOKEN_ROWS = re.compile(r"^%token_rows_(sum|dot)(?:\.\d+)?$")


def token_rows_calls(name: str) -> Dict[str, int]:
    """``{"sum": n, "dot": m}``: the token-side walk kernels of program
    ``name``, read as ``grouped_matmul_calls`` reads its kernels (three an
    expert layer's first chunk: 12 in mellum2's step program; none where the
    walks run in XLA)."""
    counts = {"sum": 0, "dot": 0}
    for instruction in scope_map(name):
        call = _TOKEN_ROWS.match(instruction)
        if call:
            counts[call.group(1)] += 1
    return counts


# -- xplane window (the trainer's staged on-chip capture) ----------------------


class XplaneWindow:
    """``jax.profiler`` capture over a fixed window of steady-state steps.

    Replaces the trainer's hand-rolled start/stop flag pair: the window
    opens before dispatching step ``start`` and closes (after a
    ``block_until_ready`` sync) once step ``start + steps - 1`` has been
    dispatched, so the xplane dump covers exactly ``steps`` full steps.
    When a span tracer is installed the same boundaries are marked with
    instant events, so host spans and the device capture line up on the
    same step window in Perfetto.
    """

    def __init__(self, log_dir, *, start: int = 2, steps: int = 3):
        self.log_dir = str(log_dir)
        self.start = int(start)
        self.steps = max(1, int(steps))
        self.started = False
        self.stopped = False

    @property
    def done(self) -> bool:
        return self.stopped

    def on_step_start(self, step_i: int) -> None:
        if not self.started and step_i == self.start:
            import jax

            jax.profiler.start_trace(self.log_dir)
            self.started = True
            instant("xplane_capture_start", cat="train",
                    args={"step": step_i, "dir": self.log_dir})

    def on_step_end(self, step_i: int, sync_tree) -> bool:
        """Close the window once the last captured step was dispatched;
        returns True when it closed here."""
        if not self.started or self.stopped:
            return False
        if step_i < self.start + self.steps - 1:
            return False
        self._stop(sync_tree)
        logger.info(
            f"Device trace (steps {self.start}-{self.start + self.steps - 1}) "
            f"written to {self.log_dir}."
        )
        return True

    def abort(self, sync_tree) -> None:
        """Close a still-open window (epoch ended mid-capture)."""
        if self.started and not self.stopped:
            self._stop(sync_tree)
            logger.info(f"Device trace written to {self.log_dir}.")

    def _stop(self, sync_tree) -> None:
        import jax

        jax.block_until_ready(sync_tree)
        jax.profiler.stop_trace()
        self.stopped = True
        instant("xplane_capture_stop", cat="train", args={"dir": self.log_dir})
        # the join key for the dump's ``XLA Ops`` events: which scope of the
        # program each instruction belongs to (asked for only here, after
        # the capture: the window itself pays nothing for it)
        maps = {name: scope_map(name) for name in registered_programs()}
        if any(maps.values()):
            atomic_write_json(os.path.join(self.log_dir, "scope_map.json"), maps)
