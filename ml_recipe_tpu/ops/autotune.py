"""Compile-probe kernel geometry autotuner with an on-disk tuning cache.

The attention kernels (``flash_attention.py`` / ``flash_streaming.py``) used
to GATE their block geometries with analytic byte-counting against a VMEM
budget. The arithmetic is a model, not a measurement, and the blocked /
streaming regimes had no backstop when it undercounted: round 5 left
seq-1024 failing to compile at HEAD with a scoped-VMEM OOM (18.31 MB vs the
16 MB limit) that the arithmetic had approved. The only regime that never
regressed was the fused backward — the one with a compile probe
(``_fused_bwd_hc``). This module generalizes that probe into the selection
mechanism for every regime:

- the caller enumerates candidate geometries and supplies a *modeled step
  cost* (fewer programs / less HBM re-streaming = cheaper);
- candidates are ranked by that cost and validated IN RANK ORDER with a real
  ``jit(...).lower(...).compile()`` probe of the same ``pallas_call`` the
  execution path builds; when the probes hand back their compiled objects,
  legal candidates are re-ranked by MEASUREMENT — a few wall-clock
  executions of each compiled probe when the programs run here (median
  ``probe_ms`` persisted per candidate, fastest wins), else XLA's own
  ``cost_analysis()`` estimates (measured properties of the lowered
  programs — fusions and layout copies included) — with the analytic prior
  deciding only walk order and ties; bool-style probes keep
  first-legal-wins;
- off-TPU (CPU / interpret mode, where Mosaic cannot OOM VMEM and tier-1
  runs) selection falls back to the caller's analytic pick — the exact
  arithmetic the old gates used, so CPU behavior is unchanged;
- winners (including the "no legal candidate" verdict) persist in a JSON
  cache under ``artifacts/tuning/<device_kind>.json`` (``MLRT_AUTOTUNE_CACHE``
  overrides the directory), so probe compiles are paid once per geometry per
  chip generation, not once per process.

TorchTitan (PAPERS.md) treats memory-budget-aware configuration as a
first-class planner rather than per-kernel arithmetic; the pjit/TPUv4
scaling work leans on measured compile-time feedback over static models.
This is the same stance: the arithmetic survives only as a ranking prior
and a no-probe fallback, never as the final gate on hardware.

The HBM-level counterpart (whole-step ``memory_analysis`` pre-flight) lives
in ``train/trainer.py`` — VMEM geometry is batch-independent, HBM planning
is not, and the two planners are deliberately separate.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import re
import threading
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence

logger = logging.getLogger(__name__)

_CACHE_VERSION = 1
# env override for the cache directory (tests point this at a tmp dir so
# tier-1 never writes into the repo's artifacts/)
ENV_CACHE_DIR = "MLRT_AUTOTUNE_CACHE"
# "0"/"false"/"off" disables autotuning process-wide (pure analytic gating)
ENV_ENABLED = "MLRT_AUTOTUNE"


def default_cache_dir() -> Path:
    env = os.environ.get(ENV_CACHE_DIR)
    if env:
        return Path(env)
    return Path(__file__).resolve().parents[2] / "artifacts" / "tuning"


def _env_enabled() -> bool:
    return os.environ.get(ENV_ENABLED, "1").strip().lower() not in (
        "0", "false", "off", "no",
    )


def _toolchain() -> str:
    """Cache invalidation key: what compiles is a property of the jax/jaxlib
    pair, not just the chip — a probe verdict must not outlive the toolchain
    that issued it (Mosaic wordings and VMEM behavior both drift)."""
    try:
        import jax
        import jaxlib

        jl = getattr(jaxlib, "__version__", None) or getattr(
            getattr(jaxlib, "version", None), "__version__", "?"
        )
        return f"jax-{jax.__version__}+jaxlib-{jl}"
    except Exception:  # noqa: BLE001 - no version = never match = re-probe
        return "unknown"


def _device_kind() -> str:
    """Cache partition key: the accelerator generation (geometry verdicts
    from one chip must never be replayed on another)."""
    import jax

    try:
        backend = jax.default_backend()
        if backend == "tpu":
            return jax.devices()[0].device_kind
        return backend
    except Exception:  # noqa: BLE001 - no backend = no persistent verdicts
        return "unknown"


def _sanitize(kind: str) -> str:
    return re.sub(r"[^A-Za-z0-9._-]+", "_", kind.strip()) or "unknown"


# Nominal chip ceilings for the roofline-lite ranking signal below. These
# are a RANKING PRIOR, not measurements: only the relative ordering of
# candidates matters, and max(flops/F, bytes/B) orders compute-bound and
# bandwidth-bound candidates sanely for any plausible F/B pair. The pair is
# the TPU v5e's published peaks (197 bf16 TFLOP/s, 819 GB/s) and has been
# looked at on v5e only.
_RANK_PEAK_FLOPS = 197e12
_RANK_PEAK_BYTES = 819e9


def _cost_estimate(compiled) -> Optional[dict]:
    """Compiled-cost estimate of one probe result, or ``None`` when the
    toolchain exposes none (ranking then falls back to the analytic prior).

    ``compiled.cost_analysis()`` is XLA's own post-optimization estimate —
    a *measured* property of the lowered program (fusion decisions, layout
    copies, re-streaming included), unlike the caller's analytic prior
    which models the kernel it HOPED to get. ``est_seconds`` is the
    roofline-lite scalar the ranking minimizes; the raw flops/bytes persist
    alongside it in the tuning cache for provenance.
    """
    fn = getattr(compiled, "cost_analysis", None)
    if fn is None:
        return None
    try:
        ca = fn()
    except Exception:  # noqa: BLE001 - estimate is best-effort by contract
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    try:
        flops = float(ca.get("flops") or 0.0)
        byts = float(ca.get("bytes accessed") or 0.0)
    except (TypeError, ValueError):
        return None
    if flops <= 0.0 and byts <= 0.0:
        return None
    return {
        "flops": flops,
        "bytes_accessed": byts,
        "est_seconds": max(flops / _RANK_PEAK_FLOPS,
                           byts / _RANK_PEAK_BYTES),
    }


def program_cost_estimate(compiled) -> Optional[dict]:
    """Public face of ``_cost_estimate`` for whole compiled PROGRAMS (the
    serving engine estimates each bucket program at warmup and persists the
    verdict via ``record_cost``)."""
    return _cost_estimate(compiled)


def _geom_json_key(geometry) -> str:
    """Stable JSON-object key for one candidate geometry."""
    if isinstance(geometry, (list, tuple)):
        return "x".join(str(g) for g in geometry)
    return str(geometry)


class _CombinedCompiled:
    """Several compiled programs presented as ONE rankable probe result:
    ``cost_analysis()`` sums their flops / bytes-accessed (a candidate that
    must compile forward AND backward is as expensive as both)."""

    def __init__(self, compiled: Sequence[Any]):
        self._compiled = list(compiled)

    def cost_analysis(self):
        total = {"flops": 0.0, "bytes accessed": 0.0}
        for compiled in self._compiled:
            est = _cost_estimate(compiled)
            if est is None:
                # one leg without an estimate poisons the sum — report
                # nothing rather than a half-truth (ranking falls back to
                # the analytic prior)
                return None
            total["flops"] += est["flops"]
            total["bytes accessed"] += est["bytes_accessed"]
        return total


def combine_for_ranking(*compiled):
    """Wrap the compiled legs of a multi-program candidate (e.g. streaming
    fwd + dkv) as one probe result the ranking pass can estimate. Falsy legs
    make the whole candidate infeasible (returns False)."""
    if not compiled or any(not c for c in compiled):
        return False
    return _CombinedCompiled(compiled)


# Timed executions per compiled probe for the wall-clock ranking signal
# (one extra warmup execution absorbs first-dispatch overhead). Three keeps
# the added probe cost at microbenchmark scale while the median rejects a
# one-off scheduling hiccup.
_PROBE_TIME_REPEATS = 3


def _time_compiled(compiled, *, repeats: int = _PROBE_TIME_REPEATS):
    """Median wall-clock execution time (ms) of one compiled probe, or
    ``None`` when the program cannot be executed here (no ``args_info``,
    not callable, or execution fails — timing is best-effort by contract).

    Inputs are ZERO-FILLED from the compiled program's own argument avals:
    the probe path never has the caller's real tensors, and attention-shaped
    kernels' run time is data-independent. Multi-leg candidates
    (:class:`_CombinedCompiled`) time as the sum of their legs — a
    candidate that must run forward AND backward costs both."""
    if isinstance(compiled, _CombinedCompiled):
        total = 0.0
        for leg in compiled._compiled:
            ms = _time_compiled(leg, repeats=repeats)
            if ms is None:
                return None
            total += ms
        return total
    info = getattr(compiled, "args_info", None)
    if info is None or not callable(compiled):
        return None
    import time

    try:
        import jax
        import jax.numpy as jnp

        def zero(a):
            shape = getattr(a, "shape", None)
            dtype = getattr(a, "dtype", None)
            if shape is None or dtype is None:
                aval = getattr(a, "aval", None)
                shape, dtype = aval.shape, aval.dtype
            return jnp.zeros(shape, dtype)

        zeroed = jax.tree_util.tree_map(zero, info)
        if (isinstance(zeroed, tuple) and len(zeroed) == 2
                and isinstance(zeroed[1], dict)):
            args, kwargs = zeroed
        else:
            args, kwargs = tuple(zeroed), {}
        jax.block_until_ready(compiled(*args, **kwargs))  # warmup dispatch
        samples = []
        for _ in range(max(1, int(repeats))):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(*args, **kwargs))
            samples.append((time.perf_counter() - t0) * 1e3)
        samples.sort()
        return samples[len(samples) // 2]
    except Exception as e:  # noqa: BLE001 - timing is a ranking extra only
        logger.debug("autotune: probe timing failed (%s: %s)",
                     type(e).__name__, e)
        return None


@dataclasses.dataclass
class Decision:
    """One selection made this session (bench provenance reporting)."""

    regime: str
    key: str
    geometry: Any
    outcome: str  # 'hit' | 'miss' | 'disabled'
    source: str   # 'probe' | 'analytic' | 'cache' provenance of the geometry


class GeometryAutotuner:
    """Process-wide geometry selector: rank -> probe -> persist.

    ``probe_count`` counts real compile probes issued (tests assert it stays
    zero on cache hits); ``hits``/``misses`` count key lookups.
    """

    def __init__(self, cache_dir: Optional[Path] = None,
                 enabled: Optional[bool] = None):
        self.enabled = _env_enabled() if enabled is None else enabled
        self._cache_dir = Path(cache_dir) if cache_dir else None
        self.probe_count = 0
        self.hits = 0
        self.misses = 0
        self._entries: Dict[str, Dict[str, dict]] = {}  # kind -> key -> entry
        # "no legal candidate" verdicts live ONLY in-process: a transient
        # probe-environment failure (host OOM during a probe compile is
        # classified as candidate-infeasible) must not poison the disk cache
        # into permanently routing a shape off-kernel — the next process
        # re-probes instead
        self._transient: Dict[str, Dict[str, dict]] = {}
        self._loaded: set = set()
        self._session: List[Decision] = []
        self._lock = threading.RLock()

    # -- configuration -------------------------------------------------------

    @property
    def cache_dir(self) -> Path:
        # resolved lazily so an env override set after import still applies
        return self._cache_dir if self._cache_dir else default_cache_dir()

    def set_cache_dir(self, cache_dir) -> None:
        with self._lock:
            self._cache_dir = Path(cache_dir) if cache_dir else None
            self._entries.clear()
            self._transient.clear()
            self._loaded.clear()

    # -- key / persistence ----------------------------------------------------

    @staticmethod
    def make_key(regime: str, *, batch: int, L: int, H: int, D: int,
                 in_dtype, out_dtype, dropout: bool, extra: str = "") -> str:
        """Stable cache key for one geometry decision.

        The batch slot carries the callers' PROBE batch (the attention
        kernels' ``_PROBE_BATCH``), not the run's: from two grid steps on,
        scoped-VMEM feasibility is batch-independent, so one verdict covers
        every batch size — HBM-level planning, which IS batch-dependent, happens in the
        trainer's pre-flight, not here.
        """
        key = (f"{regime}|B{batch}|L{L}|H{H}|D{D}|{in_dtype}|{out_dtype}"
               f"|drop{int(bool(dropout))}")
        if extra:
            key += f"|{extra}"
        return key

    def _cache_file(self, kind: str) -> Path:
        return self.cache_dir / f"{_sanitize(kind)}.json"

    @staticmethod
    def _valid_entry(value) -> bool:
        if not isinstance(value, dict) or "geometry" not in value:
            return False
        geom = value["geometry"]
        return geom is None or isinstance(geom, int) or (
            isinstance(geom, list) and all(isinstance(g, int) for g in geom)
        )

    def _load(self, kind: str) -> None:
        if kind in self._loaded:
            return
        self._loaded.add(kind)
        path = self._cache_file(kind)
        entries: Dict[str, dict] = {}
        try:
            raw = json.loads(path.read_text())
            if raw.get("version") != _CACHE_VERSION:
                logger.warning(
                    "autotune: tuning cache %s has version %r (want %d); "
                    "ignoring it", path, raw.get("version"), _CACHE_VERSION,
                )
            elif raw.get("toolchain") != _toolchain():
                # probe verdicts are jax/jaxlib-specific: a geometry that
                # compiled under the old toolchain may not lower under this
                # one (and vice versa) — drop the file and re-probe
                logger.warning(
                    "autotune: tuning cache %s was written by toolchain %r "
                    "(running %r); ignoring it and re-probing",
                    path, raw.get("toolchain"), _toolchain(),
                )
            else:
                for key, value in (raw.get("entries") or {}).items():
                    if self._valid_entry(value):
                        entries[key] = value
        except FileNotFoundError:
            pass
        except (OSError, ValueError, KeyError, AttributeError, TypeError) as e:
            # corrupt cache: degrade to re-probing, never to a crash — the
            # next persisted winner rewrites the file wholesale
            logger.warning(
                "autotune: corrupt tuning cache %s (%s: %s); starting fresh",
                path, type(e).__name__, e,
            )
        self._entries.setdefault(kind, {}).update(entries)

    def _persist(self, kind: str) -> None:
        path = self._cache_file(kind)
        try:
            path.parent.mkdir(parents=True, exist_ok=True)
            # merge-before-write: another process (multi-host pod, a bench
            # run sharing the cache dir) may have persisted keys since our
            # lazy _load — re-read and overlay our entries so last-writer-
            # wins loses at most a concurrently-written key, not the file
            disk: Dict[str, dict] = {}
            try:
                raw = json.loads(path.read_text())
                if (raw.get("version") == _CACHE_VERSION
                        and raw.get("toolchain") == _toolchain()):
                    for key, value in (raw.get("entries") or {}).items():
                        if self._valid_entry(value):
                            disk[key] = value
            except (OSError, ValueError, KeyError, AttributeError, TypeError):
                pass  # unreadable/foreign file: our entries replace it
            payload = {
                "version": _CACHE_VERSION,
                "device_kind": kind,
                "toolchain": _toolchain(),
                "entries": {**disk, **self._entries.get(kind, {})},
            }
            tmp = path.with_name(f"{path.name}.tmp.{os.getpid()}")
            tmp.write_text(json.dumps(payload, indent=1, sort_keys=True))
            os.replace(tmp, path)
        except OSError as e:
            logger.warning(
                "autotune: could not persist tuning cache %s: %s", path, e
            )

    # -- selection ------------------------------------------------------------

    def select(
        self,
        regime: str,
        *,
        L: int,
        H: int,
        D: int,
        in_dtype,
        out_dtype,
        dropout: bool,
        candidates: Sequence[Any],
        cost: Callable[[Any], Any],
        probe: Optional[Callable[[Any], bool]] = None,
        analytic: Optional[Callable[[], Any]] = None,
        interpret: bool = False,
        extra: str = "",
        batch: int = 1,
    ):
        """Winning geometry for this key, or ``None`` when no candidate is
        legal (the caller then declines the regime, exactly like the old
        analytic gates returning ``None``).

        On TPU (and not interpret) candidates are probed in ascending
        modeled-cost order; a probe returning the compiled object opts into
        timing-ranked selection (every candidate probed, winner = smallest
        ``cost_analysis()`` estimate — see ``_probe_ranked``), a probe
        returning bare ``True`` keeps first-legal-wins. Elsewhere the
        caller's ``analytic`` pick is returned unchanged (old-gate parity).
        Either way the verdict is cached in memory and on disk, so a second
        invocation at the same key performs zero probes. A probe that raises
        (an unclassified compile error the caller chose not to swallow)
        propagates and caches nothing.
        """
        import jax

        if not self.enabled:
            geometry = analytic() if analytic is not None else None
            self._record(regime, "", geometry, "disabled", "analytic")
            return geometry

        can_probe = (
            probe is not None
            and not interpret
            and jax.default_backend() == "tpu"
        )
        with self._lock:
            kind = _device_kind()
            key = self.make_key(
                regime, batch=batch, L=L, H=H, D=D, in_dtype=in_dtype,
                out_dtype=out_dtype, dropout=dropout, extra=extra,
            )
            self._load(kind)
            ent = (self._entries.get(kind, {}).get(key)
                   or self._transient.get(kind, {}).get(key))
            # A probe-capable lookup must not trust an unprobed verdict: an
            # interpret-mode run on a TPU host caches analytic picks under
            # the SAME device kind, and serving one to a compiled run would
            # re-introduce the exact unvalidated-arithmetic OOM this module
            # exists to prevent. Such entries are upgraded (re-selected via
            # probe and overwritten) instead of served.
            if ent is not None and not (can_probe
                                        and ent.get("source") != "probe"):
                self.hits += 1
                geometry = ent["geometry"]
                if isinstance(geometry, list):
                    geometry = tuple(geometry)
                self._record(regime, key, geometry, "hit",
                             ent.get("source", "cache"))
                return geometry

            self.misses += 1
            ranking = None
            estimates: Dict[str, dict] = {}
            if can_probe:
                source = "probe"
                geometry, ranking, estimates = self._probe_ranked(
                    candidates, cost, probe,
                )
            else:
                source = "analytic"
                geometry = analytic() if analytic is not None else None

            stored = list(geometry) if isinstance(geometry, tuple) else geometry
            entry = {"geometry": stored, "source": source}
            if ranking in ("measured", "timed"):
                # persist the ranking signal: which estimates (and, when
                # the probes executed, which measured probe_ms timings) the
                # winner beat, and that the verdict came from measurement
                # rather than the analytic prior
                entry["ranking"] = ranking
                entry["cost_estimates"] = estimates
            if geometry is None:
                # session-only: a "nothing legal" verdict may be a transient
                # probe-environment failure — don't let it outlive the
                # process (the next one re-probes)
                self._transient.setdefault(kind, {})[key] = entry
            else:
                self._entries.setdefault(kind, {})[key] = entry
                self._persist(kind)
            self._record(regime, key, geometry, "miss", source)
            return geometry

    def _probe_ranked(self, candidates, cost, probe):
        """Probe-validate candidates and pick the winner, preferring
        measured signals over the analytic prior — wall-clock probe
        timings first, compiled-cost estimates second.

        Candidates are walked in ascending prior-cost order. A probe that
        returns a bare ``True`` keeps the legacy contract — the first legal
        candidate wins and the walk stops (nothing to rank by). A probe
        that returns the *compiled object* opts into measured selection:
        every candidate is probed and ``compiled.cost_analysis()``
        estimates are collected; then, when every legal candidate's
        compiled program can actually EXECUTE here, each is timed for a
        few wall-clock runs (``_time_compiled``) and the fastest median
        wins (``ranking='timed'``, per-candidate ``probe_ms`` persisted in
        the tuning cache next to the estimates). When timing is
        unavailable (the compiled objects don't execute off-device, a run
        fails) the estimate ranking decides (``'measured'``), and the
        analytic prior keeps deciding only walk order and ties (ROADMAP
        raw-speed item b: measured timings > cost estimates > analytic
        prior).

        Probe exceptions before the first legal candidate propagate (the
        legacy safety contract: an unclassified compile error at a
        conservative candidate is a kernel bug, see flash_attention's
        ``_probe_compiles``); once a legal winner exists, ranking probes
        are best-effort — a failure there logs and skips the candidate
        rather than killing a selection that already has an answer.

        Returns ``(geometry, ranking, estimates)`` with ranking in
        ``('timed', 'measured', 'prior', None)``.
        """
        legal: List[Any] = []
        estimates: Dict[str, dict] = {}
        compiled_objs: Dict[str, Any] = {}
        for cand in sorted(candidates, key=cost):
            self.probe_count += 1
            if legal:
                try:
                    res = probe(cand)
                except Exception as e:  # noqa: BLE001 - ranking extras only
                    logger.warning(
                        "autotune: ranking probe failed for candidate %r "
                        "(%s); skipping it", cand, e,
                    )
                    continue
            else:
                res = probe(cand)
            if not res:
                continue
            est = _cost_estimate(res) if res is not True else None
            legal.append(cand)
            if est is None:
                # bool-style probe (or no cost model available): legacy
                # first-legal-wins — further probes buy nothing
                break
            estimates[_geom_json_key(cand)] = est
            compiled_objs[_geom_json_key(cand)] = res
        if not legal:
            return None, None, {}
        if len(estimates) == len(legal) and len(legal) > 1:
            timings: Optional[Dict[str, float]] = {}
            for cand in legal:
                key = _geom_json_key(cand)
                ms = _time_compiled(compiled_objs[key])
                if ms is None:
                    # no partial verdicts: ranking two candidates by time
                    # and the rest by estimate would compare incomparable
                    # units — all-or-nothing keeps the order meaningful
                    timings = None
                    break
                timings[key] = ms
            if timings:
                for key, ms in timings.items():
                    estimates[key]["probe_ms"] = round(ms, 4)
                winner = min(
                    legal, key=lambda c: timings[_geom_json_key(c)]
                )
                return winner, "timed", estimates
            winner = min(
                legal, key=lambda c: estimates[_geom_json_key(c)]["est_seconds"]
            )
            return winner, "measured", estimates
        return legal[0], "prior", estimates

    # -- whole-program step-cost estimates (serving flush ranking) -------------
    #
    # The serving engine records one ``cost_analysis()`` estimate per bucket
    # PROGRAM (not per kernel candidate) under a namespaced key, so the
    # micro-batcher can rank deadline flushes by measured step cost
    # (ROADMAP serving front (d)) and a warm restart gets the ranking
    # without compiling. These ride the same per-device-kind JSON files,
    # version/toolchain checks, and merge-before-write discipline as the
    # geometry entries; they never touch the probe/hit counters (zero-probe
    # warm-restart guarantees are unaffected).

    def record_cost(self, key: str, est: dict) -> None:
        """Persist one whole-program cost estimate (``_cost_estimate``
        shape: flops / bytes_accessed / est_seconds) under ``key``."""
        if not self.enabled:
            return
        with self._lock:
            kind = _device_kind()
            self._load(kind)
            self._entries.setdefault(kind, {})[key] = {
                "geometry": None,
                "source": "cost",
                "cost_estimates": {"program": dict(est)},
            }
            self._persist(kind)

    def lookup_cost(self, key: str) -> Optional[dict]:
        """The persisted whole-program estimate for ``key``, or None."""
        if not self.enabled:
            return None
        with self._lock:
            kind = _device_kind()
            self._load(kind)
            ent = self._entries.get(kind, {}).get(key)
            if not isinstance(ent, dict):
                return None
            est = (ent.get("cost_estimates") or {}).get("program")
            if not isinstance(est, dict) or "est_seconds" not in est:
                return None
            return dict(est)

    # -- session provenance (bench JSON) --------------------------------------

    def _record(self, regime, key, geometry, outcome, source) -> None:
        self._session.append(Decision(regime, key, geometry, outcome, source))

    def session_summary(self) -> dict:
        """Provenance for bench.py's JSON line: the overall cache outcome
        ('hit' only when every decision was served from cache), probe/hit
        counters, and the chosen geometry per decided key."""
        if not self.enabled:
            overall = "disabled"
        elif not self._session:
            overall = "unused"
        elif any(d.outcome == "miss" for d in self._session):
            overall = "miss"
        else:
            overall = "hit"
        geometries = {}
        for d in self._session:
            geometries[d.key or d.regime] = {
                "regime": d.regime,
                "geometry": list(d.geometry)
                if isinstance(d.geometry, tuple) else d.geometry,
                "outcome": d.outcome,
                "source": d.source,
            }
        return {
            "cache": overall,
            "probes": self.probe_count,
            "hits": self.hits,
            "misses": self.misses,
            "decisions": geometries,
        }


_instance: Optional[GeometryAutotuner] = None


def get() -> GeometryAutotuner:
    """The process-wide autotuner (created on first use)."""
    global _instance
    if _instance is None:
        _instance = GeometryAutotuner()
    return _instance


def configure(*, enabled: Optional[bool] = None,
              cache_dir=None) -> GeometryAutotuner:
    """(Re)configure the process-wide autotuner — the CLI/bench wiring for
    ``--autotune`` / ``--autotune_cache``."""
    inst = get()
    if enabled is not None:
        inst.enabled = enabled
    if cache_dir is not None:
        inst.set_cache_dir(cache_dir)
    return inst


def reset() -> GeometryAutotuner:
    """Drop the process-wide autotuner and return a fresh one (tests)."""
    global _instance
    _instance = None
    return get()
