"""Ahead-of-time compiled-program artifact store: zero-compile warm restarts.

The goodput ledger (PR 13) measured ``compile_warmup`` as the single largest
badput category on the smoke runs, and every supervisor restart, elastic
shrunk-mesh resume (PR 16) and serving rolling restart re-pays XLA
compilation for the whole program set. The pjit/TPUv4 systems work (arxiv
2204.06514, PAPERS.md) treats persistent compilation caching as a
first-class discipline for exactly this reason; TorchTitan (arxiv
2410.06511) frames fast restart as what makes preemptible capacity usable.

This module generalizes the PR-2 autotune cache (per-device-kind geometry
WINNERS in ``artifacts/tuning/*.json``) into a store of the compiled
PROGRAMS themselves: :meth:`ProgramCache.load_or_compile` performs
``jit(...).lower(...).compile()`` once, serializes the executable via
``jax.experimental.serialize_executable``, and on the next process —
a restarted trainer, a rolling-restarted serving replica — deserializes it
instead of compiling. Backends whose runtime cannot (de)serialize degrade
loudly to plain recompilation; training and serving semantics never depend
on the store.

Artifact anatomy (one file per program under
``<cache_dir>/<device_kind>/``):

- filename = ``<name>--<geometry>--<plan>--<extra>.aot`` — the LOOKUP key:
  program name, bucket/batch geometry, `ParallelPlan` mesh axes, and the
  precision/model suffix (the ``-q8`` discipline of ops/quant_matmul.py);
- content = magic + one JSON header line + the pickled
  ``serialize_executable`` payload. The header carries the VALIDITY
  fingerprint — ``code`` (package source hash + ``MLRT_AOT_SALT``),
  ``jax`` / ``jaxlib`` versions, and ``hlo`` (a hash of the lowered
  StableHLO text, so ANY semantic change to the program — a different
  learning-rate closure, another batch_split — invalidates exactly) —
  plus the blob's length and sha256 for corrupt/truncation recovery.

A stale fingerprint MISSES loudly (one structured log line naming the
changed component) and recompiles; a corrupt or truncated blob is deleted
and recompiled; writes go through ``metrics.artifacts.atomic_write_bytes``
(tmp + rename) so a concurrently warming process never reads a torn blob.
``--aot_cache off`` (or an absent store) leaves every call site compiling
exactly what HEAD compiled.

The inspection CLI lives in ``__main__``::

    python -m ml_recipe_tpu.ops.aot --list
    python -m ml_recipe_tpu.ops.aot --verify
    python -m ml_recipe_tpu.ops.aot --evict --aot_cache_bytes 512M

and is stdlib-only (no jax import): it must run on a host that merely
ADMINISTERS the store.

The store is OPT-IN: it is active only where someone names a directory for
it (``--aot_cache DIR`` or ``MLRT_AOT_CACHE``). By default every call site
compiles through plain ``lower().compile()`` and JAX's persistent
compilation cache (``utils/platform.configure_compile_cache``) is the one
compile cache in play.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import logging
import os
import pickle
import re
import threading
import time
from contextlib import contextmanager
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional, Tuple

logger = logging.getLogger(__name__)

_MAGIC = b"MLRTAOT1\n"
_STORE_VERSION = 1

# "0"/"false"/"off" disables the store process-wide (plain recompilation)
ENV_ENABLED = "MLRT_AOT"
# store directory; naming one (here or via --aot_cache) is what turns the
# store on
ENV_CACHE_DIR = "MLRT_AOT_CACHE"
# byte budget for the store (K/M/G suffixes); unset/0 = unbounded
ENV_CACHE_BYTES = "MLRT_AOT_CACHE_BYTES"
# extra fingerprint salt: a fleet-wide invalidation lever that needs no
# source change (and the regression tests' stale-fingerprint mutation hook)
ENV_SALT = "MLRT_AOT_SALT"

# validity components, compared in this order on lookup
FINGERPRINT_COMPONENTS = ("code", "jax", "jaxlib", "hlo")


def default_cache_dir() -> Optional[Path]:
    """``$MLRT_AOT_CACHE``, or None — there is no in-code default
    directory: an unnamed store is an inactive store."""
    env = os.environ.get(ENV_CACHE_DIR)
    return Path(env) if env else None


def _env_enabled() -> bool:
    return os.environ.get(ENV_ENABLED, "1").strip().lower() not in (
        "0", "false", "off", "no",
    )


def parse_bytes(text) -> Optional[int]:
    """``'512M'`` -> 536870912. None/''/0 -> None (unbounded). Accepts
    K/M/G suffixes (binary units) and plain byte counts."""
    if text is None:
        return None
    if isinstance(text, (int, float)):
        value = int(text)
        return value if value > 0 else None
    text = str(text).strip()
    if not text:
        return None
    match = re.fullmatch(r"(\d+)\s*([kKmMgG]?)[bB]?", text)
    if not match:
        raise ValueError(
            f"unparseable byte budget {text!r} (want e.g. 512M, 2G, 1048576)"
        )
    value = int(match.group(1))
    scale = {"": 1, "k": 1 << 10, "m": 1 << 20, "g": 1 << 30}[
        match.group(2).lower()
    ]
    value *= scale
    return value if value > 0 else None


def _device_kind() -> str:
    """Store partition key — the accelerator generation, exactly the
    autotune cache's discipline (a program compiled for one chip must
    never be deserialized on another)."""
    from . import autotune

    return autotune._device_kind()


def _jax_versions() -> Tuple[str, str]:
    try:
        import jax
        import jaxlib

        jl = getattr(jaxlib, "__version__", None) or getattr(
            getattr(jaxlib, "version", None), "__version__", "?"
        )
        return str(jax.__version__), str(jl)
    except Exception:  # noqa: BLE001 - no version = never match = recompile
        return "unknown", "unknown"


_CODE_FP: Optional[str] = None


def _code_fingerprint() -> str:
    """Hash of the package's Python source (memoized per process), mixed
    with ``MLRT_AOT_SALT`` — the salt is read per call so a test (or an
    operator forcing fleet-wide invalidation) can flip it without a new
    process."""
    global _CODE_FP
    if _CODE_FP is None:
        digest = hashlib.sha256()
        root = Path(__file__).resolve().parents[1]
        for path in sorted(root.rglob("*.py")):
            try:
                digest.update(path.relative_to(root).as_posix().encode())
                digest.update(path.read_bytes())
            except OSError:
                continue
        _CODE_FP = digest.hexdigest()[:16]
    salt = os.environ.get(ENV_SALT, "")
    if salt:
        return hashlib.sha256(
            f"{_CODE_FP}+{salt}".encode()
        ).hexdigest()[:16]
    return _CODE_FP


def _sanitize_part(part) -> str:
    """Filename-safe key component (MAY be empty — emptiness is part of
    the key: ``(geometry='', plan='x')`` must not collide with
    ``(geometry='x', plan='')``)."""
    return re.sub(r"[^A-Za-z0-9._-]+", "_", str(part))


def plan_signature(plan) -> str:
    """Stable mesh-axes key component from a ``ParallelPlan`` (or
    anything with ``describe() -> {axis: size}``), e.g. ``data4-model2``.
    Axis ORDER is part of the signature — it is the mesh order."""
    describe = getattr(plan, "describe", None)
    axes = describe() if callable(describe) else plan
    if isinstance(axes, dict):
        return "-".join(f"{k}{v}" for k, v in axes.items())
    return str(axes or "")


# -- serialization adapters (monkeypatch points for the unsupported-backend
# -- tests: a backend that cannot serialize raises here, never crashes a run)

def _serialize(compiled):
    """``(serialized, in_tree, out_tree, device_ids)``: the ids, in
    assignment order, of the devices the program was compiled for — a load
    must hand the runtime exactly those (its default is every device of the
    backend, which mis-loads a one-device program on an eight-device host)."""
    import jax
    from jax.experimental import serialize_executable

    device_ids = None
    sharding = next(iter(jax.tree_util.tree_leaves(
        (compiled.input_shardings, compiled.output_shardings))), None)
    if sharding is not None:
        mesh = getattr(sharding, "mesh", None)
        devices = mesh.devices.flat if mesh is not None else sharding.device_set
        device_ids = [int(d.id) for d in devices]
    return (*serialize_executable.serialize(compiled), device_ids)


@contextmanager
def _genuine_compile():
    """Compile with jax's own persistent compilation cache suspended.

    An executable that cache served (deserialized from
    ``JAX_COMPILATION_CACHE_DIR``) re-serializes to a payload that
    references compiled symbols it does not carry — deserializing it later
    fails with ``Symbols not found``. A store-bound compile must therefore
    be genuine, or a warm XLA cache would silently keep the program store
    empty (the write-validation in :meth:`ProgramCache._store` would
    refuse every blob). The jit dispatch cache is unaffected; this only
    bypasses the cross-process disk cache for the one compile the store
    is about to own.

    Flipping ``jax_enable_compilation_cache`` alone is not enough: the
    compiler gates on ``compilation_cache.is_cache_used(backend)``, which
    latches its verdict in module globals the first time any compile
    consults the cache. ``reset_cache()`` is the documented way to drop
    that latch, so it is called after each toggle — once so the flag-off
    compile re-probes (and skips) the cache, once so later non-store
    compiles re-probe with it enabled again."""
    try:
        import jax

        prev = bool(jax.config.jax_enable_compilation_cache)
    except Exception:  # noqa: BLE001 - no jax config = nothing to suspend
        yield
        return
    if not prev:
        yield
        return

    def _drop_latch():
        try:
            from jax._src import compilation_cache as _cc

            _cc.reset_cache()
        except Exception as e:  # noqa: BLE001 - private API; best effort,
            # the write-validation in _store backstops correctness
            logger.debug("AOT: compilation_cache.reset_cache failed: %s", e)

    jax.config.update("jax_enable_compilation_cache", False)
    _drop_latch()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        _drop_latch()


def _deserialize(payload):
    import jax
    from jax.experimental import serialize_executable

    serialized, in_tree, out_tree, device_ids = payload
    devices = None
    if device_ids is not None:
        by_id = {int(d.id): d for d in jax.devices()}
        devices = [by_id[i] for i in device_ids]  # KeyError: not this host's
    return serialize_executable.deserialize_and_load(
        serialized, in_tree, out_tree, execution_devices=devices
    )


# -- artifact file I/O ---------------------------------------------------------

def _read_artifact(path: Path):
    """``(header, blob, problem)``: problem is None when the artifact is
    structurally sound, ``'absent'`` when missing, else a human-readable
    corruption verdict (bad magic / torn header / truncated or
    checksum-failed blob) — the ``--verify`` CLI prints these verbatim."""
    try:
        raw = path.read_bytes()
    except FileNotFoundError:
        return None, None, "absent"
    except OSError as e:
        return None, None, f"unreadable ({e})"
    if not raw.startswith(_MAGIC):
        return None, None, "corrupt (bad magic)"
    try:
        end = raw.index(b"\n", len(_MAGIC))
        header = json.loads(raw[len(_MAGIC):end])
    except ValueError:
        return None, None, "corrupt (torn header)"
    if not isinstance(header, dict):
        return None, None, "corrupt (header is not an object)"
    blob = raw[end + 1:]
    want = header.get("blob_bytes")
    if want != len(blob):
        return None, None, (
            f"corrupt (truncated: {len(blob)} of {want} blob bytes)"
        )
    if header.get("blob_sha256") != hashlib.sha256(blob).hexdigest():
        return None, None, "corrupt (blob checksum mismatch)"
    return header, blob, None


def _iter_artifacts(cache_dir: Path) -> Iterator[Tuple[Path, Optional[dict], Optional[str]]]:
    """Every ``*.aot`` under the store, with its parsed header (or the
    corruption verdict)."""
    root = Path(cache_dir)
    if not root.is_dir():
        return
    for path in sorted(root.rglob("*.aot")):
        header, _, problem = _read_artifact(path)
        yield path, header, problem


def evict_to_budget(cache_dir, budget_bytes: Optional[int]) -> List[Path]:
    """Prune oldest-first (mtime) until the store's ``*.aot`` total fits
    ``budget_bytes``; returns the removed paths. No-op when unbounded."""
    if not budget_bytes or budget_bytes <= 0:
        return []
    root = Path(cache_dir)
    if not root.is_dir():
        return []
    entries = []
    for path in root.rglob("*.aot"):
        try:
            st = path.stat()
        except OSError:
            continue
        entries.append((st.st_mtime, st.st_size, path))
    total = sum(size for _, size, _ in entries)
    removed: List[Path] = []
    for _, size, path in sorted(entries):
        if total <= budget_bytes:
            break
        try:
            path.unlink()
        except OSError:
            continue
        total -= size
        removed.append(path)
    if removed:
        logger.info(
            "AOT: evicted %d artifact(s) to fit the %d-byte budget "
            "(store now %d bytes).", len(removed), budget_bytes, total,
        )
    return removed


def verify_store(cache_dir) -> List[dict]:
    """``--verify``'s engine: one report row per artifact — corrupt blobs
    are REPORTED (status carries the verdict), never silently deleted, so
    warmup does not trip on them and an operator sees why."""
    rows = []
    for path, header, problem in _iter_artifacts(cache_dir):
        try:
            size = path.stat().st_size
        except OSError:
            size = None
        rows.append({
            "path": str(path),
            "status": "ok" if problem is None else problem,
            "bytes": size,
            "fingerprint": (header or {}).get("fingerprint"),
        })
    return rows


# -- the store -----------------------------------------------------------------

class ProgramCache:
    """Process-wide AOT compiled-program store: lower -> (load | compile
    -> serialize) keyed by (device kind, program name, geometry, plan
    axes, extra) and fingerprint-validated by (code, jax, jaxlib, hlo).

    ``hits`` count disk loads that produced a running executable without
    an XLA compile; ``misses`` count real compiles while the store was
    active (the zero-compile warm-restart drills pin these); ``bypass``
    counts compiles with the store inactive (no directory named, or
    ``--aot_cache off`` — the plain ``lower().compile()`` path).
    """

    def __init__(self, cache_dir: Optional[Path] = None,
                 enabled: Optional[bool] = None,
                 cache_bytes: Optional[int] = None):
        self._switch = _env_enabled() if enabled is None else bool(enabled)
        self._cache_dir = Path(cache_dir) if cache_dir else None
        self.cache_bytes = (
            cache_bytes if cache_bytes is not None
            else parse_bytes(os.environ.get(ENV_CACHE_BYTES))
        )
        self.hits = 0
        self.misses = 0
        self.bypass = 0
        self.evictions = 0
        self.load_times_s: List[float] = []
        self._session: List[dict] = []
        # loud-once latch: a backend that cannot serialize fails every
        # attempt — warn at the first, stop paying serialize cost after
        self._serialize_unsupported = False
        self._lock = threading.RLock()

    # -- configuration ---------------------------------------------------------

    @property
    def enabled(self) -> bool:
        """Active only when switched on AND a directory is named."""
        return self._switch and self.cache_dir is not None

    @enabled.setter
    def enabled(self, value: bool) -> None:
        self._switch = bool(value)

    @property
    def cache_dir(self) -> Optional[Path]:
        # resolved lazily so an env override set after import still applies
        return self._cache_dir if self._cache_dir else default_cache_dir()

    def set_cache_dir(self, cache_dir) -> None:
        with self._lock:
            self._cache_dir = Path(cache_dir) if cache_dir else None

    # -- the one entry point ---------------------------------------------------

    def load_or_compile(self, name: str, jit_fn, *args, geometry: str = "",
                        plan: str = "", extra: str = "",
                        key_by_hlo: bool = False):
        """The compiled executable for ``jit_fn`` at ``args`` — loaded
        from the store when a valid artifact exists, compiled (and
        stored) otherwise. See :meth:`load_or_compile_ex` for the
        outcome-reporting variant."""
        return self.load_or_compile_ex(
            name, jit_fn, *args, geometry=geometry, plan=plan, extra=extra,
            key_by_hlo=key_by_hlo,
        )[0]

    def load_or_compile_ex(self, name: str, jit_fn, *args,
                           geometry: str = "", plan: str = "",
                           extra: str = "", key_by_hlo: bool = False):
        """``(compiled, outcome, seconds)`` with outcome one of
        ``'hit'`` (deserialized, zero XLA compile), ``'miss'`` (compiled;
        stale/corrupt/absent/deserialize-failed artifact) or ``'bypass'``
        (store disabled — the HEAD-identical compile).

        Compile errors PROPAGATE: the fused-kernel probes
        (quant_matmul/flash_attention) classify them (VMEM overflow vs
        kernel bug) and the store must not swallow that signal. Only
        store I/O and (de)serialization failures degrade — loudly — to
        recompilation.

        ``key_by_hlo=True`` appends the lowered program's own hash to the
        filename key — for PROBE sites that compile many sibling
        candidates at identical argument shapes (the candidate geometry
        is baked into the ``pallas_call``), where a shape-stable filename
        would make candidates stale-invalidate each other every sweep.
        """
        t0 = time.perf_counter()
        lowered = jit_fn.lower(*args)
        if not self.enabled:
            compiled = lowered.compile()
            self._note(name, "bypass", None, time.perf_counter() - t0)
            return compiled, "bypass", time.perf_counter() - t0

        try:
            hlo = hashlib.sha256(
                lowered.as_text().encode()
            ).hexdigest()[:16]
        except Exception as e:  # noqa: BLE001 - no text = no safe validity
            logger.warning(
                "AOT: cannot fingerprint lowered program %r (%s: %s); "
                "compiling without the store.", name, type(e).__name__, e,
            )
            compiled = lowered.compile()
            self._note(name, "miss", "unfingerprintable",
                       time.perf_counter() - t0)
            return compiled, "miss", time.perf_counter() - t0

        jax_ver, jaxlib_ver = _jax_versions()
        fingerprint = {
            "code": _code_fingerprint(),
            "jax": jax_ver,
            "jaxlib": jaxlib_ver,
            "hlo": hlo,
        }
        kind = _device_kind()
        if key_by_hlo:
            geometry = f"{geometry}-h{hlo}" if geometry else f"h{hlo}"
        path = self._artifact_path(kind, name, geometry, plan, extra)

        loaded, reason = self._try_load(path, name, fingerprint)
        if loaded is not None:
            seconds = time.perf_counter() - t0
            self._note(name, "hit", None, seconds)
            return loaded, "hit", seconds

        with _genuine_compile():
            compiled = lowered.compile()  # errors propagate to the caller
        self._store(path, compiled, name=name, geometry=geometry,
                    plan=plan, extra=extra, device_kind=kind,
                    fingerprint=fingerprint)
        seconds = time.perf_counter() - t0
        self._note(name, "miss", reason, seconds)
        return compiled, "miss", seconds

    # -- load / store ----------------------------------------------------------

    def _artifact_path(self, kind: str, name: str, geometry: str,
                       plan: str, extra: str) -> Path:
        stem = "--".join(
            _sanitize_part(part) for part in (name, geometry, plan, extra)
        )
        return self.cache_dir / _sanitize_part(kind or "unknown") / f"{stem}.aot"

    def _try_load(self, path: Path, name: str, fingerprint: dict):
        """``(executable, None)`` on a valid load, else ``(None, miss
        reason)``. Stale artifacts are never deserialized; corrupt ones
        are deleted so the recompile's store attempt replaces them."""
        header, blob, problem = _read_artifact(path)
        if problem == "absent":
            return None, "absent"
        if problem is not None:
            logger.warning(
                "AOT: MISS (corrupt) %s — %s; deleting the artifact and "
                "recompiling.", path, problem,
            )
            try:
                path.unlink()
            except OSError:
                pass
            return None, "corrupt"
        stored = header.get("fingerprint") or {}
        changed = [
            c for c in FINGERPRINT_COMPONENTS
            if stored.get(c) != fingerprint.get(c)
        ]
        if changed:
            # the loud stale-invalidation contract: ONE structured line
            # naming each changed component — never deserialize stale
            logger.warning(
                "AOT: MISS (stale) %s — fingerprint changed: %s; "
                "recompiling.", path,
                ", ".join(
                    f"component={c} artifact={stored.get(c)!r} "
                    f"current={fingerprint.get(c)!r}" for c in changed
                ),
            )
            return None, f"stale:{','.join(changed)}"
        try:
            executable = _deserialize(pickle.loads(blob))
        except Exception as e:  # noqa: BLE001 - backend/runtime specific
            logger.warning(
                "AOT: artifact %s exists and is valid but this "
                "backend/runtime cannot deserialize it (%s: %s); falling "
                "back to recompilation.", path, type(e).__name__, e,
            )
            return None, "deserialize"
        return executable, None

    def _store(self, path: Path, compiled, *, name: str, geometry: str,
               plan: str, extra: str, device_kind: str,
               fingerprint: dict) -> None:
        """Serialize + atomically write one artifact (best-effort: a
        store failure costs persistence, never the run)."""
        if self._serialize_unsupported:
            return
        try:
            blob = pickle.dumps(_serialize(compiled))
        except Exception as e:  # noqa: BLE001 - backend specific
            with self._lock:
                first = not self._serialize_unsupported
                self._serialize_unsupported = True
            if first:
                logger.warning(
                    "AOT: this backend cannot serialize compiled programs "
                    "(%s: %s); the store is read-only for this process — "
                    "every program recompiles.", type(e).__name__, e,
                )
            return
        # round-trip validation BEFORE persisting: an executable that XLA's
        # own persistent compile cache deserialized serializes to a payload
        # referencing symbols it does not carry ("Symbols not found" on
        # load) — persisting it would make every warm restart warn-and-
        # recompile. Deserializing here (off the critical path: this is the
        # miss path, the compile already ran) keeps the store hit-or-absent.
        try:
            _deserialize(pickle.loads(blob))
        except Exception as e:  # noqa: BLE001 - backend/runtime specific
            logger.warning(
                "AOT: program %r serialized but its payload does not "
                "deserialize on this backend/runtime (%s: %s); not "
                "persisting it. (A program served from XLA's persistent "
                "compile cache is the known source.)",
                name, type(e).__name__, e,
            )
            return
        header = {
            "store_version": _STORE_VERSION,
            "name": name,
            "geometry": geometry,
            "plan": plan,
            "extra": extra,
            "device_kind": device_kind,
            "fingerprint": dict(fingerprint),
            "blob_bytes": len(blob),
            "blob_sha256": hashlib.sha256(blob).hexdigest(),
            "created": time.time(),
        }
        payload = (
            _MAGIC
            + json.dumps(header, separators=(",", ":")).encode()
            + b"\n"
            + blob
        )
        from ..metrics.artifacts import atomic_write_bytes

        try:
            atomic_write_bytes(path, payload)
        except OSError as e:
            logger.warning(
                "AOT: could not persist artifact %s: %s", path, e,
            )
            return
        with self._lock:
            removed = evict_to_budget(self.cache_dir, self.cache_bytes)
            self.evictions += len(removed)

    # -- accounting ------------------------------------------------------------

    def _note(self, name: str, outcome: str, reason: Optional[str],
              seconds: float) -> None:
        with self._lock:
            if outcome == "hit":
                self.hits += 1
                self.load_times_s.append(seconds)
            elif outcome == "miss":
                self.misses += 1
            else:
                self.bypass += 1
            event: Dict[str, Any] = {
                "name": name, "outcome": outcome,
                "seconds": round(seconds, 6),
            }
            if reason:
                event["reason"] = reason
            self._session.append(event)

    def session_summary(self) -> dict:
        """Provenance for bench.py's JSON line, mirroring the autotuner's:
        overall outcome ('hit' only when every active decision loaded),
        hit/miss/bypass counters and the per-program events."""
        with self._lock:
            if not self.enabled:
                overall = "disabled"
            elif not self._session:
                overall = "unused"
            elif any(e["outcome"] == "miss" for e in self._session):
                overall = "miss"
            else:
                overall = "hit"
            return {
                "cache": overall,
                "hits": self.hits,
                "misses": self.misses,
                "bypass": self.bypass,
                "evictions": self.evictions,
                "load_s_total": round(sum(self.load_times_s), 6),
                "events": [dict(e) for e in self._session],
            }


def probe_compile(name: str, fn, *args, geometry: str = "",
                  extra: str = ""):
    """Route one fused-kernel validation / autotune probe compile through
    the store — the ``jax.jit(fn).lower(*args).compile()`` the kernel
    probes perform, with warm restarts loading the verdict's executable
    instead of re-paying Mosaic. Keyed by the lowered program's own hash
    (``key_by_hlo``), so sibling candidates sharing argument shapes never
    invalidate each other. Compile errors propagate unchanged for the
    caller to classify (VMEM overflow vs kernel bug)."""
    import jax

    return get().load_or_compile(
        name, jax.jit(fn), *args, geometry=geometry, extra=extra,
        key_by_hlo=True,
    )


_instance: Optional[ProgramCache] = None


def get() -> ProgramCache:
    """The process-wide program store (created on first use)."""
    global _instance
    if _instance is None:
        _instance = ProgramCache()
    return _instance


def configure(*, enabled: Optional[bool] = None, cache_dir=None,
              cache_bytes=None) -> ProgramCache:
    """(Re)configure the process-wide store — the CLI/bench wiring for
    ``--aot_cache`` / ``--aot_cache_bytes``."""
    inst = get()
    if enabled is not None:
        inst.enabled = enabled
    if cache_dir is not None:
        inst.set_cache_dir(cache_dir)
    if cache_bytes is not None:
        inst.cache_bytes = parse_bytes(cache_bytes)
    return inst


def reset() -> ProgramCache:
    """Drop the process-wide store and return a fresh one (tests)."""
    global _instance
    _instance = None
    return get()


# -- inspection CLI (stdlib-only: runs on hosts that only ADMINISTER the
# -- store, no jax import on any path here) ------------------------------------

def _fmt_age(seconds: float) -> str:
    if seconds < 120:
        return f"{seconds:.0f}s"
    if seconds < 7200:
        return f"{seconds / 60:.0f}m"
    if seconds < 172800:
        return f"{seconds / 3600:.1f}h"
    return f"{seconds / 86400:.1f}d"


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m ml_recipe_tpu.ops.aot",
        description="Inspect / verify / evict the AOT compiled-program "
                    "artifact store.",
    )
    parser.add_argument(
        "--cache_dir", default=None,
        help="store root (default: $MLRT_AOT_CACHE)")
    parser.add_argument(
        "--list", action="store_true",
        help="enumerate artifacts with key, size, age and fingerprint")
    parser.add_argument(
        "--verify", action="store_true",
        help="check every artifact's header + blob checksum; corrupt or "
             "truncated blobs are reported (exit 1), not deleted")
    parser.add_argument(
        "--evict", action="store_true",
        help="prune oldest artifacts until the store fits "
             "--aot_cache_bytes")
    parser.add_argument(
        "--aot_cache_bytes", default=None,
        help="byte budget for --evict (K/M/G suffixes, e.g. 512M)")
    args = parser.parse_args(argv)

    cache_dir = Path(args.cache_dir) if args.cache_dir else default_cache_dir()
    if cache_dir is None:
        parser.error("no store named: pass --cache_dir or set MLRT_AOT_CACHE")
    if not (args.list or args.verify or args.evict):
        args.list = True

    status = 0
    if args.list:
        rows = list(_iter_artifacts(cache_dir))
        if not rows:
            print(f"AOT store {cache_dir}: empty")
        else:
            now = time.time()
            total = 0
            for path, header, problem in rows:
                try:
                    st = path.stat()
                except OSError:
                    continue
                total += st.st_size
                fp = (header or {}).get("fingerprint") or {}
                fp_text = (
                    " ".join(f"{k}={fp.get(k)}"
                             for k in FINGERPRINT_COMPONENTS)
                    if fp else f"<{problem}>"
                )
                print(
                    f"{path.relative_to(cache_dir)}  "
                    f"{st.st_size}B  age={_fmt_age(max(0.0, now - st.st_mtime))}  "
                    f"{fp_text}"
                )
            print(f"total: {len(rows)} artifact(s), {total} bytes")
    if args.verify:
        rows = verify_store(cache_dir)
        bad = [r for r in rows if r["status"] != "ok"]
        for row in rows:
            print(f"{row['status'].upper():<40}  {row['path']}")
        print(
            f"verified {len(rows)} artifact(s): {len(rows) - len(bad)} ok, "
            f"{len(bad)} corrupt"
        )
        if bad:
            status = 1
    if args.evict:
        budget = parse_bytes(args.aot_cache_bytes)
        if budget is None:
            parser.error("--evict requires --aot_cache_bytes (e.g. 512M)")
        removed = evict_to_budget(cache_dir, budget)
        for path in removed:
            print(f"evicted {path}")
        print(f"evicted {len(removed)} artifact(s)")
    return status


if __name__ == "__main__":
    raise SystemExit(main())
