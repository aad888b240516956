"""Serving hot-path cache tests (ISSUE 7, serve/cache.py + engine wiring).

Tier-1 coverage of the two-tier caching layer: byte-budget LRU eviction
exactness, tier-2 key isolation across checkpoint fingerprint and
precision, single-flight dedup, bit-identical responses cached vs uncached,
budget-0 == HEAD behavior, the overload fast-fail precheck, measured
per-bucket flush ranking, and the /metrics-vs-README docs-consistency gate.
The SIGTERM drain drill with hit and miss chunks in flight lives at the
bottom under the ``chaos`` marker (tests/test_serve_chaos.py conventions).
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import jax

from ml_recipe_tpu.models import EncoderConfig, QAModel
from ml_recipe_tpu.ops import autotune
from ml_recipe_tpu.parallel import build_mesh
from ml_recipe_tpu.serve.batcher import (
    ChunkWork,
    DrainingError,
    MicroBatcher,
    QueueFullError,
)
from ml_recipe_tpu.serve.bucketing import BucketGrid
from ml_recipe_tpu.serve.cache import (
    ByteBudgetLRU,
    ChunkResultCache,
    content_key,
    params_fingerprint,
    row_key,
)

from helpers import make_tokenizer

_REPO = Path(__file__).resolve().parents[1]

_QUESTION = "what is the capital of england ?"
# long enough that the first sliding window is FULL (document_len tokens)
# — an appended edit then leaves that window's token slice bit-identical,
# which is what the partial-hit test exploits
_DOCUMENT = (
    "<P> London is the capital of England . </P> "
    "<P> Big Ben was built in the city . The river Thames runs through "
    "London . </P> "
    "<P> The city is the biggest city of England . People like the river "
    "and the big city . </P> "
    "<P> The capital is big and the river runs through the capital . </P> "
    "<P> England is the country of the city of London . </P>"
)
_DOCUMENT_EXT = _DOCUMENT + (
    " <P> England is a country and London is big . </P>"
)


# ---------------------------------------------------------------------------
# ByteBudgetLRU: byte-budget eviction exactness
# ---------------------------------------------------------------------------


@pytest.mark.unit
def test_lru_byte_budget_eviction_exact():
    lru = ByteBudgetLRU(250)
    assert lru.put("a", "A", 100) == 0
    assert lru.put("b", "B", 100) == 0
    assert lru.bytes == 200 and len(lru) == 2
    # refresh recency: 'a' becomes MRU, so 'b' is the eviction victim
    assert lru.get("a") == "A"
    assert lru.put("c", "C", 100) == 1  # 300 > 250: evict exactly LRU 'b'
    assert lru.get("b") is None
    assert lru.get("a") == "A" and lru.get("c") == "C"
    assert lru.bytes == 200 and len(lru) == 2
    s = lru.stats()
    assert s["evictions"] == 1 and s["bytes"] == 200 and s["entries"] == 2

    # a refreshed key releases its old cost before re-accounting
    assert lru.put("a", "A2", 150) == 0  # 100 out, 150 in -> 250 == budget
    assert lru.bytes == 250 and lru.get("a") == "A2"

    # an entry whose own cost exceeds the whole budget is refused outright
    assert lru.put("big", "X", 251) == 0
    assert lru.get("big") is None
    assert lru.bytes == 250 and len(lru) == 2
    # ... and refusing a REFRESH of an existing key removes the stale value
    # (serving a stale row would violate transparency)
    lru.put("a", "A3", 9999)
    assert lru.get("a") is None
    assert lru.bytes == 100 and len(lru) == 1  # only 'c' remains


@pytest.mark.unit
def test_lru_budget_zero_and_exact_fit():
    lru = ByteBudgetLRU(100)
    assert lru.put("exact", 1, 100) == 0  # cost == budget fits
    assert lru.get("exact") == 1
    assert lru.put("next", 2, 100) == 1   # displaces the only entry
    assert lru.get("exact") is None and lru.get("next") == 2


# ---------------------------------------------------------------------------
# tier-2 keys: fingerprint / precision / row isolation
# ---------------------------------------------------------------------------


@pytest.mark.unit
def test_row_key_isolation_across_fingerprint_precision_and_row():
    row = [2, 17, 3, 9, 9, 3]
    base = row_key("fpA", "off", row)
    assert base == row_key("fpA", "off", list(row))  # deterministic
    assert base != row_key("fpB", "off", row)        # checkpoint isolation
    assert base != row_key("fpA", "int8", row)       # precision isolation
    assert base != row_key("fpA", "off", row[:-1] + [4])  # any byte differs
    assert base.startswith("fpA|off|")


@pytest.mark.unit
def test_params_fingerprint_distinguishes_checkpoints():
    a = {"layer": {"kernel": np.ones((4, 4), np.float32),
                   "bias": np.zeros((4,), np.float32)}}
    b = {"layer": {"kernel": np.ones((4, 4), np.float32),
                   "bias": np.zeros((4,), np.float32)}}
    assert params_fingerprint(a) == params_fingerprint(b)
    b["layer"]["kernel"][0, 0] = 2.0  # one weight differs -> different key
    assert params_fingerprint(a) != params_fingerprint(b)
    # dtype changes alone change the fingerprint (same bytes reinterpreted
    # through different arithmetic are a different serving function)
    c = {"layer": {"kernel": np.ones((4, 4), np.float16),
                   "bias": np.zeros((4,), np.float32)}}
    assert params_fingerprint(a) != params_fingerprint(c)


@pytest.mark.unit
def test_content_key_is_content_hash():
    assert content_key("abc") == content_key("abc")
    assert content_key("abc") != content_key("abd")


# ---------------------------------------------------------------------------
# single-flight dedup (unit)
# ---------------------------------------------------------------------------


@pytest.mark.unit
def test_single_flight_join_complete_fail_abort():
    cache = ChunkResultCache(1 << 16)
    # first caller leases the flight, identical callers join as waiters
    assert not cache.join_flight("k", ("t0", 0))
    assert cache.join_flight("k", ("t1", 0))
    assert cache.join_flight("k", ("t2", 3))
    assert cache.flight_joins == 2 and cache.inflight() == 1

    waiters, _ = cache.complete("k", {"scores": 1.0}, 64)
    assert waiters == [("t1", 0), ("t2", 3)]
    assert cache.inflight() == 0
    assert cache.get("k") == {"scores": 1.0}  # leader's row is now cached

    # failure path: nothing cached, waiters surface for ticket-fail
    assert not cache.join_flight("f", ("t3", 0))
    assert cache.join_flight("f", ("t4", 0))
    assert cache.fail_flight("f") == [("t4", 0)]
    assert cache.get("f") is None

    # abort (admission rollback) forgets the lease
    assert not cache.join_flight("a", ("t5", 0))
    cache.abort_flight("a")
    assert cache.inflight() == 0
    assert not cache.join_flight("a", ("t6", 0))  # fresh lease again


@pytest.mark.unit
def test_single_flight_remove_waiters_by_owner():
    cache = ChunkResultCache(1 << 16)
    assert not cache.join_flight("k1", ("lead", 0))
    assert cache.join_flight("k1", ("victim", 1))
    assert cache.join_flight("k1", ("other", 2))
    assert not cache.join_flight("k2", ("lead2", 0))
    assert cache.join_flight("k2", ("victim", 5))
    assert cache.remove_waiters("victim") == 2
    # joins stay MONOTONIC (they mirror into a Prometheus counter); the
    # undo is a separate monotonic rollback count
    assert cache.flight_joins == 3
    assert cache.flight_join_rollbacks == 2
    waiters, _ = cache.complete("k1", "row", 8)
    assert waiters == [("other", 2)]


# ---------------------------------------------------------------------------
# measured flush ranking (batcher unit)
# ---------------------------------------------------------------------------


def _work(seq):
    return ChunkWork(seq=seq, payload=None)


@pytest.mark.unit
def test_flush_ranking_prefers_cheapest_measured_program():
    grid = BucketGrid.from_spec("2x64,2x128")
    costs = {64: 5.0, 128: 1.0}
    b = MicroBatcher(grid, lambda s, w: None, max_batch_delay_ms=0,
                     queue_size=16,
                     flush_cost_fn=lambda seq, n: costs[seq])
    b.submit_many([_work(64)])
    time.sleep(0.002)
    b.submit_many([_work(128)])
    with b._cv:
        first = b._take_locked()
        second = b._take_locked()
    # seq 64 is OLDER, but 128's measured step cost is lower: it flushes
    # first (front (d): cheap programs stop queueing behind expensive ones)
    assert first[0] == 128 and second[0] == 64


@pytest.mark.unit
def test_flush_ranking_falls_back_without_estimates():
    grid = BucketGrid.from_spec("2x64,2x128,2x256")
    # PARTIAL estimates: measured seqs first, the rest after them in
    # ascending-seq order (the documented fallback)
    costs = {64: None, 128: 0.1, 256: None}
    b = MicroBatcher(grid, lambda s, w: None, max_batch_delay_ms=0,
                     queue_size=16,
                     flush_cost_fn=lambda seq, n: costs[seq])
    b.submit_many([_work(256)])
    time.sleep(0.002)
    b.submit_many([_work(64)])
    time.sleep(0.002)
    b.submit_many([_work(128)])
    with b._cv:
        assert b._take_locked()[0] == 128  # the only measured seq
        # with no measured seq left eligible, ranking has no evidence:
        # back to oldest-first (256 was submitted before 64)
        assert b._take_locked()[0] == 256
        assert b._take_locked()[0] == 64

    # NO estimate for anything (cost_analysis yields nothing on this
    # toolchain): must not reorder on no evidence — oldest-first, as if
    # the hook were absent
    b2 = MicroBatcher(grid, lambda s, w: None, max_batch_delay_ms=0,
                      queue_size=16, flush_cost_fn=lambda seq, n: None)
    b2.submit_many([_work(128)])
    time.sleep(0.002)
    b2.submit_many([_work(64)])
    with b2._cv:
        assert b2._take_locked()[0] == 128

    # no hook at all: historical oldest-item-first order
    b3 = MicroBatcher(grid, lambda s, w: None, max_batch_delay_ms=0,
                      queue_size=16)
    b3.submit_many([_work(128)])
    time.sleep(0.002)
    b3.submit_many([_work(64)])
    with b3._cv:
        assert b3._take_locked()[0] == 128


@pytest.mark.unit
def test_flush_ranking_starvation_guard():
    """Under sustained cheap-bucket load the cheap queue re-expires every
    iteration; once the oldest eligible item has waited past the
    starvation bound, fairness overrides cost ranking — an expensive
    bucket is delayed, never denied."""
    grid = BucketGrid.from_spec("2x64,2x128")
    costs = {64: 0.001, 128: 5.0}
    b = MicroBatcher(grid, lambda s, w: None, max_batch_delay_ms=0,
                     queue_size=16,
                     flush_cost_fn=lambda seq, n: costs[seq])
    b.submit_many([_work(128)])  # expensive; left to age past the bound
    time.sleep(b._starve_after_s + 0.01)
    b.submit_many([_work(64)])   # cheap and fresh: would win on cost alone
    with b._cv:
        assert b._take_locked()[0] == 128


@pytest.mark.unit
def test_full_bucket_still_preempts_cost_ranking():
    grid = BucketGrid.from_spec("2x64,2x128")
    costs = {64: 5.0, 128: 0.1}
    b = MicroBatcher(grid, lambda s, w: None, max_batch_delay_ms=0,
                     queue_size=16,
                     flush_cost_fn=lambda seq, n: costs[seq])
    b.submit_many([_work(64), _work(64), _work(128)])
    with b._cv:
        # 64 fills its largest bucket: full buckets fire first, always
        assert b._take_locked()[0] == 64


@pytest.mark.unit
def test_precheck_fast_fails_full_and_draining():
    grid = BucketGrid.from_spec("4x64")
    b = MicroBatcher(grid, lambda s, w: None, queue_size=2)
    b.precheck()  # empty queue: admissible
    b.submit_many([_work(64), _work(64)])
    with pytest.raises(QueueFullError):
        b.precheck()
    b2 = MicroBatcher(grid, lambda s, w: None, queue_size=2)
    assert b2.drain(timeout=1.0)
    with pytest.raises(DrainingError):
        b2.precheck()


# ---------------------------------------------------------------------------
# engine integration (tiny model, CPU mesh)
# ---------------------------------------------------------------------------


def _tiny_model(tok, max_len=64):
    cfg = EncoderConfig(
        vocab_size=len(tok), hidden_size=16, num_layers=1, num_heads=2,
        intermediate_size=32, max_position_embeddings=max_len + 2,
        num_labels=5,
    )
    model = QAModel(cfg)
    params = model.init(
        jax.random.key(0), np.zeros((1, 8), dtype=np.int32)
    )["params"]
    return model, params


def _result_tuple(r):
    return (r.answer, r.label, r.score, r.start, r.end, r.n_chunks)


@pytest.fixture(scope="module")
def stack(tmp_path_factory):
    from ml_recipe_tpu.serve.engine import QAEngine

    tmp = tmp_path_factory.mktemp("serve_cache")
    tok = make_tokenizer(tmp)
    model, params = _tiny_model(tok)
    mesh = build_mesh()

    def make_engine(**kw):
        kw.setdefault("grid", BucketGrid.from_spec("4x64,8x64"))
        kw.setdefault("max_batch_delay_ms", 5)
        kw.setdefault("queue_size", 64)
        kw.setdefault("max_question_len", 16)
        kw.setdefault("doc_stride", 24)
        return QAEngine(model, params, tok, mesh=mesh, **kw)

    plain = make_engine()
    plain_report = plain.warmup(hbm_preflight=False)
    cached = make_engine(serve_cache_bytes=1 << 20, doc_cache_bytes=1 << 20)
    cached_report = cached.warmup(hbm_preflight=False)
    yield SimpleNamespace(
        tok=tok, model=model, params=params, mesh=mesh,
        make_engine=make_engine, plain=plain, cached=cached,
        plain_report=plain_report, cached_report=cached_report,
    )
    plain.close()
    cached.close()


def test_cached_responses_bit_identical_and_hot_bypasses_device(stack):
    """ISSUE-7 acceptance: span/score parity cached vs uncached, and a
    fully-hot request launches ZERO batches."""
    r_plain = stack.plain.submit(_QUESTION, _DOCUMENT).result(timeout=120)
    r_miss = stack.cached.submit(_QUESTION, _DOCUMENT).result(timeout=120)
    batches_after_miss = stack.cached.m_batches.value
    hits_before = stack.cached._chunk_cache.stats()["hits"]

    r_hit = stack.cached.submit(_QUESTION, _DOCUMENT).result(timeout=120)

    assert _result_tuple(r_plain) == _result_tuple(r_miss)
    assert _result_tuple(r_miss) == _result_tuple(r_hit)  # bit-identical
    # the hot request never touched the batcher or the device
    assert stack.cached.m_batches.value == batches_after_miss
    assert (stack.cached._chunk_cache.stats()["hits"] - hits_before
            == r_hit.n_chunks)


def test_budget_zero_disables_tiers_completely(stack):
    """``--serve_cache_bytes 0`` must be bit-identical to HEAD: no cache
    objects exist, every request launches device work."""
    assert stack.plain._chunk_cache is None
    assert stack.plain._doc_cache is None
    assert stack.plain.cache_stats() == {"doc": None, "chunk": None}

    before = stack.plain.m_batches.value
    r1 = stack.plain.submit(_QUESTION, _DOCUMENT).result(timeout=120)
    r2 = stack.plain.submit(_QUESTION, _DOCUMENT).result(timeout=120)
    assert _result_tuple(r1) == _result_tuple(r2)
    assert stack.plain.m_batches.value >= before + 2  # no bypass ever


def test_partial_hit_only_computes_changed_windows(stack):
    """The same question over an edited/grown document recomputes only the
    windows whose exact device rows changed."""
    engine = stack.cached
    t = engine.submit(_QUESTION, _DOCUMENT)
    base = t.result(timeout=120)
    assert base.n_chunks >= 2

    s0 = engine._chunk_cache.stats()
    t2 = engine.submit(_QUESTION, _DOCUMENT_EXT)
    ext = t2.result(timeout=120)
    s1 = engine._chunk_cache.stats()

    hits = s1["hits"] - s0["hits"]
    misses = s1["misses"] - s0["misses"]
    assert hits >= 1, "no window of the edited document was reused"
    assert misses >= 1, "the edit must have changed at least one window"
    assert hits + misses == ext.n_chunks
    assert ext.label in ("yes", "no", "short", "long", "unknown")


def test_doc_cache_skips_host_tokenization(stack, monkeypatch):
    """Tier 1: a hot document never re-enters ``encode_document``, across
    DIFFERENT questions of the same token length (the layout key carries
    only the question's length, not its text)."""
    from ml_recipe_tpu.serve import engine as engine_mod

    calls = []
    real = engine_mod.encode_document

    def counting(tokenizer, text):
        calls.append(text)
        return real(tokenizer, text)

    monkeypatch.setattr(engine_mod, "encode_document", counting)
    doc = _DOCUMENT + " <P> A new paragraph makes the text unique . </P>"
    engine = stack.cached
    engine.submit(_QUESTION, doc).result(timeout=120)
    assert len(calls) == 1
    engine.submit(_QUESTION, doc).result(timeout=120)
    engine.submit("what is the capital of england now ?", doc).result(
        timeout=120)
    assert len(calls) == 1, "hot document re-tokenized"


def test_single_flight_dedup_identical_inflight_chunks(stack):
    """A burst of one (question, document) pair costs ONE device row per
    window: later arrivals join the in-flight computation as waiters."""
    engine = stack.make_engine(
        serve_cache_bytes=1 << 20, max_batch_delay_ms=250)
    engine.batcher.start()  # no warmup: the single launch pays the compile
    try:
        doc = _DOCUMENT + " <P> Single flight paragraph . </P>"
        t1 = engine.submit(_QUESTION, doc)
        depth_after_first = engine.batcher.depth
        t2 = engine.submit(_QUESTION, doc)  # identical: joins, no new slots
        assert engine.batcher.depth == depth_after_first
        assert engine._chunk_cache.flight_joins == t1.n_chunks

        r1 = t1.result(timeout=120)
        r2 = t2.result(timeout=120)
        assert _result_tuple(r1) == _result_tuple(r2)
        assert engine.m_batches.value == 1  # one coalesced launch total
    finally:
        engine.close()


def test_precheck_rejects_before_tokenizing(stack, monkeypatch):
    """Overload fast-fail: a saturated/draining engine rejects BEFORE
    paying host tokenization (the authoritative all-or-nothing admission
    stays in submit_many)."""
    from ml_recipe_tpu.serve import engine as engine_mod

    def boom(tokenizer, text):  # noqa: ARG001 - signature parity
        raise AssertionError("tokenized a document the precheck must veto")

    engine = stack.make_engine(queue_size=2)  # batcher never started
    t = engine.submit(_QUESTION, "<P> london is big . </P>")
    t2 = engine.submit(_QUESTION, "<P> london is the capital . </P>")
    assert t.n_chunks == t2.n_chunks == 1  # queue now full (2/2)

    monkeypatch.setattr(engine_mod, "encode_document", boom)
    with pytest.raises(QueueFullError):
        engine.submit(_QUESTION, _DOCUMENT)
    assert engine.m_rejected_full.value == 1

    drained = stack.make_engine()
    assert drained.batcher.drain(timeout=1.0)  # empty: drains instantly
    with pytest.raises(DrainingError):
        drained.submit(_QUESTION, _DOCUMENT)
    assert drained.m_rejected_draining.value == 1


def test_fully_hot_request_served_despite_full_queue(stack):
    """With the chunk-result cache enabled, the overload precheck keeps
    only its draining arm: a fully-hot request needs zero queue slots and
    must be served even when the queue is at capacity (rejecting it would
    429 exactly the traffic the cache exists to absorb)."""
    engine = stack.cached
    warm = engine.submit(_QUESTION, _DOCUMENT).result(timeout=120)

    b = engine.batcher
    with b._cv:
        real_pending = b._n_pending
        b._n_pending = b.queue_size  # simulate saturation
    try:
        hot = engine.submit(_QUESTION, _DOCUMENT).result(timeout=5)
        assert _result_tuple(hot) == _result_tuple(warm)
        with pytest.raises(QueueFullError):
            # a cold request still hits the authoritative admission check
            engine.submit(_QUESTION, _DOCUMENT + " <P> fresh text . </P>")
    finally:
        with b._cv:
            b._n_pending = real_pending


def test_oversized_fully_hot_document_served(stack):
    """The queue-can-never-hold-this rejection applies to MISS chunks only
    when the chunk cache is on: a document with more windows than
    queue_size is served when its rows are cached (they need zero queue
    slots), while the same shape cold is still a permanent client error."""
    from ml_recipe_tpu.serve.engine import RequestRejected

    engine = stack.cached
    warm = engine.submit(_QUESTION, _DOCUMENT).result(timeout=120)
    assert warm.n_chunks >= 2  # the bound below must bite multi-window docs

    b = engine.batcher
    real_queue_size = b.queue_size
    b.queue_size = 1  # every multi-window doc now exceeds total capacity
    try:
        hot = engine.submit(_QUESTION, _DOCUMENT).result(timeout=5)
        assert _result_tuple(hot) == _result_tuple(warm)
        cold = _DOCUMENT.replace("London", "Paris").replace(
            "England", "France")
        with pytest.raises(RequestRejected, match="uncached windows"):
            engine.submit(_QUESTION, cold)
        # rollback left no leaked flights for the rejected request
        assert engine._chunk_cache.inflight() == 0
    finally:
        b.queue_size = real_queue_size


def test_flush_hook_not_wired_without_autotune(stack):
    """With the autotuner disabled there is no cost source: the engine must
    NOT wire the flush-ranking hook (which would silently reorder deadline
    flushes to the ascending-seq fallback with nothing measured behind it)
    — the batcher keeps the historical oldest-first order."""
    tuner = autotune.get()
    was_enabled = tuner.enabled
    tuner.enabled = False
    try:
        off = stack.make_engine()
        assert off.batcher._flush_cost_fn is None
    finally:
        tuner.enabled = was_enabled
    assert stack.cached.batcher._flush_cost_fn is not None


def test_warmup_records_program_costs_for_flush_ranking(stack):
    """Front (d) plumbing: warmup persists one ``cost_analysis()`` estimate
    per bucket program in the autotune cache, the engine's flush hook reads
    it back, and a warm restart performs zero probes with the caches on."""
    report = stack.cached_report
    assert report["autotune"]["probes"] == 0  # zero-probe startup intact
    costs = report["program_costs"]
    assert set(costs) == {"4x64", "8x64"}
    for bucket, est in costs.items():
        assert est is not None and est > 0.0, (bucket, est)

    engine = stack.cached
    tuner = autotune.get()
    for batch, seq in ((4, 64), (8, 64)):
        persisted = tuner.lookup_cost(engine._program_cost_key(batch, seq))
        assert persisted is not None
        assert persisted["est_seconds"] == costs[f"{batch}x{seq}"]
    # the batcher-thread hook resolves through the memo to the same number
    assert engine._flush_cost(64, 3) == costs["4x64"]
    assert engine._flush_cost(64, 5) == costs["8x64"]


def test_no_estimate_verdict_persisted_once(stack, monkeypatch):
    """A toolchain whose cost_analysis yields nothing still gets its
    verdict persisted (a ``{"est_seconds": None}`` marker): the cost-probe
    AOT compile is paid once per cache lifetime, not once per startup, and
    the flush hook treats the marker as no-estimate."""
    from ml_recipe_tpu.serve import engine as engine_mod

    monkeypatch.setattr(
        engine_mod.autotune, "program_cost_estimate", lambda compiled: None)
    engine = stack.make_engine(
        grid=BucketGrid.from_spec("2x32"),
        serve_cache_bytes=1 << 20)
    engine.warmup(hbm_preflight=False)
    try:
        key = engine._program_cost_key(2, 32)
        marker = autotune.get().lookup_cost(key)
        assert marker == {"est_seconds": None}
        assert engine._flush_cost(32, 1) is None

        # count real XLA compiles, not lowers: the AOT program store lowers
        # on every build to validate the artifact's HLO fingerprint (a warm
        # hit lowers but never compiles), so `lowered.compile` is the
        # boundary the once-per-cache-lifetime promise lives at
        compiles = []
        real_lower = engine._jit.lower

        class _CountingLowered:
            def __init__(self, lowered):
                self._lowered = lowered

            def compile(self, *a, **kw):
                compiles.append(1)
                return self._lowered.compile(*a, **kw)

            def __getattr__(self, name):
                return getattr(self._lowered, name)

        monkeypatch.setattr(
            engine._jit, "lower",
            lambda *a, **kw: _CountingLowered(real_lower(*a, **kw)))
        again = stack.make_engine(
            grid=BucketGrid.from_spec("2x32"),
            serve_cache_bytes=1 << 20)
        again._jit = engine._jit
        again.warmup(hbm_preflight=False)
        again.batcher.drain(timeout=5)
        assert compiles == []  # the marker short-circuits the cost compile
    finally:
        engine.batcher.drain(timeout=5)


def test_metrics_surface_consistent_with_docs(stack):
    """CI satellite, shared by BOTH planes: every metric registered in the
    serving engine's registry AND the training telemetry registry must
    render in its /metrics output AND appear in the README metrics tables,
    so neither Prometheus surface can silently drift from the docs."""
    engine = stack.cached
    names = engine.metrics.names()
    assert len(names) >= 28  # the full serving surface, cache series included
    for prefix in ("qa_doc_cache", "qa_chunk_cache", "qa_chunk_flight"):
        assert any(n.startswith(prefix) for n in names), prefix

    readme = (_REPO / "README.md").read_text()
    rendered = engine.render_metrics()
    missing_render = [n for n in names if n not in rendered]
    missing_docs = [n for n in names if n not in readme]

    # training plane rides the same gate (observability plane): the
    # --metrics_port registry's names, rendered by the exporter
    from ml_recipe_tpu.train.telemetry import TrainTelemetry

    telemetry = TrainTelemetry()
    telemetry.refresh()
    train_names = telemetry.registry.names()
    assert len(train_names) >= 20  # the full training surface
    for prefix in ("train_step_", "train_supervisor_", "train_watchdog_"):
        assert any(n.startswith(prefix) for n in train_names), prefix
    rendered_train = telemetry.registry.render()
    missing_render += [n for n in train_names if n not in rendered_train]
    missing_docs += [n for n in train_names if n not in readme]

    # fleet plane (ISSUE 18): the router's own registry rides the same
    # gate, plus the README must carry a "Serving fleet" section
    from ml_recipe_tpu.fleet import FleetRouter

    router = FleetRouter()
    try:
        fleet_names = router.metrics.names()
        assert len(fleet_names) >= 12  # the full router surface
        for prefix in ("fleet_engine", "fleet_spilled", "fleet_shed",
                       "fleet_ejections", "fleet_hop"):
            assert any(n.startswith(prefix) for n in fleet_names), prefix
        rendered_fleet = router.metrics.render()
        missing_render += [n for n in fleet_names if n not in rendered_fleet]
        missing_docs += [n for n in fleet_names if n not in readme]
        assert "## Serving fleet" in readme
    finally:
        router._httpd.server_close()  # constructed, never started

    assert not missing_render, (
        f"registered metrics absent from /metrics output: {missing_render}")
    assert not missing_docs, (
        f"registered metrics absent from the README metrics tables "
        f"(document them): {missing_docs}")


# ---------------------------------------------------------------------------
# chaos: SIGTERM drain with cache-hit and cache-miss chunks in flight
# ---------------------------------------------------------------------------


def _post(url, question, document, timeout=60.0):
    req = urllib.request.Request(
        f"{url}/v1/qa",
        data=json.dumps(
            {"question": question, "document": document}
        ).encode("utf-8"),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


@pytest.mark.chaos
def test_sigterm_drain_flushes_hit_and_miss_chunks(tmp_path):
    """ISSUE-7 satellite drill: SIGTERM while a partially-hot request
    (cache-hit chunks already offered, cache-miss chunks still queued) and
    an all-miss request are in flight — BOTH flush to real 200s and the
    process exits 0."""
    from helpers import write_vocab

    vocab = write_vocab(tmp_path)
    ready = tmp_path / "ready.json"
    log_path = tmp_path / "serve.log"

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(_REPO) + os.pathsep + env.get("PYTHONPATH", "")
    log = open(log_path, "w")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "ml_recipe_tpu.cli.serve",
            "--model", "bert-tiny",
            "--vocab_file", str(vocab),
            "--lowercase",
            "--buckets", "8x64",
            # long coalescing deadline: miss chunks are still QUEUED when
            # SIGTERM lands, while hit chunks were already offered — the
            # drain must flush the queued misses so partially-hot tickets
            # complete
            "--max_batch_delay_ms", "600",
            "--max_question_len", "16",
            "--doc_stride", "24",
            "--serve_cache_bytes", "1M",
            "--doc_cache_bytes", "1M",
            "--port", "0",
            "--ready_file", str(ready),
            "--hbm_preflight", "false",
        ],
        env=env, cwd=str(_REPO),
        # a file, not a pipe: nobody reads while the server runs, and a
        # child that fills an unread pipe (the params banner plus one loader
        # line per compile-cache hit is enough) blocks before it is ready
        stdout=log, stderr=subprocess.STDOUT,
    )
    log.close()  # the child holds its own descriptor
    try:
        deadline = time.monotonic() + 600
        while not ready.exists():
            assert proc.poll() is None, (
                f"serve exited rc={proc.returncode} before ready:\n"
                f"{log_path.read_text()[-4000:]}"
            )
            assert time.monotonic() < deadline, "server never became ready"
            time.sleep(0.2)
        info = json.loads(ready.read_text())
        url = f"http://{info['host']}:{info['port']}"

        # prime: the base document's rows enter the tier-2 cache
        status, _ = _post(url, _QUESTION, _DOCUMENT, timeout=120)
        assert status == 200

        # in-flight wave: a partially-hot request (shared windows hit, the
        # edit's windows miss -> queued) and an all-miss request
        results = [None, None, None]

        def worker(i, doc):
            results[i] = _post(url, _QUESTION, doc, timeout=120)

        threads = [
            threading.Thread(target=worker, args=(0, _DOCUMENT_EXT)),
            threading.Thread(target=worker, args=(1, _DOCUMENT.replace(
                "London", "Paris"))),
            # a fully-hot rider: must answer even as the drain begins
            threading.Thread(target=worker, args=(2, _DOCUMENT)),
        ]
        for t in threads:
            t.start()
        time.sleep(0.25)  # misses admitted + queued (600 ms deadline open)

        # the cache actually engaged before the signal (hit chunks offered)
        with urllib.request.urlopen(f"{url}/metrics", timeout=10) as r:
            metrics = r.read().decode()
        hits = [
            float(line.split()[-1]) for line in metrics.splitlines()
            if line.startswith("qa_chunk_cache_hits_total")
        ]
        assert hits and hits[0] >= 1, "no cache-hit chunk was in flight"

        proc.send_signal(signal.SIGTERM)
        for t in threads:
            t.join(timeout=120)
        rc = proc.wait(timeout=120)

        assert rc == 0, log_path.read_text()[-4000:]
        for status, body in results:
            assert status == 200, (status, body)
            assert body["label"], body
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
