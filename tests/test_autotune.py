"""Geometry-autotuner tests (ops/autotune.py): on-disk cache round-trip,
corrupt-cache recovery, probe-counter semantics (a cache hit performs ZERO
compile probes), modeled-cost ranking, and CPU-fallback selection parity
with the old analytic VMEM gates."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ml_recipe_tpu.ops import autotune

pytestmark = pytest.mark.unit


@pytest.fixture
def tuner(tmp_path, monkeypatch):
    """Fresh autotuner on a per-test cache dir, device-kind pinned so the
    cache partition is deterministic."""
    at = autotune.reset()
    at.set_cache_dir(tmp_path / "tuning")
    monkeypatch.setattr(autotune, "_device_kind", lambda: "FakeTPU v0")
    yield at
    autotune.reset()


def _fake_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _select(at, *, probe=None, analytic=None, interpret=False,
            regime="fused_bwd", candidates=(12, 6, 4, 2), dropout=False):
    return at.select(
        regime, L=512, H=12, D=64, in_dtype="bfloat16", out_dtype="bfloat16",
        dropout=dropout, candidates=list(candidates),
        cost=lambda hc: 12 // hc, probe=probe, analytic=analytic,
        interpret=interpret,
    )


def test_probe_rank_order_and_winner(tuner, monkeypatch):
    """Candidates are probed in ascending modeled-cost order; the first
    that compiles wins (it is the model-optimal legal geometry)."""
    _fake_tpu(monkeypatch)
    probed = []

    def probe(hc):
        probed.append(hc)
        return hc <= 6  # pretend only hc<=6 lowers

    assert _select(tuner, probe=probe) == 6
    assert probed == [12, 6]  # cost order, stopped at first legal
    assert tuner.probe_count == 2


def test_cache_round_trip_zero_probes_on_second_invocation(
    tuner, tmp_path, monkeypatch,
):
    """Acceptance: a second invocation at the same key — even from a fresh
    process (fresh autotuner, same disk cache) — performs zero compile
    probes and reports a cache hit."""
    _fake_tpu(monkeypatch)
    assert _select(tuner, probe=lambda hc: hc <= 4) == 4
    assert tuner.probe_count == 3
    cache_file = tuner._cache_file("FakeTPU v0")
    assert cache_file.exists()
    payload = json.loads(cache_file.read_text())
    assert payload["version"] == 1
    (entry,) = payload["entries"].values()
    assert entry == {"geometry": 4, "source": "probe"}

    # same process, same key: memory hit
    assert _select(tuner, probe=lambda hc: pytest.fail("probed on hit")) == 4
    assert tuner.probe_count == 3 and tuner.hits == 1
    assert tuner.session_summary()["cache"] == "miss"  # first decision probed

    # "new process": fresh autotuner over the same disk cache
    fresh = autotune.GeometryAutotuner(cache_dir=tuner.cache_dir)
    assert _select(fresh, probe=lambda hc: pytest.fail("probed on hit")) == 4
    assert fresh.probe_count == 0 and fresh.hits == 1
    assert fresh.session_summary()["cache"] == "hit"


def test_tuple_geometry_and_none_verdict_policies(tuner, monkeypatch):
    """(q_blk, hc) tuples survive the JSON round trip; the 'no legal
    candidate' verdict is SESSION-ONLY — served from memory within the
    process (no duplicate probe walks) but never persisted, because a
    transient probe-environment failure (host OOM classified as
    candidate-infeasible) must not permanently route the shape off-kernel."""
    _fake_tpu(monkeypatch)
    cands = [(512, 12), (512, 6), (256, 12)]
    got = tuner.select(
        "blocked_fwd", L=1024, H=12, D=64, in_dtype="bf16", out_dtype="bf16",
        dropout=False, candidates=cands,
        cost=lambda g: (1024 // g[0]) * (12 // g[1]),
        probe=lambda g: g == (256, 12),
    )
    assert got == (256, 12)

    def select_stream(at, probe):
        return at.select(
            "stream", L=4096, H=12, D=64, in_dtype="bf16", out_dtype="bf16",
            dropout=False, candidates=cands,
            cost=lambda g: (4096 // g[0]) * (12 // g[1]), probe=probe,
        )

    assert select_stream(tuner, lambda g: False) is None
    # in-process: the None verdict IS served (no duplicate walk)...
    assert select_stream(
        tuner, lambda g: pytest.fail("re-probed in-process")
    ) is None

    fresh = autotune.GeometryAutotuner(cache_dir=tuner.cache_dir)
    assert fresh.select(
        "blocked_fwd", L=1024, H=12, D=64, in_dtype="bf16", out_dtype="bf16",
        dropout=False, candidates=cands,
        cost=lambda g: (1024 // g[0]) * (12 // g[1]),
        probe=lambda g: pytest.fail("probed on hit"),
    ) == (256, 12)
    assert fresh.probe_count == 0
    # ...but a fresh process re-probes the None verdict (not on disk)
    reprobed = []
    assert select_stream(
        fresh, lambda g: reprobed.append(g) or False
    ) is None
    assert len(reprobed) == len(cands)


def test_corrupt_cache_recovery(tuner, monkeypatch):
    """A truncated/garbage cache file degrades to re-probing (with a
    warning), never to a crash — and the next winner rewrites it valid."""
    _fake_tpu(monkeypatch)
    cache_file = tuner._cache_file("FakeTPU v0")
    cache_file.parent.mkdir(parents=True, exist_ok=True)
    cache_file.write_text('{"version": 1, "entries": {trunca')  # torn write

    probed = []
    assert _select(tuner, probe=lambda hc: probed.append(hc) or True) == 12
    assert probed == [12]  # cache unreadable -> really probed
    # rewritten valid
    payload = json.loads(cache_file.read_text())
    assert list(payload["entries"].values())[0]["geometry"] == 12

    # schema-invalid entries are dropped on load, valid ones kept
    key = list(payload["entries"])[0]
    payload["entries"]["bogus"] = {"geometry": "not-a-geometry"}
    cache_file.write_text(json.dumps(payload))
    fresh = autotune.GeometryAutotuner(cache_dir=tuner.cache_dir)
    assert _select(fresh, probe=lambda hc: pytest.fail("valid entry lost")) == 12
    assert key in fresh._entries["FakeTPU v0"]
    assert "bogus" not in fresh._entries["FakeTPU v0"]


def test_probe_exception_propagates_and_caches_nothing(tuner, monkeypatch):
    """A probe that raises (unclassified compile error at the conservative
    pick — a genuine kernel bug) must propagate, and the poisoned key must
    NOT be cached as a verdict."""
    _fake_tpu(monkeypatch)

    def probe(hc):
        raise RuntimeError("genuine kernel bug")

    with pytest.raises(RuntimeError, match="genuine kernel bug"):
        _select(tuner, probe=probe)
    assert not tuner._entries.get("FakeTPU v0")


def test_cpu_takes_analytic_and_caches(tuner):
    """Off-TPU the probe must never run; the analytic pick is returned,
    cached, and served as a hit on the second lookup."""
    assert _select(
        tuner,
        probe=lambda hc: pytest.fail("probed on cpu"),
        analytic=lambda: 6,
    ) == 6
    assert tuner.probe_count == 0 and tuner.misses == 1
    assert _select(
        tuner,
        probe=lambda hc: pytest.fail("probed on cpu"),
        analytic=lambda: pytest.fail("analytic re-ran on hit"),
    ) == 6
    assert tuner.hits == 1


def test_probe_capable_lookup_upgrades_analytic_entries(tuner, monkeypatch):
    """An interpret-mode run on a TPU host caches ANALYTIC picks under the
    hardware device kind; a later compiled (probe-capable) run must NOT
    serve them as hits — it re-selects via probe and overwrites, otherwise
    the unvalidated arithmetic is back in charge on hardware."""
    # interpret on the "TPU": analytic source, cached
    _fake_tpu(monkeypatch)
    assert _select(tuner, probe=lambda hc: pytest.fail("probed interpret"),
                   analytic=lambda: 12, interpret=True) == 12
    # compiled lookup at the same key: must probe, not trust the entry
    probed = []
    assert _select(tuner, probe=lambda hc: probed.append(hc) or hc <= 6) == 6
    assert probed == [12, 6]
    # ...and the upgraded probe verdict now serves compiled hits
    assert _select(tuner, probe=lambda hc: pytest.fail("probed on hit")) == 6


def test_cache_invalidated_on_toolchain_change(tuner, monkeypatch):
    """Probe verdicts must not outlive the jax/jaxlib pair that issued them:
    a cache written by another toolchain is ignored and re-probed."""
    _fake_tpu(monkeypatch)
    assert _select(tuner, probe=lambda hc: hc <= 6) == 6
    cache_file = tuner._cache_file("FakeTPU v0")
    payload = json.loads(cache_file.read_text())
    assert payload["toolchain"] == autotune._toolchain()
    payload["toolchain"] = "jax-0.0.1+jaxlib-0.0.1"
    cache_file.write_text(json.dumps(payload))

    fresh = autotune.GeometryAutotuner(cache_dir=tuner.cache_dir)
    probed = []
    assert _select(fresh, probe=lambda hc: probed.append(hc) or hc <= 6) == 6
    assert probed == [12, 6]  # stale-toolchain entries were dropped


def test_disabled_bypasses_cache_entirely(tuner, monkeypatch):
    """--autotune off: pure analytic gating, no probes, no cache I/O."""
    _fake_tpu(monkeypatch)
    tuner.enabled = False
    assert _select(
        tuner, probe=lambda hc: pytest.fail("probed while disabled"),
        analytic=lambda: 2,
    ) == 2
    assert tuner.probe_count == 0
    assert not tuner._cache_file("FakeTPU v0").exists()
    assert tuner.session_summary()["cache"] == "disabled"


def test_cpu_selection_parity_with_old_analytic_gates(tuner):
    """CPU fallback: the autotuned geometry selectors must agree EXACTLY
    with the pre-autotuner analytic cfg functions across the shipped
    geometry grid (tier-1 runs on CPU — selection there must not move)."""
    from ml_recipe_tpu.ops import flash_attention as fa
    from ml_recipe_tpu.ops import flash_streaming as fs

    for L in (1024, 2048, 3072, 4096):
        for isz, dt in ((2, jnp.bfloat16), (4, jnp.float32)):
            for rate in (0.0, 0.1):
                assert fa._blocked_fwd_geometry(
                    L, 12, 64, dt, dt, rate
                ) == fa._blocked_fwd_cfg(L, 12, 64, isz, isz, rate), (
                    L, isz, rate, "blocked_fwd")
                assert fa._blocked_bwd_geometry(
                    L, 12, 64, dt, rate, out_dtype=dt
                ) == fa._blocked_bwd_cfg(L, 12, 64, isz, rate,
                                         out_itemsize=isz), (
                    L, isz, rate, "blocked_bwd")
                assert fs._streaming_geometry(
                    L, 12, 64, dt, dt, rate
                ) == fs.streaming_cfg(L, 12, 64, isz, isz, rate), (
                    L, isz, rate, "stream")
    # fused forward: selection equals the old _pick_head_chunk arithmetic
    for L in (128, 256, 512):
        for want_lse in (False, True):
            hc = fa._fused_fwd_hc(1, L, 12, 64, jnp.bfloat16, jnp.int32,
                                  jnp.bfloat16, 0.0, want_lse, False)
            assert hc == fa._fused_fwd_analytic_hc(L, 12, 64, 2, 2, want_lse)
    # fused backward off-TPU: the aggressive-budget arithmetic, as before
    hc = fa._fused_bwd_hc(4, 512, 12, 64, jnp.bfloat16, jnp.int32,
                          jnp.bfloat16, 0.0, interpret=True)
    assert hc == fa._pick_head_chunk(
        12, 64,
        bytes_per_head=fa._fused_bwd_bytes_per_head(512, 64, 2, 2),
        temp_bytes=fa._FUSED_BWD_TEMPS * 512 * 512 * 4,
        budget=fa._fused_bwd_budget(),
    )


def test_tuning_cache_smoke_end_to_end(tuner):
    """Tier-1 smoke (ISSUE 2 satellite): a real flash_attention dispatch on
    the CPU mesh populates the tuning cache through the selection path
    (analytic source off-TPU, zero probes), and the second call hits."""
    from ml_recipe_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 1024, 2, 64)),
                           dtype=jnp.float32) for _ in range(3))
    out = flash_attention(q, k, v, None, dtype=jnp.float32, interpret=True)
    assert out.shape == (1, 1024, 2, 64)
    assert tuner.probe_count == 0
    assert tuner._cache_file("FakeTPU v0").exists()
    decisions = tuner.session_summary()["decisions"]
    assert any(d["regime"] == "blocked_fwd" for d in decisions.values())

    flash_attention(q, k, v, None, dtype=jnp.float32, interpret=True)
    assert tuner.hits >= 1


# ---------------------------------------------------------------------------
# timing-ranked selection (ROADMAP raw-speed item b): probes that hand back
# their compiled objects opt into cost_analysis ranking
# ---------------------------------------------------------------------------


class _FakeCompiled:
    """A compiled-program stand-in exposing XLA's cost_analysis dict."""

    def __init__(self, flops, byts, as_list=False):
        self._ca = {"flops": float(flops), "bytes accessed": float(byts)}
        self._as_list = as_list

    def cost_analysis(self):
        return [self._ca] if self._as_list else self._ca


def test_measured_ranking_overrides_prior(tuner, monkeypatch):
    """When every legal candidate carries a compiled-cost estimate, the
    winner is the MEASURED-cheapest one even when the analytic prior ranks
    another first — and the ranking signal persists in the cache JSON."""
    _fake_tpu(monkeypatch)
    probed = []
    # prior order is [12, 6, 4, 2]; measured cost says hc=4 is cheapest
    est_bytes = {12: 9e9, 6: 6e9, 4: 1e9, 2: 5e9}

    def probe(hc):
        probed.append(hc)
        return _FakeCompiled(flops=1e9, byts=est_bytes[hc],
                             as_list=(hc == 6))  # list-form tolerated

    assert _select(tuner, probe=probe) == 4
    assert probed == [12, 6, 4, 2]  # ranking probes ALL candidates
    assert tuner.probe_count == 4

    payload = json.loads(tuner._cache_file("FakeTPU v0").read_text())
    (entry,) = payload["entries"].values()
    assert entry["geometry"] == 4
    assert entry["ranking"] == "measured"
    assert set(entry["cost_estimates"]) == {"12", "6", "4", "2"}
    assert entry["cost_estimates"]["4"]["bytes_accessed"] == 1e9
    assert entry["cost_estimates"]["4"]["est_seconds"] > 0

    # the measured verdict round-trips the disk cache: fresh process, zero
    # probes, same winner
    fresh = autotune.GeometryAutotuner(cache_dir=tuner.cache_dir)
    assert _select(fresh, probe=lambda hc: pytest.fail("probed on hit")) == 4
    assert fresh.probe_count == 0


def test_ranking_probe_failures_are_best_effort(tuner, monkeypatch):
    """Once a legal winner exists, a ranking probe that raises is skipped
    (logged), never fatal — the legacy safety contract only covers the walk
    UP TO the first legal candidate."""
    _fake_tpu(monkeypatch)

    def probe(hc):
        if hc == 6:
            raise RuntimeError("transient probe-environment failure")
        return _FakeCompiled(flops=1e9, byts={12: 2e9, 4: 8e9, 2: 9e9}[hc])

    assert _select(tuner, probe=probe) == 12  # measured-cheapest survivor
    entry = list(tuner._entries["FakeTPU v0"].values())[0]
    assert entry["ranking"] == "measured"
    assert set(entry["cost_estimates"]) == {"12", "4", "2"}


def test_bool_probes_keep_first_legal_contract(tuner, monkeypatch):
    """A probe returning bare True (no compiled object) keeps the legacy
    first-legal-wins semantics: the walk stops, no ranking keys appear in
    the cache entry."""
    _fake_tpu(monkeypatch)
    probed = []
    assert _select(tuner, probe=lambda hc: probed.append(hc) or True) == 12
    assert probed == [12]
    (entry,) = tuner._entries["FakeTPU v0"].values()
    assert entry == {"geometry": 12, "source": "probe"}


def test_estimate_extraction_is_best_effort(tuner, monkeypatch):
    """A compiled object whose cost_analysis raises or reports nothing
    degrades to first-legal-wins instead of crashing the selection."""
    _fake_tpu(monkeypatch)

    class _Broken:
        def cost_analysis(self):
            raise RuntimeError("not supported on this backend")

    probed = []
    assert _select(tuner, probe=lambda hc: probed.append(hc) or _Broken()
                   ) == 12
    assert probed == [12]  # no estimate -> stop at first legal
    assert autotune._cost_estimate(_Broken()) is None
    assert autotune._cost_estimate(object()) is None
    assert autotune._cost_estimate(
        _FakeCompiled(flops=0.0, byts=0.0)) is None


# ---------------------------------------------------------------------------
# wall-clock probe timing (ROADMAP raw-speed item b, the measured tier):
# compiled probes that EXECUTE are timed, probe_ms persists per candidate,
# and timings outrank the cost estimates which outrank the analytic prior
# ---------------------------------------------------------------------------


class _FakeTimedCompiled(_FakeCompiled):
    """A compiled-program stand-in that also EXECUTES: args_info says
    'no arguments' and __call__ burns a deterministic wall-clock cost."""

    def __init__(self, flops, byts, ms):
        super().__init__(flops, byts)
        self.ms = float(ms)
        self.args_info = ()
        self.calls = 0

    def __call__(self):
        import time

        self.calls += 1
        time.sleep(self.ms / 1e3)
        return np.zeros(())


def test_timed_ranking_overrides_cost_estimates(tuner, monkeypatch):
    """When every legal candidate's compiled probe executes, the winner is
    the wall-clock FASTEST one — even when both the analytic prior and the
    cost_analysis estimates rank others first — and per-candidate probe_ms
    persists in the tuning-cache JSON next to the estimates."""
    _fake_tpu(monkeypatch)
    # prior order is [12, 6, 4, 2]; estimates say 4 is cheapest; the
    # wall clock says 2 is fastest — the wall clock must win
    est_bytes = {12: 9e9, 6: 6e9, 4: 1e9, 2: 5e9}
    sleep_ms = {12: 6.0, 6: 4.0, 4: 3.0, 2: 0.5}
    fakes = {
        hc: _FakeTimedCompiled(1e9, est_bytes[hc], sleep_ms[hc])
        for hc in est_bytes
    }

    assert _select(tuner, probe=lambda hc: fakes[hc]) == 2
    # warmup + _PROBE_TIME_REPEATS timed runs per candidate
    assert all(
        f.calls == 1 + autotune._PROBE_TIME_REPEATS for f in fakes.values()
    )

    payload = json.loads(tuner._cache_file("FakeTPU v0").read_text())
    (entry,) = payload["entries"].values()
    assert entry["geometry"] == 2
    assert entry["ranking"] == "timed"
    assert set(entry["cost_estimates"]) == {"12", "6", "4", "2"}
    for key, est in entry["cost_estimates"].items():
        assert est["probe_ms"] > 0, key
        assert est["est_seconds"] > 0, key  # estimates still ride along
    # the fastest candidate really carries the smallest persisted timing
    assert min(
        entry["cost_estimates"], key=lambda k: entry["cost_estimates"][k]["probe_ms"]
    ) == "2"

    # acceptance: warm restart (fresh process over the same disk cache)
    # performs ZERO probes and serves the timed winner
    fresh = autotune.GeometryAutotuner(cache_dir=tuner.cache_dir)
    assert _select(fresh, probe=lambda hc: pytest.fail("probed on hit")) == 2
    assert fresh.probe_count == 0


def test_timing_unavailable_falls_back_to_cost_estimates(tuner, monkeypatch):
    """One candidate whose compiled probe cannot execute (no args_info —
    e.g. a device-resident program on a probe-only host) withdraws the
    whole timing tier: ranking falls back to the cost estimates, with no
    partial probe_ms keys (mixing timed and estimated candidates would
    compare incomparable units)."""
    _fake_tpu(monkeypatch)
    est_bytes = {12: 9e9, 6: 6e9, 4: 1e9, 2: 5e9}

    def probe(hc):
        if hc == 6:  # this one doesn't execute
            return _FakeCompiled(1e9, est_bytes[hc])
        return _FakeTimedCompiled(1e9, est_bytes[hc], ms=0.5)

    assert _select(tuner, probe=probe) == 4  # estimate-cheapest
    (entry,) = tuner._entries["FakeTPU v0"].values()
    assert entry["ranking"] == "measured"
    assert all("probe_ms" not in est for est in entry["cost_estimates"].values())


def test_time_compiled_unit():
    """_time_compiled: real compiled jax programs time (zero-filled args
    from their own args_info), non-executable objects return None, and
    combined multi-leg candidates sum their legs."""
    import jax
    import jax.numpy as jnp

    compiled = jax.jit(lambda x: x * 2).lower(jnp.zeros((8,))).compile()
    ms = autotune._time_compiled(compiled, repeats=2)
    assert ms is not None and ms >= 0

    assert autotune._time_compiled(object()) is None
    assert autotune._time_compiled(_FakeCompiled(1e9, 1e9)) is None

    a = _FakeTimedCompiled(1e9, 1e9, ms=1.0)
    b = _FakeTimedCompiled(1e9, 1e9, ms=2.0)
    combined = autotune._CombinedCompiled([a, b])
    total = autotune._time_compiled(combined, repeats=1)
    assert total is not None and total >= 2.5  # ~1ms + ~2ms of sleeps
    # one leg that cannot execute poisons the combined timing
    assert autotune._time_compiled(
        autotune._CombinedCompiled([a, _FakeCompiled(1e9, 1e9)])
    ) is None


def test_combine_for_ranking_sums_legs():
    """Multi-program candidates (streaming fwd + dkv) rank by the SUM of
    their legs' estimates; any falsy leg fails the candidate and any
    estimate-less leg withdraws the estimate (prior ranking then applies)."""
    a = _FakeCompiled(flops=1e9, byts=2e9)
    b = _FakeCompiled(flops=3e9, byts=4e9, as_list=True)
    combined = autotune.combine_for_ranking(a, b)
    est = autotune._cost_estimate(combined)
    assert est["flops"] == 4e9 and est["bytes_accessed"] == 6e9

    assert autotune.combine_for_ranking(a, False) is False
    assert autotune.combine_for_ranking() is False

    class _NoCost:
        pass

    assert autotune._cost_estimate(
        autotune.combine_for_ranking(a, _NoCost())) is None
