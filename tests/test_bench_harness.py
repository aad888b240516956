"""bench.py harness units: the MFU arithmetic and what a result line may
say without a chip, plus the bench subprocess smokes below."""

import importlib.util
import json
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.unit

_BENCH = Path(__file__).resolve().parents[1] / "bench.py"


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location("bench_module", _BENCH)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench_module"] = mod
    spec.loader.exec_module(mod)
    yield mod
    sys.modules.pop("bench_module", None)


def test_mfu_fields_auditable(bench):
    """VERDICT r4 weak #5: the bench must carry model_gflops_per_example +
    mfu so the headline is auditable against chip peak. Pin the arithmetic
    at the headline shape; without a chip the field reads "not measured",
    and a TPU kind with no peak on record is an error."""
    from ml_recipe_tpu.models import MODEL_PRESETS

    cfg = MODEL_PRESETS["bert-base-uncased"]
    C, F, L, layers = 768, 3072, 512, 12
    per_token = layers * (8 * C * C + 4 * C * F + 4 * L * C)
    expect_fwd = per_token * L / 1e9
    assert bench._matmul_gflops_per_example(cfg, L, train=False) == \
        pytest.approx(expect_fwd)
    assert bench._matmul_gflops_per_example(cfg, L, train=True) == \
        pytest.approx(3 * expect_fwd)

    # 355 ex/s at the headline shape lands in a plausible MFU band vs the
    # 197 TFLOPs v5e bf16 peak (sanity: >0, <1)
    g = bench._matmul_gflops_per_example(cfg, 512, train=True)
    mfu = bench._mfu(g, 355.0, 197.0)
    assert 0.1 < mfu < 1.0
    # achieved TFLOPs / peak, exactly
    assert mfu == pytest.approx((g * 355.0 / 1e3) / 197.0, abs=1e-4)

    # no chip, no device metric: never a number, never a silent null
    cpu = {"platform": "cpu", "kind": "cpu", "count": 8}
    assert bench._chip_peak_tflops(cpu) is None
    assert bench._mfu(g, 355.0, None) == bench.NOT_MEASURED == "not measured"
    v5e = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert bench._chip_peak_tflops(v5e) == 197.0
    # an unlisted TPU generation is an error, not a ratio against the
    # wrong generation's peak
    with pytest.raises(RuntimeError, match="no bf16 peak on record"):
        bench._chip_peak_tflops(
            {"platform": "tpu", "kind": "TPU v9 hyper", "count": 1})
    # the peak table keys off device_kind substrings (review r5: a v4 run
    # must not be scored against the v5e peak)
    peaks = dict(bench.TPU_BF16_PEAK_TFLOPS)
    assert peaks["v5 lite"] == 197.0 and peaks["v4"] == 275.0


def test_widen_positions_for_long_bench(bench):
    """Long-context bench rows must run the widened-table model (the one a
    real long-context run needs), not a clamped 512-row table."""
    from ml_recipe_tpu.models import MODEL_PRESETS

    cfg = MODEL_PRESETS["bert-base-uncased"]
    assert bench._widen_positions(cfg, 512) is cfg  # within table: untouched
    wide = bench._widen_positions(cfg, 4096)
    assert wide.max_position_embeddings == 4096
    rob = MODEL_PRESETS["roberta-base"]  # offset 2, table 514
    assert bench._widen_positions(rob, 512) is rob
    assert bench._widen_positions(rob, 1024).max_position_embeddings == 1026


def test_bench_input_emits_padding_accounting_json(bench, capsys):
    """ISSUE-4 satellite: ``bench.py --mode input`` measures the host input
    pipeline in isolation (no device work) and reports both sides of the
    padding story — pad-to-max waste vs bucketed waste — so pipeline
    throughput accounting can't silently break. The synthetic NQ length
    distribution is a fixed cycle, so the ≥2x waste-reduction acceptance is
    deterministic and pinned here."""
    import types

    args = types.SimpleNamespace(
        seq_len=128,
        global_batch=8,
        input_docs=48,
        input_doc_len=400,
        infer_jobs=4,
        doc_stride=64,
        length_buckets="auto",
    )
    bench.bench_input(args)
    out = capsys.readouterr().out.strip().splitlines()
    parsed = json.loads(out[-1])  # the driver parses the last stdout line
    assert parsed["metric"] == "input_pipeline_nonpad_tokens_per_sec"
    assert parsed["unit"] == "nonpad_tokens/sec"
    assert parsed["value"] > 0
    assert parsed["nonpad_tokens_per_sec"] == parsed["value"]
    assert parsed["batches_padmax"] >= 1 and parsed["batches_bucketed"] >= 1
    # bucketed batching reports strictly less padding waste — and on the NQ
    # length mix, at least 2x less (the ISSUE acceptance criterion)
    assert 0 <= parsed["padding_waste_pct"] < parsed["padding_waste_pct_padmax"]
    assert parsed["waste_reduction_x"] >= 2.0
    assert parsed["length_buckets"][-1] == 128
    assert all(int(b) >= 1 for b in parsed["bucket_batches"].values())


def test_bench_input_length_buckets_off_skips_bucketed_pass(bench, capsys):
    import types

    args = types.SimpleNamespace(
        seq_len=128,
        global_batch=8,
        input_docs=24,
        input_doc_len=300,
        infer_jobs=4,
        doc_stride=64,
        length_buckets="off",
    )
    bench.bench_input(args)
    parsed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "padding_waste_pct_padmax" in parsed
    assert "padding_waste_pct" not in parsed  # no bucketed pass ran
    assert parsed["value"] == parsed["nonpad_tokens_per_sec_padmax"]


def test_bench_serve_emits_closed_loop_latency_json(bench, capsys):
    """ISSUE-3 satellite: ``bench.py --mode serve`` drives the serving
    engine closed-loop and emits p50/p95/p99 latency, throughput, and
    batch-occupancy in the JSON line."""
    import types

    args = types.SimpleNamespace(
        model="bert-tiny",
        serve_buckets="4x64",
        serve_clients=2,
        serve_requests=6,
        serve_queue_size=32,
        max_batch_delay_ms=5.0,
        doc_stride=32,
        ln_impl="xla",
        hbm_preflight=False,
    )
    bench.bench_serve(args)
    out = capsys.readouterr().out.strip().splitlines()
    parsed = json.loads(out[-1])  # the driver parses the last stdout line
    assert parsed["metric"] == "bert-tiny_qa_serve_p95_ms"
    assert parsed["unit"] == "ms"
    assert parsed["requests"] == 6 and parsed["failed"] == 0
    assert parsed["p50_ms"] > 0
    assert parsed["p50_ms"] <= parsed["p95_ms"] <= parsed["p99_ms"]
    assert parsed["value"] == parsed["p95_ms"]
    assert parsed["throughput_rps"] > 0
    assert parsed["batches"] >= 1
    assert 0 < parsed["batch_occupancy_mean"] <= 1
    assert 0 <= parsed["padding_waste_mean"] < 1
    assert parsed["buckets"] == ["4x64"]
    assert parsed["autotune_probes"] == 0
    # ISSUE-6: the precision provenance fields ride every serve JSON line
    # (off by default; args without the attr mean off too)
    assert parsed["quantize"] == "off"
    assert parsed["quant_mem_bytes"] is None
    assert parsed["parity_span_agreement"] is None
    assert parsed["parity_score_max_delta"] is None
    # caches off by default: no hot-set fields beyond the null provenance
    assert parsed["hot_fraction"] == 0.0
    assert parsed["chunk_cache"] is None and parsed["doc_cache"] is None
    assert parsed["chunk_cache_hit_rate"] is None


def test_bench_serve_hot_set_workload_pins_cache_win(bench, capsys):
    """ISSUE-7 acceptance: ``--mode serve`` with the hot-set workload
    (>=50% repeated question/document pairs) reports cache hit rate in the
    JSON and shows >=5x lower p50 latency for hit-served requests vs
    miss-served on CPU. The priming pass makes every hot pick a true
    repeat, so the split measures steady-state cache behavior."""
    import types

    args = types.SimpleNamespace(
        model="bert-tiny",
        serve_buckets="4x64",
        serve_clients=2,
        serve_requests=16,
        serve_queue_size=32,
        serve_hot_fraction=0.6,
        serve_hot_docs=2,
        serve_cache_bytes=1 << 20,
        doc_cache_bytes=1 << 20,
        max_batch_delay_ms=5.0,
        doc_stride=32,
        ln_impl="xla",
        hbm_preflight=False,
    )
    bench.bench_serve(args)
    out = capsys.readouterr().out.strip().splitlines()
    parsed = json.loads(out[-1])
    assert parsed["requests"] == 16 and parsed["failed"] == 0
    assert parsed["hot_fraction"] == 0.6
    assert parsed["hot_requests"] >= 1
    assert parsed["chunk_cache"]["hits"] >= parsed["hot_requests"]
    assert 0 < parsed["chunk_cache_hit_rate"] <= 1
    assert 0 < parsed["doc_cache_hit_rate"] <= 1
    # the headline cache win: hit-served p50 at least 5x below miss-served
    assert parsed["p50_hit_ms"] is not None
    assert parsed["p50_miss_ms"] is not None
    assert parsed["p50_hit_ms"] * 5 <= parsed["p50_miss_ms"], parsed


def test_bench_serve_long_request_leg_pins_longdoc_json(bench, capsys):
    """ISSUE 20 satellite: ``--mode serve`` with ``--serve_long_doc_tokens``
    drives one multi-thousand-token synthetic document through the long
    buckets after the closed loop; its sliding-window chunks scatter
    chunk-parallel across dedicated batches and the JSON line gains
    ``longdoc_chunks``/``longdoc_scatter_batches`` + longdoc p50/p95."""
    import types

    args = types.SimpleNamespace(
        model="bert-tiny",
        serve_buckets="4x64,16x64",
        serve_clients=2,
        serve_requests=4,
        serve_queue_size=256,
        serve_long_doc_tokens=2048,
        serve_long_requests=2,
        max_batch_delay_ms=5.0,
        doc_stride=32,
        ln_impl="xla",
        hbm_preflight=False,
    )
    bench.bench_serve(args)
    out = capsys.readouterr().out.strip().splitlines()
    parsed = json.loads(out[-1])
    assert parsed["requests"] == 4 and parsed["failed"] == 0
    assert parsed["longdoc_tokens"] == 2048
    # a ~2k-token document windows into dozens of chunks at seq 64
    assert parsed["longdoc_chunks"] > 16
    # ...which scatter into ceil(chunks / 16) dedicated batches — far
    # fewer launches than chunks (the chunk-parallel win)
    expected = -(-parsed["longdoc_chunks"] // 16)
    assert parsed["longdoc_scatter_batches"] == expected
    assert parsed["longdoc_p50_ms"] > 0
    assert parsed["longdoc_p50_ms"] <= parsed["longdoc_p95_ms"]
    # the leg must not perturb the headline closed-loop numbers' shape
    assert parsed["p50_ms"] > 0 and parsed["batches"] >= 1


def test_bench_fleet_pins_affinity_cache_win(bench, capsys):
    """ISSUE-18 acceptance: ``bench.py --mode fleet`` runs the SAME seeded
    zipf schedule through a consistent-hash tier and a random-routing tier
    and the doc-cache hit-rate delta rides the JSON line, pinned >= 0.1 —
    a conservative floor; with 2 engines and 8 zipf docs the analytic win
    (random routing pays one first-touch miss per engine per document,
    hashing pays one per document) lands well above it. serve_clients=1
    keeps the request order, and so both hit rates, fully deterministic."""
    import types

    args = types.SimpleNamespace(
        model="bert-tiny",
        serve_buckets="4x64",
        serve_clients=1,
        serve_requests=24,
        serve_queue_size=32,
        fleet_engines=2,
        fleet_docs=8,
        max_batch_delay_ms=5.0,
        doc_stride=32,
        ln_impl="xla",
        hbm_preflight=False,
    )
    bench.bench_fleet(args)
    out = capsys.readouterr().out.strip().splitlines()
    parsed = json.loads(out[-1])
    assert parsed["metric"] == "bert-tiny_qa_fleet_p95_ms"
    assert parsed["unit"] == "ms"
    assert parsed["value"] == parsed["hash"]["p95_ms"]
    assert parsed["engines"] == 2 and parsed["docs"] == 8
    # tier-1 doc cache defaults ON in fleet mode (the affinity target)
    assert parsed["doc_cache_bytes"] == 1 << 20
    for routing in ("hash", "random"):
        run = parsed[routing]
        assert run["routing"] == routing
        assert run["requests"] == 24 and run["failed"] == 0
        assert run["spilled"] == 0 and run["shed"] == 0
        assert run["p50_ms"] > 0
        assert run["p50_ms"] <= run["p95_ms"] <= run["p99_ms"]
        assert sum(run["per_engine_requests"].values()) == 24
        assert 0 <= run["doc_cache_hit_rate"] <= 1
    # the acceptance pin: consistent hashing beats random routing on
    # doc-cache hit rate by a margin, not a rounding error
    assert parsed["doc_cache_hit_rate_delta"] >= 0.1, parsed
    assert (parsed["hash"]["doc_cache_hit_rate"]
            > parsed["random"]["doc_cache_hit_rate"])


def test_bench_input_packed_pass_pins_waste_reduction(bench, capsys):
    """ISSUE-5 acceptance: the sequence-packed loader pass of ``bench.py
    --mode input`` on the synthetic NQ mix (the recorded 45.7% -> 12.1%
    corpus at its seq-512 shape) cuts the residual bucketed waste >= 5x.
    The absolute packed waste lands at ~2.3%: the mix's quantized 463-token
    chunks leave a 49-token hole NO chunk can fill, flooring any
    non-splitting packer around 2% — the packer itself lands under 2% on
    continuous NQ-like length mixes (pinned in test_packing.py). Everything
    here is seeded, so these numbers are deterministic."""
    import types

    args = types.SimpleNamespace(
        seq_len=512,
        global_batch=32,
        input_docs=384,
        input_doc_len=1800,
        infer_jobs=8,
        doc_stride=256,
        length_buckets="auto",
        sequence_packing="on",
        pack_max_segments=8,
        pack_splitting="off",  # this test pins the NON-splitting floor
    )
    bench.bench_input(args)
    parsed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # the recorded bucketed baseline (~12%) reproduces at this shape...
    assert 10.0 < parsed["padding_waste_pct"] < 14.0
    # ...and packing removes >= 5x of that residual waste
    assert parsed["waste_reduction_x_packed"] >= 5.0
    assert parsed["padding_waste_pct_packed"] < 3.0
    assert parsed["packing_efficiency"] >= 0.97
    assert parsed["padding_waste_pct_packed"] < parsed["padding_waste_pct"]
    # throughput/accounting fields ride along for the driver
    assert parsed["rows_per_sec_packed"] > 0
    assert parsed["nonpad_tokens_per_sec_packed"] > 0
    assert parsed["batches_packed"] >= 1
    assert parsed["pack_max_segments"] == 8


def test_bench_input_splitting_pass_pins_waste_floor_break(bench, capsys):
    """ISSUE-11 acceptance: the splitting-packer pass of ``bench.py --mode
    input`` on the synthetic NQ mix breaks the non-splitting floor — the
    mix's quantized ~463-token chunks leave 49-token holes NO whole chunk
    can fill (2.40% at HEAD), and hole-filling fragments take measured
    waste to <= 1.2%. The splitter stats (splits performed, fragment-size
    histogram, waste before/after) ride the same JSON line. Everything is
    seeded, so these numbers are deterministic."""
    import types

    args = types.SimpleNamespace(
        seq_len=512,
        global_batch=32,
        input_docs=384,
        input_doc_len=1800,
        infer_jobs=8,
        doc_stride=256,
        length_buckets="auto",
        sequence_packing="on",
        pack_max_segments=8,
        pack_splitting="fill",
        pack_min_fragment=32,
    )
    bench.bench_input(args)
    parsed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # the non-splitting pass still reports its floor (~2.4%) ...
    assert 1.6 < parsed["padding_waste_pct_packed"] < 3.0
    # ... and the splitting pass breaks it: the ISSUE-11 acceptance bar
    assert parsed["padding_waste_pct_split"] <= 1.2, parsed
    # packing_efficiency is the HONEST supervised-token ratio (ISSUE-11
    # satellite: sibling fragments' ignore-indexed tokens must not inflate
    # it) — on this mix the spans sit near chunk starts, so the small head
    # fragments carry the labels and the large unsupervised tails pull the
    # ratio well below 1-waste; it must never read as ~1.0 here
    assert 0.5 < parsed["packing_efficiency_split"] < 0.9
    assert (
        parsed["packing_efficiency_split"]
        < 1.0 - parsed["padding_waste_pct_split"] / 100.0
    )
    # splitter stats: splits happened, fragments histogrammed, before/after
    assert parsed["split_count"] > 0
    assert parsed["fragment_rows"] > 0
    assert sum(parsed["fragment_size_hist"].values()) >= parsed["split_count"]
    assert parsed["waste_before_split_pct"] == parsed["padding_waste_pct_packed"]
    assert parsed["waste_after_split_pct"] == parsed["padding_waste_pct_split"]
    assert parsed["waste_reduction_x_split"] >= 2.0
    assert parsed["pack_splitting"] == "fill"
    assert parsed["pack_min_fragment"] == 32
    # throughput/accounting fields ride along for the driver
    assert parsed["rows_per_sec_split"] > 0
    assert parsed["nonpad_tokens_per_sec_split"] > 0
    assert parsed["batches_split"] >= 1


def test_bench_input_pack_splitting_off_skips_split_pass(bench, capsys):
    import types

    args = types.SimpleNamespace(
        seq_len=128,
        global_batch=8,
        input_docs=24,
        input_doc_len=300,
        infer_jobs=4,
        doc_stride=64,
        length_buckets="off",
        sequence_packing="on",
        pack_max_segments=8,
        pack_splitting="off",
    )
    bench.bench_input(args)
    parsed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "padding_waste_pct_packed" in parsed  # packed pass still ran
    assert "padding_waste_pct_split" not in parsed
    assert "split_count" not in parsed


def test_bench_input_sequence_packing_off_skips_packed_pass(bench, capsys):
    import types

    args = types.SimpleNamespace(
        seq_len=128,
        global_batch=8,
        input_docs=24,
        input_doc_len=300,
        infer_jobs=4,
        doc_stride=64,
        length_buckets="off",
        sequence_packing="off",
        pack_max_segments=8,
    )
    bench.bench_input(args)
    parsed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "padding_waste_pct_packed" not in parsed
    assert "packing_efficiency" not in parsed


def test_param_count_probe_reports_modeled_zero1_bytes(bench, capsys):
    """ISSUE-8 satellite: ``bench.py --mode train --param_count_probe``
    reports modeled replicated-vs-zero1 optimizer bytes per chip WITHOUT
    running (or compiling) a step, at a mocked device count — the HBM
    planning that must work before a TPU window opens. The acceptance
    inequality (savings >= (N-1)/N of the sharded-leaf footprint) is
    pinned on the probe's own numbers."""
    import types

    N = 8
    args = types.SimpleNamespace(
        model="bert-tiny", seq_len=128, optimizer="adam",
        probe_devices=N, zero_min_size=0,
    )
    bench.param_count_probe(args)
    parsed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert parsed["mode"] == "param_count_probe"
    assert parsed["devices"] == N
    assert parsed["param_count"] > 0
    rep = parsed["opt_bytes_per_chip_replicated"]
    zero = parsed["opt_bytes_per_chip_zero1"]
    sharded = parsed["opt_bytes_sharded_leaves"]
    # adam: mu+nu, so the replicated state is ~2 f32 per param
    assert rep >= 8 * parsed["param_count"]
    # the acceptance inequality, with one shard-row of padding slack
    assert rep - zero >= (N - 1) / N * sharded - 0.01 * sharded
    assert parsed["zero1_savings_pct"] > 80

    # a wider mocked pod shrinks the per-chip bytes further
    args.probe_devices = 64
    bench.param_count_probe(args)
    wide = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert wide["opt_bytes_per_chip_zero1"] < zero
    assert wide["opt_bytes_per_chip_replicated"] == rep


def test_param_count_probe_adamod_carries_third_moment(bench, capsys):
    """AdaMod adds exp_avg_lr: its modeled replicated footprint must be
    ~3/2 of adam's on the same model."""
    import types

    def probe(opt):
        args = types.SimpleNamespace(
            model="bert-tiny", seq_len=128, optimizer=opt,
            probe_devices=8, zero_min_size=0,
        )
        bench.param_count_probe(args)
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    adam = probe("adam")
    adamod = probe("adamod")
    ratio = (
        adamod["opt_bytes_per_chip_replicated"]
        / adam["opt_bytes_per_chip_replicated"]
    )
    assert 1.3 < ratio < 1.7
