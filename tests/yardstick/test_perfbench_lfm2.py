"""The benchmark's files for ``lfm2-8b-a1b-ep4`` and its cell, off the chip:
the configuration's keys against the catalog's row, the preset against the
file, the manifest's new entries, the FLOP and byte functions against
hand-worked values, the trace readers on hand-made events, on a trace
recorded on the chip and on the other configurations' traces, the
convolution's own comparison, the runner that binds the configuration's
comparison, the cell's ``--rehearse`` run."""

import importlib
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from perfbench.harness import flops_lfm2, lfm2_trace, manifest  # noqa: E402
from perfbench.harness import trace_reduce as tr  # noqa: E402
from perfbench.harness.result import read_per_layer  # noqa: E402

BENCH = REPO / "perfbench"
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "lfm2-ep4-train-seq8192"
CONFIG = json.loads((BENCH / "configs" / "lfm2-8b-a1b-ep4.json").read_text())
NEW_READERS = (
    "lfm2_mfu_pct", "conv_block_ms_step", "short_conv_ms_step",
    "short_conv_roofline", "gqa_attn_ms_step", "gqa_attn_roofline",
    "lfm2_moe_ms_step", "lfm2_moe_dispatch_ms_step", "lfm2_expert_roofline",
    "lfm2_moe_load_max_pct")
OLDER_CELLS = ["base-train-full512", "large-train-dp4",
               "joyai-ep16-train-seq4096"]
PERIOD = ["full_attention", "conv", "conv", "conv"]

# the `config` of the catalog's row LFM2-8B-A1B (model-configs guide,
# architectures.jsonl; source_url as the configuration's `source`)
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": ["conv", "conv"] + PERIOD * 4 + [
        "full_attention", "conv", "conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}
WIDTH = re.compile(      # what `reduced` may never name
    r"(_dim|_rank)$|(hidden|intermediate|latent|state|proj\w*)_size|head"
    r"|expan|experts_per_tok")
SOURCE = "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"


# -- the configuration ---------------------------------------------------------------

def test_configuration_keys_against_the_catalog_row():
    reduced = CONFIG["reduced"]
    assert reduced == ["num_hidden_layers", "num_dense_layers", "layer_types",
                       "num_experts", "vocab_size"]
    assert len(CATALOG["layer_types"]) == 24
    assert CATALOG["layer_types"].count("full_attention") == 6
    for key, published in CATALOG.items():
        assert key in CONFIG, key
        if key in reduced:
            assert CONFIG[key] != published, key
            assert CONFIG["published"][key] == published
        else:
            assert CONFIG[key] == published, key
            assert type(CONFIG[key]) is type(published), key
    assert not [k for k in reduced if WIDTH.search(k)]
    assert CONFIG["source"] == SOURCE
    # the floors: the leading dense layer once, then a whole period of the
    # pattern and at least four layers, at least eight experts, at least an
    # eighth of the vocabulary
    kinds = CONFIG["layer_types"]
    assert len(kinds) == CONFIG["num_hidden_layers"]
    after_dense = kinds[CONFIG["num_dense_layers"]:]
    assert len(after_dense) >= 4 and after_dense == PERIOD
    assert kinds[:CONFIG["num_dense_layers"]] == ["conv"]   # as published
    assert CATALOG["layer_types"][1:6] == kinds             # layers 1..5
    assert CONFIG["num_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= CATALOG["vocab_size"]
    assert CONFIG["experts_held"] == {
        "first": 0, "count": CONFIG["num_experts"],
        "of": CATALOG["num_experts"]}
    for said in ("deployment", "assumed", "not_built", "published"):
        assert CONFIG[said], said
    assert "4 chips share each layer" in CONFIG["deployment"]
    assert set(CONFIG["not_built"]) == {"lm_head"}
    assert {"weights", "head_dim", "rope", "expert_bias", "vocabulary",
            "qa_heads", "precision"} <= set(CONFIG["assumed"])
    assert CONFIG["head_dim"] * CONFIG["num_attention_heads"] == \
        CONFIG["hidden_size"]


def test_the_preset_is_the_configuration_file():
    from ml_recipe_tpu.models.config import MODEL_PRESETS

    preset = MODEL_PRESETS[CONFIG["model"]]
    same = {"hidden_size": "hidden_size", "num_hidden_layers": "num_layers",
            "num_attention_heads": "num_heads",
            "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
            "vocab_size": "vocab_size",
            "intermediate_size": "intermediate_size",
            "moe_intermediate_size": "moe_intermediate_size",
            "num_dense_layers": "first_k_dense_replace",
            "num_experts_per_tok": "num_experts_per_tok",
            "norm_topk_prob": "norm_topk_prob",
            "routed_scaling_factor": "routed_scaling_factor",
            "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
            "conv_L_cache": "conv_L_cache",
            "initializer_range": "initializer_range",
            "expert_bias_range": "expert_bias_range",
            "model_type": "model_type"}
    for key, field in same.items():
        assert getattr(preset, field) == CONFIG[key], key
    assert list(preset.layer_types) == CONFIG["layer_types"]
    held = CONFIG["experts_held"]
    assert (preset.experts_first, preset.experts_held,
            preset.n_routed_experts) == (held["first"], held["count"],
                                         held["of"])
    assert preset.n_shared_experts == 0 and preset.qk_norm
    assert not preset.rope_interleaved and preset.norm_topk_eps == 1e-6
    assert preset.hidden_dropout_prob == 0.0
    assert preset.attention_probs_dropout_prob == 0.0


def test_the_manifest_gained_the_cell_at_the_end_of_each_list():
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert list(cells)[:3] == OLDER_CELLS and list(cells)[3] == CELL
    assert cells[CELL] == {
        "name": CELL, "config": "lfm2-8b-a1b-ep4", "traffic": "full8192-ep4",
        "chips": 1, "why": cells[CELL]["why"]}
    assert "1/4" in cells[CELL]["why"] and "4x" in cells[CELL]["why"]
    assert len(cells[CELL]["why"]) <= 200
    entry = MANIFEST["configs"][3]
    assert entry["name"] == "lfm2-8b-a1b-ep4"
    assert entry["file"] == "perfbench/configs/lfm2-8b-a1b-ep4.json"
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"] == SOURCE
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index("moe_load_max_over_mean") + 1      # the last before
    assert tuple(names[at:at + 10]) == NEW_READERS
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    layers = {m["layer"] for m in MANIFEST["per_layer"][:at]}
    for name in NEW_READERS:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s_chip"
        assert m["unit"] in ("%", "ms") and m["layer"] in layers
        assert ("roofline" in name or "mfu" in name or "pct" in name) == (
            m["unit"] == "%")
        assert (BENCH / "metrics" / f"{name}.py").is_file()
    # no older metric's list took the new cell
    for m in MANIFEST["per_layer"][:at]:
        assert CELL not in m.get("workloads", [])
    job = manifest.load_cell(CELL).traffic["job"]
    flags = job["flags"]
    assert (flags["max_seq_len"], flags["train_batch_size"],
            flags["batch_split"], flags["remat"], flags["hbm_preflight"],
            flags["max_question_len"], flags["length_buckets"]) == (
        8192, 4, 2, False, True, 64, "off")
    assert (job["mesh"], job["trainer_seed"], job["rows"]) == (
        "data:1", 0, 1000000)
    other = manifest.load_cell("joyai-ep16-train-seq4096").traffic["job"]
    for key in ("warmup_batches", "trace_batches", "trace_seconds"):
        assert job[key] == other[key], key


# -- FLOPs and bytes, worked by hand ---------------------------------------------------

def test_flops_a_token_at_the_published_widths():
    cfg = CONFIG
    assert flops_lfm2.conv_projection_flops(cfg) == 2 * (
        2048 * 6144 + 2048 * 2048) == 33_554_432
    assert flops_lfm2.attention_projection_flops(cfg) == 2 * (
        2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048) == 20_971_520
    core = flops_lfm2.causal_core_flops(cfg, 1, 8192, train=False) / 8192
    assert core == 2 * 32 * (8193 / 2) * 128 == pytest.approx(
        33.56e6, rel=1e-3)
    assert flops_lfm2.swiglu_flops(2048, 7168) == 88_080_384
    assert flops_lfm2.swiglu_flops(2048, 1792) == 22_020_096
    assert flops_lfm2.held_per_token_expected(cfg) == 1.0
    fwd = flops_lfm2.matmul_flops_per_token(cfg, 8192, train=False)
    moe = 2 * 2048 * 32 + 1.0 * 22_020_096
    assert fwd == pytest.approx(
        4 * 33_554_432 + (20_971_520 + core) + 88_080_384 + 4 * moe)
    assert fwd == pytest.approx(365.4e6, rel=1e-3)
    assert flops_lfm2.matmul_flops_per_token(
        cfg, 8192, train=True) == pytest.approx(3 * fwd)
    # the counter's reading moves only the routed experts' term
    more = flops_lfm2.matmul_flops_per_token(
        cfg, 8192, train=False, held_per_token=1.5)
    assert more - fwd == pytest.approx(4 * 0.5 * 22_020_096)


def test_causal_core_short_conv_and_grouped_matmul_bytes_and_flops():
    cfg = CONFIG
    rows, L = 2, 8192
    assert flops_lfm2.causal_core_flops(cfg, rows, L, train=True) == \
        3 * flops_lfm2.causal_core_flops(cfg, rows, L, train=False)
    # q and the context a query head, k and v a key/value head, in bf16
    fwd = (2 * 32 + 2 * 8) * 64 * 2
    assert flops_lfm2.causal_core_bytes(cfg, rows, L, train=False) == \
        rows * L * fwd == rows * L * 10_240
    # + q, context, its cotangent, dq (32 heads); k, v, dk, dv (8 heads)
    assert flops_lfm2.causal_core_bytes(cfg, rows, L, train=True) == \
        rows * L * (fwd + (4 * 32 + 4 * 8) * 64 * 2) == rows * L * 30_720
    # the gating and taps: 16 KB forward, 28 KB backward a token and layer
    assert flops_lfm2.short_conv_bytes(cfg, 1, train=False) == \
        (3 + 1) * 2048 * 2 == 16_384
    assert flops_lfm2.short_conv_bytes(cfg, 1, train=True) == \
        16_384 + (3 + 1 + 3) * 2048 * 2 == 45_056
    # a step of the cell: 4 conv layers x 32,768 tokens = 5.9 GB, 7.2 ms
    step = 4 * flops_lfm2.short_conv_bytes(cfg, 32_768, train=True)
    assert step == pytest.approx(5.906e9, rel=1e-3)
    assert step / 819e9 == pytest.approx(7.21e-3, rel=1e-3)
    assert flops_lfm2.short_conv_flops(cfg, 1, train=False) == 7 * 2048
    assert flops_lfm2.short_conv_flops(cfg, 1, train=True) == 22 * 2048
    assert flops_lfm2.grouped_matmul_flops(cfg, 1000, train=True) == \
        3 * 1000 * 22_020_096
    weights = 8 * 3 * 2048 * 1792 * 2
    assert flops_lfm2.grouped_matmul_bytes(cfg, 1000, 4, train=False) == \
        4 * weights + 1000 * (2 * 2048 + 4 * 1792) * 2
    # 2,048 rows an expert: compute-bound, well over the chip's ridge
    held, calls = 16_384.0, 1
    intensity = flops_lfm2.grouped_matmul_flops(cfg, held, train=True) \
        / flops_lfm2.grouped_matmul_bytes(cfg, held, calls, train=True)
    assert intensity > 197e12 / 819e9


# -- the trace readers -----------------------------------------------------------------

BODY = "jit(train_step)/while/body/closed_call/forward_backward"


@pytest.mark.parametrize("op_name,want", [
    (f"{BODY}/jvp(QAModel)/transformer/layer_0/conv/in_proj/dot_general",
     "conv"),
    (f"{BODY}/transpose(jvp(QAModel))/transformer/layer_3/conv/out_proj/"
     "transpose", "conv"),
    (f"{BODY}/jvp(QAModel)/transformer/layer_2/conv/short_conv/mul",
     "short_conv"),
    (f"{BODY}/transpose(jvp(QAModel))/transformer/layer_4/conv/short_conv/"
     "jit(_pad)/pad", "short_conv"),
    (f"{BODY}/jvp(QAModel)/transformer/layer_2/mlp/router/dot_general",
     "router"),
    (f"{BODY}/transpose(jvp(QAModel))/transformer/layer_1/mlp/experts/remat/"
     "mul", "experts"),
    (f"{BODY}/jvp(QAModel)/transformer/layer_4/mlp/dispatch/sort",
     "dispatch"),
    (f"{BODY}/jvp(QAModel)/transformer/layer_1/mlp/combine/convert", "combine"),
    (f"{BODY}/jvp(QAModel)/transformer/layer_1/mlp/reshape", "other"),
    # the leading dense layer's FFN is no expert layer, attention is the
    # shared readers'
    (f"{BODY}/jvp(QAModel)/transformer/layer_0/mlp/gate/dot_general", "rest"),
    (f"{BODY}/jvp(QAModel)/transformer/layer_1/attention/q/dot_general",
     "rest"),
    # a conv_general_dilated of some other program is no conv module
    ("jit(f)/jvp(M)/conv_general_dilated", "rest"),
    ("jit(train_step)/optimizer/add", "rest"),
    (None, "rest"),
])
def test_labels_by_scope(op_name, want):
    assert lfm2_trace.label("%fusion.7", op_name, 1) == want


def test_kernels_are_told_by_name_whatever_their_scope():
    assert lfm2_trace.label("%flash_causal_fwd.3", None, 1) == "causal_kernels"
    assert lfm2_trace.label("%flash_causal_bwd.1", None, 1) == "causal_kernels"
    assert lfm2_trace.label("%ragged-dot-none.7", None, 1) == "experts"


def test_hand_made_events_reduce_to_parts():
    scope = {
        "%fusion.1": f"{BODY}/jvp(QAModel)/transformer/layer_1/mlp/router/dot",
        "%fusion.2": f"{BODY}/jvp(QAModel)/transformer/layer_0/conv/"
                     "short_conv/mul",
        "%fusion.3": f"{BODY}/jvp(QAModel)/transformer/layer_0/conv/in_proj/"
                     "dot_general",
        "%fusion.4": f"{BODY}/jvp(QAModel)/transformer/layer_0/mlp/gate/dot",
        "%while.1": "jit(train_step)/while",
    }
    ops = {0: [("%while.1", 0, 1000), ("%fusion.1", 0, 100),
               ("%flash_causal_fwd.3", 100, 300),
               ("%ragged-dot-metadata.1", 300, 310),
               ("%ragged-dot-none.2", 310, 400), ("%fusion.2", 400, 450),
               ("%flash_causal_bwd.1", 450, 650), ("%fusion.3", 650, 700),
               ("%fusion.4", 700, 720)]}
    modules = {0: [("jit_train_step(7)", 0, 1000)]}
    found = lfm2_trace.reduce(ops, modules, (0, 1000), 2,
                              lambda program: scope, 1)
    to_ms = 1e-6 / 2
    assert found["causal_kernels"] == pytest.approx(400 * to_ms)
    assert found["experts"] == pytest.approx(100 * to_ms)
    assert found["router"] == pytest.approx(100 * to_ms)
    assert found["short_conv"] == pytest.approx(50 * to_ms)
    assert found["conv"] == pytest.approx(50 * to_ms)
    # the dense layer's FFN and the loop's own bookkeeping are the rest
    assert found["rest"] == pytest.approx((20 + 280) * to_ms)
    assert sum(found.values()) == pytest.approx(1000 * to_ms)
    assert lfm2_trace.reduce({}, {}, (0, 0), 2, lambda p: {}, 1) is None


def _fed_telemetry():
    from ml_recipe_tpu.train.telemetry import TrainTelemetry

    telemetry = TrainTelemetry()
    for step, (held, load) in enumerate(
            ((1000.0, 1.25), (1040.0, 1.31), (980.0, 1.27))):
        telemetry.observe_step(step, data_wait_s=0.001, host_s=0.007,
                               device_s=0.56, host_overlapped=True)
        telemetry.observe_scalars({
            "moe_held_assignments": held, "moe_load_max_over_mean": load,
            "moe_held_share": 0.25})
    return telemetry


class _Stretch:
    all_tokens, steps = 3 * 512, 3


def _ctx(fixture, **more):
    from perfbench.harness import device

    cell = manifest.load_cell(CELL)
    path = str(BENCH / "fixtures" / fixture)
    return {"cell": cell, "trace": tr.load(path, "modules"),
            "trace_file": path, "trace_steps": 3, "chips": 1, "train": True,
            "trace_shapes": [(2, 256)] * 3, "seq_len": 256,
            "peaks": device.peaks("TPU v5 lite"), "token_rate_chip": 60_000.0,
            "micro_rows_chip": 2, "stretch": _Stretch,
            "memory_peak_bytes": 14_000_000_000,
            "compile": {"setup": {"seconds": 1.0}, "window_compiles": 0},
            **more}


@pytest.mark.parametrize("fixture", ["tiny.xplane.pb", "joyai_tiny.xplane.pb"])
def test_another_configurations_trace_reads_as_nothing_for_the_new_readers(
        fixture, monkeypatch):
    """What the parent's programs give these readers: no ``conv`` scope and
    no counter. Nothing, and no exception. joyai's program runs the same
    causal kernels and the same expert layer (``router`` / ``dispatch`` /
    ``experts`` / ``combine``), so the readers of those, twins of joyai's own
    (PERF.md section 7), read its trace as joyai's do."""
    from ml_recipe_tpu.metrics import trace as program_trace

    maps = json.loads(
        (BENCH / "fixtures" / "joyai_tiny.scope_map.json").read_text())
    monkeypatch.setattr(program_trace, "scope_map",
                        lambda name: maps.get(name, {}))
    ctx = _ctx(fixture)
    got = read_per_layer(ctx["cell"], ctx)
    shared = {"gqa_attn_ms_step", "gqa_attn_roofline", "lfm2_moe_ms_step",
              "lfm2_moe_dispatch_ms_step"} if fixture.startswith("joyai") \
        else set()
    assert set(NEW_READERS) & set(got) == shared
    assert not {"conv", "short_conv"} & set(ctx["lfm2_table"])
    for name in NEW_READERS:
        read = importlib.import_module(f"perfbench.metrics.{name}").read
        assert read({}) is None, name


@pytest.fixture
def recorded(monkeypatch):
    """``fixtures/lfm2_tiny.xplane.pb`` (three calls of a two-layer
    ``train_step`` with the short convolution, the causal kernels at grouped
    heads and the TPU's grouped matmuls, recorded on the v5e by
    ``fixtures/record_fixture_lfm2.py``, PR 31) and the scope map that
    program gave, as the program would hand it over."""
    from ml_recipe_tpu.metrics import trace as program_trace

    maps = json.loads(
        (BENCH / "fixtures" / "lfm2_tiny.scope_map.json").read_text())
    monkeypatch.setattr(program_trace, "scope_map",
                        lambda name: maps.get(name, {}))
    ctx = _ctx("lfm2_tiny.xplane.pb", telemetry=_fed_telemetry().registry)
    return dict(ctx, busy=tr.busy_idle(ctx["trace"]))


def test_every_new_reader_reads_the_recorded_trace(recorded, capsys):
    from perfbench.harness.flops import roofline_seconds

    ctx = recorded
    cfg = ctx["cell"].config
    got = read_per_layer(ctx["cell"], ctx)
    assert set(NEW_READERS) <= set(got)
    assert {m["name"] for m in ctx["cell"].per_layer} == set(got)
    assert got["step_ms"]["value"] == pytest.approx(560.0)
    table = ctx["lfm2_table"]
    assert set(table) == {"rest", "causal_kernels", "router", "dispatch",
                          "experts", "combine", "other", "conv", "short_conv"}
    # ms a step of the three recorded calls, read by hand from the trace
    for part, ms in RECORDED_MS.items():
        assert table[part] == pytest.approx(ms, rel=1e-3), part
    assert got["gqa_attn_ms_step"]["value"] == pytest.approx(
        table["causal_kernels"])
    assert got["short_conv_ms_step"]["value"] == pytest.approx(
        table["short_conv"])
    assert got["conv_block_ms_step"]["value"] == pytest.approx(
        table["conv"] + table["short_conv"])
    assert got["lfm2_moe_ms_step"]["value"] == pytest.approx(
        sum(table[k] for k in ("router", "dispatch", "experts", "combine",
                               "other")))
    assert got["lfm2_moe_dispatch_ms_step"]["value"] == pytest.approx(
        table["router"] + table["dispatch"] + table["combine"])
    assert got["lfm2_moe_load_max_pct"] == {"value": 127.0, "unit": "%"}
    # the shares: the benchmark's own FLOP and byte functions over that time
    # (the cell's configuration has one attention and four conv layers)
    least = 3 * roofline_seconds(
        flops_lfm2.causal_core_flops(cfg, 2, 256, train=True),
        flops_lfm2.causal_core_bytes(cfg, 2, 256, train=True),
        ctx["peaks"])[0]
    assert got["gqa_attn_roofline"]["value"] == pytest.approx(
        100 * least / (table["causal_kernels"] * 1e-3 * 3))
    seconds, bound = roofline_seconds(
        flops_lfm2.short_conv_flops(cfg, 3 * 512, train=True),
        flops_lfm2.short_conv_bytes(cfg, 3 * 512, train=True), ctx["peaks"])
    assert bound == "bytes"
    assert got["short_conv_roofline"]["value"] == pytest.approx(
        100 * 4 * seconds / (table["short_conv"] * 1e-3 * 3))
    held = 1000.0       # the median of the fed counter
    assert got["lfm2_expert_roofline"]["value"] == pytest.approx(
        100 * roofline_seconds(
            flops_lfm2.grouped_matmul_flops(cfg, held, train=True),
            flops_lfm2.grouped_matmul_bytes(cfg, held, 4, train=True),
            ctx["peaks"])[0] / (table["experts"] * 1e-3))
    per_token = flops_lfm2.matmul_flops_per_token(
        cfg, 256, train=True, held_per_token=held / 512 / 4)
    assert got["lfm2_mfu_pct"]["value"] == pytest.approx(
        100 * 60_000.0 * per_token / 197e12)
    # (the cell's widths over a tiny program's times: the shares' sizes mean
    # nothing here, only that the readers reached their numbers)
    # the table went out once, on an earlier line
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert len([x for x in lines if "lfm2_table" in x]) == 1


# ms a step of the recorded fixture's parts (three calls), by hand: the
# profile's own events, nested ones subtracted, joined to the scope map
RECORDED_MS = {
    "causal_kernels": 0.026713, "experts": 0.019119, "router": 0.013636,
    "dispatch": 0.021553, "combine": 0.018084, "other": 0.024948,
    "conv": 0.003398, "short_conv": 0.001494, "rest": 0.080258}


def test_the_recorded_kernels_are_told_by_name(recorded):
    from perfbench.harness.joyai_trace import (CAUSAL_KERNELS,
                                               GROUPED_KERNELS, load_named)

    ops, modules = load_named(recorded["trace_file"])
    names = [n for n, _, _ in ops[0]]
    causal = [n for n in names if CAUSAL_KERNELS.match(n)]
    grouped = [n for n in names if GROUPED_KERNELS.match(n)]
    # per call: one attention layer's forward and its one fused backward;
    # the expert layer's two forward ragged dots, their four transposes and
    # the tile metadata
    assert len(causal) == 3 * 2 and len(grouped) == 3 * 8
    assert {re.sub(r"[.\d]+$", "", n) for n in causal} == {
        "%flash_causal_fwd", "%flash_causal_bwd"}
    assert [m[0].split("(")[0] for m in modules[0]] == ["jit_train_step"] * 3
    maps = json.loads(
        (BENCH / "fixtures" / "lfm2_tiny.scope_map.json").read_text())
    scopes = set(maps["jit_train_step"].values())
    assert any("/conv/short_conv/" in s and "transpose(" in s for s in scopes)
    assert any("/conv/short_conv/" in s and "/jvp(" in s for s in scopes)


# -- the comparison's own part: the convolution alone ------------------------------------

def test_the_convolution_alone_tells_one_rounding_from_several():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.harness import checks_lfm2, reference_lfm2

    rng = np.random.default_rng(0)
    read = jnp.asarray(rng.normal(size=(2, 64, 3 * 32)), jnp.bfloat16)
    taps = jnp.asarray(rng.normal(size=(32, 3)), jnp.float32)
    mask = np.ones((2, 64), np.int32)
    mask[1, 40:] = 0
    exact = reference_lfm2.gated_conv(read.astype(jnp.float32), taps)
    once = exact.astype(jnp.bfloat16)
    report = jax.jit(checks_lfm2.conv_report)
    good = report(taps, read, once, mask)
    assert float(good["beyond_one_rounding_share"]) == 0.0
    assert float(good["largest_distance_in_roundings"]) <= 1.0
    bf16 = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    kept, reference_lfm2._gating = reference_lfm2._gating, bf16
    try:
        rounded = reference_lfm2.gated_conv(read.astype(jnp.float32), taps)
    finally:
        reference_lfm2._gating = kept
    bad = report(taps, read, rounded.astype(jnp.bfloat16), mask)
    assert float(bad["beyond_one_rounding_share"]) > 0.1
    assert float(bad["beyond_one_rounding_share"]) > \
        100 * checks_lfm2.CONV_BEYOND_ONE_ROUNDING
    # garbage on padded positions is not judged
    spoiled = once.at[1, 40:].set(1e3)
    assert float(report(taps, read, spoiled, mask)[
        "beyond_one_rounding_share"]) == 0.0


# -- the runner that binds the configuration's comparison ------------------------------

def test_train_own_check_binds_the_named_comparison_and_restores(monkeypatch):
    from perfbench.harness import checks_lfm2
    from perfbench.runners import train, train_own_check

    cell = manifest.load_cell(CELL)
    assert cell.runner == "train_own_check"
    assert cell.config["comparison"] == "checks_lfm2"
    seen = {}

    def fake_run(cell, **how):
        seen["bound"] = train.check_against_reference
        return 7

    monkeypatch.setattr(train, "run", fake_run)
    original = train.check_against_reference
    assert train_own_check.run(cell, seed=1) == 7
    assert seen["bound"] is checks_lfm2.compare
    assert train.check_against_reference is original


# -- the cell's whole course at the tiny preset, on the CPU ----------------------------

_OUT_OF_THE_WAY = (
    "import os, runpy, sys; os.nice(19); "
    "os.sched_setaffinity(0, {max(os.sched_getaffinity(0))}); "
    "sys.argv = sys.argv[1:]; "
    "runpy.run_path(sys.argv[0], run_name='__main__')")


def test_rehearsal_of_the_new_cell_prints_the_contracts_last_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    out = subprocess.run(
        [sys.executable, "-c", _OUT_OF_THE_WAY,
         str(REPO / "perfbench" / "run.py"), "--workload", CELL, "--seed",
         "3100000011", "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=str(REPO), env=env, text=True, capture_output=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(x) for x in out.stdout.splitlines()
             if x.startswith("{")]
    last = lines[-1]
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is True, lines[-4:]
    assert last["failed"] == 0 and last["attempted"] > 0
    assert last["metrics"] == {}, "no CPU number under a metric's name"
    assert last["device"]["platform"] == "cpu"
    said = {k: v for line in lines[:-1] for k, v in line.items()}
    assert said["correct"]["window_compiles"] == 0
    check = said["reference_check"]
    assert check["ok"] is True and check["failed_parts"] == []
    assert len(check["routing"]["layers"]) == 3
    assert len(check["routing"]["trajectory_differ_share"]) == 3
    assert len(check["conv"]["layers"]) == 2
    assert said["stretches"]["telemetry"]["steps"] >= 2
    assert said["run"]["seed"] == 3100000011      # more than 32 signed bits


def test_the_parent_refuses_the_new_preset_at_once():
    """What the driver's first try of the cell on the parent meets: the
    model parser's ``--model`` choices are the preset registry, so a tree
    without the preset exits from argument parsing."""
    from ml_recipe_tpu.config.parser import get_model_parser

    choices = next(a.choices for a in get_model_parser()._actions
                   if "--model" in a.option_strings)
    assert CONFIG["model"] in choices and "lfm2-tiny" in choices
    with pytest.raises(SystemExit):
        get_model_parser().parse_args(["--model", "no-such-preset"])
