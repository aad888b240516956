"""The benchmark's files for ``mellum2-12b-a2.5b-ep4`` and its cell, off the
chip: the configuration's keys against the catalog's row, the preset against
the file, the parameter count, the manifest's new entries (by name), the FLOP
and byte functions against hand-worked values, the trace readers on hand-made
events, on a trace recorded on the chip and on the other configurations'
traces, the operators' own comparison, the cell's ``--rehearse`` run, and
that a tree without the preset refuses it at once."""

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

from perfbench.harness import flops_mellum2, manifest  # noqa: E402
from perfbench.harness import mellum2_trace  # noqa: E402
from perfbench.harness import trace_reduce as tr  # noqa: E402
from perfbench.harness.result import read_per_layer  # noqa: E402

BENCH = REPO / "perfbench"
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "mellum2-ep4-train-seq8192"
NAME = "mellum2-12b-a2.5b-ep4"
CONFIG = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
NEW_READERS = (
    "mellum2_mfu_pct", "window_attn_ms_step", "window_attn_roofline",
    "mellum2_full_attn_ms_step", "mellum2_full_attn_roofline",
    "mellum2_moe_ms_step", "mellum2_moe_dispatch_ms_step",
    "mellum2_expert_roofline", "mellum2_moe_load_max_pct",
    "window_pairs_walked_pct")
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]

# the `config` of the catalog's row Mellum2-12B-A2.5B-Instruct (model-configs
# guide, architectures.jsonl; source_url as the configuration's `source`)
CATALOG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168, "layer_types": PERIOD * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True,
}
WIDTH = re.compile(      # what `reduced` may never name
    r"(_dim|_rank)$|(hidden|intermediate|latent|state|proj\w*)_size|head"
    r"|expan|experts_per_tok")
SOURCE = ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/"
          "main/config.json")


# -- the configuration ---------------------------------------------------------------

def test_configuration_keys_against_the_catalog_row():
    reduced = CONFIG["reduced"]
    assert reduced == ["num_hidden_layers", "layer_types", "mlp_layer_types",
                       "num_experts", "vocab_size"]
    assert len(CATALOG["layer_types"]) == 28
    assert CATALOG["layer_types"].count("full_attention") == 7
    for key, published in CATALOG.items():
        assert key in CONFIG, key
        if key in reduced:
            assert CONFIG[key] != published, key
            assert CONFIG["published"][key] == published
        else:
            assert CONFIG[key] == published, key
            assert type(CONFIG[key]) is type(published), key
    assert not [k for k in reduced if WIDTH.search(k)]
    # the floors: one whole period of four layers, 8 experts or more a layer,
    # an eighth of the vocabulary or more
    assert CONFIG["layer_types"] == PERIOD and CONFIG["num_hidden_layers"] == 4
    assert CONFIG["mlp_layer_types"] == ["sparse"] * 4
    assert CONFIG["num_experts"] == 16 >= 8
    assert CONFIG["experts_held"] == {"first": 0, "count": 16, "of": 64}
    assert CONFIG["vocab_size"] * 4 == CATALOG["vocab_size"]
    assert CONFIG["source"] == SOURCE
    assert CONFIG["comparison"] == "checks_mellum2"
    assert "4 chips share each layer" in CONFIG["deployment"]
    assert CONFIG["published"]["parameters"] == "12B, 2.5B active a token"
    assert {"lm_head", "mtp"} <= set(CONFIG["not_built"])
    for inferred in ("norm_placement", "qk_norm", "rope_pairing", "router",
                     "weights", "precision"):
        assert inferred in CONFIG["assumed"], inferred
    for family in ("norm_placement", "qk_norm", "rope_pairing", "router"):
        assert "qwen3_moe" in CONFIG["assumed"][family], family


def test_the_preset_is_the_configuration_file():
    from ml_recipe_tpu.models.config import MODEL_PRESETS

    preset = MODEL_PRESETS[CONFIG["model"]]
    same = {"hidden_size": "hidden_size", "num_hidden_layers": "num_layers",
            "num_attention_heads": "num_heads", "vocab_size": "vocab_size",
            "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
            "moe_intermediate_size": "moe_intermediate_size",
            "num_experts": "experts_held",
            "num_experts_per_tok": "num_experts_per_tok",
            "norm_topk_prob": "norm_topk_prob",
            "sliding_window": "sliding_window",
            "rms_norm_eps": "rms_norm_eps", "model_type": "model_type",
            "initializer_range": "initializer_range",
            "embedding_range": "embedding_range"}
    for key, field in same.items():
        assert getattr(preset, field) == CONFIG[key], key
    assert preset.embedding_range == 1.0        # unit-RMS rows: see `assumed`
    assert "embedding_range 1.0" in CONFIG["assumed"]["weights"]
    assert list(preset.layer_types) == CONFIG["layer_types"]
    held = CONFIG["experts_held"]
    assert (preset.experts_first, preset.experts_held,
            preset.n_routed_experts) == (held["first"], held["count"],
                                         held["of"])
    yarn = CONFIG["rope_parameters"]["full_attention"]
    assert (preset.rope_theta, preset.yarn_factor,
            preset.yarn_original_positions, preset.yarn_beta_fast,
            preset.yarn_beta_slow, preset.yarn_attention_factor) == (
        yarn["rope_theta"], yarn["factor"],
        yarn["original_max_position_embeddings"], yarn["beta_fast"],
        yarn["beta_slow"], yarn["attention_factor"])
    assert CONFIG["rope_parameters"]["sliding_attention"] == {
        "rope_type": "default", "rope_theta": preset.rope_theta}
    assert preset.qk_norm is True and not preset.rope_interleaved
    assert preset.scoring_func == "softmax" and not preset.n_shared_experts
    assert preset.first_k_dense_replace == 0    # no leading dense layer
    assert preset.routed_scaling_factor == 1.0 and not preset.norm_topk_eps
    assert preset.routes and preset.windows and not preset.scans
    assert preset.hidden_dropout_prob == 0.0
    assert preset.attention_probs_dropout_prob == 0.0
    tiny = MODEL_PRESETS["mellum2-tiny"]
    assert set(tiny.layer_types) == {"sliding_attention", "full_attention"}
    assert tiny.num_heads // tiny.num_kv_heads > 1
    assert (tiny.experts_first, tiny.experts_held, tiny.n_routed_experts) \
        == (2, 4, 8)
    rehearsal = manifest.load_cell(CELL).traffic["rehearsal"]
    assert rehearsal["model"] == "mellum2-tiny"
    ref = rehearsal["reference_config"]
    assert (ref["hidden_size"], ref["head_dim"], ref["sliding_window"],
            ref["layer_types"], ref["experts_held"]) == (
        tiny.hidden_size, tiny.head_dim, tiny.sliding_window,
        list(tiny.layer_types), {"first": 2, "count": 4, "of": 8})
    assert tiny.sliding_window < rehearsal["flags"]["max_seq_len"]
    assert ref["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] == tiny.yarn_original_positions


def test_the_parameter_count_is_the_issues_arithmetic():
    """538.55M parameters: the cut's own count, from shapes."""
    import jax
    import jax.numpy as jnp

    from ml_recipe_tpu.models import MODEL_PRESETS, QAModel

    shapes = jax.eval_shape(
        lambda: QAModel(MODEL_PRESETS[NAME], attention_impl="xla").init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    count = lambda tree: sum(  # noqa: E731
        int(jnp.prod(jnp.asarray(a.shape))) for a in
        jax.tree_util.tree_leaves(tree))
    layer = shapes["transformer"]["layer_0"]
    attention = layer["attention"]
    assert sum(count(attention[k]) for k in ("q", "k", "v", "output")) \
        == 2304 * (4096 + 512 + 512) + 4096 * 2304 == 21_233_664
    assert set(layer["mlp"]["router"]) == {"kernel"}        # no bias
    assert count(layer["mlp"]["router"]) == 2304 * 64 == 147_456
    assert count(layer["mlp"]["experts"]) == 16 * 3 * 2304 * 896 \
        == 16 * 6_193_152
    norms = count(layer) - 21_233_664 - 147_456 - 16 * 6_193_152
    assert norms == 2 * 2304 + 2 * 128 == 4_864
    assert count(layer) == 120_476_416
    assert all(count(shapes["transformer"][f"layer_{i}"]) == 120_476_416
               for i in range(4))
    assert count(shapes["transformer"]["word_embeddings"]) == 24576 * 2304 \
        == 56_623_104
    assert count(shapes) == pytest.approx(538.55e6, rel=2e-4)


def test_the_manifest_gained_its_entries_by_name():
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": NAME, "traffic": "full8192-mellum2-ep4",
        "chips": 1, "why": cells[CELL]["why"]}
    assert "window" in cells[CELL]["why"] and "4x" in cells[CELL]["why"]
    assert len(cells[CELL]["why"]) <= 200
    four_chip = [w["name"] for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert four_chip == ["large-train-dp4"]
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    entry = configs[NAME]
    assert entry["file"] == f"perfbench/configs/{NAME}.json"
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"] == SOURCE
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    assert [c["config"] for c in MANIFEST["workloads"]].count(NAME) == 1
    # the new entries stand at the end of their lists
    assert MANIFEST["configs"][-1]["name"] == NAME
    assert MANIFEST["workloads"][-1]["name"] == CELL
    assert tuple(m["name"] for m in MANIFEST["per_layer"][-10:]) \
        == NEW_READERS
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    older_layers = {m["layer"] for m in MANIFEST["per_layer"]
                    if m["name"] not in NEW_READERS}
    for name in NEW_READERS:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s_chip"
        assert m["unit"] in ("%", "ms") and m["layer"] in older_layers
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert name.endswith("_ms_step") == (m["unit"] == "ms")
        assert (BENCH / "metrics" / f"{name}.py").is_file()
    # one share of the whole step, and it says so in its name
    assert [n for n in NEW_READERS if "mfu" in n] == ["mellum2_mfu_pct"]
    assert [n for n in NEW_READERS if n.endswith("_roofline")] == [
        "window_attn_roofline", "mellum2_full_attn_roofline",
        "mellum2_expert_roofline"]
    # no other metric's list took the new cell
    for m in MANIFEST["per_layer"]:
        if m["name"] not in NEW_READERS:
            assert CELL not in m.get("workloads", []), m["name"]
    job = manifest.load_cell(CELL).traffic["job"]
    flags = job["flags"]
    assert (flags["max_seq_len"], flags["train_batch_size"],
            flags["batch_split"], flags["remat"], flags["hbm_preflight"],
            flags["max_question_len"], flags["length_buckets"]) == (
        8192, 4, 4, False, True, 64, "off")
    assert (job["mesh"], job["trainer_seed"], job["rows"]) == (
        "data:1", 0, 1000000)
    other = manifest.load_cell("lfm2-ep4-train-seq8192").traffic["job"]
    for key in ("warmup_batches", "trace_batches", "trace_seconds"):
        assert job[key] == other[key], key


# -- FLOPs and bytes, worked by hand ---------------------------------------------------

def test_flops_a_token_at_the_published_widths():
    cfg = CONFIG
    assert flops_mellum2.attention_projection_flops(cfg) == 2 * 21_233_664
    assert flops_mellum2.swiglu_flops(2304, 896) == 2 * 6_193_152
    assert flops_mellum2.held_per_token_expected(cfg) == 8 * 16 / 64 == 2.0
    # a window row's pairs, and a causal row's
    assert flops_mellum2.permitted_pairs(cfg, "sliding_attention", 8192) \
        == 1024 * 8192 - 1024 * 1023 / 2 == 7_864_832
    assert flops_mellum2.permitted_pairs(cfg, "full_attention", 8192) \
        == 8192 * 8193 / 2 == 33_558_528
    # a row no longer than the window is the causal triangle
    assert flops_mellum2.permitted_pairs(cfg, "sliding_attention", 512) \
        == flops_mellum2.permitted_pairs(cfg, "full_attention", 512) \
        == 512 * 513 / 2
    cores = (3 * 7_864_832 + 33_558_528) * 2 * 32 * 2 * 128 / 8192
    per_layer = 2 * (21_233_664 + 147_456 + 2.0 * 6_193_152)
    assert flops_mellum2.matmul_flops_per_token(cfg, 8192, train=False) \
        == pytest.approx(4 * per_layer + cores, rel=1e-12)
    trained = flops_mellum2.matmul_flops_per_token(cfg, 8192, train=True)
    assert trained == pytest.approx(3 * (4 * per_layer + cores), rel=1e-12)
    assert trained == pytest.approx(1.1532e9, rel=1e-3)
    # the counter's word on the held assignments moves the experts' term only
    more = flops_mellum2.matmul_flops_per_token(
        cfg, 8192, train=True, held_per_token=2.5)
    assert more - trained == pytest.approx(3 * 4 * 0.5 * 2 * 6_193_152)


def test_core_flops_and_bytes_by_kind():
    cfg = CONFIG
    window = flops_mellum2.core_flops(cfg, "sliding_attention", 4, 8192,
                                      train=True)
    full = flops_mellum2.core_flops(cfg, "full_attention", 4, 8192,
                                    train=True)
    assert window == 3 * 2 * 4 * 32 * 7_864_832 * 256
    assert full == 3 * 2 * 4 * 32 * 33_558_528 * 256
    assert window / full == pytest.approx(0.2344, rel=1e-3)
    # q and the context a query head, k and v a key/value head; backward
    # twice that and the cotangent
    assert flops_mellum2.core_bytes(cfg, 4, 8192, train=False) \
        == 4 * 8192 * (2 * 32 + 2 * 4) * 128 * 2
    assert flops_mellum2.core_bytes(cfg, 4, 8192, train=True) \
        == 4 * 8192 * (6 * 32 + 6 * 4) * 128 * 2
    from perfbench.harness import device
    from perfbench.harness.flops import roofline_seconds

    peaks = device.peaks("TPU v5 lite")
    for kind in flops_mellum2.KINDS:
        seconds, bound = roofline_seconds(
            flops_mellum2.core_flops(cfg, kind, 4, 8192, train=True),
            flops_mellum2.core_bytes(cfg, 4, 8192, train=True), peaks)
        assert bound == "flops", kind
    assert flops_mellum2.grouped_matmul_flops(cfg, 1000, train=True) \
        == 3 * 1000 * 2 * 6_193_152


# -- the trace readers -------------------------------------------------------------------

FWD = "jit(train_step)/jvp(forward_backward)/QAModel/transformer"
BWD = ("jit(train_step)/transpose(jvp(forward_backward))/QAModel/transformer")


@pytest.mark.parametrize("name, op_name, want", [
    ("%flash_window_fwd.3", None, "window_kernels"),
    ("%flash_window_bwd.1", f"{BWD}/layer_0/attention/x", "window_kernels"),
    ("%flash_window_bwd_dq.2", None, "window_kernels"),
    ("%flash_window_bwd_dkv.2", None, "window_kernels"),
    ("%flash_causal_fwd.3", None, "causal_kernels"),
    ("%flash_causal_bwd.1", f"{BWD}/layer_3/attention/x", "causal_kernels"),
    ("%ragged-dot-none.7", None, "experts"),
    ("%fusion.1", f"{FWD}/layer_0/mlp/router/checkpoint/dot_general",
     "router"),
    ("%fusion.2", f"{FWD}/layer_0/mlp/dispatch/sort", "dispatch"),
    ("%fusion.3", f"{BWD}/layer_2/mlp/combine/mul", "combine"),
    ("%fusion.4", f"{FWD}/layer_1/mlp/experts/mul", "experts"),
    ("%fusion.5", f"{FWD}/layer_1/mlp/reshape", "other"),
    # the first layer is an expert layer: no leading dense one
    ("%fusion.6", f"{FWD}/layer_0/mlp/experts/mul", "experts"),
    ("%fusion.7", f"{FWD}/layer_3/attention/q/dot_general", "rest"),
    ("%fusion.8", "jit(train_step)/optimizer/add", "rest"),
    ("%fusion.9", None, "rest"),
])
def test_labels_by_instruction_name_and_scope(name, op_name, want):
    assert mellum2_trace.label(name, op_name) == want


def test_the_causal_readers_pattern_does_not_match_a_window_call():
    from perfbench.harness.joyai_trace import CAUSAL_KERNELS

    for name in ("%flash_window_fwd.3", "%flash_window_bwd.1",
                 "%flash_window_bwd_dq", "%flash_window_bwd_dkv.4"):
        assert not CAUSAL_KERNELS.match(name), name
        assert mellum2_trace.WINDOW_KERNELS.match(name), name
    assert not mellum2_trace.WINDOW_KERNELS.match("%flash_causal_fwd.1")


def test_hand_made_events_reduce_to_parts():
    scope = {
        "%fusion.1": f"{FWD}/layer_0/mlp/router/dot_general",
        "%fusion.2": f"{FWD}/layer_0/mlp/dispatch/sort",
        "%fusion.3": f"{FWD}/layer_0/attention/q/dot_general",
        "%fusion.4": f"{FWD}/layer_0/mlp/combine/mul",
    }
    ops = {0: [("%flash_window_fwd.3", 0, 200), ("%fusion.1", 200, 250),
               ("%flash_causal_fwd.3", 250, 450), ("%fusion.2", 450, 500),
               ("%ragged-dot-none.1", 500, 600),
               ("%flash_window_bwd.1", 600, 900), ("%fusion.3", 900, 950),
               ("%fusion.4", 950, 1000)]}
    modules = {0: [("jit_train_step(7)", 0, 1000)]}
    found = mellum2_trace.reduce(ops, modules, (0, 1000), 2,
                                 lambda program: scope)
    to_ms = 1e-6 / 2
    assert found["window_kernels"] == pytest.approx(500 * to_ms)
    assert found["causal_kernels"] == pytest.approx(200 * to_ms)
    assert found["experts"] == pytest.approx(100 * to_ms)
    assert found["router"] == found["dispatch"] == found["combine"] \
        == pytest.approx(50 * to_ms)
    assert found["rest"] == pytest.approx(50 * to_ms)
    assert sum(found.values()) == pytest.approx(1000 * to_ms)
    assert mellum2_trace.reduce({}, {}, (0, 0), 2, lambda p: {}) is None


def _fed_telemetry():
    from ml_recipe_tpu.train.telemetry import TrainTelemetry

    telemetry = TrainTelemetry()
    for step, (held, load) in enumerate(
            ((6100.0, 1.21), (6144.0, 1.25), (6200.0, 1.3))):
        telemetry.observe_step(step, data_wait_s=0.001, host_s=0.007,
                               device_s=0.56, host_overlapped=True)
        telemetry.observe_scalars({
            "moe_held_assignments": held, "moe_load_max_over_mean": load,
            "moe_held_share": 0.25, "attn_window_block_pairs": 80.0,
            "attn_causal_block_pairs": 96.0})
    return telemetry


class _Stretch:
    all_tokens, steps = 3 * 1536, 3


def _ctx(fixture, cell=CELL, **more):
    from perfbench.harness import device

    path = str(BENCH / "fixtures" / fixture)
    return {"cell": manifest.load_cell(cell),
            "trace": tr.load(path, "modules"),
            "trace_file": path, "trace_steps": 3, "chips": 1, "train": True,
            "trace_shapes": [(2, 768)] * 3, "seq_len": 768,
            "peaks": device.peaks("TPU v5 lite"), "token_rate_chip": 9_000.0,
            "micro_rows_chip": 2, "stretch": _Stretch,
            "memory_peak_bytes": 15_000_000_000,
            "compile": {"setup": {"seconds": 1.0}, "window_compiles": 0},
            **more}


def test_the_counters_reach_the_registry_under_their_names():
    registry = _fed_telemetry().registry
    for name, median in (("train_attn_window_block_pairs", 80.0),
                         ("train_attn_causal_block_pairs", 96.0),
                         ("train_moe_held_assignments", 6144.0),
                         ("train_moe_load_max_over_mean", 1.25)):
        series = registry.get(name)
        assert series is not None and series.count == 3, name
        assert series.quantile(0.5) == pytest.approx(median), name
    read = importlib.import_module(
        "perfbench.metrics.window_pairs_walked_pct").read
    assert read({"telemetry": registry}) == pytest.approx(100 * 80 / 96)
    from ml_recipe_tpu.train.telemetry import TrainTelemetry

    assert read({"telemetry": TrainTelemetry().registry}) is None
    assert read({}) is None


@pytest.mark.parametrize("fixture, maps, cell", [
    ("tiny.xplane.pb", "joyai_tiny.scope_map.json", "base-train-full512"),
    ("joyai_tiny.xplane.pb", "joyai_tiny.scope_map.json",
     "joyai-ep16-train-seq4096"),
    ("lfm2_tiny.xplane.pb", "lfm2_tiny.scope_map.json",
     "lfm2-ep4-train-seq8192"),
    ("olmo_hybrid_tiny.xplane.pb", "olmo_hybrid_tiny.scope_map.json",
     "olmo-hybrid-pp8-train-seq8192"),
])
def test_another_configurations_program_reads_as_nothing_for_the_new_readers(
        fixture, maps, cell, monkeypatch):
    """What the parent's programs give these readers, under their own cells'
    configurations: no ``sliding_attention`` layer and no pair counter, so
    nothing, and no exception."""
    from ml_recipe_tpu.metrics import trace as program_trace
    from ml_recipe_tpu.train.telemetry import TrainTelemetry

    scope_maps = json.loads((BENCH / "fixtures" / maps).read_text())
    monkeypatch.setattr(program_trace, "scope_map",
                        lambda name: scope_maps.get(name, {}))
    telemetry = TrainTelemetry()
    telemetry.observe_scalars({"moe_held_assignments": 100.0,
                               "moe_load_max_over_mean": 1.2})
    ctx = _ctx(fixture, cell=cell, telemetry=telemetry.registry)
    for name in NEW_READERS:
        read = importlib.import_module(f"perfbench.metrics.{name}").read
        assert read(ctx) is None, name
        assert read({}) is None, name
    assert ctx["mellum2_table"] is None
    assert not set(NEW_READERS) & set(read_per_layer(ctx["cell"], ctx))


def test_the_new_cells_readers_under_a_program_without_the_kernels(
        monkeypatch):
    """The new cell's configuration over a program that has no window call,
    no scope map and no counter (what a parent that lacks them would trace):
    the readers find nothing and raise nothing."""
    from ml_recipe_tpu.metrics import trace as program_trace

    monkeypatch.setattr(program_trace, "scope_map", lambda name: {})
    ctx = _ctx("tiny.xplane.pb")
    for name in NEW_READERS:
        read = importlib.import_module(f"perfbench.metrics.{name}").read
        assert read(ctx) is None, name
    assert set(ctx["mellum2_table"]) == {"rest"}


@pytest.fixture
def recorded(monkeypatch):
    """``fixtures/mellum2_tiny.xplane.pb`` (three calls of a two-layer
    ``train_step`` with a sliding-window layer and a full-attention layer,
    each with an expert layer behind a softmax router, recorded on the v5e by
    ``fixtures/record_fixture_mellum2.py``, PR 37) and the scope map that
    program gave, as the program would hand it over."""
    from ml_recipe_tpu.metrics import trace as program_trace

    maps = json.loads(
        (BENCH / "fixtures" / "mellum2_tiny.scope_map.json").read_text())
    monkeypatch.setattr(program_trace, "scope_map",
                        lambda name: maps.get(name, {}))
    ctx = _ctx("mellum2_tiny.xplane.pb", telemetry=_fed_telemetry().registry)
    return dict(ctx, busy=tr.busy_idle(ctx["trace"]))


RECORDED_PARTS = {"window_kernels", "causal_kernels", "experts", "router",
                  "dispatch", "combine", "other", "rest"}


def test_every_new_reader_reads_the_recorded_trace(recorded, capsys):
    from perfbench.harness.flops import roofline_seconds

    ctx = recorded
    cfg = ctx["cell"].config
    got = read_per_layer(ctx["cell"], ctx)
    assert set(NEW_READERS) <= set(got)
    assert {m["name"] for m in ctx["cell"].per_layer} == set(got)
    table = ctx["mellum2_table"]
    assert set(table) == RECORDED_PARTS
    assert all(ms > 0 for ms in table.values())
    assert sum(table.values()) == pytest.approx(
        ctx["busy"]["busy_s"] * 1e3 / 3, rel=1e-3)
    assert got["window_attn_ms_step"]["value"] == pytest.approx(
        table["window_kernels"])
    assert got["mellum2_full_attn_ms_step"]["value"] == pytest.approx(
        table["causal_kernels"])
    assert got["mellum2_moe_ms_step"]["value"] == pytest.approx(
        sum(table[k] for k in ("experts", "router", "dispatch", "combine",
                               "other")))
    assert got["mellum2_moe_dispatch_ms_step"]["value"] == pytest.approx(
        sum(table[k] for k in ("router", "dispatch", "combine")))
    # the shares: the benchmark's own FLOP and byte functions over that time
    # (the cell's configuration has three window layers and one full)
    for name, kind, part, layers in (
            ("window_attn_roofline", "sliding_attention", "window_kernels", 3),
            ("mellum2_full_attn_roofline", "full_attention",
             "causal_kernels", 1)):
        least = 3 * layers * roofline_seconds(
            flops_mellum2.core_flops(cfg, kind, 2, 768, train=True),
            flops_mellum2.core_bytes(cfg, 2, 768, train=True),
            ctx["peaks"])[0]
        assert got[name]["value"] == pytest.approx(
            100 * least / (table[part] * 1e-3 * 3)), name
    least = roofline_seconds(
        flops_mellum2.grouped_matmul_flops(cfg, 6144.0, train=True),
        flops_mellum2.grouped_matmul_bytes(cfg, 6144.0, 4 * 1.0, train=True),
        ctx["peaks"])[0]
    assert got["mellum2_expert_roofline"]["value"] == pytest.approx(
        100 * least / (table["experts"] * 1e-3))
    per_token = flops_mellum2.matmul_flops_per_token(
        cfg, 768, train=True, held_per_token=6144.0 / 1536 / 4)
    assert got["mellum2_mfu_pct"]["value"] == pytest.approx(
        100 * 9_000.0 * per_token / 197e12)
    assert got["mellum2_moe_load_max_pct"]["value"] == pytest.approx(125.0)
    assert got["window_pairs_walked_pct"]["value"] == pytest.approx(
        100 * 80 / 96)
    # (the cell's widths over a tiny program's times: the shares' sizes mean
    # nothing here, only that the readers reached their numbers)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert len([x for x in lines if "mellum2_table" in x]) == 1


def test_the_recorded_kernels_and_scopes(recorded):
    from perfbench.harness.joyai_trace import CAUSAL_KERNELS, load_named

    ops, modules = load_named(recorded["trace_file"])
    bare = lambda n: re.sub(r"[.\d]+$", "", n)  # noqa: E731
    window = [bare(n) for n, _, _ in ops[0]
              if mellum2_trace.WINDOW_KERNELS.match(n)]
    causal = [bare(n) for n, _, _ in ops[0] if CAUSAL_KERNELS.match(n)]
    # per call: one forward and one fused backward a layer
    assert sorted(window) == ["%flash_window_bwd"] * 3 \
        + ["%flash_window_fwd"] * 3
    assert sorted(causal) == ["%flash_causal_bwd"] * 3 \
        + ["%flash_causal_fwd"] * 3
    assert [m[0].split("(")[0] for m in modules[0]] == ["jit_train_step"] * 3
    maps = json.loads(
        (BENCH / "fixtures" / "mellum2_tiny.scope_map.json").read_text())
    scopes = maps["jit_train_step"]
    # the window calls lie under block ``attention`` of the sliding layer,
    # the causal ones under the full layer's
    under = {bare(name): scope for name, scope in scopes.items()
             if name.startswith("%flash_")}
    assert "/layer_0/attention/" in under["%flash_window_fwd"]
    assert "/layer_0/attention/" in under["%flash_window_bwd"]
    assert "/layer_1/attention/" in under["%flash_causal_fwd"]
    for part in ("router", "dispatch", "combine"):
        assert any(f"/layer_0/mlp/{part}/" in s for s in scopes.values()), part


# -- the comparison's own parts ---------------------------------------------------------

def test_the_cores_comparison_tells_a_rounding_from_a_missing_window():
    import jax.numpy as jnp
    import numpy as np

    from perfbench.harness import checks_mellum2, reference_mellum2

    rng = np.random.default_rng(0)
    read = tuple(jnp.asarray(rng.normal(size=(1, 96, heads, 16)),
                             jnp.bfloat16) for heads in (4, 2, 2))
    mask = jnp.ones((1, 96), jnp.int32).at[0, 80:].set(0)
    exact = reference_mellum2.attention_core(*read, mask, 24, q_block=32)
    once = checks_mellum2.core_report(
        read, exact.astype(jnp.bfloat16), mask, 24)
    assert float(once["error_rms_share"]) < 2.0 ** -8
    causal = reference_mellum2.attention_core(*read, mask, None, q_block=32)
    missing = checks_mellum2.core_report(
        read, causal.astype(jnp.bfloat16), mask, 24)
    assert float(missing["error_rms_share"]) > 0.3
    assert float(once["error_rms_share"]) \
        < checks_mellum2.CORE_ERROR_SHARE \
        < float(missing["error_rms_share"])
    # padded positions do not count, whatever they hold
    junk = exact.astype(jnp.bfloat16).at[0, 80:].set(9.0)
    assert float(checks_mellum2.core_report(read, junk, mask, 24)[
        "error_rms_share"]) == pytest.approx(
        float(once["error_rms_share"]))
    drift = checks_mellum2.input_drift(
        read, (read[0] * 1.2772588722239782,) + read[1:], mask)
    assert float(drift["q"]) == pytest.approx(0.217 * 0.8, rel=0.2)
    assert float(drift["k"]) == float(drift["v"]) == 0.0
    assert float(drift["q"]) > checks_mellum2.INPUT_DRIFT


def test_the_references_band_is_written_from_i_minus_j():
    import jax.numpy as jnp
    import numpy as np

    from perfbench.harness import reference_mellum2

    # values that name their position: a uniform softmax over the permitted
    # keys returns their mean
    L = 12
    q = jnp.zeros((1, L, 1, 4))
    k = jnp.zeros((1, L, 1, 4))
    v = jnp.broadcast_to(jnp.arange(L, dtype=jnp.float32)[None, :, None, None],
                         (1, L, 1, 4))
    mask = jnp.ones((1, L), jnp.int32)
    got = np.asarray(reference_mellum2.attention_core(q, k, v, mask, 3,
                                                      q_block=4))[0, :, 0, 0]
    want = [np.mean(range(max(0, i - 2), i + 1)) for i in range(L)]
    assert np.allclose(got, want)
    full = np.asarray(reference_mellum2.attention_core(q, k, v, mask, None,
                                                       q_block=4))[0, :, 0, 0]
    assert np.allclose(full, [i / 2 for i in range(L)])


def test_train_own_check_binds_the_named_comparison_and_restores(monkeypatch):
    from perfbench.harness import checks_mellum2
    from perfbench.runners import train, train_own_check

    cell = manifest.load_cell(CELL)
    assert cell.runner == "train_own_check"
    seen = {}

    def fake_run(cell, **how):
        seen["bound"] = train.check_against_reference
        return 7

    monkeypatch.setattr(train, "run", fake_run)
    original = train.check_against_reference
    assert train_own_check.run(cell, seed=1) == 7
    assert seen["bound"] is checks_mellum2.compare
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
         "3700000011", "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=str(REPO), env=env, text=True, capture_output=True, timeout=900)
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
    assert said["batch_split"] == 4
    check = said["reference_check"]
    assert check["ok"] is True and check["failed_parts"] == []
    assert check["attention"]["kinds"] == PERIOD
    assert len(check["attention"]["layers"]) == 4
    assert len(check["routing"]["layers"]) == 4
    assert said["stretches"]["telemetry"]["steps"] >= 2
    assert said["run"]["seed"] == 3700000011      # more than 32 signed bits


def test_the_parent_refuses_the_new_preset_at_once():
    """What the driver's first try of the cell on the parent meets: the
    model parser's ``--model`` choices are the preset registry, so a tree
    without the preset exits from argument parsing."""
    from ml_recipe_tpu.config.parser import get_model_parser

    choices = next(a.choices for a in get_model_parser()._actions
                   if "--model" in a.option_strings)
    assert CONFIG["model"] in choices and "mellum2-tiny" in choices
    with pytest.raises(SystemExit):
        get_model_parser().parse_args(["--model", "no-such-preset"])
