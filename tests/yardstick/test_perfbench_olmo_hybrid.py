"""The benchmark's files for ``olmo-hybrid-7b-pp8`` and its cell, off the
chip: the configuration's keys against the catalog's row, the preset against
the file, the manifest's new entries (by name), the FLOP and byte functions
against hand-worked values, the trace readers on hand-made events, on a trace
recorded on the chip and on the other configurations' traces, the operator's
own comparison, the reference's hand-made ``exp``, the cell's ``--rehearse``
run, and that a tree without the preset refuses it at once."""

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

from perfbench.harness import flops_olmo_hybrid, manifest  # noqa: E402
from perfbench.harness import olmo_hybrid_trace  # noqa: E402
from perfbench.harness import trace_reduce as tr  # noqa: E402
from perfbench.harness.result import read_per_layer  # noqa: E402

BENCH = REPO / "perfbench"
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "olmo-hybrid-pp8-train-seq8192"
NAME = "olmo-hybrid-7b-pp8"
CONFIG = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
NEW_READERS = (
    "olmo_hybrid_mfu_pct", "linear_attn_block_ms_step", "gated_delta_ms_step",
    "gated_delta_roofline", "olmo_attn_ms_step", "olmo_attn_roofline")
PERIOD = ["linear_attention"] * 3 + ["full_attention"]

# the `config` of the catalog's row Olmo-Hybrid-7B (model-configs guide,
# architectures.jsonl; source_url as the configuration's `source`)
CATALOG = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}
WIDTH = re.compile(      # what `reduced` may never name
    r"(_dim|_rank)$|(hidden|intermediate|latent|state|proj\w*)_size|head"
    r"|expan|experts_per_tok")
SOURCE = "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"


# -- the configuration ---------------------------------------------------------------

def test_configuration_keys_against_the_catalog_row():
    reduced = CONFIG["reduced"]
    assert reduced == ["num_hidden_layers", "layer_types", "vocab_size"]
    assert len(CATALOG["layer_types"]) == 32
    assert CATALOG["layer_types"].count("full_attention") == 8
    for key, published in CATALOG.items():
        assert key in CONFIG, key
        if key in reduced:
            assert CONFIG[key] != published, key
            assert CONFIG["published"][key] == published
        else:
            assert CONFIG[key] == published, key
            assert type(CONFIG[key]) is type(published), key
    assert not [k for k in reduced if WIDTH.search(k)]
    # the floors: one whole period, an eighth of the vocabulary
    assert CONFIG["layer_types"] == PERIOD and CONFIG["num_hidden_layers"] == 4
    assert CONFIG["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert CONFIG["source"] == SOURCE
    assert CONFIG["comparison"] == "checks_olmo_hybrid"
    assert "8 pipeline stages" in CONFIG["deployment"]
    assert "lm_head" in CONFIG["not_built"]
    for inferred in ("norm_placement", "qk_norm", "rope", "head_dim",
                     "weights", "precision", "layer_equations"):
        assert inferred in CONFIG["assumed"], inferred
    assert "A_log" in CONFIG["assumed"]["weights"]


def test_the_preset_is_the_configuration_file():
    from ml_recipe_tpu.models.config import MODEL_PRESETS

    preset = MODEL_PRESETS[CONFIG["model"]]
    same = {"hidden_size": "hidden_size", "num_hidden_layers": "num_layers",
            "num_attention_heads": "num_heads", "vocab_size": "vocab_size",
            "intermediate_size": "intermediate_size",
            "rms_norm_eps": "rms_norm_eps", "model_type": "model_type",
            "linear_num_key_heads": "linear_num_heads",
            "linear_num_value_heads": "linear_num_heads",
            "linear_key_head_dim": "linear_key_head_dim",
            "linear_value_head_dim": "linear_value_head_dim",
            "linear_conv_kernel_dim": "linear_conv_kernel_dim",
            "linear_allow_neg_eigval": "linear_allow_neg_eigval",
            "initializer_range": "initializer_range"}
    for key, field in same.items():
        assert getattr(preset, field) == CONFIG[key], key
    assert list(preset.layer_types) == CONFIG["layer_types"]
    assert preset.rope_theta is CONFIG["rope_parameters"]["rope_theta"] is None
    assert (preset.num_kv_heads or preset.num_heads) == CONFIG[
        "num_key_value_heads"]
    assert (preset.head_dim or preset.hidden_size // preset.num_heads) \
        == CONFIG["head_dim"] == 128
    assert preset.norm_after and preset.qk_norm == "whole"
    assert preset.first_k_dense_replace == preset.num_layers  # no expert layer
    assert not preset.routes and preset.scans
    assert preset.hidden_dropout_prob == 0.0
    assert preset.attention_probs_dropout_prob == 0.0
    tiny = MODEL_PRESETS["olmo-hybrid-tiny"]
    assert tiny.linear_key_head_dim != tiny.linear_value_head_dim
    assert set(tiny.layer_types) == {"linear_attention", "full_attention"}
    rehearsal = manifest.load_cell(CELL).traffic["rehearsal"]
    assert rehearsal["model"] == "olmo-hybrid-tiny"
    ref = rehearsal["reference_config"]
    assert (ref["hidden_size"], ref["linear_key_head_dim"],
            ref["linear_value_head_dim"], ref["layer_types"]) == (
        tiny.hidden_size, tiny.linear_key_head_dim,
        tiny.linear_value_head_dim, list(tiny.layer_types))


def test_the_parameter_count_is_the_issues_arithmetic():
    """880.7M parameters: the cut's own count, from shapes."""
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
    assert count(layer["mlp"]) == 3 * 3840 * 11008 == 126_812_160
    assert count(layer["linear_attention"]) == (
        3840 * (2880 + 2880 + 5760 + 5760 + 30 + 30) + 5760 * 3840
        + 4 * (2880 + 2880 + 5760) + 30 + 30 + 192)
    assert count(shapes["transformer"]["layer_3"]["attention"]) \
        == 4 * 3840 ** 2 + 2 * 3840
    assert count(shapes["transformer"]["word_embeddings"]) == 12544 * 3840
    assert count(shapes) == pytest.approx(880.7e6, rel=2e-4)


def test_the_manifest_gained_its_entries_by_name():
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": NAME, "traffic": "full8192-row1", "chips": 1,
        "why": cells[CELL]["why"]}
    assert "remat" in cells[CELL]["why"] and "scan" in cells[CELL]["why"]
    assert len(cells[CELL]["why"]) <= 200
    four_chip = [w["name"] for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert four_chip == ["large-train-dp4"]
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    entry = configs[NAME]
    assert entry["file"] == f"perfbench/configs/{NAME}.json"
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"] == SOURCE
    assert len(entry["why"]) <= 200
    assert [c["config"] for c in MANIFEST["workloads"]].count(NAME) == 1
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    older_layers = {m["layer"] for m in MANIFEST["per_layer"]
                    if m["name"] not in NEW_READERS}
    for name in NEW_READERS:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s_chip"
        assert m["unit"] in ("%", "ms") and m["layer"] in older_layers
        assert ("roofline" in name or "mfu" in name) == (m["unit"] == "%")
        assert (BENCH / "metrics" / f"{name}.py").is_file()
    # one share of the whole step, and it says so in its name
    assert [n for n in NEW_READERS if "mfu" in n] == ["olmo_hybrid_mfu_pct"]
    # no other metric's list took the new cell
    for m in MANIFEST["per_layer"]:
        if m["name"] not in NEW_READERS:
            assert CELL not in m.get("workloads", []), m["name"]
    job = manifest.load_cell(CELL).traffic["job"]
    flags = job["flags"]
    assert (flags["max_seq_len"], flags["train_batch_size"],
            flags["batch_split"], flags["remat"], flags["hbm_preflight"],
            flags["max_question_len"], flags["length_buckets"]) == (
        8192, 1, 1, True, True, 64, "off")
    assert (job["mesh"], job["trainer_seed"], job["rows"]) == (
        "data:1", 0, 1000000)
    other = manifest.load_cell("lfm2-ep4-train-seq8192").traffic["job"]
    for key in ("warmup_batches", "trace_batches", "trace_seconds"):
        assert job[key] == other[key], key


# -- FLOPs and bytes, worked by hand ---------------------------------------------------

def test_flops_a_token_at_the_published_widths():
    cfg = CONFIG
    assert flops_olmo_hybrid.linear_projection_flops(cfg) == 2 * (
        3840 * (2880 + 2880 + 5760 + 5760 + 30 + 30) + 5760 * 3840) \
        == 177_408_000
    assert flops_olmo_hybrid.attention_projection_flops(cfg) \
        == 2 * 4 * 3840 ** 2 == 117_964_800
    core = flops_olmo_hybrid.causal_core_flops(
        cfg, 1, 8192, train=False) / 8192
    assert core == 2 * 30 * (8193 / 2) * 2 * 128 == pytest.approx(
        62.92e6, rel=1e-3)
    scan = flops_olmo_hybrid.gated_delta_flops(cfg, 1, train=False)
    assert scan == 4 * 2 * 30 * 96 * 192 == 4_423_680
    assert flops_olmo_hybrid.swiglu_flops(3840, 11008) == 253_624_320
    fwd = flops_olmo_hybrid.matmul_flops_per_token(cfg, 8192, train=False)
    assert fwd == pytest.approx(
        4 * 253_624_320 + 3 * (177_408_000 + scan) + 117_964_800 + core)
    assert fwd == pytest.approx(1740.9e6, rel=1e-4)
    assert flops_olmo_hybrid.matmul_flops_per_token(
        cfg, 8192, train=True) == pytest.approx(3 * fwd) == pytest.approx(
        5.2226e9, rel=1e-4)


def test_gated_delta_and_causal_core_bytes_and_flops():
    cfg = CONFIG
    # forward: q, k, v in bf16 and g, beta in f32 read, o written
    read = 30 * ((96 + 96 + 192) * 2 + 2 * 4)
    assert read == 23_280
    assert flops_olmo_hybrid.gated_delta_bytes(cfg, 1, train=False) \
        == read + 30 * 192 * 2 == 34_800
    # backward: the inputs and o's cotangent read, five gradients written
    assert flops_olmo_hybrid.gated_delta_bytes(cfg, 1, train=True) \
        == 34_800 + read + 11_520 + read == 92_880
    assert flops_olmo_hybrid.gated_delta_flops(cfg, 1, train=True) \
        == 3 * 4_423_680
    # a step of the cell: 3 scan layers x 8,192 tokens: bandwidth-bound,
    # 2.28 GB = 2.8 ms of the chip's 819 GB/s
    from perfbench.harness.flops import roofline_seconds

    peaks = {"bf16_tflops": 197.0, "hbm_gb_per_s": 819.0}
    seconds, bound = roofline_seconds(
        flops_olmo_hybrid.gated_delta_flops(cfg, 8192, train=True),
        flops_olmo_hybrid.gated_delta_bytes(cfg, 8192, train=True), peaks)
    assert bound == "bytes"
    assert 3 * seconds == pytest.approx(2.787e-3, rel=1e-3)
    # the attention core: q, context a head; k, v a head; as many of each
    fwd = (2 * 30 + 2 * 30) * 128 * 2
    assert flops_olmo_hybrid.causal_core_bytes(cfg, 1, 8192, train=False) \
        == 8192 * fwd == 8192 * 30_720
    assert flops_olmo_hybrid.causal_core_bytes(cfg, 1, 8192, train=True) \
        == 8192 * 3 * 30_720
    seconds, bound = roofline_seconds(
        flops_olmo_hybrid.causal_core_flops(cfg, 1, 8192, train=True),
        flops_olmo_hybrid.causal_core_bytes(cfg, 1, 8192, train=True), peaks)
    assert bound == "flops" and seconds == pytest.approx(7.85e-3, rel=1e-3)


# -- the trace readers -----------------------------------------------------------------

FWD = "jit(train_step)/jvp(forward_backward)/QAModel/transformer"
BWD = ("jit(train_step)/transpose(jvp(forward_backward))/QAModel/transformer/"
       "jvp(forward_backward)/QAModel/transformer/checkpoint")


@pytest.mark.parametrize("op_name,want", [
    (f"{FWD}/layer_0/linear_attention/gated_delta/while/body/dot_general",
     "gated_delta"),
    (f"{BWD}/rematted_computation/layer_1/linear_attention/gated_delta/"
     "while/body/dynamic_update_slice", "gated_delta"),
    (f"{BWD}/layer_2/linear_attention/gated_delta/transpose(jvp())/"
     "reduce_sum", "gated_delta"),
    (f"{FWD}/layer_0/linear_attention/qkv_conv/checkpoint/mul", "qkv_conv"),
    (f"{BWD}/layer_0/linear_attention/gated_norm/checkpoint/"
     "rematted_computation/rsqrt", "gated_norm"),
    (f"{FWD}/layer_1/linear_attention/q/dot_general", "linear_attention"),
    (f"{BWD}/layer_1/linear_attention/output/transpose", "linear_attention"),
    # attention and the FFN are the shared readers'
    (f"{FWD}/layer_3/attention/q/dot_general", "rest"),
    (f"{FWD}/layer_0/mlp/gate/dot_general", "rest"),
    # a scope of that name outside the module is not the operator's
    ("jit(f)/gated_delta/mul", "rest"),
    ("jit(train_step)/optimizer/add", "rest"),
    (None, "rest"),
])
def test_labels_by_scope(op_name, want):
    assert olmo_hybrid_trace.label("%fusion.7", op_name) == want


def test_kernels_are_told_by_name_whatever_their_scope():
    label = olmo_hybrid_trace.label
    assert label("%flash_causal_fwd.3", None) == "causal_kernels"
    assert label("%flash_causal_bwd.1", f"{FWD}/layer_3/attention/x") \
        == "causal_kernels"


def test_hand_made_events_reduce_to_parts():
    scope = {
        "%fusion.1": f"{FWD}/layer_0/linear_attention/gated_delta/while/body/"
                     "dot_general",
        "%fusion.2": f"{FWD}/layer_0/linear_attention/qkv_conv/mul",
        "%fusion.3": f"{FWD}/layer_0/linear_attention/q/dot_general",
        "%fusion.4": f"{FWD}/layer_0/mlp/gate/dot_general",
        "%fusion.5": f"{FWD}/layer_0/linear_attention/gated_norm/mul",
        "%while.1": f"{FWD}/layer_0/linear_attention/gated_delta/while",
    }
    ops = {0: [("%while.1", 0, 400), ("%fusion.1", 0, 100),
               ("%fusion.1", 150, 250),
               ("%flash_causal_fwd.3", 400, 600), ("%fusion.2", 600, 650),
               ("%flash_causal_bwd.1", 650, 850), ("%fusion.3", 850, 900),
               ("%fusion.4", 900, 980), ("%fusion.5", 980, 1000)]}
    modules = {0: [("jit_train_step(7)", 0, 1000)]}
    found = olmo_hybrid_trace.reduce(ops, modules, (0, 1000), 2,
                                     lambda program: scope)
    to_ms = 1e-6 / 2
    assert found["causal_kernels"] == pytest.approx(400 * to_ms)
    # the loop's own bookkeeping is the scan's too: 200 in its body, 200 not
    assert found["gated_delta"] == pytest.approx(400 * to_ms)
    assert found["qkv_conv"] == pytest.approx(50 * to_ms)
    assert found["gated_norm"] == pytest.approx(20 * to_ms)
    assert found["linear_attention"] == pytest.approx(50 * to_ms)
    assert found["rest"] == pytest.approx(80 * to_ms)
    assert sum(found.values()) == pytest.approx(1000 * to_ms)
    assert olmo_hybrid_trace.reduce({}, {}, (0, 0), 2, lambda p: {}) is None


def _fed_telemetry():
    from ml_recipe_tpu.train.telemetry import TrainTelemetry

    telemetry = TrainTelemetry()
    for step, (decay, beta) in enumerate(
            ((0.82, 0.999), (0.83, 1.0), (0.81, 1.001))):
        telemetry.observe_step(step, data_wait_s=0.001, host_s=0.007,
                               device_s=0.56, host_overlapped=True)
        telemetry.observe_scalars({"linear_decay_mean": decay,
                                   "linear_beta_mean": beta})
    return telemetry


class _Stretch:
    all_tokens, steps = 3 * 512, 3


def _ctx(fixture, cell=CELL, **more):
    from perfbench.harness import device

    path = str(BENCH / "fixtures" / fixture)
    return {"cell": manifest.load_cell(cell),
            "trace": tr.load(path, "modules"),
            "trace_file": path, "trace_steps": 3, "chips": 1, "train": True,
            "trace_shapes": [(2, 256)] * 3, "seq_len": 256,
            "peaks": device.peaks("TPU v5 lite"), "token_rate_chip": 9_000.0,
            "micro_rows_chip": 2, "stretch": _Stretch,
            "memory_peak_bytes": 15_000_000_000,
            "compile": {"setup": {"seconds": 1.0}, "window_compiles": 0},
            **more}


def test_the_counters_reach_the_registry_under_their_names():
    registry = _fed_telemetry().registry
    for name, median in (("train_linear_decay_mean", 0.82),
                         ("train_linear_beta_mean", 1.0)):
        series = registry.get(name)
        assert series is not None and series.count == 3
        assert series.quantile(0.5) == pytest.approx(median, abs=0.3)
    assert registry.get("train_moe_held_assignments").count == 0


@pytest.mark.parametrize("fixture, maps, cell", [
    ("tiny.xplane.pb", "joyai_tiny.scope_map.json", "base-train-full512"),
    ("joyai_tiny.xplane.pb", "joyai_tiny.scope_map.json",
     "joyai-ep16-train-seq4096"),
    ("lfm2_tiny.xplane.pb", "lfm2_tiny.scope_map.json",
     "lfm2-ep4-train-seq8192"),
])
def test_another_configurations_program_reads_as_nothing_for_the_new_readers(
        fixture, maps, cell, monkeypatch):
    """What the parent's programs give these readers, under their own cells'
    configurations: no ``linear_attention`` layer, so nothing, and no
    exception (the causal kernels of joyai's and lfm2's programs are their own
    readers')."""
    from ml_recipe_tpu.metrics import trace as program_trace

    scope_maps = json.loads((BENCH / "fixtures" / maps).read_text())
    monkeypatch.setattr(program_trace, "scope_map",
                        lambda name: scope_maps.get(name, {}))
    ctx = _ctx(fixture, cell=cell)
    for name in NEW_READERS:
        read = importlib.import_module(f"perfbench.metrics.{name}").read
        assert read(ctx) is None, name
        assert read({}) is None, name
    assert ctx["olmo_hybrid_table"] is None
    assert not set(NEW_READERS) & set(read_per_layer(ctx["cell"], ctx))


def test_the_new_cells_readers_under_a_program_without_the_scopes(monkeypatch):
    """The new cell's configuration over a program that has no such module
    (what a parent that lacks the operator would trace): the scope readers
    find nothing and raise nothing."""
    from ml_recipe_tpu.metrics import trace as program_trace

    monkeypatch.setattr(program_trace, "scope_map", lambda name: {})
    ctx = _ctx("tiny.xplane.pb")
    for name in ("linear_attn_block_ms_step", "gated_delta_ms_step",
                 "gated_delta_roofline", "olmo_attn_ms_step",
                 "olmo_attn_roofline"):
        read = importlib.import_module(f"perfbench.metrics.{name}").read
        assert read(ctx) is None, name
    assert set(ctx["olmo_hybrid_table"]) == {"rest"}


@pytest.fixture
def recorded(monkeypatch):
    """``fixtures/olmo_hybrid_tiny.xplane.pb`` (three calls of a two-layer
    ``train_step`` with the chunked scan, its convolutions and gated norm,
    the causal kernels and ``remat``, recorded on the v5e by
    ``fixtures/record_fixture_olmo_hybrid.py``, PR 33) and the scope map that
    program gave, as the program would hand it over."""
    from ml_recipe_tpu.metrics import trace as program_trace

    maps = json.loads(
        (BENCH / "fixtures" / "olmo_hybrid_tiny.scope_map.json").read_text())
    monkeypatch.setattr(program_trace, "scope_map",
                        lambda name: maps.get(name, {}))
    ctx = _ctx("olmo_hybrid_tiny.xplane.pb",
               telemetry=_fed_telemetry().registry)
    return dict(ctx, busy=tr.busy_idle(ctx["trace"]))


# ms a step of the recorded fixture's parts (three calls): the profile's own
# events, nested ones subtracted, joined to the scope map; they add up to the
# device's self time in the window
RECORDED_MS = {
    "gated_delta": 0.245436, "qkv_conv": 0.019371, "gated_norm": 0.010163,
    "linear_attention": 0.014566, "causal_kernels": 0.020590,
    "rest": 0.086253}


def test_every_new_reader_reads_the_recorded_trace(recorded, capsys):
    from perfbench.harness.flops import roofline_seconds

    ctx = recorded
    cfg = ctx["cell"].config
    got = read_per_layer(ctx["cell"], ctx)
    assert set(NEW_READERS) <= set(got)
    assert {m["name"] for m in ctx["cell"].per_layer} == set(got)
    assert got["step_ms"]["value"] == pytest.approx(560.0)
    table = ctx["olmo_hybrid_table"]
    assert set(table) == set(RECORDED_MS)
    for part, ms in RECORDED_MS.items():
        assert table[part] == pytest.approx(ms, rel=1e-3), part
    assert sum(table.values()) == pytest.approx(
        ctx["busy"]["busy_s"] * 1e3 / 3, rel=1e-3)
    assert got["gated_delta_ms_step"]["value"] == pytest.approx(
        table["gated_delta"])
    assert got["olmo_attn_ms_step"]["value"] == pytest.approx(
        table["causal_kernels"])
    assert got["linear_attn_block_ms_step"]["value"] == pytest.approx(
        sum(table[k] for k in ("gated_delta", "qkv_conv", "gated_norm",
                               "linear_attention")))
    # the shares: the benchmark's own FLOP and byte functions over that time
    # (the cell's configuration has three scan layers and one of attention)
    seconds, bound = roofline_seconds(
        flops_olmo_hybrid.gated_delta_flops(cfg, 3 * 512, train=True),
        flops_olmo_hybrid.gated_delta_bytes(cfg, 3 * 512, train=True),
        ctx["peaks"])
    assert bound == "bytes"
    assert got["gated_delta_roofline"]["value"] == pytest.approx(
        100 * 3 * seconds / (table["gated_delta"] * 1e-3 * 3))
    least = 3 * roofline_seconds(
        flops_olmo_hybrid.causal_core_flops(cfg, 2, 256, train=True),
        flops_olmo_hybrid.causal_core_bytes(cfg, 2, 256, train=True),
        ctx["peaks"])[0]
    assert got["olmo_attn_roofline"]["value"] == pytest.approx(
        100 * least / (table["causal_kernels"] * 1e-3 * 3))
    per_token = flops_olmo_hybrid.matmul_flops_per_token(cfg, 256, train=True)
    assert got["olmo_hybrid_mfu_pct"]["value"] == pytest.approx(
        100 * 9_000.0 * per_token / 197e12)
    # (the cell's widths over a tiny program's times: the shares' sizes mean
    # nothing here, only that the readers reached their numbers)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert len([x for x in lines if "olmo_hybrid_table" in x]) == 1


def test_the_recorded_kernels_and_scopes(recorded):
    from perfbench.harness.joyai_trace import CAUSAL_KERNELS, load_named

    ops, modules = load_named(recorded["trace_file"])
    causal = [n for n, _, _ in ops[0] if CAUSAL_KERNELS.match(n)]
    # per call: the attention layer's forward, its second forward (remat)
    # and its one fused backward
    assert len(causal) == 3 * 3
    assert sorted(re.sub(r"[.\d]+$", "", n) for n in causal[:3]) == [
        "%flash_causal_bwd", "%flash_causal_fwd", "%flash_causal_fwd"]
    assert [m[0].split("(")[0] for m in modules[0]] == ["jit_train_step"] * 3
    maps = json.loads(
        (BENCH / "fixtures" / "olmo_hybrid_tiny.scope_map.json").read_text())
    scopes = set(maps["jit_train_step"].values())
    for scope in ("gated_delta", "qkv_conv", "gated_norm"):
        under = [s for s in scopes if f"/linear_attention/{scope}/" in s]
        assert any("transpose(" in s for s in under), scope
        assert any("transpose(" not in s for s in under), scope
    # the second forward and the chunks' loop are the scan's
    assert any("rematted_computation" in s and "/gated_delta/while/body/" in s
               for s in scopes)


# -- the comparison's own parts ---------------------------------------------------------

def test_the_operators_comparison_tells_one_rounding_from_a_lowered_state():
    import jax.numpy as jnp
    import numpy as np

    from perfbench.harness import checks_olmo_hybrid, reference_olmo_hybrid

    rng = np.random.default_rng(0)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    read = (jnp.asarray(unit(rng.normal(size=(1, 96, 2, 8))), jnp.bfloat16),
            jnp.asarray(unit(rng.normal(size=(1, 96, 2, 8))), jnp.bfloat16),
            jnp.asarray(rng.normal(size=(1, 96, 2, 16)), jnp.bfloat16),
            jnp.asarray(-np.exp(rng.normal(size=(1, 96, 2)) - 2), jnp.float32),
            jnp.asarray(rng.uniform(0.2, 1.8, size=(1, 96, 2)), jnp.float32))
    mask = jnp.ones((1, 96), jnp.int32).at[0, 80:].set(0)
    exact = reference_olmo_hybrid.delta_rule(*read)
    once = checks_olmo_hybrid.scan_report(
        read, exact.astype(jnp.bfloat16), mask)
    assert float(once["beyond_one_rounding_share"]) == 0.0
    assert float(once["largest_distance_in_roundings"]) <= 1.0
    twice = checks_olmo_hybrid.scan_report(
        read, (exact * 1.02).astype(jnp.bfloat16), mask)
    assert float(twice["beyond_one_rounding_share"]) > 0.5
    # padded positions do not count, whatever they hold
    junk = exact.astype(jnp.bfloat16).at[0, 80:].set(9.0)
    assert float(checks_olmo_hybrid.scan_report(read, junk, mask)[
        "beyond_one_rounding_share"]) == 0.0
    drift = checks_olmo_hybrid.input_drift(
        read, read[:4] + (read[4] / 2,), mask)
    assert float(drift["beta"]) == pytest.approx(1.0, rel=0.2)
    assert float(drift["q"]) == float(drift["g"]) == 0.0


def test_the_references_decay_is_exp_to_float32s_last_bits():
    import numpy as np

    from perfbench.harness.reference_olmo_hybrid import decay_of

    g = -np.exp(np.random.default_rng(0).uniform(
        np.log(1e-8), np.log(80.0), 100_000)).astype(np.float32)
    g[:2] = [0.0, -1e-30]
    got = np.asarray(decay_of(g), np.float64)
    want = np.exp(g.astype(np.float64))
    assert got[0] == got[1] == 1.0
    assert np.max(np.abs(got - want) / want) < 2e-7
    assert float(decay_of(np.float32(-200.0))) == 0.0


def test_train_own_check_binds_the_named_comparison_and_restores(monkeypatch):
    from perfbench.harness import checks_olmo_hybrid
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
    assert seen["bound"] is checks_olmo_hybrid.compare
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
         "3300000011", "--seconds", "2", "--trace", "1", "--rehearse"],
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
    assert said["batch_split"] == 1
    check = said["reference_check"]
    assert check["ok"] is True and check["failed_parts"] == []
    assert len(check["scan"]["layers"]) == 3
    assert len(check["scan"]["input_drift"]) == 3
    assert said["stretches"]["telemetry"]["steps"] >= 2
    assert said["run"]["seed"] == 3300000011      # more than 32 signed bits


def test_the_parent_refuses_the_new_preset_at_once():
    """What the driver's first try of the cell on the parent meets: the
    model parser's ``--model`` choices are the preset registry, so a tree
    without the preset exits from argument parsing."""
    from ml_recipe_tpu.config.parser import get_model_parser

    choices = next(a.choices for a in get_model_parser()._actions
                   if "--model" in a.option_strings)
    assert CONFIG["model"] in choices and "olmo-hybrid-tiny" in choices
    with pytest.raises(SystemExit):
        get_model_parser().parse_args(["--model", "no-such-preset"])
