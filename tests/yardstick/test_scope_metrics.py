"""Device time by scope (harness/scope_reduce.py) and its six readers: the
rules on hand-written op_names, the reduction on hand-made events and a
hand-made map, the recorded trace with no map, the readers found by name."""

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from perfbench.harness import manifest, scope_reduce  # noqa: E402
from perfbench.harness import trace_reduce as tr  # noqa: E402
from perfbench.harness.result import read_per_layer  # noqa: E402

BENCH = REPO / "perfbench"
CELLS = [w["name"] for w in json.loads(
    (REPO / "BENCHMARK.json").read_text())["workloads"]]
READERS = ("fwd_ms_step", "bwd_ms_step", "update_ms_step",
           "attn_block_ms_step", "mlp_ms_step", "scope_unattributed_pct")
BODY = "jit(train_step)/while/body/closed_call/forward_backward"


# -- the rules ---------------------------------------------------------------------

@pytest.mark.parametrize("op_name,want", [
    (f"{BODY}/jvp(QAModel)/transformer/layer_3/attention/query/dot_general",
     ("fwd", "attention", "query")),
    (f"{BODY}/transpose(jvp(QAModel))/transformer/layer_3/attention/add",
     ("bwd", "attention", "-")),
    (f"{BODY}/jvp(QAModel)/transformer/layer_0/attention/flash_fwd/flash_fwd"
     "/pallas_call", ("fwd", "flash_fwd", "flash_fwd")),
    (f"{BODY}/transpose(jvp(QAModel))/transformer/layer_0/attention/flash_bwd"
     "/reshape", ("bwd", "flash_bwd", "-")),
    (f"{BODY}/jvp(QAModel)/transformer/layer_1/mlp/Dropout_0/jit(_bernoulli)"
     "/jit(_uniform)/rng_bit_generator", ("fwd", "mlp", "Dropout_0")),
    (f"{BODY}/transpose(jvp(QAModel))/transformer/layer_1/mlp/layer_norm"
     "/reduce_sum", ("bwd", "mlp", "layer_norm")),
    (f"{BODY}/jvp(QAModel)/transformer/embeddings/word_embeddings/jit(_take)"
     "/gather", ("fwd", "embeddings", "word_embeddings")),
    (f"{BODY}/jvp(QAModel)/Dropout_0/jit(_bernoulli)/lt",
     ("fwd", "dropout", "-")),
    (f"{BODY}/transpose(jvp(QAModel))/position_outputs/dot_general",
     ("bwd", "heads", "-")),
    (f"{BODY}/jvp(loss)/jit(log_softmax)/reduce_max", ("fwd", "loss", "-")),
    (f"{BODY}/transpose(jvp(loss))/mul;{BODY}/transpose(jvp(loss))",
     ("bwd", "loss", "-")),
    (f"{BODY}/jvp(QAModel)/transformer/pooler/tanh", ("fwd", "heads", "-")),
    (f"{BODY}/jvp(QAModel)/transformer/convert_element_type",
     ("fwd", "other", "-")),
    ("jit(train_step)/while/body/closed_call/grad_accumulate/concatenate",
     ("update", "grad_accumulate", "-")),
    ("jit(train_step)/grad_clip/reduce_sum", ("update", "grad_clip", "-")),
    ("jit(train_step)/optimizer/grad_reduce/pad",
     ("update", "grad_reduce", "-")),
    ("jit(train_step)/optimizer/jit(_where)/select_n",
     ("update", "optimizer", "-")),
    ("jit(train_step)/step_metrics/mul", ("update", "step_metrics", "-")),
    ("jit(train_step)/while", None),
    ("jit(train_step)/while/body/dynamic_slice", None),
    ("params['transformer']['pooler']['bias']", None),
    ("", None),
    (None, None),
])
def test_rules_on_op_names(op_name, want):
    assert scope_reduce.classify(op_name) == want


def test_module_names_lose_their_id():
    assert scope_reduce.module_name(
        "jit_train_step(9701493265859229110)") == "jit_train_step"
    assert scope_reduce.module_name("jit__multi_slice") == "jit__multi_slice"


# -- the reduction on hand-made events ---------------------------------------------

STEP_MAP = {
    "%while.7": "jit(train_step)/while",
    "%fusion.1": f"{BODY}/jvp(QAModel)/transformer/layer_0/attention/query"
                 "/dot_general",
    "%fusion.2": f"{BODY}/transpose(jvp(QAModel))/transformer/layer_0/mlp"
                 "/intermediate/dot_general",
    "%fusion.3": "jit(train_step)/optimizer/add",
    # the kernel's real name: the loader has renamed the event, so no join
    "%flash_fwd.9": f"{BODY}/jvp(QAModel)/transformer/layer_0/attention"
                    "/flash_fwd/flash_fwd/pallas_call",
}


def _chip(update_end):
    return [("%while.7", 0, 100), ("%fusion.1", 10, 40),
            (scope_reduce.MOSAIC, 40, 50), ("%fusion.2", 50, 90),
            ("%fusion.3", 100, update_end),
            ("%copy.4", update_end, update_end + 10)]


def _two_chips():
    return tr.Trace(
        {0: _chip(130), 1: _chip(150)},
        {0: [("jit_train_step(123)", 0, 140)],
         1: [("jit_train_step(123)", 0, 160)]}, [], "modules")


def _maps(**programs):
    return lambda name: programs.get(name, {})


def test_hand_made_events_reduce_to_known_phases():
    found = scope_reduce.reduce(
        _two_chips(), 2, _maps(jit_train_step=STEP_MAP))
    ms = 1e-6 / 2                       # nanoseconds of two steps -> ms a step
    assert found["chips"] == 2 and found["steps"] == 2
    assert found["phases"]["fwd"] == pytest.approx(30 * ms)
    assert found["phases"]["bwd"] == pytest.approx(40 * ms)
    # two chips averaged: 30 ns and 50 ns of optimizer
    assert found["phases"]["update"] == pytest.approx(40 * ms)
    # the while's own 20 ns, the renamed Mosaic call, the unmapped copy
    assert found["phases"]["unattributed"] == pytest.approx(40 * ms)
    assert found["device_ms_step"] == pytest.approx(150 * ms)
    assert found["unattributed_pct"] == pytest.approx(100 * 40 / 150)
    assert found["mosaic_ms_step"] == pytest.approx(10 * ms)


def test_the_partition_adds_up_to_the_devices_self_time():
    trace = _two_chips()
    found = scope_reduce.reduce(trace, 1, _maps(jit_train_step=STEP_MAP))
    per_chip = [sum(tr.self_seconds(ops).values())
                for ops in trace.device_ops.values()]
    assert sum(found["phases"].values()) == pytest.approx(
        1e-6 * sum(per_chip) / len(per_chip), rel=1e-3)
    assert sum(found["phases"].values()) == pytest.approx(
        found["device_ms_step"])


def test_a_nested_while_counts_for_its_own_time_only():
    found = scope_reduce.reduce(
        _two_chips(), 1, _maps(jit_train_step=STEP_MAP))
    loose = {tuple(row[:-1]): row[-1] for row in found["unattributed_top"]}
    assert loose[("jit_train_step", "%while.7")] == pytest.approx(20e-6)
    assert loose[("jit_train_step", scope_reduce.MOSAIC)] == pytest.approx(
        10e-6)
    assert loose[("jit_train_step", "%copy.4")] == pytest.approx(10e-6)


def test_blocks_and_table_rows():
    found = scope_reduce.reduce(
        _two_chips(), 1, _maps(jit_train_step=STEP_MAP))
    assert found["blocks"]["attention"] == pytest.approx(30e-6)
    assert found["blocks"]["mlp"] == pytest.approx(40e-6)
    assert found["blocks"]["optimizer"] == pytest.approx(40e-6)
    assert "flash_fwd" not in found["blocks"], "the renamed call joins nothing"
    rows = {tuple(row[:-1]): row[-1] for row in found["table"]}
    assert rows == {("fwd", "attention", "query"): pytest.approx(30e-6),
                    ("bwd", "mlp", "intermediate"): pytest.approx(40e-6),
                    ("update", "optimizer", "-"): pytest.approx(40e-6)}


def test_an_instruction_is_looked_up_in_the_map_of_its_own_program():
    """``%fusion.1`` of another program is not the step's ``%fusion.1``."""
    ops = [("%fusion.1", 0, 10), ("%fusion.1", 20, 50)]
    modules = [("jit_convert_element_type(7)", 0, 10),
               ("jit_train_step(123)", 20, 50)]
    trace = tr.Trace({0: ops}, {0: modules}, [], "modules")
    found = scope_reduce.reduce(trace, 1, _maps(jit_train_step=STEP_MAP))
    assert found["phases"]["fwd"] == pytest.approx(30e-6)
    assert found["unattributed_top"] == [
        ["jit_convert_element_type", "%fusion.1", pytest.approx(10e-6)]]


def test_with_no_map_everything_is_unattributed():
    found = scope_reduce.reduce(_two_chips(), 1, _maps())
    assert [found["phases"][p] for p in scope_reduce.PHASES] == [0.0] * 3
    assert found["unattributed_pct"] == pytest.approx(100.0)
    assert found["blocks"] == {} and found["table"] == []


def test_no_device_event_or_no_step_reduces_to_nothing():
    assert scope_reduce.reduce(tr.Trace({}, {}, []), 3, _maps()) is None
    assert scope_reduce.reduce(_two_chips(), 0, _maps()) is None


# -- the readers, found by name ------------------------------------------------------

def _ctx(cell, trace, steps):
    return {"cell": cell, "trace": trace, "trace_steps": steps,
            "compile": {"setup": {"seconds": 1.0}, "window_compiles": 0}}


@pytest.mark.parametrize("workload", CELLS)
def test_recorded_trace_with_no_map_reads_zeros_and_a_hundred(workload):
    """The recorded program registered no scope map: numbers, not ``None``."""
    cell = manifest.load_cell(workload)
    trace = tr.load(str(BENCH / "fixtures" / "tiny.xplane.pb"), "modules")
    got = read_per_layer(cell, _ctx(cell, trace, 3))
    assert set(READERS) <= set(got)
    for name in READERS[:5]:
        assert got[name] == {"value": 0.0, "unit": "ms"}
    assert got["scope_unattributed_pct"] == {"value": 100.0, "unit": "%"}


@pytest.fixture
def registered_step(monkeypatch):
    from ml_recipe_tpu.metrics import trace as program_trace

    monkeypatch.setattr(program_trace, "_programs", {})
    monkeypatch.setattr(program_trace, "_scope_maps", {})
    text = "ENTRY %main.1 (p: f32[8]) -> f32[8] {\n" + "".join(
        f'  {name} = f32[8]{{0}} add(%p, %p), metadata={{op_name="{op}"}}\n'
        for name, op in STEP_MAP.items()) + "}\n"
    program_trace.register_program("jit_train_step", lambda: text)


@pytest.mark.parametrize("workload", CELLS)
def test_the_six_readers_join_through_the_programs_own_table(
        workload, registered_step, capsys):
    cell = manifest.load_cell(workload)
    ctx = _ctx(cell, _two_chips(), 1)
    got = read_per_layer(cell, ctx)
    want = {"fwd_ms_step": 30e-6, "bwd_ms_step": 40e-6,
            "update_ms_step": 40e-6, "attn_block_ms_step": 30e-6,
            "mlp_ms_step": 40e-6,
            "scope_unattributed_pct": 100 * 40 / 150}
    for name, value in want.items():
        assert got[name]["value"] == pytest.approx(value), name
    entries = [m for m in cell.per_layer if m["name"] in READERS]
    assert [m["name"] for m in entries] == list(READERS)
    for m in entries:
        assert (m["source"], m["better"], m["moves"]) == (
            "device_trace", "lower", "tokens_per_s_chip")
        assert "workloads" not in m
    # the table went out once, on an earlier line, whoever asked first
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    tables = [x["scope_table"] for x in lines if "scope_table" in x]
    assert len(tables) == 1
    assert tables[0]["programs"] == {"jit_train_step": len(STEP_MAP)}
    assert tables[0]["scope_map_s"] >= 0.0
    assert ctx["scope_table"]["phases"]["fwd"] == pytest.approx(30e-6)


@pytest.mark.parametrize("reader", READERS)
def test_a_reader_finds_nothing_without_a_trace(reader):
    import importlib

    read = importlib.import_module(f"perfbench.metrics.{reader}").read
    assert read({}) is None
    assert read({"trace": _two_chips(), "trace_steps": 0}) is None


def test_under_a_program_older_than_the_scope_map_the_readers_leave_out(
        monkeypatch):
    """The driver runs these readers over the parent's checkout too."""
    from ml_recipe_tpu.metrics import trace as program_trace

    monkeypatch.delattr(program_trace, "scope_map")
    cell = manifest.load_cell(CELLS[0])
    got = read_per_layer(cell, _ctx(cell, _two_chips(), 1))
    assert not set(READERS) & set(got)


# -- the real map of the tiny trainer's step -----------------------------------------

def test_the_tiny_trainers_own_map_sorts_into_all_three_phases(
        tmp_path, monkeypatch):
    """Every instruction of the real step text as one event of 1 us: the
    rules meet what jax and flax really write, not what the tests above
    imagine. Counts of instructions, no time of any device."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from ml_recipe_tpu.metrics import trace as program_trace

    sys.path.insert(0, str(REPO / "tests"))
    from test_trainer import _make_trainer

    monkeypatch.setattr(program_trace, "_programs", {})
    monkeypatch.setattr(program_trace, "_scope_maps", {})
    # names are metadata and no part of the compile cache's key: compile here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        trainer, _ = _make_trainer(tmp_path, batch_split=2,
                                   mesh_spec="data:2")
        trainer.train()
        step_map = program_trace.scope_map("jit_train_step")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    ops = [(name, 1000 * i, 1000 * i + 1000)
           for i, name in enumerate(sorted(step_map))]
    trace = tr.Trace({0: ops}, {0: [("jit_train_step(1)", 0, 1000 * len(ops))]},
                     [], "modules")
    found = scope_reduce.reduce(trace, 1, _maps(jit_train_step=step_map))
    for phase in scope_reduce.PHASES:
        assert found["phases"][phase] > 0, phase
    assert {"attention", "mlp", "embeddings", "heads", "loss", "optimizer",
            "grad_clip", "grad_accumulate"} <= set(found["blocks"])
    parts = {(row[1], row[2]) for row in found["table"]}
    assert {("attention", "query"), ("attention", "layer_norm"),
            ("mlp", "intermediate"), ("mlp", "Dropout_0")} <= parts
