"""The benchmark's data and arithmetic, without jax: the manifest and every
file it names, the FLOP and byte functions against hand-worked values, the
trace reduction on hand-made events, the seeded text, and that a new cell
needs new files only."""

import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from perfbench.harness import flops, manifest, textgen  # noqa: E402
from perfbench.harness import trace_reduce as tr  # noqa: E402

BENCH = REPO / "perfbench"
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
UNITS = {"tokens/s/chip", "ms", "s", "%", "GB", "count", "chunks/s"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_manifest_has_exactly_the_contract_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "perfbench/run.py"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    for path in MANIFEST["paths"]:
        assert (REPO / path).is_dir()


def test_names_units_and_sources():
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]), m
        assert UNIT.match(m["unit"]) and m["unit"] in UNITS, m
        assert m["better"] in ("lower", "higher"), m
        assert m["source"] in SOURCES, m
        assert set(m.get("workloads", CELLS)) <= set(CELLS), m
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1, m
    assert "setup_s" in names


def test_configs_and_cells_cross_reference():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert used == set(configs), "every configuration is used by some cell"
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["source"].startswith("https://") and len(c["why"]) <= 200
        body = json.loads((REPO / c["file"]).read_text())
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"] == []
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs)) and len(CELLS) == len(set(CELLS))
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200


@pytest.mark.parametrize("workload", CELLS)
def test_cell_loads_and_reports_what_the_contract_asks(workload):
    cell = manifest.load_cell(workload)
    assert (BENCH / "runners" / f"{cell.runner}.py").is_file()
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in cell.per_layer if m["moves"] in e2e]
    assert layer, "at least one per-layer metric where the moved one is"
    for m in cell.per_layer:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]
    job = cell.traffic["job"]
    assert job["rate_metric"] in e2e
    assert job["mesh"] == f"data:{cell.chips}"
    assert "rehearsal" in cell.traffic


@pytest.mark.parametrize("traffic,runner", [("nqmix", "train"),
                                            ("steady", "serve")])
def test_a_waiting_cell_is_whole(traffic, runner):
    """A traffic file whose cell is not in ``BENCHMARK.json`` yet carries the
    manifest entries the cell needs and says what stopped it: every metric
    has its reader, names and units hold the contract's rules."""
    body = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    extra = body["manifest"]
    assert body["runner"] == runner and "PERF.md" in body["not_a_cell_yet"]
    assert (BENCH / "runners" / f"{runner}.py").is_file()
    assert extra["workload"]["traffic"] == traffic
    assert extra["workload"]["name"] not in CELLS
    assert len(extra["workload"]["why"]) <= 200
    moved = {m["name"] for m in extra["end_to_end"]} | {"setup_s"}
    for m in extra["end_to_end"] + extra["per_layer"]:
        assert NAME.match(m["name"]) and m["unit"] in UNITS
    for m in extra["per_layer"]:
        assert m["moves"] in moved
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    if runner == "serve":
        assert body["job"]["rate"] == pytest.approx(0.8 * body["job"]["knee"])
    else:
        assert body["job"]["rate_metric"] in moved


def test_every_file_under_paths_is_plainly_named():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in MANIFEST["paths"]:
        for f in (REPO / path).rglob("*"):
            rel = f.relative_to(REPO).as_posix()
            if "/.cache/" in rel or "__pycache__" in rel:
                continue
            assert ok.match(rel), rel


def test_layers_are_spelled_one_way():
    layers = {m["layer"] for m in MANIFEST["per_layer"]}
    text = (REPO / "PERF.md").read_text()
    for layer in layers:
        assert "\t" not in layer and len(layer) <= 200
        assert f"**{layer}**" in text, f"PERF.md's list of layers lacks {layer!r}"


# -- FLOPs and bytes: bert-base at 512, worked by hand ---------------------------

BASE = {"hidden_size": 768, "intermediate_size": 3072,
        "num_hidden_layers": 12, "num_attention_heads": 12}


def test_matmul_flops_per_token_bert_base_512():
    # per layer and token: projections 2*4*768^2 = 4,718,592; FFN
    # 2*2*768*3072 = 9,437,184; attention 4*512*768 = 1,572,864
    per_layer = 4_718_592 + 9_437_184 + 1_572_864
    assert flops.matmul_flops_per_token(BASE, 512, train=False) == 12 * per_layer
    assert flops.matmul_flops_per_token(BASE, 512, train=True) == 36 * per_layer
    # 289.9 GFLOP a 512-token example: bench.py's number for the same shape
    assert round(36 * per_layer * 512 / 1e9, 1) == 289.9


def test_attention_flops_and_bytes_bert_base_512():
    # one layer, 64 rows: one dot is 2*64*12*512*512*64 = 25,769,803,776
    dot = 25_769_803_776
    assert flops.attention_flops(64, 512, 12, 64, train=False) == 2 * dot
    assert flops.attention_flops(64, 512, 12, 64, train=True) == 6 * dot
    tensor = 64 * 512 * 12 * 64 * 2          # one [B, L, H, D] bf16 tensor
    assert flops.attention_bytes(64, 512, 12, 64, train=False) == 4 * tensor
    assert flops.attention_bytes(64, 512, 12, 64, train=True) == 12 * tensor
    peaks = {"bf16_tflops": 197.0, "hbm_gb_per_s": 819.0}
    t, bound = flops.roofline_seconds(6 * dot, 12 * tensor, peaks)
    assert bound == "flops" and t == pytest.approx(6 * dot / 197e12)
    assert 12 * tensor / 819e9 < t      # D = 64 at L = 512 is FLOP-bound


def test_an_unknown_device_kind_is_an_error():
    from perfbench.harness import device

    assert device.peaks("TPU v5 lite")["bf16_tflops"] == 197.0
    with pytest.raises(RuntimeError, match="no peaks on record"):
        device.peaks("TPU v9 imaginary")


# -- trace reduction on hand-made events -----------------------------------------

def _trace():
    ops0 = [("fusion.1", 0, 40), ("all-reduce.1", 40, 60),
            ("fused_bwd.2", 70, 100)]
    ops1 = [("fusion.1", 0, 50), ("all-reduce.1", 50, 60),
            ("fused_bwd.2", 60, 100)]
    host = [(tr.WINDOW_OPEN, 0, 0), (tr.WINDOW_CLOSE, 100, 100),
            ("bench:loader_next", 58, 72)]
    return tr.Trace({0: ops0, 1: ops1}, {}, host)


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tr.subtract([(0, 10), (20, 30)], [(2, 3), (5, 25)]) == [
        (0, 2), (3, 5), (25, 30)]
    assert tr.gaps([(2, 4)], 0, 10) == [(0, 2), (4, 10)]


def test_busy_idle_kernels_collectives_and_gaps():
    t = _trace()
    busy = tr.busy_idle(t)
    assert busy["chips"] == 2
    assert busy["busy_s"] == pytest.approx(95e-9)      # (90 + 100) / 2
    assert busy["window_s"] == pytest.approx(100e-9)
    assert busy["idle_pct"] == pytest.approx(5.0)
    assert tr.kernel_seconds(t, "fused_bwd") == pytest.approx(35e-9)
    coll = tr.collectives(t)
    assert coll["collective_s"] == pytest.approx(15e-9)
    assert coll["exposed_s"] == pytest.approx(15e-9)   # nothing ran beside them
    b = tr.breakdown(t)
    assert b["device_ops"][0] == ["fusion", pytest.approx(40e-9)]
    assert b["idle_gaps"][0] == ["waiting for data", pytest.approx(10e-9)]


def test_nested_operations_count_for_their_own_time_only():
    ops = [("%while.7", 0, 100), ("%fusion.1", 10, 40), ("%fusion.2", 50, 90),
           ("%tpu_custom_call.3", 60, 70)]
    assert tr.self_seconds(ops) == {"%while": 30.0, "%fusion": 60.0,
                                    "%tpu_custom_call": 10.0}
    assert tr.short("%fusion.12 = bf16[8]{0} fusion(%p.1), kind=kLoop") == "%fusion.12"
    t = tr.Trace({0: ops}, {0: [("jit_step(1)", 5, 95)]}, [], "modules")
    assert tr.window(t) if False else t.window() == (5, 95)
    assert tr.busy_idle(t)["idle_pct"] == pytest.approx(0.0)


def test_a_collective_inside_a_while_is_not_hidden_by_the_while():
    ops = [("%while.7", 0, 100), ("%fusion.1", 0, 60), ("%all-reduce.2", 60, 90),
           ("%fusion.3", 90, 100)]
    coll = tr.collectives(tr.Trace({0: ops}, {}, []))
    assert coll["collective_s"] == pytest.approx(30e-9)
    assert coll["exposed_s"] == pytest.approx(30e-9)
    assert [n for n, _, _ in tr.leaves(ops)] == [
        "%fusion.1", "%all-reduce.2", "%fusion.3"]


def test_collective_time_is_averaged_over_the_chips_that_have_any():
    t = tr.Trace({0: [("%fusion.1", 0, 80), ("%all-reduce.1", 80, 100)],
                  1: [("%fusion.1", 0, 60), ("%reduce-scatter.1", 60, 100)],
                  2: [("%fusion.1", 0, 100)]}, {}, [])
    coll = tr.collectives(t)
    assert coll["collective_s"] == pytest.approx(30e-9)
    assert coll["exposed_s"] == pytest.approx(30e-9)


def test_recorded_chip_trace_reduces_to_known_numbers():
    """``fixtures/tiny.xplane.pb``: three calls of a small program round the
    repo's Pallas attention, a 20 ms ``bench:loader_next`` pause before each,
    recorded on the v5e by ``fixtures/record_fixture.py`` (PR 22)."""
    path = BENCH / "fixtures" / "tiny.xplane.pb"
    t = tr.load(str(path))
    assert sorted(t.device_ops) == [0] and len(t.device_ops[0]) == 21
    assert [m[0] for m in t.device_modules[0]] == [
        "jit_tiny_step(9701493265859229110)"] * 3
    busy = tr.busy_idle(t)
    assert busy["busy_s"] == pytest.approx(7.053e-06, rel=1e-6)
    assert busy["window_s"] == pytest.approx(0.065139495, rel=1e-6)
    assert busy["idle_pct"] == pytest.approx(99.989172, abs=1e-5)
    # the Mosaic kernel, whatever XLA called it: three calls, 4.331 us
    assert tr.kernel_seconds(t, r"^%tpu_custom_call") == pytest.approx(
        4.331e-06, rel=1e-6)
    assert tr.collectives(t) is None
    b = tr.breakdown(t)
    assert b["device_ops"][0] == ["%tpu_custom_call", pytest.approx(4.331e-06)]
    assert b["device_ops"][1] == ["%fusion", pytest.approx(1.853e-06)]
    assert b["idle_gaps"][0] == ["waiting for data",
                                 pytest.approx(0.063260593, rel=1e-6)]
    whole_steps = tr.load(str(path), window_from="modules")
    assert tr.busy_idle(whole_steps)["window_s"] == pytest.approx(
        0.043399603, rel=1e-6)


def test_every_reader_of_a_train_cell_reads_the_recorded_trace():
    """The traced run's last step off the chip: the readers of both cells,
    found by name, over the recorded trace and a fed telemetry registry."""
    from ml_recipe_tpu.train.telemetry import TrainTelemetry
    from perfbench.harness import device
    from perfbench.harness.result import read_per_layer

    telemetry = TrainTelemetry()
    for step, device_s in enumerate((0.71, 0.72, 0.70)):
        telemetry.observe_step(step, data_wait_s=0.001, host_s=0.009,
                               device_s=device_s, host_overlapped=True)
    trace = tr.load(str(BENCH / "fixtures" / "tiny.xplane.pb"), "modules")
    for workload in CELLS:
        cell = manifest.load_cell(workload)
        ctx = {"cell": cell, "peaks": device.peaks("TPU v5 lite"),
               "trace": trace, "trace_steps": 3, "busy": tr.busy_idle(trace),
               "trace_shapes": [(2, 128)] * 3, "chips": 1, "train": True,
               "telemetry": telemetry.registry, "token_rate_chip": 180_000.0,
               "seq_len": 512, "memory_peak_bytes": 14_000_000_000,
               "compile": {"setup": {"seconds": 14.5}, "window_compiles": 0}}
        got = read_per_layer(cell, ctx)
        want = {m["name"] for m in cell.per_layer} - {
            "collective_ms_step", "collective_exposed_pct"}  # none in it
        assert set(got) == want, workload
        assert got["step_ms"]["value"] == pytest.approx(710.0)
        assert got["attn_ms_step"]["value"] == pytest.approx(4.331e-3 / 3)
        assert got["peak_hbm_gb"] == {"value": 14.0, "unit": "GB"}
        assert got["attn_roofline"]["value"] > 0     # not this model's kernel
        assert got["device_idle_pct"]["value"] == pytest.approx(99.98375, abs=1e-4)


def test_no_device_event_reads_as_nothing():
    t = tr.Trace({}, {}, [])
    assert tr.busy_idle(t) is None and tr.collectives(t) is None
    assert tr.breakdown(t) == {"device_ops": [], "idle_gaps": []}


# -- seeded text ---------------------------------------------------------------------

def test_vocabulary_is_a_function_of_the_seed(tmp_path):
    a = textgen.write_vocab(tmp_path / "a.txt", 3, 600)
    b = textgen.write_vocab(tmp_path / "b.txt", 3, 600)
    c = textgen.write_vocab(tmp_path / "c.txt", 4, 600)
    assert a == b != c
    lines = (tmp_path / "a.txt").read_text().split("\n")[:-1]
    assert len(lines) == 600 == len(set(lines))
    assert lines[:5] == ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"]


def test_corpus_is_nq_schema_and_heavy_tailed(tmp_path):
    words = textgen.vocab_words(0, 500)
    p = {"documents": 64, "median_words": 300, "sigma": 0.9, "min_words": 50,
         "max_words": 3000, "sentence_words": [5, 20],
         "short_answer_share": 0.35}
    stats = textgen.write_nq_corpus(tmp_path / "c.jsonl", 1, words, p)
    again = textgen.write_nq_corpus(tmp_path / "d.jsonl", 1, words, p)
    assert stats == again
    assert (tmp_path / "c.jsonl").read_bytes() == (tmp_path / "d.jsonl").read_bytes()
    lines = [json.loads(x) for x in (tmp_path / "c.jsonl").read_text().splitlines()]
    assert len(lines) == 64 and stats["words_max"] > 3 * stats["words_median"]
    for line in lines:
        tokens = line["document_text"].split()
        ann = line["annotations"][0]
        la = ann["long_answer"]
        assert tokens[la["start_token"]] == "<P>"
        assert tokens[la["end_token"] - 1] == "</P>"
        for sa in ann["short_answers"]:
            assert la["start_token"] < sa["start_token"] < sa["end_token"] <= la["end_token"]


def test_serve_requests_hit_the_chunk_counts_they_aim_at():
    from ml_recipe_tpu.data.chunking import window_chunks

    words = textgen.vocab_words(0, 500)
    mix = json.loads((BENCH / "traffic" / "steady.json").read_text())["job"]["mix"]
    reqs = textgen.serve_requests(2, words, mix, 200)
    assert reqs[0]["body"] == textgen.serve_requests(2, words, mix, 1)[0]["body"]
    bodies = {r["body"] for r in reqs}
    assert len(bodies) == len(reqs), "all documents distinct"
    for r in reqs:
        n_tokens = len(json.loads(r["body"])["document"].split())
        chunks = window_chunks(list(range(n_tokens)), ("unknown", -1, -1),
                               question_len=10, max_seq_len=mix["max_seq"],
                               doc_stride=mix["doc_stride"])
        assert len(chunks) == r["chunks"]
    share = sum(r["chunks"] <= 2 for r in reqs) / len(reqs)
    assert 0.35 < share < 0.65 and max(r["chunks"] for r in reqs) >= 9


# -- a new cell is new files only ------------------------------------------------------

def test_adding_a_cell_a_config_and_a_metric_edits_no_existing_file(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "perfbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    before = {p: p.read_bytes() for p in (root / "perfbench").rglob("*")
              if p.is_file()}
    cfg = json.loads((BENCH / "configs" / "bert-base-uncased.json").read_text())
    cfg.update(model="roberta-large", source="https://example.org/new.json")
    (root / "perfbench/configs/new-model.json").write_text(json.dumps(cfg))
    traffic = json.loads((BENCH / "traffic" / "full512.json").read_text())
    traffic["job"]["mesh"] = "data:4"
    (root / "perfbench/traffic/new-mix.json").write_text(json.dumps(traffic))
    (root / "perfbench/metrics/new_metric.py").write_text(
        "def read(ctx):\n    return ctx['answer']\n")
    new = json.loads(json.dumps(MANIFEST))
    new["configs"].append({"name": "new-model", "source": cfg["source"],
                           "file": "perfbench/configs/new-model.json",
                           "reduced": [], "why": "test"})
    new["workloads"].append({"name": "new-cell", "config": "new-model",
                             "traffic": "new-mix", "chips": 4, "why": "test"})
    new["per_layer"].append({"name": "new_metric", "unit": "count",
                             "better": "higher", "source": "program_counter",
                             "layer": "trainer", "moves": "tokens_per_s_chip",
                             "workloads": ["new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    code = (
        "import sys, json; sys.path.insert(0, sys.argv[1])\n"
        "from perfbench.harness.manifest import load_cell\n"
        "from perfbench.harness.result import read_per_layer\n"
        "cell = load_cell('new-cell')\n"
        "ctx = {'answer': 42, 'compile': {'setup': {'seconds': 1.5}, "
        "'window_compiles': 0}}\n"
        "print(json.dumps([cell.runner, cell.chips, cell.config['model'], "
        "read_per_layer(cell, ctx)]))\n")
    out = subprocess.run([sys.executable, "-c", code, str(root)], text=True,
                         capture_output=True, timeout=60, cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr
    runner, chips, model, metrics = json.loads(out.stdout.splitlines()[-1])
    assert (runner, chips, model) == ("train", 4, "roberta-large")
    assert metrics["new_metric"] == {"value": 42.0, "unit": "count"}
    assert metrics["compile_s"] == {"value": 1.5, "unit": "s"}
    assert "step_ms" not in metrics          # nothing to read: left out
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"
