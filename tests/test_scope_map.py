"""The scope map (metrics/trace.py): optimized HLO text -> instruction name
-> op_name, the process-global table of programs that yields it on demand,
and the names the train step and the attention kernels put into that text."""

import gc
import json
import re

import jax
import jax.numpy as jnp
import pytest

from ml_recipe_tpu.metrics import trace
from ml_recipe_tpu.ops import aot
from ml_recipe_tpu.train import Trainer

from test_trainer import _make_trainer

STEP_PHASES = ("forward_backward", "loss", "grad_accumulate", "grad_clip",
               "optimizer", "step_metrics")


@pytest.fixture(autouse=True)
def clean_table(monkeypatch):
    """Every test starts from an empty table and leaves none behind."""
    monkeypatch.setattr(trace, "_programs", {})
    monkeypatch.setattr(trace, "_scope_maps", {})


@pytest.fixture
def fresh_compiles():
    """Scope names are HLO metadata, and metadata is no part of the compile
    cache's key: an executable read from the persistent cache carries the
    names of whichever process compiled it first. A test that reads names
    out of a compiled program compiles it itself."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# -- the parser on a hand-written module -----------------------------------------

HAND_WRITTEN = '''HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %inner_mul.1 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(step)/forward_backward/jvp(M)/layer_0/mlp/mul" source_file="m.py" source_line=3}
  ROOT %inner_add.2 = f32[8]{0} add(%inner_mul.1, %param_0.1), metadata={op_name="jit(step)/forward_backward/jvp(M)/layer_0/mlp/add"}
}

%region_0.3 (a.1: f32[], b.1: f32[]) -> f32[] {
  %a.1 = f32[] parameter(0), metadata={op_name="jit(step)/grad_clip/reduce_sum"}
  %b.1 = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a.1, %b.1), metadata={op_name="jit(step)/grad_clip/reduce_sum"}
}

%while_body.4 (carry.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %carry.1 = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.5 = f32[8]{0} get-tuple-element(%carry.1), index=1
  %fusion.12 = f32[8]{0} fusion(%get-tuple-element.5), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/while/body/forward_backward/jvp(M)/layer_0/mlp/add" source_file="m.py" source_line=4}
  %multiply_reduce_fusion.3 = f32[8]{0} negate(%fusion.12), metadata={op_name="jit(step)/while/body/forward_backward/transpose(jvp(M))/layer_1/ln/reduce_sum"}
  %custom-call.7 = f32[8]{0} custom-call(%fusion.12), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/while/body/forward_backward/jvp(M)/layer_0/attention/flash_fwd/pallas_call"}
  ROOT %tuple.8 = (s32[], f32[8]{0}) tuple(%get-tuple-element.5, %multiply_reduce_fusion.3)
}

%while_cond.5 (carry.2: (s32[], f32[8])) -> pred[] {
  %carry.2 = (s32[], f32[8]{0}) parameter(0)
  ROOT %compare.6 = pred[] constant(true), metadata={op_name="jit(step)/while/cond/lt"}
}

ENTRY %main.20 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="params[\\'w\\']"}
  %while.7 = (s32[], f32[8]{0}) while(%Arg_0.1), condition=%while_cond.5, body=%while_body.4, metadata={op_name="jit(step)/while"}
  %reduce.11 = f32[] reduce(%Arg_0.1), dimensions={0}, to_apply=%region_0.3, metadata={op_name="jit(step)/grad_clip/reduce_sum"}
  ROOT %multiply_subtract_fusion.2 = f32[8]{0} subtract(%Arg_0.1, %Arg_0.1), metadata={op_name="jit(step)/optimizer/sub"}
}
'''


def test_parse_reads_entry_and_while_bodies():
    found = trace.parse_scope_map(HAND_WRITTEN)
    assert found["%fusion.12"] == \
        "jit(step)/while/body/forward_backward/jvp(M)/layer_0/mlp/add"
    assert found["%multiply_reduce_fusion.3"].endswith(
        "transpose(jvp(M))/layer_1/ln/reduce_sum")
    assert found["%custom-call.7"].endswith("attention/flash_fwd/pallas_call")
    assert found["%multiply_subtract_fusion.2"] == "jit(step)/optimizer/sub"
    assert found["%while.7"] == "jit(step)/while"
    assert found["%compare.6"] == "jit(step)/while/cond/lt"
    assert found["%Arg_0.1"] == r"params[\'w\']"


def test_parse_leaks_nothing_from_inside_a_fused_computation():
    found = trace.parse_scope_map(HAND_WRITTEN)
    assert "%inner_mul.1" not in found and "%inner_add.2" not in found
    assert "%param_0.1" not in found


def test_parse_skips_instructions_without_an_op_name():
    found = trace.parse_scope_map(HAND_WRITTEN)
    assert "%get-tuple-element.5" not in found and "%tuple.8" not in found
    assert trace.parse_scope_map("") == {}
    assert trace.parse_scope_map("HloModule empty\n") == {}


# -- the parser on what this installation really prints ---------------------------

def test_parse_finds_forward_backward_and_optimizer_of_a_flax_grad_step(
        fresh_compiles):
    import flax.linen as nn
    import optax

    class M(nn.Module):
        @nn.compact
        def __call__(self, x):
            for i in range(2):
                x = nn.LayerNorm(name=f"ln_{i}")(
                    nn.gelu(nn.Dense(16, name=f"layer_{i}")(x)))
            return x

    model, tx = M(), optax.adamw(1e-3)
    x = jnp.ones((4, 16))
    params = model.init(jax.random.key(0), x)["params"]

    def step(params, opt_state, x):
        with jax.named_scope("forward_backward"):
            grads = jax.grad(
                lambda p: jnp.sum(model.apply({"params": p}, x) ** 2))(params)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state

    text = jax.jit(step).lower(params, tx.init(params), x).compile().as_text()
    found = trace.parse_scope_map(text)
    assert found, "this installation's as_text() carries op_name metadata"
    scopes = list(found.values())
    assert any("forward_backward/jvp(M)/" in s for s in scopes)
    assert any("forward_backward/transpose(jvp(M))/" in s for s in scopes)
    assert any("jit(step)/optimizer/" in s for s in scopes)
    assert all(name.startswith("%") or name[0].isalpha() for name in found)
    # every key is a name the text really gives an instruction
    for name in list(found)[:50]:
        assert f"{name} = " in text


# -- the table --------------------------------------------------------------------

def test_nothing_registered_reads_as_an_empty_map():
    assert trace.scope_map("jit_train_step") == {}
    assert trace.registered_programs() == []


def test_the_thunk_is_never_called_unless_a_map_is_asked_for():
    def explode():
        raise AssertionError("nobody asked for a scope map")

    trace.register_program("jit_train_step", explode)
    assert trace.registered_programs() == ["jit_train_step"]
    assert trace.scope_map("jit_other_program") == {}


def test_the_thunk_is_called_once_and_the_map_memoised():
    calls = []

    def source():
        calls.append(1)
        return HAND_WRITTEN

    trace.register_program("jit_step", source)
    first = trace.scope_map("jit_step")
    assert first["%fusion.12"].endswith("mlp/add")
    assert trace.scope_map("jit_step") is first
    assert len(calls) == 1


@pytest.mark.parametrize("source", [
    lambda: None,
    lambda: "",
    lambda: (_ for _ in ()).throw(RuntimeError("no text to be had")),
], ids=["none", "empty", "raises"])
def test_a_text_that_cannot_be_had_is_an_empty_map_asked_for_once(source):
    calls = []

    def counted():
        calls.append(1)
        return source()

    trace.register_program("jit_step", counted)
    assert trace.scope_map("jit_step") == {}
    assert trace.scope_map("jit_step") == {}
    assert len(calls) == 1


def test_registering_again_replaces_the_entry_and_its_map():
    trace.register_program("jit_step", lambda: HAND_WRITTEN)
    assert "%fusion.12" in trace.scope_map("jit_step")
    other = HAND_WRITTEN.replace("%fusion.12", "%fusion.99")
    trace.register_program("jit_step", lambda: other)
    found = trace.scope_map("jit_step")
    assert "%fusion.99" in found and "%fusion.12" not in found


# -- the causal backward's counter -----------------------------------------------------

def _with_causal_calls(*names):
    """``HAND_WRITTEN`` with the while body's one Mosaic call replaced by
    calls of these names, as the TPU compiler writes them."""
    line = next(ln for ln in HAND_WRITTEN.splitlines()
                if ln.lstrip().startswith("%custom-call.7 = "))
    calls = "\n".join(
        line.replace("%custom-call.7", f"%{name}").replace(
            "flash_fwd/pallas_call", f"flash_bwd/{name.split('.')[0]}")
        for name in names)
    return HAND_WRITTEN.replace(line, calls)


@pytest.mark.parametrize("names, want", [
    (["flash_causal_fwd.1", "flash_causal_bwd.2", "flash_causal_bwd.13",
      "flash_causal_bwd"], {"fused": 3, "split": 0}),
    (["flash_causal_fwd", "flash_causal_bwd_dq.4", "flash_causal_bwd_dkv.5",
      "flash_causal_bwd_dq", "flash_causal_bwd_dkv"],
     {"fused": 0, "split": 2}),
    (["flash_causal_bwd.1", "flash_causal_bwd_dq.2",
      "flash_causal_bwd_dkv.3"], {"fused": 1, "split": 1}),
    (["custom-call.7"], {"fused": 0, "split": 0}),
], ids=["fused", "split", "both", "no_causal_kernel"])
def test_the_causal_backward_counter_reads_the_kernels_by_name(names, want):
    """One backward call is one ``flash_causal_bwd`` (fused) or one
    ``flash_causal_bwd_dq`` with its ``_dkv`` (split), whatever suffix the
    compiler gives the instruction (tests/test_chip_compile.py reads the
    same off a program compiled for the chip, lowered both ways)."""
    trace.register_program("jit_step", lambda: _with_causal_calls(*names))
    assert trace.kernel_calls("jit_step")["causal_backward"] == want
    assert trace.kernel_calls("jit_other")["causal_backward"] == {
        "fused": 0, "split": 0}


def test_on_map_runs_once_after_the_map_can_be_read():
    seen = []
    trace.register_program(
        "jit_step", lambda: _with_causal_calls("flash_causal_bwd.3"),
        lambda: seen.append(trace.kernel_calls("jit_step")["causal_backward"]))
    assert seen == []
    trace.scope_map("jit_step")
    trace.scope_map("jit_step")
    assert seen == [{"fused": 1, "split": 0}]


# -- the capture window -------------------------------------------------------------

def test_closing_an_xplane_window_leaves_scope_map_json(tmp_path, monkeypatch):
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    trace.register_program("jit_step", lambda: HAND_WRITTEN)
    window = trace.XplaneWindow(tmp_path, start=0, steps=1)
    window.started = True
    assert window.on_step_end(0, jnp.ones(2)) is True
    body = json.loads((tmp_path / "scope_map.json").read_text())
    assert body["jit_step"]["%multiply_subtract_fusion.2"] == \
        "jit(step)/optimizer/sub"


def test_a_window_with_no_registered_program_writes_no_file(
        tmp_path, monkeypatch):
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    window = trace.XplaneWindow(tmp_path, start=0, steps=1)
    window.started = True
    window.abort(jnp.ones(2))
    assert window.done and not (tmp_path / "scope_map.json").exists()


# -- the trainer's step --------------------------------------------------------------

def _scopes(found):
    """Every scope of every op_name, out of its ``transpose(jvp(...))``."""
    return {re.sub(r"^(?:\w+\()+([^()]*)\)+$", r"\1", c)
            for op_name in found.values() for c in op_name.split("/")}


def test_the_trainer_registers_its_step_and_lowers_only_when_asked(
        tmp_path, monkeypatch, fresh_compiles):
    trainer, _ = _make_trainer(tmp_path, batch_split=2, mesh_spec="data:2")
    lowered = []
    real = Trainer._train_step_hlo_text
    monkeypatch.setattr(Trainer, "_train_step_hlo_text",
                        lambda self: lowered.append(1) or real(self))
    trainer.train()
    assert trace.registered_programs() == ["jit_train_step"]
    assert lowered == [], "a run nobody traces lowers nothing"
    found = trace.scope_map("jit_train_step")
    assert len(lowered) == 1
    scopes = _scopes(found)
    assert set(STEP_PHASES) <= scopes
    assert any("/transpose(jvp(" in op_name for op_name in found.values())
    assert {"attention", "mlp", "embeddings", "layer_norm"} <= scopes
    trace.scope_map("jit_train_step")
    assert len(lowered) == 1


def test_before_the_first_step_the_trainer_has_no_text(tmp_path):
    trainer, _ = _make_trainer(tmp_path)
    trainer._jit_train_step = trainer._build_train_step()
    assert trainer._train_step_hlo_text() is None
    assert trace.scope_map("jit_train_step") == {}


def test_zero1_puts_the_gradient_exchange_under_grad_reduce(
        tmp_path, fresh_compiles):
    """Where the micro-batch loop runs as a data island the exchange is the
    sum of the chips' per-tensor carries after it, under ``grad_reduce``.
    (One micro-batch a step keeps the GSPMD body: there the partitioner
    names each collective after the backward op whose partial sums it
    finishes, and no instruction of its own is left under the scope.)"""
    trainer, _ = _make_trainer(tmp_path, mesh_spec="data:2", dropout=0.0,
                               batch_split=2, optimizer_sharding="zero1",
                               zero_min_size=0)
    trainer.train()
    found = trace.scope_map("jit_train_step")
    scopes = _scopes(found)
    assert "grad_reduce" in scopes and "optimizer" in scopes
    assert any("grad_reduce" in op_name for name, op_name in found.items()
               if re.match(r"%?(all-reduce|reduce-scatter)", name)), \
        sorted(n for n in found if "reduce" in n)


def test_a_rebuilt_step_registers_again_and_a_dropped_trainer_is_let_go(
        tmp_path):
    trainer, _ = _make_trainer(tmp_path, batch_split=1, mesh_spec="data:1")
    trainer.train()
    first = trace.scope_map("jit_train_step")
    assert first
    trainer._jit_train_step = trainer._build_train_step()   # as a raised split
    assert trace._scope_maps == {}, "the old program's map is dropped"
    assert trace.scope_map("jit_train_step") is not first
    trainer._jit_train_step = trainer._build_train_step()
    del trainer
    gc.collect()
    assert trace.scope_map("jit_train_step") == {}, \
        "the table holds the trainer weakly"


def test_with_the_aot_store_on_the_text_comes_from_the_held_executable(
        tmp_path, monkeypatch, fresh_compiles):
    monkeypatch.setattr(aot, "_device_kind", lambda: "FakeTPU v0")
    store = aot.reset()
    store.enabled = True
    store.set_cache_dir(tmp_path / "aot")
    try:
        trainer, _ = _make_trainer(tmp_path, mesh_spec="data:1")
        trainer.train()
        held = next(iter(trainer._compiled_steps.values()))
        monkeypatch.setattr(
            trainer, "_jit_train_step",
            type("NoLowering", (), {"lower": lambda *a, **k: 1 / 0})())
        assert trainer._train_step_hlo_text() == held.as_text()
        assert set(STEP_PHASES) <= _scopes(trace.scope_map("jit_train_step"))
    finally:
        aot.reset()


def test_entry_points_keep_whole_scope_paths_in_the_hlo(fresh_compiles):
    """``configure_compile_cache`` (conftest calls it as every entry point
    does) keeps locations to one frame through the frame limit. Switching
    ``jax_include_full_tracebacks_in_locations`` off, as it once did, leaves
    XLA ``add`` of ``jit(step)/optimizer/add``: pinned here, because the
    scope map has nothing to join by then."""
    assert jax.config.jax_traceback_in_locations_limit == 1
    assert jax.config.jax_include_full_tracebacks_in_locations is True

    def step(x):
        with jax.named_scope("optimizer"):
            return jnp.sin(x) + 1

    x = jnp.ones((4, 16))
    whole = trace.parse_scope_map(jax.jit(step).lower(x).compile().as_text())
    assert "jit(step)/optimizer/add" in whole.values()
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    try:
        short = trace.parse_scope_map(
            jax.jit(lambda x: step(x)).lower(x).compile().as_text())
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", True)
    assert "optimizer" not in _scopes(short)


# -- the kernels ---------------------------------------------------------------------

@pytest.mark.parametrize("regime", ["flash_attention", "flash_streaming"])
def test_the_attention_kernels_carry_flash_fwd_and_flash_bwd(
        regime, fresh_compiles):
    if regime == "flash_attention":
        from ml_recipe_tpu.ops.flash_attention import flash_attention as attn
        B, L, H, D = 2, 128, 2, 64
    else:
        from ml_recipe_tpu.ops.flash_streaming import (
            streaming_attention as attn,
        )
        B, L, H, D = 1, 256, 2, 64
    q = jnp.ones((B, L, H, D), jnp.float32)
    mask = jnp.ones((B, L), jnp.int32)

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v, mask, dtype=jnp.float32, interpret=True))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).compile().as_text()
    scopes = _scopes(trace.parse_scope_map(text))
    assert "flash_fwd" in scopes and "flash_bwd" in scopes
