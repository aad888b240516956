"""What ``remat`` keeps in the decoder trunk (``models/mla_moe.KeepDear``):
every matmul's output and every kernel call's outputs stay, the elementwise
stretches run again. On the CPU at the tiny ``olmo_hybrid`` preset, float32:
the loss and every gradient against whole-layer ``remat``'s and ``remat``
off's, bit for bit, with the XLA forms and with the Mosaic kernels
interpreted; what the gradient's jaxpr then holds (one forward call of each
kernel a layer, the matmuls of ``remat`` off); and the bytes the policy
tallies, by hand, where the pre-flight reports them.
"""

import dataclasses
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from ml_recipe_tpu.metrics import trace  # noqa: E402
from ml_recipe_tpu.models import MODEL_PRESETS, QAModel  # noqa: E402
from ml_recipe_tpu.models import mla_moe  # noqa: E402
from ml_recipe_tpu.ops import flash_causal, gated_delta  # noqa: E402

from test_mla_moe import make_trainer  # noqa: E402

TINY = MODEL_PRESETS["olmo-hybrid-tiny"]
# head widths the kernels take (the causal family: multiples of 64; the delta
# rule: multiples of 32) and a row of two causal blocks and four chunks
KERNEL_SIZED = dataclasses.replace(
    TINY, head_dim=64, linear_key_head_dim=32, linear_value_head_dim=32)
FORMS = {"xla": (TINY, 48), "kernels": (KERNEL_SIZED, 256)}
# remat, and the policy ``DecoderTrunk`` hands ``nn.remat`` (None: none, the
# whole layer is run again, as before the policy)
MODES = {"off": (False, mla_moe.REMAT_KEEPS), "whole": (True, None),
         "policy": (True, mla_moe.REMAT_KEEPS)}


def _loss_fn(form, mode, patch):
    """``(loss(params), params)`` of the trunk in ``form`` under ``mode``;
    ``patch`` is a ``MonkeyPatch`` that outlives every trace of it."""
    cfg, length = FORMS[form]
    remat, policy = MODES[mode]
    params = QAModel(cfg, attention_impl="xla").init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    patch.setattr(mla_moe, "REMAT_KEEPS", policy)
    if form == "kernels":       # after the init, which runs the layers eagerly
        patch.setattr(gated_delta, "kernel_mode", lambda *w: True)
        patch.setattr(flash_causal, "causal_attention", functools.partial(
            flash_causal.causal_attention, interpret=True))
    model = QAModel(cfg, attention_impl="pallas" if form == "kernels"
                    else "xla", remat=remat)
    rng = np.random.default_rng(3)
    ids = jnp.asarray(rng.integers(5, cfg.vocab_size, (2, length)), jnp.int32)
    mask = jnp.asarray(np.arange(length)[None, :] < np.asarray(
        [[length], [length - 7]]), jnp.int32)

    def loss(p):
        preds = model.apply({"params": p}, input_ids=ids, attention_mask=mask,
                            deterministic=True)
        # every head takes part, no two tokens weigh alike
        return sum(jnp.sum(jnp.sin(1.0 + jnp.arange(v.size, dtype=jnp.float32)
                                   ).reshape(v.shape)
                           * jnp.where(jnp.isfinite(v) & (v > -1e8), v, 0.0))
                   for v in jax.tree_util.tree_leaves(preds))

    return loss, params


@functools.lru_cache(maxsize=None)
def _loss_and_gradients(form, mode):
    with pytest.MonkeyPatch.context() as patch:
        loss, params = _loss_fn(form, mode, patch)
        return jax.device_get(jax.jit(jax.value_and_grad(loss))(params))


@pytest.mark.parametrize("other", ["off", "whole"])
@pytest.mark.parametrize("form", list(FORMS))
def test_the_policy_changes_when_a_value_exists_not_what_it_is(form, other):
    """A kept value is the value the second pass would have made again: the
    loss and every gradient are those of ``remat`` off and of whole-layer
    ``remat``, bit for bit."""
    loss, grads = _loss_and_gradients(form, "policy")
    want_loss, want_grads = _loss_and_gradients(form, other)
    assert np.isfinite(loss) and loss == want_loss
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert len(flat) > 60
    for (path, got), want in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        assert np.abs(want).max() > 0, path          # every leaf takes part
        assert np.array_equal(got, want), path


def _counts(jaxpr, counts=None):
    """Equations of ``jaxpr`` and of every jaxpr its equations hold, kernel
    bodies left out: ``dot_general`` and each ``pallas_call`` by its name."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            name = eqn.params["name"]
            counts[name] = counts.get(name, 0) + 1
            continue
        if name == "dot_general":
            counts[name] = counts.get(name, 0) + 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _counts(sub, counts)
    return counts


@functools.lru_cache(maxsize=None)
def _gradient_counts(mode):
    with pytest.MonkeyPatch.context() as patch:
        loss, params = _loss_fn("kernels", mode, patch)
        return _counts(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)


@pytest.mark.parametrize("mode, forward_calls", [
    ("off", 1), ("policy", 1), ("whole", 2)])
def test_the_gradient_calls_each_forward_kernel_once_a_layer(
        mode, forward_calls):
    """Under the policy the gradient holds ONE forward call of the delta rule
    and of the causal kernel a layer and the matmuls of ``remat`` off; with
    the whole layer run again it holds two and a layer's matmuls once more."""
    counts = _gradient_counts(mode)
    assert counts["gated_delta_fwd"] == 3 * forward_calls
    assert counts["flash_causal_fwd"] == forward_calls
    assert counts["gated_delta_bwd"] == 3
    assert counts["flash_causal_bwd"] == 1
    off = _gradient_counts("off")["dot_general"]
    # a layer's forward matmuls: six projections and the output (three and
    # the output in the attention layer), the FFN's three
    again = (3 * 10 + 7) * (forward_calls - 1)
    assert counts["dot_general"] == off + again


def _hand_count(cfg, tokens, itemsize=4):
    """Bytes of every matmul's output in one forward pass of the layers."""
    H, F = cfg.hidden_size, cfg.intermediate_size
    heads = cfg.linear_num_heads
    keys, values = (heads * cfg.linear_key_head_dim,
                    heads * cfg.linear_value_head_dim)
    ffn = F + F + H                                     # gate, up, down
    linear = 2 * keys + 2 * values + 2 * heads + H      # q k, v g, a b, output
    full = 4 * cfg.num_heads * (cfg.head_dim or H // cfg.num_heads)
    kinds = {"linear_attention": linear, "full_attention": full}
    return tokens * itemsize * sum(
        kinds[kind] + ffn for kind in cfg.layer_types)


@pytest.mark.parametrize("remat, split", [
    (False, 1), (True, 1), (True, 2)])
def test_the_preflight_reports_what_the_policy_kept(tmp_path, remat, split):
    """``kept_bytes`` beside ``split`` and ``verdict`` on the attempt's span
    and in the report: 0 with ``remat`` off, with it on the matmul outputs of
    a micro-batch's layers (on the CPU no kernel is called)."""
    trainer = make_trainer(tmp_path, batch_split=split, preset=TINY)
    trainer.model = dataclasses.replace(trainer.model, remat=remat)
    inputs, labels = next(iter(trainer.train_dataloader))
    trace.clear_record()
    report = trainer.preflight_train_step(inputs, labels, limit_bytes=10 ** 12)
    assert trainer.batch_split == split
    cfg = trainer.model.cfg
    want = _hand_count(cfg, 8 // split * 48) if remat else 0
    assert report["kept_bytes"] == want
    (attempt,) = [r for r in trace.recent("setup")
                  if r.name == "preflight_attempt"]
    assert attempt.args == {"split": split, "verdict": attempt.args["verdict"],
                            "kept_bytes": want}
    assert want == 0 or want == {1: 3_575_808, 2: 1_787_904}[split]


def test_only_the_causal_trunk_has_a_policy_to_tally():
    assert QAModel(TINY).remat_kept_bytes == mla_moe.REMAT_KEEPS.kept_bytes
    assert QAModel(MODEL_PRESETS["bert-tiny"]).remat_kept_bytes == 0
