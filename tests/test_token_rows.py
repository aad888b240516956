"""``ops/token_rows.py``: the token-side walk kernels, interpreted, against
``expert_ffn._rows_to_tokens`` and ``_combine_bwd``'s XLA dots (the oracle);
``kernel_mode``'s refusals; ``routed_experts`` whole in both forms; the
counters that say which form a program holds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ml_recipe_tpu.metrics import trace
from ml_recipe_tpu.ops import expert_ffn, kernel_forms
from ml_recipe_tpu.ops import token_rows as tr

T, K, TB = 64, 4, 16
HELD_EXPERTS, OF, FIRST = 4, 8, 2
# held experts a token picks (the rest it picks among the others): what the
# walk has to get right
HELD = {
    "zero_one_and_k_rows": lambda rng, n: rng.integers(0, K + 1, size=n),
    "every_token_holds_k": lambda rng, n: np.full(n, K),
    "no_token_past_depth_one": lambda rng, n: rng.integers(0, 2, size=n),
    "nothing_held": lambda rng, n: np.zeros(n, int),
}
DTYPES = ("bfloat16", "float32")


def _chunk(case: str, tokens: int = T, depth: int = K):
    """The first chunk of a routing in which token ``t`` picks ``HELD[case]``
    of the held experts and the rest of its ``depth`` slots elsewhere, in a
    seeded order (every token of ``every_token_holds_k`` overflows the first
    chunk: its later rows lie in a granule)."""
    rng = np.random.default_rng(len(case))
    picks = np.minimum(HELD[case](rng, tokens), depth)
    held_experts = np.arange(FIRST, FIRST + HELD_EXPERTS)
    others = np.setdiff1d(np.arange(OF), held_experts)
    chosen = np.stack([rng.permutation(np.concatenate([
        rng.permutation(held_experts)[:h],
        rng.permutation(others)[:depth - h]])) for h in picks])
    plan = expert_ffn.make_plan(
        jnp.asarray(chosen, jnp.int32), jnp.ones((tokens, depth)), FIRST,
        HELD_EXPERTS, OF)
    return plan, expert_ffn._chunk_of(plan, 0, plan.capacity)


def _operands(case: str, dtype: str):
    """Rows, weights, ``g`` and the chunk; the rows past the held ones hold
    NaN (no held slot reads them)."""
    rng = np.random.default_rng(len(case) + 1)
    width = 256 if dtype == "bfloat16" else 128
    plan, chunk = _chunk(case)
    rows = rng.normal(size=(plan.capacity, width))
    rows[int(plan.n_held):] = np.nan
    return (jnp.asarray(rows, dtype),
            jnp.asarray(rng.normal(size=(T, K)), jnp.float32),
            jnp.asarray(rng.normal(size=(T, width)), jnp.float32), chunk)


def _kernel(body: str, rows, weights, g, chunk):
    kw = dict(dtype=rows.dtype, interpret=True, tb=TB)
    packed, held = tr.pack(rows), expert_ffn._held(chunk)
    if body == "dot":
        return tr.token_rows_dot(g, packed, chunk.slot_row, held, **kw)
    out_dtype = jnp.float32 if body == "weighted_sum" else rows.dtype
    return tr.token_rows_sum(
        packed, chunk.slot_row, held,
        weights if body == "weighted_sum" else None, width=rows.shape[1],
        out_dtype=out_dtype, **kw)


def _oracle(body: str, rows, weights, g, chunk):
    if body == "weighted_sum":          # combine's forward
        return expert_ffn._rows_to_tokens(rows, chunk, weights)
    if body == "sum":                   # dispatch's backward
        return expert_ffn._rows_to_tokens(rows, chunk).astype(rows.dtype)
    return expert_ffn._combine_bwd(None, (rows, chunk), g)[1]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("case", sorted(HELD))
def test_the_sum_is_the_walks_to_the_bit(case, dtype):
    """``dispatch``'s backward: float32 adds in ascending depth, one rounding
    at the end: the same bits."""
    operands = _operands(case, dtype)
    got = np.asarray(_kernel("sum", *operands), np.float32)
    want = np.asarray(_oracle("sum", *operands), np.float32)
    assert not np.isnan(got).any()
    assert np.array_equal(got, want)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("case", sorted(HELD))
def test_the_weighted_sum_is_the_walks_to_a_rounding(case, dtype):
    """``combine``'s forward. The CPU's XLA fuses the walk's multiply and add
    into one rounding where it chooses (the chip does not: there the two
    forms agree to the bit, ``scripts/token_rows_on_chip.py``), so each
    depth may differ by a rounding of its product."""
    operands = _operands(case, dtype)
    got = np.asarray(_kernel("weighted_sum", *operands))
    want = np.asarray(_oracle("weighted_sum", *operands))
    rows, weights, _, chunk = operands
    held = np.asarray(chunk.slot_ok)
    terms = np.where(held[..., None], np.abs(
        np.asarray(rows, np.float32)[np.asarray(chunk.slot_row)]
        * np.asarray(weights)[..., None]), 0.0)
    assert not np.isnan(got).any()
    assert (np.abs(got - want) <= K * 2 ** -23 * terms.sum(axis=1)).all()
    assert not got[~held.any(axis=1)].any()


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("case", sorted(HELD))
def test_the_dot_is_the_xla_dots(case, dtype):
    operands = _operands(case, dtype)
    got = np.asarray(_kernel("dot", *operands))
    want = np.asarray(_oracle("dot", *operands))
    assert got.shape == (T, K) and not np.isnan(got).any()
    # the width is reduced in another order: float32 roundings apart
    assert np.allclose(got, want, rtol=1e-5, atol=1e-4)
    held = np.asarray(expert_ffn._held(operands[-1]))
    assert not got[np.arange(K)[None] >= held[:, None]].any()


def test_a_block_reads_its_slots_from_an_smem_block_of_several():
    """1,024 tokens of two slots in blocks of 128: four token blocks share a
    1,024-entry SMEM block of row indices, eight one of held counts; a
    block's next one lies in the same SMEM block or the one after."""
    tokens, depth, width = 1024, 2, 256
    walk = tr._plan(tokens, depth, width, 2, 128)
    assert (walk.idx_per, walk.held_per, walk.steps) == (4, 8, 8)
    plan, chunk = _chunk("zero_one_and_k_rows", tokens, depth)
    rows = jnp.asarray(np.random.default_rng(7).normal(
        size=(plan.capacity, width)), jnp.bfloat16)
    got = tr.token_rows_sum(tr.pack(rows), chunk.slot_row,
                            expert_ffn._held(chunk), width=width,
                            dtype=rows.dtype, out_dtype=rows.dtype,
                            interpret=True, tb=128)
    want = expert_ffn._rows_to_tokens(rows, chunk).astype(rows.dtype)
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))


@pytest.mark.parametrize("body", ["sum", "dot"])
@pytest.mark.parametrize("n_rows", [4, 8, 12])
def test_a_chunk_of_fewer_rows_than_a_wait_takes(n_rows, body):
    """A model's example at init routes a chunk of a few rows: the walk waits
    for them one by one, none past the chunk's rows."""
    tokens, depth, width = 16, 2, 128
    rng = np.random.default_rng(n_rows)
    held = rng.integers(0, depth + 1, size=tokens).astype(np.int32)
    held[held.cumsum() > n_rows] = 0        # each row held once
    slot_row = np.zeros((tokens, depth), np.int32)
    order = iter(rng.permutation(n_rows))
    for t in range(tokens):
        slot_row[t, :held[t]] = [next(order) for _ in range(held[t])]
    rows = rng.normal(size=(n_rows, width)).astype(np.float32)
    g = rng.normal(size=(tokens, width)).astype(np.float32)
    args = (jnp.asarray(slot_row), jnp.asarray(held))
    kw = dict(dtype=jnp.float32, interpret=True, tb=8)
    if body == "dot":
        got = np.asarray(tr.token_rows_dot(jnp.asarray(g),
                                           tr.pack(jnp.asarray(rows)), *args,
                                           **kw))
        want = np.where(np.arange(depth)[None] < held[:, None],
                        np.einsum("th,tjh->tj", g, rows[slot_row]), 0.0)
        assert np.allclose(got, want, rtol=1e-5, atol=1e-5)
        return
    got = np.asarray(tr.token_rows_sum(tr.pack(jnp.asarray(rows)), *args,
                                       width=width, out_dtype=jnp.float32,
                                       **kw))
    want = np.zeros((tokens, width), np.float32)
    for j in range(depth):      # float32 adds in ascending depth
        want = want + np.where((j < held)[:, None], rows[slot_row[:, j]], 0)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("dtype", DTYPES)
def test_pack_keeps_every_bit_of_a_row(dtype):
    rows = jnp.asarray(np.random.default_rng(3).normal(size=(24, 512)), dtype)
    packed = np.asarray(tr.pack(rows))
    assert packed.dtype == np.uint32
    words = packed.reshape(24, -1)
    if dtype == "float32":
        assert np.array_equal(words.view(np.float32), np.asarray(rows))
    else:   # element c low, c + width / 2 high
        bits = np.asarray(rows).view(np.uint16).astype(np.uint32)
        assert np.array_equal(words, bits[:, :256] | (bits[:, 256:] << 16))


# -- which form runs -----------------------------------------------------------


@pytest.mark.parametrize("shape, why", [
    ((8192, 8, 2304, "bfloat16"), None), ((8192, 4, 2048, "bfloat16"), None),
    ((8192, 8, 2048, "float32"), None),
    ((8192, 8, 2176, "bfloat16"), "not whole 128s"),    # 1,088 words
    ((8192, 8, 100, "float32"), "not whole 128s"),
    ((8192, 8, 256, "float16"), "rows of float16"),     # no bf16 halves
    ((8192, 8, 256, "int8"), "rows of int8"),
    ((12, 2, 256, "bfloat16"), "no token block"),
    ((8192, 64, 2 ** 16, "float32"), "no token block")])
def test_refusals(shape, why):
    got = tr.refusal(*shape)
    assert (got is None) if why is None else (why in got)


def test_the_cells_token_blocks():
    assert tr.token_block(8192, 8, 2304, 2) == 256       # mellum2
    assert tr.token_block(8192, 4, 2048, 2) == 256       # lfm2
    assert tr.token_block(8192, 8, 2048, 2) == 256       # joyai
    assert tr.token_block(8192, 8, 4608, 2) == 128


def test_kernel_mode_answers_from_what_it_can_see(monkeypatch):
    x = jnp.zeros((512, 256), jnp.bfloat16)
    slot_row = jnp.zeros((512, 4), jnp.int32)
    assert tr.kernel_mode(x, slot_row, True) is None    # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert tr.kernel_mode(x, slot_row, True) is None    # 8 devices here
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert tr.kernel_mode(x, slot_row, True) is False
    # a granule of the overflow loop keeps the XLA walk
    assert tr.kernel_mode(x, slot_row, False) is None
    # a row of 64 words
    assert tr.kernel_mode(x[:, :128], slot_row, True) is None


def test_under_a_shard_map_the_kernels_run_on_many_devices(monkeypatch):
    from jax.sharding import Mesh, PartitionSpec as P

    from ml_recipe_tpu.parallel.compat import shard_map

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seen = []

    def shard(x):
        seen.append(tr.kernel_mode(x, jnp.zeros((512, 4), jnp.int32), True))
        return x

    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    jax.eval_shape(shard_map(shard, mesh=mesh, in_specs=P("data"),
                             out_specs=P("data")),
                   jnp.zeros((1024, 256), jnp.bfloat16))
    assert seen == [False]


# -- the expert layer whole ----------------------------------------------------

TOKENS, TOP_K, HELD_EXPERTS, OF, H, F = 32, 2, 4, 8, 128, 128


def _layer(routing: str):
    rng = np.random.default_rng(5)
    if routing == "two_granules":   # every token picks held experts only
        chosen = rng.integers(2, 2 + HELD_EXPERTS, size=(TOKENS, TOP_K))
    else:
        chosen = np.stack([rng.permutation(OF)[:TOP_K]
                           for _ in range(TOKENS)])
    operands = (
        jnp.asarray(rng.normal(size=(TOKENS, H)), jnp.float32),
        jnp.asarray(rng.uniform(0.1, 1.0, size=(TOKENS, TOP_K)), jnp.float32),
        jnp.asarray(rng.normal(size=(HELD_EXPERTS, H, 2 * F)) * 0.1,
                    jnp.float32),
        jnp.asarray(rng.normal(size=(HELD_EXPERTS, F, H)) * 0.1, jnp.float32))
    plan = expert_ffn.make_plan(jnp.asarray(chosen, jnp.int32), operands[1],
                                2, HELD_EXPERTS, OF)
    return operands, plan


@pytest.mark.parametrize("routing", ["near_the_expectation", "two_granules"])
def test_routed_experts_in_both_forms(monkeypatch, routing):
    operands, plan = _layer(routing)
    weigh = jnp.asarray(np.random.default_rng(6).normal(size=(TOKENS, H)),
                        jnp.float32)

    def value_and_grads():
        return jax.value_and_grad(
            lambda *ops: jnp.sum(expert_ffn.routed_experts(*ops, plan)
                                 * weigh), argnums=(0, 1, 2, 3))(*operands)

    def traced_by(run):
        before = kernel_forms.traced()
        out = run()
        return out, kernel_forms.traced(since=before)["token_rows"]

    # the tally the pre-flight reports: a layer's three first-chunk walks
    # (combine, dispatch's backward, the weight gradient) and a granule's
    # four (its forward, recomputed, and the two backward walks)
    (want, want_grads), tally = traced_by(value_and_grads)
    assert tally == {"xla": 7}
    firsts = []
    monkeypatch.setattr(tr, "kernel_mode", lambda x, slot_row, first: (
        firsts.append(first) or (True if first else None)))
    (got, got_grads), tally = traced_by(value_and_grads)
    assert tally == {"kernel": 3, "xla": 4}
    assert set(firsts) == {True, False}
    # a rounding a depth apart where the CPU's XLA fuses a multiply-add
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name, a, b in zip(("x", "weights", "w_gate_up", "w_down"), got_grads,
                          want_grads):
        assert np.allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                           atol=1e-6), name


PROGRAM = """HloModule jit_step

%body (q: f32[8]) -> f32[8] {
  %q = f32[8]{0} parameter(0)
  %token_rows_sum.3 = f32[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/layer_0/mlp/combine/token_rows_sum/pallas_call"}
  %token_rows_sum = bf16[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(layer_0))/mlp/dispatch/token_rows_sum/pallas_call"}
  %token_rows_dot.12 = f32[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(layer_0))/mlp/combine/token_rows_dot/pallas_call"}
  %gather.7 = f32[8]{0} gather(%q), metadata={op_name="jit(step)/layer_0/mlp/combine/gather"}
  ROOT %token_rows_summed = f32[8]{0} negate(%q), metadata={op_name="x"}
}
"""


def test_token_rows_calls_tell_the_forms_apart():
    trace.register_program("jit_step_of_test_token_rows", lambda: PROGRAM)
    assert trace.kernel_calls("jit_step_of_test_token_rows")["token_rows"] == {
        "sum": 2, "dot": 1}
    # the XLA walks leave gathers and fusions, no call of their own
    assert trace.kernel_calls("jit_nobody_registered")["token_rows"] == {
        "sum": 0, "dot": 0}
