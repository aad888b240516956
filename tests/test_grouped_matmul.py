"""``ops/grouped_matmul.py``: the Mosaic kernels, interpreted, against
``jax.lax.ragged_dot`` and its ``jax.vjp`` (the oracle); the tile pick at the
published widths; ``kernel_mode``'s refusals; ``routed_experts`` whole in
both forms; the counter and the call count the mechanism brings."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ml_recipe_tpu.metrics import trace
from ml_recipe_tpu.ops import expert_ffn, kernel_forms
from ml_recipe_tpu.ops import grouped_matmul as gm

M, K, N, TM = 64, 128, 256, 16
# rows a group: what the walk has to get right
SIZES = {
    "an_empty_group": [10, 0, 22, 16],
    "a_group_smaller_than_a_tile": [16, 5, 16, 27],
    "a_boundary_inside_a_tile": [24, 24, 8, 8],
    "filler_past_the_last_group": [7, 13, 9, 6],
    "every_row_held": [16, 16, 16, 16],
    "empty_groups_at_both_ends": [0, 30, 0, 0],
    "nothing_held": [0, 0, 0, 0],
}
CALLS = ("fwd", "drows", "dweights")


def _ragged(rows, weights, sizes):
    return jax.lax.ragged_dot(rows, weights, sizes,
                              preferred_element_type=rows.dtype)


@functools.lru_cache(maxsize=None)
def _both_forms(case: str, dtype: str):
    """``{call: (kernel form, ragged_dot form)}`` on one set of operands."""
    rng = np.random.default_rng(len(case))
    dt = jnp.dtype(dtype)
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    rows = jnp.asarray(rng.normal(size=(M, K)), dt)
    weights = jnp.asarray(rng.normal(size=(len(SIZES[case]), K, N)) * 0.1, dt)
    cotangent = jnp.asarray(rng.normal(size=(M, N)), dt)

    def outputs(matmul):
        out, vjp = jax.vjp(lambda r, w: matmul(r, w, sizes), rows, weights)
        return dict(zip(CALLS, (out, *vjp(cotangent))))

    kernel = outputs(lambda r, w, s: gm._kernels(r, w, s, TM, True))
    ragged = outputs(_ragged)
    return {call: (np.asarray(kernel[call], np.float32),
                   np.asarray(ragged[call], np.float32)) for call in CALLS}


@pytest.mark.parametrize("call", CALLS)
@pytest.mark.parametrize("case", sorted(SIZES))
def test_the_kernels_give_what_ragged_dot_gives(case, call):
    got, want = _both_forms(case, "bfloat16")[call]
    assert got.shape == want.shape and not np.isnan(got).any()
    # one float32 accumulation, one rounding: at most a bf16 step apart
    assert np.abs(got - want).max() <= 2 ** -7 * max(np.abs(want).max(), 1e-9)
    held = sum(SIZES[case])
    if call != "dweights":      # rows past the last group: zeros
        assert not got[held:].any() and not want[held:].any()
    else:                       # an empty group: a zero gradient
        for group, size in enumerate(SIZES[case]):
            assert size or not got[group].any()


@pytest.mark.parametrize("call", CALLS)
def test_the_kernels_in_float32(call):
    got, want = _both_forms("filler_past_the_last_group", "float32")[call]
    assert np.allclose(got, want, rtol=1e-5, atol=1e-5)


def test_filler_rows_add_nothing_to_the_weight_gradient():
    """The cotangent's rows past the last group are not zero here."""
    sizes = jnp.asarray([5, 9, 0, 3], jnp.int32)
    rng = np.random.default_rng(3)
    rows = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    weights = jnp.asarray(rng.normal(size=(4, K, N)), jnp.float32)
    cot = jnp.asarray(rng.normal(size=(M, N)), jnp.float32)
    _, vjp = jax.vjp(lambda w: gm._kernels(rows, w, sizes, TM, True), weights)
    held = jnp.arange(M)[:, None] < 17
    _, vjp_held = jax.vjp(lambda w: gm._kernels(
        jnp.where(held, rows, 7.0), w, sizes, TM, True), weights)
    assert np.array_equal(np.asarray(vjp(cot)[0]),
                          np.asarray(vjp_held(jnp.where(held, cot, -3.0))[0]))


@pytest.mark.parametrize("tm", [8, 16, 32, 64])
def test_the_walk_visits_each_tile_of_each_group_once(tm):
    sizes = jnp.asarray([10, 0, 22, 16, 0], jnp.int32)
    for every_group in (False, True):
        offsets, group, tile, n_active = (np.asarray(x) for x in gm._visits(
            sizes, M, tm, every_group))
        assert len(group) == len(tile) == M // tm + len(sizes) - 1
        want = [(g, t) for g in range(5) for t in range(M // tm)
                if min(offsets[g + 1], (t + 1) * tm) > max(offsets[g], t * tm)]
        if every_group:
            want = sorted(want + [(1, 10 // tm), (4, 48 // tm)])
        n = int(n_active[0])
        assert list(zip(group[:n], tile[:n])) == want
        spare = list(tile[n:])
        if every_group:     # the last step again: no block moves
            assert set(zip(group[n:], spare)) <= {want[-1]}
        else:               # the tiles no held row reaches, then the last
            reached = -(-48 // tm)
            unreached = list(range(reached, M // tm))
            assert spare[:len(unreached)] == unreached
            assert set(spare[len(unreached):]) <= {M // tm - 1}


# -- tiles ---------------------------------------------------------------------

PUBLISHED = {   # cell: hidden, the experts' width, rows an expert expects
    "mellum2": (2304, 896, 1024), "lfm2": (2048, 1792, 1024),
    "joyai": (2048, 768, 256)}


@pytest.mark.parametrize("cell", sorted(PUBLISHED))
def test_every_tile_divides_a_published_width_and_none_pads(cell):
    H, F, expected = PUBLISHED[cell]
    for k, n in ((H, 2 * F), (F, H)):
        tiles = {"fwd": (k, gm.width_tile(k, n, 2)),
                 "drows": (n, gm.width_tile(n, k, 2)),
                 "dweights": gm.gradient_tiles(k, n)}
        for call, (t_in, t_out) in tiles.items():
            width_in, width_out = (n, k) if call == "drows" else (k, n)
            assert width_in % t_in == 0 and width_out % t_out == 0, call
            assert t_in % 128 == 0 and t_out % 128 == 0, call
        assert tiles["fwd"][1] * k * 2 <= gm._WEIGHT_BLOCK
        assert np.prod(tiles["dweights"]) <= gm._ACCUMULATOR
        assert gm.refusal(4096, k, n, gm.row_tile(4096, expected), 2) is None
    assert gm.width_tile(2304, 1792, 2) == 896      # 4.1 MB a block
    assert gm.gradient_tiles(2304, 1792) == (1152, 896)


@pytest.mark.parametrize("rows, expected, want", [
    (24576, 1024, 256), (12288, 1024, 256), (6144, 256, 128),
    (4096, 1024, 256), (1024, 256, 128), (24576, 4096, 512),
    (48, 8, 16), (8, 8, 8), (100, 50, None)])
def test_the_row_tile_follows_the_rows_an_expert_expects(rows, expected, want):
    assert gm.row_tile(rows, expected) == want


@pytest.mark.parametrize("shape, tm, why", [
    ((4096, 100, 256), 256, "not multiples of 128"),
    ((4096, 256, 100), 256, "not multiples of 128"),
    ((4096, 128 * 200, 256), 256, "MiB of VMEM"),
    ((4096 + 8, 256, 256), 8, "no row tile of 128"),
    ((100, 256, 256), None, "no row tile of 128"),
    ((4096, 2304, 1792), 256, None)])
def test_refusals(shape, tm, why):
    m, k, n = shape
    got = gm.refusal(m, k, n, tm, 2)
    assert (got is None) if why is None else (why in got)


def test_kernel_mode_answers_from_what_it_can_see(monkeypatch):
    rows = jnp.zeros((512, 256), jnp.bfloat16)
    weights = jnp.zeros((4, 256, 128), jnp.bfloat16)
    assert gm.kernel_mode(rows, weights, 128, 128) is None  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gm.kernel_mode(rows, weights, 128, 128) is None  # 8 devices here
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert gm.kernel_mode(rows, weights, 128, 128) is False
    assert gm.kernel_mode(rows, weights, 64, 128) is None
    assert gm.kernel_mode(rows[:, :100], weights[:, :100], 128, 128) is None
    # fewer rows than the four groups expect: a granule takes ragged_dot
    assert gm.kernel_mode(rows, weights, 128, 129) is None
    # ... and the entry then takes ragged_dot, whose values it returns
    sizes = jnp.asarray([100, 200, 0, 50], jnp.int32)
    got = gm.grouped_matmul(rows[:, :100] + 1, weights[:, :100] + 1, sizes)
    assert float(got[349, 0]) == 100.0 and float(got[350, 0]) == 0.0


def test_under_a_shard_map_the_kernels_run_on_many_devices(monkeypatch):
    from jax.sharding import Mesh, PartitionSpec as P

    from ml_recipe_tpu.parallel.compat import shard_map

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seen = []

    def shard(rows):
        seen.append(gm.kernel_mode(
            rows, jnp.zeros((4, 256, 128), rows.dtype), 128, 128))
        return rows

    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    jax.eval_shape(shard_map(shard, mesh=mesh, in_specs=P("data"),
                             out_specs=P("data")),
                   jnp.zeros((1024, 256), jnp.bfloat16))
    assert seen == [False]


# -- the expert layer whole ----------------------------------------------------

T, TOP_K, HELD, OF, H, F = 32, 2, 4, 8, 128, 128


def _layer(routing: str):
    rng = np.random.default_rng(5)
    if routing == "two_granules":   # every token picks held experts only
        chosen = rng.integers(2, 2 + HELD, size=(T, TOP_K))
    else:
        chosen = np.stack([rng.permutation(OF)[:TOP_K] for _ in range(T)])
    operands = (
        jnp.asarray(rng.normal(size=(T, H)), jnp.float32),
        jnp.asarray(rng.uniform(0.1, 1.0, size=(T, TOP_K)), jnp.float32),
        jnp.asarray(rng.normal(size=(HELD, H, 2 * F)) * 0.1, jnp.float32),
        jnp.asarray(rng.normal(size=(HELD, F, H)) * 0.1, jnp.float32))
    plan = expert_ffn.make_plan(jnp.asarray(chosen, jnp.int32), operands[1],
                                2, HELD, OF)
    return operands, plan


@pytest.mark.parametrize("routing", ["near_the_expectation", "two_granules"])
def test_routed_experts_in_both_forms(monkeypatch, routing):
    operands, plan = _layer(routing)
    trips = int(expert_ffn.routing_stats(plan)["moe_overflow_chunks"])
    assert trips == (2 if routing == "two_granules" else 0)
    weigh = jnp.asarray(np.random.default_rng(6).normal(size=(T, H)),
                        jnp.float32)

    def value_and_grads():
        return jax.value_and_grad(
            lambda *ops: jnp.sum(expert_ffn.routed_experts(*ops, plan) * weigh),
            argnums=(0, 1, 2, 3))(*operands)

    def traced_by(run):
        before = kernel_forms.traced()
        out = run()
        return out, kernel_forms.traced(since=before)["grouped_matmul"]

    # the tally the pre-flight reports: a layer's 14 kernel calls, or the 6
    # grouped_matmul calls that took ragged_dot (its backward is JAX's own)
    (want, want_grads), tally = traced_by(value_and_grads)
    assert tally == {"ragged_dot": 6}
    forms = []
    monkeypatch.setattr(
        gm, "kernel_mode", lambda *a: forms.append(a[2]) or True)
    (got, got_grads), tally = traced_by(value_and_grads)
    assert tally == {"kernel": 14}
    assert set(forms) == {16, 8}    # the first chunk's tile, a granule's
    assert float(got) == pytest.approx(float(want), rel=1e-4)
    for name, a, b in zip(("x", "weights", "w_gate_up", "w_down"), got_grads,
                          want_grads):
        assert np.allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                           atol=1e-5), name


def test_row_tile_fill_on_a_hand_made_plan():
    # 4 held experts' rows [0, 5) [5, 5) [5, 21) [21, 30) at a row tile of 8:
    # tiles 0 | - | 0 1 2 | 2 3 are visited, 6 x 8 rows for 30 held
    plan = expert_ffn.RoutingPlan(
        order=jnp.zeros((64,), jnp.int32),
        position=jnp.zeros((32, 2), jnp.int32),
        held=jnp.zeros((32, 2), bool), row_weight=jnp.zeros((64,)),
        offsets=jnp.asarray([0, 5, 5, 21, 30], jnp.int32),
        n_held=jnp.asarray(30, jnp.int32), capacity=48, granule=8)
    stats = expert_ffn.routing_stats(plan)
    assert float(stats["moe_row_tile_fill"]) == pytest.approx(30 / 48)
    # whole tiles, nothing cut: 1.0; nothing held: 1.0 too
    for offsets in ([0, 8, 8, 24, 32], [0, 0, 0, 0, 0]):
        even = expert_ffn.RoutingPlan(
            plan.order, plan.position, plan.held, plan.row_weight,
            jnp.asarray(offsets, jnp.int32),
            jnp.asarray(offsets[-1], jnp.int32), 48, 8)
        assert float(expert_ffn.routing_stats(even)["moe_row_tile_fill"]) == 1
    # the cells' tiles: 1,024 rows an expert -> 256, joyai's 256 -> 128
    assert gm.row_tile(np.gcd(24576, 4096), expert_ffn.Fraction(24576) / 1.5
                       / 16) == 256
    assert gm.row_tile(np.gcd(6144, 1024), 256) == 128


PROGRAM = """HloModule jit_step

%fused_computation (p: bf16[8]) -> bf16[8] {
  %p = bf16[8]{0} parameter(0)
  ROOT %negate.99 = bf16[8]{0} negate(%p), metadata={op_name="x"}
}

%body (q: bf16[8]) -> bf16[8] {
  %q = bf16[8]{0} parameter(0)
  %grouped_matmul_fwd.3 = bf16[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/layer_0/mlp/experts/grouped_matmul_fwd/pallas_call"}
  %grouped_matmul_drows = bf16[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(layer_0))/mlp/experts/grouped_matmul_drows/pallas_call"}
  %grouped_matmul_dweights.12 = bf16[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(layer_0))/mlp/experts/grouped_matmul_dweights/pallas_call"}
  %ragged-dot-metadata.7 = s32[8]{0} custom-call(%q), metadata={op_name="ragged-dot-none"}
  %ragged-dot-none.7 = bf16[8]{0} custom-call(%q), metadata={op_name="ragged-dot-none"}
  %fusion.1 = bf16[8]{0} fusion(%q), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/grouped_matmul_fwd_like"}
  ROOT %ragged-dot-none = bf16[8]{0} custom-call(%fusion.1), metadata={op_name="ragged-dot-none"}
}
"""


def test_grouped_matmul_calls_tell_the_two_forms_apart():
    trace.register_program("jit_step_of_test_grouped_matmul", lambda: PROGRAM)
    want = {"kernel": 3, "ragged_dot": 2}
    calls = trace.kernel_calls("jit_step_of_test_grouped_matmul")
    assert calls["grouped_matmul"] == want
    assert trace.kernel_calls("jit_nobody_registered")["grouped_matmul"] == {
        "kernel": 0, "ragged_dot": 0}
