"""Compile-only guard: the main path's attention kernels against the TPU's
own compiler, at bert-base width, with no chip.

The installed TPU compiler compiles for a chip that is described and not
attached (``topologies.get_topology_desc``). Interpret-mode tests cannot see
what it refuses — a lane slice off the tiling, more fast memory than a
kernel may use — so every regime the dispatcher can pick for bert-base is
compiled here at the geometry the analytic arithmetic returns. Nothing runs:
a pass says the kernel compiles, not that it is right or fast.

Compiled at ``_PROBE_BATCH`` (2), not 1: a one-step grid gets no second
pipeline buffer and the compiler under-reports scoped VMEM for it (the
finding behind ``flash_attention._PROBE_BATCH``).

The gated delta rule's kernels (``ops/gated_delta_kernel.py``) are compiled
at the one published shape that runs them, the grouped matmuls' kernels
(``ops/grouped_matmul.py``) and the token-side walks
(``ops/token_rows.py``) at the three MoE cells' widths. A last program is the dispatcher itself on a four-chip ``data`` mesh: GSPMD
refuses to partition a Mosaic kernel, so ``ops/attention.py`` has to
shard_map it — the failure a ``--mesh data:4`` run would otherwise meet at
its first compile.

One more program is Olmo-Hybrid-7B's four layers at their cell's row with
``remat`` on, forward and backward: what the trunk's policy keeps
(``models/mla_moe.KeepDear``) shows in the compiled program as the kernel
calls and matmuls that are left.

The compiles run concurrently once per module (the compiler releases the
GIL), each parametrised case reads its own verdict.
"""

import contextlib
import os
from concurrent.futures import ThreadPoolExecutor

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

from ml_recipe_tpu.ops import flash_attention as fa
from ml_recipe_tpu.ops import flash_streaming as fs

B, H, D, BF16, RATE = fa._PROBE_BATCH, 12, 64, jnp.bfloat16, 0.1


def _cases(shape):
    """name -> (pallas_call, argument shapes); ``shape(dims, dtype)`` makes a
    ShapeDtypeStruct on the described chip."""
    def seeds_mask(L):
        return [shape((B,), jnp.int32), shape((B, 1, L), jnp.int32)]

    def rows(L, n):
        return [shape((B, L, H * D), BF16)] * n

    def lse(L, blk):
        return [shape((B, L // blk, 1, H * blk), jnp.float32)]

    cases = {}
    L = 512
    # without lse: the eval/serve forward, which is deterministic (with
    # dropout the compiler refuses hc=12 here, 16.78M of 16M — no program
    # runs that pair, and on the chip the probe walks past it); with lse:
    # the training forward
    for want_lse, rate in ((False, 0.0), (True, RATE)):
        hc = fa._fused_fwd_analytic_hc(L, H, D, 2, 2, want_lse)
        cases[f"fused_fwd{'_lse' if want_lse else ''}-L512"] = (
            fa._build_fused_fwd_call(B, L, H, D, BF16, BF16, rate, hc,
                                     False, want_lse),
            seeds_mask(L) + rows(L, 3))
    for seg in (False, True):
        hc = fa._pick_head_chunk(
            H, D, fa._fused_bwd_bytes_per_head(L, D, 2, 2),
            (fa._FUSED_BWD_TEMPS + seg) * L * L * 4, fa._fused_bwd_budget())
        cases[f"fused_bwd{'_segmented' if seg else ''}-L512"] = (
            fa._build_fused_bwd_call(B, L, H, D, BF16, RATE, hc, False,
                                     seg=seg),
            seeds_mask(L) + rows(L, 5) + lse(L, L))
    L = 1024
    q_blk, hc = fa._blocked_bwd_cfg(L, H, D, 2, RATE, 2)
    cases["blocked_bwd-L1024"] = (
        fa._build_blocked_bwd_call(B, L, H, D, BF16, RATE, q_blk, hc, False),
        seeds_mask(L) + rows(L, 5) + lse(L, q_blk))
    # the analytic forward pick (256, 12) is the seq-1024 scoped-VMEM
    # failure of ROADMAP S1 (18.32M of 16M); the backward's geometry is the
    # largest the compiler takes for the forward too
    cases["blocked_fwd-L1024"] = (
        fa._build_blocked_fwd_call(B, L, H, D, BF16, BF16, RATE, q_blk, hc,
                                   False, True),
        seeds_mask(L) + rows(L, 3))
    L = 4096
    blk, hc = fs.streaming_cfg(L, H, D, 2, 2, RATE)
    base = [shape((B,), jnp.int32), shape((2,), jnp.int32),
            shape((B, 1, L), jnp.int32)]
    cases["stream_fwd-L4096"] = (
        fs._build_stream_fwd_call(B, L, H, D, BF16, BF16, RATE, blk, hc,
                                  False),
        base + rows(L, 3))
    cases["stream_dkv-L4096"] = (
        fs._build_stream_dkv_call(B, L, H, D, BF16, RATE, blk, hc, False),
        base + rows(L, 5) + lse(L, blk))
    # the two-width causal family at the published MLA widths (32 heads,
    # d_qk 192, d_v 128): the forward and the fused backward (the row's dq in
    # VMEM, so the call states its own limit) at the cell's length, and the
    # split backward at a length whose row passes the budget
    from ml_recipe_tpu.ops import flash_causal as fc

    heads, d_qk, d_v = 32, 192, 128

    def causal(L):
        pairs = fc._pairs(L // fc.pick_block(L), k_outer=False).shape[1]
        wide, narrow = (shape((B, heads, L, d), BF16) for d in (d_qk, d_v))
        stat = shape((B, heads, 1, L), jnp.float32)
        head = [shape((pairs,), jnp.int32)] * 2 + [shape((B, 1, L), jnp.int32)]
        return (head + [wide, wide, narrow],                  # q, k, v
                head + [wide, narrow, wide],                  # k, v, q
                [narrow, stat, stat])                         # g, lse, delta

    L = 4096
    q_major, kv_major, rest = causal(L)
    cases["causal_fwd-L4096"] = (
        fc.build_fwd_call(B, heads, L, d_qk, d_v, BF16, BF16), q_major)
    (bwd,) = fc.build_bwd_calls(B, heads, L, d_qk, d_v, BF16)
    cases["causal_bwd-L4096"] = (bwd, kv_major + rest)
    L = 16384
    q_major, kv_major, rest = causal(L)
    dq, dkv = fc.build_bwd_calls(B, heads, L, d_qk, d_v, BF16)
    cases["causal_dq-L16384"] = (dq, q_major + rest)
    cases["causal_dkv-L16384"] = (dkv, kv_major + rest)
    # the same family at grouped-query heads (32 query heads over 8 key/value
    # heads of 64: k and v enter with their own head count, dk and dv come
    # out a query head) at the longest row its fused backward takes
    L, kv_heads, d = 8192, 8, 64
    pairs = fc._pairs(L // fc.pick_block(L), k_outer=False).shape[1]
    per_q, per_kv = (shape((B, n, L, d), BF16) for n in (heads, kv_heads))
    stat = shape((B, heads, 1, L), jnp.float32)
    head = [shape((pairs,), jnp.int32)] * 2 + [shape((B, 1, L), jnp.int32)]
    group = heads // kv_heads
    cases["gqa_fwd-L8192"] = (
        fc.build_fwd_call(B, heads, L, d, d, BF16, BF16, group=group),
        head + [per_q, per_kv, per_kv])
    (bwd,) = fc.build_bwd_calls(B, heads, L, d, d, BF16, group=group)
    cases["gqa_bwd-L8192"] = (
        bwd, head + [per_kv, per_kv, per_q, per_q, stat, stat])
    # the window family at Mellum2's heads (32 query over 4 key/value heads of
    # 128, a window of 1,024) and its cell's row: the forward and the fused
    # backward, and the split pair at a row whose dq passes the budget
    from ml_recipe_tpu.ops import flash_window as fw

    kv_heads, d, window, group = 4, 128, 1024, 8

    def windowed(L):
        pairs = fw.block_pairs(L, window)[0]
        per_q, per_kv = (shape((B, n, L, d), BF16) for n in (heads, kv_heads))
        stat = shape((B, heads, 1, L), jnp.float32)
        head = [shape((pairs,), jnp.int32)] * 2 + [shape((B, 1, L), jnp.int32)]
        return (head + [per_q, per_kv, per_kv],
                head + [per_kv, per_kv, per_q, per_q, stat, stat],
                head + [per_q, per_kv, per_kv, per_q, stat, stat])

    L = 8192
    q_major, kv_major, _ = windowed(L)
    cases["window_fwd-L8192"] = (
        fw.build_fwd_call(B, heads, L, d, d, window, BF16, BF16, group=group),
        q_major)
    (bwd,) = fw.build_bwd_calls(B, heads, L, d, d, window, BF16, group=group)
    cases["window_bwd-L8192"] = (bwd, kv_major)
    L = 32768
    _, kv_major, dq_major = windowed(L)
    dq, dkv = fw.build_bwd_calls(B, heads, L, d, d, window, BF16, group=group)
    cases["window_dq-L32768"] = (dq, dq_major)
    cases["window_dkv-L32768"] = (dkv, kv_major)
    L = 8192
    # the gated delta rule's kernels at Olmo-Hybrid-7B's heads (30 of d_k 96,
    # d_v 192) and its cell's row: the forward that keeps no state, the one
    # that keeps a state a chunk, and the backward
    import functools

    from ml_recipe_tpu.ops import gated_delta, gated_delta_kernel as gdk

    heads, d_k, d_v, C = 30, 96, 192, gated_delta.CHUNK
    assert gdk.refusal(heads, L, d_k, d_v, C, 2) is None
    wide, narrow = (shape((B, heads, L, d), BF16) for d in (d_k, d_v))
    a_row = shape((B, heads, L // C, C), jnp.float32)
    states = shape((L // C, B, heads, d_k, d_v), jnp.float32)
    inputs = [wide, wide, narrow, a_row, a_row]
    cases["gated_delta_fwd-L8192"] = (
        functools.partial(gdk.forward, keep_states=False), inputs)
    cases["gated_delta_fwd_states-L8192"] = (
        functools.partial(gdk.forward, keep_states=True), inputs)
    cases["gated_delta_bwd-L8192"] = (gdk.backward,
                                      inputs + [states, narrow])
    # the expert layers' grouped matmuls (``ops/grouped_matmul.py``) at the
    # three MoE cells' widths and first chunks, each matmul with both its
    # gradients (three kernels a program), at the row tile the pick gives
    from ml_recipe_tpu.ops import grouped_matmul as gm

    for cell, (groups, hidden, width, rows, expected) in GROUPED.items():
        tm = gm.row_tile(rows, expected)
        for name, (k, n) in (("gate_up", (hidden, 2 * width)),
                             ("down", (width, hidden))):
            assert gm.refusal(rows, k, n, tm, 2) is None

            def all_three(r, w, sizes, g, tm=tm):
                out, vjp = jax.vjp(
                    lambda r, w: gm._kernels(r, w, sizes, tm, False), r, w)
                return (out, *vjp(g))

            cases[f"grouped_{name}-{cell}"] = (all_three, [
                shape((rows, k), BF16), shape((groups, k, n), BF16),
                shape((groups,), jnp.int32), shape((rows, n), BF16)])
    # the expert layers' token-side walks (``ops/token_rows.py``) at the three
    # MoE cells' first chunks, the rows packed as the layer packs them
    from ml_recipe_tpu.ops import token_rows as tr

    for cell, (tokens, top_k, hidden, rows) in TOKEN_ROWS.items():
        assert tr.refusal(tokens, top_k, hidden, BF16) is None
        operands = [shape((rows, hidden), BF16),
                    shape((tokens, top_k), jnp.int32),
                    shape((tokens,), jnp.int32)]

        def weighted(r, slot_row, held, w, hidden=hidden):
            return tr.token_rows_sum(tr.pack(r), slot_row, held, w,
                                     width=hidden, dtype=BF16,
                                     out_dtype=jnp.float32)

        def unweighted(r, slot_row, held, hidden=hidden):
            return tr.token_rows_sum(tr.pack(r), slot_row, held,
                                     width=hidden, dtype=BF16, out_dtype=BF16)

        def dots(r, slot_row, held, g):
            return tr.token_rows_dot(g, tr.pack(r), slot_row, held,
                                     dtype=BF16)

        cases[f"token_rows_sum-{cell}"] = (weighted, operands + [
            shape((tokens, top_k), jnp.float32)])
        cases[f"token_rows_sum_unweighted-{cell}"] = (unweighted, operands)
        cases[f"token_rows_dot-{cell}"] = (dots, operands + [
            shape((tokens, hidden), jnp.float32)])
    return cases


# cell: tokens of a micro-batch, top-k, hidden, rows of the first chunk
TOKEN_ROWS = {"mellum2": (8192, 8, 2304, 24576), "lfm2": (8192, 4, 2048, 12288),
              "joyai": (8192, 8, 2048, 6144)}
# cell: experts held, hidden, the experts' width, rows of the first chunk,
# rows an expert expects
GROUPED = {"mellum2": (16, 2304, 896, 24576, 1024),
           "lfm2": (8, 2048, 1792, 12288, 1024),
           "joyai": (16, 2048, 768, 6144, 256)}


def _sharded_attention_case(topo):
    """fwd + q/k/v grads of ``dot_product_attention(impl='pallas')`` with the
    batch sharded over a four-chip ``data`` mesh."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ml_recipe_tpu.ops.attention import dot_product_attention

    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    rows = NamedSharding(mesh, P("data"))
    L = 512
    q = jax.ShapeDtypeStruct((4 * B, L, H, D), BF16, sharding=rows)
    mask = jax.ShapeDtypeStruct((4 * B, L), jnp.int32, sharding=rows)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=NamedSharding(mesh, P()))

    def loss(q, k, v, mask, key):
        return dot_product_attention(
            q, k, v, mask, dropout_rate=RATE, dropout_rng=key, dtype=BF16,
            impl="pallas", mesh=mesh).astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2)), [q, q, q, mask, key]


CASE_NAMES = (
    "fused_fwd-L512", "fused_fwd_lse-L512", "fused_bwd-L512",
    "fused_bwd_segmented-L512", "blocked_fwd-L1024", "blocked_bwd-L1024",
    "stream_fwd-L4096", "stream_dkv-L4096", "causal_fwd-L4096",
    "causal_bwd-L4096", "causal_dq-L16384", "causal_dkv-L16384",
    "gqa_fwd-L8192", "gqa_bwd-L8192", "window_fwd-L8192", "window_bwd-L8192",
    "window_dq-L32768", "window_dkv-L32768", "gated_delta_fwd-L8192",
    "gated_delta_fwd_states-L8192", "gated_delta_bwd-L8192",
    "grouped_gate_up-mellum2", "grouped_down-mellum2", "grouped_gate_up-lfm2",
    "grouped_down-lfm2", "grouped_gate_up-joyai", "grouped_down-joyai",
    "token_rows_sum-mellum2", "token_rows_sum_unweighted-mellum2",
    "token_rows_dot-mellum2", "token_rows_sum-lfm2",
    "token_rows_sum_unweighted-lfm2", "token_rows_dot-lfm2",
    "token_rows_sum-joyai", "token_rows_sum_unweighted-joyai",
    "token_rows_dot-joyai", "sharded_attention-data4",
)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")


@contextlib.contextmanager
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip: keep the cache out of the way."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def verdicts(topo):
    chip = jax.sharding.SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    def compile_one(item):
        name, (call, args) = item
        try:
            jax.jit(call).lower(*args).compile()
            return name, None
        except Exception as e:  # noqa: BLE001 - the verdict under test
            return name, str(e)

    with _no_compile_cache():
        cases = _cases(shape)
        cases["sharded_attention-data4"] = _sharded_attention_case(topo)
        assert set(cases) == set(CASE_NAMES)
        with ThreadPoolExecutor(len(cases)) as pool:
            return dict(pool.map(compile_one, cases.items()))


@pytest.mark.parametrize("name", CASE_NAMES)
def test_kernel_compiles_for_v5e(verdicts, name):
    assert verdicts[name] is None, (
        f"the TPU compiler refused {name}: {verdicts[name][:1500]}")


@pytest.mark.parametrize("budget, want", [
    (None, {"fused": 1, "split": 0}), (0, {"fused": 0, "split": 1})],
    ids=["fused", "split"])
def test_a_compiled_program_names_the_backward_it_got(
        topo, monkeypatch, budget, want):
    """``metrics.trace.causal_backward_calls`` counts the backward kernels of
    a program by their instruction names, which only a program compiled for
    the chip has: a tiny causal gradient, lowered with the row's dq in VMEM
    and with the budget at 0."""
    from ml_recipe_tpu.metrics import trace
    from ml_recipe_tpu.ops import flash_causal as fc

    if budget is not None:
        monkeypatch.setattr(fc, "_DQ_ROW_BUDGET", budget)
    monkeypatch.setattr(trace, "_programs", {})
    monkeypatch.setattr(trace, "_scope_maps", {})
    chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
    q, v = (jax.ShapeDtypeStruct((B, 256, 2, d), BF16, sharding=chip)
            for d in (192, 128))

    def loss(q, k, v):
        return fc.causal_attention(q, k, v, dtype=BF16).astype(
            jnp.float32).sum()

    with _no_compile_cache():
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, q, v).compile()
    trace.register_program("jit_loss", compiled.as_text)
    assert trace.causal_backward_calls("jit_loss") == want
    flash = [name for name in trace.scope_map("jit_loss")
             if name.startswith("%flash_causal")]
    assert len(flash) == 2 + want["split"], flash       # and one forward


def test_a_compiled_expert_layer_runs_the_kernels_under_its_scope(
        topo, monkeypatch):
    """An expert layer's gradient compiled for the chip, ``kernel_mode``
    told it stands on one TPU: the first chunk's 6 calls are the kernels,
    a granule's 2 + 6 (forward, and recomputed with its backward) stay
    ``ragged_dot``; every kernel, forward and backward, lies on an
    ``op_name`` path the benchmark's reader labels ``experts``
    (``perfbench/harness/joyai_trace.py:expert_part``): the row the expert
    rooflines divide by."""
    import numpy as np

    from ml_recipe_tpu.metrics import trace
    from ml_recipe_tpu.ops import expert_ffn, grouped_matmul as gm
    from perfbench.harness.joyai_trace import expert_part

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    monkeypatch.setattr(trace, "_programs", {})
    monkeypatch.setattr(trace, "_scope_maps", {})
    chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
    T, top_k, held, of, hidden, width = 4096, 4, 4, 8, 256, 128

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    def loss(x, weights, w_gate_up, w_down, chosen):
        with jax.named_scope("trunk"), jax.named_scope("layer_2"), \
                jax.named_scope("mlp"):
            plan = expert_ffn.make_plan(chosen, weights, 0, held, of)
            return expert_ffn.routed_experts(
                x, weights, w_gate_up, w_down, plan).sum()

    before = gm.traced()
    with _no_compile_cache():
        compiled = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3))).lower(
            shape((T, hidden), BF16), shape((T, top_k), jnp.float32),
            shape((held, hidden, 2 * width), BF16),
            shape((held, width, hidden), BF16),
            shape((T, top_k), jnp.int32)).compile()
    trace.register_program("jit_loss", compiled.as_text)
    assert trace.grouped_matmul_calls("jit_loss") == {
        "kernel": 6, "ragged_dot": 8}
    # what the pre-flight reports, the calls TRACED: the kernels' number,
    # and the calls that took ragged_dot (their backward is JAX's own)
    assert {form: n - before[form] for form, n in gm.traced().items()} == {
        "kernel": 6, "ragged_dot": 4}
    kernels = {name: op_name for name, op_name in
               trace.scope_map("jit_loss").items()
               if name.startswith("%grouped_matmul")}
    assert len(kernels) == 6
    assert {expert_part(op_name, 0) for op_name in kernels.values()} == {
        "experts"}, kernels
    assert expert_part(next(iter(kernels.values())), 3) is None
    by_call = np.unique([name.split(".")[0] for name in kernels],
                        return_counts=True)
    assert dict(zip(*by_call)) == {
        "%grouped_matmul_fwd": 2, "%grouped_matmul_drows": 2,
        "%grouped_matmul_dweights": 2}


def test_a_compiled_expert_layer_runs_the_token_walks_under_its_scopes(
        topo, monkeypatch):
    """The same layer's gradient: the first chunk's three token-side walks
    are ``%token_rows_*`` kernels (the forward ``combine``, ``dispatch``'s
    backward, ``combine``'s weight gradient), a granule's stay XLA; each
    kernel lies under ``layer_N/mlp/.../combine`` or ``.../dispatch``, where
    the benchmark's readers (``joyai_trace.expert_part``,
    ``mellum2_trace.label``) put the walks they replace: the rows
    ``*_dispatch_ms_step`` reads."""
    from ml_recipe_tpu.metrics import trace
    from ml_recipe_tpu.ops import expert_ffn, token_rows as tr
    from perfbench.harness.joyai_trace import expert_part
    from perfbench.harness.mellum2_trace import label

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    monkeypatch.setattr(trace, "_programs", {})
    monkeypatch.setattr(trace, "_scope_maps", {})
    chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
    T, top_k, held, of, hidden, width = 2048, 4, 4, 8, 256, 128

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    def loss(x, weights, w_gate_up, w_down, chosen):
        with jax.named_scope("trunk"), jax.named_scope("layer_1"), \
                jax.named_scope("mlp"):
            plan = expert_ffn.make_plan(chosen, weights, 0, held, of)
            return expert_ffn.routed_experts(
                x, weights, w_gate_up, w_down, plan).sum()

    before = tr.traced()
    with _no_compile_cache():
        compiled = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3))).lower(
            shape((T, hidden), BF16), shape((T, top_k), jnp.float32),
            shape((held, hidden, 2 * width), BF16),
            shape((held, width, hidden), BF16),
            shape((T, top_k), jnp.int32)).compile()
    trace.register_program("jit_loss", compiled.as_text)
    assert trace.token_rows_calls("jit_loss") == {"sum": 2, "dot": 1}
    # the pre-flight's tally: the first chunk's three, a granule's four
    assert {form: n - before[form] for form, n in tr.traced().items()} == {
        "kernel": 3, "xla": 4}
    kernels = {name: op_name for name, op_name in
               trace.scope_map("jit_loss").items()
               if name.startswith("%token_rows")}
    parts = {name.split(".")[0] + ":" + expert_part(op_name, 0)
             for name, op_name in kernels.items()}
    assert parts == {"%token_rows_sum:combine", "%token_rows_sum:dispatch",
                     "%token_rows_dot:combine"}, kernels
    assert {label(name, op_name) for name, op_name in kernels.items()} == {
        "combine", "dispatch"}
    assert expert_part(next(iter(kernels.values())), 2) is None


@pytest.fixture(scope="module")
def remat_program(topo):
    """The gradient of Olmo-Hybrid-7B's pipeline stage (three linear-attention
    layers and a full-attention layer at published widths, the Mosaic kernels
    compiled) over one row of 8,192 tokens with ``remat`` on, compiled for the
    described chip: the optimized HLO's text. 30 s."""
    from ml_recipe_tpu.models import MODEL_PRESETS, QAModel
    from ml_recipe_tpu.ops import gated_delta

    chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
    cfg = MODEL_PRESETS["olmo-hybrid-7b-pp8"]
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        jax.eval_shape(lambda: QAModel(cfg, attention_impl="xla").init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=chip)
    model = QAModel(cfg, dtype=BF16, attention_impl="pallas", remat=True)

    def loss(p, ids):
        preds = model.apply({"params": p}, input_ids=ids,
                            attention_mask=jnp.ones_like(ids),
                            deterministic=True)
        return sum(jnp.sum(v.astype(jnp.float32))
                   for v in jax.tree_util.tree_leaves(preds))

    with pytest.MonkeyPatch.context() as patch, _no_compile_cache():
        # a CPU process picks the XLA form: ask for the compiled kernels
        patch.setattr(gated_delta, "kernel_mode", lambda *w: False)
        return jax.jit(jax.grad(loss)).lower(params, ids).compile().as_text()


@pytest.mark.parametrize("instruction, count", [
    # each forward kernel once a layer: the second pass calls none again
    (r"%gated_delta_fwd[\w.]* = ", 3), (r"%flash_causal_fwd[\w.]* = ", 1),
    (r"%gated_delta_bwd[\w.]* = ", 3), (r"%flash_causal_bwd[\w.]* = ", 1),
    # the matmuls: a linear-attention layer's ten and the attention layer's
    # seven, forward, dX and dW, and the span head's two; with the whole
    # layer run again (no policy) there are 37 more, and 6 / 2 forward calls
    (r" convolution\(", 3 * (3 * 10 + 7) + 2)],
    ids=["gated_delta_fwd", "flash_causal_fwd", "gated_delta_bwd",
         "flash_causal_bwd", "matmuls"])
def test_remat_keeps_the_matmuls_and_the_kernels_outputs(
        remat_program, instruction, count):
    """What ``models/mla_moe.KeepDear`` keeps, read off the compiled program:
    the kernel calls and matmuls that are left in the stage's gradient."""
    import re

    assert len(re.findall(instruction, remat_program)) == count
