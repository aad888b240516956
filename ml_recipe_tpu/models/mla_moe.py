"""The causal decoder trunk. Each layer picks its operator from the
configuration (``DecoderConfig.operator``): latent attention (``mla``: the
``joyai_llm_flash`` / DeepSeek-V3 block), grouped-query attention with a q/k
norm a head or over the whole projection, rotated or not
(``full_attention``), the same under a sliding window and with a rotation of
its own (``sliding_attention``; with ``full_attention`` under YaRN the two of
``mellum``), a double-gated short convolution (``conv``; the two of
``lfm2_moe``) or gated delta-rule linear attention (``linear_attention``;
with ``full_attention`` the two of ``olmo_hybrid``). Its FFN is a dense
SwiGLU in the leading layers and, after them, routed experts of which this
process holds a share, with a shared expert where the configuration has one.

Layer ``l``: ``h = x + Op_l(RMSNorm(x))``, ``x' = h + FFN_l(RMSNorm(h))``,
or with ``norm_after`` (Olmo's reordered norm) ``h = x + RMSNorm(Op_l(x))``,
``x' = h + RMSNorm(FFN_l(h))``; one more RMSNorm after the last layer
(``lfm2``'s ``embedding_norm``). No bias, no position or token-type table, no
dropout. Matmuls run in ``dtype`` (bf16) with f32 accumulation on f32
parameters; the norms, the rotary rotation, the convolutions' gating and
taps, the delta rule's decay, write strength, l2 norms and state, the router
and the softmax run in f32. The two norms of a layer keep the names
``input_layer_norm`` and ``post_attention_layer_norm`` whatever the operator
is (``lfm2`` publishes them as ``operator_norm`` / ``ffn_norm``); reordered
they are ``post_attention_layer_norm`` and ``post_feedforward_layer_norm``.
A module's name says what the trace readers count it under: ``attention``,
``conv``, ``linear_attention``, ``mlp``.

Departures from the published models, the system's own:

- the multi-token-prediction module and the LM head are not built: they
  predict tokens, and the recipe has no token-level loss;
- the class and regressor heads read the state of each row's LAST attended
  token (a causal trunk's first token sees only itself), with no pooler, as
  ``*ForSequenceClassification`` does for causal trunks;
- ``e_score_correction_bias`` / ``expert_bias`` (``router/bias``) is held
  constant: no gradient reaches it (``stop_gradient``), it is named ``bias``
  so it takes no decay, and Adam's moments of a zero gradient stay zero;
- the short convolution gates and sums its taps in f32 on the bf16
  projection (the published code multiplies in bf16), and is computed as
  shifted reads in ``[B, L, D]`` and not as a channels-first ``Conv1d``
  (``ops/short_conv.py``); q and k go from their norm through the rotation in
  f32 and are rounded once.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import dot_product_attention
from ..ops.expert_ffn import make_plan, routed_experts, routing_stats
from ..ops.flash_causal import fused_backward
from ..ops.flash_window import block_pairs
from ..ops.gated_delta import over_batch_shards
from ..ops.short_conv import causal_conv_silu, gated_short_conv
from .config import DecoderConfig

ROUTING = "routing"     # the collection the layers sow into: the counters'
#                         and the benchmark's view of a step


def unsupported(cfg, *, mesh=None, quantize="off", attention_impl="auto",
                packing=False) -> None:
    """One error naming every mechanism asked for that this trunk lacks."""
    axes = dict(zip(mesh.axis_names, mesh.devices.shape)) if mesh is not None \
        else {}
    asked = [what for what, on in (
        ("sequence packing (the delta rule's state is not reset at a "
         "segment's boundary)" if cfg.scans else "sequence packing", packing),
        ("a seq mesh axis / ring attention",
         axes.get("seq", 1) > 1 or attention_impl == "ring"),
        ("a pipe mesh axis", axes.get("pipe", 1) > 1),
        ("a model (tensor-parallel) mesh axis", axes.get("model", 1) > 1),
        ("int8 serving", quantize not in (None, "off")),
    ) if on]
    if asked:
        operators = " / ".join(sorted(
            {cfg.operator(i) for i in range(cfg.num_layers)}))
        ffn = "routed experts" if cfg.routes else "dense FFN"
        raise NotImplementedError(
            f"the {cfg.model_type} trunk ({operators} + {ffn}) does "
            f"not support {', '.join(asked)}; it runs on one chip or "
            f"replicated under --mesh data:N")


# Small f32 elementwise stretches are recomputed in the backward pass from
# their bf16 inputs (``jax.checkpoint``) and not kept: at 8,192 tokens a
# micro-batch the f32 copies autodiff would save of every norm's input, of the
# router's input and one-hot product and of every SwiGLU's activation came to
# 3.9 of 8.3 GB of residuals (traced at the published widths).

@functools.partial(jax.checkpoint, static_argnums=(2, 3))
def _rms_norm(x, scale, epsilon, dtype):
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + epsilon)
    return (x * scale).astype(dtype)


@jax.checkpoint
def _swiglu_act(gate, up):
    return (nn.silu(gate.astype(jnp.float32))
            * up.astype(jnp.float32)).astype(gate.dtype)


@jax.checkpoint
def _router_scores(x, kernel):
    return jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), kernel, precision=jax.lax.Precision.HIGHEST))


@jax.checkpoint
def _router_probabilities(x, kernel):
    """The softmax router's scores: over ALL experts, in f32."""
    return jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), kernel, precision=jax.lax.Precision.HIGHEST),
        axis=-1)


@jax.custom_vjp
def _pick(scores, chosen):
    """``scores[t, chosen[t, k]]``. The transpose is written as a one-hot
    product (``take_along_axis``' own is a scatter-add, which serialises on a
    TPU) and keeps the indices only."""
    return jnp.take_along_axis(scores, chosen, axis=-1)


def _pick_fwd(scores, chosen):
    return _pick(scores, chosen), (chosen, scores.shape[-1])


def _pick_bwd(residuals, g):
    chosen, width = residuals
    hot = chosen[:, :, None] == jnp.arange(width)[None, None, :]
    return jnp.sum(jnp.where(hot, g[:, :, None], 0.0), axis=1), None


_pick.defvjp(_pick_fwd, _pick_bwd)


class RMSNorm(nn.Module):
    epsilon: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        return _rms_norm(x, scale, self.epsilon, self.dtype)


def _dense(cfg, features, name, dtype):
    return nn.Dense(
        features, use_bias=False, name=name, dtype=dtype,
        kernel_init=nn.initializers.normal(cfg.initializer_range))


def pair_frequencies(cfg, kind: str, d: int):
    """``(frequencies [d / 2] f32, factor)``: what turns the ``d / 2`` pairs
    of a head of a layer of ``kind`` a position, and what its cos and sin are
    multiplied by. Plain RoPE: ``rope_theta ** (-2i / d)`` and 1. A
    ``full_attention`` layer under ``yarn_factor`` (YaRN): pair ``i`` turns
    ``n`` times in ``yarn_original_positions`` positions at ``c(n) = d
    ln(positions / (2 pi n)) / (2 ln theta)``; between ``low =
    floor(c(beta_fast))`` and ``high = ceil(c(beta_slow))`` the frequency
    goes linearly from its own to its own over ``yarn_factor``, and cos and
    sin grow by ``yarn_attention_factor`` (so a logit by its square)."""
    own = cfg.rope_theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if kind != "full_attention" or not cfg.yarn_factor:
        return own, 1.0
    low, high = yarn_range(cfg, d)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return ((1.0 - ramp) * own + ramp * own / cfg.yarn_factor,
            cfg.yarn_attention_factor
            or 0.1 * math.log(cfg.yarn_factor) + 1.0)


def yarn_range(cfg, d: int) -> tuple:
    """``(low, high)``: the pairs between which YaRN blends a frequency."""
    def pair_turning(n):
        return d * math.log(cfg.yarn_original_positions / (2 * math.pi * n)) \
            / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(pair_turning(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(pair_turning(cfg.yarn_beta_slow)), d - 1)
    return low, (high if high > low else low + 0.001)


def _cos_sin(x, positions, theta, factor: float = 1.0):
    """cos and sin of ``position * theta ** (-2i / d)`` for the ``d / 2``
    pairs of ``x`` [B, L, ..., d], shaped to broadcast over it, each times
    ``factor``. ``theta``: RoPE's base, or the pair frequencies themselves
    (``pair_frequencies``)."""
    d = x.shape[-1]
    inv_freq = theta if jnp.ndim(theta) else \
        theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (d // 2,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    return (cos, sin) if factor == 1.0 else (cos * factor, sin * factor)


def rotate_interleaved(x, positions, theta, factor: float = 1.0):
    """RoPE over interleaved pairs ``(x[2i], x[2i+1])`` of the last axis:
    angle ``position * theta ** (-2i / d)``. ``x`` [B, L, ..., d], in f32."""
    d = x.shape[-1]
    cos, sin = _cos_sin(x, positions, theta, factor)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack(
        [even * cos - odd * sin, even * sin + odd * cos], axis=-1
    ).reshape(x.shape)


def rotate_half_split(x, positions, theta, factor: float = 1.0):
    """RoPE over the pairs ``(x[i], x[i + d/2])`` of the last axis (``x * cos
    + rotate_half(x) * sin``), the same angles. ``x`` [B, L, ..., d], in
    f32."""
    d = x.shape[-1]
    cos, sin = _cos_sin(x, positions, theta, factor)
    x = x.astype(jnp.float32)
    low, high = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [low * cos - high * sin, high * cos + low * sin], axis=-1)


class GroupedQueryAttention(nn.Module):
    """Causal attention of ``num_heads`` query heads over ``num_kv_heads``
    key/value heads of ``head_dim`` (query head ``i`` reads ``i // group``);
    with ``qk_norm`` True each head's q and k pass an RMSNorm over the head's
    width (one learned scale, shared by the heads) before the rotation, with
    ``qk_norm`` "whole" one over the whole projection's width; with
    ``rope_theta`` None nothing is rotated. The layer's ``kind`` gives its
    mask and its rotation: ``sliding_attention`` reads the ``sliding_window``
    keys up to the query's own and rotates plainly, ``full_attention`` reads
    the whole triangle and rotates as ``pair_frequencies`` says (YaRN where
    the configuration has it)."""

    cfg: DecoderConfig
    dtype: jnp.dtype = jnp.float32
    attention_impl: str = "xla"
    mesh: Any = None
    kind: str = "full_attention"

    @nn.compact
    def __call__(self, u, mask):
        cfg, dtype = self.cfg, self.dtype
        B, L, _ = u.shape
        H = cfg.num_heads
        H_kv = cfg.num_kv_heads or H
        d = cfg.head_dim or cfg.hidden_size // H
        positions = jnp.arange(L)
        rotate = rotate_interleaved if cfg.rope_interleaved \
            else rotate_half_split
        window = cfg.sliding_window if self.kind == "sliding_attention" \
            else None

        def head_states(name, heads):
            x = _dense(cfg, heads * d, name, dtype)(u)
            if cfg.qk_norm == "whole":
                x = RMSNorm(cfg.rms_norm_eps, jnp.float32,
                            name=f"{name}_layer_norm")(x)
            x = x.reshape(B, L, heads, d)
            if cfg.qk_norm is True:
                x = RMSNorm(cfg.rms_norm_eps, jnp.float32,
                            name=f"{name}_layer_norm")(x)
            if cfg.rope_theta is None:
                return x.astype(dtype)
            return rotate(x, positions,
                          *pair_frequencies(cfg, self.kind, d)).astype(dtype)

        q, k = head_states("q", H), head_states("k", H_kv)
        v = _dense(cfg, H_kv * d, "v", dtype)(u).reshape(B, L, H_kv, d)
        ctx = dot_product_attention(
            q, k, v, mask, dtype=dtype, impl=self.attention_impl,
            mesh=self.mesh, causal=True, window=window)
        # what the operator read and wrote, for a comparison of it alone
        if cfg.windows:
            self.sow(ROUTING, "attention_input", (q, k, v))
            self.sow(ROUTING, "attention_output", ctx)
        if window is not None:
            walked, causal = block_pairs(L, window)
            calls = B * H * (2 + (not fused_backward(L, d)))
            self.sow(ROUTING, "stats", {
                "attn_window_block_pairs": jnp.float32(calls * walked),
                "attn_causal_block_pairs": jnp.float32(calls * causal)})
        return _dense(cfg, cfg.hidden_size, "output", dtype)(
            ctx.reshape(B, L, H * d))


class ShortConv(nn.Module):
    """``W_out(Cg * conv(Bg * x))`` with ``[Bg | Cg | x] = u W_in``: the
    double-gated causal convolution of ``conv_L_cache`` taps a channel
    (``ops/short_conv.py``). Takes no mask: position ``t`` reads ``t`` and
    the taps before it, and rows are padded on the right."""

    cfg: DecoderConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        D = cfg.hidden_size
        taps = self.param(
            "taps", nn.initializers.normal(cfg.initializer_range),
            (D, cfg.conv_L_cache), jnp.float32)
        projected = _dense(cfg, 3 * D, "in_proj", self.dtype)(u)
        gated = gated_short_conv(projected, taps)
        # what the operator read and wrote, for a comparison of it alone
        self.sow(ROUTING, "conv_input", projected)
        self.sow(ROUTING, "conv_output", gated)
        return _dense(cfg, D, "out_proj", self.dtype)(gated)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _log_decay(a, A_log, dt_bias):
    """``g = -exp(A_log) softplus(a + dt_bias)``, the log of a step's decay."""
    return -jnp.exp(A_log) * jax.nn.softplus(a + dt_bias)


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _gated_norm(o, gate, scale, epsilon, dtype):
    """``RMSNorm(o) * silu(gate)`` over each head's width."""
    o, gate = o.astype(jnp.float32), gate.astype(jnp.float32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + epsilon)
    return (o * scale * nn.silu(gate)).astype(dtype)


def _decay_init(key, shape, dtype=jnp.float32):
    """``A_log = log U(0, 16)``."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-4, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """``softplus^-1(dt)`` with ``dt = exp U(log 1e-3, log 1e-1)``."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, jnp.log(1e-3), jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class LinearAttention(nn.Module):
    """Gated delta-rule linear attention (the ``GatedDeltaNet`` layer that
    the ``linear_*`` keys name): q, k and v each pass a causal depthwise
    convolution of ``linear_conv_kernel_dim`` taps and a SiLU; q and k are
    l2-normalised a head; a head's state takes a decay ``exp(g)`` and a write
    of strength ``beta`` a token (``ops/gated_delta.py``); the output passes
    an RMSNorm a head, gated by ``silu(u W_g)``, and the output projection.
    Takes the mask only to count: the rule is causal and rows are padded on
    the right; the mesh only to run the rule's Mosaic form a shard of the
    batch."""

    cfg: DecoderConfig
    dtype: jnp.dtype = jnp.float32
    mesh: Any = None

    @nn.compact
    def __call__(self, u, mask):
        cfg, dtype = self.cfg, self.dtype
        B, L, _ = u.shape
        H, d_k, d_v = (cfg.linear_num_heads, cfg.linear_key_head_dim,
                       cfg.linear_value_head_dim)
        init = nn.initializers.normal(cfg.initializer_range)

        def projected(name, width):
            taps = self.param(f"{name}_taps", init,
                              (width, cfg.linear_conv_kernel_dim), jnp.float32)
            return _dense(cfg, width, name, dtype)(u), taps

        (q, q_taps), (k, k_taps), (v, v_taps) = (
            projected("q", H * d_k), projected("k", H * d_k),
            projected("v", H * d_v))
        a = _dense(cfg, H, "a", jnp.float32)(u)
        b = _dense(cfg, H, "b", jnp.float32)(u)
        gate = _dense(cfg, H * d_v, "g", dtype)(u)
        A_log = self.param("A_log", _decay_init, (H,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (H,), jnp.float32)
        with jax.named_scope("qkv_conv"):
            q, k = (_l2norm(causal_conv_silu(x, w).reshape(B, L, H, d_k)
                            ).astype(dtype) for x, w in ((q, q_taps),
                                                         (k, k_taps)))
            v = causal_conv_silu(v, v_taps).reshape(B, L, H, d_v).astype(dtype)
            beta = jax.nn.sigmoid(b) * (2.0 if cfg.linear_allow_neg_eigval
                                        else 1.0)
            g = _log_decay(a, A_log, dt_bias)
        o = over_batch_shards(self.mesh, q, k, v, g, beta)
        # what the operator read and wrote, for a comparison of it alone
        self.sow(ROUTING, "scan_input", (q, k, v, g, beta))
        self.sow(ROUTING, "scan_output", o)
        real = mask.astype(jnp.float32)[:, :, None]
        count = jnp.maximum(jnp.sum(real), 1.0) * H
        self.sow(ROUTING, "stats", {
            "linear_decay_mean": jnp.sum(jnp.exp(g) * real) / count,
            "linear_beta_mean": jnp.sum(beta * real) / count})
        scale = self.param("o_layer_norm", nn.initializers.ones, (d_v,),
                           jnp.float32)
        with jax.named_scope("gated_norm"):
            gated = _gated_norm(o, gate.reshape(B, L, H, d_v), scale,
                                cfg.rms_norm_eps, dtype)
        return _dense(cfg, cfg.hidden_size, "output", dtype)(
            gated.reshape(B, L, H * d_v))


class LatentAttention(nn.Module):
    """MLA in its training form: low-rank q and kv projections, a rotary key
    part shared by all heads, then causal multi-head attention with
    ``d_qk = nope + rope`` and ``d_v``."""

    cfg: DecoderConfig
    dtype: jnp.dtype = jnp.float32
    attention_impl: str = "xla"
    mesh: Any = None

    @nn.compact
    def __call__(self, u, mask):
        cfg, dtype = self.cfg, self.dtype
        B, L, _ = u.shape
        H, nope, rope = cfg.num_heads, cfg.qk_nope_head_dim, \
            cfg.qk_rope_head_dim
        positions = jnp.arange(L)

        c_q = RMSNorm(cfg.rms_norm_eps, dtype, name="q_a_layer_norm")(
            _dense(cfg, cfg.q_lora_rank, "q_a", dtype)(u))
        q = _dense(cfg, H * cfg.qk_head_dim, "q_b", dtype)(c_q).reshape(
            B, L, H, cfg.qk_head_dim)
        kv = _dense(cfg, cfg.kv_lora_rank + rope, "kv_a", dtype)(u)
        c_kv = RMSNorm(cfg.rms_norm_eps, dtype, name="kv_a_layer_norm")(
            kv[..., :cfg.kv_lora_rank])
        k_nope_v = _dense(cfg, H * (nope + cfg.v_head_dim), "kv_b", dtype)(
            c_kv).reshape(B, L, H, nope + cfg.v_head_dim)

        q_rope = rotate_interleaved(q[..., nope:], positions, cfg.rope_theta)
        k_rope = rotate_interleaved(
            kv[..., cfg.kv_lora_rank:], positions, cfg.rope_theta)
        q = jnp.concatenate([q[..., :nope], q_rope.astype(dtype)], axis=-1)
        k = jnp.concatenate(
            [k_nope_v[..., :nope],
             jnp.broadcast_to(k_rope.astype(dtype)[:, :, None, :],
                              (B, L, H, rope))], axis=-1)
        ctx = dot_product_attention(
            q, k, k_nope_v[..., nope:], mask, dtype=dtype,
            impl=self.attention_impl, mesh=self.mesh, causal=True)
        return _dense(cfg, cfg.hidden_size, "output", dtype)(
            ctx.reshape(B, L, H * cfg.v_head_dim))


class GatedFFN(nn.Module):
    """``W_down(silu(x W_gate) * (x W_up))``."""

    cfg: DecoderConfig
    width: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate = _dense(cfg, self.width, "gate", self.dtype)(x)
        up = _dense(cfg, self.width, "up", self.dtype)(x)
        return _dense(cfg, cfg.hidden_size, "down", self.dtype)(
            _swiglu_act(gate, up))


class Router(nn.Module):
    """Scores in f32 over ALL experts, by ``scoring_func``. Sigmoid: the
    top-k of ``score + bias`` are chosen (the bias selects, it does not
    weigh). Softmax: the top-k of the scores, and no ``bias`` parameter.
    Either way the chosen weigh ``scale * score / (sum of the chosen scores
    + norm_topk_eps)``. Returns ``(chosen [T, K] ids, weights [T, K]
    f32)``."""

    cfg: DecoderConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        K = cfg.num_experts_per_tok
        kernel = self.param(
            "kernel", nn.initializers.normal(cfg.initializer_range),
            (x.shape[-1], cfg.n_routed_experts), jnp.float32)
        if cfg.scoring_func == "softmax":
            scores = _router_probabilities(x, kernel)
            _, chosen = jax.lax.top_k(scores, K)
        else:
            bias = self.param(
                "bias", nn.initializers.normal(
                    cfg.expert_bias_range or cfg.initializer_range),
                (cfg.n_routed_experts,), jnp.float32)
            scores = _router_scores(x, kernel)
            biased = scores + jax.lax.stop_gradient(bias)
            _, chosen = jax.lax.top_k(biased, K)
        weights = _pick(scores, chosen)
        if cfg.norm_topk_prob:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                                 + cfg.norm_topk_eps)
        return chosen, weights * cfg.routed_scaling_factor


class Experts(nn.Module):
    """The held experts' SwiGLU weights, stacked: ``gate``/``up``
    [E_held, H, F], ``down`` [E_held, F, H]."""

    cfg: DecoderConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self):
        cfg = self.cfg
        init = nn.initializers.normal(cfg.initializer_range)
        E, H, F = cfg.experts_held, cfg.hidden_size, cfg.moe_intermediate_size
        gate = self.param("gate", init, (E, H, F), jnp.float32)
        up = self.param("up", init, (E, H, F), jnp.float32)
        down = self.param("down", init, (E, F, H), jnp.float32)
        return (jnp.concatenate([gate, up], axis=-1).astype(self.dtype),
                down.astype(self.dtype))


class ExpertLayer(nn.Module):
    """``Shared(x) + sum_{i chosen and held} w_i Expert_i(x)``: routes over
    all ``n_routed_experts``, computes the part of the routed sum that the
    experts ``[experts_first, experts_first + experts_held)`` give, and leaves
    the rest out (another chip's part). With ``n_shared_experts`` 0 there is
    no shared branch. What it chose is sown into the ``routing`` collection
    (the counters' and the benchmark's view of it)."""

    cfg: DecoderConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, L, H = x.shape
        tokens = x.reshape(B * L, H)
        chosen, weights = Router(cfg, name="router")(tokens)
        with jax.named_scope("dispatch"):
            plan = make_plan(chosen, weights, cfg.experts_first,
                             cfg.experts_held, cfg.n_routed_experts)
        w_gate_up, w_down = Experts(cfg, self.dtype, name="experts")()
        routed = routed_experts(tokens, weights, w_gate_up, w_down, plan)
        self.sow(ROUTING, "stats", routing_stats(plan))
        self.sow(ROUTING, "chosen", chosen.reshape(B, L, -1))
        self.sow(ROUTING, "router_input", x)
        if not cfg.n_shared_experts:
            with jax.named_scope("combine"):
                return routed.reshape(B, L, H).astype(self.dtype)
        shared = GatedFFN(
            cfg, cfg.moe_intermediate_size * cfg.n_shared_experts, self.dtype,
            name="shared_expert")(x)
        with jax.named_scope("combine"):
            return (shared.astype(jnp.float32)
                    + routed.reshape(B, L, H)).astype(self.dtype)


class DecoderLayer(nn.Module):
    cfg: DecoderConfig
    dense: bool
    dtype: jnp.dtype = jnp.float32
    attention_impl: str = "xla"
    mesh: Any = None
    operator: str = "mla"

    @nn.compact
    def __call__(self, x, mask):
        cfg, dtype = self.cfg, self.dtype
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dtype, name=name)  # noqa: E731
        if self.operator == "conv":
            op = ShortConv(cfg, dtype, name="conv")
        elif self.operator == "linear_attention":
            op = functools.partial(
                LinearAttention(cfg, dtype, self.mesh,
                                name="linear_attention"), mask=mask)
        elif self.operator == "mla":
            op = functools.partial(
                LatentAttention(cfg, dtype, self.attention_impl, self.mesh,
                                name="attention"), mask=mask)
        else:
            op = functools.partial(
                GroupedQueryAttention(
                    cfg, dtype, self.attention_impl, self.mesh, self.operator,
                    name="attention"), mask=mask)
        ffn = (GatedFFN(cfg, cfg.intermediate_size, dtype, name="mlp")
               if self.dense else ExpertLayer(cfg, dtype, name="mlp"))
        if cfg.norm_after:
            h = x + norm("post_attention_layer_norm")(op(x))
            return h + norm("post_feedforward_layer_norm")(ffn(h))
        h = x + op(norm("input_layer_norm")(x))
        return h + ffn(norm("post_attention_layer_norm")(h))


class KeepDear:
    """What ``remat`` keeps of a layer between its forward and its backward
    pass, as a ``jax.checkpoint`` policy that tells an operation by what it
    is: a matmul's output stays (a ``dot_general`` with no batch dimension:
    the operators' projections and the FFN's three; not ``ragged_dot``), and
    so do a kernel call's outputs (a ``pallas_call``: the causal kernels'
    output and row logsumexp, the delta rule's output and chunk states), which
    are what the kernels' own backward rules read of them. The second pass
    then runs the elementwise stretches only, each a checkpoint of its own
    that keeps bf16 inputs: norms, taps + SiLU + l2 norm, SwiGLU's product,
    the gated norm, the heads-first transposes round the kernels. Nothing is
    told by a name: a ``checkpoint_name`` inside a forward rule would renumber
    the private functions of every step program that calls it, ``remat`` or
    not, which is another compile-cache key. A policy answers once an
    equation while the step is traced; ``kept_bytes`` adds up what it has
    said yes to since the process began (a trace's share is the
    difference)."""

    def __init__(self):
        self.kept_bytes = 0

    def __call__(self, prim, *avals, **params) -> bool:
        keep = prim.name == "pallas_call" or \
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable(
                prim, *avals, **params)
        if keep:
            outs, _ = prim.abstract_eval(*avals, **params)
            self.kept_bytes += sum(
                out.size * out.dtype.itemsize
                for out in (outs if prim.multiple_results else (outs,)))
        return keep


REMAT_KEEPS = KeepDear()


class DecoderTrunk(nn.Module):
    """``(sequence_output, pooled)``: every token's final-norm state, and
    that of each row's last attended token. With ``remat`` a layer keeps what
    ``REMAT_KEEPS`` says and runs the rest again in its backward pass."""

    cfg: DecoderConfig
    dtype: jnp.dtype = jnp.float32
    attention_impl: str = "xla"
    remat: bool = False
    mesh: Any = None

    @nn.compact
    def __call__(self, input_ids, attention_mask: Optional[jnp.ndarray] = None,
                 token_type_ids=None, *, deterministic: bool = True,
                 position_ids=None, segment_ids=None, segment_starts=None):
        cfg = self.cfg
        del token_type_ids, deterministic    # no such table, no dropout
        unsupported(cfg, mesh=self.mesh, attention_impl=self.attention_impl,
                    packing=segment_ids is not None
                    or segment_starts is not None or position_ids is not None)
        if attention_mask is None:
            attention_mask = jnp.ones_like(input_ids)
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=self.dtype,
            embedding_init=nn.initializers.normal(
                cfg.embedding_range or cfg.initializer_range),
            name="word_embeddings")(input_ids)
        layer_cls = nn.remat(DecoderLayer, policy=REMAT_KEEPS) \
            if self.remat else DecoderLayer
        for i in range(cfg.num_layers):
            x = layer_cls(
                cfg, i < cfg.first_k_dense_replace, self.dtype,
                self.attention_impl, self.mesh, cfg.operator(i),
                name=f"layer_{i}")(x, attention_mask)
        x = RMSNorm(cfg.rms_norm_eps, self.dtype, name="final_layer_norm")(x)
        last = jnp.maximum(
            jnp.sum(attention_mask.astype(jnp.int32), axis=-1) - 1, 0)
        pooled = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        return x, pooled


STEP_STAT_KEYS = ("moe_held_assignments", "moe_load_max_over_mean",
                  "moe_held_share", "moe_overflow_chunks", "moe_filler_share",
                  "moe_row_tile_fill")
# those of them that are counts and add up (over the expert layers, and over
# a step's micro-batches and chips); the others are ratios and average
STEP_STAT_SUMS = ("moe_held_assignments", "moe_overflow_chunks")
# what the linear-attention layers report: the mean decay ``exp(g)`` and the
# mean write strength over real tokens, heads and layers
SCAN_STAT_KEYS = ("linear_decay_mean", "linear_beta_mean")
# what the sliding-window layers report: the (q block, k block) pairs their
# kernel calls' grids walk (a forward and a backward call, or two where the
# backward is split, a row and head: ``flash_window.block_pairs``), and the
# pairs the causal triangle would have had them walk: counts, which add up
# as ``STEP_STAT_SUMS`` do
WINDOW_STAT_KEYS = ("attn_window_block_pairs", "attn_causal_block_pairs")


def step_stat_keys(cfg) -> tuple:
    """The counters ``step_stats`` gives for a trunk of this configuration:
    the routing counters where a layer routes, the scan's where one scans,
    the window's where one attends under a window."""
    return (STEP_STAT_KEYS * cfg.routes + SCAN_STAT_KEYS * cfg.scans
            + WINDOW_STAT_KEYS * cfg.windows)


def step_stats(routing: dict) -> dict:
    """The step's counters from what the layers sowed: the expert layers'
    counts (``STEP_STAT_SUMS``) summed over those layers, every ratio
    averaged over the layers that report it."""
    layers = [layer for _, layer in sorted(routing["transformer"].items())]
    out = {}
    for module, keys in (("mlp", STEP_STAT_KEYS),
                         ("linear_attention", SCAN_STAT_KEYS),
                         ("attention", WINDOW_STAT_KEYS)):
        stats = [layer[module]["stats"][0] for layer in layers
                 if "stats" in layer.get(module, {})]
        if stats:
            out.update({key: sum(s[key] for s in stats)
                        / (1.0 if key in STEP_STAT_SUMS + WINDOW_STAT_KEYS
                           else float(len(stats)))
                        for key in keys})
    return out
