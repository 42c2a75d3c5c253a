"""Qwen3-Next / Qwen3.5 hybrid GatedDeltaNet family.

Reference capability: ``veomni/models/transformers/qwen3_5/`` (8,146 LoC
generated modeling: hybrid linear-attention + full-attention decoder) with
``ops/kernels/gated_delta_rule/`` Triton kernels. Architecture (public
Qwen3Next): a periodic layer pattern — ``full_attention_interval - 1``
GatedDeltaNet linear-attention layers followed by one gated full-attention
layer — each with a (MoE or dense) MLP, shared expert + sigmoid gate.

TPU-first design:

* **Super-layer scan**: the layer pattern is periodic, so params are stacked
  as [G, P, ...] (G groups x P linear layers) and [G, ...] (one full-attn
  layer per group) and the forward is ONE ``lax.scan`` over G with an inner
  scan over P — two compiled layer bodies total regardless of depth.
* **Chunkwise gated delta rule in pure XLA**: the sequential delta-rule
  recurrence is reformulated chunkwise (chunk 64): the in-chunk UT transform
  is a batched unit-triangular solve (``jax.scipy.linalg.solve_triangular``
  — MXU-friendly, differentiable), and only the O(S/64) inter-chunk state
  scan is sequential. Numerics in f32 like the reference kernels.
* Depthwise causal conv1d = ``lax.conv_general_dilated`` with
  ``feature_group_count`` and left-only padding.

Semantics match ``transformers`` Qwen3Next (torch fallback path:
``torch_chunk_gated_delta_rule``) and are parity-tested against it.

Packed multi-segment rows are fully reset-aware: ``segment_ids`` mask the
full-attention layers (ops.attention facade), reset the delta-rule
recurrence at document boundaries (see ``chunk_gated_delta_rule``), and
zero conv taps crossing boundaries — matching the reference's varlen
``ops/kernels/gated_delta_rule`` handling. Sequence parallelism applies to
the full-attention layers via the ops.attention facade; linear layers
compute on the gathered sequence (GSPMD handles the sharded scan).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from veomni_tpu import ops
from veomni_tpu.models import transformer as core
from veomni_tpu.models.config import TransformerConfig

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# Chunkwise gated delta rule
# --------------------------------------------------------------------------
def _l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


def chunk_gated_delta_rule(q, k, v, g, beta, chunk: int = 64, segment_ids=None):
    """q/k [B,S,H,Dk] (pre-l2norm'd, head-repeated), v [B,S,H,Dv],
    g [B,S,H] log-decay (f32), beta [B,S,H]. Returns [B,S,H,Dv] (f32).

    Chunkwise form of: S_t = S_{t-1}*exp(g_t) + k_t (beta_t (v_t - k_t^T
    S_{t-1}exp(g_t)))^T; o_t = q_t S_t. In-chunk inversion via triangular
    solve instead of the reference's row-by-row forward substitution.

    ``segment_ids`` [B,S] (packed documents; 0 = padding) resets the
    recurrence at document boundaries, matching the reference's varlen
    handling (``ops/kernels/gated_delta_rule`` cu_seqlens path) without
    re-chunking per document: because documents are contiguous, every
    cross-document interaction is killed by masks —

    * in-chunk pair masks (tril AND same-segment) on the decay matrix, the
      UT-transform Gram matrix, and the intra-chunk attention: the
      triangular solve becomes block-diagonal per document, so ``v_prime``/
      ``k_cumdecay`` rows never mix documents;
    * a continuation mask (position's segment == segment at the end of the
      previous chunk) gates every read of the carried state S — only the
      document that was active at the previous chunk boundary may see it;
    * the state update keeps S only if no boundary occurred in the chunk and
      accumulates only positions belonging to the chunk-final document.
    """
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    q, k, v = (x.transpose(0, 2, 1, 3).astype(jnp.float32) for x in (q, k, v))
    g = g.transpose(0, 2, 1).astype(jnp.float32)       # [B,H,S]
    beta = beta.transpose(0, 2, 1).astype(jnp.float32)  # [B,H,S]
    seg = (
        jnp.ones((b, s), jnp.int32)
        if segment_ids is None
        else segment_ids.astype(jnp.int32)
    )

    pad = (-s) % chunk
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) for x in (q, k, v))
        g = jnp.pad(g, ((0, 0), (0, 0), (0, pad)))
        beta = jnp.pad(beta, ((0, 0), (0, 0), (0, pad)))
        seg = jnp.pad(seg, ((0, 0), (0, pad)))
    n = (s + pad) // chunk
    c = chunk

    q = q.reshape(b, h, n, c, dk) * (dk ** -0.5)
    k = k.reshape(b, h, n, c, dk)
    v = v.reshape(b, h, n, c, dv)
    g = g.reshape(b, h, n, c).cumsum(-1)               # in-chunk cumulative decay
    beta = beta.reshape(b, h, n, c)
    seg = seg.reshape(b, 1, n, c)                      # broadcast over heads

    k_beta = k * beta[..., None]
    v_beta = v * beta[..., None]
    # pair mask: j <= i AND same document (documents are contiguous, so the
    # in-chunk cumsum g_i - g_j spans only same-document decay when i,j are
    # in the same document)
    tril = jnp.tril(jnp.ones((c, c), bool))
    same = seg[..., :, None] == seg[..., None, :]      # [B,1,n,c,c]
    mask = tril & same
    # decay[i,j] = exp(g_i - g_j) for valid pairs. Mask the exponent BEFORE
    # exp: upper-triangle g_i - g_j is large-positive, and
    # where(mask, exp(big), 0) backprops 0 * inf = NaN through the exp.
    decay = jnp.exp(jnp.where(mask, g[..., :, None] - g[..., None, :], -1e30))

    # UT transform: T = (I + strict_tril(k_beta K^T * decay))^{-1}
    kk = jnp.einsum("bhnic,bhnjc->bhnij", k_beta, k) * decay
    kk = jnp.where(jnp.tril(jnp.ones((c, c), bool), -1) & same, kk, 0.0)
    eye = jnp.eye(c, dtype=jnp.float32)
    T = jax.scipy.linalg.solve_triangular(
        eye + kk, jnp.broadcast_to(eye, kk.shape), lower=True, unit_diagonal=True
    )
    v_prime = jnp.einsum("bhnij,bhnjd->bhnid", T, v_beta)
    k_cumdecay = jnp.einsum(
        "bhnij,bhnjd->bhnid", T, k_beta * jnp.exp(g)[..., None]
    )

    def chunk_step(carry, xs):
        S, seg_prev_last = carry                       # S [B,H,dk,dv]; [B,1]
        q_i, k_i, v_i, g_i, kcd_i, seg_i = xs          # seg_i [B,1,c]
        cont = (seg_i == seg_prev_last[..., None]).astype(jnp.float32)
        attn = jnp.einsum("bhic,bhjc->bhij", q_i, k_i)
        mask_i = tril & (seg_i[..., :, None] == seg_i[..., None, :])
        dec_i = jnp.exp(
            jnp.where(mask_i, g_i[..., :, None] - g_i[..., None, :], -1e30)
        )
        attn = jnp.where(mask_i, attn, 0.0) * dec_i
        v_new = v_i - jnp.einsum(
            "bhik,bhkd->bhid", kcd_i * cont[..., None], S
        )
        out_i = (
            jnp.einsum("bhik,bhkd->bhid", q_i * jnp.exp(g_i)[..., None], S)
            * cont[..., None]
            + jnp.einsum("bhij,bhjd->bhid", attn, v_new)
        )
        seg_last = seg_i[..., -1]                      # [B,1]
        keep = (seg_last == seg_prev_last).astype(jnp.float32)
        accum = (seg_i == seg_last[..., None]).astype(jnp.float32)
        g_last = g_i[..., -1]
        S = S * jnp.exp(g_last)[..., None, None] * keep[..., None, None] \
            + jnp.einsum(
                "bhik,bhid->bhkd",
                k_i * jnp.exp(g_last[..., None] - g_i)[..., None]
                * accum[..., None],
                v_new,
            )
        return (S, seg_last), out_i

    xs = tuple(
        jnp.moveaxis(x, 2, 0) for x in (q, k, v_prime, g, k_cumdecay, seg)
    )  # each [n, B, H, ...]
    S0 = jnp.zeros((b, h, dk, dv), jnp.float32)
    _, out = jax.lax.scan(chunk_step, (S0, seg[:, :, 0, 0]), xs)
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, n * c, dv)[:, :, :s]
    return out.transpose(0, 2, 1, 3)  # [B,S,H,Dv]


def _causal_conv1d(x, weight, segment_ids=None, bias=None):
    """Depthwise causal conv: x [B,S,C], weight [C,K], bias [C] or None ->
    [B,S,C] (silu'd). Shared with ``granite_hybrid``'s Mamba-2 mixer.

    Written as K shifted multiply-adds rather than ``lax.conv``: the kernel
    is tiny (K=4), elementwise ops fuse into the surrounding projections, and
    XLA:CPU's oneDNN grouped-conv path computes in reduced precision (breaks
    the HF-parity oracle).

    With ``segment_ids`` [B,S], taps reaching across a packed-document
    boundary are zeroed (each document sees the same left-zero-padded window
    it would see unpacked)."""
    s = x.shape[1]
    k = weight.shape[-1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    if segment_ids is None:
        out = sum(weight[None, None, :, i] * xp[:, i:i + s, :] for i in range(k))
    else:
        # pad with -1 so out-of-range taps never match a real segment id
        segp = jnp.pad(segment_ids, ((0, 0), (k - 1, 0)), constant_values=-1)
        out = sum(
            weight[None, None, :, i]
            * xp[:, i:i + s, :]
            * (segp[:, i:i + s] == segment_ids)[..., None]
            for i in range(k)
        )
    return jax.nn.silu(out if bias is None else out + bias)


def _gated_delta_net(x, lp, cfg: TransformerConfig, segment_ids=None):
    """One GatedDeltaNet mixer (HF Qwen3NextGatedDeltaNet.forward)."""
    b, s, _ = x.shape
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    rep = nv // nk
    key_dim, value_dim = nk * dk, nv * dv

    qkvz = jnp.dot(x, lp["in_proj_qkvz"])  # [B,S, 2*key_dim + 2*value_dim]
    ba = jnp.dot(x, lp["in_proj_ba"])      # [B,S, 2*nv]
    # per-k-head interleaved layout (HF fix_query_key_value_ordering)
    qkvz = qkvz.reshape(b, s, nk, 2 * dk + 2 * rep * dv)
    qg = qkvz[..., :dk]
    kg = qkvz[..., dk:2 * dk]
    vg = qkvz[..., 2 * dk:2 * dk + rep * dv].reshape(b, s, nv, dv)
    z = qkvz[..., 2 * dk + rep * dv:].reshape(b, s, nv, dv)
    ba = ba.reshape(b, s, nk, 2 * rep)
    b_ = ba[..., :rep].reshape(b, s, nv)
    a = ba[..., rep:].reshape(b, s, nv)

    # conv over flattened (q, k, v)
    mixed = jnp.concatenate(
        [qg.reshape(b, s, key_dim), kg.reshape(b, s, key_dim),
         vg.reshape(b, s, value_dim)], axis=-1
    )
    mixed = _causal_conv1d(mixed, lp["conv_weight"], segment_ids)
    q = mixed[..., :key_dim].reshape(b, s, nk, dk)
    k = mixed[..., key_dim:2 * key_dim].reshape(b, s, nk, dk)
    v = mixed[..., 2 * key_dim:].reshape(b, s, nv, dv)

    beta = jax.nn.sigmoid(b_.astype(jnp.float32))
    g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32)
    )
    q = _l2norm(q.astype(jnp.float32))
    k = _l2norm(k.astype(jnp.float32))
    if rep > 1:
        q = jnp.repeat(q, rep, axis=2)
        k = jnp.repeat(k, rep, axis=2)

    out = chunk_gated_delta_rule(
        q, k, v, g, beta, segment_ids=segment_ids
    )  # [B,S,nv,dv] f32

    # gated RMSNorm (norm before gate), f32 silu gate
    var = (out * out).mean(-1, keepdims=True)
    out = out * jax.lax.rsqrt(var + cfg.rms_norm_eps)
    out = (lp["norm"] * out.astype(cfg.dtype)).astype(cfg.dtype)
    out = out * jax.nn.silu(z.astype(jnp.float32)).astype(cfg.dtype)
    return jnp.dot(out.reshape(b, s, value_dim), lp["out_proj"])


def _gated_full_attention(x, lp, cfg: TransformerConfig, cos, sin, segment_ids):
    """Full-attention mixer with per-head output gate (HF Qwen3NextAttention)."""
    b, s, _ = x.shape
    nh, nkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    qg = jnp.dot(x, lp["q_proj"]).reshape(b, s, nh, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = jnp.dot(x, lp["k_proj"]).reshape(b, s, nkv, hd)
    v = jnp.dot(x, lp["v_proj"]).reshape(b, s, nkv, hd)
    q = core._norm(q, lp["q_norm"], cfg)
    k = core._norm(k, lp["k_norm"], cfg)
    rot = cos.shape[-1]
    q_r, k_r = ops.apply_rotary(q[..., :rot], k[..., :rot], cos, sin)
    q = jnp.concatenate([q_r, q[..., rot:]], axis=-1)
    k = jnp.concatenate([k_r, k[..., rot:]], axis=-1)
    attn = ops.attention(
        q, k, v, segment_ids=segment_ids, causal=True, softmax_scale=hd ** -0.5
    )
    attn = attn * jax.nn.sigmoid(gate)
    return jnp.dot(attn.reshape(b, s, nh * hd), lp["o_proj"])


def _mlp(x, lp, cfg: TransformerConfig):
    """Dense or MoE MLP reusing the core helpers (incl. EP dispatch)."""
    b, s, h = x.shape
    if cfg.is_moe:
        from veomni_tpu.parallel.parallel_state import get_parallel_state_or_none

        ps = get_parallel_state_or_none()
        if ps is not None and ps.ep_enabled:
            from veomni_tpu.parallel.moe import ep_moe_mlp

            return ep_moe_mlp(x, lp, cfg, ps)
        out, aux = core._moe_mlp(x.reshape(b * s, h), lp, cfg)
        return out.reshape(b, s, h), aux, jnp.float32(0.0)
    gate = jnp.dot(x, lp["gate_proj"])
    up = jnp.dot(x, lp["up_proj"])
    out = jnp.dot(core.gated_act(gate, up, cfg), lp["down_proj"])
    return out, jnp.float32(0.0), jnp.float32(0.0)


def _sublayer(hidden, lp, mixer, *, cfg):
    constrain = core._activation_constraint()
    hidden = constrain(hidden)
    x = core._norm(hidden, lp["input_layernorm"], cfg)
    hidden = hidden + mixer(x, lp)
    hidden = constrain(hidden)
    x = core._norm(hidden, lp["post_attention_layernorm"], cfg)
    out, aux, dropped = _mlp(x, lp, cfg)
    return constrain(hidden + out), aux, dropped


def period_scan(hidden, stacks, period, bodies, fold=None):
    """A hybrid stack as ONE ``lax.scan`` over its periods.

    ``period``: the kind of each layer of one period, in order. ``stacks``:
    kind -> that kind's parameters ``[G, n_kind, ...]`` for ``G`` periods of
    ``n_kind`` such layers each. ``bodies``: kind -> ``(hidden, layer params)
    -> (hidden, aux)``, ``aux`` a pytree of scalars (or None). Inside a period
    each run of consecutive layers of one kind is an inner scan, so every kind
    compiles one body whatever the depth and wherever in the period it sits.
    Returns (hidden, aux summed over each period's layers, ``[G]``); where a
    sum is not how the layers' aux joins, ``fold`` is ``(zero, (total, a
    run's aux [n, ...]) -> total)``."""
    runs, seen = [], {}
    for kind in period:
        at = seen.get(kind, 0)
        seen[kind] = at + 1
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, at, 1])

    def one_period(hidden, group):
        total = None if fold is None else fold[0]
        for kind, start, n in runs:
            sub = jax.tree.map(lambda t: t[start:start + n], group[kind])
            hidden, aux = jax.lax.scan(bodies[kind], hidden, sub)
            if fold is not None:
                total = fold[1](total, aux)
            else:
                aux = jax.tree.map(lambda a: a.sum(0), aux)
                total = aux if total is None else jax.tree.map(jnp.add, total, aux)
        return hidden, total

    return jax.lax.scan(one_period, hidden, stacks)


# --------------------------------------------------------------------------
# Params
# --------------------------------------------------------------------------
def _mixer_linear_params(keys, cfg, L, pd):
    h, s = cfg.hidden_size, cfg.initializer_range
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    key_dim, value_dim = nk * dk, nv * dv
    conv_dim = 2 * key_dim + value_dim
    return {
        "input_layernorm": jnp.ones((L, h), pd),
        "post_attention_layernorm": jnp.ones((L, h), pd),
        "in_proj_qkvz": core._dense_init(
            next(keys), (L, h, 2 * key_dim + 2 * value_dim), pd, s
        ),
        "in_proj_ba": core._dense_init(next(keys), (L, h, 2 * nv), pd, s),
        "conv_weight": core._dense_init(
            next(keys), (L, conv_dim, cfg.linear_conv_kernel_dim), pd, s
        ),
        "dt_bias": jnp.ones((L, nv), pd),
        "A_log": jnp.zeros((L, nv), pd),
        "norm": jnp.ones((L, dv), pd),
        "out_proj": core._dense_init(next(keys), (L, value_dim, h), pd, s),
    }


def _mixer_full_params(keys, cfg, L, pd):
    h, s = cfg.hidden_size, cfg.initializer_range
    nh, nkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    return {
        "input_layernorm": jnp.ones((L, h), pd),
        "post_attention_layernorm": jnp.ones((L, h), pd),
        "q_proj": core._dense_init(next(keys), (L, h, nh * hd * 2), pd, s),
        "k_proj": core._dense_init(next(keys), (L, h, nkv * hd), pd, s),
        "v_proj": core._dense_init(next(keys), (L, h, nkv * hd), pd, s),
        "o_proj": core._dense_init(next(keys), (L, nh * hd, h), pd, s),
        "q_norm": jnp.ones((L, hd), pd),
        "k_norm": jnp.ones((L, hd), pd),
    }


def _group_shape(cfg) -> Tuple[int, int]:
    interval = cfg.full_attention_interval
    L = cfg.num_hidden_layers
    if L % interval:
        raise ValueError(
            f"qwen3_next requires num_hidden_layers ({L}) divisible by "
            f"full_attention_interval ({interval})"
        )
    return L // interval, interval - 1  # (groups, linear layers per group)


def init_params(rng: jax.Array, cfg: TransformerConfig) -> Params:
    G, P = _group_shape(cfg)
    pd = cfg.param_dtype
    keys = iter(jax.random.split(rng, 64))
    mlp = partial(
        core._moe_params if cfg.is_moe else core._dense_mlp_params, keys, cfg
    )

    def reshape_gp(tree, lead):
        return jax.tree.map(lambda t: t.reshape(lead + t.shape[1:]), tree)

    params: Params = {
        "embed_tokens": core._dense_init(
            next(keys), (cfg.vocab_size, cfg.hidden_size), pd, cfg.initializer_range
        ),
        "norm": jnp.ones((cfg.hidden_size,), pd),
        "linear_layers": reshape_gp(
            {**_mixer_linear_params(keys, cfg, G * P, pd), **mlp(G * P, pd)},
            (G, P),
        ),
        "full_layers": {**_mixer_full_params(keys, cfg, G, pd), **mlp(G, pd)},
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = core._dense_init(
            next(keys), (cfg.hidden_size, cfg.vocab_size), pd, cfg.initializer_range
        )
    return params


def abstract_params(cfg: TransformerConfig) -> Params:
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


# --------------------------------------------------------------------------
# Forward / loss
# --------------------------------------------------------------------------
def forward_hidden(params, cfg, input_ids, position_ids, segment_ids=None,
                   inputs_embeds=None):
    compute = jax.tree.map(lambda p: p.astype(cfg.dtype), params)
    hidden = (
        inputs_embeds.astype(cfg.dtype)
        if inputs_embeds is not None
        else compute["embed_tokens"][input_ids]
    )
    rot_dim = int(cfg.head_dim * cfg.partial_rotary_factor)
    cos, sin = ops.rotary_tables(
        position_ids, rot_dim, cfg.rope_theta, rope_scaling=cfg.rope_scaling
    )
    cos, sin = cos.astype(cfg.dtype), sin.astype(cfg.dtype)

    def lin_body(h_, lp):
        h_, aux, drop = _sublayer(
            h_, lp,
            lambda x, lp_: _gated_delta_net(x, lp_, cfg, segment_ids),
            cfg=cfg,
        )
        return h_, (aux, drop)

    def full_body(h_, lp):
        h_, aux, drop = _sublayer(
            h_, lp,
            lambda x, lp_: _gated_full_attention(x, lp_, cfg, cos, sin, segment_ids),
            cfg=cfg,
        )
        return h_, (aux, drop)

    if cfg.remat:
        lin_body = jax.checkpoint(lin_body, policy=core._remat_policy(cfg))
        full_body = jax.checkpoint(full_body, policy=core._remat_policy(cfg))
    _, P = _group_shape(cfg)
    hidden, (auxes, drops) = period_scan(
        hidden,
        # the one full layer a period has is stacked [G, ...]: give it its axis
        {"linear": compute["linear_layers"],
         "full": jax.tree.map(lambda t: t[:, None], compute["full_layers"])},
        ("linear",) * P + ("full",), {"linear": lin_body, "full": full_body},
    )
    hidden = core._norm(hidden, compute["norm"], cfg)
    return hidden, auxes.sum(), drops.sum() / max(cfg.num_hidden_layers, 1)


def loss_fn(params, cfg, batch):
    hidden, aux, dropped = forward_hidden(
        params, cfg, batch["input_ids"], batch["position_ids"],
        batch.get("segment_ids"),
    )
    return core.head_loss(params, cfg, hidden, batch["labels"], aux, dropped)


def forward_logits(params, cfg, input_ids, position_ids, segment_ids=None):
    hidden, _, _ = forward_hidden(params, cfg, input_ids, position_ids, segment_ids)
    kernel = core.lm_head_kernel(params, cfg).astype(cfg.dtype)
    return jnp.dot(hidden, kernel, preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# HF checkpoint io
# --------------------------------------------------------------------------
def _hf_layer_maps(cfg):
    """(our_key, hf_suffix, transpose) for each mixer kind + the MLP."""
    lin = [
        ("input_layernorm", "input_layernorm.weight", False),
        ("post_attention_layernorm", "post_attention_layernorm.weight", False),
        ("in_proj_qkvz", "linear_attn.in_proj_qkvz.weight", True),
        ("in_proj_ba", "linear_attn.in_proj_ba.weight", True),
        ("dt_bias", "linear_attn.dt_bias", False),
        ("A_log", "linear_attn.A_log", False),
        ("norm", "linear_attn.norm.weight", False),
        ("out_proj", "linear_attn.out_proj.weight", True),
    ]
    full = [
        ("input_layernorm", "input_layernorm.weight", False),
        ("post_attention_layernorm", "post_attention_layernorm.weight", False),
        ("q_proj", "self_attn.q_proj.weight", True),
        ("k_proj", "self_attn.k_proj.weight", True),
        ("v_proj", "self_attn.v_proj.weight", True),
        ("o_proj", "self_attn.o_proj.weight", True),
        ("q_norm", "self_attn.q_norm.weight", False),
        ("k_norm", "self_attn.k_norm.weight", False),
    ]
    if cfg.is_moe:
        mlp = [
            ("router", "mlp.gate.weight", True),
            ("shared_experts.gate_proj", "mlp.shared_expert.gate_proj.weight", True),
            ("shared_experts.up_proj", "mlp.shared_expert.up_proj.weight", True),
            ("shared_experts.down_proj", "mlp.shared_expert.down_proj.weight", True),
            ("shared_expert_gate", "mlp.shared_expert_gate.weight", True),
        ]
    else:
        mlp = [
            ("gate_proj", "mlp.gate_proj.weight", True),
            ("up_proj", "mlp.up_proj.weight", True),
            ("down_proj", "mlp.down_proj.weight", True),
        ]
    return lin, full, mlp


def hf_to_params(model_dir: str, cfg: TransformerConfig, target_shardings=None):
    """Load an HF Qwen3Next checkpoint into the [G, P]-stacked layout.

    Streamed + shard-aligned like ``hf_io.hf_to_params``: with
    ``target_shardings`` every stacked tensor is built via
    ``jax.make_array_from_callback`` whose callback reads only the (layer,
    expert, feature) slices the local shards need from the mmap'd
    safetensors — peak host RAM O(one shard slice), EP processes read only
    their expert slice (reference ``module_utils.py:348,530,867``)."""
    import itertools

    import numpy as np

    from veomni_tpu.models.hf_io import LazyHFTensors
    from veomni_tpu.parallel.parallel_plan import param_path_str

    G, P = _group_shape(cfg)
    interval = cfg.full_attention_interval
    lin_map, full_map, mlp_map = _hf_layer_maps(cfg)
    lazy = LazyHFTensors(model_dir)
    pd = cfg.param_dtype
    pd_np = np.dtype(jnp.zeros((), pd).dtype)

    shardings: Dict[str, Any] = {}
    if target_shardings is not None:
        jax.tree_util.tree_map_with_path(
            lambda p, s: shardings.__setitem__(param_path_str(p), s),
            target_shardings,
        )

    def place(dotted, shape, read_block):
        sh = shardings.get(dotted)
        if shardings and sh is None:
            raise KeyError(f"param {dotted!r} missing from target_shardings")
        if sh is not None:
            return jax.make_array_from_callback(
                tuple(shape), sh,
                lambda idx: np.ascontiguousarray(read_block(idx)).astype(pd_np),
            )
        full = read_block(tuple(slice(None) for _ in shape))
        return jnp.asarray(np.ascontiguousarray(full), pd)

    def lead_positions(lead_slices, lead):
        """Cartesian product of the selected leading (group/per-group)
        positions -> flat layer-list indices + output block shape."""
        ranges = [range(*sl.indices(n)) for sl, n in zip(lead_slices, lead)]
        return list(itertools.product(*ranges)), [len(r) for r in ranges]

    def stacked(dotted, names, lead, transpose, extract=None):
        one = lazy.shape(names[0])
        one_ours = tuple(reversed(one)) if transpose else one
        if extract is not None:
            one_ours = extract.shape(one_ours)
        for real in names:
            lazy.mark_consumed(real)

        def read(idx):
            lead_sl, rest = idx[: len(lead)], tuple(idx[len(lead):])
            pos, block = lead_positions(lead_sl, lead)
            parts = []
            for coords in pos:
                flat = 0
                for c, n in zip(coords, lead):
                    flat = flat * n + c
                if extract is not None:
                    part = extract.extract(
                        lazy.read_slice(names[flat], tuple(slice(None) for _ in one))
                    )[rest]
                elif transpose:
                    part = lazy.read_slice(names[flat], tuple(reversed(rest))).T
                else:
                    part = lazy.read_slice(names[flat], rest)
                parts.append(part)
            return np.stack(parts).reshape(tuple(block) + parts[0].shape)

        return place(dotted, tuple(lead) + tuple(one_ours), read)

    class _ConvSqueeze:
        """HF conv1d [C, 1, K] -> [C, K]."""

        @staticmethod
        def shape(s):
            return (s[0], s[2])

        @staticmethod
        def extract(t):
            return t[:, 0, :]

    def experts_stacked(dotted, idxs, lead, name):
        names = [
            [f"model.layers.{i}.mlp.experts.{e}.{name}.weight"
             for e in range(cfg.num_experts)]
            for i in idxs
        ]
        for row in names:
            for real in row:
                lazy.mark_consumed(real)
        o_dim, i_dim = lazy.shape(names[0][0])

        def read(idx):
            lead_sl, esl = idx[: len(lead)], idx[len(lead)]
            isl, osl = idx[len(lead) + 1], idx[len(lead) + 2]
            pos, block = lead_positions(lead_sl, lead)
            parts = []
            for coords in pos:
                flat = 0
                for c, n in zip(coords, lead):
                    flat = flat * n + c
                row = [
                    lazy.read_slice(names[flat][e], (osl, isl)).T
                    for e in range(*esl.indices(cfg.num_experts))
                ]
                parts.append(np.stack(row))
            return np.stack(parts).reshape(
                tuple(block) + parts[0].shape
            )

        return place(
            dotted, tuple(lead) + (cfg.num_experts, i_dim, o_dim), read
        )

    lin_idxs = [i for i in range(cfg.num_hidden_layers) if (i + 1) % interval]
    full_idxs = [i for i in range(cfg.num_hidden_layers) if not (i + 1) % interval]

    def build_tree(prefix, idxs, maps, lead):
        out: Params = {}
        for ours, suffix, tr in maps:
            names = [f"model.layers.{i}.{suffix}" for i in idxs]
            node = out
            parts = ours.split(".")
            for p_ in parts[:-1]:
                node = node.setdefault(p_, {})
            node[parts[-1]] = stacked(
                f"{prefix}.{ours}", names, lead, tr
            )
        return out

    params: Params = {
        "embed_tokens": place(
            "embed_tokens",
            lazy.shape("model.embed_tokens.weight"),
            lambda idx: lazy.read_slice("model.embed_tokens.weight", idx),
        ),
        "norm": place(
            "norm", lazy.shape("model.norm.weight"),
            lambda idx: lazy.read_slice("model.norm.weight", idx),
        ),
        "linear_layers": build_tree("linear_layers", lin_idxs, lin_map + mlp_map, (G, P)),
        "full_layers": build_tree("full_layers", full_idxs, full_map + mlp_map, (G,)),
    }
    lazy.mark_consumed("model.embed_tokens.weight")
    lazy.mark_consumed("model.norm.weight")
    params["linear_layers"]["conv_weight"] = stacked(
        "linear_layers.conv_weight",
        [f"model.layers.{i}.linear_attn.conv1d.weight" for i in lin_idxs],
        (G, P), False, extract=_ConvSqueeze,
    )
    if cfg.is_moe:
        for tree, idxs, lead, prefix in (
            (params["linear_layers"], lin_idxs, (G, P), "linear_layers"),
            (params["full_layers"], full_idxs, (G,), "full_layers"),
        ):
            tree["experts"] = {
                name: experts_stacked(f"{prefix}.experts.{name}", idxs, lead, name)
                for name in ("gate_proj", "up_proj", "down_proj")
            }
    if not cfg.tie_word_embeddings:
        hf_shape = lazy.shape("lm_head.weight")
        params["lm_head"] = place(
            "lm_head", tuple(reversed(hf_shape)),
            lambda idx: lazy.read_slice(
                "lm_head.weight", tuple(reversed(idx))).T,
        )
        lazy.mark_consumed("lm_head.weight")
    return params


def save_hf_checkpoint(params, cfg: TransformerConfig, out_dir: str) -> None:
    """Export to HF Qwen3Next layout (inverse of hf_to_params)."""
    import os

    import numpy as np
    from safetensors.numpy import save_file

    from veomni_tpu.models.hf_io import gather_to_host

    host = gather_to_host(params)
    if jax.process_index() != 0:
        return
    os.makedirs(out_dir, exist_ok=True)
    G, P = _group_shape(cfg)
    interval = cfg.full_attention_interval
    lin_map, full_map, mlp_map = _hf_layer_maps(cfg)
    flat: Dict[str, Any] = {
        "model.embed_tokens.weight": np.asarray(host["embed_tokens"]),
        "model.norm.weight": np.asarray(host["norm"]),
    }
    if not cfg.tie_word_embeddings:
        flat["lm_head.weight"] = np.asarray(host["lm_head"]).T

    def unstack(tree, idxs, maps, lead_ndim):
        for ours, suffix, tr in maps:
            node = tree
            for p_ in ours.split("."):
                node = node[p_]
            t = np.asarray(node)
            t = t.reshape((-1,) + t.shape[lead_ndim:])
            for pos, i in enumerate(idxs):
                flat[f"model.layers.{i}.{suffix}"] = t[pos].T if tr else t[pos]

    lin_idxs = [i for i in range(cfg.num_hidden_layers) if (i + 1) % interval]
    full_idxs = [i for i in range(cfg.num_hidden_layers) if not (i + 1) % interval]
    unstack(host["linear_layers"], lin_idxs, lin_map + mlp_map, 2)
    unstack(host["full_layers"], full_idxs, full_map + mlp_map, 1)
    conv = np.asarray(host["linear_layers"]["conv_weight"])
    conv = conv.reshape((-1,) + conv.shape[2:])
    for pos, i in enumerate(lin_idxs):
        flat[f"model.layers.{i}.linear_attn.conv1d.weight"] = conv[pos][:, None, :]
    if cfg.is_moe:
        for tree, idxs, lead in (
            (host["linear_layers"], lin_idxs, 2),
            (host["full_layers"], full_idxs, 1),
        ):
            for name in ("gate_proj", "up_proj", "down_proj"):
                t = np.asarray(tree["experts"][name])
                t = t.reshape((-1,) + t.shape[lead:])
                for pos, i in enumerate(idxs):
                    for e in range(cfg.num_experts):
                        flat[f"model.layers.{i}.mlp.experts.{e}.{name}.weight"] = (
                            t[pos, e].T
                        )
    save_file({k: np.ascontiguousarray(v) for k, v in flat.items()},
              os.path.join(out_dir, "model.safetensors"))
    import json

    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump(cfg.to_hf_config(), f, indent=2)


def parallel_plan(cfg):
    from veomni_tpu.parallel.parallel_plan import ParallelPlan

    rules: Dict[str, tuple] = {}
    if cfg.is_moe:
        rules[r"(linear|full)_layers\.experts\..*"] = ("ep", "ep_fsdp", None)
        rules[r"(linear|full)_layers\.router$"] = ()
    return ParallelPlan(
        rules=rules,
        stacked_layer_prefixes=(("linear_layers", 2), ("full_layers", 1)),
    )
