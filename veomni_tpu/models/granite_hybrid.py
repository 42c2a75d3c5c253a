"""granitemoehybrid: Mamba-2 state-space layers and attention layers in one
stack (``transformers``' ``GraniteMoeHybridForCausalLM``; the dense members
of the family, ``num_local_experts`` 0).

Every layer is ``h += m * mixer(norm(h)); h += m * mlp(norm(h))`` with the
residual multiplier ``m``; the MLP is a SwiGLU behind one fused input
projection. Embeddings are scaled by ``embed_scale`` (config.json's
``embedding_multiplier``) and logits divided by ``logits_scaling``. The
attention layers have NO positional embedding (``position_embedding_type``
"nope": no rotary call at all) and the softmax scale ``attention_multiplier``.

The Mamba-2 mixer (``GraniteMoeHybridMambaLayer``)::

    z, xBC, dt = split(in_proj(u), [d_inner, d_inner + 2 G N, H])
    x, B, C    = split(silu(causal_conv1d(xBC, w) + b), [d_inner, G N, G N])
    dt         = softplus(dt + dt_bias);  A = -exp(A_log)
    y          = ssd_scan(x, dt, A, B, C, D)          # ops/ssd_scan.py
    out        = out_proj(rmsnorm(y * silu(z)) * w_norm)   # gate BEFORE the norm

In a packed row every document starts from a zero state and zero conv taps
(``segment_ids``), which the torch slow path cannot do.

The stack is scanned by its period (``qwen3_next.period_scan``): ``layer_types``
gives the order, the smallest period that repeats is found from it, and the
parameters are stacked by kind, ``mamba_layers [G, n_mamba, ...]`` and
``attn_layers [G, n_attn, ...]`` for ``G`` periods.
"""

from __future__ import annotations

import json
import os
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from veomni_tpu import ops
from veomni_tpu.models import transformer as core
from veomni_tpu.models.config import TransformerConfig
from veomni_tpu.models.qwen3_next import _causal_conv1d, period_scan

Params = Dict[str, Any]
KINDS = {"mamba": "mamba_layers", "attention": "attn_layers"}


def period_of(cfg: TransformerConfig) -> Tuple[str, ...]:
    """The layer pattern's period: the shortest prefix of ``layer_types`` that
    the whole list is a whole number of copies of (the list itself at worst)."""
    types = tuple(cfg.layer_types or ())
    L = cfg.num_hidden_layers
    if len(types) != L or not set(types) <= set(KINDS):
        raise ValueError(
            f"granitemoehybrid needs layer_types with one of {sorted(KINDS)} for each of "
            f"its {L} layers (num_hidden_layers has to be a whole number of the pattern's "
            f"periods), got {len(types)}: {types}")
    p = next(p for p in range(1, L + 1)
             if L % p == 0 and all(types[i] == types[i % p] for i in range(L)))
    return types[:p]


def _check(cfg: TransformerConfig) -> None:
    if cfg.num_experts:
        raise NotImplementedError(
            "granitemoehybrid with num_local_experts > 0 (routed experts beside the shared "
            "MLP) is not implemented: only the family's dense members are")
    if cfg.mamba_proj_bias or cfg.attention_bias:
        raise NotImplementedError("granitemoehybrid with projection biases")
    if cfg.position_embedding_type != "nope":
        raise NotImplementedError(
            f"granitemoehybrid with position_embedding_type {cfg.position_embedding_type!r}: "
            "only 'nope' (no positional embedding) is implemented")
    if cfg.mamba_n_heads * cfg.mamba_d_head != cfg.mamba_expand * cfg.hidden_size:
        raise ValueError("mamba_n_heads * mamba_d_head must be mamba_expand * hidden_size")


def _sizes(cfg: TransformerConfig):
    d_inner = cfg.mamba_n_heads * cfg.mamba_d_head
    bc = cfg.mamba_n_groups * cfg.mamba_d_state
    return d_inner, bc, d_inner + 2 * bc


# --------------------------------------------------------------------------
# Params
# --------------------------------------------------------------------------
def _mlp_params(keys, cfg, lead, pd):
    h, im, s = cfg.hidden_size, cfg.intermediate_size, cfg.initializer_range
    return {
        "input_layernorm": jnp.ones(lead + (h,), pd),
        "post_attention_layernorm": jnp.ones(lead + (h,), pd),
        "input_linear": core._dense_init(next(keys), lead + (h, 2 * im), pd, s),
        "output_linear": core._dense_init(next(keys), lead + (im, h), pd, s),
    }


def _mamba_params(keys, cfg, lead, pd):
    h, s, nh = cfg.hidden_size, cfg.initializer_range, cfg.mamba_n_heads
    d_inner, _, conv_dim = _sizes(cfg)
    # the Mamba-2 initialisation ``transformers`` documents (time_step_min/max):
    # dt log-uniform in [0.001, 0.1], dt_bias its inverse softplus
    dt = jnp.exp(jax.random.uniform(next(keys), lead + (nh,), jnp.float32,
                                    jnp.log(0.001), jnp.log(0.1)))
    return {
        "in_proj": core._dense_init(next(keys), lead + (h, d_inner + conv_dim + nh), pd, s),
        # torch's Conv1d default, what the Mamba-2 reference implementation keeps
        "conv_weight": jax.random.uniform(
            next(keys), lead + (conv_dim, cfg.mamba_d_conv), jnp.float32,
            -cfg.mamba_d_conv ** -0.5, cfg.mamba_d_conv ** -0.5).astype(pd),
        "conv_bias": jnp.zeros(lead + (conv_dim,), pd),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
        "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
                                  lead + (nh,)).astype(pd),
        "D": jnp.ones(lead + (nh,), pd),
        "norm": jnp.ones(lead + (d_inner,), pd),
        "out_proj": core._dense_init(next(keys), lead + (d_inner, h), pd, s),
    }


def _attn_params(keys, cfg, lead, pd):
    h, s = cfg.hidden_size, cfg.initializer_range
    return {
        "q_proj": core._dense_init(next(keys), lead + (h, cfg.q_dim), pd, s),
        "k_proj": core._dense_init(next(keys), lead + (h, cfg.kv_dim), pd, s),
        "v_proj": core._dense_init(next(keys), lead + (h, cfg.kv_dim), pd, s),
        "o_proj": core._dense_init(next(keys), lead + (cfg.q_dim, h), pd, s),
    }


def init_params(rng: jax.Array, cfg: TransformerConfig) -> Params:
    _check(cfg)
    period = period_of(cfg)
    G = cfg.num_hidden_layers // len(period)
    pd = cfg.param_dtype
    keys = iter(jax.random.split(rng, 32))
    params: Params = {
        "embed_tokens": core._dense_init(
            next(keys), (cfg.vocab_size, cfg.hidden_size), pd, cfg.initializer_range),
        "norm": jnp.ones((cfg.hidden_size,), pd),
    }
    for kind, mixer in (("mamba", _mamba_params), ("attention", _attn_params)):
        if kind in period:
            lead = (G, period.count(kind))
            params[KINDS[kind]] = {**mixer(keys, cfg, lead, pd), **_mlp_params(keys, cfg, lead, pd)}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = core._dense_init(
            next(keys), (cfg.hidden_size, cfg.vocab_size), pd, cfg.initializer_range)
    return params


def abstract_params(cfg: TransformerConfig) -> Params:
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


# --------------------------------------------------------------------------
# Forward / loss
# --------------------------------------------------------------------------
def _mamba_mixer(x, lp, cfg: TransformerConfig, segment_ids):
    b, s, _ = x.shape
    nh, p, g, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups, cfg.mamba_d_state
    d_inner, bc, conv_dim = _sizes(cfg)
    with jax.named_scope("ssm.proj"):
        zxbcdt = jnp.dot(x, lp["in_proj"])
    with jax.named_scope("ssm.conv"):
        z, xbc, dt = jnp.split(zxbcdt, [d_inner, d_inner + conv_dim], axis=-1)
        xbc = _causal_conv1d(xbc, lp["conv_weight"], segment_ids,
                             bias=lp["conv_bias"] if cfg.mamba_conv_bias else None)
        xs, bm, cm = jnp.split(xbc, [d_inner, d_inner + bc], axis=-1)
    with jax.named_scope("ssm.scan"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32))
        y = ops.ssd_scan(
            xs.reshape(b, s, nh, p), dt, -jnp.exp(lp["A_log"].astype(jnp.float32)),
            bm.reshape(b, s, g, n), cm.reshape(b, s, g, n), lp["D"],
            segment_ids=segment_ids, chunk=cfg.mamba_chunk_size,
        ).reshape(b, s, d_inner)
    with jax.named_scope("ssm.gate_norm"):
        y = _gated_norm(y, z, lp["norm"], cfg)
    with jax.named_scope("ssm.proj"):
        return jnp.dot(y, lp["out_proj"])


def _gated_norm(y, z, weight, cfg: TransformerConfig):
    """``rmsnorm(y * silu(z)) * weight`` in f32: the gate goes on BEFORE the
    norm (``GraniteMoeHybridRMSNormGated``), and the norm is over all of
    ``d_inner``, not per head."""
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_norm_eps)
    return weight * y.astype(cfg.dtype)


def _attention_mixer(x, lp, cfg: TransformerConfig, segment_ids):
    b, s, _ = x.shape
    with jax.named_scope("attn.qkv"):
        q = jnp.dot(x, lp["q_proj"]).reshape(b, s, cfg.num_attention_heads, cfg.head_dim)
        k = jnp.dot(x, lp["k_proj"]).reshape(b, s, cfg.num_key_value_heads, cfg.head_dim)
        v = jnp.dot(x, lp["v_proj"]).reshape(b, s, cfg.num_key_value_heads, cfg.head_dim)
    with jax.named_scope("attn.flash"):
        attn = ops.attention(
            q, k, v, segment_ids=segment_ids, causal=True,
            softmax_scale=cfg.attention_multiplier or cfg.head_dim ** -0.5)
    attn = core.checkpoint_name(attn, "attn_ctx")
    with jax.named_scope("attn.out"):
        return jnp.dot(attn.reshape(b, s, cfg.q_dim), lp["o_proj"])


def _layer(hidden, lp, *, kind: str, cfg: TransformerConfig, segment_ids):
    constrain = core._activation_constraint()
    m = jnp.asarray(cfg.residual_multiplier, cfg.dtype)
    hidden = constrain(hidden)
    if kind == "mamba":
        with jax.named_scope("ssm"):
            x = core._norm(hidden, lp["input_layernorm"], cfg)
            hidden = hidden + m * _mamba_mixer(x, lp, cfg, segment_ids)
    else:
        with jax.named_scope("attn.qkv"):
            x = core._norm(hidden, lp["input_layernorm"], cfg)
        mixed = _attention_mixer(x, lp, cfg, segment_ids)
        with jax.named_scope("attn.out"):
            hidden = hidden + m * mixed
    hidden = constrain(hidden)
    with jax.named_scope("mlp"):
        x = core._norm(hidden, lp["post_attention_layernorm"], cfg)
        gate, up = jnp.split(jnp.dot(x, lp["input_linear"]), 2, axis=-1)
        hidden = hidden + m * jnp.dot(ops.swiglu(gate, up), lp["output_linear"])
    return constrain(hidden), None


def forward_hidden(params, cfg, input_ids, position_ids=None, segment_ids=None,
                   inputs_embeds=None):
    """Final normed hidden ``[B,S,H]``. ``position_ids`` is taken and not
    read: nothing in this family is positional."""
    _check(cfg)
    period = period_of(cfg)
    compute = jax.tree.map(lambda p: p.astype(cfg.dtype), params)
    if inputs_embeds is not None:
        hidden = inputs_embeds.astype(cfg.dtype)
    else:
        with jax.named_scope("embed"):
            hidden = compute["embed_tokens"][input_ids]
            if cfg.embed_scale:
                hidden = hidden * jnp.asarray(cfg.embed_scale, cfg.dtype)
    bodies = {}
    for kind in set(period):
        body = partial(_layer, kind=kind, cfg=cfg, segment_ids=segment_ids)
        if cfg.remat:
            body = jax.checkpoint(body, policy=core._remat_policy(cfg))
        bodies[kind] = body
    hidden, _ = period_scan(
        hidden, {kind: compute[KINDS[kind]] for kind in bodies}, period, bodies)
    with jax.named_scope("lm_head_loss"):
        return core._norm(hidden, compute["norm"], cfg)


def _scaled_for_head(hidden, cfg):
    """The logit divisor, folded into the hidden state in front of the head:
    ``(h / d) W = (h W) / d``, and the division is of [T, H], not [T, V]."""
    if cfg.logits_scaling == 1.0:
        return hidden
    return hidden * jnp.asarray(1.0 / cfg.logits_scaling, hidden.dtype)


def loss_fn(params, cfg, batch):
    hidden = forward_hidden(
        params, cfg, batch["input_ids"], batch.get("position_ids"), batch.get("segment_ids"))
    with jax.named_scope("lm_head_loss"):
        hidden = _scaled_for_head(hidden, cfg)
    return core.head_loss(params, cfg, hidden, batch["labels"], jnp.float32(0.0))


def forward_logits(params, cfg, input_ids, position_ids=None, segment_ids=None):
    hidden = _scaled_for_head(
        forward_hidden(params, cfg, input_ids, position_ids, segment_ids), cfg)
    kernel = core.lm_head_kernel(params, cfg).astype(cfg.dtype)
    return jnp.dot(hidden, kernel, preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# HF checkpoint io (``transformers``' names)
# --------------------------------------------------------------------------
# (ours, the checkpoint's suffix under model.layers.<i>., transposed)
_MLP_NAMES = [
    ("input_layernorm", "input_layernorm.weight", False),
    ("post_attention_layernorm", "post_attention_layernorm.weight", False),
    ("input_linear", "shared_mlp.input_linear.weight", True),
    ("output_linear", "shared_mlp.output_linear.weight", True),
]
_HF_NAMES = {
    "mamba": [
        ("in_proj", "mamba.in_proj.weight", True),
        ("conv_weight", "mamba.conv1d.weight", False),   # [C, 1, K] there, [C, K] here
        ("conv_bias", "mamba.conv1d.bias", False),
        ("dt_bias", "mamba.dt_bias", False),
        ("A_log", "mamba.A_log", False),
        ("D", "mamba.D", False),
        ("norm", "mamba.norm.weight", False),
        ("out_proj", "mamba.out_proj.weight", True),
    ] + _MLP_NAMES,
    "attention": [
        ("q_proj", "self_attn.q_proj.weight", True),
        ("k_proj", "self_attn.k_proj.weight", True),
        ("v_proj", "self_attn.v_proj.weight", True),
        ("o_proj", "self_attn.o_proj.weight", True),
    ] + _MLP_NAMES,
}


def _layers_of(cfg, kind):
    return [i for i, t in enumerate(cfg.layer_types) if t == kind]


def hf_to_params(model_dir: str, cfg: TransformerConfig, target_shardings=None) -> Params:
    """A ``GraniteMoeHybridForCausalLM`` checkpoint into the by-kind stacks,
    one stacked leaf at a time (host memory: one leaf)."""
    from veomni_tpu.models.hf_io import LazyHFTensors

    _check(cfg)
    period = period_of(cfg)
    G = cfg.num_hidden_layers // len(period)
    lazy = LazyHFTensors(model_dir)
    pd = np.dtype(jnp.zeros((), cfg.param_dtype).dtype)

    def place(path, arr):
        arr = np.ascontiguousarray(arr).astype(pd)
        if target_shardings is None:
            return jnp.asarray(arr)
        node = target_shardings
        for part in path:
            node = node[part]
        return jax.device_put(arr, node)

    params: Params = {
        "embed_tokens": place(("embed_tokens",), lazy.read("model.embed_tokens.weight")),
        "norm": place(("norm",), lazy.read("model.norm.weight")),
    }
    for kind in dict.fromkeys(period):
        tree = {}
        for ours, theirs, transposed in _HF_NAMES[kind]:
            rows = [lazy.read(f"model.layers.{i}.{theirs}") for i in _layers_of(cfg, kind)]
            if ours == "conv_weight":
                rows = [r[:, 0, :] for r in rows]
            rows = np.stack([r.T if transposed else r for r in rows])
            tree[ours] = place((KINDS[kind], ours),
                               rows.reshape((G, period.count(kind)) + rows.shape[1:]))
        params[KINDS[kind]] = tree
    if not cfg.tie_word_embeddings:
        params["lm_head"] = place(("lm_head",), lazy.read("lm_head.weight").T)
    else:
        lazy.mark_consumed("lm_head.weight")
    left = lazy.keys()
    if left:
        raise ValueError(f"checkpoint tensors with no place in the model: {sorted(left)[:8]}")
    return params


def save_hf_checkpoint(params, cfg: TransformerConfig, out_dir: str) -> None:
    """The inverse of :func:`hf_to_params`: every name the torch model's
    ``state_dict`` has."""
    from safetensors.numpy import save_file

    from veomni_tpu.models.hf_io import gather_to_host

    host = gather_to_host(params)
    if jax.process_index() != 0:
        return
    os.makedirs(out_dir, exist_ok=True)
    flat = {"model.embed_tokens.weight": np.asarray(host["embed_tokens"]),
            "model.norm.weight": np.asarray(host["norm"])}
    # a tied head is in the state_dict too (the same tensor under its own name)
    flat["lm_head.weight"] = (flat["model.embed_tokens.weight"] if cfg.tie_word_embeddings
                              else np.asarray(host["lm_head"]).T)
    for kind in dict.fromkeys(period_of(cfg)):
        for ours, theirs, transposed in _HF_NAMES[kind]:
            t = np.asarray(host[KINDS[kind]][ours])
            t = t.reshape((-1,) + t.shape[2:])
            for pos, i in enumerate(_layers_of(cfg, kind)):
                leaf = t[pos].T if transposed else t[pos]
                flat[f"model.layers.{i}.{theirs}"] = (
                    leaf[:, None, :] if ours == "conv_weight" else leaf)
    save_file({k: np.ascontiguousarray(v) for k, v in flat.items()},
              os.path.join(out_dir, "model.safetensors"))
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump(cfg.to_hf_config(), f, indent=2)


def parallel_plan(cfg):
    from veomni_tpu.parallel.parallel_plan import ParallelPlan

    return ParallelPlan(
        rules={}, stacked_layer_prefixes=tuple((name, 2) for name in KINDS.values()))
