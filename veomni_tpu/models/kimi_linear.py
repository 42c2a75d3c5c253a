"""kimi_linear: Kimi Delta Attention layers and latent-attention (MLA) layers in
one stack, MoE MLPs behind both (Kimi Linear, arXiv:2510.26692;
``moonshotai/Kimi-Linear-48B-A3B-Instruct``'s ``modeling_kimi.py``).

Every layer is ``h += mixer(norm(h)); h += mlp(norm(h))``. Layer ``i`` (from 1)
has the KDA mixer where ``linear_attn_config["kda_layers"]`` lists it and the
MLA mixer where ``full_attn_layers`` does; the first ``first_k_dense_replace``
layers have a dense SwiGLU, every other the sigmoid-routed expert layer of
``transformer.moe_mlp_with_stats`` (one shared expert, a correction bias that
only chooses, ``routed_scaling_factor``).

The KDA mixer (``H`` heads of ``d``)::

    q, k, v = silu(conv_q(q_proj x)), silu(conv_k(k_proj x)), silu(conv_v(v_proj x))
    q, k    = l2norm(q), l2norm(k)                         a head
    g       = -exp(A_log) * softplus(f_b(f_a x) + dt_bias)  one decay a key channel, f32
    beta    = sigmoid(b_proj x)                            a head
    o       = kda_scan(q, k, v, g, beta)                   ops/kda.py
    out     = o_proj(rmsnorm(o; o_norm) * sigmoid(g_b(g_a x)))

each conv its own depthwise causal one without a bias. In a packed row every
document starts from a zero state and zero conv taps (``segment_ids``).

The MLA mixer is ``transformer._mla_attention`` as the DeepSeek-V3 dialect runs
it, with ``q_lora_rank`` null (one ``q_proj``) and, under ``mla_use_nope``, no
rotary on either part: the op between the projections and the attention op
(``ops.mla_qkv_rotary``) is handed the identity rotation (cos 1, sin 0), so
its kernel still does the split, the broadcast of ``k_rope`` to every head and
the relayout in one pass, and what it writes is the unrotated lanes exactly.

The stack is cut into segments, each a period repeated (``segments_of``): the
published 27 layers are the dense first layer, ``K K M K`` six times, ``K``,
``M``; each segment goes through ``qwen3_next.period_scan``. Parameters are
stacked by kind of layer, ``kda_dense_layers``, ``mla_dense_layers``,
``kda_layers``, ``mla_layers`` ``[layers of the kind, ...]``.

Train path only: there is no cached decode (``decode.no_cached_decode_reason``),
and expert parallelism and sequence parallelism over the KDA layers are not
proven.
"""

from __future__ import annotations

import json
import os
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from veomni_tpu import ops
from veomni_tpu.models import transformer as core
from veomni_tpu.models.config import TransformerConfig
from veomni_tpu.models.qwen3_next import _causal_conv1d, _l2norm, period_scan

Params = Dict[str, Any]
_HELD_IN_F32 = ("A_log", "dt_bias", "o_norm")   # read in the master dtype, not the compute dtype
# kind of layer -> its stack: the mixer, and "_dense" where the MLP is dense
KINDS = {"kda_dense": "kda_dense_layers", "mla_dense": "mla_dense_layers",
         "kda": "kda_layers", "mla": "mla_layers"}


def layer_kinds(cfg: TransformerConfig) -> Tuple[str, ...]:
    """The kind of every layer, in order, from the published 1-based lists."""
    lac = cfg.linear_attn_config or {}
    kda, mla = set(lac.get("kda_layers", ())), set(lac.get("full_attn_layers", ()))
    n = cfg.num_hidden_layers
    if kda & mla or kda | mla != set(range(1, n + 1)):
        raise ValueError(
            f"kimi_linear needs linear_attn_config's kda_layers and full_attn_layers to name "
            f"each of the layers 1..{n} once, got {sorted(kda)} and {sorted(mla)}")
    dense = cfg.first_k_dense_replace if cfg.is_moe else n
    return tuple(("kda" if i in kda else "mla") + ("_dense" if i <= dense else "")
                 for i in range(1, n + 1))


def segments_of(kinds: Tuple[str, ...]) -> List[Tuple[Tuple[str, ...], int]]:
    """``kinds`` as (period, repeats) segments, greedily: from each place the
    period whose whole repeats cover most (the shortest on a tie), a single
    layer where nothing repeats."""
    out, at = [], 0
    while at < len(kinds):
        best = (1, 1)
        for length in range(1, (len(kinds) - at) // 2 + 1):
            period = kinds[at:at + length]
            reps = 1
            while kinds[at + reps * length:at + (reps + 1) * length] == period:
                reps += 1
            if reps > 1 and length * reps > best[0] * best[1]:
                best = (length, reps)
        out.append((kinds[at:at + best[0]], best[1]))
        at += best[0] * best[1]
    return out


def _check(cfg: TransformerConfig) -> None:
    lac = cfg.linear_attn_config or {}
    missing = [k for k in ("kda_layers", "full_attn_layers", "num_heads", "head_dim",
                           "short_conv_kernel_size") if k not in lac]
    if missing:
        raise ValueError(f"kimi_linear needs linear_attn_config with {missing}")
    if not cfg.use_mla or cfg.use_dsa:
        raise NotImplementedError("kimi_linear's full-attention layers are MLA (kv_lora_rank)")
    if cfg.num_nextn_predict_layers:
        raise NotImplementedError("kimi_linear with multi-token prediction modules")
    if cfg.attention_bias or cfg.mlp_bias or cfg.sandwich_norms:
        raise NotImplementedError("kimi_linear with projection biases or sandwich norms")


def _kda_sizes(cfg: TransformerConfig) -> Tuple[int, int, int]:
    lac = cfg.linear_attn_config
    return lac["num_heads"], lac["head_dim"], lac["short_conv_kernel_size"]


# --------------------------------------------------------------------------
# Params
# --------------------------------------------------------------------------
def _kda_params(keys, cfg: TransformerConfig, n: int, pd) -> Params:
    h, s = cfg.hidden_size, cfg.initializer_range
    nh, d, kw = _kda_sizes(cfg)
    proj = nh * d
    # the published modelling file's (and flash-linear-attention's)
    # initialisation: A = U(1, 16) a head, dt log-uniform in [0.001, 0.1]
    dt = jnp.exp(jax.random.uniform(next(keys), (n, proj), jnp.float32,
                                    jnp.log(0.001), jnp.log(0.1)))
    p = {"input_layernorm": jnp.ones((n, h), pd),
         "post_attention_layernorm": jnp.ones((n, h), pd)}
    for name in ("q", "k", "v"):
        p[f"{name}_proj"] = core._dense_init(next(keys), (n, h, proj), pd, s)
        # torch's Conv1d default, which the published ShortConvolution keeps
        p[f"{name}_conv1d"] = jax.random.uniform(
            next(keys), (n, proj, kw), jnp.float32, -kw ** -0.5, kw ** -0.5).astype(pd)
    p.update({
        "f_a_proj": core._dense_init(next(keys), (n, h, d), pd, s),
        "f_b_proj": core._dense_init(next(keys), (n, d, proj), pd, s),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
        "A_log": jnp.log(jax.random.uniform(next(keys), (n, nh), jnp.float32, 1.0, 16.0)).astype(pd),
        "b_proj": core._dense_init(next(keys), (n, h, nh), pd, s),
        "g_a_proj": core._dense_init(next(keys), (n, h, d), pd, s),
        "g_b_proj": core._dense_init(next(keys), (n, d, proj), pd, s),
        "o_norm": jnp.ones((n, d), pd),
        "o_proj": core._dense_init(next(keys), (n, proj, h), pd, s),
    })
    return p


def init_params(rng: jax.Array, cfg: TransformerConfig) -> Params:
    _check(cfg)
    kinds = layer_kinds(cfg)
    pd = cfg.param_dtype
    keys = iter(jax.random.split(rng, 128))
    params: Params = {
        "embed_tokens": core._dense_init(
            next(keys), (cfg.vocab_size, cfg.hidden_size), pd, cfg.initializer_range),
        "norm": jnp.ones((cfg.hidden_size,), pd),
    }
    for kind in dict.fromkeys(kinds):
        n = kinds.count(kind)
        mixer = (_kda_params if kind.startswith("kda") else core._attn_params)(keys, cfg, n, pd)
        mlp = (core._dense_mlp_params if kind.endswith("_dense") else core._moe_params)(
            keys, cfg, n, pd)
        params[KINDS[kind]] = {**mixer, **mlp}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = core._dense_init(
            next(keys), (cfg.hidden_size, cfg.vocab_size), pd, cfg.initializer_range)
    return params


def abstract_params(cfg: TransformerConfig) -> Params:
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


# --------------------------------------------------------------------------
# Forward / loss
# --------------------------------------------------------------------------
def _kda_mixer(x, lp, cfg: TransformerConfig, segment_ids):
    b, s, _ = x.shape
    nh, d, _ = _kda_sizes(cfg)
    f32 = jnp.float32
    with jax.named_scope("kda.proj"):
        q, k, v = (jnp.dot(x, lp[f"{name}_proj"]) for name in ("q", "k", "v"))
        decay = jnp.dot(jnp.dot(x, lp["f_a_proj"]), lp["f_b_proj"])
        gate = jnp.dot(jnp.dot(x, lp["g_a_proj"]), lp["g_b_proj"])
        beta = jnp.dot(x, lp["b_proj"])
    with jax.named_scope("kda.conv"):
        q, k, v = (_causal_conv1d(t, lp[f"{name}_conv1d"], segment_ids).reshape(b, s, nh, d)
                   for name, t in (("q", q), ("k", k), ("v", v)))
    with jax.named_scope("kda.gate"):
        q, k = (_l2norm(t.astype(f32)).astype(cfg.dtype) for t in (q, k))
        g = -jnp.exp(lp["A_log"].astype(f32))[:, None] * jax.nn.softplus(
            (decay.astype(f32) + lp["dt_bias"].astype(f32)).reshape(b, s, nh, d))
        beta = jax.nn.sigmoid(beta.astype(f32))
    with jax.named_scope("kda.scan"):
        o = ops.kda_scan(q, k, v, g, beta, segment_ids)
    with jax.named_scope("kda.gate"):
        # the norm a head, its weight and the gate in f32, one rounding at the end
        o = o.astype(f32)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        y = (lp["o_norm"].astype(f32) * o
             * jax.nn.sigmoid(gate.astype(f32)).reshape(b, s, nh, d)).astype(cfg.dtype)
    with jax.named_scope("kda.proj"):
        return jnp.dot(y.reshape(b, s, nh * d), lp["o_proj"])


def _layer(hidden, lp, *, kind: str, cfg: TransformerConfig, segment_ids, cos, sin):
    hidden = core._activation_constraint()(hidden)
    if kind.startswith("kda"):
        with jax.named_scope("kda"):
            x = core._norm(hidden, lp["input_layernorm"], cfg)
            hidden = hidden + _kda_mixer(x, lp, cfg, segment_ids)
    else:
        with jax.named_scope("attn.qkv"):
            x = core._norm(hidden, lp["input_layernorm"], cfg)
        mixed = core._mla_attention(x, lp, cfg, cos, sin, segment_ids, None)
        with jax.named_scope("attn.out"):
            hidden = hidden + mixed
    return core.feed_forward(hidden, lp, cfg, not kind.endswith("_dense"))


def _mla_tables(cfg: TransformerConfig, position_ids, shape):
    """cos, sin ``[B,S,rope lanes]`` for the MLA layers: the identity rotation
    under ``mla_use_nope``, the rotary tables otherwise."""
    dr = cfg.qk_rope_head_dim
    if cfg.mla_use_nope:
        return jnp.ones(shape + (dr,), cfg.dtype), jnp.zeros(shape + (dr,), cfg.dtype)
    cos, sin = ops.rotary_tables(position_ids, dr, cfg.rope_theta, rope_scaling=cfg.rope_scaling,
                                 interleaved=core.mla_rope_interleaved(cfg))
    return cos.astype(cfg.dtype), sin.astype(cfg.dtype)


def forward_layers(params, cfg, input_ids, position_ids=None, segment_ids=None,
                   inputs_embeds=None) -> Dict[str, Any]:
    """The stack: ``hidden`` (final normed ``[B,S,H]``) and the MoE layers'
    readings under the names ``transformer.forward_layers`` gives them."""
    _check(cfg)
    kinds = layer_kinds(cfg)
    # the decay's own parameters and the gated norm's weight stay as they are
    # held: bf16 would move dt = softplus(. + dt_bias) by up to a percent
    compute = jax.tree_util.tree_map_with_path(
        lambda path, p: p if path[-1].key in _HELD_IN_F32 else p.astype(cfg.dtype), params)
    if inputs_embeds is not None:
        hidden = inputs_embeds.astype(cfg.dtype)
    else:
        with jax.named_scope("embed"):
            hidden = compute["embed_tokens"][input_ids]
    cos, sin = _mla_tables(cfg, position_ids, hidden.shape[:2])
    bodies = {}
    for kind in set(kinds):
        body = partial(_layer, kind=kind, cfg=cfg, segment_ids=segment_ids, cos=cos, sin=sin)
        if cfg.remat:
            body = jax.checkpoint(body, policy=core._remat_policy(cfg))
        bodies[kind] = body
    fold = (jnp.zeros((6,), jnp.float32), core._add_layer_stats)
    stats, done = fold[0], {kind: 0 for kind in bodies}
    for period, reps in segments_of(kinds):
        stacks = {}
        for kind in set(period):
            n = period.count(kind)
            lo = done[kind]
            done[kind] += reps * n
            stacks[kind] = jax.tree.map(
                lambda t: t[lo:lo + reps * n].reshape((reps, n) + t.shape[1:]),
                compute[KINDS[kind]])
        hidden, per_period = period_scan(hidden, stacks, period, bodies, fold=fold)
        stats = core._add_layer_stats(stats, per_period)
    with jax.named_scope("lm_head_loss"):
        hidden = core._norm(hidden, compute["norm"], cfg)
    out = {"hidden": hidden, "moe_aux": stats[0],
           "moe_dropped_frac": stats[1] / jnp.maximum(stats[2], 1.0)}
    n_moe = sum(not kind.endswith("_dense") for kind in kinds)
    if n_moe:
        routed = n_moe * hidden.shape[0] * hidden.shape[1] * cfg.num_experts_per_tok
        with jax.named_scope("moe.route"):
            out["moe_assignment_counts"] = jnp.stack(
                [jnp.float32(routed), stats[2], stats[1], *stats[3:5]])
        out["moe_load_max_over_mean"] = stats[5]
    return out


def loss_fn(params, cfg, batch):
    out = forward_layers(params, cfg, batch["input_ids"], batch.get("position_ids"),
                         batch.get("segment_ids"))
    total, metrics = core.head_loss(params, cfg, out["hidden"], batch["labels"],
                                    out["moe_aux"], out["moe_dropped_frac"])
    # read by observability/callback.py (moe.assignments* counters, the gauge)
    metrics.update({k: out[k] for k in ("moe_assignment_counts", "moe_load_max_over_mean")
                    if k in out})
    return total, metrics


def forward_logits(params, cfg, input_ids, position_ids=None, segment_ids=None):
    hidden = forward_layers(params, cfg, input_ids, position_ids, segment_ids)["hidden"]
    kernel = core.lm_head_kernel(params, cfg).astype(cfg.dtype)
    return jnp.dot(hidden, kernel, preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# HF checkpoint io (the published module names)
# --------------------------------------------------------------------------
# (ours, the checkpoint's suffix under model.layers.<i>., transposed)
_NORM_NAMES = [("input_layernorm", "input_layernorm.weight", False),
               ("post_attention_layernorm", "post_attention_layernorm.weight", False)]
_MIXER_NAMES = {
    "kda": [(f"{n}_proj", f"self_attn.{n}_proj.weight", True) for n in "qkv"]
    + [(f"{n}_conv1d", f"self_attn.{n}_conv1d.weight", False) for n in "qkv"]  # [C, 1, K] there
    + [("f_a_proj", "self_attn.f_a_proj.weight", True),
       ("f_b_proj", "self_attn.f_b_proj.weight", True),
       ("dt_bias", "self_attn.dt_bias", False),
       ("A_log", "self_attn.A_log", False),                                    # [1, 1, H, 1] there
       ("b_proj", "self_attn.b_proj.weight", True),
       ("g_a_proj", "self_attn.g_a_proj.weight", True),
       ("g_b_proj", "self_attn.g_b_proj.weight", True),
       ("o_norm", "self_attn.o_norm.weight", False),
       ("o_proj", "self_attn.o_proj.weight", True)],
    "mla": [("q_proj", "self_attn.q_proj.weight", True),
            ("kv_a_proj_with_mqa", "self_attn.kv_a_proj_with_mqa.weight", True),
            ("kv_a_layernorm", "self_attn.kv_a_layernorm.weight", False),
            ("kv_b_proj", "self_attn.kv_b_proj.weight", True),
            ("o_proj", "self_attn.o_proj.weight", True)],
}
_DENSE_NAMES = [(n, f"mlp.{n}.weight", True) for n in ("gate_proj", "up_proj", "down_proj")]
_MOE_NAMES = [("router", "block_sparse_moe.gate.weight", True),
              ("e_score_correction_bias", "block_sparse_moe.gate.e_score_correction_bias", False)]
# ours under experts. / shared_experts. -> the checkpoint's (w1 gate, w3 up, w2 down)
_EXPERT_NAMES = {"gate_proj": "w1", "up_proj": "w3", "down_proj": "w2"}


def _leaf_names(kind: str):
    mixer = _MIXER_NAMES["kda" if kind.startswith("kda") else "mla"]
    return _NORM_NAMES + mixer + (_DENSE_NAMES if kind.endswith("_dense") else _MOE_NAMES)


def _to_checkpoint(ours: str, leaf):
    if ours.endswith("_conv1d"):
        return leaf[:, None, :]
    if ours == "A_log":
        return leaf.reshape(1, 1, -1, 1)
    return leaf


def _from_checkpoint(ours: str, leaf):
    if ours.endswith("_conv1d"):
        return leaf[:, 0, :]
    if ours == "A_log":
        return leaf.reshape(-1)
    return leaf


def _held(cfg: TransformerConfig):
    """The routed experts this model holds, by their published numbers."""
    return range(cfg.moe_experts_held_first, cfg.moe_experts_held_first + cfg.experts_held)


def hf_to_params(model_dir: str, cfg: TransformerConfig, target_shardings=None) -> Params:
    """A ``KimiLinearForCausalLM`` checkpoint into the by-kind stacks, one
    stacked leaf at a time (host memory: one leaf). Of the routed experts only
    those this model holds are read."""
    from veomni_tpu.models.hf_io import LazyHFTensors

    _check(cfg)
    kinds = layer_kinds(cfg)
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
    for kind in dict.fromkeys(kinds):
        layers = [i for i, k in enumerate(kinds) if k == kind]
        tree: Params = {}
        for ours, theirs, transposed in _leaf_names(kind):
            rows = [_from_checkpoint(ours, lazy.read(f"model.layers.{i}.{theirs}")) for i in layers]
            tree[ours] = place((KINDS[kind], ours), np.stack([r.T if transposed else r for r in rows]))
        if not kind.endswith("_dense"):
            moe = "model.layers.{}.block_sparse_moe."
            tree["experts"] = {ours: place((KINDS[kind], "experts", ours), np.stack([np.stack([
                lazy.read(f"{moe.format(i)}experts.{e}.{theirs}.weight").T for e in _held(cfg)])
                for i in layers])) for ours, theirs in _EXPERT_NAMES.items()}
            tree["shared_experts"] = {ours: place((KINDS[kind], "shared_experts", ours), np.stack([
                lazy.read(f"{moe.format(i)}shared_experts.{ours}.weight").T for i in layers]))
                for ours in _EXPERT_NAMES}
        params[KINDS[kind]] = tree
    if not cfg.tie_word_embeddings:
        params["lm_head"] = place(("lm_head",), lazy.read("lm_head.weight").T)
    else:
        lazy.mark_consumed("lm_head.weight")
    left = lazy.keys()
    if cfg.experts_held != cfg.num_experts:
        left = [k for k in left if ".block_sparse_moe.experts." not in k]  # the other ranks'
    if left:
        raise ValueError(f"checkpoint tensors with no place in the model: {sorted(left)[:8]}")
    return params


def save_hf_checkpoint(params, cfg: TransformerConfig, out_dir: str) -> None:
    """The inverse of :func:`hf_to_params`: every name the torch model's
    ``state_dict`` has for what this model holds."""
    from safetensors.numpy import save_file

    from veomni_tpu.models.hf_io import gather_to_host

    host = gather_to_host(params)
    if jax.process_index() != 0:
        return
    os.makedirs(out_dir, exist_ok=True)
    kinds = layer_kinds(cfg)
    flat = {"model.embed_tokens.weight": np.asarray(host["embed_tokens"]),
            "model.norm.weight": np.asarray(host["norm"])}
    flat["lm_head.weight"] = (flat["model.embed_tokens.weight"] if cfg.tie_word_embeddings
                              else np.asarray(host["lm_head"]).T)
    for kind in dict.fromkeys(kinds):
        tree = host[KINDS[kind]]
        for pos, i in enumerate(k_i for k_i, k in enumerate(kinds) if k == kind):
            at = f"model.layers.{i}."
            for ours, theirs, transposed in _leaf_names(kind):
                leaf = np.asarray(tree[ours][pos])
                flat[at + theirs] = _to_checkpoint(ours, leaf.T if transposed else leaf)
            if kind.endswith("_dense"):
                continue
            for ours, theirs in _EXPERT_NAMES.items():
                for slot, e in enumerate(_held(cfg)):
                    flat[f"{at}block_sparse_moe.experts.{e}.{theirs}.weight"] = np.asarray(
                        tree["experts"][ours][pos, slot]).T
                flat[f"{at}block_sparse_moe.shared_experts.{ours}.weight"] = np.asarray(
                    tree["shared_experts"][ours][pos]).T
    save_file({k: np.ascontiguousarray(v) for k, v in flat.items()},
              os.path.join(out_dir, "model.safetensors"))
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump(cfg.to_hf_config(), f, indent=2)


def parallel_plan(cfg):
    from veomni_tpu.parallel.parallel_plan import ParallelPlan

    return ParallelPlan(
        rules={r"(kda|mla)_layers\.experts\..*": ("ep", "ep_fsdp", None),
               r"(kda|mla)_layers\.router$": ()},
        stacked_layer_prefixes=tuple((name, 1) for name in KINDS.values()))
