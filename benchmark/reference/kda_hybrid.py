"""Plain reference for the ``kimi_linear`` family: Kimi Delta Attention layers
and NoPE latent-attention (MLA) layers in one stack, a sigmoid-routed expert
layer with a shared expert behind both, a dense SwiGLU in the leading layers.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernel, no chunked form, no sorting or dispatch, and nothing of the program is
imported (the seeded-weight plumbing, the linear with its lower-precision
control, the conv by explicit taps, the row geometry, the clip and AdamW are
``ssm_hybrid.py``'s, beside this file). It follows the Kimi Linear technical
report (arXiv:2510.26692, section 3) and the published ``modeling_kimi.py``:

  * every layer: ``h += mixer(rmsnorm(h)); h += mlp(rmsnorm(h))``, eps
    ``rms_norm_eps``; a final norm; an untied head;
  * the KDA mixer, ``H`` heads of ``d``::

        q, k, v = silu(conv(W_q x)), silu(conv(W_k x)), silu(conv(W_v x))   each its own conv
        q, k    = q / sqrt(|q|^2 + 1e-6), k / sqrt(|k|^2 + 1e-6)            a head
        g_t     = -exp(A_log) * softplus(W_fb W_fa x_t + dt_bias)           [H, d], <= 0
        beta_t  = sigmoid(W_b x_t)                                          [H]
        S'      = diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
        o_t     = d^-1/2 S_t^T q_t
        out     = W_o (rmsnorm_d(o_t; w_o) * sigmoid(W_gb W_ga x_t))

    with the state ``S [H, d, d]`` advanced TOKEN BY TOKEN (``lax.scan`` over
    time; multiplies and sums, no matmul), so that it shares no algebra with
    the program's chunked form;
  * the MLA mixer: ``q = W_q x`` (no low-rank query), ``[c | k_r] = W_kva x``,
    ``[k_n | v] = W_kvb rmsnorm(c)``, ``k = [k_n | k_r to every head]``, NO
    rotary on any part (``mla_use_nope``), a causal softmax inside the
    document at ``(nope + rope) ** -0.5``;
  * the expert layer: ``s = sigmoid(W_r x)``, the top k of ``s + b`` (one
    group), gates ``routed_scaling_factor * s_sel / sum(s_sel)``, SwiGLU
    experts, one shared expert.

Departures and choices, each noted where it happens:
  * documents: a packed row's ``segment_ids`` zero the state ``S`` and the
    convs' taps where a document starts and mask attention to the document
    (the published code takes ``cu_seqlens`` to the same end);
  * this chip's share: the router is ``num_experts_published`` wide and the
    layer sums over the ``num_experts`` experts held here, from
    ``first_expert_held``; what the absent experts would add is left out, as in
    the program; the vocabulary is the slice the configuration gives; the depth
    is the layers ``layer_kinds_run`` names;
  * every held expert visits every position (its gate is zero where the
    router did not choose it): no dispatch to get wrong;
  * the rank's capacity (``moe_capacity_factor``, absent or <= 0: none): the
    held experts together take at most that factor times what an even routing
    would send them from one micro-batch (its rows one after another, in whole
    128-row tiles), filled in the order the positions come; an assignment past
    it is dropped (its gate is zero). The published model drops nothing; the
    buffer is the system's static shape, and what it drops is part of what the
    cell computes, here as in the program;
  * the correction bias ``b`` has no gradient and stays at its seeded zeros; no
    balancing loss term (the family balances by updating ``b``);
  * the time scan is checkpointed in blocks of ``TIME_BLOCK`` steps (every
    step's state at 8,192 tokens would be 17 GB a layer), layers and rows are
    under ``jax.checkpoint``, attention runs over blocks of query positions,
    experts one after another, the head and the loss over chunks of positions:
    so that a step fits one chip beside its own gradient.

Given ``quant="fp8"`` or ``"int8"`` the same functions are the control: every
linear projection rounds both operands to that format (per-row absmax
scaling, straight-through gradient). The router's projection, the recurrence,
the convs and the norms stay in float32 there too.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.ssm_hybrid import (  # noqa: F401  (seed_key, nest: the job's interface)
    _linear, _rms_norm, adamw_step, causal_conv, clip_by_global_norm, flatten, nest,
    row_geometry, seed_key,
)

Params = Dict[str, Any]
INIT_STD = 0.02  # the family's `initializer_range`
STACKS = {"kda_dense": "kda_dense_layers", "mla_dense": "mla_dense_layers",
          "kda": "kda_layers", "mla": "mla_layers"}   # leaves [layers of the kind, ...]
QUERY_BLOCK = 512  # query positions whose scores against all keys exist at once
TIME_BLOCK = 64    # steps of the recurrence whose states the backward pass keeps
HIGHEST = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# weights from the seed (the benchmark's, handed to the program and used here)
# --------------------------------------------------------------------------
def layer_kinds(cfg: dict) -> Tuple[str, ...]:
    """The kind of every layer that is run, in order: ``layer_kinds_run`` (the
    published ``linear_attn_config`` lists cut to ``num_hidden_layers``, as one
    string, since the job hands the reference the file's plain values only),
    with ``_dense`` on the first ``first_k_dense_replace``."""
    kinds = tuple(cfg["layer_kinds_run"].split(","))
    if len(kinds) != cfg["num_hidden_layers"] or not set(kinds) <= {"kda", "mla"}:
        raise ValueError(f"layer_kinds_run {kinds} does not name {cfg['num_hidden_layers']} layers")
    return tuple(k + ("_dense" if i < cfg["first_k_dense_replace"] else "")
                 for i, k in enumerate(kinds))


def _sizes(cfg: dict) -> dict:
    return dict(
        h=cfg["hidden_size"], kh=cfg["kda_num_heads"], kd=cfg["kda_head_dim"],
        kw=cfg["kda_conv_kernel"], heads=cfg["num_attention_heads"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        kvl=cfg["kv_lora_rank"],
        # the router's width is the model's; the experts held are this chip's
        router=cfg.get("num_experts_published", cfg["num_experts"]),
        held=cfg["num_experts"], first=cfg.get("first_expert_held", 0),
        k=cfg["num_experts_per_token"], im=cfg["moe_intermediate_size"],
        shared=cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
    )


def param_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    """Leaf name -> shape, names as the published checkpoint's
    (``kda_layers.f_a_proj`` is ``model.layers.*.self_attn.f_a_proj`` of the
    KDA layers with an expert MLP, stacked and stored input-major; a conv is
    ``self_attn.*_conv1d.weight`` without its middle axis; ``router`` is
    ``block_sparse_moe.gate.weight``)."""
    z = _sizes(cfg)
    h, proj = z["h"], z["kh"] * z["kd"]
    kda = {**{f"{n}_proj": (h, proj) for n in "qkv"},
           **{f"{n}_conv1d": (proj, z["kw"]) for n in "qkv"},
           "f_a_proj": (h, z["kd"]), "f_b_proj": (z["kd"], proj), "dt_bias": (proj,),
           "A_log": (z["kh"],), "b_proj": (h, z["kh"]),
           "g_a_proj": (h, z["kd"]), "g_b_proj": (z["kd"], proj),
           "o_norm": (z["kd"],), "o_proj": (proj, h)}
    mla = {"q_proj": (h, z["heads"] * (z["dn"] + z["dr"])),
           "kv_a_proj_with_mqa": (h, z["kvl"] + z["dr"]), "kv_a_layernorm": (z["kvl"],),
           "kv_b_proj": (z["kvl"], z["heads"] * (z["dn"] + z["dv"])),
           "o_proj": (z["heads"] * z["dv"], h)}
    im = cfg["intermediate_size"]
    dense = {"gate_proj": (h, im), "up_proj": (h, im), "down_proj": (im, h)}
    sparse = {"router": (h, z["router"]), "e_score_correction_bias": (z["router"],),
              "experts.gate_proj": (z["held"], h, z["im"]),
              "experts.up_proj": (z["held"], h, z["im"]),
              "experts.down_proj": (z["held"], z["im"], h),
              "shared_experts.gate_proj": (h, z["shared"]),
              "shared_experts.up_proj": (h, z["shared"]),
              "shared_experts.down_proj": (z["shared"], h)}
    norms = {"input_layernorm": (h,), "post_attention_layernorm": (h,)}
    shapes = {"embed_tokens": (cfg["vocab_size"], h), "norm": (h,),
              "lm_head": (h, cfg["vocab_size"])}
    kinds = layer_kinds(cfg)
    for kind in dict.fromkeys(kinds):
        leaves = {**norms, **(kda if kind.startswith("kda") else mla),
                  **(dense if kind.endswith("_dense") else sparse)}
        for name, shape in leaves.items():
            shapes[f"{STACKS[kind]}.{name}"] = (kinds.count(kind),) + shape
    return shapes


def make_params(cfg: dict, key: jax.Array, dtype=jnp.float32) -> Params:
    """Flat dict name -> array, every random leaf drawn in float32 from
    ``fold_in(key, index of the name)`` and then cast: norms ones, the
    correction bias zeros, ``A_log`` the log of U(1, 16) a head and ``dt_bias``
    the inverse softplus of a ``dt`` log-uniform in [0.001, 0.1] (the published
    modelling file's and flash-linear-attention's initialisation); the convs
    uniform in +-1/sqrt(K) (torch's ``Conv1d`` default, which the published
    ``ShortConvolution`` keeps); all else N(0, 0.02). Meant to run inside one
    ``jax.jit`` that takes ``key`` as an ARGUMENT."""
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        last = name.rsplit(".", 1)[-1]
        if last.endswith("norm"):
            leaf = jnp.ones(shape, jnp.float32)
        elif last == "e_score_correction_bias":
            leaf = jnp.zeros(shape, jnp.float32)
        elif last == "A_log":
            leaf = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif last == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, np.log(0.001), np.log(0.1)))
            leaf = dt + jnp.log(-jnp.expm1(-dt))
        elif last.endswith("_conv1d"):
            bound = shape[-1] ** -0.5
            leaf = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        else:
            leaf = jax.random.normal(k, shape, jnp.float32) * INIT_STD
        out[name] = leaf.astype(dtype)
    return out


# --------------------------------------------------------------------------
# one row through one mixer
# --------------------------------------------------------------------------
def delta_recurrence(q, k, v, g, beta, first):
    """The gated delta rule with one decay a key channel, one token at a time.
    q, k, v, g [S, H, d], beta [S, H], first [S] (True where a document starts)
    -> o [S, H, d]. Blocks of ``TIME_BLOCK`` steps are recomputed in the
    backward pass, which then keeps a block's states and one state a block."""
    s, heads, d = q.shape
    scale = d ** -0.5

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t, first_t = xs
        state = jnp.where(first_t, 0.0, state)          # a document starts from nothing
        state = jnp.exp(g_t)[:, :, None] * state        # one decay a key channel (a row of S)
        seen = jnp.sum(state * k_t[:, :, None], axis=1)              # S'^T k_t  [H, d]
        state = state + (b_t[:, None] * k_t)[:, :, None] * (v_t - seen)[:, None, :]
        return state, scale * jnp.sum(state * q_t[:, :, None], axis=1)

    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    blk = TIME_BLOCK if s % TIME_BLOCK == 0 else s
    xs = tuple(t.reshape(s // blk, blk, *t.shape[1:]) for t in (q, k, v, g, beta, first))
    _, o = jax.lax.scan(jax.checkpoint(block), jnp.zeros((heads, d, d), jnp.float32), xs)
    return o.reshape(s, heads, d)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_mixer(x, lp, cfg, geometry, quant):
    z = _sizes(cfg)
    s, heads, d = x.shape[0], z["kh"], z["kd"]
    since = geometry["since_start"]
    q, k, v = (causal_conv(_linear(x, lp[f"{n}_proj"], quant), lp[f"{n}_conv1d"], 0.0, since)
               .reshape(s, heads, d) for n in "qkv")
    a = _linear(_linear(x, lp["f_a_proj"], quant), lp["f_b_proj"], quant)
    g = -jnp.exp(lp["A_log"])[:, None] * jax.nn.softplus(a + lp["dt_bias"]).reshape(s, heads, d)
    beta = jax.nn.sigmoid(_linear(x, lp["b_proj"], quant))
    o = delta_recurrence(_l2norm(q), _l2norm(k), v, g, beta, since == 0)
    gate = _linear(_linear(x, lp["g_a_proj"], quant), lp["g_b_proj"], quant).reshape(s, heads, d)
    y = _rms_norm(o, lp["o_norm"], cfg["rms_norm_eps"]) * jax.nn.sigmoid(gate)   # a head
    return _linear(y.reshape(s, heads * d), lp["o_proj"], quant)


def mla_mixer(x, lp, cfg, geometry, quant):
    z = _sizes(cfg)
    s, nh, dn, dr, dv = x.shape[0], z["heads"], z["dn"], z["dr"], z["dv"]
    q = _linear(x, lp["q_proj"], quant).reshape(s, nh, dn + dr)   # no low-rank query
    kv_a = _linear(x, lp["kv_a_proj_with_mqa"], quant)
    c_kv, k_r = kv_a[:, :z["kvl"]], kv_a[:, z["kvl"]:]
    kv = _linear(_rms_norm(c_kv, lp["kv_a_layernorm"], cfg["rms_norm_eps"]), lp["kv_b_proj"], quant)
    kv = kv.reshape(s, nh, dn + dv)
    # no rotary on q's second part nor on k_r, which every head shares
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r[:, None, :], (s, nh, dr))], axis=-1)
    v = kv[..., dn:]
    scale = 1.0 / math.sqrt(dn + dr)

    def some_queries(qm):
        q_blk, mask_blk = qm
        scores = jnp.einsum("qhd,khd->hqk", q_blk, k, precision=HIGHEST)
        scores = jnp.where(mask_blk[None], scores * scale, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)

    blk = min(s, QUERY_BLOCK)
    if s % blk:
        blk = s
    ctx = jax.lax.map(jax.checkpoint(some_queries),
                      (q.reshape(s // blk, blk, nh, dn + dr), geometry["mask"].reshape(s // blk, blk, s)))
    return _linear(ctx.reshape(s, nh * dv), lp["o_proj"], quant)


# --------------------------------------------------------------------------
# the MLPs
# --------------------------------------------------------------------------
def _swiglu(x, gate_w, up_w, down_w, quant):
    return _linear(jax.nn.silu(_linear(x, gate_w, quant)) * _linear(x, up_w, quant), down_w, quant)


def route(x, lp, cfg):
    """(expert index [T, k], gate [T, k]): sigmoid scores over ALL the model's
    experts, the top k of score + bias (one group of one: no group limit),
    gates from the scores alone, normalised and scaled. Float32 under every
    ``quant``."""
    scores = jax.nn.sigmoid(jnp.matmul(x, lp["router"], precision=HIGHEST))
    choice = scores + jax.lax.stop_gradient(lp["e_score_correction_bias"])
    _, idx = jax.lax.top_k(choice, _sizes(cfg)["k"])
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("moe_renormalize", True):
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    return idx, gate * cfg["routed_scaling_factor"]


def rank_capacity(cfg: dict, positions: int) -> Optional[int]:
    """Assignments the held experts take from ``positions`` positions, or None
    where the configuration sets no capacity."""
    factor = cfg.get("moe_capacity_factor") or 0
    if factor <= 0:
        return None
    z = _sizes(cfg)
    rows = math.ceil(factor * positions * z["k"] * z["held"] / z["router"])
    return min(-(-rows // 128) * 128, positions * z["k"])


def within_capacity(idx, cfg):
    """idx [T, k] of all the micro-batch's positions in order -> bool [T, k]:
    False for an assignment to a held expert that comes after the rank's
    capacity is full (counted position after position, a position's choices in
    the order top-k gives them)."""
    rows = rank_capacity(cfg, idx.shape[0])
    if rows is None:
        return jnp.ones(idx.shape, bool)
    z = _sizes(cfg)
    mine = (idx >= z["first"]) & (idx < z["first"] + z["held"])
    return ~mine | (jnp.cumsum(mine.reshape(-1)).reshape(idx.shape) <= rows)


def expert_layer(x, lp, cfg, quant=None, routing=None):
    """x [S, H] -> the shared expert's output plus the part of the routed sum
    that the experts held here give. Every held expert computes every
    position; its gate is zero where the router chose another. ``routing``:
    (expert index, gate) where the caller has routed already (a micro-batch of
    several rows under a rank capacity)."""
    z = _sizes(cfg)
    if routing is None:
        idx, gate = route(x, lp, cfg)
        gate = jnp.where(within_capacity(idx, cfg), gate, 0.0)
    else:
        idx, gate = routing

    @jax.checkpoint
    def one_expert(x, e, gate_w, up_w, down_w):
        g = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1)  # [S]
        return g[:, None] * _swiglu(x, gate_w, up_w, down_w, quant)

    ex, se = lp["experts"], lp["shared_experts"]
    shared = _swiglu(x, se["gate_proj"], se["up_proj"], se["down_proj"], quant)
    ids = z["first"] + jnp.arange(z["held"])
    return jax.lax.scan(lambda total, ew: (total + one_expert(x, *ew), None), shared,
                        (ids, ex["gate_proj"], ex["up_proj"], ex["down_proj"]))[0]


# --------------------------------------------------------------------------
# rows: the stack, the head
# --------------------------------------------------------------------------
def one_layer(x, lp, kind: str, cfg, segments, quant):
    """x [R, S, H] through one layer: rows one after another inside it; the
    router sees the micro-batch's rows one after another, since the rank's
    capacity is counted over all of them."""
    eps = cfg["rms_norm_eps"]
    mixer = kda_mixer if kind.startswith("kda") else mla_mixer
    sparse = not kind.endswith("_dense")

    def mix(xs):
        xr, seg = xs
        xr = xr + mixer(_rms_norm(xr, lp["input_layernorm"], eps), lp, cfg, row_geometry(seg), quant)
        return xr, _rms_norm(xr, lp["post_attention_layernorm"], eps)

    def feed_forward(xyr):
        xr, yr, routing = xyr
        if sparse:
            return xr + expert_layer(yr, lp, cfg, quant, routing)
        return xr + _swiglu(yr, lp["gate_proj"], lp["up_proj"], lp["down_proj"], quant)

    x, y = jax.lax.map(jax.checkpoint(mix), (x, segments))
    routing = None
    if sparse:
        r, s, h = y.shape
        idx, gate = route(y.reshape(r * s, h), lp, cfg)
        gate = jnp.where(within_capacity(idx, cfg), gate, 0.0)
        routing = (idx.reshape(r, s, -1), gate.reshape(r, s, -1))
    return jax.lax.map(jax.checkpoint(feed_forward), (x, y, routing))


def loss(params: Params, cfg: dict, ids, segments, quant=None, chunk: int = 128):
    """ids/segments [R, S] -> mean next-token NLL over the predicting
    positions of all rows."""
    kinds = layer_kinds(cfg)
    seen = {kind: 0 for kind in STACKS}
    x = params["embed_tokens"][ids]
    for kind in kinds:
        lp = jax.tree.map(lambda t: t[seen[kind]], params[STACKS[kind]])
        seen[kind] += 1
        x = jax.checkpoint(lambda x, lp, kind=kind: one_layer(x, lp, kind, cfg, segments, quant))(x, lp)
    x = _rms_norm(x, params["norm"], cfg["rms_norm_eps"])
    labels = jnp.concatenate([ids[:, 1:], jnp.zeros_like(ids[:, :1])], axis=1)
    valid = jax.vmap(lambda seg: row_geometry(seg)["valid"])(segments)

    h = x.shape[-1]
    x, labels, valid = x.reshape(-1, h), labels.reshape(-1), valid.reshape(-1)
    n_chunks = -(-x.shape[0] // chunk)
    pad = n_chunks * chunk - x.shape[0]
    x = jnp.pad(x, ((0, pad), (0, 0))).reshape(n_chunks, chunk, h)
    labels = jnp.pad(labels, (0, pad)).reshape(n_chunks, chunk)
    valid_c = jnp.pad(valid, (0, pad)).reshape(n_chunks, chunk)

    def one_chunk(total, hlv):
        hid, lab, ok = hlv
        logp = jax.nn.log_softmax(_linear(hid, params["lm_head"], quant), axis=-1)
        nll = -jnp.take_along_axis(logp, lab[:, None], axis=-1)[:, 0]
        return total + jnp.sum(jnp.where(ok, nll, 0.0)), None

    total = jax.lax.scan(jax.checkpoint(one_chunk), jnp.float32(0.0), (x, labels, valid_c))[0]
    return total / jnp.maximum(jnp.sum(valid), 1)


# --------------------------------------------------------------------------
# training
# --------------------------------------------------------------------------
JOINED = ("A_log", "dt_bias")  # one leaf each over all KDA layers
LEFT_OUT = ("o_norm",)


def leaf_norms(tree: Params) -> Dict[str, jax.Array]:
    """Norm of every leaf that is compared; a leaf stacked over layers gives
    one norm per layer. The small leaves are where a norm is least steady:
    bf16 flips a few top-k choices in the expert layers, the gradient behind
    them changes for those positions, and while a matrix's norm hardly moves
    (what changes is all but orthogonal to the rest), the norm of a vector of
    32 or 128 numbers does. So:

    * ``routers`` are ONE leaf over all layers, as in ``mla_moe.py``;
    * ``A_log`` (32 numbers a layer) and ``dt_bias`` are ONE leaf each over all
      KDA layers, as in ``ssm_hybrid.py``;
    * ``o_norm`` (128 numbers a layer, each a sum over every position AND
      head) is left out, as ``D`` is in ``ssm_hybrid.py``: on the chip the
      first layer's, which every expert layer lies behind, moved by -6.6e-3 to
      +6.8e-3 over 10 sound seeds (the worst leaf in 4 of them, at 6.0e-3 to
      6.8e-3) where every other leaf stays under 3.3e-3; the later layers' move
      as far against their own norms and only pass because those norms are a
      fifth of the median leaf's. Kept in, it alone would set the limit twice
      looser for every other leaf (PERF.md, PR 36). ``o_norm`` itself is
      still held: the CPU tests compare its gradient too, and every gradient
      below it passes through it."""
    out, joined = {}, {"routers": []}
    for name, x in flatten(tree).items():
        x = x.astype(jnp.float32)
        last = name.rsplit(".", 1)[-1]
        if last in LEFT_OUT:
            continue
        if last == "router":
            joined["routers"].append(jnp.sum(jnp.square(x)))
        elif last in JOINED:
            joined.setdefault(last, []).append(jnp.sum(jnp.square(x)))
        elif name.startswith(tuple(STACKS.values())):
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))[None]
    for name, parts in joined.items():
        if parts:
            out[name] = jnp.sqrt(sum(parts))[None]
    return out


def train_reference(cfg: dict, opt: dict, seed: int, batches, quant=None, log=lambda m: None):
    """Follow the first ``len(batches)`` optimizer steps from the seeded
    weights. ``batches``: list of (ids [R, S], segments [R, S]) int arrays.
    Returns host values: the loss of every step, the per-leaf norms of the
    first (clipped) gradient, the per-leaf norms of the parameters' change
    after the last step.

    A step is two programs, so that it fits one chip beside nothing but
    itself: the gradient (weights, gradient and the backward pass's own
    memory on the device) and the update (weights, two moments and the
    gradient; the first three donated). Between steps the moments wait on
    the host."""
    key = seed_key(seed)

    @jax.jit
    def init(key):
        return nest(make_params(cfg, key))

    @jax.jit
    def gradient(params, ids, seg):
        with jax.default_matmul_precision("highest"):
            value, grads = jax.value_and_grad(lambda p: loss(p, cfg, ids, seg, quant))(params)
            grads = clip_by_global_norm(grads, opt["max_grad_norm"])
        return value, grads, leaf_norms(grads)

    def update(params, m, v, grads, t):
        return adamw_step(params, m, v, grads, t.astype(jnp.float32), lr=opt["lr"],
                          b1=opt["betas"][0], b2=opt["betas"][1], eps=1e-8,
                          weight_decay=opt["weight_decay"])

    update = jax.jit(update, donate_argnums=(0, 1, 2))
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))

    @jax.jit
    def change(params, key):
        p0 = nest(make_params(cfg, key))
        return leaf_norms(jax.tree.map(jnp.subtract, params, p0))

    params = init(key)
    jax.block_until_ready(params)
    log("reference: weights made")
    for i, (_, seg) in enumerate(batches):
        starts = [np.flatnonzero(np.diff(row)) + 1 for row in np.asarray(seg)]
        log(f"reference: step {i + 1} follows rows whose documents start at "
            f"{[s.tolist() for s in starts]} (besides 0)")
    out_losses, first_grad = [], None
    m = v = None  # on the host between steps
    for i, (ids, seg) in enumerate(batches):
        value, grads, gnorms = gradient(
            params, jnp.asarray(ids, jnp.int32), jnp.asarray(seg, jnp.int32))
        out_losses.append(value)
        if i == 0:
            first_grad = gnorms
        m, v = (zeros(params), zeros(params)) if m is None else jax.device_put((m, v))
        params, m, v = update(params, m, v, grads, jnp.int32(i + 1))
        del grads
        if i + 1 < len(batches):
            m, v = jax.device_get((m, v))
        jax.block_until_ready(params)
        log(f"reference: step {i + 1} done")
    del m, v
    delta = change(params, key)
    host = jax.device_get((out_losses, first_grad, delta))
    del params
    return {"losses": [float(x) for x in host[0]],
            "first_grad_norms": {k: np.asarray(x) for k, x in host[1].items()},
            "param_change_norms": {k: np.asarray(x) for k, x in host[2].items()}}
