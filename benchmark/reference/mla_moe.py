"""Plain reference for the DeepSeek-V3 block family: latent attention (MLA),
a sigmoid-routed expert layer with a shared expert, multi-token prediction.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernel, no cache, no sorting or dispatch, and nothing of the program is
imported. It follows the published description (DeepSeek-V3 technical report,
sections 2.1 and 2.2, and the ``transformers`` ``deepseek_v3`` modeling code
that ``JoyAI-LLM-Flash``'s ``config.json`` names the keys of):

  * attention: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` split per head into
    nope and rope parts; ``[c_kv | k_r] = x W_kva``, ``[k_nope | v] =
    RMSNorm(c_kv) W_kvb``; rope on ``q``'s rope part and on ``k_r``, which all
    heads share; scores scaled by ``(nope + rope) ** -0.5``;
  * expert layer: ``s = sigmoid(x W_r)``, the top k of ``s + b`` (one group),
    gates ``routed_scaling_factor * s_sel / sum(s_sel)``, SwiGLU experts, one
    shared expert;
  * multi-token prediction: ``h' = W_eh [RMSNorm(Emb(t[i+1])) ; RMSNorm(h[i])]``,
    one decoder layer, RMSNorm, the model's head, cross-entropy against
    ``t[i+2]``; loss ``L_main + lambda * L_mtp``.

Departures and choices, each noted where it happens:
  * rope rotates adjacent pairs ``(x[2i], x[2i+1])`` in place; the released
    code first moves the pairs apart and rotates halves, which gives the same
    scores (q and k are permuted alike);
  * this chip's share: the router is ``n_routed_experts_published`` wide and the
    layer sums over the ``n_routed_experts`` experts held here, from
    ``first_expert_held``; what the absent experts would add is left out, as in
    the program; the vocabulary is the slice the configuration gives;
  * every held expert visits every position (its gate is zero where the
    router did not choose it): no dispatch to get wrong;
  * the rank's capacity (``moe_capacity_factor``, absent or <= 0: none): the
    held experts together take at most that factor times what an even routing
    would send them from one micro-batch (its rows one after another, in
    whole 128-row tiles), filled in the order the positions come; an
    assignment past it is dropped (its gate is zero). The published model
    drops nothing; the cell's buffer is the issue's, and what it drops is
    part of what the cell computes, here as in the program;
  * ``h[i]`` handed to the MTP module is the last layer's output before the
    final norm (the report's ``h_i^{k-1}``; the module has its own ``hnorm``);
    ``eh_proj`` takes the embedding first, as the released checkpoints do;
  * the correction bias ``b`` is a parameter with no gradient and stays at its
    seeded zeros; no balancing loss term (the family balances by updating
    ``b``, a recipe the config does not give);
  * layers are stacked and scanned under ``jax.checkpoint``, rows are mapped
    one after another inside each layer, attention runs over blocks of query
    positions, the head and the loss over chunks of positions: so that the
    step fits beside its own AdamW state on one chip.

Given ``quant="fp8"`` or ``"int8"`` the same functions are the control: every
linear projection rounds both operands to that format (per-row absmax
scaling, straight-through gradient). The router's projection stays in float32
there too, as lower-precision recipes keep it: the control then fails by its
arithmetic, not by the experts it flips.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]
INIT_STD = 0.02  # the family's `initializer_range`
STACKED = ("dense_layers.", "layers.", "mtp.")  # leaves with a leading layer axis
QUERY_BLOCK = 512  # query positions whose scores against all keys exist at once


# --------------------------------------------------------------------------
# weights from the seed (the benchmark's, handed to the program and used here)
# --------------------------------------------------------------------------
def _sizes(cfg: dict) -> dict:
    return dict(
        h=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        ql=cfg["q_lora_rank"], kvl=cfg["kv_lora_rank"],
        dense=cfg["first_k_dense_replace"], depth=cfg.get("num_nextn_predict_layers", 0),
        # the router's width is the model's; the experts held are this chip's
        router=cfg.get("n_routed_experts_published", cfg["n_routed_experts"]),
        held=cfg["n_routed_experts"], first=cfg.get("first_expert_held", 0),
        k=cfg["num_experts_per_tok"], im=cfg["moe_intermediate_size"],
        shared=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
    )


def param_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    """Leaf name -> shape, names as the published checkpoint's
    (``layers.q_a_proj`` is ``model.layers.*.self_attn.q_a_proj`` stacked over
    the expert layers and stored input-major; ``mtp.*`` is
    ``model.layers.{num_hidden_layers}.*``)."""
    z = _sizes(cfg)
    h, nh = z["h"], z["heads"]
    L = cfg["num_hidden_layers"]

    def attention(p, n):
        return {
            f"{p}.input_layernorm": (n, h),
            f"{p}.q_a_proj": (n, h, z["ql"]),
            f"{p}.q_a_layernorm": (n, z["ql"]),
            f"{p}.q_b_proj": (n, z["ql"], nh * (z["dn"] + z["dr"])),
            f"{p}.kv_a_proj_with_mqa": (n, h, z["kvl"] + z["dr"]),
            f"{p}.kv_a_layernorm": (n, z["kvl"]),
            f"{p}.kv_b_proj": (n, z["kvl"], nh * (z["dn"] + z["dv"])),
            f"{p}.o_proj": (n, nh * z["dv"], h),
            f"{p}.post_attention_layernorm": (n, h),
        }

    def experts(p, n):
        return {
            f"{p}.router": (n, h, z["router"]),
            f"{p}.e_score_correction_bias": (n, z["router"]),
            f"{p}.experts.gate_proj": (n, z["held"], h, z["im"]),
            f"{p}.experts.up_proj": (n, z["held"], h, z["im"]),
            f"{p}.experts.down_proj": (n, z["held"], z["im"], h),
            f"{p}.shared_experts.gate_proj": (n, h, z["shared"]),
            f"{p}.shared_experts.up_proj": (n, h, z["shared"]),
            f"{p}.shared_experts.down_proj": (n, z["shared"], h),
        }

    im = cfg["intermediate_size"]
    shapes = {
        "embed_tokens": (cfg["vocab_size"], h),
        "norm": (h,),
        "lm_head": (h, cfg["vocab_size"]),
        **attention("dense_layers", z["dense"]),
        "dense_layers.gate_proj": (z["dense"], h, im),
        "dense_layers.up_proj": (z["dense"], h, im),
        "dense_layers.down_proj": (z["dense"], im, h),
        **attention("layers", L - z["dense"]),
        **experts("layers", L - z["dense"]),
    }
    if z["depth"]:
        d = z["depth"]
        shapes.update({"mtp.enorm": (d, h), "mtp.hnorm": (d, h), "mtp.eh_proj": (d, 2 * h, h),
                       "mtp.norm": (d, h), **attention("mtp", d), **experts("mtp", d)})
    return shapes


def seed_key(seed: int) -> jax.Array:
    """A key from any whole-number seed (the driver's pass 2**31)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)), seed // (2 ** 31))


def make_params(cfg: dict, key: jax.Array, dtype=jnp.float32) -> Params:
    """Flat dict name -> array: norms are ones, the routers' correction bias
    zeros, every other leaf N(0, 0.02) drawn in float32 from
    ``fold_in(key, index of the name)`` and then cast. Meant to run inside one
    ``jax.jit`` that takes ``key`` as an ARGUMENT (a constant key is folded at
    compile time: PERF.md, PR 26)."""
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        if name.endswith("norm"):
            leaf = jnp.ones(shape, dtype)
        elif name.endswith("e_score_correction_bias"):
            leaf = jnp.zeros(shape, dtype)
        else:
            leaf = (jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
                    * INIT_STD).astype(dtype)
        out[name] = leaf
    return out


def nest(flat: Params) -> Params:
    """``{"layers.experts.up_proj": x}`` -> ``{"layers": {"experts": {"up_proj": x}}}``."""
    tree: Params = {}
    for name, leaf in flat.items():
        node = tree
        *parents, last = name.split(".")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def flatten(tree: Params, prefix: str = "") -> Params:
    out = {}
    for k, v in tree.items():
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, name + "."))
        else:
            out[name] = v
    return out


# --------------------------------------------------------------------------
# precision: float32 "highest", or the control's lower format
# --------------------------------------------------------------------------
def _fake_quant(x, quant: str):
    """Round ``x`` to ``quant`` with one absmax scale per row of its last
    axis; the gradient passes straight through."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True) + 1e-30
    if quant == "int8":
        q = jnp.round(x / amax * 127.0) / 127.0 * amax
    elif quant == "fp8":
        q = (x / amax * 448.0).astype(jnp.float8_e4m3fn).astype(x.dtype) / 448.0 * amax
    else:
        raise ValueError(f"unknown control precision {quant!r}")
    return x + jax.lax.stop_gradient(q - x)


def _linear(x, w, quant: Optional[str]):
    """x [..., K] @ w [K, N]."""
    if quant:
        x = _fake_quant(x, quant)
        w = _fake_quant(w.T, quant).T  # one scale per output channel
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


# --------------------------------------------------------------------------
# one row through one decoder layer
# --------------------------------------------------------------------------
def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope_angles(positions, dim: int, theta: float):
    """[S, dim]: position times ``theta ** (-2 i / dim)``, each frequency
    ``i`` twice, side by side (for the pair it turns)."""
    inv = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    inv = jnp.asarray(np.repeat(inv, 2), jnp.float32)
    return positions.astype(jnp.float32)[:, None] * inv[None, :]


def _rope(x, ang):
    """x [S, H, D]: the pair (x[2i], x[2i+1]) turned by ``ang[:, 2i]``:
    ``(a, b) -> (a cos - b sin, b cos + a sin)``. (The released code
    de-interleaves first and rotates halves: q and k are then permuted alike,
    and q . k is the same.) The partner of a lane is its neighbour: the next
    lane for an even one, the one before for an odd one."""
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even = (jnp.arange(x.shape[-1]) % 2) == 0
    partner = jnp.where(even, -jnp.roll(x, -1, axis=-1), jnp.roll(x, 1, axis=-1))
    return x * cos + partner * sin


def _attention(x, lp, cfg, ang, mask, quant):
    z = _sizes(cfg)
    s, nh, dn, dr, dv = x.shape[0], z["heads"], z["dn"], z["dr"], z["dv"]
    eps = cfg["rms_norm_eps"]
    c_q = _rms_norm(_linear(x, lp["q_a_proj"], quant), lp["q_a_layernorm"], eps)
    q = _linear(c_q, lp["q_b_proj"], quant).reshape(s, nh, dn + dr)
    kv_a = _linear(x, lp["kv_a_proj_with_mqa"], quant)
    c_kv, k_r = kv_a[:, :z["kvl"]], kv_a[:, z["kvl"]:]
    kv = _linear(_rms_norm(c_kv, lp["kv_a_layernorm"], eps), lp["kv_b_proj"], quant)
    kv = kv.reshape(s, nh, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], ang)], axis=-1)
    k_r = _rope(k_r[:, None, :], ang)  # one rope key for all heads
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_r, (s, nh, dr))], axis=-1)
    scale = 1.0 / math.sqrt(dn + dr)

    def some_queries(qm):
        # a block of query positions against every key, so that the [H, S, S]
        # scores of an 8192-token row never exist at once
        q_blk, mask_blk = qm
        scores = jnp.einsum("qhd,khd->hqk", q_blk, k, precision=jax.lax.Precision.HIGHEST)
        scores = jnp.where(mask_blk[None], scores * scale, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=jax.lax.Precision.HIGHEST)

    blk = min(s, QUERY_BLOCK)
    if s % blk:
        blk = s
    ctx = jax.lax.map(jax.checkpoint(some_queries),
                      (q.reshape(s // blk, blk, nh, dn + dr), mask.reshape(s // blk, blk, s)))
    return _linear(ctx.reshape(s, nh * dv), lp["o_proj"], quant)


def _swiglu(x, gate_w, up_w, down_w, quant):
    gate = _linear(x, gate_w, quant)
    up = _linear(x, up_w, quant)
    return _linear(jax.nn.silu(gate) * up, down_w, quant)


def route(x, lp, cfg):
    """(expert index [S, k], gate [S, k]): sigmoid scores over ALL the model's
    experts, the top k of score + bias (one group, so no group limit), gates
    from the scores alone, normalised and scaled. Float32 under every
    ``quant``."""
    z = _sizes(cfg)
    scores = jax.nn.sigmoid(jnp.matmul(x, lp["router"], precision=jax.lax.Precision.HIGHEST))
    choice = scores + jax.lax.stop_gradient(lp["e_score_correction_bias"])
    _, idx = jax.lax.top_k(choice, z["k"])
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
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
    # one expert after another; only the expert's own work is recomputed in
    # the backward pass, so the running sum is kept nowhere
    ids = z["first"] + jnp.arange(z["held"])
    return jax.lax.scan(lambda total, ew: (total + one_expert(x, *ew), None), shared,
                        (ids, ex["gate_proj"], ex["up_proj"], ex["down_proj"]))[0]


def _attend(x, lp, cfg, ang, mask, quant):
    """The attention block of a layer: (x after it, the next block's normed input)."""
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, lp["input_layernorm"], eps), lp, cfg, ang, mask, quant)
    return x, _rms_norm(x, lp["post_attention_layernorm"], eps)


# --------------------------------------------------------------------------
# rows: targets, the stack, the heads
# --------------------------------------------------------------------------
def row_targets(ids, segments, ahead: int = 1):
    """(ids ``ahead`` positions on, which positions predict them, positions
    within the document). A position predicts the token ``ahead`` places on
    only inside its own document; padding (segment 0) predicts nothing."""
    s = ids.shape[0]
    pad = lambda a: jnp.concatenate([a[ahead:], jnp.zeros((ahead,), a.dtype)])
    valid = (segments > 0) & (pad(segments) == segments)
    starts = jnp.where(
        jnp.concatenate([jnp.ones((1,), bool), segments[1:] != segments[:-1]]),
        jnp.arange(s), 0)
    return pad(ids), valid, jnp.arange(s) - jax.lax.cummax(starts)


def _row_geometry(cfg, ids, segments):
    s = ids.shape[0]
    idx = jnp.arange(s)
    mask = (idx[:, None] >= idx[None, :]) & (segments[:, None] == segments[None, :])
    positions = row_targets(ids, segments)[2]
    return _rope_angles(positions, cfg["qk_rope_head_dim"], cfg["rope_theta"]), mask


def _stack(x, layers, cfg, ids, segments, quant, sparse):
    """x [R, S, H] through a stack of layers: layers outermost, rows one after
    another inside each (so that a layer's weight gradient is summed over the
    rows at a layer's size, not the model's)."""

    def body(x, lp):
        def attend(xis):
            xr, i, seg = xis
            ang, mask = _row_geometry(cfg, i, seg)
            return _attend(xr, lp, cfg, ang, mask, quant)

        def feed_forward(xyr):
            xr, yr, routing = xyr
            if sparse:
                return xr + expert_layer(yr, lp, cfg, quant, routing)
            return xr + _swiglu(yr, lp["gate_proj"], lp["up_proj"], lp["down_proj"], quant)

        x, y = jax.lax.map(jax.checkpoint(attend), (x, ids, segments))
        routing = None
        if sparse:
            # the router sees the micro-batch's rows one after another: the
            # rank's capacity is counted over all of them
            r, s_, h = y.shape
            idx, gate = route(y.reshape(r * s_, h), lp, cfg)
            gate = jnp.where(within_capacity(idx, cfg), gate, 0.0)
            routing = (idx.reshape(r, s_, -1), gate.reshape(r, s_, -1))
        return jax.lax.map(jax.checkpoint(feed_forward), (x, y, routing)), None

    return jax.lax.scan(jax.checkpoint(body), x, layers)[0]


def _nll_sum(params, hidden, labels, valid, quant, chunk: int = 128):
    """Sum of the next-token NLL of normed ``hidden [R, S, H]`` through the
    head over the ``valid`` positions, in chunks of positions."""
    h = hidden.shape[-1]
    hidden, labels, valid = hidden.reshape(-1, h), labels.reshape(-1), valid.reshape(-1)
    n = hidden.shape[0]
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    hidden = jnp.pad(hidden, ((0, pad), (0, 0))).reshape(n_chunks, chunk, h)
    labels = jnp.pad(labels, (0, pad)).reshape(n_chunks, chunk)
    valid = jnp.pad(valid, (0, pad)).reshape(n_chunks, chunk)

    def one_chunk(total, hlv):
        hid, lab, ok = hlv
        logp = jax.nn.log_softmax(_linear(hid, params["lm_head"], quant), axis=-1)
        nll = -jnp.take_along_axis(logp, lab[:, None], axis=-1)[:, 0]
        return total + jnp.sum(jnp.where(ok, nll, 0.0)), None

    return jax.lax.scan(jax.checkpoint(one_chunk), jnp.float32(0.0), (hidden, labels, valid))[0]


def losses(params: Params, cfg: dict, ids, segments, quant=None):
    """ids/segments [R, S] -> (main loss, MTP loss): each the mean of its NLL
    over its own predicting positions of all rows (MTP: the mean over the
    modules of such means; 0 without modules)."""
    eps = cfg["rms_norm_eps"]
    x = params["embed_tokens"][ids]
    x = _stack(x, params["dense_layers"], cfg, ids, segments, quant, False)
    x = _stack(x, params["layers"], cfg, ids, segments, quant, True)
    targets = jax.vmap(row_targets)(ids, segments)
    main = _nll_sum(params, _rms_norm(x, params["norm"], eps), targets[0], targets[1], quant)
    main = main / jnp.maximum(jnp.sum(targets[1]), 1)
    depth = _sizes(cfg)["depth"]
    mtp = jnp.float32(0.0)
    for d in range(depth):
        mp = jax.tree.map(lambda t: t[d:d + 1], params["mtp"])
        # position i joins the embedding of token i+d+1 with the representation
        # below it at i (the last layer's output BEFORE the final norm for the
        # first module), and predicts token i+d+2 inside i's own document
        nxt = jax.vmap(lambda i, s: row_targets(i, s, d + 1)[0])(ids, segments)
        joined = jnp.concatenate([_rms_norm(params["embed_tokens"][nxt], mp["enorm"][0], eps),
                                  _rms_norm(x, mp["hnorm"][0], eps)], axis=-1)
        x = _stack(_linear(joined, mp["eh_proj"][0], quant), mp, cfg, ids, segments, quant, True)
        labels, valid, _ = jax.vmap(lambda i, s: row_targets(i, s, d + 2))(ids, segments)
        nll = _nll_sum(params, _rms_norm(x, mp["norm"][0], eps), labels, valid, quant)
        mtp = mtp + nll / jnp.maximum(jnp.sum(valid), 1) / depth
    return main, mtp


def total_loss(params: Params, cfg: dict, ids, segments, quant=None):
    """``L_main + lambda * L_mtp`` (DeepSeek-V3 report, eq. 25; lambda is the
    configuration's ``mtp_loss_weight``), and the two parts."""
    main, mtp = losses(params, cfg, ids, segments, quant)
    return main + cfg.get("mtp_loss_weight", 0.3) * mtp, (main, mtp)


# --------------------------------------------------------------------------
# training: global-norm clip, AdamW
# --------------------------------------------------------------------------
def clip_by_global_norm(grads: Params, max_norm: float) -> Params:
    if not max_norm:
        return grads
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-6))
    return jax.tree.map(lambda g: g * scale, grads)


def adamw_step(params, m, v, grads, t, *, lr, b1, b2, eps, weight_decay):
    """Step number ``t`` (from 1) of AdamW with bias correction; decay only
    on matrices (leaves of more than one axis)."""
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(g), v, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t

    def upd(p, m_, v_):
        step = (m_ / c1) / (jnp.sqrt(v_ / c2) + eps)
        if weight_decay and p.ndim > 1:
            step = step + weight_decay * p
        return p - lr * step

    return jax.tree.map(upd, params, m, v), m, v


def leaf_norms(tree: Params) -> Dict[str, jax.Array]:
    """Norm of every leaf; a leaf stacked over layers gives one norm per
    layer. The routers of all layers (and modules) are ONE further leaf,
    ``routers``, and are left out of the per-layer list: a router's gradient
    moves with every top-k choice that bf16 flips (PERF.md, stage C of PR 26),
    so a single layer's norm swings over seeds as far as a lower precision
    moves it, while the norm over all of them is steadier."""
    out, routers = {}, []
    for name, x in flatten(tree).items():
        x = x.astype(jnp.float32)
        if name.endswith(".router"):
            routers.append(jnp.sum(jnp.square(x)))
        elif name.startswith(STACKED):
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))[None]
    if routers:
        out["routers"] = jnp.sqrt(sum(routers))[None]
    return out


def train_reference(cfg: dict, opt: dict, seed: int, batches, quant=None, log=lambda m: None):
    """Follow the first ``len(batches)`` optimizer steps from the seeded
    weights. ``batches``: list of (ids [R, S], segments [R, S]) int arrays.
    Returns host values: the loss of every step (main + lambda * MTP, and the
    parts under ``main_losses`` / ``mtp_losses``), the per-leaf norms of the
    first (clipped) gradient, the per-leaf norms of the parameters' change
    after the last step.

    A step is two programs, so that it fits one chip beside nothing but
    itself: the gradient (weights, gradient and the backward pass's own
    memory on the device) and the update (weights, two moments and the
    gradient; the first three donated). Between steps the moments wait on the host."""
    key = seed_key(seed)

    @jax.jit
    def init(key):
        return nest(make_params(cfg, key))

    @jax.jit
    def gradient(params, ids, seg):
        with jax.default_matmul_precision("highest"):
            (loss, parts), grads = jax.value_and_grad(
                lambda p: total_loss(p, cfg, ids, seg, quant), has_aux=True)(params)
            grads = clip_by_global_norm(grads, opt["max_grad_norm"])
        return loss, parts, grads, leaf_norms(grads)

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
    out_losses, parts, first_grad = [], [], None
    m = v = None  # on the host between steps
    for i, (ids, seg) in enumerate(batches):
        loss, part, grads, gnorms = gradient(
            params, jnp.asarray(ids, jnp.int32), jnp.asarray(seg, jnp.int32))
        out_losses.append(loss)
        parts.append(part)
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
    host = jax.device_get((out_losses, first_grad, delta, parts))
    del params
    return {"losses": [float(x) for x in host[0]],
            "main_losses": [float(a) for a, _ in host[3]],
            "mtp_losses": [float(b) for _, b in host[3]],
            "first_grad_norms": {k: np.asarray(x) for k, x in host[1].items()},
            "param_change_norms": {k: np.asarray(x) for k, x in host[2].items()}}
