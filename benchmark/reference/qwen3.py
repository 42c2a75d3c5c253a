"""Plain reference for the Qwen3 dense decoder family.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no kernel, no cache, rows independent of each other, and nothing of the
program is imported. It follows the published architecture
(``Qwen/Qwen3-0.6B`` ``config.json`` plus the ``transformers`` modeling code
it names): pre-norm decoder, grouped-query attention with per-head RMS norm
on q and k, half-rotation rope, SwiGLU MLP.

Departures, each noted where it happens:
  * layers are stacked along a leading axis and scanned with
    ``jax.checkpoint`` so that 28 layers of a 4096-token row fit one chip;
  * attention runs over blocks of query positions (each against all keys),
    the vocabulary projection and the loss over chunks of positions.

The same functions, given ``quant="int8"`` or ``"fp8"``, are the control:
every linear projection then rounds both of its operands to that format
(per-row absmax scaling, straight-through gradient), the nearest precision
below the bf16 the configuration states.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]
INIT_STD = 0.02  # `initializer_range` of the published config


# --------------------------------------------------------------------------
# weights from the seed (the benchmark's, handed to the program and used here)
# --------------------------------------------------------------------------
def param_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    """Leaf name -> shape. Names follow the published checkpoint's
    (``layers.q_proj`` is ``model.layers.*.self_attn.q_proj`` stacked over
    layers and stored input-major)."""
    h, L, im = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["intermediate_size"]
    d = cfg["head_dim"]
    qd, kvd = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    shapes = {
        "embed_tokens": (cfg["vocab_size"], h),
        "norm": (h,),
        "layers.input_layernorm": (L, h),
        "layers.q_proj": (L, h, qd),
        "layers.k_proj": (L, h, kvd),
        "layers.v_proj": (L, h, kvd),
        "layers.o_proj": (L, qd, h),
        "layers.q_norm": (L, d),
        "layers.k_norm": (L, d),
        "layers.post_attention_layernorm": (L, h),
        "layers.gate_proj": (L, h, im),
        "layers.up_proj": (L, h, im),
        "layers.down_proj": (L, im, h),
    }
    if not cfg.get("tie_word_embeddings", False):
        shapes["lm_head"] = (h, cfg["vocab_size"])
    return shapes


def seed_key(seed: int) -> jax.Array:
    """A key from any whole-number seed (the driver's pass 2**31)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)), seed // (2 ** 31))


def make_params(cfg: dict, key: jax.Array, dtype=jnp.float32) -> Params:
    """Flat dict name -> array: norms are ones, every other leaf is
    N(0, 0.02) drawn in float32 from ``fold_in(key, index of the name)``
    and then cast. Meant to run inside one ``jax.jit`` that takes ``key``
    (``seed_key(seed)``) as an ARGUMENT: with the key a constant, the
    compiler folds the whole draw at compile time (22 s for 0.6 B weights on
    a v5e, measured in PR 26, and too large to cache)."""
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        if name.endswith("norm") or name.endswith("layernorm"):
            leaf = jnp.ones(shape, dtype)
        else:
            leaf = (jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
                    * INIT_STD).astype(dtype)
        out[name] = leaf
    return out


def nest(flat: Params) -> Params:
    """``{"layers.q_proj": x}`` -> ``{"layers": {"q_proj": x}}``."""
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
# forward
# --------------------------------------------------------------------------
def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, cos, sin):
    """Half-rotation: x [S, H, D], cos/sin [S, D]."""
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + rotated * sin[:, None, :]


def _rope_tables(positions, head_dim: int, theta: float):
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def _attention(x, lp, cfg, cos, sin, mask, quant):
    s = x.shape[0]
    nq, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    q = _linear(x, lp["q_proj"], quant).reshape(s, nq, d)
    k = _linear(x, lp["k_proj"], quant).reshape(s, nkv, d)
    v = _linear(x, lp["v_proj"], quant).reshape(s, nkv, d)
    q = _rope(_rms_norm(q, lp["q_norm"], eps), cos, sin)
    k = _rope(_rms_norm(k, lp["k_norm"], eps), cos, sin)
    rep = nq // nkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)

    def some_queries(qm):
        # a block of query positions against every key: the [H, S, S] scores
        # of a 4096-token row would not fit beside the optimizer's state
        q_blk, mask_blk = qm
        scores = jnp.einsum("qhd,khd->hqk", q_blk, k, precision=jax.lax.Precision.HIGHEST)
        scores = jnp.where(mask_blk[None], scores / math.sqrt(d), -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=jax.lax.Precision.HIGHEST)

    blk = min(s, 512)
    if s % blk:
        blk = s
    ctx = jax.lax.map(jax.checkpoint(some_queries),
                      (q.reshape(s // blk, blk, nq, d), mask.reshape(s // blk, blk, s)))
    ctx = ctx.reshape(s, nq, d)
    return _linear(ctx.reshape(s, nq * d), lp["o_proj"], quant)


def _swiglu(x, gate_w, up_w, down_w, quant):
    gate = _linear(x, gate_w, quant)
    up = _linear(x, up_w, quant)
    return _linear(jax.nn.silu(gate) * up, down_w, quant)


def _layer(x, lp, cfg, cos, sin, mask, quant):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, lp["input_layernorm"], eps), lp, cfg, cos, sin, mask, quant)
    y = _rms_norm(x, lp["post_attention_layernorm"], eps)
    return x + _swiglu(y, lp["gate_proj"], lp["up_proj"], lp["down_proj"], quant)


def hidden_states(params: Params, cfg: dict, ids, positions, segments, quant=None):
    """One row: ids/positions/segments [S] -> final normed hidden [S, H]. A
    position attends to earlier positions of its own segment."""
    s = ids.shape[0]
    idx = jnp.arange(s)
    mask = (idx[:, None] >= idx[None, :]) & (segments[:, None] == segments[None, :])
    cos, sin = _rope_tables(positions, cfg["head_dim"], cfg["rope_theta"])
    x = params["embed_tokens"][ids]

    def body(x, lp):
        return _layer(x, lp, cfg, cos, sin, mask, quant), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["layers"])
    return _rms_norm(x, params["norm"], cfg["rms_norm_eps"])


def logits_at(params: Params, hidden, quant=None):
    """hidden [S, H] -> logits [S, V]; a tied head reads the embedding as it
    lies ([V, H]), so that no transposed copy of it is made."""
    if "lm_head" in params:
        return _linear(hidden, params["lm_head"], quant)
    w = params["embed_tokens"]
    if quant:
        hidden, w = _fake_quant(hidden, quant), _fake_quant(w, quant)
    return jnp.einsum("sh,vh->sv", hidden, w, precision=jax.lax.Precision.HIGHEST)


def row_targets(ids, segments):
    """(next ids, which positions predict, positions within the document):
    a position predicts the next token of its own document, the last position
    of a document and padding (segment 0) predict nothing."""
    s = ids.shape[0]
    nxt_ids = jnp.concatenate([ids[1:], jnp.zeros((1,), ids.dtype)])
    nxt_seg = jnp.concatenate([segments[1:], jnp.zeros((1,), segments.dtype)])
    valid = (segments > 0) & (nxt_seg == segments)
    starts = jnp.where(
        jnp.concatenate([jnp.ones((1,), bool), segments[1:] != segments[:-1]]),
        jnp.arange(s), 0)
    return nxt_ids, valid, jnp.arange(s) - jax.lax.cummax(starts)


def row_loss_sum(params: Params, cfg: dict, ids, segments, quant=None, chunk: int = 128):
    """(sum of next-token NLL over this row's predicted positions; the count
    of predicted positions). Labels and positions are worked out here from
    the ids and the segments."""
    s = ids.shape[0]
    nxt_ids, valid, positions = row_targets(ids, segments)
    hidden = hidden_states(params, cfg, ids, positions, segments, quant)
    n_chunks = -(-s // chunk)
    pad = n_chunks * chunk - s
    hidden = jnp.pad(hidden, ((0, pad), (0, 0))).reshape(n_chunks, chunk, -1)
    labels = jnp.pad(nxt_ids, (0, pad)).reshape(n_chunks, chunk)
    valid_c = jnp.pad(valid, (0, pad)).reshape(n_chunks, chunk)

    def one_chunk(total, hlv):
        h, lab, ok = hlv
        logits = logits_at(params, h, quant)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, lab[:, None], axis=-1)[:, 0]
        return total + jnp.sum(jnp.where(ok, nll, 0.0)), None

    total, _ = jax.lax.scan(jax.checkpoint(one_chunk), jnp.float32(0.0),
                            (hidden, labels, valid_c))
    return total, jnp.sum(valid)


# --------------------------------------------------------------------------
# training: token-mean loss over a batch, global-norm clip, AdamW
# --------------------------------------------------------------------------
def batch_loss_and_grads(params: Params, cfg: dict, ids, segments, quant=None):
    """ids/segments [R, S] -> (token-mean loss over all rows, its gradient),
    one row at a time so that only one row's activations are alive."""
    n_valid = jnp.sum(jax.vmap(lambda i, s: row_targets(i, s)[1])(ids, segments))
    denom = jnp.maximum(n_valid, 1).astype(jnp.float32)

    def row(carry, xs):
        g_acc, l_acc = carry
        (l, _), g = jax.value_and_grad(
            lambda p: row_loss_sum(p, cfg, xs[0], xs[1], quant),
            has_aux=True)(params)
        return (jax.tree.map(jnp.add, g_acc, g), l_acc + l), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (g, l), _ = jax.lax.scan(row, (zeros, jnp.float32(0.0)), (ids, segments))
    return l / denom, jax.tree.map(lambda x: x / denom, g)


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
    """Norm of every leaf; a leaf stacked over layers (under ``layers.``)
    gives one norm per layer."""
    out = {}
    for name, x in flatten(tree).items():
        x = x.astype(jnp.float32)
        if name.startswith("layers."):
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))[None]
    return out


def train_reference(cfg: dict, opt: dict, seed: int, batches, quant=None, log=lambda m: None):
    """Follow the first ``len(batches)`` optimizer steps from the seeded
    weights. ``batches``: list of (ids [R, S], segments [R, S]) int arrays.
    Returns host values: the loss of every step, the per-leaf norms of the
    first (clipped) gradient, the per-leaf norms of the parameters' change
    after the last step."""
    key = seed_key(seed)

    @jax.jit
    def init(key):
        p = nest(make_params(cfg, key))
        return p, jax.tree.map(jnp.zeros_like, p), jax.tree.map(jnp.zeros_like, p)

    @jax.jit
    def step(params, m, v, ids, seg, t):
        with jax.default_matmul_precision("highest"):
            loss, grads = batch_loss_and_grads(params, cfg, ids, seg, quant)
            grads = clip_by_global_norm(grads, opt["max_grad_norm"])
            new_p, m, v = adamw_step(
                params, m, v, grads, t.astype(jnp.float32), lr=opt["lr"],
                b1=opt["betas"][0], b2=opt["betas"][1], eps=1e-8,
                weight_decay=opt["weight_decay"])
        return new_p, m, v, loss, leaf_norms(grads)

    @jax.jit
    def change(params, key):
        p0 = nest(make_params(cfg, key))
        return leaf_norms(jax.tree.map(jnp.subtract, params, p0))

    params, m, v = init(key)
    jax.block_until_ready(params)
    log("reference: weights made")
    losses, first_grad = [], None
    for i, (ids, seg) in enumerate(batches):
        params, m, v, loss, gnorms = step(
            params, m, v, jnp.asarray(ids, jnp.int32), jnp.asarray(seg, jnp.int32),
            jnp.int32(i + 1))
        losses.append(loss)
        if i == 0:
            first_grad = gnorms
        jax.block_until_ready(loss)
        log(f"reference: step {i + 1} done")
    delta = change(params, key)
    host = jax.device_get((losses, first_grad, delta))
    del params, m, v
    return {"losses": [float(x) for x in host[0]],
            "first_grad_norms": {k: np.asarray(x) for k, x in host[1].items()},
            "param_change_norms": {k: np.asarray(x) for k, x in host[2].items()}}
