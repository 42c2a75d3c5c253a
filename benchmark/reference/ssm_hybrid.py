"""Plain reference for the hybrid state-space family: Mamba-2 layers and
attention layers in one stack (``granitemoehybrid``, dense members).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernel, no chunked scan, and nothing of the program is imported. It follows
``transformers``' ``GraniteMoeHybridForCausalLM`` (4.57) and the Mamba-2 paper
(Dao and Gu 2024, section 2: the recurrence, not its chunked dual):

  * every layer: ``h += m * mixer(rmsnorm(h)); h += m * mlp(rmsnorm(h))`` with
    ``m = residual_multiplier``; the MLP is ``output_linear(silu(g) * u)`` with
    ``[g | u] = input_linear(x)``;
  * embeddings times ``embedding_multiplier``; logits over ``logits_scaling``;
    the head is the embedding, transposed;
  * attention: q, k, v projections, NO positional embedding, scores times
    ``attention_multiplier`` (not ``head_dim ** -0.5``), causal;
  * the Mamba-2 mixer::

        z, xBC, dt = split(in_proj(u), [d_inner, d_inner + 2 G N, H])
        x, B, C    = split(silu(conv1d(xBC) + b), [d_inner, G N, G N])
        dt         = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t        = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t     per head
        y_t        = S_t C_t + D x_t
        out        = out_proj(rmsnorm(y * silu(z)) * w_norm)

    computed TOKEN BY TOKEN (``lax.scan`` over time with the state
    ``[H, P, N]``), so that it shares no algebra with the program's chunked
    scan.

Departures and choices, each noted where it happens:
  * documents: a packed row's ``segment_ids`` zero the state ``S`` and the
    conv's taps where a document starts, and mask attention to the document
    (``transformers``' torch slow path refuses ``seq_idx``; its fast path does
    the same resets);
  * the vocabulary is the slice the configuration gives (ids, logits and loss
    over it), the depth the layers ``layer_types_run`` names;
  * the time scan is checkpointed in blocks of ``TIME_BLOCK`` steps (every
    step's state at 8,192 tokens would be 17 GB a layer), layers and rows are
    under ``jax.checkpoint``, attention runs over blocks of query positions,
    the head and the loss over chunks of positions: so that a step fits one
    chip beside its own gradient.

Given ``quant="fp8"`` or ``"int8"`` the same functions are the control: every
linear projection rounds both operands to that format (per-row absmax
scaling, straight-through gradient). The recurrence, the conv and the norms
stay in float32 there too.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]
INIT_STD = 0.02  # the family's `initializer_range`
STACKED = ("mamba_layers.", "attn_layers.")  # leaves [periods, layers of the kind, ...]
KINDS = {"mamba": "mamba_layers", "attention": "attn_layers"}
QUERY_BLOCK = 512  # query positions whose scores against all keys exist at once
TIME_BLOCK = 64    # steps of the recurrence whose states the backward pass keeps


# --------------------------------------------------------------------------
# weights from the seed (the benchmark's, handed to the program and used here)
# --------------------------------------------------------------------------
def layer_kinds(cfg: dict) -> Tuple[str, ...]:
    """The kind of every layer that is run, in order (``layer_types_run``:
    the published ``layer_types`` cut to ``num_hidden_layers``, as one string,
    since the job hands the reference the file's plain values only)."""
    kinds = tuple(cfg["layer_types_run"].split(","))
    if len(kinds) != cfg["num_hidden_layers"] or not set(kinds) <= set(KINDS):
        raise ValueError(f"layer_types_run {kinds} does not name {cfg['num_hidden_layers']} layers")
    return kinds


def _period(kinds: Tuple[str, ...]) -> Tuple[str, ...]:
    """The shortest prefix the list is a whole number of copies of."""
    n = len(kinds)
    p = next(p for p in range(1, n + 1)
             if n % p == 0 and all(kinds[i] == kinds[i % p] for i in range(n)))
    return kinds[:p]


def _sizes(cfg: dict) -> dict:
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    d_inner = heads * p
    if d_inner != cfg["mamba_expand"] * cfg["hidden_size"]:
        raise ValueError("mamba_n_heads * mamba_d_head is not mamba_expand * hidden_size")
    bc = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return dict(h=cfg["hidden_size"], heads=heads, p=p, n=cfg["mamba_d_state"],
                groups=cfg["mamba_n_groups"], d_inner=d_inner, bc=bc, conv=d_inner + 2 * bc,
                k=cfg["mamba_d_conv"], im=cfg["shared_intermediate_size"],
                nq=nq, nkv=nkv, hd=cfg.get("head_dim") or cfg["hidden_size"] // nq)


def param_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    """Leaf name -> shape. A layer's leaves are stacked by kind:
    ``mamba_layers.in_proj`` is ``model.layers.*.mamba.in_proj.weight`` of the
    state-space layers, ``[periods, such layers a period, in, out]`` (stored
    input-major); ``conv_weight`` is ``mamba.conv1d.weight`` without its middle
    axis; ``input_linear``/``output_linear`` are ``shared_mlp.*``."""
    z = _sizes(cfg)
    period = _period(layer_kinds(cfg))
    g = cfg["num_hidden_layers"] // len(period)
    h = z["h"]
    shapes = {"embed_tokens": (cfg["vocab_size"], h), "norm": (h,)}
    if not cfg.get("tie_word_embeddings", True):
        shapes["lm_head"] = (h, cfg["vocab_size"])
    mixers = {
        "mamba": {"in_proj": (h, z["d_inner"] + z["conv"] + z["heads"]),
                  "conv_weight": (z["conv"], z["k"]), "conv_bias": (z["conv"],),
                  "dt_bias": (z["heads"],), "A_log": (z["heads"],), "D": (z["heads"],),
                  "norm": (z["d_inner"],), "out_proj": (z["d_inner"], h)},
        "attention": {"q_proj": (h, z["nq"] * z["hd"]), "k_proj": (h, z["nkv"] * z["hd"]),
                      "v_proj": (h, z["nkv"] * z["hd"]), "o_proj": (z["nq"] * z["hd"], h)},
    }
    mlp = {"input_layernorm": (h,), "post_attention_layernorm": (h,),
           "input_linear": (h, 2 * z["im"]), "output_linear": (z["im"], h)}
    for kind in dict.fromkeys(period):
        lead = (g, period.count(kind))
        for name, shape in {**mixers[kind], **mlp}.items():
            shapes[f"{KINDS[kind]}.{name}"] = lead + shape
    return shapes


def seed_key(seed: int) -> jax.Array:
    """A key from any whole-number seed (the driver's pass 2**31)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)), seed // (2 ** 31))


def make_params(cfg: dict, key: jax.Array, dtype=jnp.float32) -> Params:
    """Flat dict name -> array, every random leaf drawn in float32 from
    ``fold_in(key, index of the name)`` and then cast: norms and ``D`` ones,
    the conv's bias zeros, ``A_log = log(1..H)`` (``transformers``'
    ``_init_weights``); ``dt_bias`` the inverse softplus of a ``dt``
    log-uniform in [0.001, 0.1] (the Mamba-2 initialisation, ``transformers``'
    ``time_step_min/max``); the conv's weights uniform in +-1/sqrt(K) (the
    Mamba-2 reference implementation's, torch's ``Conv1d`` default: at
    ``transformers``' N(0, 0.02) the scan would add a ten-thousandth of what
    the ``D`` skip does and no limit could see it); all else N(0, 0.02).
    Meant to run inside one ``jax.jit`` that takes ``key`` as an ARGUMENT."""
    heads = cfg["mamba_n_heads"]
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        last = name.rsplit(".", 1)[-1]
        if last.endswith("norm") or last == "D":
            leaf = jnp.ones(shape, jnp.float32)
        elif last == "conv_bias":
            leaf = jnp.zeros(shape, jnp.float32)
        elif last == "A_log":
            leaf = jnp.broadcast_to(jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32)), shape)
        elif last == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, np.log(0.001), np.log(0.1)))
            leaf = dt + jnp.log(-jnp.expm1(-dt))
        elif last == "conv_weight":
            bound = shape[-1] ** -0.5
            leaf = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        else:
            leaf = jax.random.normal(k, shape, jnp.float32) * INIT_STD
        out[name] = leaf.astype(dtype)
    return out


def nest(flat: Params) -> Params:
    """``{"mamba_layers.in_proj": x}`` -> ``{"mamba_layers": {"in_proj": x}}``."""
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
# one row through one layer
# --------------------------------------------------------------------------
def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def causal_conv(x, w, b, starts_in):
    """x [S, C], w [C, K], b [C]: ``out_t = b + sum_k w[:, K-1-k] x_{t-k}``
    over the ``k`` that stay inside ``t``'s document (``starts_in [S]``: how
    many positions back the document began), then silu. ``conv1d``'s last tap
    multiplies the current position."""
    k = w.shape[-1]
    out = jnp.zeros_like(x) + b
    for back in range(k):
        shifted = jnp.pad(x, ((back, 0), (0, 0)))[:x.shape[0]]
        out = out + jnp.where((starts_in >= back)[:, None], shifted * w[:, k - 1 - back], 0.0)
    return jax.nn.silu(out)


def recurrence(x, dt, a, bm, cm, d, first):
    """The state-space layer one token at a time. x [S, H, P], dt [S, H],
    a [H], bm, cm [S, G, N], d [H], first [S] (True where a document starts)
    -> y [S, H, P]. Blocks of ``TIME_BLOCK`` steps are recomputed in the
    backward pass, which then keeps a block's states and one state a block."""
    s, heads, p = x.shape
    groups, n = bm.shape[-2:]
    bm, cm = (jnp.repeat(t, heads // groups, axis=1) for t in (bm, cm))  # [S, H, N]

    def step(state, xs):
        x_t, dt_t, b_t, c_t, first_t = xs
        state = jnp.where(first_t, 0.0, state)  # a document starts from nothing
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1) + d[:, None] * x_t

    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    blk = TIME_BLOCK if s % TIME_BLOCK == 0 else s
    xs = tuple(t.reshape(s // blk, blk, *t.shape[1:]) for t in (x, dt, bm, cm, first))
    _, y = jax.lax.scan(jax.checkpoint(block), jnp.zeros((heads, p, n), jnp.float32), xs)
    return y.reshape(s, heads, p)


def mamba_mixer(u, lp, cfg, geometry, quant):
    z_ = _sizes(cfg)
    s = u.shape[0]
    d_inner, bc = z_["d_inner"], z_["bc"]
    proj = _linear(u, lp["in_proj"], quant)
    z, xbc, dt = jnp.split(proj, [d_inner, d_inner + z_["conv"]], axis=-1)
    xbc = causal_conv(xbc, lp["conv_weight"], lp["conv_bias"], geometry["since_start"])
    x, bm, cm = jnp.split(xbc, [d_inner, d_inner + bc], axis=-1)
    dt = jax.nn.softplus(dt + lp["dt_bias"])  # time_step_limit (0, inf): no clamp
    y = recurrence(x.reshape(s, z_["heads"], z_["p"]), dt, -jnp.exp(lp["A_log"]),
                   bm.reshape(s, z_["groups"], z_["n"]), cm.reshape(s, z_["groups"], z_["n"]),
                   lp["D"], geometry["since_start"] == 0)
    # the gate goes on BEFORE the norm, and the norm is over all of d_inner
    y = _rms_norm(y.reshape(s, d_inner) * jax.nn.silu(z), lp["norm"], cfg["rms_norm_eps"])
    return _linear(y, lp["out_proj"], quant)


def attention_mixer(x, lp, cfg, geometry, quant):
    z = _sizes(cfg)
    s, nq, nkv, hd = x.shape[0], z["nq"], z["nkv"], z["hd"]
    q = _linear(x, lp["q_proj"], quant).reshape(s, nq, hd)
    k = _linear(x, lp["k_proj"], quant).reshape(s, nkv, hd)
    v = _linear(x, lp["v_proj"], quant).reshape(s, nkv, hd)
    k, v = (jnp.repeat(t, nq // nkv, axis=1) for t in (k, v))
    scale = cfg["attention_multiplier"]  # no positional embedding: q and k as they are

    def some_queries(qm):
        q_blk, mask_blk = qm
        scores = jnp.einsum("qhd,khd->hqk", q_blk, k, precision=jax.lax.Precision.HIGHEST)
        scores = jnp.where(mask_blk[None], scores * scale, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=jax.lax.Precision.HIGHEST)

    blk = min(s, QUERY_BLOCK)
    if s % blk:
        blk = s
    ctx = jax.lax.map(jax.checkpoint(some_queries),
                      (q.reshape(s // blk, blk, nq, hd), geometry["mask"].reshape(s // blk, blk, s)))
    return _linear(ctx.reshape(s, nq * hd), lp["o_proj"], quant)


def layer(x, lp, kind: str, cfg, geometry, quant):
    eps, m = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    mixer = mamba_mixer if kind == "mamba" else attention_mixer
    x = x + m * mixer(_rms_norm(x, lp["input_layernorm"], eps), lp, cfg, geometry, quant)
    gate, up = jnp.split(
        _linear(_rms_norm(x, lp["post_attention_layernorm"], eps), lp["input_linear"], quant),
        2, axis=-1)
    return x + m * _linear(jax.nn.silu(gate) * up, lp["output_linear"], quant)


# --------------------------------------------------------------------------
# rows: geometry, the stack, the head
# --------------------------------------------------------------------------
def row_geometry(segments):
    """What a row's documents mean for its layers: how many positions back
    each position's document began, the attention mask (causal, within the
    document), and which positions predict their next token."""
    s = segments.shape[0]
    idx = jnp.arange(s)
    first = jnp.concatenate([jnp.ones((1,), bool), segments[1:] != segments[:-1]])
    since_start = idx - jax.lax.cummax(jnp.where(first, idx, 0))
    mask = (idx[:, None] >= idx[None, :]) & (segments[:, None] == segments[None, :])
    nxt = jnp.concatenate([segments[1:], jnp.zeros((1,), segments.dtype)])
    return {"since_start": since_start, "mask": mask, "valid": (segments > 0) & (nxt == segments)}


def loss(params: Params, cfg: dict, ids, segments, quant=None, chunk: int = 128):
    """ids/segments [R, S] -> mean next-token NLL over the predicting
    positions of all rows."""
    kinds = layer_kinds(cfg)
    period = _period(kinds)
    seen = {kind: 0 for kind in KINDS}
    x = params["embed_tokens"][ids] * cfg["embedding_multiplier"]
    for i, kind in enumerate(kinds):
        # the layer's own slice of its kind's stack: [period, place within the kind]
        at = (i // len(period), seen[kind] % period.count(kind))
        seen[kind] += 1
        lp = jax.tree.map(lambda t: t[at], params[KINDS[kind]])

        def one_row(xs, lp=lp, kind=kind):
            xr, seg = xs
            return layer(xr, lp, kind, cfg, row_geometry(seg), quant)

        x = jax.lax.map(jax.checkpoint(one_row), (x, segments))
    x = _rms_norm(x, params["norm"], cfg["rms_norm_eps"])
    head = params["embed_tokens"].T if cfg.get("tie_word_embeddings", True) else params["lm_head"]
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
        logits = _linear(hid, head, quant) / cfg["logits_scaling"]
        nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), lab[:, None], axis=-1)[:, 0]
        return total + jnp.sum(jnp.where(ok, nll, 0.0)), None

    total = jax.lax.scan(jax.checkpoint(one_chunk), jnp.float32(0.0), (x, labels, valid_c))[0]
    return total / jnp.maximum(jnp.sum(valid), 1)


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


PER_HEAD = ("A_log", "dt_bias")  # one number a head (64 a layer at the published size)
LEFT_OUT = ("D",)


def leaf_norms(tree: Params) -> Dict[str, jax.Array]:
    """Norm of every leaf that is compared; a leaf stacked over layers gives
    one norm per layer (periods outermost). Two exceptions, both for the
    per-head vectors of the state-space layers, 64 numbers a layer:

    * ``A_log`` and ``dt_bias`` are ONE leaf each over all layers (as the
      routers are in ``mla_moe.py``);
    * ``D`` is left out. Its gradient is, per head, the sum over every
      position of ``x * dy``, and since ``y`` is ``D x`` but for a few percent
      and the norm after it makes the loss blind to ``y``'s scale, that sum all
      but cancels: on the chip bf16 compute moved one layer's norm by
      2.5e-3..9.4e-3 over 15 seeds and all nine layers' together by
      0.7e-3..6.6e-3 over 13 (the worst leaf in 22 of 28 sound runs, PR 33),
      where every other leaf stays under 2e-3 and the fp8 control's sit at
      6.9e-3. Kept in, it alone would set the limit, three times looser for
      every other leaf. ``D`` itself is still held: every other gradient and
      the loss pass through it, and the CPU tests compare its gradient too."""
    out = {}
    for name, x in flatten(tree).items():
        last = name.rsplit(".", 1)[-1]
        if last in LEFT_OUT:
            continue
        x = x.astype(jnp.float32)
        if name.startswith(STACKED) and last not in PER_HEAD:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(2, x.ndim)))).reshape(-1)
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))[None]
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
