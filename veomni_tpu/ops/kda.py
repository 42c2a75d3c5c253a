"""The recurrence of a Kimi Delta Attention layer: a gated delta rule with one
decay a key CHANNEL (Kimi Linear, arXiv:2510.26692), chunked.

Per head, with a state ``S`` in ``R^{dk x dv}`` that is zero where a document
starts::

    S'  = diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = scale * S_t^T q_t

``q, k [B,S,H,dk]`` (l2-normed by the caller), ``v [B,S,H,dv]``, ``g
[B,S,H,dk]`` (f32, never positive), ``beta [B,S,H]`` (f32), ``segment_ids
[B,S]`` or None. Returns ``o [B,S,H,dv]`` in ``v``'s dtype.

Impl ``xla`` is the chunked form. With ``G`` the cumulative sum of ``g``
inside a chunk and ``u_j = beta_j (v_j - S'_j^T k_j)`` the rows the delta rule
writes::

    (I + diag(beta) A) U = diag(beta) (V - (K * exp(G)) S_in)      A strictly lower
    o_i   = scale * ((q_i * exp(G_i)) S_in + sum_{j<=i} P_ij u_j)
    S_out = diag(exp(G_last)) S_in + sum_j (k_j * exp(G_last - G_j)) u_j^T

with the two pair terms ``A_ij = sum_c k_ic k_jc exp(G_ic - G_jc)`` and
``P_ij`` the same with ``q_i``. With one decay a head they would be a Gram
matrix times a decay matrix; with one a channel the exponent sits inside the
sum. It is made a matmul again by splitting the chunk into sub-blocks of
``SUB`` rows, as flash-linear-attention's ``chunk_kda`` does:

* rows ``i`` of sub-block ``I`` against columns ``j`` of an EARLIER sub-block:
  ``(x_i * exp(G_i - G_r)) . (k_j * exp(G_r - G_j))`` about ``r``, the first
  row of ``I``. ``j < r <= i``, so both exponents are non-positive whatever the
  decays (about the chunk's start the second would be ``exp(-G_j)``, which
  overflows f32 after 64 tokens of a log-decay of -1.6);
* inside a sub-block: elementwise, ``sum_c x_ic k_jc exp(G_ic - G_jc)`` over
  the ``SUB x SUB`` pairs with ``j <= i``.

Every exponent is a difference of cumulative sums of non-positive numbers
taken the right way round and is masked BEFORE ``exp`` (``where(mask,
exp(big), 0)`` backpropagates ``0 * inf``). Decays, cumulative sums, the
inverse and the state are f32; the matmuls take the input's dtype in and
accumulate in f32. ``(I + diag(beta) A)^{-1}`` is built from matmuls alone
(no row-by-row substitution, which XLA would run as ``chunk`` tiny kernels):
the ``SUB x SUB`` diagonal blocks by the product form of the Neumann series
(exact: they are nilpotent; its cancelling terms grow like C(15, 7), which f32
carries), neighbours merged by ``[[A,0],[C,B]]^-1 = [[A^-1,0],[-B^-1 C A^-1,
B^-1]]``.

Documents: pairs are masked to one document; only the document open when the
chunk began reads ``S_in``; ``S_out`` keeps ``S_in`` where no document started
in the chunk and gathers the rows of the document open at its end.

Two scans, one inside the other. Everything above but the three uses of the
carried state needs no state, so the outer ``lax.scan`` walks BLOCKS of
``BLOCK`` chunks and computes those terms for all of a block's chunks at once
(rows x chunks folded into one batch axis); the inner scan hands the state
from chunk to chunk with four matmuls. The outer body is under
``jax.checkpoint``: every ``[.., chunk, chunk]`` matrix and the exponents of
the pair terms exist for one block at a time, forward and backward. The
largest are the diagonal sub-blocks' exponents where XLA does not fuse them
into their sum, ``SUB * H * dk`` f32 a token of the block in flight (256 KiB at
32 heads of 128: 64 MiB a block of 256 tokens, where the whole ``[chunk,
chunk, dk]`` term would be 1 MiB a token and 8.6 GB a row of 8192), and the
earlier sub-blocks' right factors, ``chunk / SUB`` times ``H * dk`` f32 a
token (64 KiB). The backward keeps one carried state a block (2 MiB at 32
heads of 128 x 128), and inside the block in flight one a chunk, and runs each
block's forward once more. On a v5e a forward over one row of 8192 at 32 heads
of 128 takes 12.3 ms and forward with backward 49.1 (13.7 and 49.6 with one
chunk a block, more with 8 chunks or more; sub-blocks of 8 rows the same, of 4
three times slower: PERF.md, PR 36): about 120 small device operations a
block forward, none of them near a peak.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY, resolve_op

CHUNK = 64    # positions a chunk: the [chunk, chunk] solve and the state hand-over
BLOCK = 4     # chunks whose own terms are computed at once (one step of the outer scan)
SUB = 16      # rows a sub-block: what the pair terms compute elementwise
_MASKED = -1e30  # an exponent no pair may use: exp gives 0, and a 0 gradient


def _inv_unit_lower(low, base: int):
    """``(I + low)^-1`` of strictly lower-triangular ``low [..., c, c]`` (f32),
    ``c = base * 2^m``, from matmuls at full f32 precision."""
    c = low.shape[-1]
    lead = low.shape[:-2]
    mm = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    n = c // base
    blocks = low.reshape(*lead, n, base, n, base)
    x = -jnp.stack([blocks[..., i, :, i, :] for i in range(n)], axis=-3)  # [.., n, base, base]
    # sum_k x^k = (I + x)(I + x^2)(I + x^4)...: x^base is zero
    inv = jnp.eye(base, dtype=low.dtype) + x
    power, reach = x, 2
    while reach < base:
        power = mm(power, power)
        inv = inv + mm(inv, power)
        reach *= 2
    size = base
    while size < c:
        m = c // (2 * size)
        halves = inv.reshape(*lead, m, 2, size, size)
        a_inv, b_inv = halves[..., 0, :, :], halves[..., 1, :, :]
        quads = low.reshape(*lead, m, 2, size, m, 2, size)
        cross = jnp.stack([quads[..., i, 1, :, i, 0, :] for i in range(m)], axis=-3)
        lower_left = -mm(mm(b_inv, cross), a_inv)
        inv = jnp.concatenate([
            jnp.concatenate([a_inv, jnp.zeros_like(a_inv)], axis=-1),
            jnp.concatenate([lower_left, b_inv], axis=-1)], axis=-2)
        size *= 2
    return inv[..., 0, :, :]


def _pair_terms(lefts, k, gc, seg, sub: int, dtype):
    """``[sum_c x_ic k_jc exp(G_ic - G_jc) for x in lefts]``, each ``[B,H,c,c]``
    f32 and valid where ``j <= i`` in one document (garbage-free elsewhere:
    zero). ``lefts`` and ``k`` are f32 ``[B,c,H,D]``, ``gc`` the cumulative
    log-decay, ``dtype`` what the matmuls take in."""
    b, c, h, _ = k.shape
    n = c // sub

    def blk(t):
        return t.reshape(b, n, sub, *t.shape[2:])

    gb = blk(gc)                                    # [B,n,sub,H,D]
    ref = gb[:, :, 0]                               # G at each sub-block's first row
    e_left = jnp.exp(gb - ref[:, :, None])          # rows at or after it: <= 0
    before = jnp.arange(c)[None, :] < (jnp.arange(n) * sub)[:, None]   # [n, c]
    e_right = jnp.exp(jnp.where(before[None, :, :, None, None],
                                ref[:, :, None] - gc[:, None], _MASKED))  # [B,n,c,H,D]
    k_right = (k[:, None] * e_right).astype(dtype)
    sb = blk(seg)
    ok = jnp.tril(jnp.ones((sub, sub), bool)) & (sb[:, :, :, None] == sb[:, :, None, :])
    e_in = jnp.exp(jnp.where(ok[..., None, None],
                             gb[:, :, :, None] - gb[:, :, None, :], _MASKED))  # [B,n,sub,sub,H,D]
    kb = blk(k)
    same = (seg[:, :, None] == seg[:, None, :])[:, None]                 # [B,1,c,c]
    on_diagonal = jnp.eye(n, dtype=jnp.float32)[:, None, :, None]        # [n,1,n,1]
    out = []
    for x in lefts:
        earlier = jnp.einsum("bnihd,bnjhd->bhnij", (blk(x) * e_left).astype(dtype), k_right,
                             preferred_element_type=jnp.float32)          # [B,H,n,sub,c]
        inside = jnp.sum(blk(x)[:, :, :, None] * kb[:, :, None, :] * e_in, axis=-1)
        inside = jnp.transpose(inside, (0, 4, 1, 2, 3))                   # [B,H,n,sub,sub]
        full = (earlier.reshape(b, h, n, sub, n, sub)
                + inside[:, :, :, :, None, :] * on_diagonal).reshape(b, h, c, c)
        out.append(jnp.where(same, full, 0.0))
    return out


def _chunk_terms(q, k, v, g, beta, seg, opened, *, sub: int):
    """What a chunk computes without the carried state, for any number of
    chunks at once (the leading axis is rows x chunks): ``q, k [N,c,H,dk]``,
    ``v [N,c,H,dv]``, ``g [N,c,H,dk]``, ``beta [N,c,H]``, ``seg [N,c]``,
    ``opened [N]`` (the segment id open when the chunk begins). Returns
    ``(u0, w, q_in, p, k_out, kept)`` with ``U = u0 - w S_in``, ``o = q_in S_in
    + p U`` and ``S_out = kept * S_in + k_out^T U``."""
    c = q.shape[1]
    dtype, f32 = v.dtype, jnp.float32
    qf, kf = q.astype(f32), k.astype(f32)
    gc = jnp.cumsum(g, axis=1)                       # non-increasing along the chunk
    a_kk, p_qk = _pair_terms((kf, qf), kf, gc, seg, sub, dtype)
    rows = jnp.arange(c)
    a_kk = jnp.where(rows[:, None] > rows[None, :], a_kk, 0.0)
    beta_rows = beta.transpose(0, 2, 1)[..., None]   # [N,H,c,1]
    solve = _inv_unit_lower(beta_rows * a_kk, sub).astype(dtype)

    # what the chunks before hand in, for the document that was open then
    cont = seg == opened[:, None]                                        # [N,c]
    decay_in = jnp.exp(jnp.where(cont[..., None, None], gc, _MASKED))    # [N,c,H,dk]
    u0 = jnp.einsum("bhij,bjhe->bihe", solve, (beta[..., None] * v.astype(f32)).astype(dtype),
                    preferred_element_type=f32)
    w = jnp.einsum("bhij,bjhd->bihd", solve, (beta[..., None] * kf * decay_in).astype(dtype),
                   preferred_element_type=f32).astype(dtype)
    # the state handed on: the document open at the chunk's end
    seg_last = seg[:, -1]
    tail = seg == seg_last[:, None]
    decay_out = jnp.exp(jnp.where(tail[..., None, None], gc[:, -1:] - gc, _MASKED))
    kept = jnp.exp(jnp.where((seg_last == opened)[:, None, None], gc[:, -1], _MASKED))  # [N,H,dk]
    return (u0, w, (qf * decay_in).astype(dtype), p_qk.astype(dtype),
            (kf * decay_out).astype(dtype), kept)


def _block_body(carry, xs, *, scale: float, sub: int):
    """One block of chunks: (state [B,H,dk,dv] f32, the segment id open at its
    start [B]) and the block's slices ``[B,G,c,...]`` -> the next carry and
    ``o [B,G,c,H,dv]``. The chunks' own terms are computed for the whole block
    at once; only the hand-over of the state walks the chunks."""
    state, seg_open = carry
    seg = xs[-1]
    b, n, _ = seg.shape
    dtype, f32 = xs[2].dtype, jnp.float32
    opened = jnp.concatenate([seg_open[:, None], seg[:, :-1, -1]], axis=1)   # [B,G]
    terms = _chunk_terms(*(t.reshape(b * n, *t.shape[2:]) for t in (*xs, opened)), sub=sub)

    def hand_over(state, t):
        u0, w, q_in, p, k_out, kept = t
        s_in = state.astype(dtype)
        u = (u0 - jnp.einsum("bjhd,bhde->bjhe", w, s_in, preferred_element_type=f32)).astype(dtype)
        o = jnp.einsum("bihd,bhde->bihe", q_in, s_in, preferred_element_type=f32)
        o = o + jnp.einsum("bhij,bjhe->bihe", p, u, preferred_element_type=f32)
        new = jnp.einsum("bjhd,bjhe->bhde", k_out, u, preferred_element_type=f32)
        return state * kept[..., None] + new, (o * scale).astype(dtype)

    state, o = jax.lax.scan(
        hand_over, state, tuple(jnp.moveaxis(t.reshape(b, n, *t.shape[1:]), 1, 0) for t in terms))
    return (state, seg[:, -1, -1]), jnp.moveaxis(o, 0, 1)


@KERNEL_REGISTRY.register("kda_scan", "xla")
def _kda_scan_xla(q, k, v, g, beta, segment_ids=None, chunk: int = CHUNK, scale=None):
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    sub = min(SUB, chunk)
    if chunk % sub or (chunk // sub) & (chunk // sub - 1):
        raise ValueError(f"kda_scan: a chunk of {chunk} is not {sub} rows times a power of two")
    seg = (jnp.ones((b, s), jnp.int32) if segment_ids is None
           else segment_ids.astype(jnp.int32))
    q, k = q.astype(v.dtype), k.astype(v.dtype)
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    per_block = min(BLOCK, -(-s // chunk))
    pad = (-s) % (per_block * chunk)
    if pad:
        # beta = 0 and g = 0 rows: they write nothing and decay nothing
        q, k, v, g = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0))) for t in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
        seg = jnp.pad(seg, ((0, 0), (0, pad)), mode="edge")
    nb = (s + pad) // (per_block * chunk)

    def blocked(t):  # [B, S, ...] -> [nb, B, chunks a block, chunk, ...]
        return jnp.moveaxis(t.reshape(b, nb, per_block, chunk, *t.shape[2:]), 1, 0)

    body = jax.checkpoint(partial(
        _block_body, scale=float(dk ** -0.5 if scale is None else scale), sub=sub))
    state0 = jnp.zeros((b, h, dk, dv), jnp.float32)
    _, o = jax.lax.scan(body, (state0, seg[:, 0]),
                        tuple(blocked(t) for t in (q, k, v, g, beta, seg)))
    return jnp.moveaxis(o, 0, 1).reshape(b, s + pad, h, dv)[:, :s]


def kda_scan(q, k, v, g, beta, segment_ids=None, chunk: int = CHUNK, scale=None):
    return resolve_op("kda_scan")(q, k, v, g, beta, segment_ids, chunk, scale)

