"""The state-space scan of a Mamba-2 layer (state-space duality, chunked).

Per head, with a state ``S`` in ``R^{P x N}``::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t
    y_t = S_t C_t + D x_t

``x [B,S,H,P]``, ``dt [B,S,H]`` (after softplus, f32), ``A [H]`` (negative),
``B, C [B,S,G,N]`` (``H % G == 0``: the heads of a group share B and C),
``D [H]``, ``segment_ids [B,S]`` or None. Returns ``y [B,S,H,P]`` in ``x``'s
dtype. Where a document starts (``segment_ids`` changes; packed rows) the
state is zero again, wherever in a chunk that falls.

Impl ``xla`` is the chunked form: with ``a = dt A`` and its cumulative sum
``cs`` inside a chunk,

* in the chunk: ``y_i += sum_{j<=i} (C_i . B_j) exp(cs_i - cs_j) dt_j x_j``
  over the ``j`` of ``i``'s own document;
* from the chunks before: ``y_i += exp(cs_i) C_i . S_in`` where ``i`` lies in
  the document that was open when the chunk began;
* the state handed on: ``exp(cs_last) S_in`` where no document started in
  the chunk, plus ``sum_j exp(cs_last - cs_j) dt_j x_j (outer) B_j`` over the
  ``j`` of the document open at the chunk's end.

Every exponent is a difference of cumulative sums of non-positive numbers
taken the right way round, and is masked BEFORE ``exp`` (``where(mask,
exp(big), 0)`` backpropagates ``0 * inf``). Decays, cumulative sums and the
state are f32; the matmuls take ``x``'s dtype in and accumulate in f32.

The chunks are walked by one ``lax.scan`` whose body is under
``jax.checkpoint``: the ``[B, H, chunk, chunk]`` f32 decay matrix (65 KB a
token if it were ever whole in HBM) exists for one chunk at a time, forward
and backward, and the backward keeps one carried state a chunk (2 MB at 64
heads of 64 x 128). The price is that the backward runs each chunk's forward
once more.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY, resolve_op

_MASKED = -1e30  # an exponent no pair may use: exp gives 0, and a 0 gradient


def _chunk_body(carry, xs, a_head, d_head, *, groups):
    """One chunk: (state [B,H,P,N] f32, the segment id open at its start [B])
    and the chunk's slices -> the next carry and ``y [B,c,H,P]``."""
    state, seg_open = carry
    x, dt, bm, cm, seg = xs                      # [B,c,H,P] [B,c,H] [B,c,G,N] x2 [B,c]
    b, c, h, p = x.shape
    n = bm.shape[-1]
    per_group = h // groups
    cs = jnp.cumsum(dt * a_head, axis=1)         # [B,c,H] f32, non-increasing
    cs_t = cs.transpose(0, 2, 1)                 # [B,H,c]
    pair = jnp.tril(jnp.ones((c, c), bool)) & (seg[:, :, None] == seg[:, None, :])  # [B,c,c]
    decay = jnp.exp(jnp.where(pair[:, None], cs_t[..., :, None] - cs_t[..., None, :], _MASKED))
    cb = jnp.einsum("bign,bjgn->bgij", cm, bm, preferred_element_type=jnp.float32)
    mix = (cb[:, :, None] * decay.reshape(b, groups, per_group, c, c)).reshape(b, h, c, c)
    xdt = (x.astype(jnp.float32) * dt[..., None]).astype(x.dtype)   # [B,c,H,P]
    y = jnp.einsum("bhij,bjhp->bihp", mix.astype(x.dtype), xdt,
                   preferred_element_type=jnp.float32)

    # what the chunks before hand in, for the document that was open then
    cont = seg == seg_open[:, None]                                 # [B,c]
    decay_in = jnp.exp(jnp.where(cont[..., None], cs, _MASKED))     # [B,c,H]
    state_g = state.reshape(b, groups, per_group, p, n)
    from_state = jnp.einsum("bign,bgkpn->bigkp", cm.astype(jnp.float32), state_g)
    y = y + from_state.reshape(b, c, h, p) * decay_in[..., None]
    y = y + x.astype(jnp.float32) * d_head[:, None]

    # the state handed on: the document open at the chunk's end
    seg_last = seg[:, -1]
    tail = seg == seg_last[:, None]                                 # [B,c]
    decay_out = jnp.exp(jnp.where(tail[..., None], cs[:, -1:, :] - cs, _MASKED))
    xdt_out = (xdt.astype(jnp.float32) * decay_out[..., None]).astype(x.dtype)
    new = jnp.einsum("bigkp,bign->bgkpn", xdt_out.reshape(b, c, groups, per_group, p), bm,
                     preferred_element_type=jnp.float32).reshape(b, h, p, n)
    kept = jnp.exp(jnp.where((seg_last == seg_open)[:, None], cs[:, -1], _MASKED))  # [B,H]
    return (state * kept[..., None, None] + new, seg_last), y.astype(x.dtype)


@KERNEL_REGISTRY.register("ssd_scan", "xla")
def _ssd_scan_xla(x, dt, a_head, bm, cm, d_head, segment_ids=None, chunk: int = 256):
    b, s, h, p = x.shape
    groups, n = bm.shape[-2:]
    if h % groups:
        raise ValueError(f"ssd_scan: {h} heads do not divide into {groups} B/C groups")
    seg = (jnp.ones((b, s), jnp.int32) if segment_ids is None
           else segment_ids.astype(jnp.int32))
    dt = dt.astype(jnp.float32)
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        # dt = 0 rows: they decay nothing and add nothing
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bm, cm = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0))) for t in (bm, cm))
        seg = jnp.pad(seg, ((0, 0), (0, pad)), mode="edge")
    nc = (s + pad) // chunk

    def chunked(t):  # [B, S, ...] -> [nc, B, chunk, ...]
        return jnp.moveaxis(t.reshape(b, nc, chunk, *t.shape[2:]), 1, 0)

    body = jax.checkpoint(partial(_chunk_body, groups=groups))
    a_head, d_head = a_head.astype(jnp.float32), d_head.astype(jnp.float32)
    state0 = jnp.zeros((b, h, p, n), jnp.float32)
    _, y = jax.lax.scan(lambda carry, xs: body(carry, xs, a_head, d_head), (state0, seg[:, 0]),
                        tuple(chunked(t) for t in (x, dt, bm.astype(x.dtype),
                                                   cm.astype(x.dtype), seg)))
    return jnp.moveaxis(y, 0, 1).reshape(b, nc * chunk, h, p)[:, :s]


def ssd_scan(x, dt, a_head, bm, cm, d_head, segment_ids=None, chunk: int = 256):
    return resolve_op("ssd_scan")(x, dt, a_head, bm, cm, d_head, segment_ids, chunk)


def chunk_census(segment_ids, chunk: int = 256):
    """(chunks, chunks in which a document starts) of a host batch's rows
    ``[..., S]``, as the scan cuts them: what the trainer loop counts into
    ``ssm.scan.chunks[_with_reset]``. A chunk counts where the segment id
    changes inside it or at its first position (against the chunk before)."""
    seg = np.asarray(segment_ids)
    seg = seg.reshape(-1, seg.shape[-1])
    s = seg.shape[-1]
    chunk = min(chunk, s)
    starts = np.concatenate([np.zeros((len(seg), 1), bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    pad = (-s) % chunk
    starts = np.pad(starts, ((0, 0), (0, pad)))
    per_chunk = starts.reshape(len(seg), -1, chunk).any(-1)
    return int(per_chunk.size), int(per_chunk.sum())
