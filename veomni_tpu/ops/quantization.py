"""Quantized serving storage: int8 KV-cache blocks and int8 decode weights.

Two independent tiers, both registry-visible and both *storage-format*
changes rather than new math — the attention/matmul semantics are the
shared f32 paths of ``ops/paged_attention.py`` and ``models/decode.py``,
applied to dequantized values:

**Tier 1 — int8 KV blocks.** :class:`QuantizedKV` packs the engine's
``[L, NB, BS, hkv, d]`` block pool as an int8 payload plus an f32 scale
sidecar of shape ``[L, NB, BS, hkv]`` — one symmetric absmax scale per
(layer, block, row, kv-head). The granularity is per *row* within a block
(not per whole block) because every decode tick appends a single row: a
coarser per-block scale would have to rescale the block's existing rows on
every append. Quantization happens on write (``.at[...].set(rows)`` with a
float value quantizes; with a :class:`QuantizedKV` value it copies payload
+ scale bit-exactly — the copy-on-write path), dequantization happens
inside the gathered attend (``paged_attention/xla_gather_q8``). The pool
stays opaque to the host-side block manager: refcounts, prefix cache, CoW
and eviction never look inside a block.

**Tier 2 — int8 decode weights.** :class:`QuantizedWeight` holds a stacked
projection weight ``[L, in, out]`` as int8 with one f32 scale per
(layer, output channel) (symmetric absmax over the input dim, kept as
``[L, 1, out]`` so ``lax.scan`` slices payload and scale along the same
leading layer axis). The decode-path matmuls dispatch through the
``decode_matmul`` registry op: the ``xla_q8`` impl computes the int8 dot
in f32 and folds the per-channel scale in afterwards — per-channel
symmetric quantization commutes with the contraction, so the fold is
exact up to the int8 rounding itself.

Zero-safe: an all-zero row quantizes to scale 0 and payload 0, and the
``xla_q8`` dequant multiplies by the stored scale — all-zero rows (the
freshly allocated pool, padded weight rows) round-trip to exact zeros.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY

#: int8 symmetric range: +-127 (–128 is unused so the range is symmetric
#: and negation never overflows)
_Q8_MAX = 127.0


def quantize_rows(x, *, axis: int = -1) -> Tuple[jax.Array, jax.Array]:
    """Symmetric absmax int8 quantization along ``axis``.

    Returns ``(payload int8, scale f32)`` with ``scale`` shaped like ``x``
    minus ``axis``. Zero rows get scale 0 (the safe divide substitutes 1,
    so the payload is exact zeros and dequantization reproduces them)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axis)
    scale = amax / _Q8_MAX
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.round(xf / jnp.expand_dims(safe, axis))
    q = jnp.clip(q, -_Q8_MAX, _Q8_MAX).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_rows(payload, scale, dtype=jnp.float32):
    """Inverse of :func:`quantize_rows` (scale broadcast over the last dim)."""
    return (payload.astype(jnp.float32) * scale[..., None]).astype(dtype)


class _KVIndexUpdate:
    """One pending ``pool.at[idx]`` update (mirrors jax's ``.at`` protocol
    for the two writes the serving paths use)."""

    __slots__ = ("_pool", "_idx")

    def __init__(self, pool: "QuantizedKV", idx):
        self._pool = pool
        self._idx = idx

    def set(self, value) -> "QuantizedKV":
        """Write rows at the index. A :class:`QuantizedKV` value copies
        payload + scale bit-exactly (CoW / segment-scan threading); a float
        value is quantized over its last (head_dim) axis on the way in —
        the quantize-on-write contract of every scatter/append site."""
        data, scale = self._pool.data, self._pool.scale
        if isinstance(value, QuantizedKV):
            return QuantizedKV(
                data.at[self._idx].set(value.data),
                scale.at[self._idx].set(value.scale),
            )
        q, s = quantize_rows(value)
        return QuantizedKV(data.at[self._idx].set(q),
                           scale.at[self._idx].set(s))


class _KVAt:
    __slots__ = ("_pool",)

    def __init__(self, pool: "QuantizedKV"):
        self._pool = pool

    def __getitem__(self, idx) -> _KVIndexUpdate:
        return _KVIndexUpdate(self._pool, idx)


@jax.tree_util.register_pytree_node_class
class QuantizedKV:
    """int8 KV block pool + per-(…, row, head) f32 scale sidecar.

    Drop-in for the dense pool arrays everywhere the serving paths touch
    them structurally: ``pool[idx]`` and ``pool.at[idx].set(...)`` apply
    the same index to payload and sidecar (valid for any index over the
    leading dims both share — everything up to the head_dim axis), and
    ``shape`` reports the logical (payload) shape. As a registered pytree
    it threads through ``jax.jit`` (donation donates both leaves) and
    ``lax.scan`` xs/ys slicing unchanged."""

    __slots__ = ("data", "scale")

    def __init__(self, data, scale):
        self.data = data    # int8 [..., d]
        self.scale = scale  # f32 [...] == data.shape[:-1]

    # ------------------------------------------------------------- structure
    def tree_flatten(self):
        return (self.data, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.data.shape

    @property
    def ndim(self):
        return self.data.ndim

    @property
    def nbytes(self) -> int:
        """Actual device bytes: int8 payload + f32 scale sidecar — what the
        capacity gauges (``observability/devmem.py``) must report."""
        return int(self.data.nbytes) + int(self.scale.nbytes)

    # ---------------------------------------------------------------- access
    def __getitem__(self, idx) -> "QuantizedKV":
        return QuantizedKV(self.data[idx], self.scale[idx])

    @property
    def at(self) -> _KVAt:
        return _KVAt(self)

    def dequantize(self, dtype=jnp.float32):
        return dequantize_rows(self.data, self.scale, dtype)


def make_kv_pool(shape, kv_quant: str, dtype):
    """Allocate one KV block pool in the requested storage mode.

    ``shape`` is the logical ``[L, NB, BS, hkv, d]``. ``"none"`` returns the
    dense ``dtype`` pool; ``"int8"`` the :class:`QuantizedKV` pair. ``"fp8"``
    is scaffolded behind the same interface (same sidecar layout, fp8
    payload) but does not ship yet."""
    if kv_quant == "none":
        return jnp.zeros(shape, dtype)
    if kv_quant == "int8":
        return QuantizedKV(
            jnp.zeros(shape, jnp.int8),
            jnp.zeros(shape[:-1], jnp.float32),
        )
    if kv_quant == "fp8":
        raise NotImplementedError(
            "kv_quant='fp8' is scaffolded behind the QuantizedKV interface "
            "(fp8 payload + f32 scale sidecar) but only 'int8' ships; use "
            "kv_quant='int8' or 'none'"
        )
    raise ValueError(
        f"unknown kv_quant {kv_quant!r}; expected 'none', 'int8' or 'fp8'"
    )


def kv_pool_nbytes(pool) -> float:
    """Device bytes of one pool, quantization-aware (``QuantizedKV``
    reports payload + sidecar; dense arrays report ``nbytes``)."""
    return float(getattr(pool, "nbytes", 0) or 0)


def kv_block_nbytes(num_layers: int, block_size: int, num_kv_heads: int,
                    head_dim: int, *, kv_quant: str = "none",
                    dtype_bytes: int = 4) -> int:
    """Bytes ONE pool block (k + v, all layers) occupies in the given
    storage mode — the sizing primitive for equal-byte pools across
    quantization modes without allocating either."""
    rows = num_layers * block_size * num_kv_heads
    if kv_quant == "int8":
        per_pool = rows * (head_dim * 1 + 4)  # int8 payload + f32 scale/row
    elif kv_quant == "none":
        per_pool = rows * head_dim * dtype_bytes
    else:
        raise ValueError(f"unknown kv_quant {kv_quant!r}")
    return 2 * per_pool


# --------------------------------------------------------------------------
# Tier 2: int8 decode weights
# --------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
class QuantizedWeight:
    """int8 stacked projection weight + per-(layer, out-channel) f32 scale.

    ``data [L, in, out]`` int8, ``scale [L, 1, out]`` f32 — both keep the
    leading layer axis so ``lax.scan`` slices them together. The singleton
    input axis on the scale makes the in-kernel fold a plain broadcast."""

    __slots__ = ("data", "scale")

    def __init__(self, data, scale):
        self.data = data
        self.scale = scale

    def tree_flatten(self):
        return (self.data, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.data.shape

    @property
    def nbytes(self) -> int:
        return int(self.data.nbytes) + int(self.scale.nbytes)

    def __getitem__(self, idx) -> "QuantizedWeight":
        return QuantizedWeight(self.data[idx], self.scale[idx])


def quantize_weight(w) -> QuantizedWeight:
    """Symmetric per-output-channel int8 quantization of a stacked
    ``[..., in, out]`` projection weight (absmax over the input dim)."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)  # [..., 1, out]
    scale = amax / _Q8_MAX
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(wf / safe), -_Q8_MAX, _Q8_MAX).astype(jnp.int8)
    return QuantizedWeight(q, scale.astype(jnp.float32))


#: decode-path projection weights eligible for int8 storage: the stacked
#: 2-D-per-layer matmuls of the dense attention/MLP blocks. Everything else
#: — embeddings, norms, biases, sinks, the lm head, routers, and the MoE
#: expert stacks (4-D, grouped-GEMM consumed) — stays full-width.
DECODE_QUANT_KEYS = frozenset({
    "q_proj", "k_proj", "v_proj", "o_proj",
    "gate_proj", "up_proj", "down_proj",
})


def quantize_decode_params(params):
    """Return a params tree whose decode-path projection weights are
    :class:`QuantizedWeight` (int8 + per-channel scale). Only the *direct*
    ``[L, in, out]`` entries of the stacked layer subtrees are converted:
    nested subtrees (``experts``, ``shared_experts``) and every non-matmul
    tensor pass through untouched, so the MoE grouped-GEMM path and the
    embedding/norm/head math are bit-identical to the f32 engine."""
    out = dict(params)
    for seg in ("layers", "dense_layers"):
        tree = params.get(seg)
        if not isinstance(tree, dict):
            continue
        new_tree = dict(tree)
        for name, w in tree.items():
            if (name in DECODE_QUANT_KEYS and not isinstance(w, dict)
                    and getattr(w, "ndim", 0) == 3):
                new_tree[name] = quantize_weight(w)
        out[seg] = new_tree
    return out


@KERNEL_REGISTRY.register("decode_matmul", "xla")
def _decode_matmul_xla(x, w):
    return jnp.dot(x, w)


@KERNEL_REGISTRY.register("decode_matmul", "xla_q8")
def _decode_matmul_q8(x, w: QuantizedWeight):
    """int8-weight matmul, dequantizing in-kernel: contract against the
    int8 payload in f32, then fold the per-output-channel scale into the
    product — exact because the scale is constant along the contraction
    axis. ``w`` arrives layer-sliced (``[in, out]`` + ``[1, out]``) inside
    the scan body or fully stacked; the broadcast handles both."""
    acc = jnp.dot(x, w.data.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    return (acc * w.scale.reshape(w.scale.shape[:-2] + (-1,))).astype(x.dtype)


def decode_dot(x, w):
    """Registry-dispatched decode-path matmul.

    Storage decides the impl — a :class:`QuantizedWeight` takes
    ``decode_matmul/xla_q8``, a dense array ``decode_matmul/xla`` — and an
    ops-config pin overrides both (the pinned impl must match the storage
    it is handed, same contract as the paged-attention pins)."""
    pin = KERNEL_REGISTRY.pinned("decode_matmul")
    if pin is not None:
        return KERNEL_REGISTRY.impls("decode_matmul")[pin].fn(x, w)
    impl = "xla_q8" if isinstance(w, QuantizedWeight) else "xla"
    return KERNEL_REGISTRY.impls("decode_matmul")[impl].fn(x, w)
