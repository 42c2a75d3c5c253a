"""HF safetensors checkpoint import/export for the native model zoo.

Reference: ``veomni/models/module_utils.py:348-1576`` (weight streaming,
sharded save) + ``checkpoint_tensor_loading.py`` (key conversion, per-expert
-> fused stacked weights). TPU simplifications: single-controller load means
no rank0-broadcast machinery — each tensor is read once and ``device_put``
directly to its target NamedSharding shard-by-shard.

Layout conversions (HF torch [out,in] linear vs our [in,out] kernels, and
per-layer tensors stacked on a leading L dim) are declared in one table.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from veomni_tpu.models.config import TransformerConfig
from veomni_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# (our path under layers.*, hf suffix, transpose?)  {i} is the layer index.
_LAYER_MAP: List[Tuple[str, str, bool]] = [
    ("input_layernorm", "input_layernorm.weight", False),
    ("q_proj", "self_attn.q_proj.weight", True),
    ("k_proj", "self_attn.k_proj.weight", True),
    ("v_proj", "self_attn.v_proj.weight", True),
    ("o_proj", "self_attn.o_proj.weight", True),
    ("q_bias", "self_attn.q_proj.bias", False),
    ("k_bias", "self_attn.k_proj.bias", False),
    ("v_bias", "self_attn.v_proj.bias", False),
    ("o_bias", "self_attn.o_proj.bias", False),
    ("q_norm", "self_attn.q_norm.weight", False),
    ("k_norm", "self_attn.k_norm.weight", False),
    ("sinks", "self_attn.sinks", False),
    # MLA (deepseek)
    ("q_a_proj", "self_attn.q_a_proj.weight", True),
    ("q_a_layernorm", "self_attn.q_a_layernorm.weight", False),
    ("q_b_proj", "self_attn.q_b_proj.weight", True),
    ("kv_a_proj_with_mqa", "self_attn.kv_a_proj_with_mqa.weight", True),
    ("kv_a_layernorm", "self_attn.kv_a_layernorm.weight", False),
    ("kv_b_proj", "self_attn.kv_b_proj.weight", True),
    # DSA lightning indexer (glm_moe_dsa)
    ("indexer.wq_b", "self_attn.indexer.wq_b.weight", True),
    ("indexer.wk", "self_attn.indexer.wk.weight", True),
    ("indexer.k_norm_w", "self_attn.indexer.k_norm.weight", False),
    ("indexer.k_norm_b", "self_attn.indexer.k_norm.bias", False),
    ("indexer.weights_proj", "self_attn.indexer.weights_proj.weight", True),
    # norms
    ("post_attention_layernorm", "post_attention_layernorm.weight", False),
    ("pre_feedforward_layernorm", "pre_feedforward_layernorm.weight", False),
    ("post_feedforward_layernorm", "post_feedforward_layernorm.weight", False),
    # dense mlp
    ("gate_proj", "mlp.gate_proj.weight", True),
    ("up_proj", "mlp.up_proj.weight", True),
    ("down_proj", "mlp.down_proj.weight", True),
    ("gate_bias", "mlp.gate_proj.bias", False),
    ("up_bias", "mlp.up_proj.bias", False),
    ("down_bias", "mlp.down_proj.bias", False),
    # routers
    ("router", "mlp.gate.weight", True),
    ("e_score_correction_bias", "mlp.gate.e_score_correction_bias", False),
    # shared experts (deepseek)
    ("shared_experts.gate_proj", "mlp.shared_experts.gate_proj.weight", True),
    ("shared_experts.up_proj", "mlp.shared_experts.up_proj.weight", True),
    ("shared_experts.down_proj", "mlp.shared_experts.down_proj.weight", True),
]
_EXPERT_MAP: List[Tuple[str, str]] = [
    ("experts.gate_proj", "mlp.experts.{e}.gate_proj.weight"),
    ("experts.up_proj", "mlp.experts.{e}.up_proj.weight"),
    ("experts.down_proj", "mlp.experts.{e}.down_proj.weight"),
]
# multi-token-prediction modules (deepseek_v3): ``model.layers.{L + d}.*`` holds
# the module's decoder layer under the names above, these four, and copies of
# the model's embedding and head (``embed_tokens``, ``shared_head.head``),
# which we share with the main model and neither keep nor write
_MTP_MAP: List[Tuple[str, str, bool]] = [
    ("enorm", "enorm.weight", False),
    ("hnorm", "hnorm.weight", False),
    ("eh_proj", "eh_proj.weight", True),
    ("norm", "shared_head.norm.weight", False),
]
_MTP_SHARED = ("embed_tokens.weight", "shared_head.head.weight")
# gpt_oss stores experts as fused 3-D tensors (gate/up interleaved on the
# last dim); handled explicitly in the load/save segment functions below
# (reference counterpart: checkpoint_tensor_loading.py fused maps).


def _read_all_tensors(model_dir: str) -> Dict[str, np.ndarray]:
    """Read every tensor from all safetensors shards (numpy, bf16-safe)."""
    import safetensors

    out: Dict[str, np.ndarray] = {}
    files = sorted(f for f in os.listdir(model_dir) if f.endswith(".safetensors"))
    if not files:
        raise FileNotFoundError(f"no .safetensors under {model_dir}")
    for fname in files:
        with safetensors.safe_open(os.path.join(model_dir, fname), framework="flax") as f:
            for key in f.keys():
                out[key] = f.get_tensor(key)
    return out


class LazyHFTensors:
    """Lazy view over a sharded safetensors checkpoint: per-tensor and
    per-slice reads instead of materializing the model in host RAM
    (reference streamed loading, ``module_utils.py:348,530,867``). Backed by
    mmap'd ``safe_open`` handles, so repeated slice reads ride the page
    cache."""

    def __init__(self, model_dir: Optional[str], tensors: Optional[Dict[str, Any]] = None):
        self._mem = tensors
        self._handles: Dict[str, Any] = {}
        self._where: Dict[str, str] = {}
        self._consumed: set = set()
        if tensors is None:
            import safetensors

            files = sorted(
                f for f in os.listdir(model_dir) if f.endswith(".safetensors")
            )
            if not files:
                raise FileNotFoundError(f"no .safetensors under {model_dir}")
            for fname in files:
                h = safetensors.safe_open(
                    os.path.join(model_dir, fname), framework="numpy"
                )
                self._handles[fname] = h
                for key in h.keys():
                    self._where[key] = fname

    def keys(self):
        if self._mem is not None:
            return [k for k in self._mem if k not in self._consumed]
        return [k for k in self._where if k not in self._consumed]

    def __contains__(self, name: str) -> bool:
        if name in self._consumed:
            return False
        return name in (self._mem if self._mem is not None else self._where)

    def mark_consumed(self, name: str) -> None:
        self._consumed.add(name)

    def read(self, name: str) -> np.ndarray:
        """Full tensor (marks consumed)."""
        if name not in self:
            raise KeyError(f"missing tensor {name!r}")
        self.mark_consumed(name)
        if self._mem is not None:
            return np.asarray(self._mem[name])
        return self._handles[self._where[name]].get_tensor(name)

    def read_slice(self, name: str, idx) -> np.ndarray:
        """Slice read WITHOUT marking consumed (callbacks re-read per shard)."""
        if self._mem is not None:
            return np.asarray(self._mem[name])[idx]
        return np.asarray(self._handles[self._where[name]].get_slice(name)[idx])

    def shape(self, name: str):
        if self._mem is not None:
            return tuple(np.asarray(self._mem[name]).shape)
        return tuple(self._handles[self._where[name]].get_slice(name).get_shape())


def hf_to_params(
    model_dir: str, cfg: TransformerConfig, target_shardings=None,
    tensors: Optional[Dict[str, np.ndarray]] = None,
    key_map: Optional[Callable[[str], Optional[str]]] = None,
) -> Dict[str, Any]:
    """Stream an HF checkpoint dir into our stacked-param pytree.

    Streamed + shard-aligned (reference ``module_utils.py:348,530,867``):
    with ``target_shardings``, every param is built via
    ``jax.make_array_from_callback`` whose callback reads ONLY the slices the
    local shards need straight from the mmap'd safetensors (per-layer /
    per-expert tensors for stacked params) — peak host RAM is
    O(one shard slice), never O(model), and multihost EP processes read only
    their expert slice. Without shardings (tests/CPU), full tensors stream
    one param at a time.

    ``tensors``: already-read {hf_name: array} mapping (small composite
    subtrees). ``key_map``: rename/filter checkpoint keys before matching
    (composite models map e.g. ``model.language_model.*`` -> ``model.*`` and
    drop other modalities' tensors by returning None) — keeps the text
    subtree of a VLM on the streamed path instead of materializing it.
    """
    lazy = LazyHFTensors(None if tensors is not None else model_dir, tensors)
    alias = {}
    for k in lazy.keys():
        nk = key_map(k) if key_map else k
        if nk is None:
            continue
        alias[re.sub(r"^model\.", "", nk)] = k
    pd = cfg.param_dtype
    pd_np = np.dtype(jnp.zeros((), pd).dtype)
    L = cfg.num_hidden_layers
    k_dense = cfg.first_k_dense_replace if cfg.is_moe else 0

    shardings: Dict[str, Any] = {}
    if target_shardings is not None:
        from veomni_tpu.parallel.parallel_plan import param_path_str

        jax.tree_util.tree_map_with_path(
            lambda p, s: shardings.__setitem__(param_path_str(p), s),
            target_shardings,
        )

    def has(name: str) -> bool:
        return name in alias and alias[name] in lazy

    broadcast = (
        os.environ.get("VEOMNI_WEIGHTS_BROADCAST") == "1"
        and jax.process_count() > 1
    )

    def place(dotted: str, shape, read_block):
        """read_block(idx: tuple[slice]) -> np array of that sub-shape."""
        sh = shardings.get(dotted)
        if shardings and sh is None:
            # a silent miss would materialize the tensor fully replicated on
            # every host — exactly the OOM this loader exists to avoid
            raise KeyError(
                f"param {dotted!r} missing from target_shardings "
                f"(have e.g. {sorted(shardings)[:4]})"
            )
        if sh is not None:
            if broadcast and not any(sh.spec):
                # fully-replicated param in rank0-broadcast mode: one
                # filesystem read on process 0, everyone else receives over
                # the interconnect (reference chunked rank0 broadcast,
                # ``module_utils.py:867`` — here one psum collective)
                from jax.experimental import multihost_utils

                if jax.process_index() == 0:
                    full = read_block(tuple(slice(None) for _ in shape))
                    host = np.ascontiguousarray(full).astype(pd_np)
                else:
                    host = np.zeros(tuple(shape), pd_np)
                arr = multihost_utils.broadcast_one_to_all(host)
                return jax.device_put(jnp.asarray(arr, pd), sh)
            return jax.make_array_from_callback(
                tuple(shape), sh,
                lambda idx: np.ascontiguousarray(read_block(idx)).astype(pd_np),
            )
        full = read_block(tuple(slice(None) for _ in shape))
        return jnp.asarray(np.ascontiguousarray(full), pd)

    def single(dotted: str, name: str, transpose: bool):
        real = alias[name]
        hf_shape = lazy.shape(real)
        shape = tuple(reversed(hf_shape)) if transpose else hf_shape
        lazy.mark_consumed(real)

        def read(idx):
            if transpose:
                return lazy.read_slice(real, tuple(reversed(idx))).T
            return lazy.read_slice(real, idx)

        return place(dotted, shape, read)

    def stacked(dotted: str, hf_suffix: str, offset: int, count: int,
                transpose: bool, postprocess=None):
        names = []
        for i in range(count):
            real = alias[f"layers.{offset + i}.{hf_suffix}"]
            lazy.mark_consumed(real)
            names.append(real)
        one = lazy.shape(names[0])
        one_ours = tuple(reversed(one)) if transpose else one
        if postprocess is not None:
            one_ours = postprocess.shape(one_ours)

        def read(idx):
            lsl, rest = idx[0], tuple(idx[1:])
            parts = []
            for i in range(*lsl.indices(count)):
                if postprocess is not None and hasattr(postprocess, "slice_read"):
                    # contiguous fused layouts: direct offset read (streamed)
                    part = postprocess.slice_read(lazy, names[i], rest, one)
                elif postprocess is not None:
                    # interleaved layouts: read the layer tensor, slice host-side
                    part = postprocess.extract(lazy.read_slice(
                        names[i], tuple(slice(None) for _ in one)))[rest]
                elif transpose:
                    part = lazy.read_slice(names[i], tuple(reversed(rest))).T
                else:
                    part = lazy.read_slice(names[i], rest)
                parts.append(part)
            return np.stack(parts)

        return place(dotted, (count,) + tuple(one_ours), read)

    def experts_stacked(dotted: str, hf_tmpl: str, offset: int, count: int):
        """[count, E, in, out] from per-expert HF [out, in] tensors — the
        EP-sliced read path: a callback for an ep-sharded target touches only
        its (layer, expert) block."""
        # the experts this model holds (all, or one chip's share of them)
        e_total, e0 = cfg.experts_held, cfg.moe_experts_held_first
        names = [[alias[f"layers.{offset + i}.{hf_tmpl.format(e=e0 + e)}"]
                  for e in range(e_total)] for i in range(count)]
        for row in names:
            for real in row:
                lazy.mark_consumed(real)
        o_dim, i_dim = lazy.shape(names[0][0])

        def read(idx):
            lsl, esl, isl, osl = idx
            ls = range(*lsl.indices(count))
            es = range(*esl.indices(e_total))
            out = None
            for a, i in enumerate(ls):
                for b, e in enumerate(es):
                    part = lazy.read_slice(names[i][e], (osl, isl)).T
                    if out is None:
                        out = np.empty((len(ls), len(es)) + part.shape, part.dtype)
                    out[a, b] = part
            return out

        return place(dotted, (count, e_total, i_dim, o_dim), read)

    def set_nested(tree, dotted, value):
        parts = dotted.split(".")
        for p in parts[:-1]:
            tree = tree.setdefault(p, {})
        tree[parts[-1]] = value

    class _Interleave:
        """gpt_oss fused gate_up [..., 2I] -> every-other-column extract."""

        def __init__(self, start):
            self.start = start

        def shape(self, s):
            return s[:-1] + (s[-1] // 2,)

        def extract(self, arr):
            return arr[..., self.start::2]

    class _Chunk:
        """qwen3_vl_moe fused gate_up [..., 2I] -> gate/up half extract.

        Halves are contiguous on the last dim, so a target-sharding slice
        maps to a direct offset read — the streamed O(slice) load contract
        holds (unlike gpt_oss's stride-2 interleave, which must read the
        full layer tensor host-side)."""

        def __init__(self, start):
            self.start = start

        def shape(self, s):
            return s[:-1] + (s[-1] // 2,)

        def slice_read(self, lazy_, name, rest, hf_shape):
            half = hf_shape[-1] // 2
            rest = tuple(rest) + tuple(
                slice(None) for _ in range(len(hf_shape) - len(rest))
            )
            lo, hi, step = rest[-1].indices(half)
            off = self.start * half
            return lazy_.read_slice(
                name, rest[:-1] + (slice(lo + off, hi + off, step),)
            )

    def load_segment(prefix: str, offset: int, count: int, moe_seg: bool):
        layers: Dict[str, Any] = {}
        for ours, hf_suffix, transpose in _LAYER_MAP:
            if not has(f"layers.{offset}.{hf_suffix}"):
                continue
            set_nested(layers, ours, stacked(
                f"{prefix}.{ours}", hf_suffix, offset, count, transpose))
        if moe_seg and cfg.is_moe:
            if has(f"layers.{offset}.mlp.experts.gate_up_proj"):
                # fused experts [E, H, 2I]: gpt_oss interleaves gate/up on the
                # last dim (and has a dedicated mlp.router); qwen3_vl_moe
                # chunks gate|up halves (router = generic mlp.gate map)
                interleaved = has(f"layers.{offset}.mlp.router.weight")
                split = _Interleave if interleaved else _Chunk
                layers["experts"] = {
                    "gate_proj": stacked(
                        f"{prefix}.experts.gate_proj", "mlp.experts.gate_up_proj",
                        offset, count, False, postprocess=split(0)),
                    "up_proj": stacked(
                        f"{prefix}.experts.up_proj", "mlp.experts.gate_up_proj",
                        offset, count, False, postprocess=split(1)),
                    "down_proj": stacked(
                        f"{prefix}.experts.down_proj", "mlp.experts.down_proj",
                        offset, count, False),
                }
                if has(f"layers.{offset}.mlp.experts.gate_up_proj_bias"):
                    layers["experts"]["gate_bias"] = stacked(
                        f"{prefix}.experts.gate_bias",
                        "mlp.experts.gate_up_proj_bias", offset, count, False,
                        postprocess=_Interleave(0))
                    layers["experts"]["up_bias"] = stacked(
                        f"{prefix}.experts.up_bias",
                        "mlp.experts.gate_up_proj_bias", offset, count, False,
                        postprocess=_Interleave(1))
                    layers["experts"]["down_bias"] = stacked(
                        f"{prefix}.experts.down_bias",
                        "mlp.experts.down_proj_bias", offset, count, False)
                if interleaved:
                    layers["router"] = stacked(
                        f"{prefix}.router", "mlp.router.weight",
                        offset, count, True)
                    if has(f"layers.{offset}.mlp.router.bias"):
                        layers["router_bias"] = stacked(
                            f"{prefix}.router_bias", "mlp.router.bias",
                            offset, count, False)
            else:
                for ours, hf_tmpl in _EXPERT_MAP:
                    set_nested(layers, ours, experts_stacked(
                        f"{prefix}.{ours}", hf_tmpl, offset, count))
        return layers

    # NOTE: gate_up_proj appears twice above (gate + up extracts); only mark
    # consumed once is fine — mark_consumed is idempotent.
    params: Dict[str, Any] = {
        "embed_tokens": single("embed_tokens", "embed_tokens.weight", False),
        "norm": single("norm", "norm.weight", False),
    }
    if k_dense:
        params["dense_layers"] = load_segment("dense_layers", 0, k_dense, False)
    params["layers"] = load_segment("layers", k_dense, L - k_dense, True)
    if cfg.num_nextn_predict_layers:
        depth = cfg.num_nextn_predict_layers
        params["mtp"] = load_segment("mtp", L, depth, True)
        for ours, hf_suffix, transpose in _MTP_MAP:
            params["mtp"][ours] = stacked(f"mtp.{ours}", hf_suffix, L, depth, transpose)
        for d in range(depth):
            for shared in _MTP_SHARED:
                if has(f"layers.{L + d}.{shared}"):
                    lazy.mark_consumed(alias[f"layers.{L + d}.{shared}"])
    if not cfg.tie_word_embeddings:
        if has("lm_head.weight"):
            params["lm_head"] = single("lm_head", "lm_head.weight", True)
        else:
            # untied head missing in the checkpoint: fall back to embed^T
            real = alias["embed_tokens.weight"]
            v, h = lazy.shape(real)
            params["lm_head"] = place(
                "lm_head", (h, v),
                lambda idx: lazy.read_slice(real, tuple(reversed(idx))).T,
            )
    remaining = sorted(
        k for k in lazy.keys() if (key_map(k) if key_map else k) is not None
    )
    if remaining:
        logger.warning_rank0("unconsumed HF tensors: %s", remaining[:8])
    return params


def _get_nested(tree, dotted):
    for p in dotted.split("."):
        if not isinstance(tree, dict) or p not in tree:
            return None
        tree = tree[p]
    return tree


def gather_to_host(params):
    """Pytree of (possibly multihost-sharded) arrays -> host numpy. In
    multiprocess runs this is COLLECTIVE (process_allgather) — every process
    must call it, even if only process 0 writes files."""
    def one(x):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            from jax.experimental import multihost_utils

            return np.asarray(multihost_utils.process_allgather(x, tiled=True))
        return np.asarray(jax.device_get(x))

    return jax.tree.map(one, params)


def params_to_hf(params: Dict[str, Any], cfg: TransformerConfig) -> Dict[str, np.ndarray]:
    """Inverse mapping, for HF-format export (gathers to host; collective in
    multiprocess runs)."""
    out: Dict[str, np.ndarray] = {}
    host = gather_to_host(params)
    out["model.embed_tokens.weight"] = host["embed_tokens"]
    out["model.norm.weight"] = host["norm"]
    if "lm_head" in host:
        out["lm_head.weight"] = host["lm_head"].T
    L = cfg.num_hidden_layers
    k_dense = cfg.first_k_dense_replace if cfg.is_moe else 0

    def dump_segment(layers, offset, count, moe_seg):
        for ours, hf_suffix, transpose in _LAYER_MAP:
            if cfg.model_type == "gpt_oss" and ours in ("router", "router_bias"):
                continue  # exported in the fused-expert block below
            t = _get_nested(layers, ours)
            if t is None:
                continue
            for i in range(count):
                x = t[i]
                out[f"model.layers.{offset + i}.{hf_suffix}"] = x.T if transpose else x
        if moe_seg and cfg.is_moe:
            ex = layers["experts"]
            layout = cfg.expert_layout or (
                "fused_interleaved" if cfg.model_type == "gpt_oss"
                else "per_expert"
            )
            if layout == "fused_chunked":
                # qwen3_vl_moe: gate_up_proj [E, H, 2I] = gate | up halves
                for i in range(count):
                    pfx = f"model.layers.{offset + i}.mlp.experts"
                    out[f"{pfx}.gate_up_proj"] = np.concatenate(
                        [ex["gate_proj"][i], ex["up_proj"][i]], axis=-1
                    )
                    out[f"{pfx}.down_proj"] = ex["down_proj"][i]
            elif cfg.model_type == "gpt_oss":
                for i in range(count):
                    gu = np.empty(
                        (cfg.num_experts, cfg.hidden_size,
                         2 * ex["gate_proj"].shape[-1]), ex["gate_proj"].dtype
                    )
                    gu[..., ::2] = ex["gate_proj"][i]
                    gu[..., 1::2] = ex["up_proj"][i]
                    pfx = f"model.layers.{offset + i}.mlp.experts"
                    out[f"{pfx}.gate_up_proj"] = gu
                    out[f"{pfx}.down_proj"] = ex["down_proj"][i]
                    if "gate_bias" in ex:
                        gub = np.empty(
                            (cfg.num_experts, 2 * ex["gate_bias"].shape[-1]),
                            ex["gate_bias"].dtype,
                        )
                        gub[..., ::2] = ex["gate_bias"][i]
                        gub[..., 1::2] = ex["up_bias"][i]
                        out[f"{pfx}.gate_up_proj_bias"] = gub
                        out[f"{pfx}.down_proj_bias"] = ex["down_bias"][i]
                    out[f"model.layers.{offset + i}.mlp.router.weight"] = (
                        layers["router"][i].T
                    )
                    if "router_bias" in layers:
                        out[f"model.layers.{offset + i}.mlp.router.bias"] = (
                            layers["router_bias"][i]
                        )
            else:
                for ours, hf_tmpl in _EXPERT_MAP:
                    b = ours.split(".")[1]
                    for i in range(count):
                        for e in range(cfg.experts_held):
                            name = hf_tmpl.format(e=cfg.moe_experts_held_first + e)
                            out[f"model.layers.{offset + i}.{name}"] = ex[b][i, e].T

    if k_dense:
        dump_segment(host["dense_layers"], 0, k_dense, False)
    dump_segment(host["layers"], k_dense, L - k_dense, True)
    if "mtp" in host:
        depth = cfg.num_nextn_predict_layers
        # the module's decoder layer under the layer map's names, its own four
        # leaves under theirs
        own = {ours for ours, _, _ in _MTP_MAP}
        dump_segment({k: v for k, v in host["mtp"].items() if k not in own}, L, depth, True)
        for ours, hf_suffix, transpose in _MTP_MAP:
            for d in range(depth):
                x = host["mtp"][ours][d]
                out[f"model.layers.{L + d}.{hf_suffix}"] = x.T if transpose else x
    return out


def save_hf_checkpoint(
    params: Dict[str, Any], cfg: TransformerConfig, out_dir: str,
    max_shard_bytes: int = 4 * 1024**3,
) -> None:
    """HF-format sharded safetensors export (reference save_model_weights,
    ``module_utils.py:1445``)."""
    from safetensors.flax import save_file

    tensors = params_to_hf(params, cfg)  # collective gather (all processes)
    if jax.process_index() != 0:
        return
    os.makedirs(out_dir, exist_ok=True)
    shards: List[Dict[str, np.ndarray]] = [{}]
    sizes = [0]
    for k in sorted(tensors):
        t = tensors[k]
        nbytes = t.size * t.dtype.itemsize
        if sizes[-1] + nbytes > max_shard_bytes and shards[-1]:
            shards.append({})
            sizes.append(0)
        shards[-1][k] = t
        sizes[-1] += nbytes
    n = len(shards)
    index = {"metadata": {"total_size": sum(sizes)}, "weight_map": {}}
    for i, shard in enumerate(shards):
        fname = (
            "model.safetensors" if n == 1
            else f"model-{i + 1:05d}-of-{n:05d}.safetensors"
        )
        save_file({k: jnp.asarray(v) for k, v in shard.items()},
                  os.path.join(out_dir, fname))
        for k in shard:
            index["weight_map"][k] = fname
    if n > 1:
        with open(os.path.join(out_dir, "model.safetensors.index.json"), "w") as f:
            json.dump(index, f, indent=2)
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump(cfg.to_hf_config(), f, indent=2)
    logger.info_rank0("saved HF checkpoint to %s (%d shards)", out_dir, n)
