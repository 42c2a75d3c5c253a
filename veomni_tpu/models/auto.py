"""Model registry + build entry points.

Reference: ``veomni/models/auto.py:41-280`` (build_foundation_model /
build_tokenizer) and ``models/loader.py:49-291`` (registries keyed by
model_type). A *family* bundles the functional pieces the trainer needs:
config class, init/apply/loss, the declarative ParallelPlan, and HF
checkpoint converters.
"""

from __future__ import annotations

import os

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import jax

from veomni_tpu.models import hf_io, transformer
from veomni_tpu.models.config import TransformerConfig
from veomni_tpu.parallel.parallel_plan import ParallelPlan
from veomni_tpu.utils.logging import get_logger
from veomni_tpu.utils.registry import Registry

logger = get_logger(__name__)

MODEL_REGISTRY = Registry("models")


@dataclass
class ModelFamily:
    """The per-model_type recipe (cf. reference MODELING_REGISTRY entries)."""

    model_type: str
    config_cls: type = TransformerConfig
    init_params: Callable = transformer.init_params
    abstract_params: Callable = transformer.abstract_params
    loss_fn: Callable = transformer.loss_fn
    forward_logits: Callable = transformer.forward_logits
    hf_to_params: Callable = hf_io.hf_to_params
    save_hf_checkpoint: Callable = hf_io.save_hf_checkpoint
    parallel_plan_fn: Optional[Callable] = None

    def get_parallel_plan(self, cfg) -> ParallelPlan:
        """Model-declared sharding (reference get_parallel_plan,
        e.g. ``models/transformers/qwen3_moe/parallel_plan.py:6-16``)."""
        if self.parallel_plan_fn is not None:
            return self.parallel_plan_fn(cfg)
        rules: Dict[str, tuple] = {}
        if getattr(cfg, "is_moe", False):
            # experts [L, E, in, out]: expert dim over ep, features over fsdp
            rules[r"(layers|mtp)\.experts\..*"] = ("ep", "ep_fsdp", None)
            rules[r"(layers|mtp)\.router$"] = ()
        return ParallelPlan(rules=rules)


for _mt in (
    "llama", "qwen2", "qwen3", "qwen3_moe",
    "gemma3", "gemma3_text",
    "deepseek_v2", "deepseek_v3", "joyai_llm_flash",
    "gpt_oss", "seed_oss", "glm_moe", "glm4_moe", "glm_moe_dsa",
):
    MODEL_REGISTRY.register(_mt, ModelFamily(model_type=_mt))


def _register_qwen3_next():
    from veomni_tpu.models import qwen3_next as q3n

    MODEL_REGISTRY.register(
        "qwen3_next",
        ModelFamily(
            model_type="qwen3_next",
            init_params=q3n.init_params,
            abstract_params=q3n.abstract_params,
            loss_fn=q3n.loss_fn,
            forward_logits=q3n.forward_logits,
            hf_to_params=q3n.hf_to_params,
            save_hf_checkpoint=q3n.save_hf_checkpoint,
            parallel_plan_fn=q3n.parallel_plan,
        ),
    )


_register_qwen3_next()


def _register_granite_hybrid():
    from veomni_tpu.models import granite_hybrid as gh

    MODEL_REGISTRY.register(
        "granitemoehybrid",
        ModelFamily(
            model_type="granitemoehybrid",
            init_params=gh.init_params,
            abstract_params=gh.abstract_params,
            loss_fn=gh.loss_fn,
            forward_logits=gh.forward_logits,
            hf_to_params=gh.hf_to_params,
            save_hf_checkpoint=gh.save_hf_checkpoint,
            parallel_plan_fn=gh.parallel_plan,
        ),
    )


_register_granite_hybrid()


def _register_kimi_linear():
    from veomni_tpu.models import kimi_linear as kl

    MODEL_REGISTRY.register(
        "kimi_linear",
        ModelFamily(
            model_type="kimi_linear",
            init_params=kl.init_params,
            abstract_params=kl.abstract_params,
            loss_fn=kl.loss_fn,
            forward_logits=kl.forward_logits,
            hf_to_params=kl.hf_to_params,
            save_hf_checkpoint=kl.save_hf_checkpoint,
            parallel_plan_fn=kl.parallel_plan,
        ),
    )


_register_kimi_linear()


def _register_deepseek_v4():
    from veomni_tpu.models import deepseek_v4 as dsv4

    MODEL_REGISTRY.register(
        "deepseek_v4",
        ModelFamily(
            model_type="deepseek_v4",
            config_cls=dsv4.DeepseekV4Config,
            init_params=dsv4.init_params,
            abstract_params=dsv4.abstract_params,
            loss_fn=dsv4.loss_fn,
            forward_logits=dsv4.forward_logits,
            hf_to_params=dsv4.hf_to_params,
            save_hf_checkpoint=dsv4.save_hf_checkpoint,
        ),
    )


_register_deepseek_v4()


def _register_vlm_families():
    from veomni_tpu.models import vlm as vlm_mod
    from veomni_tpu.models.vlm import VLMConfig

    def _save_native(params, cfg, out_dir):
        """Native flat-safetensors save for composite models (HF-layout VLM
        export is a follow-up; the language_model subtree additionally gets a
        standard HF export)."""
        import os

        from safetensors.flax import save_file

        from veomni_tpu.parallel.parallel_plan import param_path_str

        host = hf_io.gather_to_host(params)  # collective in multiprocess
        if jax.process_index() == 0:
            os.makedirs(out_dir, exist_ok=True)
            flat = {}
            jax.tree_util.tree_map_with_path(
                lambda p, x: flat.__setitem__(param_path_str(p), x), host
            )
            save_file(flat, f"{out_dir}/model.safetensors")
        # reuse the gathered host copy: gather_to_host inside is a no-op on
        # numpy leaves, so the LM isn't allgathered a second time
        hf_io.save_hf_checkpoint(
            host["language_model"], cfg.text, f"{out_dir}/language_model"
        )

    # generic fixed-slot VLM composite (any ViT + any registered LM) — the
    # didactic/testing baseline; real checkpoint families have their own archs
    MODEL_REGISTRY.register(
        "slot_vlm",
        ModelFamily(
            model_type="slot_vlm",
            config_cls=VLMConfig,
            init_params=vlm_mod.init_vlm_params,
            abstract_params=vlm_mod.abstract_vlm_params,
            loss_fn=vlm_mod.vlm_loss_fn,
            forward_logits=None,
            hf_to_params=None,
            save_hf_checkpoint=_save_native,
        ),
    )

    # qwen2_vl is the real architecture (full-attn LayerNorm ViT, per-frame
    # segments, quick-GELU MLP, mrope)
    from veomni_tpu.models import qwen2_vl as q2vl

    MODEL_REGISTRY.register(
        "qwen2_vl",
        ModelFamily(
            model_type="qwen2_vl",
            config_cls=q2vl.Qwen2VLConfig,
            init_params=q2vl.init_params,
            abstract_params=q2vl.abstract_params,
            loss_fn=q2vl.loss_fn,
            forward_logits=None,
            hf_to_params=q2vl.hf_to_params,
            save_hf_checkpoint=q2vl.save_hf_checkpoint,
        ),
    )

    # qwen3_vl is the real architecture (deepstack ViT + interleaved mrope);
    # qwen3_vl_moe = same tower + qwen3_moe text (fused-chunked experts)
    from veomni_tpu.models import qwen3_vl as q3vl

    for mt in ("qwen3_vl", "qwen3_vl_moe"):
        MODEL_REGISTRY.register(
            mt,
            ModelFamily(
                model_type=mt,
                config_cls=q3vl.Qwen3VLConfig,
                init_params=q3vl.init_params,
                abstract_params=q3vl.abstract_params,
                loss_fn=q3vl.loss_fn,
                forward_logits=None,
                hf_to_params=q3vl.hf_to_params,
                save_hf_checkpoint=q3vl.save_hf_checkpoint,
            ),
        )

    # qwen2_5_vl is the real architecture (window-attn ViT + mrope + merger)
    from veomni_tpu.models import qwen2_5_vl as q25

    MODEL_REGISTRY.register(
        "qwen2_5_vl",
        ModelFamily(
            model_type="qwen2_5_vl",
            config_cls=q25.Qwen25VLConfig,
            init_params=q25.init_params,
            abstract_params=q25.abstract_params,
            loss_fn=q25.loss_fn,
            forward_logits=None,
            hf_to_params=q25.hf_to_params,
            save_hf_checkpoint=q25.save_hf_checkpoint,
        ),
    )

    # janus: unified understanding (SigLIP ViT) + generation (llamagen VQ)
    from veomni_tpu.models import janus as janus_mod

    def _janus_plan(_cfg):
        from veomni_tpu.parallel.parallel_plan import ParallelPlan

        # replicate the (frozen) VQ tokenizer: GSPMD-partitioned conv
        # kernels deadlock XLA:CPU's rendezvous and gain nothing on TPU
        return ParallelPlan(rules={r"(^|\.)gen_vision\.": ()})

    MODEL_REGISTRY.register(
        "janus",
        ModelFamily(
            model_type="janus",
            config_cls=janus_mod.JanusConfig,
            init_params=janus_mod.init_params,
            abstract_params=janus_mod.abstract_params,
            loss_fn=janus_mod.loss_fn,
            forward_logits=None,
            hf_to_params=janus_mod.hf_to_params,
            save_hf_checkpoint=janus_mod.save_hf_checkpoint,
            parallel_plan_fn=_janus_plan,
        ),
    )

    # qwen2_5_omni thinker: real audio tower + qwen2_5_vl vision/LM
    from veomni_tpu.models import qwen2_5_omni as q25o

    MODEL_REGISTRY.register(
        "qwen2_5_omni",
        ModelFamily(
            model_type="qwen2_5_omni",
            config_cls=q25o.Qwen25OmniConfig,
            init_params=q25o.init_params,
            abstract_params=q25o.abstract_params,
            loss_fn=q25o.loss_fn,
            forward_logits=None,
            hf_to_params=q25o.hf_to_params,
            save_hf_checkpoint=q25o.save_hf_checkpoint,
            parallel_plan_fn=q25o.parallel_plan,
        ),
    )

    # qwen3_omni_moe thinker: AuT audio + qwen3_vl vision + MoE LM
    from veomni_tpu.models import qwen3_omni_moe as q3o

    MODEL_REGISTRY.register(
        "qwen3_omni_moe",
        ModelFamily(
            model_type="qwen3_omni_moe",
            config_cls=q3o.Qwen3OmniMoeConfig,
            init_params=q3o.init_params,
            abstract_params=q3o.abstract_params,
            loss_fn=q3o.loss_fn,
            forward_logits=None,
            hf_to_params=q3o.hf_to_params,
            save_hf_checkpoint=q3o.save_hf_checkpoint,
            parallel_plan_fn=q3o.parallel_plan,
        ),
    )


def _register_diffusion_families():
    from veomni_tpu.models import (
        flux as flux_mod,
        ltx2 as ltx2_mod,
        qwen_image as qi_mod,
        wan as wan_mod,
    )

    for mt, mod, cfg_cls in (
        ("wan_t2v", wan_mod, wan_mod.WanConfig),
        ("qwen_image", qi_mod, qi_mod.QwenImageConfig),
        ("flux", flux_mod, flux_mod.FluxConfig),
        ("ltx2", ltx2_mod, ltx2_mod.LTX2Config),
    ):
        MODEL_REGISTRY.register(
            mt,
            ModelFamily(
                model_type=mt,
                config_cls=cfg_cls,
                init_params=mod.init_params,
                abstract_params=mod.abstract_params,
                loss_fn=mod.loss_fn,
                forward_logits=None,
                hf_to_params=mod.hf_to_params,
                save_hf_checkpoint=mod.save_hf_checkpoint,
            ),
        )


_register_vlm_families()
_register_diffusion_families()

VLM_MODEL_TYPES = ("slot_vlm", "qwen2_vl", "qwen2_5_vl", "qwen3_vl", "qwen3_vl_moe")


def build_config(model_type: str = "", **overrides):
    """Construct the right config class for a model_type (VLM vs text).

    For VLM types, top-level non-VLM keys (dtype, remat, ...) flow into the
    nested text config so the same override surface works for both.
    """
    overrides.pop("model_type", None)
    if model_type == "janus":
        from veomni_tpu.models.janus import JanusConfig

        kw = {
            k: overrides.pop(k)
            for k in ("vision", "gen_vision", "aligner_depth",
                      "gen_aligner_depth", "gen_head_embed", "image_token_id",
                      "image_gen_token_id", "gen_loss_weight", "freeze_vision",
                      "freeze_gen_vision", "max_images", "max_gen_images")
            if k in overrides
        }
        text = dict(overrides.pop("text", {}) or {})
        text.update(overrides)
        text.setdefault("model_type", "llama")
        return JanusConfig(text=text, **kw)
    if model_type == "deepseek_v4":
        from veomni_tpu.models.deepseek_v4 import DeepseekV4Config

        return DeepseekV4Config(**overrides)
    if model_type in ("qwen2_vl", "qwen2_5_vl", "qwen3_vl", "qwen3_vl_moe"):
        if model_type == "qwen2_vl":
            from veomni_tpu.models.qwen2_vl import Qwen2VLConfig as vl_cfg

            text_mt = "qwen2"
        elif model_type == "qwen2_5_vl":
            from veomni_tpu.models.qwen2_5_vl import Qwen25VLConfig as vl_cfg

            text_mt = "qwen2"
        else:
            from veomni_tpu.models.qwen3_vl import Qwen3VLConfig as vl_cfg

            text_mt = "qwen3_moe" if model_type == "qwen3_vl_moe" else "qwen3"
        kw = {
            k: overrides.pop(k)
            for k in ("vision", "image_token_id", "video_token_id",
                      "vision_start_token_id", "freeze_vision")
            if k in overrides
        }
        text = dict(overrides.pop("text", {}) or {})
        text.update(overrides)
        text.setdefault("model_type", text_mt)
        if model_type.startswith("qwen3_vl") and text.get("rope_scaling"):
            # qwen3-vl mrope is interleaved — keep both config paths
            # (build_config and config_from_hf) on the same rope layout
            rs = dict(text["rope_scaling"])
            rs.setdefault("mrope_interleaved", True)
            text["rope_scaling"] = rs
        if model_type == "qwen3_vl_moe":
            text.setdefault("expert_layout", "fused_chunked")
            kw["model_type"] = model_type
        return vl_cfg(text=text, **kw)
    if model_type == "qwen2_5_omni":
        from veomni_tpu.models.qwen2_5_omni import Qwen25OmniConfig

        kw = {
            k: overrides.pop(k)
            for k in ("vision", "audio", "image_token_id", "video_token_id",
                      "audio_token_id", "vision_start_token_id",
                      "audio_start_token_id", "audio_end_token_id",
                      "position_id_per_seconds", "freeze_vision",
                      "freeze_audio")
            if k in overrides
        }
        text = dict(overrides.pop("text", {}) or {})
        text.update(overrides)
        text.setdefault("model_type", "qwen2")
        return Qwen25OmniConfig(text=text, **kw)
    if model_type == "qwen3_omni_moe":
        from veomni_tpu.models.qwen3_omni_moe import Qwen3OmniMoeConfig

        kw = {
            k: overrides.pop(k)
            for k in ("vision", "audio", "image_token_id", "video_token_id",
                      "audio_token_id", "vision_start_token_id",
                      "audio_start_token_id", "position_id_per_seconds",
                      "freeze_vision", "freeze_audio")
            if k in overrides
        }
        text = dict(overrides.pop("text", {}) or {})
        text.update(overrides)
        text.setdefault("model_type", "qwen3_moe")
        if text.get("rope_scaling"):
            rs = dict(text["rope_scaling"])
            rs.setdefault("mrope_interleaved", True)
            text["rope_scaling"] = rs
        return Qwen3OmniMoeConfig(text=text, **kw)
    if model_type in VLM_MODEL_TYPES:
        from veomni_tpu.models.vlm import VLMConfig

        vlm_kw = {
            k: overrides.pop(k)
            for k in ("vision", "image_token_id", "freeze_vision")
            if k in overrides
        }
        text = dict(overrides.pop("text", {}) or {})
        text.update(overrides)
        return VLMConfig(model_type=model_type, text=text, **vlm_kw)
    if model_type == "granitemoehybrid":
        # config.json's spellings (embedding_multiplier, ...) are taken too
        for theirs, ours in TransformerConfig._GRANITE_HYBRID_RENAMED.items():
            if theirs in overrides:
                overrides[ours] = overrides.pop(theirs)
    if model_type == "kimi_linear":
        # config.json's spellings (num_experts_per_token, ...) are taken too
        theirs = (*TransformerConfig._KIMI_LINEAR_RENAMED, "use_grouped_topk")
        overrides = {**TransformerConfig.kimi_linear_fields(overrides),
                     **{k: v for k, v in overrides.items() if k not in theirs}}
    if model_type in TransformerConfig._DEEPSEEK_V3_DIALECT:
        for key, value in TransformerConfig.deepseek_defaults(model_type).items():
            overrides.setdefault(key, value)
    return TransformerConfig(model_type=model_type or "llama", **overrides)


@dataclass
class FoundationModel:
    """What build_foundation_model returns: config + family + (lazy) params."""

    config: TransformerConfig
    family: ModelFamily
    params: Optional[Any] = None

    def init(self, rng: jax.Array):
        self.params = self.family.init_params(rng, self.config)
        return self.params

    def abstract(self):
        return self.family.abstract_params(self.config)

    def loss_fn(self, params, batch):
        return self.family.loss_fn(params, self.config, batch)

    def get_parallel_plan(self) -> ParallelPlan:
        return self.family.get_parallel_plan(self.config)

    def load_hf(self, model_dir: str, target_shardings=None):
        if self.family.hf_to_params is None:
            raise NotImplementedError(
                f"HF checkpoint import not wired for {self.family.model_type}; "
                "load the native safetensors export instead"
            )
        self.params = self.family.hf_to_params(model_dir, self.config, target_shardings)
        return self.params

    def save_hf(self, out_dir: str, params=None):
        self.family.save_hf_checkpoint(
            params if params is not None else self.params, self.config, out_dir
        )


def build_foundation_model(
    config_path: Optional[str] = None,
    *,
    config: Optional[TransformerConfig] = None,
    weights_path: Optional[str] = None,
    ops_implementation: Optional[Dict[str, str]] = None,
    **config_overrides,
) -> FoundationModel:
    """Reference ``build_foundation_model`` (models/auto.py:110): resolve
    config -> bind ops -> construct (weights load deferred to the
    parallelized build so tensors land shard-aligned)."""
    from veomni_tpu.ops.kernel_registry import apply_ops_config

    if config is None:
        if config_path is None:
            raise ValueError("need config_path or config")
        import json as _json
        import os as _os

        with open(_os.path.join(config_path, "config.json")) as f:
            hf_dict = _json.load(f)
        if hf_dict.get("model_type") == "deepseek_v4":
            from veomni_tpu.models.deepseek_v4 import config_from_hf as dsv4_from_hf

            config = dsv4_from_hf(hf_dict, **config_overrides)
        elif hf_dict.get("model_type") == "qwen2_vl":
            from veomni_tpu.models.qwen2_vl import config_from_hf as q2vl_from_hf

            config = q2vl_from_hf(hf_dict, **config_overrides)
        elif hf_dict.get("model_type") == "qwen2_5_vl":
            from veomni_tpu.models.qwen2_5_vl import config_from_hf

            config = config_from_hf(hf_dict, **config_overrides)
        elif hf_dict.get("model_type") in ("qwen3_vl", "qwen3_vl_moe"):
            from veomni_tpu.models.qwen3_vl import config_from_hf as q3vl_from_hf

            config = q3vl_from_hf(hf_dict, **config_overrides)
        elif hf_dict.get("model_type") == "janus":
            from veomni_tpu.models.janus import config_from_hf as janus_from_hf

            config = janus_from_hf(hf_dict, **config_overrides)
        elif hf_dict.get("model_type") in ("qwen2_5_omni", "qwen2_5_omni_thinker"):
            from veomni_tpu.models.qwen2_5_omni import config_from_hf as omni_from_hf

            config = omni_from_hf(hf_dict, **config_overrides)
        elif hf_dict.get("model_type") in ("qwen3_omni_moe", "qwen3_omni_moe_thinker"):
            from veomni_tpu.models.qwen3_omni_moe import config_from_hf as q3o_from_hf

            config = q3o_from_hf(hf_dict, **config_overrides)
        elif (hf_dict.get("model_type") == "wan_t2v"
              or hf_dict.get("_class_name") == "WanTransformer3DModel"):
            from veomni_tpu.models.wan import config_from_hf as wan_from_hf

            config = wan_from_hf(hf_dict, **config_overrides)
        elif (hf_dict.get("model_type") == "qwen_image"
              or hf_dict.get("_class_name") == "QwenImageTransformer2DModel"):
            from veomni_tpu.models.qwen_image import config_from_hf as qi_from_hf

            config = qi_from_hf(hf_dict, **config_overrides)
        elif (hf_dict.get("model_type") == "flux"
              or hf_dict.get("_class_name") == "FluxTransformer2DModel"):
            from veomni_tpu.models.flux import config_from_hf as flux_from_hf

            config = flux_from_hf(hf_dict, **config_overrides)
        elif (hf_dict.get("model_type") == "ltx2"
              or hf_dict.get("_class_name") == "LTXVideoTransformerModel"):
            from veomni_tpu.models.ltx2 import config_from_hf as ltx2_from_hf

            config = ltx2_from_hf(hf_dict, **config_overrides)
        else:
            config = TransformerConfig.from_hf_config(hf_dict, **config_overrides)
    if config.model_type not in MODEL_REGISTRY:
        logger.warning_rank0(
            "model_type %r not registered; using llama-family core", config.model_type
        )
    family = (
        MODEL_REGISTRY.get(config.model_type)
        if config.model_type in MODEL_REGISTRY
        else ModelFamily(model_type=config.model_type)
    )
    apply_ops_config(ops_implementation)
    model = FoundationModel(config=config, family=family)
    if weights_path:
        model.load_hf(weights_path)
    return model


def build_tokenizer(path: str):
    """HF tokenizer passthrough (reference models/auto.py:41).

    Local checkpoint dirs live on shared filesystems whose reads fail
    transiently — those retry with the same bounded deterministic backoff as
    the other I/O edges (resilience/retry.py). Hub-id loads do NOT retry:
    transformers raises plain OSError for PERMANENT errors too (unknown
    model id, gated repo), and retrying those burns round-trips while
    masking the real message."""
    from transformers import AutoTokenizer

    if os.path.isdir(path):
        from veomni_tpu.resilience.retry import retry_call

        return retry_call(
            AutoTokenizer.from_pretrained, path, trust_remote_code=True,
            description=f"tokenizer load {path}",
        )
    return AutoTokenizer.from_pretrained(path, trust_remote_code=True)
