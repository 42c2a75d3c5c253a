"""Reference-schema YAML compatibility (VERDICT r4 #6: a VeOmni recipe drops
in). Reference: ``veomni/arguments/arguments_types.py:465-526,1440``."""

import glob
import os

import pytest

from veomni_tpu.arguments import VeOmniArguments, parse_args

REFERENCE_YAML = """
model:
  model_path: Some-Model-Base
  ops_implementation:
    attn_implementation: flash_attention_2
    cross_entropy_loss_implementation: chunk_loss
    rms_norm_implementation: eager
  lora_config:
    rank: 64
    alpha: 32
    lora_modules: [q_proj, v_proj]
data:
  train_path: corpus
  data_type: conversation
  max_seq_len: 2048
  train_size: 750000
  datasets_type: iterable
  dataloader:
    type: native
    drop_last: true
train:
  accelerator:
    ulysses_size: 2
    ep_size: 4
    dp_shard_size: 8
    fsdp_config:
      fsdp_mode: fsdp2
      reshard_after_forward: true
      mixed_precision:
        enable: true
        param_dtype: bfloat16
    offload_config:
      enable_activation: true
  gradient_checkpointing:
    enable: true
    enable_reentrant: false
  global_batch_size: 64
  micro_batch_size: 1
  max_steps: 500
  dyn_bsz: true
  freeze_vit: true
  vit_lr: 1.0e-5
  bsz_warmup_ratio: 0.007
  init_device: meta
  empty_cache_steps: 500
  optimizer:
    type: adamw
    lr: 1.0e-4
    lr_decay_style: cosine
    lr_warmup_ratio: 0.01
    weight_decay: 0.1
    max_grad_norm: 1.0
  checkpoint:
    output_dir: run_out
    manager: dcp
    save_steps: 100
    save_hf_weights: true
  wandb:
    enable: true
    project: VeOmni
    name: my_run
  profile:
    enable: true
    start_step: 3
    end_step: 5
    record_shapes: true
dpo_config:
  beta: 0.25
  loss_type: sigmoid
"""


def test_reference_recipe_translates(tmp_path):
    p = tmp_path / "ref.yaml"
    p.write_text(REFERENCE_YAML)
    a = parse_args(VeOmniArguments, [str(p)])

    # accelerator block -> flat parallel sizes
    assert a.train.ulysses_parallel_size == 2
    assert a.train.expert_parallel_size == 4
    assert a.train.data_parallel_shard_size == 8
    assert a.train.data_parallel_mode == "fsdp"
    # mixed precision / offload / gradient checkpointing
    assert a.train.bf16 is True
    assert a.train.gradient_checkpointing_policy == "offload"
    assert a.train.enable_gradient_checkpointing is True
    # optimizer flatten
    assert a.train.optimizer == "adamw"
    assert a.train.lr == pytest.approx(1e-4)
    assert a.train.lr_decay_style == "cosine"
    assert a.train.weight_decay == pytest.approx(0.1)
    # checkpoint block (dcp -> orbax)
    assert a.train.output_dir == "run_out"
    assert a.train.ckpt_manager == "orbax"
    assert a.train.save_steps == 100
    # wandb / profile
    assert a.train.use_wandb is True and a.train.wandb_project == "VeOmni"
    assert a.train.enable_profiling is True and a.train.profile_end_step == 5
    # cross-section moves
    assert a.train.train_steps == 500
    assert a.data.dyn_bsz is True
    assert "^vision_tower" in a.model.freeze_modules
    assert a.train.module_lr_scales["^vision_tower"] == pytest.approx(0.1)
    # lora_config + ops impls
    assert a.model.lora["rank"] == 64 and a.model.lora["alpha"] == 32
    assert a.model.attn_implementation == "auto"
    assert a.model.ops_implementation == {
        "fused_linear_cross_entropy": "xla_chunked",
        "rms_norm": "xla",
        "qk_norm_rotary": "xla",  # the eager norm covers the op that joins it to rope
    }
    # data block
    assert a.data.dataset_type == "iterable"
    assert a.data.dataloader_type == "native"
    # top-level dpo_config
    assert a.train.dpo_beta == pytest.approx(0.25)


def test_native_schema_keeps_typo_safety(tmp_path):
    p = tmp_path / "native.yaml"
    p.write_text("train:\n  learning_rate: 1e-4\n")  # typo for lr
    with pytest.raises(AttributeError, match="learning_rate"):
        parse_args(VeOmniArguments, [str(p)])


def test_native_flat_keys_survive_translator(tmp_path):
    """A native scalar that collides with a reference block name (optimizer)
    must pass through untouched."""
    p = tmp_path / "native.yaml"
    p.write_text("train:\n  optimizer: muon\n  lr: 3.0e-4\n")
    a = parse_args(VeOmniArguments, [str(p)])
    assert a.train.optimizer == "muon"
    assert a.train.lr == pytest.approx(3e-4)


def test_native_ops_implementation_not_translated(tmp_path):
    p = tmp_path / "native.yaml"
    p.write_text(
        "model:\n  ops_implementation:\n    rms_norm: xla\n"
    )
    a = parse_args(VeOmniArguments, [str(p)])
    assert a.model.ops_implementation == {"rms_norm": "xla"}


@pytest.mark.skipif(
    not os.path.isdir("/root/reference/configs"),
    reason="reference recipes not present",
)
def test_all_reference_recipes_parse():
    paths = sorted(
        glob.glob("/root/reference/configs/**/*.yaml", recursive=True)
    )
    assert len(paths) >= 30
    for p in paths:
        parse_args(VeOmniArguments, [p])  # must not raise
