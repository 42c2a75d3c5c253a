"""BaseTrainer: builds the full training stack and runs the loop.

Reference: ``veomni/trainer/base.py:233-893``. Build sequence mirrors
``__init__:299-343`` (setup -> model -> data -> parallelize -> optimizer ->
callbacks); the hot loop (train_step w/ grad accum, clip, optimizer) is one
jit program (see train/train_step.py). Trainer-free usage stays first-class:
every ``_build_*`` piece is a plain function call (cf. the reference's linear
``tasks/omni/train_omni_model.py``).
"""

from __future__ import annotations

import os
from typing import Any, Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from veomni_tpu.arguments import VeOmniArguments
from veomni_tpu.checkpoint import build_checkpointer
from veomni_tpu.data.data_collator import TextPackingCollator
from veomni_tpu.data.data_loader import build_dataloader
from veomni_tpu.data.data_transform import build_data_transform
from veomni_tpu.data.dataset import build_dataset
from veomni_tpu.models import build_foundation_model, build_tokenizer
from veomni_tpu.observability.flight_recorder import (
    configure_flight_recorder,
    dump_postmortem,
    record as flight_record,
)
from veomni_tpu.observability.metrics import get_registry
from veomni_tpu.observability.spans import enable_spans, span
from veomni_tpu.optim import build_lr_scheduler, build_optimizer
from veomni_tpu.parallel import init_parallel_state, use_parallel_state
from veomni_tpu.train import build_train_state, build_train_step
from veomni_tpu.train.train_step import resolve_state_shardings
from veomni_tpu.trainer.callbacks import (
    Callback,
    CheckpointCallback,
    EnvironMeterCallback,
    HFCheckpointCallback,
    LoggingCallback,
    ProfileCallback,
    TrainerControlState,
    WandbCallback,
)
from veomni_tpu.utils.count_flops import FlopsCounter
from veomni_tpu.utils.helper import EnvironMeter, set_seed
from veomni_tpu.utils.logging import get_logger

logger = get_logger(__name__)

BATCH_KEYS = ("input_ids", "labels", "position_ids", "segment_ids")


def maybe_initialize_distributed() -> None:
    """Join the cluster when launcher env vars say so (reference
    ``dist.init_process_group``, trainer/base.py:355-356; here
    ``jax.distributed.initialize`` — ICI/DCN wiring is the runtime's job).

    Explicit: VEOMNI_COORDINATOR_ADDRESS + VEOMNI_NUM_PROCESSES +
    VEOMNI_PROCESS_ID (works on any backend incl. multi-process CPU tests).
    Auto: VEOMNI_AUTO_DISTRIBUTED=1 calls bare initialize() for platforms
    with cluster auto-detection (TPU pods, SLURM, GKE).

    Must run BEFORE the first backend touch; no-op if already initialized.
    """
    try:
        if jax.distributed.global_state.client is not None:
            return
    except AttributeError:
        pass
    coord = os.environ.get("VEOMNI_COORDINATOR_ADDRESS")
    if coord:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=int(os.environ["VEOMNI_NUM_PROCESSES"]),
            process_id=int(os.environ["VEOMNI_PROCESS_ID"]),
        )
        logger.info_rank0(
            "jax.distributed initialized: %d processes", jax.process_count()
        )
    elif os.environ.get("VEOMNI_AUTO_DISTRIBUTED") == "1":
        jax.distributed.initialize()
        logger.info_rank0(
            "jax.distributed auto-initialized: %d processes", jax.process_count()
        )


def _seconds_since_process_start() -> Optional[float]:
    """Wall seconds this process has lived, from one read of its
    ``/proc/self/stat`` start tick against ``/proc/uptime``; None where
    there is no such file."""
    try:
        with open("/proc/self/stat") as f:
            # the command name (field 2) may hold spaces: count from its ")"
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


class BaseTrainer:
    def __init__(self, args: VeOmniArguments):
        self.args = args
        self.current_batch: Optional[Dict[str, np.ndarray]] = None
        self.meter: Optional[EnvironMeter] = None
        # spans from the first line on, so the program's own set-up is
        # inside its own tracing (setup.build > setup.data, setup.state)
        if args.train.observability_spans:
            enable_spans()
        # what the process spent before any trainer existed: the
        # interpreter, every import, whatever the entry point did first
        launched = _seconds_since_process_start()
        if launched is not None:
            get_registry().gauge("setup.launch_to_trainer_s").set(launched)
        with span("setup.build"):
            self._setup()
            with use_parallel_state(self.parallel_state):
                self._build_model()
                self._flash_tile_counters = self._build_flash_tile_counters()
                self._scan_chunk_counters = self._build_scan_chunk_counters()
                with span("setup.data"):
                    self._build_data_transform()
                    self._build_dataset()
                    self._build_dataloader()
                with span("setup.state"):
                    self._build_parallelized_state()
                self._init_callbacks()

    # ------------------------------------------------------------------ setup
    def _setup(self):
        t = self.args.train
        if t.num_virtual_devices and not t.platform:
            logger.warning_rank0(
                "train.num_virtual_devices is ignored without train.platform "
                "(set platform: cpu for virtual-mesh simulation)"
            )
        if t.platform:
            # must run before first backend use
            updates = [("jax_platforms", t.platform)]
            if t.num_virtual_devices:
                updates.append(("jax_num_cpu_devices", t.num_virtual_devices))
            if t.platform == "cpu":
                # many virtual devices on few cores: in-flight executions can
                # starve the collective rendezvous of pool threads (deadlock)
                updates.append(("jax_cpu_enable_async_dispatch", False))
            for key, val in updates:
                try:
                    jax.config.update(key, val)
                except RuntimeError as e:
                    logger.warning_rank0(
                        "could not apply %s=%r (backends already initialized?): %s",
                        key, val, e,
                    )
        maybe_initialize_distributed()
        self.rng = set_seed(t.seed)
        dp_replicate = t.data_parallel_replicate_size
        dp_shard = t.data_parallel_shard_size
        if t.data_parallel_mode == "ddp":
            # all non-sp/tp devices replicate; nothing is FSDP-sharded
            dp_replicate, dp_shard = -1, 1
        elif dp_replicate < 1:
            # fsdp mode: the shard extent is what's inferred; replicate
            # (HSDP) must be explicit, so -1/0 normalizes to "no replication"
            dp_replicate = 1
        self.parallel_state = init_parallel_state(
            dp_replicate_size=dp_replicate,
            dp_shard_size=dp_shard,
            ep_size=t.expert_parallel_size,
            ulysses_size=t.ulysses_parallel_size,
            cp_size=t.context_parallel_size,
            tp_size=t.tensor_parallel_size,
            pp_size=t.pipeline_parallel_size,
        )
        os.makedirs(t.output_dir, exist_ok=True)
        if jax.process_index() == 0:
            from veomni_tpu.arguments import save_args

            save_args(self.args, t.output_dir)

    def _build_model(self):
        m = self.args.model
        overrides = dict(m.config_overrides)
        overrides.setdefault("dtype", self.args.train.compute_dtype)
        overrides.setdefault("param_dtype", self.args.train.param_dtype)
        overrides["remat"] = self.args.train.enable_gradient_checkpointing
        overrides.setdefault("remat_policy", self.args.train.gradient_checkpointing_policy)
        if self.args.train.chunk_mbs:
            overrides.setdefault("chunk_mbs", self.args.train.chunk_mbs)
        if m.model_type:
            overrides["model_type"] = m.model_type
        ops_pins = dict(m.ops_implementation)
        if m.attn_implementation not in ("auto", ""):
            ops_pins["attention"] = m.attn_implementation
        if m.moe_implementation not in ("auto", ""):
            ops_pins["group_gemm"] = m.moe_implementation
        if self.args.train.ulysses_async:
            # chunked a2a/compute overlap pipeline for the Ulysses SP wrap
            ops_pins.setdefault("ulysses", "ulysses_async")
            overrides.setdefault(
                "ulysses_async_chunks", self.args.train.ulysses_async_chunks
            )
        self.model = build_foundation_model(
            m.config_path or None,
            config=None if m.config_path else self._toy_config(overrides),
            ops_implementation=ops_pins,
            **(overrides if m.config_path else {}),
        )
        # pretokenized data needs no tokenizer; don't fail on weights-only dirs
        needs_tokenizer = self.args.data.data_type not in ("pretokenized",)
        self.tokenizer = None
        if m.tokenizer_path and needs_tokenizer:
            self.tokenizer = build_tokenizer(m.tokenizer_path)

    def _build_flash_tile_counters(self):
        """``attn.flash.tile_pairs[_live]`` and their ratio: how many of the
        flash kernel's (q-tile, kv-tile) pairs the packing leaves alive,
        counted once a step ON THE HOST from the host batch's segment ids by
        the functions the kernel wrapper calls on the device (no sync, no
        callback in the jitted step). None unless attention resolves to
        ``pallas_flash`` in a causal text decoder (ring CP hands the kernel
        chunks the host does not see)."""
        from veomni_tpu.models.config import TransformerConfig
        from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY
        from veomni_tpu.ops.pallas.flash_attention import tile_census

        cfg = getattr(self.model, "config", None)
        if (KERNEL_REGISTRY.resolved_name("attention") != "pallas_flash"
                or not isinstance(cfg, TransformerConfig)
                or self.parallel_state.cp_size > 1):
            return None
        # MLA's q and k are qk_head_dim wide and its v v_head_dim: the
        # config's head_dim is not a width of its attention
        head_dim, v_dim = (
            (cfg.qk_head_dim, cfg.v_head_dim) if cfg.use_mla else (cfg.head_dim, None))
        dtype = cfg.dtype
        reg = get_registry()
        pairs, live = reg.counter("attn.flash.tile_pairs"), reg.counter("attn.flash.tile_pairs_live")
        share = reg.gauge("attn.flash.tiles_live_share")

        def count(batch_np):
            seg = batch_np.get("segment_ids")
            if seg is None:
                return
            n, n_live = tile_census(seg, head_dim, dtype, v_head_dim=v_dim)
            if n:
                pairs.inc(n)
                live.inc(n_live)
                share.set(live.value / pairs.value)

        return count

    def _build_scan_chunk_counters(self):
        """``<scan>.chunks[_with_reset]`` and their ratio ``<scan>.
        reset_chunk_share`` for a model with recurrent layers (``ssm.scan``:
        the Mamba-2 layers' state-space scan; ``kda.scan``: the Kimi Delta
        Attention layers' recurrence): how many of the scan's chunks hold a
        document's start (a reset inside the chunk, which a kernel has to mask
        and cannot skip), counted once a step ON THE HOST from the host
        batch's segment ids, as the flash kernel's tiles are. None for a model
        without such layers."""
        from veomni_tpu.ops import kda
        from veomni_tpu.ops.ssd_scan import chunk_census

        cfg = getattr(self.model, "config", None)
        if getattr(cfg, "mamba_n_heads", 0):
            name, chunk = "ssm.scan", cfg.mamba_chunk_size
            layers = list(getattr(cfg, "layer_types", None) or ()).count("mamba")
        elif getattr(cfg, "linear_attn_config", None):
            name, chunk = "kda.scan", kda.CHUNK
            layers = len(cfg.linear_attn_config.get("kda_layers", ()))
        else:
            return None
        if not layers:
            return None
        reg = get_registry()
        chunks, resets = reg.counter(f"{name}.chunks"), reg.counter(f"{name}.chunks_with_reset")
        share = reg.gauge(f"{name}.reset_chunk_share")

        def count(batch_np):
            seg = batch_np.get("segment_ids")
            if seg is None:
                return
            n, n_reset = chunk_census(seg, chunk)
            chunks.inc(n * layers)
            resets.inc(n_reset * layers)
            share.set(resets.value / chunks.value)

        return count

    def _toy_config(self, overrides):
        from veomni_tpu.models.auto import build_config

        return build_config(overrides.get("model_type", ""), **{
            k: v for k, v in overrides.items() if k != "model_type"
        })

    def _build_data_transform(self):
        d = self.args.data
        self.data_transform = build_data_transform(
            d.data_type, tokenizer=self.tokenizer,
            text_keys=d.text_keys, max_seq_len=d.max_seq_len,
            channel_list=d.channel_list, chat_template=d.chat_template,
        )

    def _build_dataset(self):
        d = self.args.data
        kwargs = {}
        if d.dataset_type == "streaming":
            # poison-record skip budget (resilience/integrity.py): bounded
            # tolerance for undecodable shard records, replayed bit-exactly
            # across resume via the rank-local cursor state
            kwargs["skip_budget"] = self.args.train.data_skip_budget
        self.dataset = build_dataset(
            d.dataset_type, path=d.train_path, transform=self.data_transform,
            **kwargs,
        )

    def _build_dataloader(self):
        t, d = self.args.train, self.args.data
        ps = self.parallel_state
        self.grad_accum_steps = self.args.compute_grad_accum(ps.dp_size)
        # each process assembles only its slice of the global batch; the jit
        # boundary stitches slices into the globally-sharded array
        nproc = jax.process_count()
        global_mb = t.micro_batch_size * ps.dp_size
        if global_mb % nproc:
            raise ValueError(
                f"global micro batch {global_mb} not divisible by process count {nproc}"
            )
        local_mb = global_mb // nproc
        collator = TextPackingCollator(
            seq_len=d.max_seq_len,
            micro_batch_size=local_mb,
            sp_size=ps.sp_size,
            with_channels=bool(d.channel_list),
        )
        if d.dyn_bsz:
            from veomni_tpu.data.dynamic_batching import DynamicBatchDataloader

            self.dataloader = DynamicBatchDataloader(
                self.dataset,
                collator,
                token_budget=local_mb * d.max_seq_len,
                grad_accum_steps=self.grad_accum_steps,
                buffer_size=d.dyn_bsz_buffer_size,
                seed=t.seed,
                dp_rank=jax.process_index(),
                dp_size=nproc,
            )
        else:
            self.dataloader = build_dataloader(
                d.dataloader_type,
                dataset=self.dataset,
                collate_fn=collator,
                micro_batch_size=local_mb,
                grad_accum_steps=self.grad_accum_steps,
                samples_per_micro_batch=max(1, d.samples_per_micro_batch * local_mb),
                seed=t.seed,
                dp_rank=jax.process_index(),
                dp_size=nproc,
                drop_last=d.drop_last,
                infinite=True,
            )

    def _build_parallelized_state(self):
        """Reference ``build_parallelize_model`` (torch_parallelize.py:546):
        here = resolve plan -> shard-aligned init or HF load -> optimizer."""
        t = self.args.train
        ps = self.parallel_state
        model = self.model
        plan = model.get_parallel_plan()

        steps = t.train_steps or max(1, len(self.dataloader) * t.num_train_epochs)
        self.train_steps = steps
        self.lr_schedule = build_lr_scheduler(
            t.lr_decay_style, lr=t.lr, train_steps=steps,
            lr_warmup_ratio=t.lr_warmup_ratio, lr_min=t.lr_min,
        )
        def _make_optimizer(abstract_trainable):
            tx = build_optimizer(
                abstract_trainable, optimizer=t.optimizer, lr=self.lr_schedule,
                betas=tuple(t.betas), weight_decay=t.weight_decay,
            )
            if self.args.model.freeze_modules or t.module_lr_scales:
                from veomni_tpu.optim.optimizer import with_param_groups

                tx = with_param_groups(
                    tx, abstract_trainable,
                    freeze_patterns=tuple(self.args.model.freeze_modules),
                    lr_scales=dict(t.module_lr_scales),
                )
            return tx

        from veomni_tpu.lora import LoraConfig
        from veomni_tpu.train.train_step import TrainState

        self.lora_config = LoraConfig.from_dict(self.args.model.lora)

        def make_base(rng):
            return model.family.init_params(rng, model.config)

        param_shardings = resolve_state_shardings(
            jax.eval_shape(make_base, self.rng), plan, ps
        )
        if self.args.model.model_path:
            # env var is the transport into the family loaders; scoped so a
            # later load_hf in this process doesn't inherit the choice
            prev = os.environ.get("VEOMNI_WEIGHTS_BROADCAST")
            if t.broadcast_weights_from_rank0:
                os.environ["VEOMNI_WEIGHTS_BROADCAST"] = "1"
            try:
                base_params = model.load_hf(
                    self.args.model.model_path, target_shardings=param_shardings
                )
            finally:
                if t.broadcast_weights_from_rank0:
                    if prev is None:
                        os.environ.pop("VEOMNI_WEIGHTS_BROADCAST", None)
                    else:
                        os.environ["VEOMNI_WEIGHTS_BROADCAST"] = prev
        else:
            base_params = jax.jit(make_base, out_shardings=param_shardings)(self.rng)

        if self.lora_config is not None:
            # frozen base + trainable adapter tree (reference base.py:411-462)
            from veomni_tpu.lora import (
                apply_lora_to_loss_fn,
                init_lora_params,
                merge_lora_params,
            )
            from veomni_tpu.lora.lora import load_adapter, lora_parallel_plan_rules
            from veomni_tpu.parallel.parallel_plan import ParallelPlan

            self.base_params = base_params
            lora = init_lora_params(self.rng, base_params, self.lora_config)
            if self.args.model.lora_adapter_path:
                lora = load_adapter(self.args.model.lora_adapter_path, lora)
            self.optimizer = _make_optimizer(jax.eval_shape(lambda: lora))
            plan = plan.merge(ParallelPlan(rules=lora_parallel_plan_rules()))
            abs_state = jax.eval_shape(lambda l: build_train_state(l, self.optimizer), lora)
            self.state_shardings = resolve_state_shardings(abs_state, plan, ps)
            self.abstract_state = abs_state
            lora = jax.jit(lambda l: l, out_shardings=self.state_shardings.params)(lora)
            self.train_state = TrainState(
                params=lora, opt_state=self.optimizer.init(lora),
                # committed to the declared sharding: an uncommitted scalar
                # has a different jit type signature than the step outputs,
                # forcing a retrace (and a stale-executable buffer mismatch
                # on XLA:CPU) at step 2+
                step=jax.device_put(jnp.int32(0), self.state_shardings.step),
            )
            loss_fn = apply_lora_to_loss_fn(self._inner_loss_fn(model), base_params)
            # subclass losses (DPO/RL) call this to turn whatever tree the
            # train step optimizes into full model params (jit-traceable)
            self.merge_params = lambda p: merge_lora_params(base_params, p)
        else:
            self.base_params = None
            self.merge_params = lambda p: p
            self.optimizer = _make_optimizer(jax.eval_shape(lambda: base_params))
            abs_state = jax.eval_shape(
                lambda p: build_train_state(p, self.optimizer), base_params
            )
            self.state_shardings = resolve_state_shardings(abs_state, plan, ps)
            self.abstract_state = abs_state
            opt_state = jax.jit(
                self.optimizer.init, out_shardings=self.state_shardings.opt_state
            )(base_params)
            self.train_state = TrainState(
                params=base_params, opt_state=opt_state,
                # committed: see the LoRA branch note on jit signature drift
                step=jax.device_put(jnp.int32(0), self.state_shardings.step),
            )
            loss_fn = self._inner_loss_fn(model)

        self.batch_shardings = {
            k: NamedSharding(ps.mesh, spec)
            for k, spec in self._batch_sharding_map().items()
        }
        grad_mask = None
        if self.args.model.freeze_modules:
            import re

            from veomni_tpu.parallel.parallel_plan import param_path_str

            patterns = tuple(self.args.model.freeze_modules)
            grad_mask = jax.tree_util.tree_map_with_path(
                lambda p, leaf: (
                    0.0 if any(re.search(pt, param_path_str(p)) for pt in patterns)
                    else 1.0
                ),
                self.abstract_state.params,
            )
        self.grad_mask = grad_mask  # subclass train_step rebuilds reuse it
        self.train_step = build_train_step(
            loss_fn, self.optimizer, ps,
            state_shardings=self.state_shardings,
            batch_shardings=self.batch_shardings,
            max_grad_norm=t.max_grad_norm,
            grad_mask=grad_mask,
            skip_nonfinite=t.resilience_skip_nonfinite,
        )
        self._loss_fn = loss_fn  # forward-only reuse (evaluate)
        # numerics observatory (observability/numerics.py): the instrumented
        # sibling step is built lazily on first use — with the interval knob
        # off it is never constructed, never compiled, never traced
        self._numerics_step = None
        self._numerics = None
        self.meter = EnvironMeter(
            flops_counter=FlopsCounter.from_config(model.config),
            world_size=ps.world_size,
        )
        self.checkpointer = build_checkpointer(
            t.load_checkpoint_path or os.path.join(t.output_dir, "checkpoints"),
            ckpt_manager=t.ckpt_manager,
            async_save=t.async_save,
            max_to_keep=t.max_ckpt_to_keep,
            io_retries=t.resilience_io_retries,
            retry_base_s=t.resilience_retry_base_s,
            verify_mode=t.ckpt_verify,
            elastic=t.ckpt_elastic,
        )

    def _inner_loss_fn(self, model):
        """Loss over FULL model params (LoRA merge, if any, wraps outside)."""
        if self.args.data.channel_list:
            from veomni_tpu.train.channel_loss import (
                make_channel_loss_fn,
                supports_channel_loss,
            )

            if not supports_channel_loss(model):
                raise NotImplementedError(
                    "data.channel_list needs a text param tree or a family "
                    "exposing a merged-hidden preamble (all VL + omni "
                    "thinkers do; seed-omni composites with generation "
                    "heads do not)"
                )
            return make_channel_loss_fn(model, len(self.args.data.channel_list))
        return lambda params, batch: model.loss_fn(params, batch)

    def _init_callbacks(self):
        from veomni_tpu.observability.callback import ObservabilityCallback

        t = self.args.train
        self.callbacks = [
            EnvironMeterCallback(self.meter),
            # after the meter (its rollup must be in the published payload),
            # before Logging/Wandb (they consume the registry export)
            ObservabilityCallback(),
            LoggingCallback(),
            CheckpointCallback(self.checkpointer, t.save_steps),
        ]
        if self.args.data.eval_path:
            from veomni_tpu.trainer.callbacks import EvaluateCallback

            self.callbacks.append(EvaluateCallback(t.eval_steps))
        if self.args.data.channel_list:
            from veomni_tpu.train.channel_loss import ChannelLossCallback

            self.callbacks.append(
                ChannelLossCallback(self.args.data.channel_list, t.log_steps * 10)
            )
        if t.enable_profiling:
            self.callbacks.append(
                ProfileCallback(t.output_dir, t.profile_start_step, t.profile_end_step)
            )
        if t.save_hf_weights:
            self.callbacks.append(HFCheckpointCallback())
        if t.use_wandb:
            import dataclasses

            self.callbacks.append(
                WandbCallback(t.wandb_project, t.wandb_name,
                              config=dataclasses.asdict(self.args))
            )

    def _batch_sharding_map(self):
        """Per-key PartitionSpec for device batches; subclasses extend for
        modality-specific keys (cf. reference DataCollateInfo sp_slice)."""
        ps = self.parallel_state
        keys = BATCH_KEYS + (("channel_ids",) if self.args.data.channel_list else ())
        return {k: P(None, ps.dp_axes, ps.sp_axes) for k in keys}

    # ----------------------------------------------------------------- resume
    def try_resume(self, step: Optional[int] = None,
                   max_step: Optional[int] = None):
        """``step=None`` walks back from the latest committed-and-verified
        checkpoint (generations failing manifest verification are
        quarantined and skipped); ``max_step`` caps the walk (supervisor
        rollback targets checkpoints from BEFORE the anomalous window); an
        explicit ``step`` pins the restore with no fallback."""
        restored, extra = self.checkpointer.load(
            jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                self.abstract_state, self.state_shardings,
            ),
            step=step,
            max_step=max_step,
        )
        if restored is not None:
            # normalize on-device layouts to what a fresh jit would produce:
            # restored buffers can carry different layouts, and XLA (notably
            # CPU/oneDNN) specializes kernels per layout — without this, a
            # resumed run is deterministic but not bit-identical to the
            # uninterrupted one
            restored = jax.jit(
                lambda s: s, out_shardings=self.state_shardings
            )(restored)
            self.train_state = restored
            logger.info_rank0("resumed from checkpoint")
        return restored is not None, extra

    def apply_restored_extra(self, state, extra: Dict[str, Any]) -> None:
        """Apply a checkpoint's extra_state (global step, epoch, rank-local
        dataloader cursor, meter, stateful callbacks) to the live run. Shared
        by auto-resume (CheckpointCallback.on_train_begin) and the anomaly
        supervisor's rollback path."""
        if not extra:
            return
        state.global_step = int(extra.get("global_step", 0))
        state.epoch = int(extra.get("epoch", 0))
        if extra.get("dataloader") and hasattr(self.dataloader, "load_state_dict"):
            self.dataloader.load_state_dict(extra["dataloader"])
        if extra.get("meter") and self.meter:
            self.meter.load_state_dict(extra["meter"])
        for cb in self.callbacks:
            cb_state = extra.get("callbacks", {}).get(type(cb).__name__)
            if cb_state and hasattr(cb, "load_state_dict"):
                cb.load_state_dict(cb_state)

    # ------------------------------------------------------------- evaluation
    def _build_eval_dataloader(self):
        """Eval pipeline via the subclass's own dataset/dataloader builders
        (same transform + collator contract as training)."""
        saved = (self.dataset, self.dataloader, self.args.data.train_path)
        self.args.data.train_path = self.args.data.eval_path
        try:
            self._build_dataset()
            self._build_dataloader()
            eval_dl = self.dataloader
        finally:
            self.dataset, self.dataloader, self.args.data.train_path = saved
        return eval_dl

    def _ship_batch(self, batch_np):
        """Host batch -> globally-sharded device arrays (multihost-aware)."""
        if jax.process_count() > 1:
            return {
                k: jax.make_array_from_process_local_data(
                    self.batch_shardings[k], v
                )
                for k, v in batch_np.items() if k in self.batch_shardings
            }
        return {
            k: jax.device_put(v, self.batch_shardings[k])
            for k, v in batch_np.items() if k in self.batch_shardings
        }

    def evaluate(self) -> Optional[float]:
        """Forward-only mean loss over ``eval_batches`` micro-batches of
        data.eval_path (the reference's EvaluateCallback is an empty TODO —
        ``trainer/callbacks/evaluate_callback.py:37`` — this one runs).

        The eval dataloader is rebuilt per call: with the fixed seed it
        yields the SAME deterministic slice every time, so eval_loss values
        at different steps are comparable."""
        if not self.args.data.eval_path:
            return None
        if not hasattr(self, "_eval_step"):
            # census-instrumented like the train step: eval flops are real
            # device work and belong in the window MFU (observability/cost)
            from veomni_tpu.observability.cost import instrument_jit
            from veomni_tpu.train.train_step import _batch_bucket

            self._eval_step = instrument_jit(
                "eval_step",
                jax.jit(lambda params, batch: self._loss_fn(params, batch)),
                bucket_fn=lambda args: _batch_bucket(args[1]),
            )
        it = iter(self._build_eval_dataloader())
        total, ntok = 0.0, 0.0
        for _ in range(self.args.train.eval_batches):
            try:
                batch_np = next(it)
            except StopIteration:
                break
            batch = self._ship_batch(batch_np)
            # accum dim: evaluate micro-batch by micro-batch ([A,B,S] -> [B,S])
            for a in range(next(iter(batch.values())).shape[0]):
                micro = {k: v[a] for k, v in batch.items()}
                loss_sum, metrics = self._eval_step(self.train_state.params, micro)
                total += float(loss_sum)
                ntok += float(metrics["ntokens"])
        return total / max(ntok, 1.0)

    # ------------------------------------------------------------------ train
    def _fire(self, hook: str, state):
        for cb in self.callbacks:
            getattr(cb, hook)(self, state)

    def _start_data_iter(self):
        """(Re)build the prefetcher + iterator — at train start and after a
        supervisor rollback restored the dataloader cursor (the prefetch
        thread starts pulling at construction, so the cursor must already be
        in place)."""
        t = self.args.train
        self._prefetcher = None
        if t.prefetch_depth > 0:
            from veomni_tpu.data.prefetch import BackgroundPrefetcher

            self._prefetcher = BackgroundPrefetcher(
                self.dataloader, depth=t.prefetch_depth
            )
        return iter(self._prefetcher or self.dataloader)

    def _close_prefetcher(self):
        """Idempotent; also invoked from the SIGTERM handler to wake a
        consumer blocked on the prefetch queue."""
        pf = getattr(self, "_prefetcher", None)
        if pf is not None:
            pf.close()

    def _close_callbacks(self):
        """Exception-safe teardown for resource-holding callbacks (live
        exporter thread, active jax.profiler trace, jsonl handles) — runs on
        BOTH the loop's exit paths and a startup failure in
        ``on_train_begin`` (where earlier callbacks may already hold
        resources the later, raising one never will release)."""
        for cb in self.callbacks:
            try:
                cb.close()
            except Exception as e:
                logger.warning_rank0(
                    "callback %s close() failed: %s",
                    type(cb).__name__, e,
                )

    @staticmethod
    def _postmortem_extra(e: BaseException, global_step: int) -> Dict[str, Any]:
        """Post-mortem payload for an exception escaping train(). A device
        allocator failure (RESOURCE_EXHAUSTED) additionally captures the
        live-buffer census and the compiled-program cost census — the two
        tables an OOM forensic needs (observability/devmem.py) — and any
        run with the numerics observatory armed attaches its non-finite
        provenance + health history (observability/numerics.py), so a
        supervisor abort names the first offending param group. Must never
        raise: forensics can't be allowed to mask the original failure."""
        extra: Dict[str, Any] = {"error": str(e)[:2000],
                                 "global_step": global_step}
        try:
            from veomni_tpu.observability.devmem import attach_oom_extra

            attach_oom_extra(e, extra)
        except Exception as forensic_err:  # even the import must be safe
            extra["oom_report_error"] = str(forensic_err)
        try:
            from veomni_tpu.observability.numerics import attach_numerics_extra

            attach_numerics_extra(extra)
        except Exception as forensic_err:
            extra["numerics_report_error"] = str(forensic_err)
        return extra

    # -------------------------------------------------------------- numerics
    def _get_numerics_step(self):
        """The INSTRUMENTED sibling train step (numerics observatory), built
        on first use through the same ``build_train_step`` as the hot step —
        same loss fn (incl. subclass DPO/RL/distill rebinds), same
        shardings, same clip/mask/skip config — so the cost census sees it
        as its own ``numerics_step`` site and the trace-count gates bound
        the tier to exactly one extra compiled program. Never donates:
        anomaly diagnosis discards the returned state."""
        if self._numerics_step is None:
            from veomni_tpu.observability.numerics import NumericsSpec

            t = self.args.train
            self._numerics_step = build_train_step(
                self._loss_fn, self.optimizer, self.parallel_state,
                state_shardings=self.state_shardings,
                batch_shardings=self.batch_shardings,
                max_grad_norm=t.max_grad_norm,
                grad_mask=self.grad_mask,
                skip_nonfinite=t.resilience_skip_nonfinite,
                numerics_spec=NumericsSpec(
                    max_groups=t.observability_numerics_max_groups
                ),
            )
        return self._numerics_step

    def _diagnose_numerics(self, ctl, batch) -> None:
        """Supervisor anomaly tie-in: re-run the same already-fetched batch
        through the instrumented step and turn the health tree into a
        provenance doc (first non-finite group, grad vs param vs update,
        recent history ring) BEFORE the verdict escalates. With
        ``skip_nonfinite`` the anomalous update never landed, so the re-run
        reproduces the exact blown-up computation; the returned state is
        discarded (the sibling step does not donate). Best-effort: the
        in-flight drain can lag detection by a few steps, in which case the
        most recent batch stands in for the anomalous one. Never raises —
        diagnosis must not out-fail the anomaly it explains."""
        if self._numerics is None:
            return
        try:
            _state, _metrics, health = self._get_numerics_step()(
                self.train_state, batch
            )
            # last_anomaly_injected, NOT last_injected: the dispatch-
            # depth queue drains an entry steps after it was observed,
            # so the anomalous entry behind this verdict is older than
            # the current observe() call's injection flag
            doc = self._numerics.diagnose(
                ctl.global_step, health,
                injected=self._supervisor.last_anomaly_injected,
            )
            del _state, _metrics
            first = doc.get("first_nonfinite")
            ctl.resilience = {**ctl.resilience,
                              "numerics_first_nonfinite": first}
        except Exception as e:
            logger.warning_rank0("numerics diagnosis failed: %s", e)

    def _rollback(self, ctl, sup):
        """Supervisor escalation: restore the latest committed checkpoint
        (params + optimizer + rank-local data cursor) and replay the
        iterator from there. Returns the fresh data iterator."""
        from veomni_tpu.resilience.supervisor import RollbackImpossible

        logger.warning_rank0(
            "anomaly escalation: rolling back from step %d to the latest "
            "committed checkpoint", ctl.global_step,
        )
        self._close_prefetcher()
        try:
            self.checkpointer.wait()  # an in-flight save may be the target
        except Exception as e:
            logger.warning_rank0("in-flight save failed during rollback: %s", e)
        # target a checkpoint committed BEFORE the anomalous run began: a
        # save that landed inside the window (detection lags by the
        # in-flight depth) would make the rewind a no-op — the cursor must
        # back up past the anomalous batches so the replay re-runs them.
        # Elastic-safe: the walk goes through the same topology gate as any
        # restore (checkpoint/checkpointer.py::_classify_step +
        # _materialize_rank_state), so a rollback target saved pre-resize
        # (an elastically-resumed run rolling back past its own resize
        # point) reshards cursors instead of silently restoring the wrong
        # world's state.
        # max_step (not a pinned step) keeps the checkpointer's verify-and-
        # fall-back walk in play: a rollback must never restore from a
        # generation that fails manifest verification, so a corrupt target
        # quarantines and the walk drops to the next-newest verified one.
        max_step = None
        first_bad = sup.consec_start
        committed = self.checkpointer.list_steps()
        if first_bad is not None:
            before = [s for s in committed if s < first_bad]
            if before:
                max_step = before[-1]
            elif committed:
                logger.warning_rank0(
                    "no committed checkpoint precedes anomalous step %d; "
                    "restoring the latest (cursor will NOT re-run the "
                    "anomalous batches)", first_bad,
                )
        restored, extra = self.try_resume(max_step=max_step)
        if not restored:
            raise RollbackImpossible(
                "rollback requested but no committed checkpoint exists "
                "(set train.save_steps to create mid-run rollback targets)"
            )
        self.apply_restored_extra(ctl, extra)
        sup.note_rollback(to_step=ctl.global_step)
        return self._start_data_iter()

    def train(self):
        t = self.args.train
        from veomni_tpu.resilience import (
            GracefulShutdown,
            SupervisorPolicy,
            TrainSupervisor,
        )
        from veomni_tpu.resilience.faults import arm_from_env, fault_point
        from veomni_tpu.resilience.supervisor import AnomalyBudgetExceeded, worse_verdict
        from veomni_tpu.utils.helper import Watchdog

        arm_from_env()  # VEOMNI_FAULT_PLAN (tests/chaos drills); no-op else
        # dump-dir wiring BEFORE any callback can raise: a startup failure
        # (EnvironMeterCallback precedes ObservabilityCallback in the hook
        # order) must still land its post-mortem in output_dir, not the
        # launcher's CWD
        configure_flight_recorder(
            max_events=t.observability_flight_events, dump_dir=t.output_dir,
            fresh=True,  # this run's history starts here, not a prior run's
        )
        ctl = TrainerControlState(train_steps=self.train_steps)
        sup = TrainSupervisor(SupervisorPolicy.from_train_args(t))
        # the observability callback wires /healthz to the supervisor state
        self._supervisor = sup
        # numerics observatory (observability/numerics.py): host-side
        # monitor for the interval health summaries + anomaly provenance;
        # registered as the process's active monitor so /debug/numerics and
        # the post-mortem attach see it. Knob off = tier fully absent.
        numerics_interval = max(0, t.observability_numerics_interval)
        if numerics_interval:
            from veomni_tpu.observability.numerics import (
                NumericsMonitor,
                set_active_monitor,
            )

            self._numerics = NumericsMonitor(
                history=t.observability_numerics_history
            )
            set_active_monitor(self._numerics)
        with use_parallel_state(self.parallel_state):
            try:
                with span("setup.train_begin"):
                    self._fire("on_train_begin", ctl)
                flight_record("train.begin", cid=str(ctl.global_step),
                              train_steps=self.train_steps)
                # prefetcher construction AFTER on_train_begin: auto-resume
                # restores the dataloader cursor there, and the thread starts
                # pulling at construction
                with span("setup.data"):
                    data_iter = self._start_data_iter()
            except BaseException as e:
                # startup failures (auto-resume hitting all-generations-
                # corrupt, a dead data path) must produce a post-mortem too
                # — the quarantine/fallback event history is exactly what a
                # CheckpointCorruptError artifact needs. The dump dir was
                # wired in the prologue above, before any callback ran.
                dump_postmortem(
                    f"exception:{type(e).__name__}",
                    extra=self._postmortem_extra(e, ctl.global_step),
                )
                # the loop's finally below is never reached from here, but
                # callbacks that ran before the raising one may already hold
                # resources (exporter thread, profiler trace)
                self._close_prefetcher()
                self._close_callbacks()
                if self._numerics is not None:
                    from veomni_tpu.observability.numerics import (
                        set_active_monitor,
                    )

                    set_active_monitor(None)
                raise
            # SIGTERM = cluster preemption notice: finish the current step,
            # take one final synchronous checkpoint, return (exit 0) so the
            # restarted job resumes bit-exactly
            shutdown = GracefulShutdown(on_request=self._close_prefetcher)
            watchdog = Watchdog(
                t.resilience_watchdog_s, on_stall=sup.note_stall,
                description="train loop",
            )
            try:
                with shutdown, watchdog:
                    while True:
                        # The supervisor's observe() preserves the loop's
                        # dispatch-depth bound, independent of log cadence:
                        # with a large log_steps the host could otherwise run
                        # arbitrarily far ahead, keeping every shipped batch +
                        # queued execution live in HBM. It blocks on the
                        # oldest in-flight loss, whose value it needs anyway.
                        while ctl.global_step < self.train_steps and not ctl.should_stop:
                            if shutdown.requested:
                                break
                            try:
                                with span("data.wait"):
                                    batch_np = next(data_iter)
                            except Exception:
                                if shutdown.requested:
                                    break  # prefetcher closed by the handler
                                raise
                            self.current_batch = batch_np
                            if self._flash_tile_counters is not None:
                                self._flash_tile_counters(batch_np)
                            if self._scan_chunk_counters is not None:
                                self._scan_chunk_counters(batch_np)
                            # straggler drill point (fleet observatory): a
                            # `delay`-mode fault here slows THIS rank's loop
                            # deterministically, so the skew exchange +
                            # straggler warning run under JAX_PLATFORMS=cpu
                            # in tier-1. Unarmed: one None check.
                            fault_point("step.delay")
                            # numerics drill point: a `nan`-mode fault here
                            # plants a REAL NaN in one param leaf (unlike
                            # step.loss, which only poisons the host-side
                            # observation) so the provenance machinery has a
                            # genuine non-finite tensor to find and name
                            # under JAX_PLATFORMS=cpu. Unarmed: None check.
                            act = fault_point("step.params")
                            if act is not None and act.mode == "nan":
                                from veomni_tpu.observability.numerics import (
                                    poison_param_group,
                                )

                                poisoned, target = poison_param_group(
                                    self.train_state.params, act.target
                                )
                                if target:
                                    self.train_state = self.train_state.replace(
                                        params=poisoned
                                    )
                                    logger.warning_rank0(
                                        "fault step.params poisoned param "
                                        "leaf %r with NaN", target,
                                    )
                                else:
                                    # mirror the corrupt mode's no-target
                                    # warning: fault_point already logged
                                    # "fault injected", and a drill that
                                    # planted nothing must say so loudly
                                    logger.warning_rank0(
                                        "fault step.params poisoned "
                                        "NOTHING: no float param leaf "
                                        "matches group %r", act.target,
                                    )
                            with span("host.callbacks"):
                                self._fire("on_step_begin", ctl)
                            # each process holds [A, B_local, S]; stitch into
                            # the globally-sharded array (single-controller)
                            with span("data.ship"):
                                batch = self._ship_batch(batch_np)
                            # flight-recorder step lifecycle: dispatch is
                            # recorded BEFORE the jitted call and end AFTER
                            # the callbacks, so a post-mortem of a hang shows
                            # the wedged step as dispatched-but-never-ended
                            flight_record("step.dispatch",
                                          cid=str(ctl.global_step + 1))
                            # numerics cadence: every interval-th step runs
                            # the instrumented sibling instead of the hot
                            # step — same update math, one extra compiled
                            # program, plus the per-group health tree the
                            # monitor fetches and publishes
                            health = None
                            numerics_due = bool(
                                numerics_interval
                                and (ctl.global_step + 1) % numerics_interval
                                == 0
                            )
                            with span("step.dispatch"):
                                if numerics_due:
                                    (self.train_state, metrics,
                                     health) = self._get_numerics_step()(
                                        self.train_state, batch
                                    )
                                else:
                                    self.train_state, metrics = self.train_step(
                                        self.train_state, batch
                                    )
                            ctl.global_step += 1
                            if health is not None:
                                self._numerics.observe(ctl.global_step, health)
                            verdict = sup.observe(ctl.global_step, metrics)
                            if sup.last_injected:
                                # a host-injected step.loss drill marks THIS
                                # step anomalous without any device-side
                                # non-finite value; stamp the published flag
                                # so window accumulators (channel loss) and
                                # the train.step_ok gauge agree with the
                                # supervisor's verdict
                                metrics = dict(metrics)
                                metrics["step_ok"] = False
                            watchdog.pet()
                            # the step dispatches asynchronously; materializing
                            # a metric would block the host on device completion
                            # and serialize batch assembly with compute. Fetch
                            # only on log steps; in between, callbacks receive
                            # device futures.
                            ctl.synced = (
                                ctl.global_step % t.log_steps == 0
                                or ctl.global_step >= self.train_steps
                            )
                            if ctl.synced:
                                # the device fetch: on the async loop this
                                # absorbs the window's real compute time, so
                                # the span keeps it out of host-stall
                                # attribution ("other" in the goodput split)
                                with span("sync.fetch"):
                                    metrics = {
                                        k: (float(v) if np.ndim(v) == 0
                                            else np.asarray(v))
                                        for k, v in metrics.items()
                                    }
                            ctl.metrics = dict(metrics)
                            if ctl.synced:
                                # optax evaluated the schedule at count ==
                                # step-1 for the update just applied; log that
                                # value, not the next step's. Schedules are jnp
                                # programs, so this float() is itself a device
                                # fetch — sync steps only.
                                ctl.metrics["lr"] = float(
                                    self.lr_schedule(ctl.global_step - 1)
                                )
                                # the host just blocked on the device anyway:
                                # inspect every queued verdict for free —
                                # unless escalation is already decided: a
                                # later OK entry would reset the supervisor's
                                # consec_start before _rollback reads it to
                                # pick a pre-anomaly target (note_rollback
                                # clears the queue regardless)
                                if verdict in ("ok", "skip"):
                                    verdict = worse_verdict(verdict, sup.drain())
                                ctl.resilience = sup.stats()
                            with span("host.callbacks"):
                                self._fire("on_step_end", ctl)
                            flight_record("step.end", cid=str(ctl.global_step),
                                          synced=ctl.synced)
                            if verdict != "ok":
                                # anomaly observed: before the verdict
                                # escalates, re-run the already-fetched
                                # batch through the instrumented step so the
                                # skip/rollback/abort is ATTRIBUTABLE (which
                                # group first went non-finite) — no-op when
                                # the numerics tier is off
                                self._diagnose_numerics(ctl, batch)
                            if verdict == "rollback":
                                data_iter = self._rollback(ctl, sup)
                            elif verdict == "abort":
                                raise AnomalyBudgetExceeded(
                                    f"anomaly budget exceeded at step "
                                    f"{ctl.global_step}: {sup.stats()}"
                                )
                        if shutdown.requested and ctl.global_step < self.train_steps:
                            ctl.preempted = True
                            ctl.should_stop = True
                            sup.drain()  # late anomalies still count in stats
                            logger.warning_rank0(
                                "preemption stop at step %d: taking the final "
                                "checkpoint, then exiting cleanly",
                                ctl.global_step,
                            )
                            # the pod is about to disappear: the post-mortem
                            # is the only record of the final seconds (the
                            # graceful checkpoint covers STATE, not events)
                            flight_record("shutdown.request",
                                          cid=str(ctl.global_step),
                                          signum=shutdown.signum)
                            dump_postmortem(
                                "sigterm",
                                extra={"global_step": ctl.global_step},
                            )
                            break
                        if ctl.should_stop:
                            # stopping anyway: no rollback/abort, but the last
                            # inflight_depth steps' anomalies must still be
                            # counted and logged, not silently dropped
                            sup.drain()
                            break
                        # step budget exhausted, but up to inflight_depth
                        # verdicts may still be queued — a blow-up in the last
                        # few steps must not slip out silently
                        verdict = sup.drain()
                        if verdict == "abort":
                            raise AnomalyBudgetExceeded(
                                f"anomaly budget exceeded in the final steps: "
                                f"{sup.stats()}"
                            )
                        if verdict == "rollback":
                            data_iter = self._rollback(ctl, sup)
                            continue  # re-run the rolled-back steps
                        break
                    # STILL inside the signal scope: schedulers often re-send
                    # SIGTERM during the grace period — the final synchronous
                    # checkpoint (on_train_end) must not die to the default
                    # handler mid-save. A repeated TERM just re-sets the flag.
                    ctl.resilience = sup.stats()
                    self._fire("on_train_end", ctl)
                    flight_record("train.end", cid=str(ctl.global_step))
            except BaseException as e:
                # uncaught exception escaping train() (supervisor abort,
                # RollbackImpossible, a data-path blowup, KeyboardInterrupt):
                # the stack trace says where it died, the post-mortem says
                # what the run was doing on the way there
                dump_postmortem(
                    f"exception:{type(e).__name__}",
                    extra=self._postmortem_extra(e, ctl.global_step),
                )
                raise
            finally:
                self._close_prefetcher()
                # exception path skips on_train_end (an abort must not run
                # the final-checkpoint hooks) but resource-holding callbacks
                # still need teardown: an active jax.profiler trace or a
                # live exporter thread must not leak past a crashed run
                self._close_callbacks()
                if self._numerics is not None:
                    from veomni_tpu.observability.numerics import (
                        get_active_monitor,
                        set_active_monitor,
                    )

                    # only un-register our own monitor (a second trainer in
                    # the process may have installed its own). NOTE: the
                    # post-mortem dump in the except path above runs BEFORE
                    # this finally, so the provenance attach still sees it.
                    if get_active_monitor() is self._numerics:
                        set_active_monitor(None)
        return ctl
