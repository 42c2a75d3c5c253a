"""What can be known about the chip without the chip: whole train steps.

The steps of the recipes the benchmark's cells run (``configs/text/*_v5e.yaml``),
as the trainer builds them, compiled by the installed TPU compiler for ONE
described v5e: which kernels are in the step and how many, that the fused
backward and ``mla_qkv_rope`` took the call, arguments + temporaries against
16 GiB, and the dense step's scope map. A case here compiles for minutes on a
CPU core: one that compiles a whole step shares a module fixture or does not go
in (docs/testing.md, "Tier-1").
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from described_chip import flash_bwd_calls, for_mosaic, kernel_instructions, on_chip_kernels, v5e  # noqa: F401

import chip_smoke  # (repo root is on sys.path via conftest)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIB = 2 ** 30


@pytest.fixture(scope="module")
def smoke_step(v5e):
    """The train step of configs/text/qwen3_0p6b_v5e.yaml, as the trainer
    builds it, compiled once for one described chip (several tests read it)."""
    with pytest.MonkeyPatch.context() as monkeypatch:
        for_mosaic(monkeypatch)
        return _compile_smoke_step(v5e)


def _compile_smoke_step(v5e, config=chip_smoke.TRAIN_CONFIG, **pinned_ops):
    return _lower_smoke_step(v5e, config, **pinned_ops).compile()


def _lower_smoke_step(v5e, config, **pinned_ops):
    """The recipe's train step traced and lowered for one described chip: the
    Mosaic calls and the trace-time counters are there, and the TPU compiler
    has not run yet."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from veomni_tpu.arguments import VeOmniArguments, parse_args
    from veomni_tpu.models import build_foundation_model
    from veomni_tpu.models.auto import build_config
    from veomni_tpu.optim import build_lr_scheduler, build_optimizer
    from veomni_tpu.parallel import init_parallel_state, use_parallel_state
    from veomni_tpu.train import build_train_state, build_train_step
    from veomni_tpu.train.train_step import resolve_state_shardings

    args = parse_args(VeOmniArguments, [os.path.join(REPO, config)])
    t = args.train
    overrides = dict(args.model.config_overrides)
    cfg = build_config(
        overrides.pop("model_type"), **overrides, dtype=t.compute_dtype,
        param_dtype=t.param_dtype, remat=t.enable_gradient_checkpointing,
        remat_policy=t.gradient_checkpointing_policy,
    )
    ps = init_parallel_state(devices=v5e[:1])
    with use_parallel_state(ps):
        # on the chip the registry resolves attention to pallas_flash by
        # platform; here the platform is the CPU, so the test pins it
        model = build_foundation_model(
            config=cfg, ops_implementation={"attention": "pallas_flash",
                                            "qk_norm_rotary": "pallas", **pinned_ops})
        opt = build_optimizer(
            model.abstract(), optimizer=t.optimizer,
            lr=build_lr_scheduler(t.lr_decay_style, lr=t.lr, train_steps=t.train_steps),
        )

        def make_state(rng):
            return build_train_state(model.family.init_params(rng, cfg), opt)

        abs_state = jax.eval_shape(make_state, jax.random.PRNGKey(0))
        shardings = resolve_state_shardings(abs_state, model.get_parallel_plan(), ps)
        keys = ("input_ids", "labels", "position_ids", "segment_ids")
        batch_sh = {k: NamedSharding(ps.mesh, P(None, ps.dp_axes, ps.sp_axes)) for k in keys}
        step = build_train_step(
            model.loss_fn, opt, ps, state_shardings=shardings, batch_shardings=batch_sh,
            max_grad_norm=t.max_grad_norm, skip_nonfinite=t.resilience_skip_nonfinite,
        )
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            abs_state, shardings,
        )
        batch = {k: jax.ShapeDtypeStruct(
            (1, t.micro_batch_size, args.data.max_seq_len), jnp.int32, sharding=batch_sh[k])
            for k in keys}
        return step.lower(state, batch)


def test_smoke_train_step_fits_one_v5e(smoke_step):
    """The kernels are in it, and arguments + temporaries leave room in
    16 GiB."""
    compiled = smoke_step
    # a layer body's forward, then the recomputed forward and the backward:
    # flash fwd, fwd + the fused backward; the q/k norm + rope fwd, fwd + bwd
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 6
    assert kernel_instructions(text) == {"flash_fwd": 2, "flash_bwd_dkv": 1,
                                          "qk_norm_rope_fwd": 2, "qk_norm_rope_bwd": 1}
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 6 * GIB  # f32 params + AdamW moments
    # 1 GiB under the 16 GiB line for what the process holds besides
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15 * GIB


def test_hybrid_state_space_train_step_fits_one_v5e(v5e, on_chip_kernels):
    """The step of configs/text/granite_4_0_h_micro_v5e.yaml (the benchmark's
    third cell: 772 M parameters at 16 bytes, ONE row of 8192): its one
    attention layer runs the flash kernels, the nine scans are XLA, and
    arguments + temporaries leave room in the 15.75 GiB a v5e gives a program."""
    fused, split = flash_bwd_calls()
    compiled = _compile_smoke_step(v5e, "configs/text/granite_4_0_h_micro_v5e.yaml")
    assert flash_bwd_calls() == (fused + 1, split)  # the one call site, fused
    assert kernel_instructions(compiled.as_text()) == {"flash_fwd": 2, "flash_bwd_dkv": 1}
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 8.5 * GIB  # f32 params + AdamW moments
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5 * GIB


KIMI_STEP_KERNELS = {"flash_fwd": 2, "flash_bwd_dkv": 1, "mla_qkv_rope_fwd": 2, "mla_qkv_rope_bwd": 1}


def _lower_kimi_linear_step(v5e):
    """The step of configs/text/kimi_linear_48b_a3b_v5e.yaml lowered, with what
    the trace alone says held: the split + rope kernel took the MLA layer's
    call (NoPE as it is: the identity rotation), and its backward is fused."""
    from veomni_tpu.observability.metrics import get_registry

    taken = get_registry().counter("attn.mla_qkv_rope.calls_kernel")
    handed = get_registry().counter("attn.mla_qkv_rope.calls_handed_over")
    before = (taken.value, handed.value)
    fused, split = flash_bwd_calls()
    lowered = _lower_smoke_step(v5e, "configs/text/kimi_linear_48b_a3b_v5e.yaml",
                                mla_qkv_rotary="pallas")
    assert (taken.value - before[0], handed.value - before[1]) == (1, 0)
    assert flash_bwd_calls() == (fused + 1, split)  # the MLA layer's backward, fused
    return lowered


def test_kimi_linear_train_step_lowers_with_its_kernels(v5e, on_chip_kernels):
    """What ``test_kimi_linear_train_step_fits_one_v5e`` (slow) holds of the
    kernels, read before the TPU compiler runs: the Mosaic calls of the lowered
    step by their ``kernel_name`` (its one MLA layer: flash forward twice under
    recompute, one fused backward; the split + rope kernel likewise; the four
    recurrences are XLA), and the counters."""
    text = _lower_kimi_linear_step(v5e).as_text()
    names = re.findall(r'kernel_name = "([a-z_]+)"', text)
    assert len(names) == text.count("@tpu_custom_call")
    assert {k: names.count(k) for k in set(names)} == KIMI_STEP_KERNELS


@pytest.mark.slow
def test_kimi_linear_train_step_fits_one_v5e(v5e, on_chip_kernels):
    """The step of configs/text/kimi_linear_48b_a3b_v5e.yaml (the benchmark's
    fourth cell: 602 M parameters at 16 bytes, ONE row of 8192) through the
    TPU compiler: the same kernels in the compiled text, and arguments +
    temporaries leave room in the 15.75 GiB a v5e gives a program.

    ``slow`` (tests/conftest.py): the compile alone is minutes on a quiet core
    (three scan bodies each hold Kimi Delta Attention's backward), and the cell
    ``kimi_linear_48b_a3b.train_packed_8k_x1_doc4k`` compiles and runs this
    very step on a v5e in every PR's check: a step that does not fit fails it."""
    compiled = _lower_kimi_linear_step(v5e).compile()
    assert kernel_instructions(compiled.as_text()) == KIMI_STEP_KERNELS
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 6.7 * GIB  # f32 params + AdamW moments
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13 * GIB


# what carries no scope of the taxonomy in the compiled step, by the last
# component of its op_name: lax.scan's own slicing and stacking of the
# per-layer tensors, the remat wrapper's layout copies, the step's bf16 cast
# of the parameters, the rope tables, buffers the compiler allocates
UNSCOPED_PLUMBING = {"squeeze", "dynamic_slice", "dynamic_update_slice", "remat2",
                     "convert_element_type", "mul", "broadcast_in_dim", "closed_call"}


@pytest.fixture(scope="module")
def smoke_scope_map(smoke_step):
    """Through the census, as a reader gets it: the executable is noted at
    compile time, the text is parsed when someone asks."""
    from veomni_tpu.observability.cost import CostCensus
    from veomni_tpu.observability.metrics import MetricsRegistry

    census = CostCensus(registry=MetricsRegistry())
    census.note_executable("smoke_step", smoke_step)
    return census.scope_map("smoke_step")


def _device_instructions(text):
    """(name, opcode) of the fusions, convolutions and custom calls of a
    compiled text: what a trace's device events are made of."""
    return re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = \S+(?: \S+)*? "
                      r"(fusion|convolution|custom-call)\(", text, re.MULTILINE)


def test_smoke_train_step_scope_map_covers_the_device_work(smoke_step, smoke_scope_map):
    from benchmark import scopes as sc

    instructions = _device_instructions(smoke_step.as_text())
    assert len(instructions) > 200
    with_name = [(n, smoke_scope_map[n]) for n, _ in instructions if n in smoke_scope_map]
    loose = [(n, op) for n, op in with_name if sc.scope_of(op) is None]
    assert {op.rsplit("/", 1)[-1] for _, op in loose} <= UNSCOPED_PLUMBING, loose
    # by count, most of what has a name has a scope, and most has a name
    # (what has none is the compiler's own: layout copies, ConcatBitcast)
    assert len(loose) < 0.2 * len(with_name)
    assert len(with_name) > 0.6 * len(instructions)


@pytest.mark.parametrize("scope", ["embed", "attn.qkv", "attn.flash", "attn.out", "mlp",
                                   "lm_head_loss", "grad_clip", "optimizer"])
def test_smoke_train_step_has_every_dense_scope(smoke_scope_map, scope):
    from benchmark import scopes as sc

    assert any(sc.scope_of(op) == scope for op in smoke_scope_map.values())


def test_smoke_train_step_phases_under_remat_nothing(smoke_step, smoke_scope_map):
    """The recomputed forward is told from the first and from the backward
    by its op_name, the kernels by their names."""
    from benchmark import scopes as sc

    classes = {n: sc.classify(n, smoke_scope_map)
               for n, _ in _device_instructions(smoke_step.as_text())}
    fwd = sorted(n for n in classes if n.startswith("flash_fwd."))
    assert sorted(classes[n] for n in fwd) == [("attn.flash", "forward"),
                                               ("attn.flash", "recompute")]
    assert {classes[n] for n in classes if n.startswith("flash_bwd_")} == {
        ("attn.flash", "backward")}
    phases = {p for s, p in classes.values() if s in ("mlp", "attn.qkv")}
    assert phases == {"forward", "recompute", "backward"}
    assert {p for s, p in classes.values() if s in ("optimizer", "grad_clip")} == {"optimizer"}
