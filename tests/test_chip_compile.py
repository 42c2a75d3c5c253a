"""What can be known about the chip without the chip.

1. AOT compiles for a DESCRIBED ``v5e:2x2`` (the TPU compiler is installed;
   no device is attached): the Pallas kernels, forward and backward, at the
   widths ``chip_smoke.py`` and the benchmark's cells run them at, and the
   dense train step at the
   smoke's size with the compiler's memory analysis held against 16 GiB.
   Interpret mode and the attention impl are steered here, in the test: the
   program picks both from ``jax.default_backend()``, which is the CPU.
2. The flag and compile-cache channels, the peak tables, and the smoke's own
   behaviour off the chip: it must fail at its device check, and its phase
   functions must pass at toy size.
"""

import json
import os
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
# libtpu lets one process at a time load it (/tmp/libtpu_lockfile). Nothing
# here touches a device, so this process and the child it starts may share
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIB = 2 ** 30

import chip_smoke  # noqa: E402  (repo root is on sys.path via conftest)


# --------------------------------------------------------------------------
# 1. AOT compiles for a described v5e
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def v5e():
    """Devices of a described (not attached) v5e 2x2."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    # a compile for a described device is written to a persistent cache but
    # cannot be read back without the chip: keep the cache off around these
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


_COMPILE_UNDER_FLAGS = """
import os, sys
os.environ["TPU_LOG_DIR"] = "disabled"
os.environ["VEOMNI_COMPILATION_CACHE"] = "0"
sys.path.insert(0, sys.argv[1])
from veomni_tpu.utils.xla_flags import apply_performance_flags
assert apply_performance_flags()
os.environ["LIBTPU_INIT_ARGS"] += sys.argv[2]
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
dev = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0]
x = jax.ShapeDtypeStruct((256, 256), jnp.bfloat16, sharding=SingleDeviceSharding(dev))
jax.jit(lambda a: a @ a).lower(x).compile()
print("COMPILED_UNDER", os.environ["LIBTPU_INIT_ARGS"])
"""


@pytest.mark.parametrize("extra,ok", [("", True), (" --xla_tpu_no_such_flag=true", False)],
                         ids=["ours", "bogus"])
def test_libtpu_takes_the_perf_flags(v5e, extra, ok):
    """The TPU compiler reads LIBTPU_INIT_ARGS when it is first asked for a
    topology and kills the process on a flag it does not know: so this runs
    in a child, and a flag the installed libtpu rejects fails here and not
    on the chip."""
    proc = subprocess.run(
        [sys.executable, "-c", _COMPILE_UNDER_FLAGS, REPO, extra],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True,
        timeout=120,
    )
    assert ("COMPILED_UNDER --xla_tpu_" in proc.stdout) is ok, proc.stderr[-2000:]
    if not ok:
        assert "xla_tpu_no_such_flag" in proc.stdout + proc.stderr


def _for_mosaic(monkeypatch):
    from veomni_tpu.ops.pallas import flash_attention, grouped_gemm

    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    monkeypatch.setattr(grouped_gemm, "_interpret", lambda: False)


@pytest.fixture
def on_chip_kernels(monkeypatch):
    """Compile the kernels for Mosaic, not for the interpreter."""
    _for_mosaic(monkeypatch)


def _kernel_instructions(text):
    """{kernel name: custom calls named after it} in a compiled text."""
    import re

    from veomni_tpu.observability.scopes import ALL_KERNEL_NAMES

    found = re.findall(r"^\s*(?:ROOT\s+)?%?([a-z_]+)\.\d+ = .* custom-call\(.*"
                       r'custom_call_target="tpu_custom_call"', text, re.MULTILINE)
    assert set(found) <= set(ALL_KERNEL_NAMES), found
    return {k: found.count(k) for k in set(found)}


def _flash_bwd_calls():
    """(fused, split): the backward's trace-time counters as they stand."""
    from veomni_tpu.observability.metrics import get_registry

    return tuple(get_registry().counter(f"attn.flash.bwd.calls_{form}").value
                 for form in ("fused", "split"))


def _described(device, shape, dtype):
    from jax.sharding import SingleDeviceSharding

    return jax.ShapeDtypeStruct(shape, dtype, sharding=SingleDeviceSharding(device))


# the shapes flash attention's callers hand it: the dense preset's (the
# benchmark cell's: this is what catches a tile choice that does not fit VMEM
# without a chip), a vision tower's (non-causal, packed images, D 64), one
# long row, a sequence that only 128 divides, a DiT's (no mask at all), and
# MLA's training form at JoyAI-LLM-Flash's widths (q, k of 128 nope + 64
# rope, v of 128: the joyai_llm_flash.train_packed_8k cell's call)
FLASH_CALLS = {
    "mla": dict(b=2, s=8192, hq=32, hkv=32, d=192, dv=128, causal=True, segments=True),
    # granite-4.0-h-micro's attention layer: 32 / 8 heads of 64, one row of
    # 8192, the configured scale 1/64 (the granite_4_0_h_micro cell's call)
    "nope64": dict(b=1, s=8192, hq=32, hkv=8, d=64, causal=True, segments=True, scale=1 / 64),
    "cell": dict(chip_smoke.FLASH_SHAPE, causal=True, segments=True),
    "vision": dict(b=2, s=2048, hq=16, hkv=16, d=64, causal=False, segments=True),
    # kimi_linear's one MLA layer: the same widths, NoPE, ONE row (the
    # kimi_linear_48b_a3b cell's call)
    "mla_x1": dict(b=1, s=8192, hq=32, hkv=32, d=192, dv=128, causal=True, segments=True),
    # the longest row whose dQ (16 MiB in f32) the fused backward keeps in VMEM
    "long": dict(b=1, s=32768, hq=16, hkv=8, d=128, causal=True, segments=True),
    # and one past the ceiling (32 MiB): the split pair, flash_bwd_dq and all
    "longer": dict(b=1, s=65536, hq=2, hkv=1, d=128, causal=True, segments=True, split=True),
    "s384": dict(b=2, s=384, hq=4, hkv=2, d=128, causal=True, segments=True),
    "dit": dict(b=2, s=1024, hq=8, hkv=8, d=128, causal=False, segments=False),
}


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("call", list(FLASH_CALLS))
def test_flash_attention_lowers_for_v5e(v5e, on_chip_kernels, call, direction):
    """The compiler's own verdict on each call's VMEM, the resident dQ row of
    the fused backward with it: a backward is ``flash_fwd`` and ONE
    ``flash_bwd_dkv``, or, for the row too long to keep, the split pair."""
    from veomni_tpu.ops.pallas.flash_attention import flash_attention

    c = FLASH_CALLS[call]
    b, s, hq, hkv, d = (c[k] for k in ("b", "s", "hq", "hkv", "d"))
    q = _described(v5e[0], (b, s, hq, d), jnp.bfloat16)
    kv = _described(v5e[0], (b, s, hkv, d), jnp.bfloat16)
    v = _described(v5e[0], (b, s, hkv, c.get("dv", d)), jnp.bfloat16)
    seg = _described(v5e[0], (b, s), jnp.int32) if c["segments"] else None

    def fwd(q, k, v, seg):
        # under its scope, as the model calls it: a kernel's instruction is
        # named after the kernel alone only below some named scope (bare
        # under jax.grad it comes out as jvp_flash_fwd_)
        with jax.named_scope("attn.flash"):
            return flash_attention(q, k, v, segment_ids=seg, causal=c["causal"],
                                   softmax_scale=c.get("scale"))

    def loss(q, k, v, seg):
        return fwd(q, k, v, seg).astype(jnp.float32).sum()

    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    text = jax.jit(fn).lower(q, kv, v, seg).compile().as_text()
    # the kernels' own names are the custom calls' instruction names
    # (observability/scopes.py::KERNEL_NAMES): a trace tells them apart
    want = {"flash_fwd": 1}
    if direction == "bwd":
        want["flash_bwd_dkv"] = 1
        if c.get("split"):
            want["flash_bwd_dq"] = 1
    assert text.count("tpu_custom_call") == sum(want.values())
    assert _kernel_instructions(text) == want


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_ssd_scan_lowers_for_v5e_within_a_chunks_memory(v5e, direction):
    """The state-space scan at the granite cell's shapes (one row of 8192, 64
    heads of 64, state 128, chunks of 256): it compiles for the chip, and its
    temporaries stay a chunk's, not the 0.5 GB a whole ``[H, S/c, c, c]`` f32
    decay matrix would take (forward AND backward)."""
    from veomni_tpu import ops

    b, s, h, p, g, n = 1, 8192, 64, 64, 1, 128
    x = _described(v5e[0], (b, s, h, p), jnp.bfloat16)
    dt = _described(v5e[0], (b, s, h), jnp.float32)
    head = _described(v5e[0], (h,), jnp.float32)
    bc = _described(v5e[0], (b, s, g, n), jnp.bfloat16)
    seg = _described(v5e[0], (b, s), jnp.int32)

    def fwd(x, dt, a, bm, cm, d, seg):
        with jax.named_scope("ssm.scan"):
            return ops.ssd_scan(x, dt, a, bm, cm, d, seg, 256)

    def loss(*args):
        return fwd(*args).astype(jnp.float32).sum()

    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=tuple(range(6)))
    compiled = jax.jit(fn).lower(x, dt, head, bc, bc, head, seg).compile()
    assert "tpu_custom_call" not in compiled.as_text()  # impl xla: no kernel yet
    whole_decay = h * s * 256 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < whole_decay // 2


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_kda_scan_lowers_for_v5e_within_a_blocks_memory(v5e, direction):
    """The Kimi Delta Attention recurrence at the kimi_linear cell's shapes (one
    row of 8192, 32 heads of 128, one decay a key channel): it compiles for
    the chip, and its temporaries stay a block's, not the 8.6 GB the
    ``[S/c, c, c, dk]`` f32 pair term of a whole row would take: the forward's
    under a quarter of ONE of the row's ``[H, S/c, c, c]`` f32 matrices (64
    MiB), the backward's (one 2 MiB state a block, a block's terms and their
    cotangents) under four of them."""
    from veomni_tpu import ops

    b, s, h, d = 1, 8192, 32, 128
    x = _described(v5e[0], (b, s, h, d), jnp.bfloat16)
    g = _described(v5e[0], (b, s, h, d), jnp.float32)
    beta = _described(v5e[0], (b, s, h), jnp.float32)
    seg = _described(v5e[0], (b, s), jnp.int32)

    def fwd(q, k, v, g, beta, seg):
        with jax.named_scope("kda.scan"):
            return ops.kda_scan(q, k, v, g, beta, seg)

    def loss(*args):
        return fwd(*args).astype(jnp.float32).sum()

    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=tuple(range(5)))
    compiled = jax.jit(fn).lower(x, x, x, g, beta, seg).compile()
    assert "tpu_custom_call" not in compiled.as_text()  # impl xla: no kernel yet
    pair_matrices = h * (s // 64) * 64 * 64 * 4
    limit = pair_matrices // 4 if direction == "fwd" else 4 * pair_matrices
    assert compiled.memory_analysis().temp_size_in_bytes < limit


def test_flash_attention_under_gspmd_lowers_for_v5e(v5e, on_chip_kernels):
    """Plain FSDP on four chips: attention sits under GSPMD, which refuses to
    partition a Mosaic kernel unless the wrapper shard_maps it."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from veomni_tpu.ops.pallas.flash_attention import flash_attention
    from veomni_tpu.parallel import init_parallel_state, use_parallel_state

    ps = init_parallel_state(devices=v5e)
    sh = NamedSharding(ps.mesh, P(ps.dp_axes))
    q = jax.ShapeDtypeStruct((4, 1024, 16, 128), jnp.bfloat16, sharding=sh)
    kv = jax.ShapeDtypeStruct((4, 1024, 8, 128), jnp.bfloat16, sharding=sh)
    seg = jax.ShapeDtypeStruct((4, 1024), jnp.int32, sharding=sh)
    with use_parallel_state(ps):
        text = jax.jit(
            lambda q, k, v, seg: flash_attention(q, k, v, segment_ids=seg, causal=True)
        ).lower(q, kv, kv, seg).compile().as_text()
    assert text.count("tpu_custom_call") == 1


# the shapes the q/k norm + rope op is handed: the qwen cell's, one long row,
# gemma's 256-wide heads under a zero-centred weight, rope alone (llama: no
# qk-norm), and MQA at a sequence only 128 divides
QK_NORM_ROPE_CALLS = {
    "cell": dict(b=4, s=4096, hq=16, hkv=8, d=128, normed=True),
    "long": dict(b=1, s=32768, hq=16, hkv=8, d=128, normed=True),
    "gemma": dict(b=2, s=2048, hq=8, hkv=4, d=256, normed=True, zero_centered=True),
    "rope_alone": dict(b=2, s=4096, hq=32, hkv=8, d=128, normed=False),
    "s384": dict(b=2, s=384, hq=4, hkv=1, d=128, normed=True),
}


def _qk_norm_rope_args(c, described):
    b, s, d = c["b"], c["s"], c["d"]
    q, k = described((b, s, c["hq"] * d)), described((b, s, c["hkv"] * d))
    table = described((b, s, d))
    w = described((d,)) if c["normed"] else None
    return q, k, table, table, w, w


def _qk_norm_rope_fns(zero_centered=False):
    from veomni_tpu.ops.pallas.qk_norm_rope import qk_norm_rope

    def fwd(q, k, cos, sin, wq, wk):
        with jax.named_scope("attn.qkv"):  # as the model calls it
            return qk_norm_rope(q, k, cos, sin, wq, wk, 1e-6, zero_centered)

    def loss(*args):
        q, k = fwd(*args)
        return q.astype(jnp.float32).sum() + k.astype(jnp.float32).sum()

    return fwd, loss


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("call", list(QK_NORM_ROPE_CALLS))
def test_qk_norm_rope_lowers_for_v5e(v5e, on_chip_kernels, call, direction):
    """One custom call each way, named after the kernel: the backward needs
    nothing of the forward's output, so the gradient alone holds no forward."""
    c = QK_NORM_ROPE_CALLS[call]
    args = _qk_norm_rope_args(c, lambda shape: _described(v5e[0], shape, jnp.bfloat16))
    fwd, loss = _qk_norm_rope_fns(c.get("zero_centered", False))
    wrt = (0, 1, 4, 5) if c["normed"] else (0, 1)
    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=wrt)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert _kernel_instructions(text) == {"qk_norm_rope_" + direction: 1}


def test_qk_norm_rope_under_gspmd_lowers_for_v5e(v5e, on_chip_kernels):
    """FSDP 2 x Ulysses 2 on four chips: the op sits under GSPMD with its
    activations sharded (dp, sp, None), and runs per device in a shard_map
    over exactly that; the weights' gradients are summed over the mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from veomni_tpu.parallel import init_parallel_state, use_parallel_state

    ps = init_parallel_state(devices=v5e, ulysses_size=2)
    rows = NamedSharding(ps.mesh, P(ps.dp_axes, ps.sp_axes))
    whole = NamedSharding(ps.mesh, P())

    def described(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                    sharding=rows if len(shape) == 3 else whole)

    args = _qk_norm_rope_args(QK_NORM_ROPE_CALLS["cell"], described)
    _, loss = _qk_norm_rope_fns()
    with use_parallel_state(ps):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 4, 5))).lower(*args).compile().as_text()
    assert _kernel_instructions(text) == {"qk_norm_rope_bwd": 1}
    assert "bf16[2,2048,2048]" in text  # a device's share of q: half the batch, half the rows
    assert "all-reduce" in text         # the weights' gradients


# the shapes the MLA split + rope op is handed: the joyai cell's (32 heads of
# 128 nope + 64 rope, v of 128: 1024 rows of four heads a step, the backward's
# sum over the groups of heads in its scratch), DeepSeek-V3's 128 heads, and
# the half rotation at a sequence only 128 divides (all four heads a step)
MLA_QKV_ROPE_CALLS = {
    "cell": dict(b=2, s=8192, h=32, interleaved=True),
    "heads128": dict(b=1, s=4096, h=128, interleaved=True),
    "halves_s384": dict(b=2, s=384, h=4, interleaved=False),
}
MLA_WIDTHS = (128, 64, 128)  # qk_nope_head_dim, qk_rope_head_dim, v_head_dim


def _mla_qkv_rope_args(c, described):
    dn, dr, dv = MLA_WIDTHS
    b, s, h = c["b"], c["s"], c["h"]
    rope = described((b, s, dr))
    return described((b, s, h * (dn + dr))), described((b, s, h * (dn + dv))), rope, rope, rope


def _mla_qkv_rope_fns(interleaved):
    from veomni_tpu.ops.pallas.mla_qkv_rope import mla_qkv_rope

    def fwd(*args):
        with jax.named_scope("attn.qkv"):  # as the model calls it
            return mla_qkv_rope(*args, *MLA_WIDTHS, interleaved)

    def loss(*args):
        return sum(x.astype(jnp.float32).sum() for x in fwd(*args))

    return fwd, loss


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("call", list(MLA_QKV_ROPE_CALLS))
def test_mla_qkv_rope_lowers_for_v5e(v5e, on_chip_kernels, call, direction):
    """One custom call each way, named after the kernel: the op is linear, so
    the gradient alone holds no forward."""
    c = MLA_QKV_ROPE_CALLS[call]
    args = _mla_qkv_rope_args(c, lambda shape: _described(v5e[0], shape, jnp.bfloat16))
    fwd, loss = _mla_qkv_rope_fns(c["interleaved"])
    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert _kernel_instructions(text) == {"mla_qkv_rope_" + direction: 1}


def test_mla_qkv_rope_under_gspmd_lowers_for_v5e(v5e, on_chip_kernels):
    """FSDP 2 x Ulysses 2 on four chips: the op sits under GSPMD with its
    activations sharded (dp, sp, None), and runs per device in a shard_map
    over exactly that; nothing is summed over the mesh (the op has no weight)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from veomni_tpu.parallel import init_parallel_state, use_parallel_state

    ps = init_parallel_state(devices=v5e, ulysses_size=2)
    rows = NamedSharding(ps.mesh, P(ps.dp_axes, ps.sp_axes))
    args = _mla_qkv_rope_args(MLA_QKV_ROPE_CALLS["cell"],
                              lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=rows))
    _, loss = _mla_qkv_rope_fns(True)
    with use_parallel_state(ps):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*args).compile().as_text()
    assert _kernel_instructions(text) == {"mla_qkv_rope_bwd": 1}
    assert "bf16[1,4096,6144]" in text  # a device's share of dq: half the batch, half the rows
    assert "all-reduce" not in text and "all-gather" not in text


def test_mla_attention_block_hands_flash_what_the_kernel_wrote(v5e, on_chip_kernels):
    """``_mla_attention`` at the joyai cell's shape, forward and backward, with
    both ops resolved as the chip resolves them: five kernels, and between
    ``mla_qkv_rope_*`` and ``flash_*`` no instruction of XLA's reads or writes
    a 192-wide per-head array (q, k and their cotangents) except the flash
    wrapper's own rounding of its f32 dK."""
    import re

    from veomni_tpu.arguments import VeOmniArguments, parse_args
    from veomni_tpu.models import transformer
    from veomni_tpu.models.auto import build_config
    from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY

    args = parse_args(VeOmniArguments, [os.path.join(REPO, "configs/text/joyai_llm_flash_v5e.yaml")])
    overrides = dict(args.model.config_overrides)
    cfg = build_config(overrides.pop("model_type"), **overrides, dtype="bfloat16",
                       param_dtype="float32")
    b, s, hidden, heads = 2, 8192, cfg.hidden_size, cfg.num_attention_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    assert (heads, dn, dr, dv) == (32, *MLA_WIDTHS)
    d = lambda *shape, dtype=jnp.bfloat16: _described(v5e[0], shape, dtype)
    lp = {"q_a_proj": d(hidden, cfg.q_lora_rank), "q_a_layernorm": d(cfg.q_lora_rank),
          "q_b_proj": d(cfg.q_lora_rank, heads * (dn + dr)),
          "kv_a_proj_with_mqa": d(hidden, cfg.kv_lora_rank + dr),
          "kv_a_layernorm": d(cfg.kv_lora_rank),
          "kv_b_proj": d(cfg.kv_lora_rank, heads * (dn + dv)), "o_proj": d(heads * dv, hidden)}

    def loss(x, lp, cos, sin, seg):
        return transformer._mla_attention(x, lp, cfg, cos, sin, seg, None).astype(jnp.float32).sum()

    KERNEL_REGISTRY.pin("attention", "pallas_flash")
    KERNEL_REGISTRY.pin("mla_qkv_rotary", "pallas")
    try:
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            d(b, s, hidden), lp, d(b, s, dr), d(b, s, dr), d(b, s, dtype=jnp.int32)
        ).compile().as_text()
    finally:
        KERNEL_REGISTRY.clear_pins()
    assert _kernel_instructions(text) == {"mla_qkv_rope_fwd": 1, "flash_fwd": 1, "flash_bwd_dkv": 1,
                                          "mla_qkv_rope_bwd": 1}
    wide = [line.split(", metadata")[0].strip() for line in text.splitlines()
            if re.search(r" = \(?(?:bf16|f32)\[2,(?:32,8192|8192,32),192\]", line)
            and not re.search(r" (?:custom-call|get-tuple-element|parameter|bitcast)\(", line)]
    assert len(wide) == 1 and " convert(" in wide[0] and wide[0].startswith("%convert"), wide


# the smoke's shapes (a fused gate_up of twice the width, as
# models/deepseek_v4.py's; E 128 at the Qwen3-30B-A3B widths), and the held
# experts' buffer of the joyai_llm_flash.train_packed_8k cell (16 of 256
# experts, 8,192 rows) at its gate/up and its down projection
GMM_SHAPES = chip_smoke.GMM_SHAPES + (
    dict(m=8192, k=2048, n=768, e=16), dict(m=8192, k=768, n=2048, e=16),
)


@pytest.mark.parametrize("shape", GMM_SHAPES,
                         ids=lambda s: f"m{s['m']}k{s['k']}n{s['n']}e{s['e']}")
@pytest.mark.parametrize("kernel", ["fwd", "dlhs", "drhs"])
def test_grouped_gemm_lowers_for_v5e(v5e, on_chip_kernels, kernel, shape):
    from veomni_tpu.ops.pallas import grouped_gemm as gg

    m, k, n, e = (shape[x] for x in ("m", "k", "n", "e"))
    tiles = gg.choose_tiles(m, k, n, e, jnp.dtype(jnp.bfloat16))
    assert gg._rows_vmem_bytes(tiles.fwd[0], k, tiles.fwd[1], 2) <= gg._VMEM_BUDGET
    assert gg._rows_vmem_bytes(tiles.dlhs[0], n, tiles.dlhs[1], 2) <= gg._VMEM_BUDGET
    assert gg._drhs_vmem_bytes(*tiles.drhs, 2) <= gg._VMEM_BUDGET
    lhs = _described(v5e[0], (m, k), jnp.bfloat16)
    g = _described(v5e[0], (m, n), jnp.bfloat16)
    rhs = _described(v5e[0], (e, k, n), jnp.bfloat16)
    starts = _described(v5e[0], (e + 1,), jnp.int32)
    fn, args = {
        "fwd": (lambda a, w, st: gg._gmm_rows(a, w, st, *tiles.fwd, name="gmm_fwd"),
                (lhs, rhs, starts)),
        "dlhs": (lambda a, w, st: gg._gmm_rows(a, w, st, *tiles.dlhs, name="gmm_dlhs"),
                 (g, rhs, starts)),
        "drhs": (lambda a, b_, st: gg._gmm_drhs(a, b_, st, *tiles.drhs), (lhs, g, starts)),
    }[kernel]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert _kernel_instructions(text) == {
        {"fwd": "gmm_fwd", "dlhs": "gmm_dlhs", "drhs": "gmm_drhs"}[kernel]: 1}


@pytest.fixture(scope="module")
def smoke_step(v5e):
    """The train step of configs/text/qwen3_0p6b_v5e.yaml, as the trainer
    builds it, compiled once for one described chip (several tests read it)."""
    with pytest.MonkeyPatch.context() as monkeypatch:
        _for_mosaic(monkeypatch)
        return _compile_smoke_step(v5e)


def _compile_smoke_step(v5e, config=chip_smoke.TRAIN_CONFIG, **pinned_ops):
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
        return step.lower(state, batch).compile()


def test_smoke_train_step_fits_one_v5e(smoke_step):
    """The kernels are in it, and arguments + temporaries leave room in
    16 GiB."""
    compiled = smoke_step
    # a layer body's forward, then the recomputed forward and the backward:
    # flash fwd, fwd + the fused backward; the q/k norm + rope fwd, fwd + bwd
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 6
    assert _kernel_instructions(text) == {"flash_fwd": 2, "flash_bwd_dkv": 1,
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
    fused, split = _flash_bwd_calls()
    compiled = _compile_smoke_step(v5e, "configs/text/granite_4_0_h_micro_v5e.yaml")
    assert _flash_bwd_calls() == (fused + 1, split)  # the one call site, fused
    assert _kernel_instructions(compiled.as_text()) == {"flash_fwd": 2, "flash_bwd_dkv": 1}
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 8.5 * GIB  # f32 params + AdamW moments
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5 * GIB


def test_kimi_linear_train_step_fits_one_v5e(v5e, on_chip_kernels):
    """The step of configs/text/kimi_linear_48b_a3b_v5e.yaml (the benchmark's
    fourth cell: 602 M parameters at 16 bytes, ONE row of 8192): its one MLA
    layer runs the flash kernels and, NoPE as it is, the split + rope kernels
    under the identity rotation (the counter says the kernel took the call);
    the four recurrences are XLA; arguments + temporaries leave room in the
    15.75 GiB a v5e gives a program."""
    from veomni_tpu.observability.metrics import get_registry

    taken = get_registry().counter("attn.mla_qkv_rope.calls_kernel")
    handed = get_registry().counter("attn.mla_qkv_rope.calls_handed_over")
    before = (taken.value, handed.value)
    fused, split = _flash_bwd_calls()
    compiled = _compile_smoke_step(v5e, "configs/text/kimi_linear_48b_a3b_v5e.yaml",
                                   mla_qkv_rotary="pallas")
    assert (taken.value - before[0], handed.value - before[1]) == (1, 0)
    assert _flash_bwd_calls() == (fused + 1, split)  # the MLA layer's backward, fused
    assert _kernel_instructions(compiled.as_text()) == {
        "flash_fwd": 2, "flash_bwd_dkv": 1, "mla_qkv_rope_fwd": 2, "mla_qkv_rope_bwd": 1}
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
    import re

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


# --------------------------------------------------------------------------
# 2. channels, tables, and the smoke off the chip
# --------------------------------------------------------------------------
def test_perf_flags_go_to_libtpu_init_args_once(monkeypatch):
    from veomni_tpu.utils.xla_flags import _PERF_FLAGS, apply_performance_flags

    monkeypatch.setenv("VEOMNI_COMPILATION_CACHE", "0")  # flags only
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    monkeypatch.setenv(
        "LIBTPU_INIT_ARGS", "--xla_tpu_enable_latency_hiding_scheduler=false")
    assert apply_performance_flags() is True
    assert apply_performance_flags() is True  # idempotent
    assert "--xla_tpu_" not in os.environ["XLA_FLAGS"]
    toks = os.environ["LIBTPU_INIT_ARGS"].split()
    # the caller's own value stands; every flag is there exactly once
    assert "--xla_tpu_enable_latency_hiding_scheduler=false" in toks
    assert sorted(t.split("=")[0] for t in toks) == sorted(
        f.split("=")[0] for f in _PERF_FLAGS)
    monkeypatch.setenv("VEOMNI_XLA_PERF_FLAGS", "0")
    monkeypatch.delenv("LIBTPU_INIT_ARGS")
    assert apply_performance_flags() is False
    assert "LIBTPU_INIT_ARGS" not in os.environ


@pytest.mark.parametrize("from_env", [True, False], ids=["env_dir", "checkout_dir"])
def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path, from_env):
    from veomni_tpu.utils import xla_flags

    updates = {}
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.__setitem__(k, v))
    monkeypatch.delenv("VEOMNI_COMPILATION_CACHE", raising=False)
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert xla_flags.enable_compilation_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in updates  # JAX reads the variable
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert xla_flags.enable_compilation_cache() == want
        assert updates["jax_compilation_cache_dir"] == want
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
    assert updates["jax_persistent_cache_min_entry_size_bytes"] == -1


def test_peak_tables_raise_on_unlisted_accelerator(monkeypatch):
    from veomni_tpu.utils import device

    class FakeDevice:
        platform = "tpu"
        device_kind = "TPU v99 mega"

    monkeypatch.setattr(device.jax, "devices", lambda: [FakeDevice()])
    device._device_peaks.cache_clear()
    try:
        for fn in (device.get_device_peak_flops, device.get_device_peak_bandwidth,
                   device.get_device_peak_interconnect_bandwidth):
            with pytest.raises(KeyError, match="TPU v99 mega"):
                fn()
        FakeDevice.device_kind = "TPU v5 lite"
        assert device.get_device_peak_flops() == 197e12
        assert device.get_device_peak_bandwidth() == 819e9
    finally:
        device._device_peaks.cache_clear()


def test_chip_smoke_fails_without_a_chip():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "VEOMNI_COMPILATION_CACHE": "0"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert chip_smoke.NO_CHIP_MSG.format(platform="cpu") in proc.stderr
    assert '"ok"' not in proc.stdout


TOY_DENSE = {
    "model_type": "qwen3", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "qk_norm": True,
    "tie_word_embeddings": True,
}


@pytest.fixture
def toy_run(monkeypatch):
    """The phases call the entry points, which set up flags and the compile
    cache: keep both out of the test process."""
    monkeypatch.setenv("VEOMNI_COMPILATION_CACHE", "0")
    monkeypatch.setenv("VEOMNI_XLA_PERF_FLAGS", "0")
    monkeypatch.chdir(REPO)  # phase_train changes directory; undo it


def test_smoke_phase_kernels_at_toy_size(toy_run):
    doc = chip_smoke.phase_kernels(
        flash=dict(b=1, s=256, hq=2, hkv=1, d=64),
        gmm=(dict(m=256, k=128, n=128, e=4), dict(m=256, k=128, n=128, e=8)),
    )
    assert doc["flash"]["err"]["dq"] <= chip_smoke.KERNEL_TOL
    assert [g["groups"]["empty"] > 0 for g in doc["gmm"]] == [True, True]
    json.dumps(doc)  # a phase's result is one JSON line


def test_smoke_phase_train_at_toy_size(toy_run):
    doc = chip_smoke.phase_train(overrides=[
        "--model.config_overrides=" + json.dumps(TOY_DENSE),
        "--data.max_seq_len=256", "--train.micro_batch_size=2", "--train.lr=1e-2",
    ])
    assert doc["steps"] == 8 and doc["train_step_traces"] == 1
    assert doc["losses"][-1] < doc["losses"][0]
    assert doc["resolved"]["attention"] == "xla"  # the CPU's; no kernel claimed
    assert not os.path.exists(os.path.join(REPO, "output", "chip_smoke", "train"))
    json.dumps(doc)


def test_smoke_phase_serve_at_toy_size(toy_run):
    # preset "": the tiny demo model of scripts/serve.py
    doc = chip_smoke.phase_serve(preset="", prompt_lens=(20, 40), n_requests=6,
                                 shared_prefix=16, max_new=8)
    assert doc["completed"] == 6 and doc["prefix_hits"] > 0
    assert doc["tokens_equal_to_greedy_generate"] == doc["tokens_total"] == 48
    json.dumps(doc)


def test_smoke_phase_serve_catches_a_wrong_token(toy_run, monkeypatch):
    from veomni_tpu.models import decode

    real = decode.greedy_generate

    def off_by_one(params, cfg, prompt, **kw):
        ids = real(params, cfg, prompt, **kw)
        ids[len(prompt) + 3] = (ids[len(prompt) + 3] + 1) % cfg.vocab_size
        return ids

    monkeypatch.setattr(decode, "greedy_generate", off_by_one)
    with pytest.raises(AssertionError, match="logit gap"):
        chip_smoke.phase_serve(preset="", prompt_lens=(20,), n_requests=2,
                               shared_prefix=16, max_new=8)


def test_smoke_phase_multichip_on_four_virtual_devices(toy_run):
    moe = dict(chip_smoke.MOE_BLOCKS, vocab_size=512, hidden_size=64,
               intermediate_size=128, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, num_experts=8, num_experts_per_tok=2,
               moe_intermediate_size=32)
    doc = chip_smoke.phase_multichip(dense_overrides=TOY_DENSE, dense_seq=64,
                                     dense_rows=2, dense_steps=2, moe=moe,
                                     moe_seq=64, moe_rows=4)
    assert doc["dense"]["four_devices"]["mesh"] == {"fsdp": 2, "ulysses": 2}
    assert doc["moe"]["four_devices"]["mesh"] == {"ep": 2, "fsdp": 2}
    assert doc["moe"]["four_devices"]["collectives"]["all-to-all"] > 0
    json.dumps(doc)
