"""What can be known about the chip without the chip: the kernels.

AOT compiles for a DESCRIBED ``v5e:2x2`` (the TPU compiler is installed; no
device is attached): the Pallas kernels, forward and backward, and the two
scans XLA compiles, at the widths ``chip_smoke.py`` and the benchmark's cells
run them at. Interpret mode and the attention impl are steered here, in the
test: the program picks both from ``jax.default_backend()``, which is the CPU.
The whole train steps are ``test_chip_compile_steps.py``'s; the flag and cache
channels and the smoke's phases at toy size are ``test_chip_smoke.py``'s.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from described_chip import described, kernel_instructions, on_chip_kernels, v5e  # noqa: F401

import chip_smoke  # (repo root is on sys.path via conftest)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
    q = described(v5e[0], (b, s, hq, d), jnp.bfloat16)
    kv = described(v5e[0], (b, s, hkv, d), jnp.bfloat16)
    v = described(v5e[0], (b, s, hkv, c.get("dv", d)), jnp.bfloat16)
    seg = described(v5e[0], (b, s), jnp.int32) if c["segments"] else None

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
    assert kernel_instructions(text) == want


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_ssd_scan_lowers_for_v5e_within_a_chunks_memory(v5e, direction):
    """The state-space scan at the granite cell's shapes (one row of 8192, 64
    heads of 64, state 128, chunks of 256): it compiles for the chip, and its
    temporaries stay a chunk's, not the 0.5 GB a whole ``[H, S/c, c, c]`` f32
    decay matrix would take (forward AND backward)."""
    from veomni_tpu import ops

    b, s, h, p, g, n = 1, 8192, 64, 64, 1, 128
    x = described(v5e[0], (b, s, h, p), jnp.bfloat16)
    dt = described(v5e[0], (b, s, h), jnp.float32)
    head = described(v5e[0], (h,), jnp.float32)
    bc = described(v5e[0], (b, s, g, n), jnp.bfloat16)
    seg = described(v5e[0], (b, s), jnp.int32)

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
    x = described(v5e[0], (b, s, h, d), jnp.bfloat16)
    g = described(v5e[0], (b, s, h, d), jnp.float32)
    beta = described(v5e[0], (b, s, h), jnp.float32)
    seg = described(v5e[0], (b, s), jnp.int32)

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
    args = _qk_norm_rope_args(c, lambda shape: described(v5e[0], shape, jnp.bfloat16))
    fwd, loss = _qk_norm_rope_fns(c.get("zero_centered", False))
    wrt = (0, 1, 4, 5) if c["normed"] else (0, 1)
    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=wrt)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert kernel_instructions(text) == {"qk_norm_rope_" + direction: 1}


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
    assert kernel_instructions(text) == {"qk_norm_rope_bwd": 1}
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
    args = _mla_qkv_rope_args(c, lambda shape: described(v5e[0], shape, jnp.bfloat16))
    fwd, loss = _mla_qkv_rope_fns(c["interleaved"])
    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert kernel_instructions(text) == {"mla_qkv_rope_" + direction: 1}


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
    assert kernel_instructions(text) == {"mla_qkv_rope_bwd": 1}
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
    d = lambda *shape, dtype=jnp.bfloat16: described(v5e[0], shape, dtype)
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
    assert kernel_instructions(text) == {"mla_qkv_rope_fwd": 1, "flash_fwd": 1, "flash_bwd_dkv": 1,
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
    lhs = described(v5e[0], (m, k), jnp.bfloat16)
    g = described(v5e[0], (m, n), jnp.bfloat16)
    rhs = described(v5e[0], (e, k, n), jnp.bfloat16)
    starts = described(v5e[0], (e + 1,), jnp.int32)
    fn, args = {
        "fwd": (lambda a, w, st: gg._gmm_rows(a, w, st, *tiles.fwd, name="gmm_fwd"),
                (lhs, rhs, starts)),
        "dlhs": (lambda a, w, st: gg._gmm_rows(a, w, st, *tiles.dlhs, name="gmm_dlhs"),
                 (g, rhs, starts)),
        "drhs": (lambda a, b_, st: gg._gmm_drhs(a, b_, st, *tiles.drhs), (lhs, g, starts)),
    }[kernel]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert kernel_instructions(text) == {
        {"fwd": "gmm_fwd", "dlhs": "gmm_dlhs", "drhs": "gmm_drhs"}[kernel]: 1}


# the serve cell's decode attention (qwen3_0p6b.serve_chat_over: 32 slots, 16 /
# 8 heads of 128, pages of 16, a pool of 2,048 blocks) at its two table
# widths, and at the narrowest the benchmark's ladder runs
@pytest.mark.parametrize("nb", [1, 256, 512])
def test_paged_attend_lowers_for_v5e_and_reads_the_pool_in_place(v5e, on_chip_kernels, nb):
    """The kernel alone, inside the compiler's scoped VMEM limit (it sets no
    limit of its own: two chunks of K and V are 2 MiB); the pool reaches it
    as a bitcast of the ``[NB, BS, hkv, d]`` argument, not as a copy."""
    from veomni_tpu.ops.pallas.paged_attention import paged_attention

    s, pool_blocks, bs, hkv, hq, d = 32, 2048, 16, 8, 16, 128
    q = described(v5e[0], (s, 1, hq, d), jnp.bfloat16)
    pool = described(v5e[0], (pool_blocks, bs, hkv, d), jnp.bfloat16)
    tables = described(v5e[0], (s, nb), jnp.int32)
    positions = described(v5e[0], (s,), jnp.int32)

    def attend(q, k_pool, v_pool, tables, positions):
        valid = jnp.arange(nb * bs)[None, None] <= positions[:, None, None]
        return paged_attention(q, k_pool, v_pool, tables, valid, num_rep=hq // hkv,
                               scale=d ** -0.5)

    compiled = jax.jit(attend).lower(q, pool, pool, tables, positions).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert kernel_instructions(text) == {"paged_attend": 1}
    call = next(line for line in text.splitlines() if "paged_attend." in line.split(" = ")[0])
    assert "paged.attend" in call  # the scope a trace files it by
    pool_sized = re.compile(r"= bf16\[%d,\d+(,\d+)?,%d\]\S* (\w[\w-]*)\(" % (pool_blocks, d))
    assert {m.group(2) for m in map(pool_sized.search, text.splitlines()) if m} <= {
        "bitcast", "parameter"}
    # nothing at the table's width beside the mask (4 B a (token, head) column)
    assert compiled.memory_analysis().temp_size_in_bytes <= s * nb * bs * hkv * 4 + 2 ** 20


# the serve cell's paged steps with a short stack (4 layers of qwen3_0p6b in
# bfloat16, 32 slots, a pool of 2,048 blocks of 16): the K/V stack is the
# layer scan's carry, so no step may slice, write back, copy or stack
# anything of a layer's pool's size (PERF.md, PR 48: 22 GB a tick at 28 layers)
PAGED_STEPS = {"decode_nb256": 256, "decode_nb512": 512, "prefill_cb32_nb8": 8}


def _compile_paged_step(v5e, step, sampled=False):
    """One of ``PAGED_STEPS`` compiled at the published widths of four layers
    for the described chip; ``sampled``: the decode step as the engine jits
    it, the per-slot key split and ``sample_tokens`` behind the logits.
    Returns (the compiled step, cfg, the pool's [layers, blocks, block size])."""
    import yaml

    from veomni_tpu.models import build_foundation_model
    from veomni_tpu.models import decode as dm
    from veomni_tpu.models.auto import build_config
    from veomni_tpu.ops.kernel_registry import KERNEL_REGISTRY

    with open(os.path.join(REPO, "configs", "text", "qwen3_0p6b_v5e.yaml")) as f:
        widths = yaml.safe_load(f)["model"]["config_overrides"]
    layers, slots, pool_blocks, bs = 4, 32, 2048, 16
    cfg = build_config(**dict(widths, num_hidden_layers=layers), dtype="bfloat16",
                       param_dtype="bfloat16")
    family = build_foundation_model(config=cfg).family
    params = jax.tree.map(
        lambda leaf: described(v5e[0], leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: family.init_params(jax.random.PRNGKey(0), cfg)))
    hkv, d = cfg.num_key_value_heads, cfg.head_dim
    pool = described(v5e[0], (layers, pool_blocks, bs, hkv, d), jnp.bfloat16)
    nb = PAGED_STEPS[step]
    if step.startswith("decode"):
        fn = lambda p, k, v, tables, pos, tok: dm.paged_decode_step(p, cfg, (k, v), tables, pos, tok)
        args = [described(v5e[0], (slots, nb), jnp.int32), described(v5e[0], (slots,), jnp.int32),
                described(v5e[0], (slots,), jnp.int32)]
        if sampled:
            def fn(p, k, v, tables, pos, tok, keys, temps, top_ks, top_ps):
                logits, pools = dm.paged_decode_step(p, cfg, (k, v), tables, pos, tok)
                split = jax.vmap(lambda key: jax.random.split(key, 2))(keys)
                return dm.sample_tokens(logits, split[:, 1], temps, top_ks, top_ps), split[:, 0], pools

            args += [described(v5e[0], (slots, 2), jnp.uint32), described(v5e[0], (slots,), jnp.float32),
                     described(v5e[0], (slots,), jnp.int32), described(v5e[0], (slots,), jnp.float32)]
    else:
        fn = lambda p, k, v, table, start, tok, n: dm.paged_prefill_step(
            p, cfg, (k, v), table, start, tok, n, 32)
        args = [described(v5e[0], (nb,), jnp.int32), described(v5e[0], (), jnp.int32),
                described(v5e[0], (32,), jnp.int32), described(v5e[0], (), jnp.int32)]
    KERNEL_REGISTRY.pin("paged_attention", "pallas")  # the chip's own choice; here the CPU's is made
    try:
        compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(params, pool, pool, *args).compile()
    finally:
        KERNEL_REGISTRY.clear_pins()
    return compiled, cfg, (layers, pool_blocks, bs)


@pytest.mark.parametrize("step", sorted(PAGED_STEPS))
def test_paged_steps_carry_the_pool_and_move_nothing_of_its_size(v5e, on_chip_kernels, step):
    compiled, cfg, (layers, pool_blocks, bs) = _compile_paged_step(v5e, step)
    hkv, d = cfg.num_key_value_heads, cfg.head_dim
    text = compiled.as_text()
    if step.startswith("decode"):
        # one kernel, in the loop's body, reading the carried stack
        assert kernel_instructions(text) == {"paged_attend": 1}
        call = next(line for line in text.splitlines() if "paged_attend." in line.split(" = ")[0])
        assert "while/body" in call and "paged.attend" in call
    # every instruction whose result is a layer's pool or the whole stack, by
    # whatever view ([NB, BS, hkv, d], [L, NB, ...], [L*NB, ...], the kernel's
    # [.., BS*hkv, d]): its name and opcode
    one_layer = pool_blocks * bs * hkv * d
    instruction = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = bf16\[([\d,]+)\]\S* ([\w-]+)\(")
    moved = []
    for m in filter(None, map(instruction.match, text.splitlines())):
        dims = [int(n) for n in m.group(2).split(",")]
        if math.prod(dims) in (one_layer, layers * one_layer) and dims[-1] == d:
            moved.append((m.group(1), m.group(3)))
    assert moved, "no pool-sized instruction found: the pattern no longer reads the text"
    for name, opcode in moved:
        assert not any(word in name or word in opcode
                       for word in ("copy", "dynamic-slice", "dynamic-update-slice",
                                    "dynamic_slice", "dynamic_update_slice")), (name, opcode)
        assert opcode in ("parameter", "bitcast", "get-tuple-element", "scatter", "fusion"), (
            name, opcode)
    # and no second pool among the temporaries: under one layer's K and V
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * one_layer * 2


def test_the_decode_step_with_its_sampler_sorts_nothing_and_switches_three_ways(v5e, on_chip_kernels):
    """The engine's decode step with its sampler (PR 49): no instruction of
    the compiled step orders a row (`sort`, which is what `lax.top_k` becomes
    here too), the sampler is one `conditional` of three branches, and the
    greedy tick's branch hands the `argmax` on and does nothing else."""
    compiled, _, _ = _compile_paged_step(v5e, "decode_nb256", sampled=True)
    text = compiled.as_text()
    assert not re.search(r" sort\(", text)
    switches = [line for line in text.splitlines()
                if re.search(r" conditional\(", line) and "/sampler/" in line]
    assert len(switches) == 1, switches
    branches = re.search(r"branch_computations=\{([^}]*)\}", switches[0]).group(1).split(",")
    assert len(branches) == 3
    greedy = re.search(r"\n%s \([^\n]*\{\n(.*?)\n\}" % re.escape(branches[0].strip()), text, re.S)
    assert greedy and len(greedy.group(1).splitlines()) <= 3, greedy
