"""chip_smoke.py: does the main path still run on the chip?

Drives the system once, at the full width and depth of Qwen3-0.6B, through
the entry points a user would call, and checks what comes out:

  kernels  both Pallas kernels, forward and backward, against their XLA
           references (flash attention vs the dense impl at highest
           precision; grouped GEMM vs ``xla_ragged``)
  train    ``tasks/train_text.py`` ``main()`` on
           ``configs/text/qwen3_0p6b_v5e.yaml``: packed sequences from a
           seeded generator, a few optimizer steps on a repeated batch, the
           final checkpoint
  serve    the engine behind ``scripts/serve.py --preset qwen3_0p6b``
           answering synthetic requests, against ``greedy_generate``

``--chips 4`` runs instead, and only, the sharded path and what it is
compared with: the dense model on ``fsdp=2 x ulysses=2`` and a Qwen3-MoE
block stack on ``ep=2 x fsdp=2``, each against one device of the same
process.

One process per chip: this parent never imports JAX and runs each phase as a
child, one after another. A phase prints one JSON line; a phase that fails
fails the run. Without a TPU the first child exits non-zero with
``NO_CHIP_MSG``. The last line of a good run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

The phase functions take their sizes as arguments so that
``tests/test_chip_smoke.py`` can rehearse them at toy size on the CPU;
the script itself has no size options. Wall times are printed as
``smoke_seconds``: they include whatever the phase compiled and are not
rates.
"""

import argparse
import json
import math
import os
import shutil
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

NO_CHIP_MSG = "chip_smoke: JAX found no TPU (platform {platform!r}); nothing was run"
TRAIN_CONFIG = os.path.join("configs", "text", "qwen3_0p6b_v5e.yaml")
# whole-run limit is 1200 s, compilation included: children share this budget
RUN_BUDGET_S = 1150.0

# --------------------------------------------------------------------------
# sizes of the real run (tests pass toy ones)
# --------------------------------------------------------------------------
# the dense preset's attention shape: B 4 x S 4096, 16 q / 8 kv heads x 128
FLASH_SHAPE = dict(b=4, s=4096, hq=16, hkv=8, d=128)
# an MoE up-projection at tokens*top_k = 8192 rows, and the Qwen3-30B-A3B
# expert shape (K 2048 x N 768) at its full 128 experts
GMM_SHAPES = (
    dict(m=8192, k=2048, n=1536, e=16),
    dict(m=8192, k=2048, n=768, e=128),
)
# max |got - ref| / max |ref|: bf16 has 8 bits of mantissa (eps 7.8e-3) and
# both sides round their outputs (and the kernels their probabilities) to it
KERNEL_TOL = 2e-2
# Qwen/Qwen3-30B-A3B config.json widths; depth cut from 48 to 2 layers and
# params kept in bf16 so that the one-device side of the comparison (1.9 B
# params and their gradients) fits one 16 GB chip
MOE_BLOCKS = dict(
    model_type="qwen3_moe", vocab_size=151936, hidden_size=2048,
    intermediate_size=6144, num_hidden_layers=2, num_attention_heads=32,
    num_key_value_heads=4, head_dim=128, qk_norm=True,
    tie_word_embeddings=False, rope_theta=1000000.0,
    max_position_embeddings=40960, rms_norm_eps=1e-6, num_experts=128,
    num_experts_per_tok=8, moe_intermediate_size=768, norm_topk_prob=True,
    param_dtype="bfloat16",
)
# sharded vs one device: same math, other reduction orders, bf16 compute
MULTICHIP_LOSS_RTOL = 1e-2
MULTICHIP_GNORM_RTOL = 5e-2
# a greedy token may differ from the reference only where the reference's
# own f32 logits put the two tokens this close (logit std at random init is
# about 0.6): bf16 rounding decides such a tie differently in a batch of 4
TIE_TOL = 2e-2


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------
def _device_doc():
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _peak_bytes(device=None):
    """``peak_bytes_in_use`` of this process on one device (None where the
    backend keeps no such statistic, as the CPU's does not)."""
    import jax

    stats = (device or jax.devices()[0]).memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _resolved(*ops):
    from veomni_tpu.ops import KERNEL_REGISTRY

    return {op: KERNEL_REGISTRY.resolved_name(op) for op in ops}


def _scaled_err(got, ref) -> float:
    import numpy as np

    got = np.asarray(got).astype(np.float32)
    ref = np.asarray(ref).astype(np.float32)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-30))


def _timed(fn, *args):
    """(result, seconds) of one call that ends in block_until_ready."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def _takes_kernel(fn, *args) -> bool:
    """Whether ``fn`` on these shapes reaches a pallas_call (and was not
    handed to an XLA impl by the public wrapper)."""
    import jax

    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


def _seeded_docs(n_docs: int, doc_len: int, vocab: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, doc_len).tolist() for _ in range(n_docs)]


# --------------------------------------------------------------------------
# phase: kernels
# --------------------------------------------------------------------------
def _check_flash(shape, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from veomni_tpu.ops.attention import _attention_dense
    from veomni_tpu.ops.pallas.flash_attention import flash_attention

    b, s, hq, hkv, d = (shape[k] for k in ("b", "s", "hq", "hkv", "d"))
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(kq, (b, s, hq, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, s, hkv, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, s, hkv, d), jnp.bfloat16)
    w = jax.random.normal(kw, (b, s, hq, d), jnp.bfloat16)  # cotangent
    # packed rows: three documents of uneven length, then a tail of padding
    rng = np.random.default_rng(seed)
    seg = np.zeros((b, s), np.int32)
    for row in range(b):
        cuts = np.sort(rng.choice(np.arange(1, s - s // 16), 2, replace=False))
        seg[row, :cuts[0]] = 1
        seg[row, cuts[0]:cuts[1]] = 2
        seg[row, cuts[1]:s - s // 32] = 3
    seg = jnp.asarray(seg)

    # seg and w are arguments, not closed over: a closed-over array becomes a
    # constant of the executable (67 MB here), too large for the compile cache
    def flash(q, k, v, seg):
        return flash_attention(q, k, v, segment_ids=seg, causal=True)

    if not _takes_kernel(flash, q, k, v, seg):
        raise AssertionError(f"pallas_flash handed {shape} to an XLA impl")

    def flash_loss(q, k, v, seg, w):
        return (flash(q, k, v, seg).astype(jnp.float32) * w).sum()

    fwd = jax.jit(flash)
    bwd = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))
    out, fwd_cold_s = _timed(fwd, q, k, v, seg)
    grads, bwd_cold_s = _timed(bwd, q, k, v, seg, w)
    _, fwd_s = _timed(fwd, q, k, v, seg)
    _, bwd_s = _timed(bwd, q, k, v, seg, w)

    # dense reference in f32 at highest matmul precision, one row at a time:
    # its [H, S, S] f32 score tensors do not fit the chip at B 4
    @jax.jit
    def ref_row(q1, k1, v1, seg1, w1):
        with jax.default_matmul_precision("highest"):
            f32 = jnp.float32
            out1, vjp = jax.vjp(
                lambda *a: _attention_dense(*a, segment_ids=seg1, causal=True),
                q1.astype(f32), k1.astype(f32), v1.astype(f32),
            )
            return out1, vjp(w1.astype(f32))

    errs = {"out": 0.0, "dq": 0.0, "dk": 0.0, "dv": 0.0}
    for row in range(b):
        sl = slice(row, row + 1)
        ref_out, ref_grads = ref_row(q[sl], k[sl], v[sl], seg[sl], w[sl])
        pairs = [("out", out[sl], ref_out)] + [
            (n, g[sl], r) for n, g, r in zip(("dq", "dk", "dv"), grads, ref_grads)
        ]
        for name, got, ref in pairs:
            errs[name] = max(errs[name], _scaled_err(got, ref))
    bad = {n: e for n, e in errs.items() if not e <= KERNEL_TOL}
    if bad:
        raise AssertionError(f"pallas_flash vs dense xla at {shape}: {bad} > {KERNEL_TOL}")
    return {"shape": shape, "dtype": "bfloat16", "segments": "packed",
            "err": {n: round(e, 5) for n, e in errs.items()},
            "compile_and_first_run_seconds": round(fwd_cold_s + bwd_cold_s, 2),
            "smoke_seconds": {"fwd": round(fwd_s, 4), "fwd_bwd": round(bwd_s, 4)}}


def _check_gmm(shape, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from veomni_tpu.ops.group_gemm import _group_gemm_ragged
    from veomni_tpu.ops.pallas.grouped_gemm import pallas_group_gemm

    m, k, n, e = (shape[x] for x in ("m", "k", "n", "e"))
    kx, kw, kc = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (m, k), jnp.bfloat16)
    w = jax.random.normal(kw, (e, k, n), jnp.bfloat16) * 0.05
    cot = jax.random.normal(kc, (m, n), jnp.bfloat16)
    # uneven groups that cross tile boundaries; every fifth expert is empty
    rng = np.random.default_rng(seed)
    share = rng.dirichlet(np.full(e, 0.5))
    share[::5] = 0.0
    sizes = rng.multinomial(m, share / share.sum())
    gs = jnp.asarray(sizes, jnp.int32)

    if not _takes_kernel(pallas_group_gemm, x, w, gs):
        raise AssertionError(f"pallas_gmm handed {shape} to an XLA impl")

    def both(impl):
        def loss(x, w, cot):  # cot an argument: see _check_flash
            return (impl(x, w, gs).astype(jnp.float32) * cot).sum()

        return (jax.jit(lambda x, w: impl(x, w, gs)),
                jax.jit(jax.grad(loss, argnums=(0, 1))))

    p_fwd, p_bwd = both(pallas_group_gemm)
    r_fwd, r_bwd = both(_group_gemm_ragged)
    out, fwd_cold_s = _timed(p_fwd, x, w)
    grads, bwd_cold_s = _timed(p_bwd, x, w, cot)
    ref_out = r_fwd(x, w)
    ref_grads = r_bwd(x, w, cot)
    errs = {"out": _scaled_err(out, ref_out),
            "dlhs": _scaled_err(grads[0], ref_grads[0]),
            "drhs": _scaled_err(grads[1], ref_grads[1])}
    bad = {n_: e_ for n_, e_ in errs.items() if not e_ <= KERNEL_TOL}
    if bad:
        raise AssertionError(f"pallas_gmm vs xla_ragged at {shape}: {bad} > {KERNEL_TOL}")
    _, fwd_s = _timed(p_fwd, x, w)
    _, bwd_s = _timed(p_bwd, x, w, cot)
    _, ref_fwd_s = _timed(r_fwd, x, w)
    _, ref_bwd_s = _timed(r_bwd, x, w, cot)
    return {"shape": shape, "dtype": "bfloat16",
            "groups": {"empty": int((sizes == 0).sum()), "max": int(sizes.max())},
            "err": {n_: round(e_, 5) for n_, e_ in errs.items()},
            "compile_and_first_run_seconds": round(fwd_cold_s + bwd_cold_s, 2),
            "smoke_seconds": {"fwd": round(fwd_s, 4), "fwd_bwd": round(bwd_s, 4),
                              "xla_ragged_fwd": round(ref_fwd_s, 4),
                              "xla_ragged_fwd_bwd": round(ref_bwd_s, 4)}}


def phase_kernels(flash=FLASH_SHAPE, gmm=GMM_SHAPES, seed: int = 0) -> dict:
    t0 = time.perf_counter()
    return {
        "registry": _resolved("attention", "group_gemm"),
        "tolerance": KERNEL_TOL,
        "flash": _check_flash(dict(flash), seed),
        "gmm": [_check_gmm(dict(s), seed + i) for i, s in enumerate(gmm)],
        "peak_bytes_in_use": _peak_bytes(),
        "smoke_seconds": round(time.perf_counter() - t0, 1),
    }


# --------------------------------------------------------------------------
# phase: train
# --------------------------------------------------------------------------
def _load_script(rel_path: str, name: str):
    """Import an entry script (tasks/ and scripts/ are not packages)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(name, os.path.join(REPO, rel_path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def phase_train(overrides=(), seed: int = 0) -> dict:
    """``tasks/train_text.py main([TRAIN_CONFIG, *overrides])`` from the
    checkout's root, on data this function writes from ``seed``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from veomni_tpu.arguments import VeOmniArguments, parse_args
    from veomni_tpu.observability.cost import get_cost_census
    from veomni_tpu.observability.metrics import get_registry
    from veomni_tpu.train import train_step as ts

    t0 = time.perf_counter()
    os.chdir(REPO)  # the config's paths are relative to the checkout
    argv = [TRAIN_CONFIG, *overrides]
    args = parse_args(VeOmniArguments, argv)
    out_dir = args.train.output_dir
    shutil.rmtree(out_dir, ignore_errors=True)  # never resume an earlier run
    os.makedirs(out_dir)
    vocab = int(args.model.config_overrides["vocab_size"])
    seq, mb = args.data.max_seq_len, args.train.micro_batch_size
    per_row = args.data.samples_per_micro_batch
    docs = _seeded_docs(per_row * mb * len(jax.devices()), seq // per_row, vocab, seed)
    with open(args.data.train_path, "w") as f:
        for ids in docs:
            f.write(json.dumps({"input_ids": ids}) + "\n")

    traces0 = ts.TRACE_COUNTS["train_step"]
    try:
        trainer = _load_script("tasks/train_text.py", "train_text").main(argv)
        t_trained = time.perf_counter()

        # the run's own record: one line per sync step (log_steps: 1), then
        # one more at train end that repeats the last step
        rows = {}
        with open(os.path.join(out_dir, "metrics_rank0.jsonl")) as f:
            for line in f:
                row = json.loads(line)
                rows.setdefault(row["step"], row)
        losses = [float(rows[i]["loss"]) for i in sorted(rows)]
        gnorms = [float(rows[i]["grad_norm"]) for i in sorted(rows)]
        steps = args.train.train_steps
        if len(losses) != steps or steps < 6:
            raise AssertionError(f"{len(losses)} logged steps of {steps} (need >= 6)")
        if not all(map(math.isfinite, losses + gnorms)):
            raise AssertionError(f"non-finite loss/grad_norm: {losses} {gnorms}")
        # random init: about ln(vocab) plus half the logit variance
        if abs(losses[0] - math.log(vocab)) > 1.0:
            raise AssertionError(f"first loss {losses[0]} vs ln({vocab}) = {math.log(vocab):.3f}")
        if not losses[-1] < losses[0]:
            raise AssertionError(f"loss did not fall on a repeated batch: {losses}")
        traces = ts.TRACE_COUNTS["train_step"] - traces0
        if traces != 1:
            raise AssertionError(f"train_step traced {traces} times for {steps} steps")

        resolved = _resolved("attention", "group_gemm")
        custom_calls = None
        if resolved["attention"] == "pallas_flash":
            # the program the trainer ran, compiled again from its own
            # shapes (a persistent-cache hit where the cache is on)
            abstract = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                trainer.abstract_state, trainer.state_shardings,
            )
            batch = {k: jax.ShapeDtypeStruct(v.shape, jnp.int32,
                                             sharding=trainer.batch_shardings[k])
                     for k, v in trainer.current_batch.items()
                     if k in trainer.batch_shardings}
            custom_calls = trainer.train_step.lower(abstract, batch).compile().as_text().count(
                "tpu_custom_call")
            if not custom_calls:
                raise AssertionError("attention resolved to pallas_flash but the "
                                     "compiled step holds no tpu_custom_call")

        ckpt = os.path.join(out_dir, "checkpoints", f"global_step_{steps}")
        if not os.path.isdir(ckpt):
            raise AssertionError(f"no final checkpoint at {ckpt}")
        census = get_cost_census().latest("train_step")
        reg = get_registry()
        n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(trainer.abstract_state.params))
        return {
            "entry": "tasks/train_text.py main() + " + TRAIN_CONFIG,
            "params": n_params,
            "layers": trainer.model.config.num_hidden_layers,
            "hidden": trainer.model.config.hidden_size,
            "vocab": vocab, "seq_len": seq, "micro_batch": mb,
            "remat_policy": trainer.model.config.remat_policy,
            "steps": steps, "losses": [round(x, 4) for x in losses],
            "grad_norms": [round(x, 4) for x in gnorms],
            "ln_vocab": round(math.log(vocab), 4),
            "train_step_traces": traces,
            "resolved": resolved, "tpu_custom_calls": custom_calls,
            "compile_seconds": round(census.compile_time_s, 2),
            "compiler_argument_bytes": int(census.argument_bytes),
            "compiler_temp_bytes": int(census.temp_bytes),
            "final_save_seconds": round(
                reg.histogram_sum("span.ckpt.save") + reg.histogram_sum("span.ckpt.wait"), 2),
            "peak_bytes_in_use": _peak_bytes(),
            "smoke_seconds": round(t_trained - t0, 1),
        }
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)  # the checkpoint is 7 GB


# --------------------------------------------------------------------------
# phase: serve
# --------------------------------------------------------------------------
def _serve_prompts(lens, n, shared_prefix, vocab, seed):
    """n prompts cycling through ``lens``; odd ones open with one shared
    ``shared_prefix``-token prefix."""
    import numpy as np

    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, vocab, shared_prefix).tolist()
    prompts = []
    for i in range(n):
        want = lens[i % len(lens)]
        head = prefix if i % 2 else []
        prompts.append(head + rng.integers(1, vocab, want - len(head)).tolist())
    return prompts


def phase_serve(preset: str = "qwen3_0p6b", prompt_lens=(48, 100, 200),
                n_requests: int = 8, shared_prefix: int = 32,
                max_new: int = 32, seed: int = 0) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from veomni_tpu.models import decode as decode_mod
    from veomni_tpu.models.decode import greedy_generate
    from veomni_tpu.models.transformer import forward_logits
    from veomni_tpu.serving import EngineConfig, InferenceEngine, Request, SamplingParams

    t0 = time.perf_counter()
    serve = _load_script("scripts/serve.py", "serve")
    params, cfg = serve.build_model(preset, seed)
    engine = InferenceEngine(params, cfg, EngineConfig())  # serve.py's defaults

    def ask(prompts):
        reqs = [Request(prompt_ids=p, sampling=SamplingParams(max_new_tokens=max_new))
                for p in prompts]
        ids = [engine.submit(r) for r in reqs]
        outs = engine.run()
        return [outs[i] for i in ids]

    # warm-up on prompts of the same lengths; the checked pass must then
    # compile nothing
    ask(_serve_prompts(prompt_lens, n_requests, shared_prefix, cfg.vocab_size, seed + 1))
    t_warm = time.perf_counter()
    traces0 = dict(decode_mod.TRACE_COUNTS)
    prompts = _serve_prompts(prompt_lens, n_requests, shared_prefix, cfg.vocab_size, seed + 2)
    outs = ask(prompts)
    t_served = time.perf_counter()
    new_traces = {k: v - traces0[k] for k, v in decode_mod.TRACE_COUNTS.items()
                  if v != traces0[k]}
    if new_traces:
        raise AssertionError(f"traces after warm-up: {new_traces}")
    incomplete = [o.request_id for o in outs
                  if o.finish_reason != "length" or len(o.token_ids) != max_new]
    if incomplete:
        raise AssertionError(f"requests did not complete: {incomplete}")
    # token for token against the contiguous-cache path
    matched, near_ties = 0, []
    for prompt, out in zip(prompts, outs):
        ref = greedy_generate(params, cfg, prompt, max_new_tokens=max_new)[len(prompt):]
        same = next((i for i, (a, b) in enumerate(zip(out.token_ids, ref)) if a != b),
                    max_new)
        matched += same
        if same == max_new:
            continue
        # first differing token: admitted only as a tie under bf16, judged
        # by the plain forward pass's f32 logits on the common prefix
        ids = prompt + out.token_ids[:same]
        width = 1 << (len(ids) - 1).bit_length()
        padded = jnp.zeros((1, width), jnp.int32).at[0, :len(ids)].set(jnp.asarray(ids))
        logits = forward_logits(params, cfg, padded, jnp.arange(width)[None])
        row = np.asarray(logits[0, len(ids) - 1].astype(jnp.float32))
        gap = abs(float(row[ref[same]] - row[out.token_ids[same]]))
        if gap > TIE_TOL:
            raise AssertionError(
                f"request {out.request_id} token {same}: engine {out.token_ids[same]} vs "
                f"greedy_generate {ref[same]}, logit gap {gap:.4f} > {TIE_TOL}")
        near_ties.append({"request": out.request_id, "token": same, "logit_gap": round(gap, 5)})
    total = n_requests * max_new
    if matched < total // 2:
        raise AssertionError(f"only {matched}/{total} tokens compared equal: {near_ties}")
    prefix_hits = sum(1 for o in outs if o.cached_tokens > 0)
    if not prefix_hits:
        raise AssertionError("no request hit the prefix cache")
    return {
        "entry": f"scripts/serve.py build_model({preset!r}) + InferenceEngine(EngineConfig())",
        "layers": cfg.num_hidden_layers, "hidden": cfg.hidden_size, "vocab": cfg.vocab_size,
        "requests": n_requests, "prompt_lens": [len(p) for p in prompts],
        "new_tokens": max_new, "completed": len(outs),
        "tokens_equal_to_greedy_generate": matched, "tokens_total": total,
        "near_tie_divergences": near_ties, "tie_tolerance": TIE_TOL,
        "prefix_hits": prefix_hits,
        "cached_tokens": sum(o.cached_tokens for o in outs),
        "traces_warmup": {k: v for k, v in traces0.items() if v},
        "traces_after_warmup": new_traces,
        "resolved": _resolved("paged_attention", "paged_prefill_attention", "decode_matmul"),
        "peak_bytes_in_use": _peak_bytes(),
        "smoke_seconds": {"build_and_warmup": round(t_warm - t0, 1),
                          "checked_pass": round(t_served - t_warm, 2)},
    }


# --------------------------------------------------------------------------
# phase: multichip (--chips 4)
# --------------------------------------------------------------------------
def _packed_batch(n_rows: int, seq: int, vocab: int, seed: int, per_row: int = 4):
    """[n_rows, seq] packed batch through the trainer's own collator."""
    from veomni_tpu.data.data_collator import TextPackingCollator

    docs = _seeded_docs(n_rows * per_row, seq // per_row, vocab, seed)
    return TextPackingCollator(seq, n_rows)([{"input_ids": d} for d in docs])


def _layout(tree, devices) -> dict:
    """How a pytree of global arrays lies on the devices: global bytes and
    the bytes each device holds."""
    import jax

    held = {d.id: 0 for d in devices}
    total = 0
    for leaf in jax.tree.leaves(tree):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    return {"global_bytes": int(total), "bytes_per_device": [int(v) for v in held.values()]}


def _assert_spread(layout, in_use, what):
    """Really sharded: no device holds more than 0.3 of the global bytes
    (0.25 plus small replicated leaves), and bytes_in_use is about equal."""
    worst = max(layout["bytes_per_device"]) / layout["global_bytes"]
    if worst > 0.3:
        raise AssertionError(f"{what}: a device holds {worst:.2f} of the state: {layout}")
    if None not in in_use and max(in_use) > 1.25 * min(in_use):
        raise AssertionError(f"{what}: bytes_in_use uneven across devices: {in_use}")


def _dense_steps(devices, mesh_kwargs, cfg, batch_np, steps, lr):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from veomni_tpu.models import build_foundation_model
    from veomni_tpu.observability.comm import get_comm_census
    from veomni_tpu.optim import build_lr_scheduler, build_optimizer
    from veomni_tpu.parallel import init_parallel_state, use_parallel_state
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state
    from veomni_tpu.train import build_train_state, build_train_step
    from veomni_tpu.train.train_step import _batch_bucket, resolve_state_shardings

    destroy_parallel_state()
    ps = init_parallel_state(devices=devices, **mesh_kwargs)
    with use_parallel_state(ps):
        model = build_foundation_model(config=cfg)
        opt = build_optimizer(
            model.abstract(), optimizer="adamw",
            lr=build_lr_scheduler("constant", lr=lr, train_steps=steps),
        )

        def make_state(rng):
            return build_train_state(model.family.init_params(rng, cfg), opt)

        key = jax.random.PRNGKey(0)
        shardings = resolve_state_shardings(
            jax.eval_shape(make_state, key), model.get_parallel_plan(), ps)
        state = jax.jit(make_state, out_shardings=shardings)(key)
        batch_sh = {k: NamedSharding(ps.mesh, P(None, ps.dp_axes, ps.sp_axes))
                    for k in batch_np}
        step = build_train_step(model.loss_fn, opt, ps, state_shardings=shardings,
                                batch_shardings=batch_sh)
        batch = {k: jax.device_put(v[None], batch_sh[k]) for k, v in batch_np.items()}
        losses, gnorms = [], []
        for _ in range(steps):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
            gnorms.append(float(metrics["grad_norm"]))
        comm = get_comm_census().get("train_step", _batch_bucket(batch))
        return {
            "mesh": {k: v for k, v in ps.mesh.shape.items() if v > 1},
            "losses": losses, "grad_norms": gnorms,
            "state": _layout(state, devices),
            "bytes_in_use": [(d.memory_stats() or {}).get("bytes_in_use") for d in devices],
            "collectives": dict(comm.counts_by_kind) if comm else {},
        }


def _moe_loss_gnorm(devices, mesh_kwargs, cfg, batch_np):
    import jax
    import jax.numpy as jnp

    from veomni_tpu.models import build_foundation_model
    from veomni_tpu.observability.comm import analyze_hlo_comm
    from veomni_tpu.parallel import init_parallel_state, use_parallel_state
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state

    destroy_parallel_state()
    ps = init_parallel_state(devices=devices, **mesh_kwargs)
    with use_parallel_state(ps):
        model = build_foundation_model(config=cfg)
        shardings = model.get_parallel_plan().resolve(model.abstract(), ps)
        params = jax.jit(
            lambda k: model.family.init_params(k, cfg), out_shardings=shardings
        )(jax.random.PRNGKey(0))
        batch = {k: jax.device_put(v, ps.batch_sharding()) for k, v in batch_np.items()}

        def loss_and_gnorm(p, b):
            def mean_loss(p):
                loss_sum, metrics = model.loss_fn(p, b)
                return loss_sum / jnp.maximum(metrics["ntokens"], 1)

            loss, grads = jax.value_and_grad(mean_loss)(p)
            sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                     for g in jax.tree.leaves(grads))
            return loss, jnp.sqrt(sq)

        compiled = jax.jit(loss_and_gnorm).lower(params, batch).compile()
        loss, gnorm = compiled(params, batch)
        experts = params["layers"]["experts"]["gate_proj"]
        return {
            "mesh": {k: v for k, v in ps.mesh.shape.items() if v > 1},
            "loss": float(loss), "grad_norm": float(gnorm),
            "params": _layout(params, devices),
            "experts_gate_proj": {
                "global": list(experts.shape),
                "shard": list(experts.addressable_shards[0].data.shape)},
            "bytes_in_use": [(d.memory_stats() or {}).get("bytes_in_use") for d in devices],
            "collectives": analyze_hlo_comm(compiled.as_text())["counts_by_kind"],
            "tpu_custom_calls": compiled.as_text().count("tpu_custom_call"),
        }


def _close(name, got, want, rtol):
    if not abs(got - want) <= rtol * abs(want):
        raise AssertionError(f"{name}: sharded {got} vs one device {want} (rtol {rtol})")


def phase_multichip(dense_overrides=None, dense_seq: int = 4096, dense_rows: int = 2,
                    dense_steps: int = 3, moe=MOE_BLOCKS, moe_seq: int = 2048,
                    moe_rows: int = 4, seed: int = 0) -> dict:
    """Four devices against one device of the same process. The sharded side
    runs first, so its bytes_in_use is read before the one-device side has
    put anything on device 0."""
    import jax
    import yaml

    from veomni_tpu.models.auto import build_config

    t0 = time.perf_counter()
    devices = jax.devices()
    if len(devices) != 4:
        raise AssertionError(f"the multichip phase needs 4 devices, found {len(devices)}")
    if dense_overrides is None:
        with open(os.path.join(REPO, TRAIN_CONFIG)) as f:
            dense_overrides = yaml.safe_load(f)["model"]["config_overrides"]
    dense_overrides = dict(dense_overrides)
    dense_cfg = build_config(dense_overrides.pop("model_type"), **dense_overrides)
    batch = _packed_batch(dense_rows, dense_seq, dense_cfg.vocab_size, seed)
    four = _dense_steps(devices, dict(ulysses_size=2), dense_cfg, batch, dense_steps, 3e-4)
    _assert_spread(four["state"], four["bytes_in_use"], "dense fsdp=2 x ulysses=2")
    one = _dense_steps(devices[:1], {}, dense_cfg, batch, dense_steps, 3e-4)
    for i in range(dense_steps):
        _close(f"dense loss[{i}]", four["losses"][i], one["losses"][i], MULTICHIP_LOSS_RTOL)
        _close(f"dense grad_norm[{i}]", four["grad_norms"][i], one["grad_norms"][i],
               MULTICHIP_GNORM_RTOL)
    if not four["collectives"]:
        raise AssertionError("the sharded dense step holds no collective")

    moe = dict(moe)
    moe_cfg = build_config(moe.pop("model_type"), **moe)
    moe_batch = _packed_batch(moe_rows, moe_seq, moe_cfg.vocab_size, seed + 1)
    moe_four = _moe_loss_gnorm(devices, dict(ep_size=2), moe_cfg, moe_batch)
    _assert_spread(moe_four["params"], moe_four["bytes_in_use"], "moe ep=2 x fsdp=2")
    if moe_four["experts_gate_proj"]["shard"][1] * 2 != moe_four["experts_gate_proj"]["global"][1]:
        raise AssertionError(f"experts not split over ep: {moe_four['experts_gate_proj']}")
    if not moe_four["collectives"].get("all-to-all"):
        raise AssertionError(f"no all-to-all in the EP program: {moe_four['collectives']}")
    moe_one = _moe_loss_gnorm(devices[:1], {}, moe_cfg, moe_batch)
    _close("moe loss", moe_four["loss"], moe_one["loss"], MULTICHIP_LOSS_RTOL)
    _close("moe grad_norm", moe_four["grad_norm"], moe_one["grad_norm"], MULTICHIP_GNORM_RTOL)
    return {
        "tolerance": {"loss_rtol": MULTICHIP_LOSS_RTOL, "grad_norm_rtol": MULTICHIP_GNORM_RTOL},
        "resolved": _resolved("attention", "group_gemm", "ulysses"),
        "dense": {"layers": dense_cfg.num_hidden_layers, "hidden": dense_cfg.hidden_size,
                  "rows": dense_rows, "seq_len": dense_seq, "steps": dense_steps,
                  "four_devices": four, "one_device": one},
        "moe": {"layers": moe_cfg.num_hidden_layers, "hidden": moe_cfg.hidden_size,
                "experts": moe_cfg.num_experts, "top_k": moe_cfg.num_experts_per_tok,
                "moe_intermediate": moe_cfg.moe_intermediate_size,
                "rows": moe_rows, "seq_len": moe_seq,
                "four_devices": moe_four, "one_device": moe_one},
        "peak_bytes_in_use": [_peak_bytes(d) for d in devices],
        "smoke_seconds": round(time.perf_counter() - t0, 1),
    }


# --------------------------------------------------------------------------
# child: one phase in the process that holds the chip
# --------------------------------------------------------------------------
PHASES = {"kernels": phase_kernels, "train": phase_train, "serve": phase_serve,
          "multichip": phase_multichip}


def _child(phase: str) -> int:
    from veomni_tpu.utils.xla_flags import apply_performance_flags

    apply_performance_flags()  # before the first JAX backend use
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(NO_CHIP_MSG.format(platform=platform), file=sys.stderr, flush=True)
        return 2
    result = PHASES[phase]()  # raises on failure: nothing is caught here
    print(json.dumps({"phase": phase, "device": _device_doc(), **result}), flush=True)
    return 0


# --------------------------------------------------------------------------
# parent: never touches JAX
# --------------------------------------------------------------------------
def _run_phase(phase: str, timeout_s: float):
    """Run one phase as a child; echo its output; (exit code, its JSON line)."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", phase],
        stdout=subprocess.PIPE, text=True,
    )
    killer = threading.Timer(timeout_s, proc.kill)
    killer.start()
    doc = None
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            if line.startswith('{"phase"'):
                doc = json.loads(line)
        return proc.wait(), doc
    finally:
        killer.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded path and what it is compared with")
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.phase:
        return _child(args.phase)

    deadline = time.monotonic() + RUN_BUDGET_S
    phases = ("multichip",) if args.chips == 4 else ("kernels", "train", "serve")
    device = None
    for phase in phases:
        rc, doc = _run_phase(phase, max(1.0, deadline - time.monotonic()))
        if rc != 0 or doc is None:
            print(f"chip_smoke: phase {phase} failed (exit code {rc})",
                  file=sys.stderr, flush=True)
            return rc or 1
        device = doc["device"]
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
