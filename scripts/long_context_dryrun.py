"""Long-context dryrun: 32k-64k-token train step on an 8-device virtual mesh.

Mirrors the reference's 128k@SP8 datapoint (BASELINE.md): ulysses x ring-CP
sequence parallelism + chunked-MLP (ChunkMBS) + remat, one REAL executed
train step per point plus XLA's compile-time memory analysis per device.

Run: python scripts/long_context_dryrun.py [--seq 32768 65536] [--sp u2cp4]
Prints one JSON line per point.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from veomni_tpu.utils.testing import force_cpu_devices  # noqa: E402


def run_point(seq_len: int, layout: dict, *, hidden=512, layers=2,
              vocab=16384, remat_policy="dots", chunk_mbs=2,
              compile_only=False):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from veomni_tpu.models import TransformerConfig, build_foundation_model
    from veomni_tpu.optim import build_optimizer
    from veomni_tpu.parallel import init_parallel_state, use_parallel_state
    from veomni_tpu.parallel.parallel_state import destroy_parallel_state
    from veomni_tpu.train import build_train_state, build_train_step
    from veomni_tpu.train.train_step import resolve_state_shardings

    destroy_parallel_state()
    ps = init_parallel_state(**layout)
    with use_parallel_state(ps):
        cfg = TransformerConfig(
            model_type="qwen3",
            vocab_size=vocab,
            hidden_size=hidden,
            intermediate_size=hidden * 3,
            num_hidden_layers=layers,
            num_attention_heads=16,
            num_key_value_heads=8,
            head_dim=hidden // 16,
            qk_norm=True,
            rope_theta=1e6,
            max_position_embeddings=131072,
            dtype=jnp.float32,  # CPU mesh; dtype is layout-neutral here
            remat=True,
            remat_policy=remat_policy,
            chunk_mbs=chunk_mbs,
        )
        model = build_foundation_model(config=cfg)
        plan = model.get_parallel_plan()
        opt = build_optimizer(model.abstract(), lr=1e-4)

        def make_state(rng):
            return build_train_state(model.family.init_params(rng, cfg), opt)

        abs_state = jax.eval_shape(make_state, jax.random.PRNGKey(0))
        shardings = resolve_state_shardings(abs_state, plan, ps)
        state = jax.jit(make_state, out_shardings=shardings)(jax.random.PRNGKey(0))

        keys = ("input_ids", "labels", "position_ids", "segment_ids")
        bsh = {k: NamedSharding(ps.mesh, P(None, ps.dp_axes, ps.sp_axes))
               for k in keys}
        step = build_train_step(
            model.loss_fn, opt, ps, state_shardings=shardings,
            batch_shardings=bsh,
        )

        rng = np.random.default_rng(0)
        # batch dim must cover the dp axes (fsdp8 layout: 8-way dp shard
        # needs 8 rows; sp layouts keep dp=1 and shard the sequence)
        ids = rng.integers(0, vocab, (1, max(ps.dp_size, 1), seq_len))
        batch = {
            "input_ids": jnp.asarray(ids, jnp.int32),
            "labels": jnp.asarray(ids, jnp.int32),
            "position_ids": jnp.asarray(
                np.broadcast_to(np.arange(seq_len), ids.shape).copy(), jnp.int32),
            "segment_ids": jnp.ones(ids.shape, jnp.int32),
        }
        batch = {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}

        lowered = step.lower(state, batch)
        t0 = time.perf_counter()
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
        mem = compiled.memory_analysis()

        if compile_only:
            # the 64k x 8-virtual-device EXECUTION needs >100G host RAM
            # (XLA:CPU materializes every buffer; OOM-killed, r5 notes) —
            # the per-device memory analysis is the long-context datapoint
            loss, step_s = float("nan"), float("nan")
        else:
            t0 = time.perf_counter()
            state, metrics = compiled(state, batch)
            loss = float(metrics["loss"])
            step_s = time.perf_counter() - t0

        n_dev = len(jax.devices())
        point = {
            "seq_len": seq_len,
            "layout": {k: v for k, v in layout.items() if v > 1},
            "remat": remat_policy,
            "chunk_mbs": chunk_mbs,
            "hidden": hidden,
            "layers": layers,
            "loss": None if loss != loss else round(loss, 4),
            "compile_s": round(compile_s, 1),
            "step_s": None if step_s != step_s else round(step_s, 1),
            # per-device activation/temp memory is THE long-context number
            "temp_MiB_per_dev": round(mem.temp_size_in_bytes / n_dev / 2**20, 1),
            "args_MiB_per_dev": round(mem.argument_size_in_bytes / n_dev / 2**20, 1),
        }
    destroy_parallel_state()
    return point


LAYOUTS = {
    "u2cp4": dict(ulysses_size=2, cp_size=4, dp_shard_size=1),
    "cp8": dict(cp_size=8, dp_shard_size=1),
    "u4cp2": dict(ulysses_size=4, cp_size=2, dp_shard_size=1),
    "fsdp8": dict(dp_shard_size=8),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, nargs="+", default=[32768, 65536])
    ap.add_argument("--sp", default="u2cp4", choices=sorted(LAYOUTS))
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--chunk_mbs", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=512)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--compile_only", action="store_true")
    args = ap.parse_args()

    if len(args.seq) > 1:
        # one seq per process: a second AOT lower/compile/call cycle in the
        # same process trips a JAX const-args miscount ("compiled for N
        # inputs but called with N-2") after the parallel-state rebuild
        import subprocess

        for seq in args.seq:
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--seq", str(seq), "--sp", args.sp,
                   "--remat", args.remat, "--chunk_mbs", str(args.chunk_mbs),
                   "--hidden", str(args.hidden), "--layers", str(args.layers)] \
                  + (["--compile_only"] if args.compile_only else [])
            subprocess.run(cmd, check=False)
        return

    force_cpu_devices(8)
    import jax

    # reruns of the same points skip the multi-minute XLA:CPU compiles when
    # the caller places a cache with JAX_COMPILATION_CACHE_DIR
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 5)
    point = run_point(
        args.seq[0], LAYOUTS[args.sp], remat_policy=args.remat,
        chunk_mbs=args.chunk_mbs, hidden=args.hidden, layers=args.layers,
        compile_only=args.compile_only,
    )
    print(json.dumps(point), flush=True)


if __name__ == "__main__":
    main()
