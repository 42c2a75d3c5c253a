"""Evidence artifact for the comm/compute-overlap story.

Thin CLI over ``veomni_tpu/utils/overlap_evidence.py`` (the census itself is
a first-class API, regression-gated by ``tests/test_async_ulysses.py``).
This script produces the human-readable artifact:

1. jit a sharded train step on an 8-device CPU mesh with ``--xla_dump_to``
   and report (a) every async collective start/done pair in the *scheduled*
   HLO with the compute placed inside the window (TPU dumps), (b) the
   backend-neutral dependency census — overlappable collective/compute
   pairs — for BOTH the monolithic and the chunked async Ulysses path, so
   the pipeline's structural win is visible off-TPU too;
2. measure the async trainer-loop win: wall-clock per step with a device
   fetch every step (log_steps=1) vs amortized fetch (log_steps=50).

Usage:  python scripts/overlap_evidence.py [out_dir]
Writes a summary to stdout.
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DUMP = sys.argv[1] if len(sys.argv) > 1 else tempfile.mkdtemp(prefix="hlo_dump_")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + f" --xla_dump_to={DUMP} --xla_dump_hlo_pass_re=scheduling|latency"
)

from veomni_tpu.utils.testing import force_cpu_devices  # noqa: E402

force_cpu_devices(8)

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

jax.config.update("jax_cpu_enable_async_dispatch", False)

from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from veomni_tpu.models import TransformerConfig, build_foundation_model  # noqa: E402
from veomni_tpu.optim import build_lr_scheduler, build_optimizer  # noqa: E402
from veomni_tpu.parallel import init_parallel_state, use_parallel_state  # noqa: E402
from veomni_tpu.parallel.parallel_state import destroy_parallel_state  # noqa: E402
from veomni_tpu.train import build_train_state, build_train_step  # noqa: E402
from veomni_tpu.train.train_step import resolve_state_shardings  # noqa: E402
from veomni_tpu.utils.overlap_evidence import (  # noqa: E402
    analyze_scheduled_dump,
    collective_bytes_census,
    compiled_hlo_text,
    overlap_report,
)


def _build_step(ulysses_async_chunks: int):
    destroy_parallel_state()
    ps = init_parallel_state(ulysses_size=2, dp_shard_size=4)
    cfg = TransformerConfig(
        model_type="qwen3", vocab_size=512, hidden_size=128,
        intermediate_size=256, num_hidden_layers=2, num_attention_heads=8,
        num_key_value_heads=4, head_dim=16, qk_norm=True, dtype=jnp.float32,
        ulysses_async_chunks=ulysses_async_chunks,
    )
    with use_parallel_state(ps):
        model = build_foundation_model(config=cfg)
        plan = model.get_parallel_plan()
        opt = build_optimizer(model.abstract(),
                              lr=build_lr_scheduler(lr=1e-3, train_steps=100))

        def make_state(rng):
            return build_train_state(model.family.init_params(rng, cfg), opt)

        abs_state = jax.eval_shape(make_state, jax.random.PRNGKey(0))
        shardings = resolve_state_shardings(abs_state, plan, ps)
        state = jax.jit(make_state, out_shardings=shardings)(jax.random.PRNGKey(0))
        keys = ("input_ids", "labels", "position_ids", "segment_ids")
        bsh = {k: NamedSharding(ps.mesh, P(None, ps.dp_axes, ps.sp_axes))
               for k in keys}
        step = build_train_step(model.loss_fn, opt, ps,
                                state_shardings=shardings, batch_shardings=bsh)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, (1, 4, 64))
        batch = {
            "input_ids": jnp.asarray(ids, jnp.int32),
            "labels": jnp.asarray(ids, jnp.int32),
            "position_ids": jnp.asarray(
                np.broadcast_to(np.arange(64), ids.shape).copy(), jnp.int32),
            "segment_ids": jnp.ones(ids.shape, jnp.int32),
        }
        batch = {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}
    return ps, step, state, batch


def main():
    # build + execute the MONOLITHIC step first: at this point the
    # --xla_dump_to dir contains only its program, so the scheduled-dump
    # census below can't conflate it with the chunked compile
    ps, step, state, batch = _build_step(1)
    with use_parallel_state(ps):
        state, metrics = step(state, batch)  # compile + dump
        _ = float(metrics["loss"])

        # async-loop win: fetch-every-step vs fetch-every-50
        def run(n, fetch_every):
            nonlocal state
            t0 = time.perf_counter()
            for i in range(n):
                state, m = step(state, batch)
                if (i + 1) % fetch_every == 0:
                    _ = float(m["loss"])
            _ = float(m["loss"])
            return (time.perf_counter() - t0) / n

        per_step_sync = run(50, 1)
        per_step_async = run(50, 50)

    # scheduled-dump census BEFORE any other compile lands in DUMP: the
    # pairs reported here are the monolithic step's and nothing else's
    pairs = analyze_scheduled_dump(DUMP)

    # dependency census for both Ulysses paths (backend-neutral evidence);
    # the monolithic step above is reused, only the chunked one compiles.
    # The toy head layout (hq=8, hkv=4, u=2) clamps the pipeline to K=2 —
    # label what actually ran.
    with use_parallel_state(ps):
        rep = overlap_report(compiled_hlo_text(step, state, batch))
    print(f"dependency census [monolithic]: {rep.describe()}")
    ps2, step2, state2, batch2 = _build_step(2)
    with use_parallel_state(ps2):
        rep = overlap_report(compiled_hlo_text(step2, state2, batch2))
    print(f"dependency census [async_chunked(K=2)]: {rep.describe()}")

    overlapped = [p for p in pairs if p.overlapped]
    print(f"HLO dump: {DUMP}")
    print(f"async collective pairs in scheduled HLO (monolithic step): "
          f"{len(pairs)}; "
          f"with compute scheduled inside the start->done window: {len(overlapped)}")
    for p in pairs[:12]:
        print(f"  {p.name:40s} window={p.window_lines:4d} lines, "
              f"compute ops inside={p.compute_inside}")
    if not pairs:
        # XLA:CPU lowers collectives synchronously — no start/done pairs
        # exist off-TPU (the latency-hiding scheduler is a TPU pass). Report
        # the GSPMD-inserted collective census of the compiled step instead:
        # these are exactly the ops the TPU scheduler overlaps. (The same
        # census now runs LIVE on every instrumented compile — the
        # comm.{site}.{bucket}.* gauges, observability/comm.py — this
        # script stays the human-readable offline artifact.)
        census: dict = {}
        for fname in os.listdir(DUMP):
            if "step_fn" not in fname or "after_optimizations.txt" not in fname:
                continue
            with open(os.path.join(DUMP, fname)) as f:
                for op, rec in collective_bytes_census(f.read()).items():
                    agg = census.setdefault(op, {"count": 0, "bytes": 0.0})
                    agg["count"] += rec["count"]
                    agg["bytes"] += rec["bytes"]
        print("CPU backend lowers collectives synchronously; GSPMD-inserted "
              "collectives in the compiled train step (what the TPU "
              "latency-hiding scheduler overlaps):")
        for op, rec in sorted(census.items()):
            print(f"  {op:20s} {rec['count']:4d}  "
                  f"{rec['bytes'] / 1e6:10.3f} MB/device")
    print(f"step time, fetch every step:  {per_step_sync * 1e3:.2f} ms")
    print(f"step time, fetch every 50:    {per_step_async * 1e3:.2f} ms")
    print(f"async-loop win: {(per_step_sync / per_step_async - 1) * 100:.1f}%")


if __name__ == "__main__":
    main()
