"""Benchmark: text-SFT training throughput on the available chip(s).

Prints ONE JSON line {"platform", "device_kind", "device_count", "metric",
"value", "unit", ...}: every record names the device it was taken on.
Metric: training tokens/sec/chip on a Qwen3-0.6B-class dense model (largest
of the family that fits a single v5e chip with full AdamW state). On a TPU,
MFU is reported alongside and vs_baseline is measured MFU / 40.0
(BASELINE.json north star: >= 40% MFU for text SFT on TPU; no published TPU
numbers exist); a record taken anywhere else carries neither.

``run_bench()`` is importable so scripts/mfu_sweep.py can ladder over
micro-batch size / attention impl / remat policy in one process.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np


def _bench_out_dir() -> str:
    """Where this bench process's diagnosis artifacts live (post-mortems,
    per-rank heartbeats): BENCH_OUT, or the fixed ``<checkout>/output/bench``
    (a chip belongs to one process at a time, so two benches never share
    it). Heartbeats must be written DURING the run (a stall diagnosis needs
    the beats from before the stall), so the dir exists on healthy runs too —
    :func:`_cleanup_default_out` reaps it at a clean exit when it holds
    nothing but heartbeats (a post-mortem is evidence and is kept)."""
    return os.environ.get("BENCH_OUT") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "output", "bench"
    )


# what the records below name as the device they were taken on; filled in
# by _claim_devices once the backend is up (a stall record printed before
# that says so with nulls)
_DEVICE = {"platform": None, "device_kind": None, "device_count": None}


def _claim_devices() -> int:
    """Initialize the backend, note the device for every record this process
    prints, return the device count."""
    import jax

    devs = jax.devices()
    _DEVICE.update(platform=devs[0].platform, device_kind=devs[0].device_kind,
                   device_count=len(devs))
    return len(devs)


def _cleanup_default_out() -> None:
    """Reap the default artifact dir at a CLEAN exit: a healthy bench must
    not leave heartbeats behind. Only heartbeat files are removed, and only
    when BENCH_OUT is unset (an operator-chosen dir is theirs) and nothing
    else — a post-mortem, a stall artifact — lives there. Never raises."""
    if os.environ.get("BENCH_OUT"):
        return
    d = _bench_out_dir()
    try:
        names = os.listdir(d)
    except OSError:
        return
    try:
        from veomni_tpu.observability.fleet import HEARTBEAT_RE

        if all(HEARTBEAT_RE.match(n) for n in names):
            for n in names:
                os.unlink(os.path.join(d, n))
            os.rmdir(d)
    except Exception:
        pass


_BEAT_MIN_INTERVAL_S = 1.0
_LAST_BEAT = {"t": 0.0, "phase": ""}


def _beat(global_step: int = 0, phase: str = "init",
          step_time_s: float = 0.0) -> None:
    """Progress heartbeat (observability/fleet.py): an atomic rewrite of
    heartbeat-<rank>.json recording the last phase/step that made progress.
    When a run stalls, the stall JSON's heartbeat ages say exactly WHERE
    progress stopped — init, first compile, or step N — instead of silence. Same-phase beats are throttled
    to one per second: the per-step call sits inside the bench's TIMED
    window, and an unthrottled write per step (milliseconds each on a
    network filesystem) would deflate the very tokens/sec the bench exists
    to measure — stall diagnosis only needs watchdog-timeout freshness.
    Never raises."""
    now = time.monotonic()
    if phase == _LAST_BEAT["phase"] and \
            now - _LAST_BEAT["t"] < _BEAT_MIN_INTERVAL_S:
        return
    _LAST_BEAT["t"], _LAST_BEAT["phase"] = now, phase
    try:
        from veomni_tpu.observability.fleet import write_heartbeat

        write_heartbeat(_bench_out_dir(), global_step=global_step,
                        phase=phase, step_time_s=step_time_s)
    except Exception:
        pass


def _start_watchdog(timeout_s: float, metric: str = "train_tokens_per_sec_per_chip"):
    """If the bench can't produce a measurement in time (backend init or a
    step that never returns), emit an honest zero-valued record — including
    the thread-stack dump showing WHERE it stalled and the flight-recorder
    post-mortem path showing WHAT it was doing — instead of hanging the
    caller. ``metric`` keeps the zero record in the right bench series
    (train vs serve). Uses the shared
    ``utils.helper.Watchdog`` (same stall detector as the train-loop
    supervisor); caller must ``.stop()`` it before printing the real record
    so the dog never races a measurement out of a block-buffered stdout via
    its os._exit."""
    from veomni_tpu.observability.flight_recorder import (
        configure_flight_recorder,
    )
    from veomni_tpu.utils.helper import Watchdog

    # the bench has no output_dir; without this the dog's post-mortem falls
    # back to the launch CWD (which may be read-only). Default is a
    # per-PROCESS dir (see _bench_out_dir), created lazily by the dump
    # itself so the common no-stall run leaks nothing. The stall JSON below
    # records the exact path either way.
    configure_flight_recorder(dump_dir=_bench_out_dir())

    def on_stall(stack_dump: str):
        # per-rank heartbeat freshness (observability/fleet.py): the beats
        # run_bench/_serve_main drop at each phase/step say where progress
        # stopped
        try:
            from veomni_tpu.observability.fleet import heartbeat_ages

            beats = heartbeat_ages(_bench_out_dir(),
                                   stale_after_s=float(timeout_s))
        except Exception:
            beats = []
        print(json.dumps({
            "metric": metric,
            "value": 0,
            "unit": f"tokens/s — no measurement within {int(timeout_s)}s "
                    "(backend init or run stalled)",
            "vs_baseline": 0,
            **_DEVICE,
            "watchdog_stack_dump": stack_dump,
            # the dog wrote postmortem-<rank>.json (event ring + metrics +
            # stacks) just before invoking this callback; wd is late-bound
            # and the dog can only fire timeout_s after it is assigned
            "postmortem": wd.last_postmortem_path,
            # heartbeat age + last-progress step/phase per rank: WHICH rank
            # stopped making progress, and at what point
            "heartbeats": beats,
            "last_progress_step": max(
                (b.get("global_step", 0) for b in beats), default=0
            ),
        }), flush=True)

    wd = Watchdog(
        timeout_s, on_stall=on_stall, exit_code=3, description=f"bench ({metric})"
    ).start()
    return wd


def _pctl(vals, q):
    """Percentile over a possibly-empty list (0.0 when empty) — shared by
    the closed-loop and open-loop serve benches."""
    return float(np.percentile(np.asarray(vals), q)) if vals else 0.0


BENCH_PRESETS = {
    # headline metric (largest of the family that fits one v5e with FULL
    # f32 AdamW state)
    "qwen3_0p6b": dict(hidden_size=1024, intermediate_size=3072,
                       num_hidden_layers=28, param_dtype="float32"),
    # MXU-representative point: hidden-2048 matmuls; fits one v5e only with
    # a momentum-only optimizer (Muon) — bf16 params 3.4G + bf16 momentum
    # 3.4G vs AdamW's 9.6G f32 state
    "qwen3_1p7b": dict(hidden_size=2048, intermediate_size=6144,
                       num_hidden_layers=28, param_dtype="bfloat16"),
    # CPU-runnable smoke point (JAX_PLATFORMS=cpu BENCH_SERVE=1 ...): the
    # serve bench's engine/cache accounting is host-side, so prefix-cache
    # hit rates and prefill-step counts measured here transfer to the real
    # presets — only the kernel timings don't
    "qwen3_smoke": dict(hidden_size=256, intermediate_size=512,
                        num_hidden_layers=2, param_dtype="float32"),
}


def bench_config(remat_policy: str = "dots", preset: str = "qwen3_0p6b"):
    import jax.numpy as jnp

    from veomni_tpu.models import TransformerConfig

    dims = dict(BENCH_PRESETS[preset])
    return TransformerConfig(
        model_type="qwen3",
        vocab_size=151936,
        num_attention_heads=16,
        num_key_value_heads=8,
        head_dim=128,
        qk_norm=True,
        tie_word_embeddings=True,
        max_position_embeddings=131072,
        rope_theta=1e6,
        dtype=jnp.bfloat16,
        param_dtype=getattr(jnp, dims.pop("param_dtype")),
        remat_policy=remat_policy,
        **dims,
    )


# previous integrity-metric readings: each bench record reports the DELTA
# since the last run_bench call (mfu_sweep.py ladders many configs in one
# process — absolute registry values would re-report the first config's
# restore traffic in every later record)
_INTEGRITY_SNAP = {"verify_s": 0.0, "quarantined": 0, "fallbacks": 0}

# previous per-bucket train-step compile times, same delta discipline (a
# sweep re-compiles the same shape bucket per config; the cumulative census
# figure would misattribute earlier configs' compiles to this record)
_CENSUS_SNAP = {}

# drift gate between the analytic FlopsCounter (the MFU denominator) and
# what XLA actually compiled: outside this band the offline MFU number is
# suspect (count_flops.py rotted behind a model change, or XLA compiled
# something structurally different from what the formula assumes). The band
# is sized to catch layer/vocab/doubling-class rot, not to demand equality:
# healthy ratios sit ~0.65-1.0 because the XLA census counts work the
# analytic convention deliberately omits — full masked causal scores (the
# formula credits seq/2), softmax/CE/norm elementwise, tied-embedding
# backward scatters.
FLOPS_RATIO_BAND = (0.6, 1.4)


def census_bench_fields(analytic_flops_per_step: float,
                        census=None, warn=True) -> dict:
    """Per-bucket XLA cost-census readout for the train-step site.

    ``compile_time_s`` is the per-bucket DELTA since the previous
    ``run_bench`` (sweep-proof); ``xla_flops_per_step`` is the latest
    train-step program's whole-mesh FLOPs (census FLOPs are per device);
    ``analytic_vs_xla_flops_ratio`` is the sanity field — a warning fires
    outside ``FLOPS_RATIO_BAND`` so the MFU denominator can no longer
    silently rot as models change. Never raises: a census-blind run (env
    kill switch, analysis-less backend) reports zeros."""
    out = {"compile_time_s": {}, "xla_flops_per_step": 0.0,
           "analytic_vs_xla_flops_ratio": 0.0}
    try:
        if census is None:
            from veomni_tpu.observability.cost import get_cost_census

            census = get_cost_census()
        for rec in census.programs("train_step"):
            prev = _CENSUS_SNAP.get(rec.bucket, 0.0)
            delta = rec.compile_time_s - prev
            _CENSUS_SNAP[rec.bucket] = rec.compile_time_s
            if delta > 0:
                out["compile_time_s"][rec.bucket] = round(delta, 4)
        rec = census.latest("train_step")
        if rec is not None and rec.flops:
            out["xla_flops_per_step"] = rec.flops * rec.num_devices
            ratio = analytic_flops_per_step / out["xla_flops_per_step"]
            out["analytic_vs_xla_flops_ratio"] = round(ratio, 4)
            lo, hi = FLOPS_RATIO_BAND
            if warn and not (lo <= ratio <= hi):
                print(
                    f"# WARNING: analytic FlopsCounter is {ratio:.3f}x the "
                    f"XLA cost census (band {lo}-{hi}): the reported MFU's "
                    "denominator disagrees with what XLA compiled — "
                    "utils/count_flops.py may have rotted behind a model "
                    "change", file=sys.stderr, flush=True,
                )
    except Exception as e:
        print(f"# cost census unavailable for bench record: {e}",
              file=sys.stderr, flush=True)
    return out


def _integrity_delta() -> dict:
    from veomni_tpu.observability.metrics import get_registry

    reg = get_registry()
    cur = {
        "verify_s": reg.histogram_sum("integrity.verify_s"),
        "quarantined": int(reg.counter("integrity.ckpt_quarantined").value),
        "fallbacks": int(reg.counter("integrity.ckpt_fallbacks").value),
    }
    delta = {k: cur[k] - _INTEGRITY_SNAP[k] for k in cur}
    _INTEGRITY_SNAP.update(cur)
    return delta


def run_bench(
    seq_len: int,
    micro_bs: int,
    steps: int,
    *,
    attention_impl: str = None,
    remat_policy: str = "dots",
    donate: bool = True,
    preset: str = "qwen3_0p6b",
    optimizer: str = "adamw",
    ulysses_size: int = 1,
    ulysses_async: bool = False,
    ulysses_async_chunks: int = 4,
) -> dict:
    """One full train-throughput measurement; returns {tok_s_chip, mfu, dt}."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from veomni_tpu.models import build_foundation_model
    from veomni_tpu.optim import build_lr_scheduler, build_optimizer
    from veomni_tpu.parallel import init_parallel_state, use_parallel_state
    from veomni_tpu.train import build_train_state, build_train_step
    from veomni_tpu.train.train_step import resolve_state_shardings
    from veomni_tpu.utils.count_flops import FlopsCounter
    from veomni_tpu.utils.device import get_device_peak_flops

    os.environ["VEOMNI_DONATE_STATE"] = "1" if donate else "0"
    pins = {}
    if attention_impl:
        pins["attention"] = attention_impl
    if ulysses_async:
        # chunked a2a/compute overlap pipeline (parallel/async_ulysses.py)
        pins["ulysses"] = "ulysses_async"
        os.environ["VEOMNI_ULYSSES_ASYNC_CHUNKS"] = str(ulysses_async_chunks)

    # first beat BEFORE the backend starts: a stall there must read as
    # "stuck at init", not as an empty heartbeat list
    _beat(phase="init")
    n_chips = _claim_devices()
    _beat(phase="backend")  # progress marker: chip claim succeeded
    ps = init_parallel_state(ulysses_size=ulysses_size)

    with use_parallel_state(ps):
        cfg = bench_config(remat_policy, preset)
        # pins ride through the builder: build_foundation_model runs
        # apply_ops_config itself, and a bare call would WIPE pins applied
        # beforehand (clear_pins precedes re-pinning)
        model = build_foundation_model(config=cfg, ops_implementation=pins or None)
        plan = model.get_parallel_plan()
        opt = build_optimizer(
            model.abstract(), optimizer=optimizer,
            lr=build_lr_scheduler(lr=1e-4, train_steps=1000),
        )

        def make_state(rng):
            return build_train_state(model.family.init_params(rng, cfg), opt)

        abs_state = jax.eval_shape(make_state, jax.random.PRNGKey(0))
        shardings = resolve_state_shardings(abs_state, plan, ps)
        state = jax.jit(make_state, out_shardings=shardings)(jax.random.PRNGKey(0))

        keys = ("input_ids", "labels", "position_ids", "segment_ids")
        batch_shardings = {
            k: NamedSharding(ps.mesh, P(None, ps.dp_axes, ps.sp_axes)) for k in keys
        }
        step = build_train_step(
            model.loss_fn, opt, ps,
            state_shardings=shardings, batch_shardings=batch_shardings,
        )

        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, (1, micro_bs, seq_len))
        batch = {
            "input_ids": jnp.asarray(ids, jnp.int32),
            "labels": jnp.asarray(ids, jnp.int32),
            "position_ids": jnp.asarray(
                np.broadcast_to(np.arange(seq_len), ids.shape).copy(), jnp.int32
            ),
            "segment_ids": jnp.ones(ids.shape, jnp.int32),
        }
        batch = {k: jax.device_put(v, batch_shardings[k]) for k, v in batch.items()}

        # warmup (compile)
        state, metrics = step(state, batch)
        jax.block_until_ready(metrics)
        _beat(phase="compile")  # progress marker: warmup compile + fetch ran

        # utilization accounting for the timed window: goodput split from
        # host spans + recompile count from the train-step trace counter
        # (a steady-state retrace inside the window voids the measurement)
        from veomni_tpu.observability.goodput import GoodputTracker
        from veomni_tpu.observability.spans import enable_spans, span
        from veomni_tpu.train import train_step as train_step_mod

        enable_spans()
        tracker = GoodputTracker()
        traces0 = train_step_mod.TRACE_COUNTS["train_step"]
        tracker.begin_window()
        t0 = time.perf_counter()
        for i in range(steps):
            with span("step.dispatch"):
                state, metrics = step(state, batch)
            # last-progress marker for the stall JSON: dispatch is async, so
            # this says the HOST kept feeding the device up to step i+1 (the
            # sync fetch below is where a wedged device surfaces)
            _beat(global_step=i + 1, phase="step")
        with span("sync.fetch"):
            jax.block_until_ready(metrics)
        dt = time.perf_counter() - t0
        _beat(global_step=steps, phase="done", step_time_s=dt / max(1, steps))
        gp = tracker.end_window()
        recompiles = train_step_mod.TRACE_COUNTS["train_step"] - traces0

        # integrity trajectory: restore-verification time + quarantine and
        # fallback counts for whatever checkpoint traffic this process did
        # (zero for the pure-throughput path; scripts driving resume flows
        # through run_bench see the real numbers)
        _integ = _integrity_delta()
        restore_verify_s = _integ["verify_s"]
        ckpt_quarantined = _integ["quarantined"]
        ckpt_fallbacks = _integ["fallbacks"]

        # numerics-tier cost (observability/numerics.py): time the
        # instrumented sibling step on the same batch and report its
        # per-step overhead over the hot step — the continuously-measured
        # price of a train.observability_numerics_interval step. Never
        # fatal: a failure reports 0.0 and the bench line says why.
        numerics_overhead_frac = 0.0
        try:
            if os.environ.get("BENCH_NUMERICS", "1") in ("0", ""):
                raise RuntimeError("disabled via BENCH_NUMERICS=0")
            from veomni_tpu.observability.numerics import NumericsSpec

            num_step = build_train_step(
                model.loss_fn, opt, ps,
                state_shardings=shardings, batch_shardings=batch_shardings,
                numerics_spec=NumericsSpec(),
            )
            # warmup compile, then a short timed loop (the sibling never
            # donates, so `state` stays live for the delete below)
            _s, _m, _h = num_step(state, batch)
            jax.block_until_ready(_m)
            n_num = max(2, min(8, steps))
            tn0 = time.perf_counter()
            for _ in range(n_num):
                _s, _m, _h = num_step(state, batch)
            jax.block_until_ready(_m)
            t_num = (time.perf_counter() - tn0) / n_num
            t_plain = dt / max(1, steps)
            numerics_overhead_frac = max(0.0, t_num / t_plain - 1.0)
            del _s, _m, _h
        except Exception as e:
            print(f"# numerics overhead probe unavailable: {e}",
                  file=sys.stderr, flush=True)

        tokens = micro_bs * seq_len * steps
        tok_per_sec_chip = tokens / dt / n_chips
        analytic_per_step = FlopsCounter.from_config(cfg).batch_flops(
            micro_bs * seq_len, seq_len
        )
        # MFU is a device metric: a record not taken on a TPU has none
        mfu = (
            100.0 * analytic_per_step * steps / dt
            / (get_device_peak_flops() * n_chips)
            if _DEVICE["platform"] == "tpu" else None
        )
        # XLA cost-census cross-check (observability/cost.py): per-bucket
        # compile time + compiled-program FLOPs, and the drift gate between
        # the analytic formula above and what XLA actually built
        census = census_bench_fields(analytic_per_step)

        # free state before the caller builds the next config
        del batch
        jax.tree.map(lambda x: x.delete(), state)
        return {**_DEVICE, "tok_s_chip": tok_per_sec_chip,
                **({} if mfu is None else {"mfu": mfu}), "dt": dt,
                "seq_len": seq_len, "micro_bs": micro_bs, "steps": steps,
                "attention": attention_impl or "auto",
                "remat_policy": remat_policy, "preset": preset,
                "optimizer": optimizer, "ulysses_size": ulysses_size,
                "ulysses_async": ulysses_async,
                "goodput_pct": gp.get("goodput_pct", 0.0),
                "data_wait_frac": gp.get("data_wait_frac", 0.0),
                "recompiles": recompiles,
                "numerics_overhead_frac": numerics_overhead_frac,
                "restore_verify_s": restore_verify_s,
                "ckpt_quarantined": ckpt_quarantined,
                "ckpt_fallbacks": ckpt_fallbacks,
                "compile_time_s": census["compile_time_s"],
                "xla_flops_per_step": census["xla_flops_per_step"],
                "analytic_vs_xla_flops_ratio":
                    census["analytic_vs_xla_flops_ratio"]}


def run_serve_bench(
    *,
    num_slots: int = 4,
    block_size: int = 16,
    n_requests: int = 16,
    prompt_lens=(64, 128, 256),
    max_new_tokens: int = 64,
    preset: str = "qwen3_0p6b",
    remat_policy: str = "dots",
    shared_prefix: int = 0,
    prefill_chunk: int = 0,
    prefix_cache: bool = True,
    spec_ks=(),
    spec_draft: str = "ngram",
    kv_quant: str = "",
    weight_quant: str = "none",
) -> dict:
    """Continuous-batching inference throughput: N requests with a cycled
    prompt-length mix through the serving engine. Returns decode tokens/s
    (steady-state, measured after the first token of the last-admitted
    request wherever possible — here simply total generated / wall) and
    mean TTFT. Single-chip, random weights: measures the engine + kernels,
    not checkpoint IO.

    ``shared_prefix`` > 0 makes every prompt open with the same
    ``shared_prefix``-token system prompt (the millions-of-users-share-a-
    system-prompt workload) and ALSO drives the same timed request set
    through a cache-off engine, so the JSON line carries TTFT p50/p99 and
    prefill step counts with the prefix cache on vs off.

    ``spec_ks`` (BENCH_SERVE_SPEC_K, e.g. ``0,2,4,8``) additionally drives
    the SAME timed request set through a draft-then-verify engine per k:
    the sweep records decode tok/s and the verify acceptance rate at each
    k, with the k=0 run doubling as the ``nospec_*`` baseline — the
    accepted-tokens-per-verify-width tradeoff curve the ROADMAP's
    speculative-decoding item regresses against.

    ``kv_quant`` (BENCH_SERVE_KV_QUANT, e.g. ``int8``; optionally paired
    with ``weight_quant`` via BENCH_SERVE_WEIGHT_QUANT) additionally
    drives the SAME timed request set through a quantized engine
    (mirroring the nocache_*/nospec_* comparisons): the JSON line then
    carries quantized-vs-f32 decode tok/s and TTFT, the measured per-block
    byte sizes (int8 payload + scale sidecar, straight from the pool's
    ``nbytes``), the fixed-pool-bytes capacity ratio, and the fixed-seed
    quality-gate stats — so a capacity win can never be reported without
    its quality cost in the same record."""
    import jax
    import jax.numpy as jnp

    from veomni_tpu.models import build_foundation_model
    from veomni_tpu.serving import (
        EngineConfig,
        InferenceEngine,
        Request,
        SamplingParams,
    )

    _beat(phase="init")  # before the backend starts: see run_bench
    _claim_devices()
    _beat(phase="backend")  # progress marker: chip claim succeeded
    cfg = bench_config(remat_policy, preset)
    model = build_foundation_model(config=cfg)
    params = model.family.init_params(jax.random.PRNGKey(0), cfg)
    _beat(phase="params")  # progress marker: weights materialized on device

    max_len = max(prompt_lens) + max_new_tokens
    rng = np.random.default_rng(0)
    prefix = [int(t) for t in rng.integers(1, cfg.vocab_size, shared_prefix)]

    def make_prompts(n, seed):
        r = np.random.default_rng(seed)
        prompts = []
        for i in range(n):
            want = prompt_lens[i % len(prompt_lens)]
            # at least one unique token per request so every prompt still
            # has an uncached suffix to run (and requests stay distinct)
            suffix = max(1, want - shared_prefix)
            prompts.append(prefix[: max(0, want - suffix)] + [
                int(t) for t in r.integers(1, cfg.vocab_size, suffix)
            ])
        return prompts

    def drive(engine_cfg, warm_prompts, timed_prompts):
        eng = InferenceEngine(params, cfg, engine_cfg)
        # warmup through the SAME engine (the decode-step jit cache is
        # per-engine), one length class at a time: a solo run walks that
        # class's whole block-allocation trajectory, so every power-of-two
        # context bucket the timed run can hit (nbb is always pow2 of SOME
        # running seq's allocation) is compiled before t0 — batch-mixed
        # warmup would let the longest prompt mask the smaller buckets.
        # With the prefix cache on this also pre-caches the shared prefix,
        # so the timed window measures the steady state.
        for wi, p in enumerate(warm_prompts):
            eng.run([Request(prompt_ids=p, sampling=SamplingParams(
                max_new_tokens=max_new_tokens))])
            _beat(global_step=wi + 1, phase="serve_warmup")
        m0 = eng.metrics()  # reset the throughput window

        timed = [Request(prompt_ids=p, sampling=SamplingParams(
            max_new_tokens=max_new_tokens)) for p in timed_prompts]
        t0 = time.perf_counter()
        ids = [eng.submit(r) for r in timed]
        outs = eng.run()
        dt = time.perf_counter() - t0
        _beat(global_step=len(timed), phase="serve_done")
        m1 = eng.metrics(reset_window=False)
        # warmup-proof deltas across the timed window; prompt_tokens counts
        # every (re)admission's recompute prompt, so the token fraction is
        # bounded by 1 even under preemption storms
        delta = {k: m1[k] - m0[k]
                 for k in ("prefill_chunks", "cached_tokens",
                           "prompt_tokens", "spec_proposed",
                           "spec_accepted")}
        return eng, ids, outs, dt, delta

    engine_cfg = EngineConfig(
        num_slots=num_slots, block_size=block_size, max_model_len=max_len,
        prefix_cache=prefix_cache, prefill_chunk=prefill_chunk,
    )
    warm = make_prompts(len(prompt_lens), seed=1)
    timed_prompts = make_prompts(n_requests, seed=2)
    eng, ids, outs, dt, delta = drive(engine_cfg, warm, timed_prompts)
    total = sum(len(outs[rid].token_ids) for rid in ids)
    ttfts = [outs[rid].ttft_s for rid in ids if outs[rid].ttft_s is not None]

    # per-request latency distribution over the TIMED requests only (the
    # outputs carry the request_trace rollup, so warmup traffic in the
    # process-global histograms can't skew these) — the numbers the
    # SLO-scheduling roadmap item regresses against
    waits = [outs[rid].queue_wait_s for rid in ids
             if outs[rid].queue_wait_s is not None]
    tpots = [outs[rid].tpot_s for rid in ids if outs[rid].tpot_s is not None]
    result = {
        "decode_tok_s": total / dt,
        "ttft_mean_s": sum(ttfts) / max(1, len(ttfts)),
        "ttft_p50_s": _pctl(ttfts, 50),
        "ttft_p99_s": _pctl(ttfts, 99),
        "total_tokens": total,
        "dt": dt,
        "num_slots": num_slots,
        "block_size": block_size,
        "n_requests": n_requests,
        "prompt_lens": list(prompt_lens),
        "max_new_tokens": max_new_tokens,
        "preset": preset,
        "shared_prefix": shared_prefix,
        "prefill_chunk": prefill_chunk,
        "prefix_cache": prefix_cache,
        "preemptions": eng.scheduler.preemption_count,
        "queue_wait_p50_s": _pctl(waits, 50),
        "queue_wait_p99_s": _pctl(waits, 99),
        "tpot_p50_s": _pctl(tpots, 50),
        "tpot_p99_s": _pctl(tpots, 99),
        # from the timed outputs, like the percentiles above — the engine-
        # cumulative scheduler counter would fold warmup traffic in
        "preemptions_per_request": sum(
            outs[rid].preemptions for rid in ids) / max(1, n_requests),
        # prefix-cache effectiveness over the timed window. Two distinct
        # views: hit RATE is request-weighted (share of timed requests
        # whose latest admission matched cached blocks), the token FRAC is
        # token-weighted over every (re)admission's recompute prompt
        # (warmup-proof engine-counter delta, bounded by 1 even when
        # preemption re-admissions inflate cached_tokens per request)
        "prefix_hit_rate": sum(
            1 for rid in ids if outs[rid].cached_tokens > 0
        ) / max(1, len(ids)),
        "cached_tokens_frac": (
            delta["cached_tokens"] / max(1.0, delta["prompt_tokens"])
        ),
        "prefill_chunks": delta["prefill_chunks"],
    }
    if shared_prefix > 0 and prefix_cache:
        # the same request set through a cache-off engine: the on-vs-off
        # TTFT/prefill-step comparison the ROADMAP's serving item regresses
        _, ids2, outs2, _, delta_off = drive(
            EngineConfig(num_slots=num_slots, block_size=block_size,
                         max_model_len=max_len, prefix_cache=False,
                         prefill_chunk=prefill_chunk),
            warm, timed_prompts,
        )
        off_ttfts = [outs2[rid].ttft_s for rid in ids2
                     if outs2[rid].ttft_s is not None]
        result["nocache_ttft_p50_s"] = _pctl(off_ttfts, 50)
        result["nocache_ttft_p99_s"] = _pctl(off_ttfts, 99)
        result["nocache_prefill_chunks"] = delta_off["prefill_chunks"]
    if spec_ks:
        # the SAME timed request set per draft length k: the k=0 run is the
        # nospec baseline (mirrors the nocache_* pattern above), the rest
        # trace the accepted-tokens-vs-verify-width curve
        sweep = []
        for k in spec_ks:
            if int(k) == 0:
                # spec_k=0 IS the main (speculation-off) drive above —
                # reuse its measurement instead of re-running warmup + the
                # whole timed set for a byte-identical engine
                entry = {
                    "spec_k": 0,
                    "decode_tok_s": result["decode_tok_s"],
                    "spec_acceptance_rate": 0.0,
                    "spec_accepted_tokens": 0.0,
                    "tpot_p50_s": result["tpot_p50_s"],
                }
            else:
                _, ids_k, outs_k, dt_k, delta_k = drive(
                    EngineConfig(num_slots=num_slots, block_size=block_size,
                                 max_model_len=max_len,
                                 prefix_cache=prefix_cache,
                                 prefill_chunk=prefill_chunk,
                                 spec_k=int(k), spec_draft=spec_draft),
                    warm, timed_prompts,
                )
                total_k = sum(len(outs_k[r].token_ids) for r in ids_k)
                tpots_k = [outs_k[r].tpot_s for r in ids_k
                           if outs_k[r].tpot_s is not None]
                entry = {
                    "spec_k": int(k),
                    "decode_tok_s": total_k / dt_k,
                    "spec_acceptance_rate": (
                        delta_k["spec_accepted"]
                        / max(1.0, delta_k["spec_proposed"])
                    ),
                    "spec_accepted_tokens": delta_k["spec_accepted"],
                    "tpot_p50_s": _pctl(tpots_k, 50),
                }
            sweep.append(entry)
            _beat(global_step=len(sweep), phase="serve_spec_sweep")
            if int(k) == 0:
                result["nospec_decode_tok_s"] = entry["decode_tok_s"]
                result["nospec_tpot_p50_s"] = entry["tpot_p50_s"]
        result["spec_sweep"] = sweep
        result["spec_draft"] = spec_draft
    if kv_quant:
        # the SAME timed request set through a quantized engine (mirrors
        # the nocache_*/nospec_* comparisons above). Byte sizes come from
        # the live pools via kv_capacity() (QuantizedKV.nbytes = int8
        # payload + f32 scale sidecar), and the fixed-seed quality gate
        # rides in the same record: capacity and quality move together.
        from veomni_tpu.serving.quality import fixed_corpus, quality_stats

        eng_q, ids_q, outs_q, dt_q, _ = drive(
            EngineConfig(num_slots=num_slots, block_size=block_size,
                         max_model_len=max_len, prefix_cache=prefix_cache,
                         prefill_chunk=prefill_chunk, kv_quant=kv_quant,
                         weight_quant=weight_quant),
            warm, timed_prompts,
        )
        _beat(phase="serve_kv_quant")
        total_q = sum(len(outs_q[rid].token_ids) for rid in ids_q)
        q_ttfts = [outs_q[rid].ttft_s for rid in ids_q
                   if outs_q[rid].ttft_s is not None]
        cap_f32 = eng.kv_capacity()
        cap_q = eng_q.kv_capacity()
        # fixed-pool-BYTES capacity: max-length sequences the quantized
        # blocks fit inside the f32 pool's byte budget vs what f32 fits —
        # the "2x the users in the same HBM" headline (block 0 stays the
        # reserved null block in both denominators)
        per_seq = max(1.0, cap_f32["blocks_per_max_len_seq"])
        q_blocks_in_f32_bytes = cap_f32["pool_bytes"] // max(
            1.0, cap_q["block_bytes"])
        q_seqs = (q_blocks_in_f32_bytes - 1) // per_seq
        stats = quality_stats(
            params, cfg, fixed_corpus(cfg.vocab_size),
            kv_quant=kv_quant, weight_quant=weight_quant,
            block_size=block_size,
        )
        result.update({
            "kv_quant": kv_quant,
            "weight_quant": weight_quant,
            "kvq_decode_tok_s": total_q / dt_q,
            "kvq_ttft_p50_s": _pctl(q_ttfts, 50),
            "kvq_ttft_p99_s": _pctl(q_ttfts, 99),
            "kv_block_bytes": cap_q["block_bytes"],
            "kv_block_bytes_f32": cap_f32["block_bytes"],
            "kv_capacity_ratio": (
                q_seqs / max(1.0, cap_f32["max_concurrent_seqs"])
            ),
            "quality_ppl_ref": stats["ppl_ref"],
            "quality_ppl_quant": stats["ppl_quant"],
            "quality_ppl_rel_delta": stats["ppl_rel_delta"],
            "quality_topk_overlap": stats["topk_overlap"],
        })
    return result


def run_serve_open_loop_bench(
    *,
    num_slots: int = 4,
    block_size: int = 16,
    n_requests: int = 32,
    prompt_lens=(64, 128, 256),
    max_new_tokens: int = 32,
    preset: str = "qwen3_0p6b",
    remat_policy: str = "dots",
    arrival_rate_mults=(0.5, 1.0, 2.0),
    arrival_rates=(),
    queue_bound: int = 0,
    deadline_s: float = 0.0,
    interactive_frac: float = 0.5,
    classes: str = "interactive:4,batch:1",
    seed: int = 0,
    kv_quant: str = "",
    weight_quant: str = "none",
    shared_prefix: int = 0,
    shared_prefix_groups: int = 1,
    replicas: int = 1,
    replica_kill_at_s: float = 0.0,
    chaos_seed: int = -1,
    chaos_stall_s: float = 2.0,
    chaos_publishes: int = 0,
    publish: bool = False,
    _model=None,
) -> dict:
    """Open-loop Poisson overload bench: arrivals fire on a fixed schedule
    regardless of whether the engine keeps up — the load model a closed
    feedback loop (``run_serve_bench``) structurally cannot produce, and
    the only one that exposes overload behavior: queue growth, shedding,
    deadline misses, p99 blowup.

    A closed-loop calibration run first measures the engine's completion
    capacity (requests/s with every slot busy); ``arrival_rate_mults``
    (default sweeps 0.5x/1x/2x, i.e. *past capacity*) scale it into the
    open-loop arrival rates (``arrival_rates`` in req/s overrides). Each
    rate drives the SAME request set — an interactive/batch mix
    (``interactive_frac``; interactive requests carry ``deadline_s`` when
    set) — against a QoS engine with a bounded queue (``queue_bound``; 0
    defaults to ``4 * num_slots``), reporting per rate: reject rate,
    deadline-miss rate, p50/p99 TTFT (overall + interactive-only), p99
    TPOT, decode tok/s, max observed queue depth, and **goodput** —
    tokens from requests that finished within their deadline per second
    of wall time, the number that keeps honest under overload when raw
    decode tok/s still looks fine.

    ``kv_quant`` (BENCH_SERVE_KV_QUANT) adds a quantized leg at FIXED
    pool bytes: the int8 pool is sized to the f32 pool's exact byte
    budget (more, smaller blocks), the same Poisson arrivals replay at
    the same rates, and each ``kvq_sweep`` entry carries the
    goodput-under-overload and reject-rate deltas vs the f32 leg.

    ``replicas`` (BENCH_SERVE_REPLICAS, N > 1) adds a scale-out leg: the
    SAME Poisson storms replay at the SAME swept rates against the
    prefix-affinity router over N data-parallel engine replicas (compiled
    programs shared — one warmup covers the fleet). Each ``router_sweep``
    entry carries the aggregate and per-replica goodput, the router's
    prefix hit rate vs the single-engine leg's (affinity should keep
    shared-prefix traffic at least as warm as one engine sees), and
    ``goodput_scaling`` — aggregate goodput over the single-engine leg's
    at the identical rate: past single-engine capacity the fleet's extra
    slots/KV/queue convert sheds and deadline misses back into goodput. ``shared_prefix`` prepends that many
    common tokens to every prompt, drawn from ``shared_prefix_groups``
    distinct prefixes (BENCH_SERVE_PREFIX_GROUPS; think N different
    system prompts — the workload affinity routing exists for: each
    group's KV warms exactly one replica instead of cold-missing on all
    of them); ``replica_kill_at_s`` (BENCH_SERVE_REPLICA_KILL_AT_S) kills one
    replica that many seconds into each router rate — the mid-storm
    fault drill (survivors absorb re-dispatched work, the entry reports
    ``redispatched``/``cancelled``).

    ``chaos_seed`` (BENCH_SERVE_CHAOS, >= 0 enables) adds the chaos soak
    leg: a seeded deterministic fault schedule (``resilience/chaos.py`` —
    replica kills + hang/delay/exception across the serve fault points)
    fires over a self-healing fleet (wedge detection at ``chaos_stall_s``,
    respawn + probation enabled) while the same Poisson storm replays
    twice — once fault-free, once under chaos. The entry reports the
    fleet invariants (no lost/duplicated ids, zero leaked blocks per
    survivor, fleet restored to full live count) and ``goodput_ratio``
    (chaos / fault-free; the acceptance floor is 0.7). The PR-17 kill
    drill (``replica_kill_at_s``) deliberately keeps respawns OFF — it
    measures the *degraded* fleet; the chaos leg measures the *healing*
    one. ``chaos_publishes`` (BENCH_SERVE_CHAOS_PUBLISHES) additionally
    schedules that many mid-storm rolling weight publications inside the
    chaos leg (drawn AFTER the seed's faults/kills, so existing seeds
    replay bit-identically) — the soak then also checks the fleet
    converged to exactly one weights version.

    ``publish`` (BENCH_SERVE_PUBLISH=1) adds the rolling-publish leg:
    the same Poisson storm replays twice over the self-healing fleet —
    once publish-free, once with ONE mid-storm ``publish_weights`` of a
    perturbed payload rolling through every replica — reporting the
    publish wall time (fire -> fleet converged on the new version), the
    goodput ratio vs the publish-free replay (acceptance floor 0.7) and
    the zero-new-traces gate across the whole drain -> swap -> rotation
    window (``models/decode.py::TRACE_COUNTS`` delta from the moment of
    publish must be 0).

    ``_model`` injects a prebuilt ``(params, cfg)`` (tier-1 CPU smoke uses
    a tiny model); by default the ``preset`` model is built fresh."""
    import jax

    from veomni_tpu.models import build_foundation_model
    from veomni_tpu.serving import (
        EngineConfig,
        InferenceEngine,
        Request,
        SamplingParams,
    )

    if _model is not None:
        params, cfg = _model
        _claim_devices()
    else:
        _beat(phase="init")
        _claim_devices()
        _beat(phase="backend")
        cfg = bench_config(remat_policy, preset)
        model = build_foundation_model(config=cfg)
        params = model.family.init_params(jax.random.PRNGKey(0), cfg)
        _beat(phase="params")

    max_len = max(prompt_lens) + max_new_tokens
    queue_bound = queue_bound or 4 * num_slots
    rng = np.random.default_rng(seed)
    # the interactive/batch roles map onto the CONFIGURED class spec: the
    # first (highest-priority) class plays "interactive" and the last
    # "batch", so a custom BENCH_SERVE_CLASSES sweep doesn't crash on
    # labels the engine never configured
    from veomni_tpu.serving import parse_classes

    class_names = [n for n, _ in parse_classes(classes)]
    hi_class, lo_class = class_names[0], class_names[-1]

    # common leading chunks (think distinct system prompts): the
    # shared-prefix workload the radix cache — and the router's affinity
    # keying on top of it — exists for. 0 keeps fully random prompts.
    prefixes = [
        [int(t) for t in rng.integers(1, cfg.vocab_size, shared_prefix)]
        for _ in range(max(1, shared_prefix_groups))
    ]

    def make_requests(n):
        reqs = []
        for i in range(n):
            want = prompt_lens[i % len(prompt_lens)]
            prefix = prefixes[int(rng.integers(0, len(prefixes)))]
            fresh = max(1, want - len(prefix))
            prompt = prefix + [
                int(t) for t in rng.integers(1, cfg.vocab_size, fresh)
            ]
            interactive = bool(rng.random() < interactive_frac)
            reqs.append(Request(
                prompt_ids=prompt,
                sampling=SamplingParams(max_new_tokens=max_new_tokens),
                priority=hi_class if interactive else lo_class,
                deadline_s=(deadline_s if interactive and deadline_s > 0
                            else None),
            ))
        return reqs

    def clone_requests(protos):
        """Fresh Request objects over the same prompts/classes/deadlines:
        every swept rate replays the IDENTICAL workload (cross-rate deltas
        measure load response, not workload noise), while each engine
        assigns its own request ids."""
        return [Request(prompt_ids=list(r.prompt_ids), sampling=r.sampling,
                        priority=r.priority, deadline_s=r.deadline_s)
                for r in protos]

    def engine_cfg(**kw):
        return EngineConfig(num_slots=num_slots, block_size=block_size,
                            max_model_len=max_len, **kw)

    # ---- closed-loop calibration: completion capacity with full slots
    # (shared warmup: compiles land here, not inside any timed window)
    cal = InferenceEngine(params, cfg, engine_cfg(classes=classes))
    warm = make_requests(len(prompt_lens))
    for r in warm:
        cal.run([r])
    _beat(phase="serve_warmup")
    proto = make_requests(n_requests)  # ONE workload, replayed per rate
    # calibration strips deadlines: an expiry "completing" a request early
    # would inflate the measured service capacity the sweep scales from
    cal_reqs = [Request(prompt_ids=list(r.prompt_ids), sampling=r.sampling,
                        priority=r.priority) for r in proto]
    t0 = time.perf_counter()
    cal.run(cal_reqs)
    cal_dt = time.perf_counter() - t0
    capacity_rps = n_requests / max(cal_dt, 1e-9)
    _beat(phase="serve_capacity")

    rates = [float(r) for r in arrival_rates] or [
        m * capacity_rps for m in arrival_rate_mults
    ]

    def run_rate(rate, **cfg_kw):
        eng = InferenceEngine(params, cfg, engine_cfg(
            queue_bound=queue_bound, classes=classes, **cfg_kw,
        ))
        for r in warm:  # per-engine jit caches: warm each engine
            eng.run([Request(prompt_ids=r.prompt_ids, sampling=r.sampling,
                             priority=r.priority)])
        reqs = clone_requests(proto)
        # per-rate seeded arrivals: the Poisson pattern is reproducible for
        # a given (seed, rate) independent of sweep order
        arng = np.random.default_rng((seed, int(rate * 1e6)))
        arrivals = np.cumsum(arng.exponential(1.0 / rate, size=n_requests))
        m0 = eng.metrics()  # reset the goodput/throughput window
        ids = []
        max_queue = 0
        t0 = time.perf_counter()
        i = 0
        while i < len(reqs) or eng.has_work:
            now = time.perf_counter() - t0
            while i < len(reqs) and arrivals[i] <= now:
                ids.append(eng.submit(reqs[i]))  # open loop: never blocks
                i += 1
            max_queue = max(max_queue, eng.scheduler.queue_depth)
            if eng.has_work:
                eng.step()
            elif i < len(reqs):
                time.sleep(min(max(arrivals[i] - now, 0.0), 0.01))
        dt = time.perf_counter() - t0
        m1 = eng.metrics(reset_window=False)
        outs = {rid: eng._outputs[rid] for rid in ids}
        done = [o for o in outs.values()
                if o.finish_reason in ("eos", "length")]
        inter_ids = [rid for rid, r in zip(ids, reqs)
                     if r.priority == hi_class]
        ttfts = [o.ttft_s for o in done if o.ttft_s is not None]
        inter_ttfts = [outs[rid].ttft_s for rid in inter_ids
                       if outs[rid].ttft_s is not None]
        tpots = [o.tpot_s for o in done if o.tpot_s is not None]
        n_rej = sum(1 for o in outs.values()
                    if o.finish_reason == "rejected")
        n_miss = sum(1 for o in outs.values() if o.deadline_missed)
        return {
            "arrival_rate_rps": rate,
            "rate_vs_capacity": rate / max(capacity_rps, 1e-9),
            "reject_rate": n_rej / max(1, n_requests),
            "deadline_miss_rate": n_miss / max(1, n_requests),
            "completed": len(done),
            "max_queue_depth": max_queue,
            "ttft_p50_s": _pctl(ttfts, 50),
            "ttft_p99_s": _pctl(ttfts, 99),
            "ttft_p99_interactive_s": _pctl(inter_ttfts, 99),
            "tpot_p99_s": _pctl(tpots, 99),
            "decode_tok_s": sum(len(o.token_ids) for o in done) / dt,
            # window deltas are warmup-proof (m0 reset the window); goodput
            # divides by the open-loop wall, not the window elapsed
            "goodput_tok_s": (m1["goodput_tokens"] - m0["goodput_tokens"])
            / dt,
            "shed_tokens": m1["shed_tokens"] - m0["shed_tokens"],
            "prefix_hit_rate": m1["prefix_hit_rate"],
        }

    def run_rate_router(rate, n_replicas):
        """Open-loop replay through the prefix-affinity router: the SAME
        storm (identical protos, identical Poisson arrivals at the same
        rate) that just hit one engine, now absorbed by N replicas — the
        question an operator staring at a shedding single engine actually
        asks. Past single-engine capacity the fleet's extra slots/KV/queue
        convert sheds and deadline misses back into goodput. Optional
        mid-storm replica kill."""
        from veomni_tpu.serving import Router, RouterConfig

        # respawns stay OFF here: this leg measures the DEGRADED fleet
        # (how survivors absorb a kill), not the healing one — the chaos
        # leg below owns resurrection. Pump workers heartbeat per replica
        # so a wedged replica is nameable from the stall JSON.
        router = Router(params, cfg, engine_cfg(
            queue_bound=queue_bound * n_replicas, classes=classes,
        ), RouterConfig(replicas=n_replicas, max_respawns=0,
                        heartbeat_dir=_bench_out_dir()))
        # compiled programs are SHARED across replicas: one warmup pass
        # through the router compiles for the whole fleet
        for r in warm:
            router.run([Request(prompt_ids=r.prompt_ids, sampling=r.sampling,
                                priority=r.priority)])
        reqs = clone_requests(proto)
        arng = np.random.default_rng((seed, int(rate * 1e6)))
        arrivals = np.cumsum(arng.exponential(1.0 / rate, size=len(reqs)))
        m0 = router.metrics()
        ids = []
        killed = ""
        t0 = time.perf_counter()
        i = 0
        while i < len(reqs) or router.has_work:
            now = time.perf_counter() - t0
            if (replica_kill_at_s > 0 and not killed
                    and now >= replica_kill_at_s
                    and len(router.live_replicas()) > 1):
                killed = router.live_replicas()[0].rid
                router.kill_replica(killed, reason="bench kill drill")
            while i < len(reqs) and arrivals[i] <= now:
                ids.append(router.submit(reqs[i]))
                i += 1
            if router.has_work:
                router.step()
            elif i < len(reqs):
                time.sleep(min(max(arrivals[i] - now, 0.0), 0.01))
        dt = time.perf_counter() - t0
        m1 = router.metrics(reset_window=False)
        outs = {rid: router._outputs[rid] for rid in ids}
        done = [o for o in outs.values()
                if o.finish_reason in ("eos", "length")]
        entry = {
            "arrival_rate_rps": rate,
            "replicas": n_replicas,
            "completed": len(done),
            "reject_rate": sum(
                1 for o in outs.values() if o.finish_reason == "rejected"
            ) / max(1, len(reqs)),
            "cancelled": sum(1 for o in outs.values()
                             if o.finish_reason == "cancelled"),
            "redispatched": int(m1["redispatched"]),
            "spills": int(m1["spills"]),
            # aggregate goodput from the OUTPUTS (deadline-met tokens over
            # the open-loop wall): a killed replica's engine totals leave
            # the fleet aggregate mid-run, so the lifetime-delta trick the
            # single-engine leg uses would undercount here
            "goodput_tok_s": sum(
                len(o.token_ids) for o in done if not o.deadline_missed
            ) / dt,
            # per-replica split from engine lifetime deltas (survivors
            # only — a killed replica drops out of the census)
            "per_replica_goodput_tok_s": {
                rid: (m["goodput_tokens"]
                      - m0["per_replica"].get(rid, {}).get(
                          "goodput_tokens", 0.0)) / dt
                for rid, m in m1["per_replica"].items()
            },
            "prefix_hit_rate": m1["prefix_hit_rate"],
        }
        if killed:
            entry["replica_killed"] = killed
            entry["replica_kill_at_s"] = replica_kill_at_s
        return entry

    sweep = []
    for rate in rates:
        sweep.append(run_rate(rate))
        _beat(global_step=len(sweep), phase="serve_open_loop")
    result = {
        "capacity_rps": capacity_rps,
        "num_slots": num_slots,
        "block_size": block_size,
        "n_requests": n_requests,
        "prompt_lens": list(prompt_lens),
        "max_new_tokens": max_new_tokens,
        "preset": preset,
        "queue_bound": queue_bound,
        "deadline_s": deadline_s,
        "interactive_frac": interactive_frac,
        "classes": classes,
        "sweep": sweep,
    }
    if kv_quant:
        # quantized leg at FIXED pool bytes: size the quantized pool to the
        # f32 pool's exact byte budget (int8 blocks are smaller, so more of
        # them fit), then replay the SAME Poisson arrivals at the SAME
        # swept rates — the per-rate goodput/reject deltas isolate what the
        # extra KV capacity buys under overload, at constant HBM spend
        import jax.numpy as jnp

        from veomni_tpu.ops.quantization import kv_block_nbytes

        kb = (cfg.num_hidden_layers, block_size,
              cfg.num_key_value_heads, cfg.head_dim)
        dtype_bytes = jnp.dtype(cfg.dtype).itemsize
        f32_block = kv_block_nbytes(*kb, kv_quant="none",
                                    dtype_bytes=dtype_bytes)
        q_block = kv_block_nbytes(*kb, kv_quant=kv_quant,
                                  dtype_bytes=dtype_bytes)
        f32_blocks = engine_cfg().num_blocks  # the defaulted f32 pool
        q_blocks = max(f32_blocks, (f32_blocks * f32_block) // q_block)
        q_sweep = []
        for rate in rates:
            q_sweep.append(run_rate(
                rate, kv_quant=kv_quant, weight_quant=weight_quant,
                num_blocks=int(q_blocks),
            ))
            _beat(global_step=len(q_sweep), phase="serve_open_loop_kvq")
        for base, q in zip(sweep, q_sweep):
            q["goodput_delta_tok_s"] = (
                q["goodput_tok_s"] - base["goodput_tok_s"]
            )
            q["reject_rate_delta"] = q["reject_rate"] - base["reject_rate"]
        result.update({
            "kv_quant": kv_quant,
            "weight_quant": weight_quant,
            "kv_block_bytes": float(q_block),
            "kv_block_bytes_f32": float(f32_block),
            "kvq_num_blocks": int(q_blocks),
            "f32_num_blocks": int(f32_blocks),
            "kvq_sweep": q_sweep,
        })
    if replicas > 1:
        # scale-out leg: the same storm at the same rate, N replicas;
        # goodput_scaling compares the fleet aggregate against the
        # single-engine leg at the identical arrival rate
        r_sweep = []
        for base, rate in zip(sweep, rates):
            entry = run_rate_router(rate, replicas)
            entry["goodput_scaling"] = (
                entry["goodput_tok_s"] / max(base["goodput_tok_s"], 1e-9)
            )
            entry["prefix_hit_rate_single"] = base["prefix_hit_rate"]
            r_sweep.append(entry)
            _beat(global_step=len(r_sweep), phase="serve_open_loop_router")
        result.update({
            "replicas": replicas,
            "replica_kill_at_s": replica_kill_at_s,
            "shared_prefix": shared_prefix,
            "router_sweep": r_sweep,
        })
    def _perturbed_params(idx: int):
        # deterministic non-trivial payload for publish drills: every
        # float leaf scaled by a per-publish factor (same shapes/dtypes,
        # so the hot-swap is zero-trace by construction)
        import jax
        import jax.numpy as jnp

        scale = 1.0 + 1e-3 * (idx + 1)
        return jax.tree_util.tree_map(
            lambda x: x * scale
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.inexact)
            else x,
            params,
        )

    if chaos_seed >= 0:
        # chaos soak: the same storm replayed fault-free and under a
        # seeded deterministic fault schedule against a SELF-HEALING
        # fleet; the seed in the report replays a failure bit-for-bit
        from veomni_tpu.resilience.chaos import (
            build_chaos_plan,
            run_chaos_soak,
        )
        from veomni_tpu.serving import Router, RouterConfig

        n_rep = replicas if replicas > 1 else 3
        chaos_rate = max(rates)
        arng = np.random.default_rng((seed, 777))
        chaos_arrivals = [float(t) for t in np.cumsum(
            arng.exponential(1.0 / chaos_rate, size=n_requests))]

        def chaos_factory():
            router = Router(params, cfg, engine_cfg(
                queue_bound=queue_bound * n_rep, classes=classes,
            ), RouterConfig(replicas=n_rep, replica_stall_ticks=2,
                            max_respawns=4, respawn_backoff_s=0.05,
                            respawn_backoff_max_s=0.5,
                            probation_requests=2,
                            heartbeat_dir=_bench_out_dir()))
            # warm under the default forgiving stall deadline — compiles
            # must not read as wedges — then run the warm set AGAIN: the
            # prefix-cache hits route through the chunked-prefill program,
            # which otherwise first compiles mid-storm and trips the
            # tightened deadline below
            for _ in range(2):
                router.run([Request(prompt_ids=list(r.prompt_ids),
                                    sampling=r.sampling,
                                    priority=r.priority) for r in warm])
            router.config.replica_stall_s = chaos_stall_s
            return router

        plan = build_chaos_plan(
            chaos_seed, duration_s=chaos_arrivals[-1],
            hang_seconds=2.0 * chaos_stall_s + 1.0,
            expected_ticks=max(50, (n_requests * max_new_tokens) // 8),
            publishes=max(0, chaos_publishes),
        )
        base_soak = run_chaos_soak(
            router_factory=chaos_factory, requests=clone_requests(proto),
            arrivals=chaos_arrivals, plan=None, restore_timeout_s=60.0)
        _beat(phase="serve_chaos_fault_free")
        chaos_soak = run_chaos_soak(
            router_factory=chaos_factory, requests=clone_requests(proto),
            arrivals=chaos_arrivals, plan=plan,
            publish_fn=(
                (lambda router, idx: router.publish_weights(
                    _perturbed_params(idx), f"storm-v{idx + 1}"))
                if chaos_publishes > 0 else None),
            restore_timeout_s=60.0)
        _beat(phase="serve_chaos")
        ratio = (chaos_soak["goodput_tok_s"]
                 / max(base_soak["goodput_tok_s"], 1e-9))

        def _slim(rep):
            return {k: v for k, v in rep.items()
                    if k not in ("outputs", "router")}

        result["chaos"] = {
            "seed": chaos_seed,
            "replicas": n_rep,
            "stall_s": chaos_stall_s,
            "arrival_rate_rps": chaos_rate,
            "plan": plan.to_doc(),
            "fault_free": _slim(base_soak),
            "chaos": _slim(chaos_soak),
            "goodput_ratio": ratio,
            # mid-storm publish coverage (chaos_publishes > 0): the soak's
            # invariants_ok already folds in version convergence
            "publishes": chaos_soak["publishes"],
            "published_versions": chaos_soak["published_versions"],
            "version_converged": chaos_soak["version_converged"],
            "publish_wall_s": chaos_soak["publish_wall_s"],
            "ok": bool(base_soak["invariants_ok"]
                       and chaos_soak["invariants_ok"]
                       and ratio >= 0.7),
        }
    if publish:
        # rolling-publish leg (BENCH_SERVE_PUBLISH=1): the same Poisson
        # storm replayed publish-free and then with ONE mid-storm rolling
        # weight publication over the self-healing fleet — publish wall
        # time, goodput ratio vs the publish-free replay (0.7 floor) and
        # the zero-new-traces gate from the moment of publish
        from veomni_tpu.models import decode as decode_mod
        from veomni_tpu.resilience.chaos import (
            build_chaos_plan,
            run_chaos_soak,
        )
        from veomni_tpu.serving import Router, RouterConfig

        n_rep = replicas if replicas > 1 else 3
        pub_rate = max(rates)
        prng = np.random.default_rng((seed, 778))
        pub_arrivals = [float(t) for t in np.cumsum(
            prng.exponential(1.0 / pub_rate, size=n_requests))]

        def publish_factory():
            router = Router(params, cfg, engine_cfg(
                queue_bound=queue_bound * n_rep, classes=classes,
            ), RouterConfig(replicas=n_rep, probation_requests=2,
                            heartbeat_dir=_bench_out_dir()))
            # warm twice like the chaos factory: the second pass routes
            # prefix-cache hits through the chunked-prefill program so
            # nothing compiles mid-storm
            for _ in range(2):
                router.run([Request(prompt_ids=list(r.prompt_ids),
                                    sampling=r.sampling,
                                    priority=r.priority) for r in warm])
            return router

        pub_plan = build_chaos_plan(
            max(0, chaos_seed) if chaos_seed >= 0 else seed,
            duration_s=pub_arrivals[-1],
            kills=0, hangs=0, delays=0, exceptions=0, publishes=1,
            expected_ticks=max(50, (n_requests * max_new_tokens) // 8),
        )
        base_rep = run_chaos_soak(
            router_factory=publish_factory, requests=clone_requests(proto),
            arrivals=pub_arrivals, plan=None, restore_timeout_s=60.0)
        _beat(phase="serve_publish_baseline")
        trace_mark: dict = {}

        def _publish_payload(router, idx):
            # trace census snapshot at the MOMENT of publish: the gate
            # covers exactly the drain -> swap -> rotation window
            trace_mark.update(decode_mod.TRACE_COUNTS)
            return router.publish_weights(_perturbed_params(idx),
                                          f"publish-v{idx + 1}")

        pub_rep = run_chaos_soak(
            router_factory=publish_factory, requests=clone_requests(proto),
            arrivals=pub_arrivals, plan=pub_plan,
            publish_fn=_publish_payload, restore_timeout_s=60.0)
        _beat(phase="serve_publish")
        trace_delta = (sum(decode_mod.TRACE_COUNTS.values())
                       - sum(trace_mark.values()))
        pratio = (pub_rep["goodput_tok_s"]
                  / max(base_rep["goodput_tok_s"], 1e-9))

        def _slim_pub(rep):
            return {k: v for k, v in rep.items()
                    if k not in ("outputs", "router")}

        result["publish"] = {
            "replicas": n_rep,
            "arrival_rate_rps": pub_rate,
            "plan": pub_plan.to_doc(),
            "baseline": _slim_pub(base_rep),
            "publish": _slim_pub(pub_rep),
            "published_versions": pub_rep["published_versions"],
            "publish_wall_s": pub_rep["publish_wall_s"],
            "version_converged": pub_rep["version_converged"],
            "goodput_ratio": pratio,
            "trace_delta": trace_delta,
            "ok": bool(base_rep["invariants_ok"]
                       and pub_rep["invariants_ok"]
                       and pub_rep["version_converged"]
                       and pratio >= 0.7
                       and trace_delta == 0),
        }
    return result


def _serve_open_loop_main(preset: str, watchdog=None):
    """BENCH_SERVE_OPEN_LOOP=1 entry: one JSON line for the overload
    trajectory (reject rate, p99 TTFT, goodput per arrival rate)."""
    lens = tuple(
        int(x) for x in
        os.environ.get("BENCH_SERVE_PROMPT_LENS", "64,128,256").split(",")
    )
    rates = tuple(
        float(x) for x in
        os.environ.get("BENCH_SERVE_ARRIVAL_RATES", "").split(",")
        if x.strip()
    )
    mults = tuple(
        float(x) for x in
        os.environ.get("BENCH_SERVE_RATE_MULTS", "0.5,1.0,2.0").split(",")
        if x.strip()
    )
    r = run_serve_open_loop_bench(
        num_slots=int(os.environ.get("BENCH_SERVE_SLOTS", 4)),
        block_size=int(os.environ.get("BENCH_SERVE_BLOCK", 16)),
        n_requests=int(os.environ.get("BENCH_SERVE_REQUESTS", 32)),
        prompt_lens=lens,
        max_new_tokens=int(os.environ.get("BENCH_SERVE_NEW_TOKENS", 32)),
        preset=preset,
        arrival_rates=rates,
        arrival_rate_mults=mults,
        queue_bound=int(os.environ.get("BENCH_SERVE_QUEUE_BOUND", 0)),
        deadline_s=float(os.environ.get("BENCH_SERVE_DEADLINE_S", 0.0)),
        interactive_frac=float(
            os.environ.get("BENCH_SERVE_INTERACTIVE_FRAC", 0.5)
        ),
        classes=os.environ.get("BENCH_SERVE_CLASSES",
                               "interactive:4,batch:1"),
        # BENCH_SERVE_KV_QUANT=int8 adds the fixed-pool-bytes quantized
        # leg (optionally BENCH_SERVE_WEIGHT_QUANT=int8 for tier 2 too)
        kv_quant=os.environ.get("BENCH_SERVE_KV_QUANT", ""),
        weight_quant=os.environ.get("BENCH_SERVE_WEIGHT_QUANT", "none"),
        # BENCH_SERVE_REPLICAS=N (N > 1) adds the scale-out router leg:
        # same arrivals at N-scaled rates over N data-parallel replicas;
        # BENCH_SERVE_REPLICA_KILL_AT_S kills one replica mid-storm and
        # BENCH_SERVE_SHARED_PREFIX makes the traffic affinity-routable
        shared_prefix=int(os.environ.get("BENCH_SERVE_SHARED_PREFIX", 0)),
        shared_prefix_groups=int(
            os.environ.get("BENCH_SERVE_PREFIX_GROUPS", 1)
        ),
        replicas=int(os.environ.get("BENCH_SERVE_REPLICAS", 1)),
        replica_kill_at_s=float(
            os.environ.get("BENCH_SERVE_REPLICA_KILL_AT_S", 0.0)
        ),
        # BENCH_SERVE_CHAOS=<seed> adds the chaos soak leg: a seeded
        # deterministic kill/hang/delay/exception schedule over a
        # self-healing fleet (3 replicas unless BENCH_SERVE_REPLICAS
        # says otherwise), reported against a fault-free replay
        chaos_seed=int(os.environ.get("BENCH_SERVE_CHAOS", -1)),
        chaos_stall_s=float(
            os.environ.get("BENCH_SERVE_CHAOS_STALL_S", 2.0)
        ),
        # BENCH_SERVE_CHAOS_PUBLISHES=N fires N mid-storm rolling weight
        # publications inside the chaos leg; BENCH_SERVE_PUBLISH=1 adds
        # the dedicated rolling-publish leg (publish wall time, goodput
        # ratio vs publish-free replay, zero-new-traces gate)
        chaos_publishes=int(
            os.environ.get("BENCH_SERVE_CHAOS_PUBLISHES", 0)
        ),
        publish=os.environ.get("BENCH_SERVE_PUBLISH", "0")
        not in ("0", ""),
    )
    if watchdog is not None:
        watchdog.stop()
    # headline = the HIGHEST swept rate, independent of the order the
    # rates/mults were supplied in (sweep entries keep supplied order)
    worst = max(r["sweep"], key=lambda e: e["arrival_rate_rps"],
                default={})
    print(json.dumps({
        **_DEVICE,
        # headline: goodput at the HIGHEST swept rate — the number that
        # stays honest when raw decode tok/s still looks fine past capacity
        "metric": "serve_open_loop_goodput_tok_s",
        "value": round(worst.get("goodput_tok_s", 0.0), 1),
        "unit": (
            f"deadline-met tokens/s ({r['preset']} bf16, "
            f"slots={r['num_slots']}, "
            f"rate={worst.get('arrival_rate_rps', 0.0):.2f}rps "
            f"~{worst.get('rate_vs_capacity', 0.0):.1f}x capacity, "
            f"queue_bound={r['queue_bound']})"
        ),
        "vs_baseline": 0.0,  # no published open-loop TPU baseline
        "capacity_rps": round(r["capacity_rps"], 3),
        "reject_rate": round(worst.get("reject_rate", 0.0), 4),
        "deadline_miss_rate": round(worst.get("deadline_miss_rate", 0.0), 4),
        "ttft_p99_s": round(worst.get("ttft_p99_s", 0.0), 5),
        "ttft_p99_interactive_s": round(
            worst.get("ttft_p99_interactive_s", 0.0), 5),
        "max_queue_depth": worst.get("max_queue_depth", 0),
        "sweep": [
            {k: (round(v, 5) if isinstance(v, float) else v)
             for k, v in entry.items()}
            for entry in r["sweep"]
        ],
        # fixed-pool-bytes quantized leg when BENCH_SERVE_KV_QUANT is set:
        # same arrivals, same byte budget, per-rate goodput/reject deltas
        **({
            "kv_quant": r["kv_quant"],
            "weight_quant": r["weight_quant"],
            "kv_block_bytes": r["kv_block_bytes"],
            "kv_block_bytes_f32": r["kv_block_bytes_f32"],
            "kvq_num_blocks": r["kvq_num_blocks"],
            "f32_num_blocks": r["f32_num_blocks"],
            "kvq_sweep": [
                {k: (round(v, 5) if isinstance(v, float) else v)
                 for k, v in entry.items()}
                for entry in r["kvq_sweep"]
            ],
        } if "kv_quant" in r else {}),
        # scale-out router leg when BENCH_SERVE_REPLICAS > 1: aggregate +
        # per-replica goodput, goodput_scaling vs the single-engine leg,
        # and the router-vs-single prefix hit rates
        **({
            "replicas": r["replicas"],
            "shared_prefix": r["shared_prefix"],
            "replica_kill_at_s": r["replica_kill_at_s"],
            "router_sweep": [
                {k: (round(v, 5) if isinstance(v, float) else
                     {rk: round(rv, 5) for rk, rv in v.items()}
                     if isinstance(v, dict) else v)
                 for k, v in entry.items()}
                for entry in r["router_sweep"]
            ],
        } if "router_sweep" in r else {}),
        # chaos soak leg when BENCH_SERVE_CHAOS is set: the seeded plan,
        # both soak reports (fault-free + chaos), the fleet invariants
        # and the goodput floor verdict
        **({
            "chaos": {
                k: (round(v, 5) if isinstance(v, float) else v)
                for k, v in r["chaos"].items()
                if k not in ("fault_free", "chaos")
            },
            "chaos_invariants_ok": r["chaos"]["chaos"]["invariants_ok"],
            "chaos_wedged": r["chaos"]["chaos"]["wedged"],
            "chaos_respawns": r["chaos"]["chaos"]["respawns"],
        } if "chaos" in r else {}),
        # rolling-publish leg when BENCH_SERVE_PUBLISH=1: publish wall
        # time, goodput ratio vs the publish-free replay and the
        # zero-new-traces verdict
        **({
            "publish": {
                k: (round(v, 5) if isinstance(v, float) else v)
                for k, v in r["publish"].items()
                if k not in ("baseline", "publish", "plan")
            },
            "publish_ok": r["publish"]["ok"],
            "publish_goodput_ratio": round(
                r["publish"]["goodput_ratio"], 5),
            "publish_trace_delta": r["publish"]["trace_delta"],
        } if "publish" in r else {}),
    }), flush=True)
    _cleanup_default_out()  # healthy exit: leave no heartbeats behind


def _serve_main(preset: str, watchdog=None):
    """BENCH_SERVE=1 entry: one JSON line for the serving trajectory."""
    lens = tuple(
        int(x) for x in
        os.environ.get("BENCH_SERVE_PROMPT_LENS", "64,128,256").split(",")
    )
    shared_prefix = int(os.environ.get("BENCH_SERVE_SHARED_PREFIX", 0))
    # chunked prefill defaults ON for the shared-prefix workload: without
    # chunks, cache-on and cache-off both run one prefill step per request
    # and the on-vs-off step-count comparison is vacuous
    prefill_chunk = int(os.environ.get(
        "BENCH_SERVE_PREFILL_CHUNK", 64 if shared_prefix > 0 else 0
    ))
    # BENCH_SERVE_SPEC_K="0,2,4,8" sweeps draft-then-verify speculation
    # over the same timed request set (empty/unset skips the sweep)
    spec_ks = tuple(
        int(x) for x in
        os.environ.get("BENCH_SERVE_SPEC_K", "").split(",") if x.strip()
    )
    r = run_serve_bench(
        num_slots=int(os.environ.get("BENCH_SERVE_SLOTS", 4)),
        block_size=int(os.environ.get("BENCH_SERVE_BLOCK", 16)),
        n_requests=int(os.environ.get("BENCH_SERVE_REQUESTS", 16)),
        prompt_lens=lens,
        max_new_tokens=int(os.environ.get("BENCH_SERVE_NEW_TOKENS", 64)),
        preset=preset,
        shared_prefix=shared_prefix,
        prefill_chunk=prefill_chunk,
        prefix_cache=os.environ.get("BENCH_SERVE_PREFIX_CACHE", "1")
        not in ("0", ""),
        spec_ks=spec_ks,
        spec_draft=os.environ.get("BENCH_SERVE_SPEC_DRAFT", "ngram"),
        # BENCH_SERVE_KV_QUANT=int8 adds the quantized-engine comparison
        # leg (optionally BENCH_SERVE_WEIGHT_QUANT=int8 for tier 2 too)
        kv_quant=os.environ.get("BENCH_SERVE_KV_QUANT", ""),
        weight_quant=os.environ.get("BENCH_SERVE_WEIGHT_QUANT", "none"),
    )
    if watchdog is not None:
        watchdog.stop()
    line = {
        **_DEVICE,
        "metric": "serve_decode_tokens_per_sec",
        "value": round(r["decode_tok_s"], 1),
        "unit": f"decode tokens/s ({r['preset']} bf16, slots={r['num_slots']}, "
                f"block={r['block_size']}, {r['n_requests']} reqs "
                f"mix{r['prompt_lens']}, ttft={r['ttft_mean_s']*1e3:.0f}ms, "
                f"preempt={r['preemptions']})",
        # nominal serving north star: 1k decode tok/s on one chip (no
        # published single-v5e continuous-batching baseline exists)
        "vs_baseline": round(r["decode_tok_s"] / 1000.0, 4),
        # per-request latency trajectory (observability/request_trace.py):
        # the SLO-scheduling roadmap item tunes priority classes against
        # exactly these percentiles, so BENCH_*.json must carry them
        "queue_wait_p50_s": round(r["queue_wait_p50_s"], 5),
        "queue_wait_p99_s": round(r["queue_wait_p99_s"], 5),
        "tpot_p50_s": round(r["tpot_p50_s"], 5),
        "tpot_p99_s": round(r["tpot_p99_s"], 5),
        "preemptions_per_request": round(r["preemptions_per_request"], 3),
        # prefix-cache effectiveness (serving/prefix_cache.py): timed-window
        # hit rate + prefill step count, with TTFT percentiles on vs off
        # when the shared-prefix workload is active
        "shared_prefix": r["shared_prefix"],
        "prefill_chunk": r["prefill_chunk"],
        "prefix_cache": r["prefix_cache"],
        "prefix_hit_rate": round(r["prefix_hit_rate"], 4),
        "cached_tokens_frac": round(r["cached_tokens_frac"], 4),
        "prefill_chunks": r["prefill_chunks"],
        "ttft_p50_s": round(r["ttft_p50_s"], 5),
        "ttft_p99_s": round(r["ttft_p99_s"], 5),
    }
    if "nocache_ttft_p50_s" in r:
        line["nocache_ttft_p50_s"] = round(r["nocache_ttft_p50_s"], 5)
        line["nocache_ttft_p99_s"] = round(r["nocache_ttft_p99_s"], 5)
        line["nocache_prefill_chunks"] = r["nocache_prefill_chunks"]
    if "spec_sweep" in r:
        # speculative decoding sweep (serving/spec_decode.py): decode tok/s
        # + verify acceptance rate per draft length k, nospec baseline from
        # the k=0 leg — the multi-token-decode tradeoff curve
        line["spec_draft"] = r["spec_draft"]
        line["spec_sweep"] = [
            {"spec_k": e["spec_k"],
             "decode_tok_s": round(e["decode_tok_s"], 1),
             "spec_acceptance_rate": round(e["spec_acceptance_rate"], 4),
             "spec_accepted_tokens": e["spec_accepted_tokens"],
             "tpot_p50_s": round(e["tpot_p50_s"], 5)}
            for e in r["spec_sweep"]
        ]
        if "nospec_decode_tok_s" in r:
            line["nospec_decode_tok_s"] = round(r["nospec_decode_tok_s"], 1)
            line["nospec_tpot_p50_s"] = round(r["nospec_tpot_p50_s"], 5)
    if "kv_quant" in r:
        # quantized serving tier (ops/quantization.py): same timed set
        # through an int8-KV (and optionally int8-weight) engine, with the
        # measured per-block bytes, the fixed-pool-bytes capacity ratio,
        # and the fixed-seed quality-gate stats riding in the same record
        line["kv_quant"] = r["kv_quant"]
        line["weight_quant"] = r["weight_quant"]
        line["kvq_decode_tok_s"] = round(r["kvq_decode_tok_s"], 1)
        line["kvq_ttft_p50_s"] = round(r["kvq_ttft_p50_s"], 5)
        line["kvq_ttft_p99_s"] = round(r["kvq_ttft_p99_s"], 5)
        line["kv_block_bytes"] = r["kv_block_bytes"]
        line["kv_block_bytes_f32"] = r["kv_block_bytes_f32"]
        line["kv_capacity_ratio"] = round(r["kv_capacity_ratio"], 3)
        line["quality_ppl_rel_delta"] = round(
            r["quality_ppl_rel_delta"], 6)
        line["quality_topk_overlap"] = round(r["quality_topk_overlap"], 4)
    print(json.dumps(line), flush=True)
    _cleanup_default_out()  # healthy exit: leave no heartbeats behind


def main():
    from veomni_tpu.utils.xla_flags import apply_performance_flags

    apply_performance_flags()
    serve = os.environ.get("BENCH_SERVE", "0") not in ("0", "")
    open_loop = os.environ.get("BENCH_SERVE_OPEN_LOOP", "0") not in ("0", "")
    watchdog = _start_watchdog(
        float(os.environ.get("BENCH_WATCHDOG_S", 900)),
        "serve_open_loop_goodput_tok_s" if open_loop
        else "serve_decode_tokens_per_sec" if serve
        else "train_tokens_per_sec_per_chip",
    )
    preset = os.environ.get("BENCH_PRESET", "qwen3_0p6b")
    if preset not in BENCH_PRESETS:  # fail fast, BEFORE the chip claim
        raise SystemExit(
            f"unknown BENCH_PRESET {preset!r}; choose from {sorted(BENCH_PRESETS)}"
        )
    if open_loop:
        return _serve_open_loop_main(preset, watchdog)
    if serve:
        return _serve_main(preset, watchdog)
    seq_len = int(os.environ.get("BENCH_SEQ_LEN", 4096))
    micro_bs = int(os.environ.get("BENCH_MICRO_BS", 4))
    steps = int(os.environ.get("BENCH_STEPS", 10))
    r = run_bench(
        seq_len, micro_bs, steps,
        attention_impl=os.environ.get("BENCH_ATTN_IMPL") or None,
        remat_policy=os.environ.get("BENCH_REMAT", "ctx"),
        preset=preset,
        optimizer=os.environ.get("BENCH_OPT", "adamw"),
        # BENCH_ULYSSES_ASYNC=1 selects the chunked async Ulysses pipeline
        # (only meaningful with BENCH_ULYSSES_SIZE > 1 on a multi-chip claim)
        ulysses_size=int(os.environ.get("BENCH_ULYSSES_SIZE", 1)),
        ulysses_async=os.environ.get("BENCH_ULYSSES_ASYNC", "0") not in ("0", ""),
        ulysses_async_chunks=int(os.environ.get("BENCH_ULYSSES_CHUNKS", 4)),
    )
    watchdog.stop()  # before printing: the watchdog must never race the
    # real record out of a block-buffered stdout via os._exit
    # MFU (and the baseline ratio built on it) only in a record taken on a
    # TPU: run_bench leaves it out elsewhere
    mfu = r.get("mfu")
    print(json.dumps({
        **_DEVICE,
        "metric": "train_tokens_per_sec_per_chip",
        "value": round(r["tok_s_chip"], 1),
        "unit": f"tokens/s/chip ({r['preset']} bf16 {r['optimizer']}, "
                f"seq{seq_len}"
                + (f", mfu={mfu:.1f}%)" if mfu is not None else ")"),
        **({} if mfu is None else {"vs_baseline": round(mfu / 40.0, 4)}),
        # utilization trajectory: BENCH_*.json now captures where the wall
        # time went, not just the headline rate (docs/observability.md)
        "goodput_pct": round(r["goodput_pct"], 2),
        "data_wait_frac": round(r["data_wait_frac"], 4),
        "recompiles": r["recompiles"],
        # integrity trajectory (docs/resilience.md "Integrity & quarantine"):
        # nonzero quarantine/fallback counts mean the measurement ran on a
        # run that survived storage rot — worth knowing next to its MFU
        "restore_verify_s": round(r["restore_verify_s"], 4),
        "ckpt_quarantined": r["ckpt_quarantined"],
        "ckpt_fallbacks": r["ckpt_fallbacks"],
        # device cost census (docs/observability.md "Device cost &
        # capacity"): what XLA compiled, how long it took, and whether the
        # analytic MFU denominator still agrees with it (FLOPS_RATIO_BAND)
        "compile_time_s": r["compile_time_s"],
        "xla_flops_per_step": r["xla_flops_per_step"],
        "analytic_vs_xla_flops_ratio": r["analytic_vs_xla_flops_ratio"],
    }), flush=True)
    _cleanup_default_out()  # healthy exit: leave no heartbeats behind


if __name__ == "__main__":
    main()
