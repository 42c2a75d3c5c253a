"""Minimal CLI driver for the continuous-batching inference engine.

Operates on token ids (tokenization is out of scope for the driver): either
a stream of synthetic random-prompt requests (``--synthetic N``) or explicit
comma-separated prompts (``--prompt-ids 5,17,3`` repeatable). Streams every
token event to stdout as it lands and prints the engine metrics at the end.

By default builds a tiny random-weight qwen3-style model (engine plumbing
demo / CPU smoke); ``--preset <name>`` builds the model of
``configs/text/<name>_v5e.yaml`` (``qwen3_0p6b``: Qwen3-0.6B at its published
widths) for the real accelerator.

Run:
  python scripts/serve.py --synthetic 8 --max-new 32
  python scripts/serve.py --prompt-ids 1,2,3 --prompt-ids 4,5 \
      --temperature 0.8 --top-p 0.9
  python scripts/serve.py --requests-json mixed_traffic.json

``--requests-json`` takes a JSON list of request objects carrying the
per-request QoS surface: ``{"prompt_ids": [...], "priority":
"interactive"|"batch", "tenant": "...", "deadline_s": 2.5,
"max_new_tokens": 32, "temperature": 0.0, ...}`` (every field except
``prompt_ids`` optional, ``-`` reads stdin). Requests load-shed by the
bounded queue (``--queue-bound``) or cancelled past their deadline come
back as distinct terminal statuses in the final JSON — the driver never
waits on tokens a shed request will not produce.

Env knobs (flags win): VEOMNI_SERVE_SLOTS, VEOMNI_SERVE_BLOCK,
VEOMNI_SERVE_MAX_LEN, VEOMNI_SERVE_LOG_STEPS, VEOMNI_SERVE_PREFIX_CACHE
(1 default; 0 disables prompt-block sharing), VEOMNI_SERVE_PREFILL_CHUNK
(tokens prefilled per engine tick, 0 = whole prompt at once),
VEOMNI_SERVE_SPEC_K (draft-then-verify speculation: max drafted tokens per
slot per tick, 0 = off) with VEOMNI_SERVE_SPEC_DRAFT selecting the drafting
strategy (`ngram` prompt-lookup default, `off` disables),
VEOMNI_SERVE_QUEUE_BOUND (max waiting requests before submissions are
load-shed with a terminal "rejected" status; 0 = unbounded),
VEOMNI_SERVE_KV_QUANT (KV block storage: `none` default | `int8` —
int8 blocks + f32 scale sidecar, ~4x concurrent sequences per pool byte
at f32, quality-gated), VEOMNI_SERVE_WEIGHT_QUANT (decode weight
storage: `none` default | `int8` per-channel, dequantized in-kernel),
VEOMNI_SERVE_CLASSES (QoS classes "name:weight,..." highest priority
first; a single class restores plain FIFO), VEOMNI_SERVE_TENANT_INFLIGHT
(per-tenant waiting+running cap, 0 = uncapped),
VEOMNI_SERVE_REPLICAS (``--replicas N``: N > 1 serves through the
scale-out router — prefix-affinity dispatch over N data-parallel engine
replicas sharing one compiled-program bundle, QoS admission at the
router, per-replica ``serve.rK.*`` metrics and a per-replica status
census in the final JSON; 1 = the bare engine, byte-identical to the
seed driver), VEOMNI_SERVE_OUT (post-mortem dump dir, default CWD; when
set, router pump workers also heartbeat there as heartbeat-<rid>.json).
Self-healing fleet knobs (router mode, docs/serving.md):
VEOMNI_SERVE_STALL_S (per-replica step() deadline before a replica is
declared wedged and its pump thread abandoned, default 60, 0 disables),
VEOMNI_SERVE_MAX_RESPAWNS (respawn budget per replica lineage before
permanent retirement, default 2, 0 disables resurrection),
VEOMNI_SERVE_PROBATION (clean completions a respawned replica must serve
on spill traffic before rejoining affinity rotation, default 2),
VEOMNI_SERVE_MIN_LIVE (live-replica floor under which /healthz answers
503, default 1).
Live weight publication (docs/serving.md "Versioned weight
publication"): ``--publish-from <step dir>`` (VEOMNI_SERVE_PUBLISH_FROM)
loads a committed checkpoint generation through the integrity gate
(VEOMNI_SERVE_PUBLISH_VERIFY: off|size|full, default size — corrupt or
uncommitted generations are refused before any live buffer is touched)
and hot-publishes it: router mode rolls the fleet replica-by-replica
after the first token lands (drain -> in-place swap -> prefix-cache
flush, zero new traces); bare-engine mode swaps in place before serving.
VEOMNI_SERVE_PUBLISH_VERSION tags the published version (default: the
step dir's basename). /healthz and /debug/router report the fleet
weights version, per-replica versions and publish-in-progress.
VEOMNI_METRICS_PORT
serves Prometheus /metrics + /healthz while the pump runs (healthz carries
rejected/deadline-miss counts); /debug/requests
rows carry each request's cached_tokens, /debug/router the router's
replica census, and /debug/fleet the collective
census of the engine's compiled programs (docs/observability.md).
VEOMNI_FAULT_PLAN arms the serving fault points (serve.admit /
serve.prefill / serve.decode_tick, docs/resilience.md) for overload and
stall drills.
"""

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def build_model(preset: str = "", seed: int = 0):
    """(params, cfg): the model of ``configs/text/<preset>_v5e.yaml``, or the
    tiny demo model when ``preset`` is empty; random weights from ``seed``.

    The preset's widths are the recipe's ``model.config_overrides``, built
    through ``models.auto.build_config`` as the trainer builds them, so they
    are written once. The recipe names no dtype: compute in bfloat16 over
    float32 parameters is ``TransformerConfig``'s default."""
    import jax
    import jax.numpy as jnp

    from veomni_tpu.models import TransformerConfig, build_foundation_model

    if preset:
        import yaml

        from veomni_tpu.models.auto import build_config

        recipes = os.path.join(_REPO, "configs", "text")
        path = os.path.join(recipes, f"{preset}_v5e.yaml")
        if not os.path.isfile(path):
            known = sorted(n[:-len("_v5e.yaml")] for n in os.listdir(recipes)
                           if n.endswith("_v5e.yaml"))
            raise SystemExit(
                f"unknown --preset {preset!r}: no {path}; choose from {known}")
        with open(path) as f:
            overrides = yaml.safe_load(f)["model"]["config_overrides"]
        cfg = build_config(**overrides)
    else:  # tiny random demo model
        cfg = TransformerConfig(
            model_type="qwen3", vocab_size=256, hidden_size=64,
            intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            qk_norm=True, dtype=jnp.float32,
        )
    model = build_foundation_model(config=cfg)
    params = model.family.init_params(jax.random.PRNGKey(seed), cfg)
    return params, cfg


def _ckpt_params_loader(step_dir):
    """Restore the params subtree of a trainer checkpoint generation.

    Abstract target comes from on-disk metadata (same idiom as
    merge_checkpoint_to_hf.py), so the loader needs no knowledge of the
    optimizer that produced the checkpoint. This orbax version has no
    partial restore, so optimizer moments are materialized then dropped —
    budget host RAM accordingly for big models.
    """
    import jax
    import orbax.checkpoint as ocp

    path = os.path.join(os.path.abspath(step_dir), "train_state")
    ckptr = ocp.StandardCheckpointer()
    meta = ckptr.metadata(path)
    # older orbax returns the tree metadata directly; newer wraps it
    meta = getattr(meta, "item_metadata", meta)
    target = jax.tree.map(
        lambda m: jax.ShapeDtypeStruct(m.shape, m.dtype),
        {"params": meta["params"], "opt_state": meta["opt_state"],
         "step": meta["step"]},
    )
    return ckptr.restore(path, target)["params"]


def main():
    from veomni_tpu.utils.xla_flags import apply_performance_flags

    apply_performance_flags()  # before the first JAX backend use
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--prompt-ids", action="append", default=[],
                    help="comma-separated token ids; repeatable")
    ap.add_argument("--synthetic", type=int, default=0,
                    help="also enqueue N random prompts")
    ap.add_argument("--synthetic-len", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--eos-id", type=int, default=-1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--preset", default="",
                    help="the model of configs/text/<preset>_v5e.yaml "
                         "instead of the tiny demo")
    ap.add_argument("--slots", type=int,
                    default=int(os.environ.get("VEOMNI_SERVE_SLOTS", 4)))
    ap.add_argument("--block-size", type=int,
                    default=int(os.environ.get("VEOMNI_SERVE_BLOCK", 16)))
    ap.add_argument("--max-model-len", type=int,
                    default=int(os.environ.get("VEOMNI_SERVE_MAX_LEN", 2048)))
    ap.add_argument("--log-steps", type=int,
                    default=int(os.environ.get("VEOMNI_SERVE_LOG_STEPS", 0)))
    ap.add_argument("--prefix-cache", type=int, choices=(0, 1),
                    default=int(os.environ.get("VEOMNI_SERVE_PREFIX_CACHE",
                                               1)),
                    help="share prompt KV blocks across requests (radix "
                         "prefix cache; 0 restores exclusive blocks)")
    ap.add_argument("--prefill-chunk", type=int,
                    default=int(os.environ.get("VEOMNI_SERVE_PREFILL_CHUNK",
                                               0)),
                    help="max tokens prefilled per engine tick (0 = whole "
                         "prompt at once; bounds how long a long arrival "
                         "stalls running decodes)")
    ap.add_argument("--spec-k", type=int,
                    default=int(os.environ.get("VEOMNI_SERVE_SPEC_K", 0)),
                    help="speculative decoding: max drafted tokens per "
                         "slot per tick, verified in one batched step "
                         "(0 = off; token-exact either way)")
    ap.add_argument("--spec-draft",
                    default=os.environ.get("VEOMNI_SERVE_SPEC_DRAFT",
                                           "ngram"),
                    help="drafting strategy registry impl (`ngram` "
                         "prompt-lookup, `off`)")
    ap.add_argument("--kv-quant", choices=("none", "int8", "fp8"),
                    default=os.environ.get("VEOMNI_SERVE_KV_QUANT", "none"),
                    help="KV block storage mode: int8 stores blocks as "
                         "int8 + f32 scale sidecar (~4x concurrent "
                         "sequences per pool byte at f32; NOT bit-exact — "
                         "ships under the fixed-seed quality gate)")
    ap.add_argument("--weight-quant", choices=("none", "int8"),
                    default=os.environ.get("VEOMNI_SERVE_WEIGHT_QUANT",
                                           "none"),
                    help="decode-path weight storage: int8 per-channel, "
                         "dequantized in-kernel (decode_matmul/xla_q8)")
    ap.add_argument("--queue-bound", type=int,
                    default=int(os.environ.get("VEOMNI_SERVE_QUEUE_BOUND",
                                               0)),
                    help="max waiting requests before submissions are "
                         "load-shed (terminal 'rejected' status; 0 = "
                         "unbounded)")
    ap.add_argument("--classes",
                    default=os.environ.get("VEOMNI_SERVE_CLASSES",
                                           "interactive:4,batch:1"),
                    help="QoS classes 'name:weight,...', highest priority "
                         "first; a single class restores plain FIFO")
    ap.add_argument("--tenant-inflight", type=int,
                    default=int(os.environ.get("VEOMNI_SERVE_TENANT_INFLIGHT",
                                               0)),
                    help="per-tenant waiting+running cap (0 = uncapped)")
    ap.add_argument("--replicas", type=int,
                    default=int(os.environ.get("VEOMNI_SERVE_REPLICAS", 1)),
                    help="N > 1 serves through the scale-out router over N "
                         "data-parallel engine replicas (prefix-affinity "
                         "dispatch, QoS at the router); 1 = bare engine")
    ap.add_argument("--priority", default="interactive",
                    help="QoS class for CLI-built requests")
    ap.add_argument("--tenant", default="",
                    help="tenant id for CLI-built requests")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="end-to-end deadline for CLI-built requests "
                         "(0 = none)")
    ap.add_argument("--requests-json", default="",
                    help="JSON list of request objects (prompt_ids + "
                         "optional priority/tenant/deadline_s/"
                         "max_new_tokens/temperature/top_k/top_p/eos_id/"
                         "seed); '-' reads stdin")
    ap.add_argument("--publish-from",
                    default=os.environ.get("VEOMNI_SERVE_PUBLISH_FROM", ""),
                    help="checkpoint step dir (global_step_N) to hot-"
                         "publish: integrity-gated load, then rolling "
                         "in-place swap mid-serve (router mode) or a "
                         "pre-serve swap (bare engine)")
    ap.add_argument("--publish-version",
                    default=os.environ.get("VEOMNI_SERVE_PUBLISH_VERSION",
                                           ""),
                    help="version tag for the published weights "
                         "(default: the step dir's basename)")
    ap.add_argument("--publish-verify", choices=("off", "size", "full"),
                    default=os.environ.get("VEOMNI_SERVE_PUBLISH_VERIFY",
                                           "size"),
                    help="manifest verification mode for --publish-from "
                         "(docs/resilience.md; corrupt generations are "
                         "refused before any buffer is touched)")
    args = ap.parse_args()

    import numpy as np

    from veomni_tpu.serving import (
        EngineConfig,
        InferenceEngine,
        Request,
        SamplingParams,
    )

    # VEOMNI_FAULT_PLAN: serving drills (serve.admit / serve.prefill /
    # serve.decode_tick) arm exactly like the trainer's
    from veomni_tpu.resilience.faults import arm_from_env

    arm_from_env()

    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    params, cfg = build_model(args.preset, args.seed)
    ecfg = EngineConfig(
        num_slots=args.slots, block_size=args.block_size,
        max_model_len=args.max_model_len, log_every_steps=args.log_steps,
        prefix_cache=bool(args.prefix_cache),
        prefill_chunk=args.prefill_chunk,
        spec_k=args.spec_k, spec_draft=args.spec_draft,
        classes=args.classes, queue_bound=args.queue_bound,
        tenant_max_inflight=args.tenant_inflight,
        kv_quant=args.kv_quant, weight_quant=args.weight_quant,
    )
    router = None
    if args.replicas > 1:
        from veomni_tpu.serving import Router, RouterConfig

        # self-healing knobs (docs/serving.md "Self-healing fleet"):
        # wedge deadline, respawn budget, probation length, and the live
        # floor under which /healthz flips 503. Heartbeats only when the
        # operator chose an artifact dir — the CLI default CWD ('.')
        # would litter launch directories with heartbeat files.
        router = Router(params, cfg, ecfg, RouterConfig(
            replicas=args.replicas,
            replica_stall_s=float(
                os.environ.get("VEOMNI_SERVE_STALL_S", 60.0)),
            max_respawns=int(
                os.environ.get("VEOMNI_SERVE_MAX_RESPAWNS", 2)),
            probation_requests=int(
                os.environ.get("VEOMNI_SERVE_PROBATION", 2)),
            min_live=int(os.environ.get("VEOMNI_SERVE_MIN_LIVE", 1)),
            heartbeat_dir=os.environ.get("VEOMNI_SERVE_OUT", ""),
        ))
        # any replica describes the per-replica pool; all are identical
        first = next(iter(router.replicas.values())).engine
        driver, cap_engine = router, first
    else:
        driver = cap_engine = InferenceEngine(params, cfg, ecfg)
    # startup echo of the quant tier next to the capacity it buys: the
    # operator sees the storage mode AND the "users that fit" figure the
    # quantized pool actually provides, before any request lands
    cap = cap_engine.kv_capacity()
    print(json.dumps({
        "kv_quant": args.kv_quant, "weight_quant": args.weight_quant,
        "replicas": args.replicas,
        "kv_pool_bytes": cap["pool_bytes"],
        "kv_block_bytes": cap["block_bytes"],
        "kv_max_concurrent_seqs": cap["max_concurrent_seqs"],
    }), flush=True)
    # --publish-from: load THROUGH the integrity gate before serving a
    # single token, so a corrupt/uncommitted generation fails fast here
    # with an actionable error instead of mid-serve. The actual swap is
    # deferred: router mode rolls it after the first token lands (the
    # hot-publish path this flag exists to exercise); bare-engine mode
    # swaps in place right away (the engine refuses swaps while busy).
    publish_params = None
    publish_version = ""
    if args.publish_from:
        from veomni_tpu.resilience.integrity import CheckpointCorruptError
        from veomni_tpu.serving import load_published_params

        try:
            publish_params = load_published_params(
                args.publish_from, _ckpt_params_loader,
                verify_mode=args.publish_verify)
        except CheckpointCorruptError as e:
            raise SystemExit(
                f"--publish-from refused by integrity gate: {e}")
        publish_version = args.publish_version or os.path.basename(
            os.path.normpath(args.publish_from))
    if publish_params is not None and router is None:
        info = driver.swap_weights(publish_params)
        print(json.dumps({"publish": publish_version, "mode": "pre-serve",
                          **info}), flush=True)
        publish_params = None  # consumed
    # VEOMNI_METRICS_PORT: Prometheus /metrics + /healthz + /debug/flight +
    # /debug/requests (per-request timelines) for the pump loop (the engine
    # feeds the same registry the trainer exports through)
    from veomni_tpu.observability.exporter import maybe_start_from_env
    from veomni_tpu.observability.flight_recorder import (
        configure_flight_recorder,
    )

    # post-mortems (watchdog / crash) land somewhere deliberate, not
    # whatever CWD the operator launched from
    configure_flight_recorder(
        dump_dir=os.environ.get("VEOMNI_SERVE_OUT", ".")
    )
    from veomni_tpu.observability.metrics import get_registry

    # the exporter's HTTP thread must NOT read live scheduler internals the
    # pump loop mutates (unlocked cross-thread read — the lock-discipline
    # audit in docs/static-analysis.md): the engine publishes these as
    # thread-safe registry gauges after every tick, so health reads those
    if router is not None:
        # router mode: engine gauges carry the serve.rK.* instance label;
        # the health doc reads the router-level aggregates instead, and
        # /debug/requests merges every replica's (thread-safe) tracer.
        # Tracer list captured at startup — the CLI never resizes the fleet
        tracers = [h.engine.tracer for h in router.replicas.values()]

        def _requests_fn():
            doc = {"inflight": [], "finished": []}
            for t in tracers:
                snap = t.snapshot()
                doc["inflight"].extend(snap.get("inflight", ()))
                doc["finished"].extend(snap.get("finished", ()))
            return doc

        def _health_fn():
            # router.health() is a thread-safe snapshot read: healthy
            # flips False — exporter answers 503 — while the live count
            # sits under min_live, and recovers when respawns land
            doc = router.health()
            reg = get_registry()
            doc["rejected"] = reg.counter("serve.router.rejected").value
            doc["deadline_cancelled"] = reg.counter(
                "serve.router.deadline_cancelled").value
            return doc

        exporter = maybe_start_from_env(
            health_fn=_health_fn, requests_fn=_requests_fn,
            memory_fn=cap_engine.kv_capacity, router_fn=router.debug_doc)
    else:
        exporter = maybe_start_from_env(health_fn=lambda: {
            "healthy": True,
            "queue_depth": get_registry().gauge("serve.queue_depth").value,
            "num_running": get_registry().gauge("serve.num_running").value,
            # overload outcomes (thread-safe registry counters, same rule):
            # a probe sees shedding/deadline pressure without log scraping
            "rejected": get_registry().counter("serve.rejected").value,
            "deadline_misses":
                get_registry().counter("serve.deadline_misses").value,
        }, requests_fn=driver.tracer.snapshot,
            # /debug/memory gains the KV pool capacity document (pool bytes
            # + estimated max-concurrent sequences) next to the buffer
            # census
            memory_fn=driver.kv_capacity)

    sampling = SamplingParams(
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        max_new_tokens=args.max_new, eos_id=args.eos_id, seed=args.seed,
    )
    cli_deadline = args.deadline_s if args.deadline_s > 0 else None
    prompts = [[int(t) for t in s.split(",")] for s in args.prompt_ids]
    rng = np.random.default_rng(args.seed)
    prompts += [
        [int(t) for t in rng.integers(1, cfg.vocab_size, args.synthetic_len)]
        for _ in range(args.synthetic)
    ]
    reqs = [Request(prompt_ids=p, sampling=sampling, priority=args.priority,
                    tenant=args.tenant, deadline_s=cli_deadline)
            for p in prompts]
    if args.requests_json:
        if args.requests_json == "-":
            docs = json.load(sys.stdin)
        else:
            with open(args.requests_json) as f:
                docs = json.load(f)
        for d in docs:
            # same convention as --deadline-s: absent falls back to the
            # CLI default, <= 0 means "no deadline" (an explicit 0 in the
            # JSON opts OUT of the CLI default rather than setting an
            # instantly-expired deadline)
            if d.get("deadline_s") is None:
                dl = cli_deadline
            else:
                dl = float(d["deadline_s"])
                dl = dl if dl > 0 else None
            reqs.append(Request(
                prompt_ids=[int(t) for t in d["prompt_ids"]],
                sampling=SamplingParams(
                    temperature=float(d.get("temperature",
                                            args.temperature)),
                    top_k=int(d.get("top_k", args.top_k)),
                    top_p=float(d.get("top_p", args.top_p)),
                    max_new_tokens=int(d.get("max_new_tokens",
                                             args.max_new)),
                    eos_id=int(d.get("eos_id", args.eos_id)),
                    seed=int(d.get("seed", args.seed)),
                ),
                request_id=str(d.get("request_id", "")),
                priority=str(d.get("priority", args.priority)),
                tenant=str(d.get("tenant", args.tenant)),
                deadline_s=dl,
            ))
    if not reqs:
        ap.error("nothing to do: pass --prompt-ids, --synthetic N "
                 "and/or --requests-json")
    try:
        for ev in driver.generate(reqs):
            line = {"request_id": ev.request_id, "index": ev.index,
                    "token": ev.token}
            if ev.finished:
                line["finished"] = ev.finish_reason
            print(json.dumps(line), flush=True)
            if publish_params is not None:
                # router mode: fire the rolling publish once the fleet
                # is demonstrably serving (first token landed). step()
                # drains each replica and swaps in place from here on;
                # generate() keeps pumping until the fleet converges.
                router.publish_weights(publish_params, publish_version)
                print(json.dumps({"publish": publish_version,
                                  "mode": "rolling"}), flush=True)
                publish_params = None
        outs = driver.run()  # no-op drain; collects final outputs
    except BaseException as e:
        # same contract as trainer.train(): a pump that dies mid-decode
        # leaves its request/event history in a post-mortem, not in the void
        from veomni_tpu.observability.flight_recorder import dump_postmortem

        extra = {"error": str(e)[:2000]}
        try:
            # a pool/allocator blowup gets the buffer + cost censuses:
            # what held HBM and which program asked for more
            from veomni_tpu.observability.devmem import attach_oom_extra

            attach_oom_extra(e, extra)
        except Exception as forensic_err:  # even the import must be safe
            extra["oom_report_error"] = str(forensic_err)
        dump_postmortem(f"exception:{type(e).__name__}", extra=extra)
        raise
    print(json.dumps({"metrics": driver.metrics()}), flush=True)
    if exporter is not None:
        exporter.stop()
    # terminal-status census first: shed/expired requests are reported
    # DISTINCTLY (they produced no final token event to learn it from)
    by_status = {"ok": 0, "rejected": 0, "deadline": 0, "cancelled": 0}
    for o in outs.values():
        key = o.finish_reason if o.finish_reason in by_status else "ok"
        by_status[key] += 1
    census = {
        "completed": by_status["ok"],
        "rejected": by_status["rejected"],
        "deadline_cancelled": by_status["deadline"],
        "cancelled": by_status["cancelled"],
        "deadline_missed": sum(1 for o in outs.values()
                               if o.deadline_missed),
    }
    if router is not None:
        # per-replica rollup in the same census line: where the traffic
        # actually landed (dispatch/redispatch counts, terminal states)
        census["replicas"] = [h.status_doc()
                              for h in router.replicas.values()]
        census["replicas_retired"] = [h.status_doc()
                                      for h in router.retired]
    print(json.dumps(census), flush=True)
    for rid in sorted(outs):
        o = outs[rid]
        line = {
            "request_id": rid, "tokens": o.token_ids,
            "finish_reason": o.finish_reason,
            "ttft_s": round(o.ttft_s, 4) if o.ttft_s is not None else None,
            "cached_tokens": o.cached_tokens,
            "spec_accepted_tokens": o.spec_accepted_tokens,
            # quant tier echoed per request: a scraped response line is
            # self-describing about whether it came off a quantized engine
            "kv_quant": args.kv_quant,
            "weight_quant": args.weight_quant,
        }
        if o.deadline_missed:
            line["deadline_missed"] = True
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
