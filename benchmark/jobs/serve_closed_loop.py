"""Driver of the ``serve_closed_loop`` traffic kind.

Builds ``InferenceEngine`` through the program's public surface
(``veomni_tpu.serving``: ``EngineConfig``, ``Request``, ``SamplingParams``,
``submit`` / ``step`` / ``pop_output``) over the benchmark's own seeded
weights, and drives a closed loop of ``clients`` clients from one host loop:
a client's next request goes in right after the tick in which its last one
finished, so ``clients - num_slots`` requests wait at any time and the
engine's slots are never empty for want of work.

Set-up (README_serve.md says why each part): the weights on the device; the
engine; a ladder of lone requests that runs each of the engine's bucketed
programs once; the small eager programs the engine builds for a new prompt
length, built here for the stream's next requests; then ``warmup_ticks`` ticks
of the closed loop itself, from the stream's first request on. The window
opens at the end of the last of those ticks, every slot busy, and ends with
the last tick that ends inside ``--seconds``. A traced run
traces ``traced_ticks`` whole ticks first (the first and last left out of
what is read) and is an untraced run from there.

``correct`` (README_serve.md, PERF.md section 2): once the window has closed,
the memory peak is read and the engine is freed, the plain reference runs one
full forward over the prompt and served tokens of a sample of the requests
the window finished (drawn from the seed, the longest among them) and reads,
at every served position, its best logit less the logit of the token served.
"""

from __future__ import annotations

import gc
import importlib
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import compare, traffic, traffic_serve
from benchmark import trace as tr
# the program, imported when the harness imports this driver: before the
# first use of the TPU, whose runtime threads slow a later import threefold
from veomni_tpu.models import build_foundation_model
from veomni_tpu.models.auto import build_config
from veomni_tpu.observability import spans as prog_spans
from veomni_tpu.observability.metrics import get_registry
from veomni_tpu.serving import EngineConfig, InferenceEngine, Request, SamplingParams

TICK_SPAN = "bench.tick"  # the benchmark's own span round one engine.step()
DECODE_SITE = "paged_decode"  # the decode step's jit site in the program's cost census
DECODE_SPAN = "serve.decode"  # the program's span round one batched decode step
ENGINE_KEYS = ("num_slots", "block_size", "max_model_len", "num_blocks", "prefix_cache",
               "prefill_chunk", "kv_quant", "weight_quant", "spec_k", "classes")
MODEL_KEYS = ("model_type", "vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim",
              "tie_word_embeddings", "rope_theta", "max_position_embeddings", "rms_norm_eps")


def _pow2(n: int, floor: int) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


class ClosedLoop:
    """The clients, and what the job sees of every request and every tick.

    One host loop and no thread: ``tick()`` is one ``engine.step()``, reads its
    token events, takes finished requests' outputs and sends each freed
    client's next request of the stream."""

    def __init__(self, engine, stream, mix):
        self.engine, self.stream = engine, stream
        self.clients = mix["clients"]
        sampling = mix["sampling"]
        self.sampling = {"temperature": sampling["temperature"], "eos_id": sampling["eos_id"]}
        self.sent = 0
        self.closed = False  # True from the moment the clients send the stream
        self.flying = {}     # request id -> record
        self.finished = []   # records, in the order they finished
        self.ticks = []      # one dict a tick
        self.max_flying = 0
        # a traced run keeps the scope map of every program the decode site
        # compiles (the census hands out its NEWEST program's, and the site has
        # one a table-width bucket) and notes which bucket each tick ran
        self.census = None
        self.scope_maps = {}
        self._calls = {}
        registry = get_registry()
        self._gauges = {k: registry.gauge(f"serve.{k}") for k in
                        ("num_running", "kv_utilization", "queue_depth", "preemptions")}

    def send(self, item, index=None) -> dict:
        request = Request(prompt_ids=item["prompt_ids"].tolist(), sampling=SamplingParams(
            max_new_tokens=item["max_new_tokens"], **self.sampling))
        rid = self.engine.submit(request)
        rec = {"id": rid, "index": index, "prompt": item["prompt_ids"],
               "max_new_tokens": item["max_new_tokens"], "events": 0, "sent_tick": len(self.ticks),
               "order_lost": 0}
        self.flying[rid] = rec
        self.max_flying = max(self.max_flying, len(self.flying))
        return rec

    def fill(self) -> None:
        while len(self.flying) < self.clients and self.sent < len(self.stream):
            self.send(self.stream[self.sent], index=self.sent)
            self.sent += 1

    def tick(self) -> dict:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(TICK_SPAN):
            events = self.engine.step()
        t1 = time.perf_counter()
        obs = {"t0": t0, "t1": t1, "tokens": len(events), "decoded": 0, "prefilled": 0,
               "forward_tokens": 0, "context_sum": 0, "decode_context": 0, "prompt_tokens": 0,
               "cached_tokens": 0, "finished": 0}
        for ev in events:
            rec = self.flying[ev.request_id]
            p = len(rec["prompt"])
            rec["order_lost"] += int(ev.index != rec["events"])
            rec["events"] += 1
            if ev.index == 0:
                # the first token comes from the prefill's last row: the
                # forwards are the prompt positions that no cached prefix held
                cached = min(self.engine.get_output(ev.request_id).cached_tokens, p - 1)
                rec["cached"] = cached
                obs["prefilled"] += 1
                obs["forward_tokens"] += p - cached
                obs["context_sum"] += (p * (p + 1) - cached * (cached + 1)) // 2
                obs["prompt_tokens"] += p
                obs["cached_tokens"] += cached
            else:
                # token ``index`` came from a forward of the token before it,
                # which read the prompt and every token up to itself
                obs["decoded"] += 1
                obs["forward_tokens"] += 1
                obs["context_sum"] += p + ev.index
                obs["decode_context"] += p + ev.index
            if ev.finished:
                out = self.engine.pop_output(ev.request_id)
                rec.update(tokens=list(out.token_ids), reason=out.finish_reason, ttft_s=out.ttft_s,
                           queue_wait_s=out.queue_wait_s, tpot_s=out.tpot_s,
                           preemptions=out.preemptions, done_tick=len(self.ticks))
                self.finished.append(self.flying.pop(ev.request_id))
                obs["finished"] += 1
        obs.update({k: float(g.value) for k, g in self._gauges.items()})
        if self.census is not None:
            obs["decode_bucket"] = self._note_decode_program()
        self.ticks.append(obs)
        if self.closed:
            self.fill()
        return obs

    def _note_decode_program(self):
        """The bucket of the decode step this tick ran (None where it ran
        none), from the census's call counts; and, where the site has just
        compiled a program, that program's scope map while it is the newest."""
        calls = {b: n for (site, b), n in self.census.call_counts().items() if site == DECODE_SITE}
        ran = [b for b, n in calls.items() if n != self._calls.get(b, 0)]
        self._calls = calls
        newest = self.census.latest(DECODE_SITE)
        if newest is not None and newest.bucket not in self.scope_maps:
            self.scope_maps[newest.bucket] = self.census.scope_map(DECODE_SITE)
        return ran[0] if len(ran) == 1 else None

    def drain(self) -> None:
        while self.flying:
            self.tick()


def _engine_programs(loop, mix, engine_cfg, vocab, seed, log) -> None:
    """A ladder of lone requests that runs each of the engine's bucketed
    programs once, as a deployment's start-up does: a prefill and its scatter
    for every prompt bucket, a decode step for every table-width bucket, and
    the chunk step behind a cached head for every (chunk bucket, table bucket)
    that a prompt of the mix can reach behind a head the cache holds whole (a
    head held in part, which an eviction can leave, compiles in the window and
    is counted). The buckets are powers of two of the prompt's length, of its
    uncached suffix and of its blocks; where exactly a bucket's edge lies is
    the engine's to decide, so one prompt is sent for every distinct (bucket
    of p, of p's blocks, of p + 1's blocks, of the suffix). Token ids are their own draw (stream 5):
    nothing of the stream is prefilled here."""
    bs = engine_cfg["block_size"]
    floor = max(16, bs)
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    head_len = mix["shared_heads"]["tokens"]
    rng = traffic.rng_for(seed, 5)
    head = rng.integers(1, vocab, head_len, dtype=np.int32)

    def blocks(n):
        return _pow2(-(-n // bs), 1)

    cold, behind = {}, {}
    for p in range(lo, hi + 1):
        cold.setdefault((_pow2(p, floor), blocks(p), blocks(p + 1)), p)
        # behind a head the cache holds whole: all of the head's blocks that lie
        # before the prompt's last token, or the whole prompt (copy on write)
        held = {min(head_len, (p - 1) // bs * bs)} | ({p} if p <= head_len and p % bs == 0 else set())
        for c in sorted(held - {0}):
            behind.setdefault((_pow2(max(p - c, 1), floor), blocks(p), blocks(p + 1), c == p), (p, c))
    t0 = time.perf_counter()
    for p in sorted(cold.values()):  # prefill, scatter, then one decode step
        loop.send({"prompt_ids": rng.integers(1, vocab, p, dtype=np.int32), "max_new_tokens": 2})
        loop.drain()
    loop.send({"prompt_ids": head, "max_new_tokens": 2})  # the head itself, into the cache
    loop.drain()
    for p, c in sorted(behind.values()):
        ids = rng.integers(1, vocab, p, dtype=np.int32)
        ids[:c] = head[:c]  # the cache holds c positions of it, and no more
        loop.send({"prompt_ids": ids, "max_new_tokens": 2})
        loop.drain()
    log(f"ladder: {len(cold)} cold prompts {sorted(cold.values())}, {len(behind)} behind a cached "
        f"prefix {sorted(behind.values())}: {time.perf_counter() - t0:.1f} s")


def _prompt_length_programs(sizes, n_requests, mix, engine_cfg) -> int:
    """The engine pads a prompt to its bucket with an eager
    ``zeros(bucket).at[:n].set(ids)``, which JAX compiles once for every new
    ``n``: 3.2 ms of compile a token of ``n`` on a v5e (2 s at 634 tokens),
    0.05 s from the cache (my chip runs, PR 45). A replica that has been up a
    while has met every length; here the same two expressions are run for the
    stream's first ``n_requests`` requests, so that the window compiles none
    of them: a prompt without a head whole, a prompt behind a head the suffix
    the cache does not hold (the heads are in the cache from the loop's first
    ticks on). Returns how many were run."""
    bs = engine_cfg["block_size"]
    floor = max(16, bs)
    head_len = mix["shared_heads"]["tokens"]
    whole, behind = set(), set()
    for p, h in zip(sizes["prompt"][:n_requests].tolist(), sizes["head"][:n_requests].tolist()):
        if h < 0:
            whole.add(p)
            continue
        behind.add(p - min(head_len, (p - 1) // bs * bs))
        if p <= head_len and p % bs == 0:
            behind.update((1, bs))  # the cache holds it whole: the last token, or block, again
    last = None
    for n in sorted(whole):
        last = jnp.zeros((1, _pow2(n, floor)), jnp.int32).at[0, :n].set(
            jnp.asarray([0] * n, jnp.int32))
    for n in sorted(behind):
        last = jnp.zeros((_pow2(n, floor),), jnp.int32).at[:n].set(jnp.asarray([0] * n, jnp.int32))
    jax.block_until_ready(last)
    return len(whole) + len(behind)


def run(ctx):
    cfg, mix = ctx.model, ctx.mix
    engine_cfg = mix["engine"]
    ref = importlib.import_module(f"benchmark.reference.{ctx.config['reference']}_serve")
    param_dtype = jnp.dtype(engine_cfg["param_dtype"])

    # ------------------------------------------------------------ set-up
    prog_spans.enable_spans()  # serve.prefill, serve.decode, jit.compile into the span ring
    overrides = {k: cfg[k] for k in MODEL_KEYS if k in cfg}
    overrides.update(ctx.config.get("program_overrides", {}))
    model_cfg = build_config(**overrides, dtype=engine_cfg["dtype"],
                             param_dtype=engine_cfg["param_dtype"])
    stream = traffic_serve.request_stream(mix, cfg["vocab_size"], ctx.seed)
    sizes = traffic_serve.request_sizes(mix)
    ctx.log(f"stream drawn: {len(stream)} requests, {int(sizes['prompt'].sum())} prompt tokens")

    t_build = time.perf_counter()
    key = ref.ref.seed_key(ctx.seed)

    def make(key):
        return ref.ref.nest(ref.ref.make_params(cfg, key, param_dtype))

    family = build_foundation_model(config=model_cfg).family
    want = jax.eval_shape(lambda k: family.init_params(k, model_cfg), key)
    got = jax.eval_shape(make, key)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise RuntimeError("the seeded weights do not have the model's parameter tree")
    params = jax.jit(make)(key)
    jax.block_until_ready(params)
    ctx.log("seeded weights on the device")
    engine = InferenceEngine(params, model_cfg, EngineConfig(
        **{k: engine_cfg[k] for k in ENGINE_KEYS}))
    del params
    engine_build_s = time.perf_counter() - t_build
    pool_gb = engine.kv_capacity()["pool_bytes"] * 1e-9
    ctx.log(f"engine built: {engine_cfg['num_slots']} slots, {engine_cfg['num_blocks']} blocks of "
            f"{engine_cfg['block_size']} ({pool_gb:.2f} GB of keys and values), "
            f"{engine_build_s:.1f} s")

    loop = ClosedLoop(engine, stream, mix)
    if ctx.trace:
        from veomni_tpu.observability.cost import get_cost_census

        loop.census = get_cost_census()
    _engine_programs(loop, mix, engine_cfg, cfg["vocab_size"], ctx.seed, ctx.log)
    t0 = time.perf_counter()
    n = _prompt_length_programs(sizes, mix["prompt_lengths_ahead"], mix, engine_cfg)
    ctx.log(f"prompt-length programs: {n} in {time.perf_counter() - t0:.1f} s")
    loop.ticks.clear()
    loop.finished.clear()
    loop.closed = True
    loop.fill()
    t0 = time.perf_counter()
    for i in range(mix["warmup_ticks"]):
        loop.tick()
        if i % 40 == 39:
            ctx.log(f"warm-up: {i + 1} ticks in {time.perf_counter() - t0:.1f} s")
    ctx.log(f"set-up done: {len(loop.ticks)} ticks of the closed loop in "
            f"{time.perf_counter() - t0:.1f} s, {len(loop.finished)} requests finished, "
            f"{loop.sent} sent, {len(loop.flying)} in flight")

    # ------------------------------------------------------------ the window
    traced = (0, 0)
    ctx.mark_window_start(time.perf_counter())
    if ctx.trace:
        ctx.start_trace()
        try:
            traced = (len(loop.ticks), len(loop.ticks) + mix["traced_ticks"])
            for _ in range(mix["traced_ticks"]):
                loop.tick()
        finally:
            ctx.stop_trace()
    built0 = ctx.programs_built()
    w0, t_open = len(loop.ticks), time.perf_counter()
    while True:
        obs = loop.tick()
        if obs["t1"] - t_open > ctx.seconds:
            break  # this tick ended outside the window: nothing of it counts
    w1 = len(loop.ticks) - 1
    if w1 <= w0:
        raise RuntimeError(f"no tick ended inside {ctx.seconds} s")
    if loop.sent >= len(stream):
        raise RuntimeError(f"the stream's {len(stream)} requests ran dry inside the window")
    built = ctx.programs_built() - built0
    ticks = loop.ticks[w0:w1]
    t_close = ticks[-1]["t1"]
    window_s = t_close - t_open
    tokens = sum(t["tokens"] for t in ticks)
    done = [r for r in loop.finished if w0 <= r["done_tick"] < w1]
    sent = sum(r["index"] is not None and w0 <= r["sent_tick"] <= w1
               for r in loop.finished + list(loop.flying.values()))
    bad = [r for r in done if r["reason"] != "length"]
    memory_peak = ctx.memory_peak_bytes()
    values = {"serve_output_tokens_per_s": tokens / window_s}
    ctx.log(f"window: {len(ticks)} ticks, {tokens} output tokens in {window_s:.3f} s "
            f"({values['serve_output_tokens_per_s']:.1f} tokens/s); requests sent {sent}, "
            f"succeeded {len(done) - len(bad)}, failed {len(bad)}, still running or waiting "
            f"{len(loop.flying)}; {built} programs built in the window")

    span_durs = _span_durations(prog_spans.live_span_events(), t_open, t_close)
    slots = engine_cfg["num_slots"]
    sums = {k: sum(t[k] for t in ticks) for k in
            ("forward_tokens", "context_sum", "prompt_tokens", "cached_tokens", "decoded",
             "prefilled", "num_running")}
    obs = {
        "window_s": window_s,
        "counters": {
            "tokens.output": tokens, "ticks": len(ticks),
            "slots.busy": sums["num_running"], "slots.all": slots * len(ticks),
            "prompt.tokens": sums["prompt_tokens"], "prompt.cached": sums["cached_tokens"],
            "forward.tokens": sums["forward_tokens"], "forward.context_sum": sums["context_sum"],
            "forward.logit_rows": tokens,
            "preemptions": ticks[-1]["preemptions"] - loop.ticks[w0 - 1]["preemptions"],
            "programs.built": built,
        },
        "spans": span_durs,
        "timers": {
            "engine_build": [engine_build_s],
            "kv_utilization": [t["kv_utilization"] for t in ticks],
            "queue_wait": [r["queue_wait_s"] for r in done if r["queue_wait_s"] is not None],
            "ttft": [r["ttft_s"] for r in done if r["ttft_s"] is not None],
            "tpot": [r["tpot_s"] for r in done if r["tpot_s"] is not None],
        },
        # what goes with the trace's ticks is filled in by on_trace
        "shapes": {"traced_steps": None, "decode_context_positions": None},
        "scope_maps": {DECODE_SITE: loop.scope_maps},
    }
    traced_ticks = loop.ticks[traced[0]:traced[1]]
    obs["on_trace"] = lambda trace: cut_on_ticks(trace, obs["shapes"], traced_ticks, ctx.log)
    if obs["timers"]["kv_utilization"]:
        ctx.log(f"kv_utilization median {statistics.median(obs['timers']['kv_utilization']):.3f}, "
                f"slots busy {sums['num_running'] / (slots * len(ticks)):.3f}, decode tokens a "
                f"tick {sums['decoded'] / len(ticks):.2f}, prefills a tick "
                f"{sums['prefilled'] / len(ticks):.2f}, prompt tokens cached "
                f"{sums['cached_tokens']} of {sums['prompt_tokens']}")

    # exact checks of the loop's own books
    checks = [
        compare.check("requests_failed", float(len(bad)), 0.0, sent=sent,
                      succeeded=len(done) - len(bad), in_flight=len(loop.flying)),
        compare.check("outputs_of_another_length", float(sum(
            len(r["tokens"]) != r["max_new_tokens"] for r in done)), 0.0),
        compare.check("tokens_lost_or_out_of_order", float(sum(
            abs(len(r["tokens"]) - r["events"]) + r["order_lost"] for r in done)), 0.0),
        compare.check("clients_over_the_mix", float(max(0, loop.max_flying - mix["clients"])), 0.0),
    ]

    # free the program before the reference takes the chip
    picked = _sample(done, mix["check_requests"], ctx.seed)
    del engine, loop.engine
    gc.collect()

    # ------------------------------------------------------------ reference
    t_ref = time.perf_counter()
    # one length for every request: the longest the engine takes, in whole blocks of
    # the reference's 512 query positions
    padded = engine_cfg["max_model_len"]
    if padded > 512:
        padded = -(-padded // 512) * 512
    out_len = mix["output_tokens"]["max"]
    reader = ref.make_reader(cfg, ctx.seed, served_dtype=param_dtype, padded_len=padded,
                             out_len=out_len)
    requests = [(r["prompt"], r["tokens"]) for r in picked]

    def gap_checks(read):
        gaps = read["gaps"]
        worst = int(np.argmax(gaps))
        r = picked[int(read["which"][worst])]
        return [
            compare.check("served_not_reference_best_share", float(np.mean(gaps > 0.0)),
                          ctx.limits["not_best_share"], positions=len(gaps)),
            compare.check("served_logit_gap_max", float(gaps[worst]), ctx.limits["logit_gap_max"],
                          request=r["index"], prompt=len(r["prompt"])),
            compare.check("served_logit_gap_mean", float(np.mean(gaps)),
                          ctx.limits["logit_gap_mean"]),
        ]

    got = gap_checks(ref.served_gaps(reader, requests, padded_len=padded, out_len=out_len))
    if ctx.control:
        # the control: the reference in the precision below the configuration's,
        # PUT IN THE PROGRAM'S PLACE. It decides ``correct`` (which has to
        # come out false); the program's own gaps are readings
        for c in got:
            ctx.log(f"reading (program) {compare.said(c)}")
        for k, quant in enumerate(ctx.control.split(",")):
            low = gap_checks(ref.served_gaps(reader, requests, padded_len=padded,
                                             out_len=out_len, quant=quant))
            for c in low:
                ctx.log(f"reading (control {quant}) {compare.said(c)}")
            if k == 0:
                got = low
    ctx.log(f"reference: {len(requests)} requests, {sum(len(t) for _, t in requests)} served "
            f"tokens, {time.perf_counter() - t_ref:.1f} s")
    checks = got + checks
    return {"checks": checks, "attempted": sent, "failed": len(bad), "values": values,
            "obs": obs, "memory_peak_bytes": memory_peak}


def _sample(done, n, seed):
    """``n`` of the requests the window finished, drawn from the seed, the
    longest (prompt and served tokens together) always among them."""
    if len(done) <= n:
        return list(done)
    longest = max(range(len(done)), key=lambda i: len(done[i]["prompt"]) + len(done[i]["tokens"]))
    rest = [i for i in range(len(done)) if i != longest]
    drawn = traffic.rng_for(seed, 7).choice(len(rest), n - 1, replace=False)
    return [done[longest]] + [done[rest[i]] for i in sorted(drawn)]


def cut_on_ticks(trace: dict, shapes: dict, ticks: list, log) -> None:
    """Cut the trace's window on the job's own ``bench.tick`` spans: from the
    start of the second tick the trace shows to the end of the last but one.
    The profiler went on with the device idle and the host blocks on every
    decode step's tokens, so each tick's device work lies inside its span. A
    trace that shows another number of ticks than were run leaves ``shapes``
    without a count, and every reader of ``ms a tick`` leaves its metric out."""
    spans = [ev for ev in tr.host_spans(trace) if ev[0] == TICK_SPAN]
    if len(spans) != len(ticks) or len(spans) < 4:
        if tr.device_planes(trace):
            log(f"the trace shows {len(spans)} {TICK_SPAN} spans where {len(ticks)} ticks ran: "
                "no `ms a tick` metric is reported")
        return
    trace[tr.STEP_WINDOW] = [spans[1][1], spans[-2][1] + spans[-2][2]]
    whole = ticks[1:-1]
    shapes.update(traced_steps=len(whole),
                  decode_context_positions=sum(t["decode_context"] for t in whole),
                  decode_tokens=sum(t["decoded"] for t in whole),
                  # the program each decode span of the window ran, in order
                  span_buckets={DECODE_SPAN: [t["decode_bucket"] for t in whole if t["decoded"]]})
    lo, hi = tr.window_ns(trace)
    log(f"the trace's window: {len(whole)} whole ticks, {(hi - lo) * 1e-9:.4f} s, "
        f"{shapes['decode_tokens']} decode tokens over {shapes['decode_context_positions']} "
        f"cached positions read, {sum(t['prefilled'] for t in whole)} prefills; decode programs "
        f"{sorted(set(map(str, shapes['span_buckets'][DECODE_SPAN])))}")


def _span_durations(events, t0: float, t1: float) -> dict:
    """The program's spans ``(name, start_ns, dur_ns, tid)`` that started in
    [t0, t1] (``perf_counter`` seconds): name -> durations in seconds."""
    lo, hi = int(t0 * 1e9), int(t1 * 1e9)
    out: dict = {}
    for name, start, dur, _tid in events:
        if lo <= start <= hi:
            out.setdefault(name, []).append(dur * 1e-9)
    return out
