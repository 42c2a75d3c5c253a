"""Driver of the ``train_packed`` traffic kind.

Builds ``TextTrainer(args)`` as ``tasks/train_text.py`` does, from the
cell's configuration file, puts the benchmark's own seeded weights into its
state, appends one ``Callback`` and lets ``trainer.train()`` run the normal
loop: the program's loader, packing, prefetcher, jitted step, supervisor and
callbacks (all but the checkpoint writer). The callback stops the loop when
the window is over.

The loop's first steps (``reference_steps`` of the cell's limits file) are
set-up AND the correctness probe: they go through the same call and feed as
the window's, and the plain reference (the module the configuration names
under ``reference``) follows them afterwards from the same seed and batches.
"""

from __future__ import annotations

import gc
import importlib
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import compare, traffic
from benchmark import trace as tr
# the program, imported when the harness imports this driver: before the
# first use of the TPU, whose runtime threads slow a later import threefold
from veomni_tpu.arguments import VeOmniArguments, parse_args
from veomni_tpu.observability import spans as prog_spans
from veomni_tpu.train import train_step as ts
from veomni_tpu.trainer import TextTrainer
from veomni_tpu.trainer.callbacks import Callback, CheckpointCallback

TRACED_STEPS = 3
# the train step's program as the device's trace names its executions
# (``XLA Modules``: ``jit_step_fn(<fingerprint>)``): the ``train_step`` jit
# site jits ``step_fn`` (train/train_step.py). Written here once; no reader
# takes it as an argument
STEP_PROGRAM = r"^jit_step_fn\("
# a traced run traces FIRST, at fixed steps of the run: the profiler goes on at
# the sync that ends the warm-up, with the device idle, TRACE_DISPATCHED steps
# go out as the loop sends them (ahead of the device), a second sync, and the
# profiler goes off. The trace's k-th execution of the step program is then
# step warm-up + k whatever the program's speed; the first (it started on an
# idle device right after the profiler) and the last (the sync waited for it)
# are left out, the TRACED_STEPS between them are the window. After it the run
# IS an untraced run: it dispatches ahead of the device for --seconds and stops
# by the same rule between the same two syncs, so its train_tokens_per_s (and
# with it mfu_pct.*) is an untraced rate, over a window that starts
# TRACE_DISPATCHED steps later in the stream; then SYNCED_STEPS single steps
# for step_ms.*
TRACE_DISPATCHED = TRACED_STEPS + 2
SYNCED_STEPS = 8


def _jsonable_args(ctx, data_path: str, out_dir: str) -> dict:
    cfg, mix = ctx.model, ctx.mix
    model_keys = ("model_type", "vocab_size", "hidden_size", "intermediate_size",
                  "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
                  "head_dim", "tie_word_embeddings", "rope_theta",
                  "max_position_embeddings", "rms_norm_eps", "num_experts",
                  "num_experts_per_tok", "moe_intermediate_size", "norm_topk_prob")
    overrides = {k: cfg[k] for k in model_keys if k in cfg}
    overrides.update(ctx.config.get("program_overrides", {}))
    train = dict(ctx.config["train"])
    train.update({
        "output_dir": out_dir,
        "micro_batch_size": mix["rows_per_chip"],
        "train_steps": 1_000_000,  # the callback ends the run
        # the loader's shuffle: the mix's, so that every --seed packs alike
        "seed": mix["size_seed"] % (2 ** 31),
        "log_steps": 1_000_000,  # no host fetch inside the window
        "save_hf_weights": False,
        "async_save": False,
        "observability_jsonl": False,
        "observability_fleet": 0,
    })
    if ctx.platform == "cpu":
        train["platform"] = "cpu"
    return {
        "model": {"config_overrides": overrides},
        "data": {"train_path": data_path, "data_type": "pretokenized",
                 "max_seq_len": mix["seq_len"], "dyn_bsz": bool(mix["dyn_bsz"]),
                 "dyn_bsz_buffer_size": mix.get("dyn_bsz_buffer_size", 200),
                 "samples_per_micro_batch": mix.get("samples_per_row", 8)},
        "train": train,
    }


def _adam_mu(opt_state):
    found = [s.mu for s in _walk(opt_state) if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state in the optimizer's, found {len(found)}")
    return found[0]


def _walk(node):
    yield node
    if isinstance(node, (tuple, list)) and not hasattr(node, "mu"):
        for child in node:
            yield from _walk(child)


def run(ctx):
    cfg, mix = ctx.model, ctx.mix
    # the configuration names its plain reference: a module under
    # benchmark/reference with seed_key, make_params, nest, leaf_norms and
    # train_reference (benchmark/README.md)
    ref = importlib.import_module(f"benchmark.reference.{ctx.config['reference']}")
    work = ctx.work_dir
    data_path = os.path.join(work, "data.jsonl")
    docs = traffic.packed_documents(mix, cfg["vocab_size"], ctx.seed)
    ctx.log("documents drawn")
    traffic.write_jsonl(docs, data_path)
    ctx.log(f"wrote {len(docs)} documents, {sum(map(len, docs))} tokens")
    args_path = os.path.join(work, "args.json")
    with open(args_path, "w") as f:
        json.dump(_jsonable_args(ctx, data_path, os.path.join(work, "run")), f)
    args = parse_args(VeOmniArguments, [args_path])
    trainer = TextTrainer(args)
    ctx.log("TextTrainer built")
    if len(trainer.parallel_state.mesh.devices.flat) != ctx.chips:
        raise RuntimeError("the trainer's mesh does not span the cell's chips")
    mesh_shape = {k: v for k, v in trainer.parallel_state.mesh.shape.items() if v > 1}
    want_mesh = {k: v for k, v in ctx.config.get("mesh", {}).items() if v > 1}
    if mesh_shape != want_mesh:
        raise RuntimeError(f"mesh {mesh_shape} is not the configuration's {want_mesh}")

    # the benchmark's weights, made on the device from the seed in one call,
    # in the trainer's own layout and sharding
    shardings = trainer.state_shardings
    abstract = trainer.abstract_state.params
    trainer.train_state = None  # free the trainer's own init first

    key = ref.seed_key(ctx.seed)

    def make(key):
        return ref.nest(ref.make_params(cfg, key, jnp.dtype(args.train.param_dtype)))

    _require_same_tree(jax.eval_shape(make, key), abstract, "the trainer's")
    params = jax.jit(make, out_shardings=shardings.params)(key)
    opt_state = jax.jit(trainer.optimizer.init, out_shardings=shardings.opt_state)(params)
    trainer.train_state = ts.TrainState(
        params=params, opt_state=opt_state,
        step=jax.device_put(jnp.int32(0), shardings.step))
    del params, opt_state
    ctx.log("seeded weights and optimizer state on the device")

    b1 = float(args.train.betas[0])
    warmup = int(ctx.limits["reference_steps"])
    norms_of = jax.jit(ref.leaf_norms)

    @jax.jit
    def change_of(p, key):
        return ref.leaf_norms(jax.tree.map(lambda a, b: a.astype(jnp.float32) - b, p, make(key)))

    class Bench(Callback):
        def __init__(self):
            self.first = []        # (ids, segments) of the warm-up steps
            self.losses = []       # device futures, one per step
            self.tokens = []       # predicted tokens per step
            self.positions = []    # all positions per step
            self.padding = []      # padded positions per step
            self.pairs = []        # attention (q, k) pairs the mask admits
            self.step_s = []       # synced step times (traced run's tail)
            self.t0 = self.t_end = self.t_stop = None
            self.window_steps = 0
            # a traced run's phases: "trace" (the profiler on, from the warm-up's
            # sync), "ahead" (the measured window: untraced, dispatching ahead
            # of the device; an untraced run's only phase) and "synced" (one
            # step at a time, for step_ms)
            self.phase = "ahead"
            self.w0 = warmup       # steps dispatched before the measured window
            self.moe = []          # [assignments, held, dropped, ..] a step, where the model routes
            self.t_last = None
            self.done = 0
            self.returns = []      # when the loop came back from each window step

        def on_step_begin(self, trainer, state):
            b = trainer.current_batch
            seg = b["segment_ids"]
            self.tokens.append(int((b["labels"] != -100).sum()))
            self.positions.append(int(seg.size))
            self.padding.append(int((seg == 0).sum()))
            self.pairs.append(_pairs(seg))
            if len(self.first) < warmup:
                self.first.append((b["input_ids"].reshape(-1, seg.shape[-1]).copy(),
                                   seg.reshape(-1, seg.shape[-1]).copy()))

        def completed(self) -> int:
            """Steps whose loss the device has produced (asked, not waited
            for): the host dispatches ahead of the device."""
            while self.done < len(self.losses) and self.losses[self.done].is_ready():
                self.done += 1
            return self.done

        def open_window(self, i, t):
            """The measured window opens at ``t``, a sync after step ``i``:
            the warm-up's last step, or the last step of a traced run's trace."""
            self.w0 = self.done = i
            self.t0 = self.t_last = t

        def end_window(self, trainer, n):
            """The measured window ends, for a traced run as for an untraced
            one, when the last of its ``n`` steps has ended."""
            jax.block_until_ready(trainer.train_state)
            self.t_end = time.perf_counter()
            self.window_steps = n

        def stop(self, state):
            self.t_stop = time.perf_counter()
            self.counts1 = dict(ts.TRACE_COUNTS)
            self.built = ctx.programs_built() - self.built0
            state.should_stop = True

        def on_step_end(self, trainer, state):
            i = state.global_step
            self.losses.append(state.metrics["loss"])
            if "moe_assignment_counts" in state.metrics:
                self.moe.append(state.metrics["moe_assignment_counts"])
            if i == 1:
                # Adam's first moment after one step is (1 - b1) x the gradient
                # the optimizer was given: the norms are scaled on the host
                self.first_grad = norms_of(_adam_mu(trainer.train_state.opt_state))
            if i < warmup:
                return
            if i == warmup:
                self.change = change_of(trainer.train_state.params, key)
                jax.block_until_ready((trainer.train_state, self.change, self.first_grad))
                self.counts0 = dict(ts.TRACE_COUNTS)
                self.built0 = ctx.programs_built()
                self.events0 = len(prog_spans.live_span_events())
                ctx.mark_window_start(time.perf_counter())
                ctx.log(f"set-up done: {warmup} warm-up steps")
                if ctx.trace:
                    ctx.start_trace()  # the device is idle: the next execution is step warmup + 1
                    self.phase = "trace"
                else:
                    self.open_window(i, ctx.window_t0)
                return
            if self.phase == "trace":
                if i == warmup + TRACE_DISPATCHED:
                    jax.block_until_ready(trainer.train_state)
                    ctx.stop_trace()
                    self.open_window(i, time.perf_counter())
                    self.phase = "ahead"
                return
            now = time.perf_counter()
            self.returns.append(now)
            n = i - self.w0                   # window steps dispatched
            c = self.completed() - self.w0    # window steps the device has finished
            if self.phase == "synced":
                # the traced run's tail: one step at a time, for step_ms
                jax.block_until_ready(trainer.train_state)
                t = time.perf_counter()
                self.step_s.append(t - self.t_last)
                self.t_last = t
                if len(self.step_s) >= SYNCED_STEPS:
                    self.stop(state)
                return
            # dispatch as users' runs do, ahead of the device, and stop where
            # the steps already dispatched will fill the window: the window
            # ends when the last of them has ended
            if c >= 1 and now + (n - c) * (now - self.t0) / c >= self.t0 + ctx.seconds:
                self.t_decided = now
                self.end_window(trainer, n)
                if ctx.trace:
                    self.t_last = self.t_end
                    self.phase = "synced"
                else:
                    self.stop(state)

    bench = Bench()
    trainer.callbacks = [cb for cb in trainer.callbacks
                         if not isinstance(cb, CheckpointCallback)] + [bench]
    try:
        trainer.train()
    finally:
        ctx.stop_trace()
    if bench.t_stop is None:
        raise RuntimeError("the trainer's loop ended before the window did")

    # ------------------------------------------------------------ program's
    losses = [float(x) for x in jax.device_get(bench.losses)]
    first_grad = {k: np.asarray(v) / (1 - b1)
                  for k, v in jax.device_get(bench.first_grad).items()}
    change = {k: np.asarray(v) for k, v in jax.device_get(bench.change).items()}
    w0, w1 = bench.w0, bench.w0 + bench.window_steps
    window_s = bench.t_end - bench.t0
    tokens = sum(bench.tokens[w0:w1])
    span_durs = _span_durations(prog_spans.live_span_events()[bench.events0:], bench.t0, bench.t_end)
    memory_peak = ctx.memory_peak_bytes()
    obs = {
        "window_s": window_s,
        # to the loop's last return in the window: what follows is the job's own sync
        "loop_s": bench.t_decided - bench.t0,
        "counters": {
            "tokens.predicted": tokens,
            "positions.all": sum(bench.positions[w0:w1]),
            "positions.padding": sum(bench.padding[w0:w1]),
            "steps": bench.window_steps,
        },
        "spans": span_durs,
        "timers": {"step": bench.step_s},
        # what goes with the trace's steps is filled in by on_trace, once the
        # trace is read and cut: until then no reader has a step to divide by
        "shapes": {"attention_pairs": None, "attention_tokens": None, "traced_steps": None,
                   "seq_len": mix["seq_len"]},
    }
    # one entry a step the run dispatched, the warm-up's included
    by_step = {"attention_pairs": bench.pairs, "attention_tokens": bench.positions}
    if bench.moe:
        # of all assignments, the rows the held experts really multiplied
        counts = np.asarray(jax.device_get(bench.moe), dtype=np.float64)
        by_step.update(moe_assignments=counts[:, 0], moe_rows_multiplied=counts[:, 1] - counts[:, 2])
    obs["on_trace"] = lambda trace: cut_on_steps(trace, obs["shapes"], by_step, warmup, ctx.log)
    values = {"train_tokens_per_s": tokens / window_s}
    # the loop's in-flight bound paces the host by the device, so the gaps
    # between its returns tell a run that was slow throughout from one stall
    gaps = np.diff([bench.t0] + bench.returns + [bench.t_stop])
    ctx.log(f"seconds between the loop's returns from the steps after set-up: "
            f"{[round(float(g), 3) for g in gaps]}")
    ctx.log(f"window: {bench.window_steps} steps, {tokens} tokens in {window_s:.3f} s"
            + (f" ({values['train_tokens_per_s']:.1f} tokens/s, what mfu_pct reads) after the "
               f"trace's {w0 - warmup} steps, then {len(bench.step_s)} synced steps"
               if ctx.trace else "")
            + f"; losses first {losses[:3]} last {losses[w1 - 3:w1]}")

    # free the program before the reference takes the chip
    first = bench.first
    opt = {"lr": float(args.train.lr), "betas": [float(b) for b in args.train.betas],
           "weight_decay": float(args.train.weight_decay),
           "max_grad_norm": float(args.train.max_grad_norm)}
    if args.train.lr_decay_style != "constant" or args.train.lr_warmup_ratio:
        raise RuntimeError("the reference follows a constant learning rate only")
    trainer.train_state = None
    del trainer, bench.first_grad, bench.change, bench.losses, bench.moe
    gc.collect()

    # ------------------------------------------------------------ reference
    def gaps(got_losses, got_grad, got_change, want):
        out = [compare.check(f"loss_{i + 1}_rel_gap",
                             abs(got_losses[i] - want["losses"][i]) / abs(want["losses"][i]),
                             ctx.limits["loss_rel"]) for i in range(warmup)]
        gap, leaf = compare.worst_leaf_gap(got_grad, want["first_grad_norms"])
        out.append(compare.check("first_grad_norm_worst_leaf", gap,
                                 ctx.limits["first_grad_norm_rel"], leaf=leaf))
        gap, leaf = compare.worst_leaf_gap(got_change, want["param_change_norms"])
        out.append(compare.check(f"param_change_norm_{warmup}_steps_worst_leaf", gap,
                                 ctx.limits["param_change_norm_rel"], leaf=leaf))
        return out

    t_ref = time.perf_counter()
    want = ref.train_reference(cfg, opt, ctx.seed, first, log=ctx.log)
    ctx.log(f"reference: {warmup} steps in {time.perf_counter() - t_ref:.1f} s")
    checks = gaps(losses, first_grad, change, want)
    if ctx.control:
        # the control: the reference in the precision below the
        # configuration's, PUT IN THE PROGRAM'S PLACE. It decides ``correct``
        # (which has to come out false); the program's own gaps are readings
        for c in checks:
            ctx.log(f"reading (program) {compare.said(c)}")
        for k, quant in enumerate(ctx.control.split(",")):
            low = ref.train_reference(cfg, opt, ctx.seed, first, quant=quant, log=ctx.log)
            got = gaps(low["losses"], low["first_grad_norms"], low["param_change_norms"], want)
            for c in got:
                ctx.log(f"reading (control {quant}) {compare.said(c)}")
            if k == 0:
                checks = got
    finite = all(np.isfinite(losses))
    checks.append(compare.check("nonfinite_losses", 0.0 if finite else 1.0, 0.0))
    # a loss that stays finite and does not rise: with random tokens it falls
    # by 0.01-0.07 over a window (12.13 towards ln V = 11.93), within a few
    # times the step-to-step noise, so the limit leaves 0.5% of the loss
    last = losses[max(w1 - 3, w0):w1]
    # the mean of the window's last three losses less the first steps'
    checks.append(compare.check(
        "loss_rise_last3_minus_first",
        float(np.mean(last) - np.mean(losses[:warmup])),
        0.005 * abs(float(np.mean(losses[:warmup])))))
    checks += _traced_programs_check(bench.counts0, bench.counts1, bench.built)
    return {"checks": checks, "attempted": bench.window_steps, "failed": 0,
            "values": values, "obs": obs, "memory_peak_bytes": memory_peak}


def cut_on_steps(trace: dict, shapes: dict, by_step: dict, steps_before: int, log) -> None:
    """Cut the trace's window on the step program's executions 2 to
    ``TRACED_STEPS`` + 1 of those the trace shows (``trace.py::cut_to_steps``)
    and put their count into ``shapes["traced_steps"]``, with the sums of
    ``by_step``'s entries (one a step the run dispatched) over those very steps
    beside it.

    Which steps they are: the profiler went on with the device idle after
    ``steps_before`` steps and off after a sync ``TRACE_DISPATCHED`` steps
    later, and the device runs the steps in order, so the trace's k-th
    execution is step ``steps_before + k`` and the window is steps
    ``steps_before + 2`` onwards, the same in every run of a cell. A trace
    that shows another number of executions than went out cannot be counted
    so: it, and one with fewer than two whole steps, leaves ``shapes`` without
    a count, the log says so, and every reader of ``ms a step`` or of a step's
    shapes then leaves its metric out."""
    runs = tr.program_executions(trace, STEP_PROGRAM)
    if len(runs) != TRACE_DISPATCHED:
        if tr.device_planes(trace):
            log(f"the trace shows {len(runs)} execution(s) of the step program where "
                f"{TRACE_DISPATCHED} went out: which steps they are is not known, and no "
                "`ms a step` metric is reported")
        return
    whole = tr.cut_to_steps(trace, STEP_PROGRAM, at_most=TRACED_STEPS, skip=1)
    if whole < 2:
        log(f"the trace holds {whole} whole execution(s) of the step program after its first "
            f"({len(runs)} in all): no `ms a step` metric is reported")
        return
    first = steps_before + 2  # the window's first step, counted from 1
    lo, hi = tr.window_ns(trace)
    mine = {k: [float(x) for x in v[first - 1:first - 1 + whole]] for k, v in by_step.items()}
    # a check by eye that these are the steps: the longer execution admitted more pairs
    log(f"the trace's window: {whole} whole device steps, {(hi - lo) * 1e-9:.4f} s: steps "
        f"{first}..{first + whole - 1} of the run, executions of "
        f"{[round((b - a) * 1e-6, 1) for a, b in runs[1:1 + whole]]} ms for attention_pairs "
        f"{mine.get('attention_pairs')}")
    shapes.update(traced_steps=whole, **{k: sum(v) for k, v in mine.items()})


def _pairs(seg: np.ndarray) -> int:
    """(query, key) pairs a causal mask within documents admits, padding
    left out: a document of n tokens has n (n + 1) / 2."""
    seg = seg.reshape(-1, seg.shape[-1])
    total = 0
    for row in seg:
        cuts = np.flatnonzero(np.diff(row)) + 1
        for part in np.split(row, cuts):
            if part[0] > 0:
                total += len(part) * (len(part) + 1) // 2
    return total


def _require_same_tree(got, want, whose: str) -> None:
    """The seeded weights have to be the program's parameter tree, leaf for
    leaf: structure, shape and dtype."""
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise RuntimeError(f"the seeded weights do not have {whose} parameter tree")


def _span_durations(events, t0: float, t1: float) -> dict:
    """The program's spans ``(name, start_ns, dur_ns, tid)`` that started in
    [t0, t1] (``perf_counter`` seconds): name -> durations in seconds."""
    lo, hi = int(t0 * 1e9), int(t1 * 1e9)
    out: dict = {}
    for name, start, dur, _tid in events:
        if lo <= start <= hi:
            out.setdefault(name, []).append(dur * 1e-9)
    return out


def _traced_programs_check(counts0: dict, counts1: dict, built: int) -> list:
    """Nothing may be traced, compiled or loaded inside the window."""
    new = {k: counts1[k] - counts0[k] for k in counts1 if counts1[k] != counts0[k]}
    # traced: the program's own jit sites; built: every program JAX compiled
    # or loaded from its cache, the eager ones too
    return [compare.check("programs_traced_in_window", float(sum(new.values())), 0.0,
                          **({"which": str(new)} if new else {})),
            compare.check("programs_built_in_window", float(built), 0.0)]
