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
# the program, imported when the harness imports this driver: before the
# first use of the TPU, whose runtime threads slow a later import threefold
from veomni_tpu.arguments import VeOmniArguments, parse_args
from veomni_tpu.observability import spans as prog_spans
from veomni_tpu.train import train_step as ts
from veomni_tpu.trainer import TextTrainer
from veomni_tpu.trainer.callbacks import Callback, CheckpointCallback

TRACED_STEPS = 3


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
            self.t0 = self.t_end = None
            self.window_steps = 0
            self.traced = (0, 0)
            self.tracing = False
            self.trace_done = not ctx.trace
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

        def end_window(self, trainer, state, n):
            jax.block_until_ready(trainer.train_state)
            self.t_end = time.perf_counter()
            self.window_steps = n
            self.counts1 = dict(ts.TRACE_COUNTS)
            self.built = ctx.programs_built() - self.built0
            state.should_stop = True

        def on_step_end(self, trainer, state):
            i = state.global_step
            self.losses.append(state.metrics["loss"])
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
                self.done = warmup
                self.t0 = self.t_last = time.perf_counter()
                ctx.mark_window_start(self.t0)
                ctx.log(f"set-up done: {warmup} warm-up steps")
                return
            now = time.perf_counter()
            self.returns.append(now)
            n = i - warmup                   # window steps dispatched
            c = self.completed() - warmup    # window steps the device has finished
            if self.trace_done and self.step_s is not None and ctx.trace:
                # the traced run's tail: one step at a time, for step_ms
                jax.block_until_ready(trainer.train_state)
                t = time.perf_counter()
                self.step_s.append(t - self.t_last)
                self.t_last = t
                if t - self.t0 >= ctx.seconds:
                    self.end_window(trainer, state, n)
                return
            if not self.trace_done:
                # trace once the device has finished a window step: from then
                # on the loop's in-flight bound paces the host, one
                # on_step_end per device step
                if not self.tracing and c >= 1:
                    ctx.start_trace()
                    self.tracing, self.traced = True, (i, i)
                elif self.tracing and i == self.traced[0] + TRACED_STEPS:
                    ctx.stop_trace()
                    self.tracing, self.trace_done = False, True
                    self.traced = (self.traced[0], i)
                    jax.block_until_ready(trainer.train_state)
                    self.t_last = time.perf_counter()
                return
            # dispatch as users' runs do, ahead of the device, and stop where
            # the steps already dispatched will fill the window: the window
            # ends when the last of them has ended
            if c >= 1 and now + (n - c) * (now - self.t0) / c >= self.t0 + ctx.seconds:
                self.end_window(trainer, state, n)

    bench = Bench()
    trainer.callbacks = [cb for cb in trainer.callbacks
                         if not isinstance(cb, CheckpointCallback)] + [bench]
    try:
        trainer.train()
    finally:
        ctx.stop_trace()
    if bench.t_end is None:
        raise RuntimeError("the trainer's loop ended before the window did")

    # ------------------------------------------------------------ program's
    losses = [float(x) for x in jax.device_get(bench.losses)]
    first_grad = {k: np.asarray(v) / (1 - b1)
                  for k, v in jax.device_get(bench.first_grad).items()}
    change = {k: np.asarray(v) for k, v in jax.device_get(bench.change).items()}
    w0, w1 = warmup, warmup + bench.window_steps
    window_s = bench.t_end - bench.t0
    tokens = sum(bench.tokens[w0:w1])
    span_durs = _span_durations(prog_spans.live_span_events()[bench.events0:], bench.t0, bench.t_end)
    memory_peak = ctx.memory_peak_bytes()
    n_traced = bench.traced[1] - bench.traced[0]
    obs = {
        "window_s": window_s,
        "counters": {
            "tokens.predicted": tokens,
            "positions.all": sum(bench.positions[w0:w1]),
            "positions.padding": sum(bench.padding[w0:w1]),
            "steps": bench.window_steps,
        },
        "spans": span_durs,
        "timers": {"step": bench.step_s},
        "shapes": {
            # the device runs behind the host, so which steps a trace holds is
            # not known exactly: the window's mean step, times the steps traced
            "attention_pairs": float(np.mean(bench.pairs[w0:w1])) * n_traced,
            "attention_tokens": float(np.mean(bench.positions[w0:w1])) * n_traced,
            "traced_steps": n_traced,
            "seq_len": mix["seq_len"],
        },
    }
    values = {"train_tokens_per_s": tokens / window_s}
    # the loop's in-flight bound paces the host by the device, so the gaps
    # between its returns tell a run that was slow throughout from one stall
    gaps = np.diff([bench.t0] + bench.returns + [bench.t_end])
    ctx.log(f"seconds between the loop's returns from the window's steps: "
            f"{[round(float(g), 3) for g in gaps]}")
    ctx.log(f"window: {bench.window_steps} steps, {tokens} tokens in {window_s:.3f} s; "
            f"losses first {losses[:3]} last {losses[-3:]}")

    # free the program before the reference takes the chip
    first = bench.first
    opt = {"lr": float(args.train.lr), "betas": [float(b) for b in args.train.betas],
           "weight_decay": float(args.train.weight_decay),
           "max_grad_norm": float(args.train.max_grad_norm)}
    if args.train.lr_decay_style != "constant" or args.train.lr_warmup_ratio:
        raise RuntimeError("the reference follows a constant learning rate only")
    trainer.train_state = None
    del trainer, bench.first_grad, bench.change, bench.losses
    gc.collect()

    # ------------------------------------------------------------ reference
    def gaps(got_losses, got_grad, got_change, want):
        out = [compare.check(f"loss[{i + 1}] relative gap",
                             abs(got_losses[i] - want["losses"][i]) / abs(want["losses"][i]),
                             ctx.limits["loss_rel"]) for i in range(warmup)]
        gap, leaf = compare.worst_leaf_gap(got_grad, want["first_grad_norms"])
        out.append(compare.check(f"first_grad_norm worst leaf ({leaf})", gap,
                                 ctx.limits["first_grad_norm_rel"]))
        gap, leaf = compare.worst_leaf_gap(got_change, want["param_change_norms"])
        out.append(compare.check(f"param_change_norm after {warmup} steps, worst leaf ({leaf})",
                                 gap, ctx.limits["param_change_norm_rel"]))
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
            ctx.log(f"reading (program) {c['name']}: {c['value']!r}")
        for k, quant in enumerate(ctx.control.split(",")):
            low = ref.train_reference(cfg, opt, ctx.seed, first, quant=quant, log=ctx.log)
            got = gaps(low["losses"], low["first_grad_norms"], low["param_change_norms"], want)
            for c in got:
                ctx.log(f"reading (control {quant}) {c['name']}: {c['value']!r}")
            if k == 0:
                checks = got
    finite = all(np.isfinite(losses))
    checks.append(compare.check("non-finite losses", 0.0 if finite else 1.0, 0.0))
    # a loss that stays finite and does not rise: with random tokens it falls
    # by 0.01-0.07 over a window (12.13 towards ln V = 11.93), within a few
    # times the step-to-step noise, so the limit leaves 0.5% of the loss
    last = losses[max(w1 - 3, w0):w1]
    checks.append(compare.check(
        "mean of the window's last three losses minus the first steps' (must not rise)",
        float(np.mean(last) - np.mean(losses[:w0])), 0.005 * abs(float(np.mean(losses[:w0])))))
    checks += _traced_programs_check(bench.counts0, bench.counts1, bench.built)
    return {"checks": checks, "attempted": bench.window_steps, "failed": 0,
            "values": values, "obs": obs, "memory_peak_bytes": memory_peak}


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
    return [compare.check(f"programs traced inside the window {new}",
                          float(sum(new.values())), 0.0),
            compare.check("programs compiled or loaded inside the window (eager ones too)",
                          float(built), 0.0)]
