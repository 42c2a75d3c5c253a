"""The jitted training step: grad accumulation, clipping, optimizer update.

Reference hot loop: ``veomni/trainer/base.py:715-826`` (forward_backward per
micro-batch with deferred FSDP reshard, then clip + optimizer step). TPU
design: the *entire* optimizer step — a ``lax.scan`` over micro-batches
accumulating token-sum gradients, global-norm clip, optax update — is one jit
program. GSPMD schedules the FSDP all-gathers/reduce-scatters; the deferral
and prefetch tricks of the reference are compiler-owned here
(SURVEY.md §7.1 "grad accumulation" row).

Loss/grad normalization follows the reference's ``mean_global_loss``: token
sums are accumulated across micro-batches (and implicitly across dp/sp via
GSPMD's replicated reduction of the scalar loss), and divided by the global
valid-token count once — so packing imbalance never skews gradients.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from veomni_tpu.observability.numerics import tree_health
from veomni_tpu.parallel.parallel_plan import ParallelPlan
from veomni_tpu.parallel.parallel_state import ParallelState
from veomni_tpu.utils.env import env_bool
from veomni_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# Trace-time counters, same discipline as ``models/decode.py::TRACE_COUNTS``:
# the jitted step body increments at TRACE time only, so a steady-state run
# holds the count flat and any later bump is a recompile. The observability
# recompile detector (``observability/goodput.py``) watches these and logs
# the offending shapes from LAST_TRACE_SHAPES. ``numerics_step`` is the
# instrumented sibling program (numerics observatory): the trace-count gate
# bounds the tier to exactly ONE extra compiled program per batch shape.
TRACE_COUNTS: Dict[str, int] = {
    "train_step": 0, "eval_step": 0, "numerics_step": 0,
}
LAST_TRACE_SHAPES: Dict[str, Any] = {}


def _batch_bucket(batch: Dict[str, Any]) -> str:
    """Cost-census bucket label for a step batch: the accum/batch/seq shape
    of the first array leaf (every retrace-relevant shape in a packed text
    batch). Falls back to a leaf count for exotic batch schemas."""
    for v in batch.values():
        shape = getattr(v, "shape", None)
        if shape:
            return "x".join(str(int(d)) for d in shape)
    return f"leaves{len(batch)}"


@flax.struct.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array  # int32 scalar


def build_train_state(params, optimizer: optax.GradientTransformation) -> TrainState:
    return TrainState(params=params, opt_state=optimizer.init(params), step=jnp.int32(0))


def resolve_state_shardings(
    abstract_state: TrainState, plan: ParallelPlan, pstate: ParallelState
) -> TrainState:
    """Shard the whole TrainState by the plan: optimizer moments inherit the
    param sharding via their path suffix (reference: FSDP2 shards optimizer
    state implicitly because DTensor params flow into optimizer.init)."""

    def _one(path, leaf):
        from veomni_tpu.parallel.parallel_plan import param_path_str

        spec = plan.spec_for(param_path_str(path), leaf.shape, pstate)
        return NamedSharding(pstate.mesh, spec)

    return jax.tree_util.tree_map_with_path(_one, abstract_state)


def build_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    pstate: ParallelState,
    *,
    state_shardings: Optional[TrainState] = None,
    batch_shardings: Optional[Any] = None,
    max_grad_norm: float = 1.0,
    grad_mask: Optional[Any] = None,
    skip_nonfinite: bool = False,
    numerics_spec: Optional[Any] = None,
) -> Callable:
    """Returns jitted ``train_step(state, batch) -> (state, metrics)``.

    ``loss_fn(params, micro_batch) -> (token_sum_loss, metrics_dict)`` where
    metrics include 'ntokens'. ``batch`` leaves have a leading micro-batch
    (grad-accum) dim A: [A, B, S].

    ``grad_mask``: optional 0/1 pytree matching params — frozen modules'
    grads are zeroed BEFORE the global-norm clip, so they neither shrink the
    trainable params' clip budget nor pollute the grad_norm metric
    (reference freeze semantics exclude params from optimization entirely).

    Metrics always include ``step_ok`` — a device-side finite-loss/finite-
    grad flag the resilience supervisor fetches with the loop's existing
    in-flight drain (no extra host syncs). With ``skip_nonfinite`` the
    update itself is gated on that flag ON DEVICE: a blown-up step leaves
    params/opt_state untouched (the ``where`` select is exact, so finite
    steps are bitwise-identical to the ungated program).

    ``numerics_spec`` (an ``observability.numerics.NumericsSpec``) builds
    the INSTRUMENTED SIBLING step of the numerics observatory instead: same
    update math, but the step additionally returns a third output — the
    per-param-group training-health tree from ``numerics.tree_health``
    (grad/param RMS, absmax, non-finite counts, update/weight ratio,
    overflow-margin bits; scan-stacked subtrees as per-layer vectors). The
    sibling registers its compiles under its own ``numerics_step`` cost-
    census site (so occasional numerics steps never pollute the train-step
    MFU window) and its own ``TRACE_COUNTS`` key (so the trace-count gates
    can prove the tier costs exactly one extra compiled program). It never
    donates its inputs: the supervisor's anomaly diagnosis re-runs the same
    already-fetched batch and DISCARDS the returned state.
    """
    site = "train_step" if numerics_spec is None else "numerics_step"

    def grads_one_micro(params, micro):
        (loss_sum, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True, allow_int=True
        )(params, micro)
        # non-differentiable leaves (frozen int lookup tables, e.g. the
        # deepseek_v4 hash-router tid2eid) produce float0 grads; zero-fill
        # so the f32 accumulation tree stays uniform (build_optimizer routes
        # these leaves to set_to_zero)
        grads = jax.tree.map(
            lambda g, p: jnp.zeros(p.shape, jnp.float32)
            if g.dtype == jax.dtypes.float0 else g,
            grads, params,
        )
        extras = {
            k: v.astype(jnp.float32)
            for k, v in metrics.items()
            if k not in ("ntokens",) and jnp.ndim(v) <= 1
        }
        return grads, loss_sum, metrics["ntokens"], extras

    def step_fn(state: TrainState, batch: Dict[str, jax.Array]):
        TRACE_COUNTS[site] += 1  # trace-time only
        LAST_TRACE_SHAPES[site] = {
            k: tuple(v.shape) for k, v in batch.items()
        }
        params = state.params

        def accum(carry, micro):
            g_acc, loss_acc, tok_acc = carry
            g, l, n, ex = grads_one_micro(params, micro)
            g_acc = jax.tree.map(jnp.add, g_acc, g)
            return (g_acc, loss_acc + l, tok_acc + n), ex

        zero_grads = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss_sum, ntokens), extras_stacked = jax.lax.scan(
            accum, (zero_grads, jnp.float32(0.0), jnp.int32(0)), batch
        )
        # scalar extras average over micro-steps; vector extras (per-channel
        # sums) accumulate
        extras = {
            k: (x.sum(0) if x.ndim > 1 else x.mean(0))
            for k, x in extras_stacked.items()
        }
        denom = jnp.maximum(ntokens, 1).astype(jnp.float32)
        # scope names: observability/scopes.py (metadata only)
        with jax.named_scope("grad_clip"):
            grads = jax.tree.map(lambda g: g / denom, grads)
            if grad_mask is not None:
                grads = jax.tree.map(lambda g, m: g * m, grads, grad_mask)
            grad_norm = optax.global_norm(grads)
            # numerics observatory reads the token-normalized, mask-applied,
            # PRE-clip gradients: the clip would hide exactly the blow-up
            # magnitude the health summary exists to see
            health_grads = grads
            if max_grad_norm:
                scale = jnp.minimum(1.0, max_grad_norm / (grad_norm + 1e-6))
                grads = jax.tree.map(lambda g: g * scale, grads)
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(grads, state.opt_state, params)
            new_params = optax.apply_updates(params, updates)
        health = None
        if numerics_spec is not None:
            health = tree_health(
                params, health_grads, updates,
                max_groups=numerics_spec.max_groups, eps=numerics_spec.eps,
            )
        # grad_norm is NaN/Inf whenever ANY grad leaf is (sqrt-of-sum-of-
        # squares propagates), so loss+grad_norm finiteness covers the tree
        step_ok = jnp.isfinite(loss_sum) & jnp.isfinite(grad_norm)
        if skip_nonfinite:
            # the gate fuses with the update it guards: same scope, or the
            # fused AdamW would carry the select's name and no scope at all
            with jax.named_scope("optimizer"):
                new_params = jax.tree.map(
                    lambda n, o: jnp.where(step_ok, n, o), new_params, params
                )
                new_opt = jax.tree.map(
                    lambda n, o: jnp.where(step_ok, n, o), new_opt, state.opt_state
                )
        new_state = TrainState(params=new_params, opt_state=new_opt, step=state.step + 1)
        metrics = {
            "loss": loss_sum / denom,
            "grad_norm": grad_norm,
            "ntokens": ntokens,
            "step_ok": step_ok,
            # auxiliary scalar metrics from the loss fn (e.g. dpo_acc),
            # averaged over micro-steps
            **extras,
        }
        if numerics_spec is not None:
            return new_state, metrics, health
        return new_state, metrics

    # the numerics sibling never donates: the supervisor's anomaly diagnosis
    # calls it and keeps the CALLER's state (the returned one is discarded)
    donate = (
        (0,) if env_bool("VEOMNI_DONATE_STATE") and numerics_spec is None
        else ()
    )
    if state_shardings is not None:
        # metrics must be explicitly replicated: fully-replicated globals are
        # host-fetchable on every process (multihost float(metrics[...]))
        replicated = NamedSharding(pstate.mesh, P())
        out_shardings = (
            (state_shardings, replicated) if numerics_spec is None
            else (state_shardings, replicated, replicated)
        )
        jitted = jax.jit(
            step_fn,
            in_shardings=(state_shardings, batch_shardings),
            out_shardings=out_shardings,
            donate_argnums=donate,
        )
    else:
        jitted = jax.jit(step_fn, donate_argnums=donate)
    # cost census (observability/cost.py): the jit site's compiles flow
    # through an AOT lower/compile pair that records XLA cost_analysis /
    # memory_analysis / compile wall-time per batch-shape bucket — the
    # attribution substrate behind the train.mfu_pct window gauge. The comm
    # observatory (observability/comm.py) rides the same compile: the
    # partitioned program's HLO is parsed once for the per-kind collective
    # byte census + overlappable/serialized pair counts behind the
    # comm.train_step.* gauges and the comm_est_frac window metric — no
    # extra compiles, so the trace-count gates stay green. Identity
    # under VEOMNI_COST_CENSUS=0; any census failure falls back to the
    # plain jit call permanently.
    from veomni_tpu.observability.cost import instrument_jit

    return instrument_jit(
        site, jitted, bucket_fn=lambda args: _batch_bucket(args[1])
    )


def build_eval_step(loss_fn: Callable, state_shardings=None, batch_shardings=None):
    def eval_fn(params, batch):
        TRACE_COUNTS["eval_step"] += 1  # trace-time only
        LAST_TRACE_SHAPES["eval_step"] = {
            k: tuple(v.shape) for k, v in batch.items()
        }
        loss_sum, metrics = loss_fn(params, batch)
        return {"loss": loss_sum / jnp.maximum(metrics["ntokens"], 1), **metrics}

    # NOT census-instrumented: the trainer's evaluate() builds (and
    # instruments) its own eval jit — a second 'eval_step' site here would
    # collide with it in the census on the same batch-shape buckets
    if state_shardings is not None:
        return jax.jit(
            eval_fn, in_shardings=(state_shardings.params, batch_shardings)
        )
    return jax.jit(eval_fn)
