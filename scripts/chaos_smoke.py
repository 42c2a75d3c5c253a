"""Tier-1 chaos smoke: the self-healing fleet survives a seeded storm.

Runs the storm drill's chaos leg (``resilience/storm.py::
run_open_loop_storm`` with ``chaos_seed``) on the tiny CPU model: a
fixed-seed deterministic fault schedule — replica kill +
hang/delay/exception across the serve fault points — fires over a 3-replica self-healing router while an open-loop
Poisson storm replays, then the same storm replays fault-free. The plan
also schedules one mid-storm weight publish, so the drill covers the
rolling hot-swap path under fire. Exits 0 only when every fleet
invariant holds on both runs (no lost/duplicated request ids, zero
leaked KV blocks per survivor, fleet restored to full live count, fleet
converged to the published weights version) and chaos goodput stays
>= 70% of the fault-free replay.

Budgeted for CI: one rate, a small storm, aggressive (sub-second) wedge
deadlines — the whole drill finishes in well under a minute on CPU.
Invoked by ``scripts/tier1.sh`` before the tests; the fixed seed
means a failure here replays bit-for-bit with the same command.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# fixed: a failing run replays bit-for-bit. Seed 11's schedule is known
# to land a hang whose victim survives long enough to be declared WEDGED
# (other seeds' kills can absorb the hanging replica first), so this
# smoke pins the full detect -> abandon -> respawn -> probation path.
SEED = 11


def main() -> int:
    import jax
    import jax.numpy as jnp

    from veomni_tpu.models import TransformerConfig, build_foundation_model
    from veomni_tpu.resilience.storm import run_open_loop_storm

    cfg = TransformerConfig(
        model_type="qwen3", vocab_size=128, hidden_size=64,
        intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, qk_norm=True,
        dtype=jnp.float32,
    )
    model = build_foundation_model(config=cfg)
    params = model.family.init_params(jax.random.PRNGKey(0), cfg)

    # absolute arrival rate, NOT a capacity multiple: the tiny CPU model
    # absorbs the whole storm in ~0.1s at measured capacity, which makes
    # the 2s chaos hang dominate any goodput ratio. 2.5 req/s spreads 16
    # requests over ~6s so the ratio measures healing, not storm length.
    r = run_open_loop_storm(
        params, cfg, num_slots=2, block_size=8, n_requests=16,
        prompt_lens=(8, 12), max_new_tokens=6, arrival_rates=(2.5,), seed=SEED,
        chaos_seed=SEED, chaos_stall_s=0.5, chaos_publishes=1,
    )
    c = r["chaos"]
    line = {
        "metric": "chaos_smoke",
        "seed": c["seed"],
        "replicas": c["replicas"],
        "ok": c["ok"],
        "goodput_ratio": round(c["goodput_ratio"], 4),
        "wedged": c["chaos"]["wedged"],
        "respawns": c["chaos"]["respawns"],
        "probation_passed": c["chaos"]["probation_passed"],
        "lost_ids": c["chaos"]["lost_ids"],
        "leaked_blocks": c["chaos"]["leaked_blocks"],
        "restored": c["chaos"]["restored"],
        "publishes": c["chaos"]["publishes"],
        "published_versions": c["chaos"]["published_versions"],
        "version_converged": c["chaos"]["version_converged"],
        "fault_free_quiet": (c["fault_free"]["wedged"] == 0
                             and c["fault_free"]["respawns"] == 0),
        "plan": c["plan"],
    }
    print("CHAOS_SMOKE " + json.dumps(line), flush=True)
    if not c["ok"]:
        print("CHAOS_SMOKE FAILED: invariants or goodput floor violated",
              file=sys.stderr)
        return 1
    if not line["fault_free_quiet"]:
        # the fault-free replay must never trip the wedge detector: a
        # wedge there means the stall deadline is mis-tuned, and every
        # chaos verdict on top of it is noise
        print("CHAOS_SMOKE FAILED: fault-free replay tripped self-healing",
              file=sys.stderr)
        return 1
    if c["chaos"]["wedged"] < 1:
        # seed 11 is chosen to wedge; zero wedges means the detector (or
        # the schedule's determinism) regressed, not that the fleet got
        # lucky
        print("CHAOS_SMOKE FAILED: expected >= 1 wedge from this seed",
              file=sys.stderr)
        return 1
    if c["chaos"]["publishes"] != 1 or not c["chaos"]["version_converged"]:
        # the plan schedules exactly one mid-storm publish; the fleet
        # must end the drill serving that version everywhere
        print("CHAOS_SMOKE FAILED: mid-storm publish did not converge",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
