"""Names the device's work carries out of the program: an interface.

Two kinds, both metadata only (neither changes a compiled instruction):

* **Kernel names**: the ``name=`` of every ``pl.pallas_call``. The optimized
  HLO names the custom call after it (``%flash_fwd.13 = ... custom-call``),
  and the profiler names a device event by that instruction's text, so a
  trace tells the kernels apart without counting wrappers.
* **Scopes**: ``jax.named_scope`` around the stages of the train step and of
  the engine's step. Each compiled instruction's ``metadata={op_name=...}``
  carries the scope path; ``observability/cost.py::CostCensus.scope_map``
  hands out ``{instruction name: op_name}`` per jit site, which is what joins
  a profiler trace's device events to these names (``benchmark/scopes.py``).

Call sites write the names as literals; ``tests/test_observability.py``
holds every literal in the code to these tuples and every name here to a call
site. ``docs/observability.md`` lists what each covers.
"""

TRAIN_SCOPES = (
    "embed",         # token embedding lookup (+ embed scale)
    "attn.qkv",      # input norm, q/k/v projections, qk-norm, rope (MLA: the split too)
    "attn.flash",    # the attention op, whichever impl the registry resolves
    "attn.out",      # output projection and the residual add
    "mlp",           # post-attention norm, dense gated MLP, residual add
    "moe.route",     # router logits, top-k, balancing loss
    "moe.dispatch",  # sort / bucket tokens by expert, EP all-to-all out
    "moe.experts",   # the grouped-GEMM expert MLP
    "moe.combine",   # EP all-to-all back, weighted scatter-add, shared experts
    "lm_head_loss",  # final norm, lm head and cross entropy
    "grad_clip",     # token normalisation, global norm, clip scale
    "optimizer",     # optimizer update and apply
)
SERVE_SCOPES = (
    "paged.gather",  # per-slot KV context gathered through the block table
    "paged.attend",  # masked dense softmax over the gathered context
    "sampler",       # temperature / top-k / top-p / greedy token choice
)
SCOPES = TRAIN_SCOPES + SERVE_SCOPES
# Scopes that cut ACROSS the taxonomy: a module that runs the same stages
# again (its layer's ``attn.*`` and ``moe.*`` scopes lie inside it, and an
# instruction keeps its innermost taxonomy scope). A reader sums the
# instructions whose path holds the name, as ``recompute`` is read.
MODULE_SCOPES = (
    "mtp",           # the multi-token-prediction modules: embedding of the next
                     # token, the joining projection, one decoder layer, head loss
    # a state-space (Mamba-2) mixer, input norm to residual add; no taxonomy
    # scope lies inside it, so a reader of the taxonomy alone files its time
    # under ``unattributed``, and reads it by these names instead
    "ssm",
    "ssm.proj",      # in_proj and out_proj
    "ssm.conv",      # the splits, the depthwise causal conv with its resets, silu
    "ssm.scan",      # softplus of dt, the scan op (ops/ssd_scan.py), the D skip
    "ssm.gate_norm", # the silu gate, then the RMS norm over all of d_inner
    # a Kimi Delta Attention mixer (models/kimi_linear.py), input norm to
    # residual add, read as the state-space mixer is
    "kda",
    "kda.proj",      # q, k, v, the two low-rank pairs (decay, output gate), beta, o_proj
    "kda.conv",      # the three depthwise causal convs with their resets, silu
    "kda.gate",      # l2norm of q and k, the log-decay (softplus), beta, the gated norm
    "kda.scan",      # the recurrence op (ops/kda.py)
)

# Kernels a trace reader files by their NAME (``benchmark/scopes.py::KERNELS``
# is the yardstick's copy of this tuple, scope and phase beside each name,
# and its own test holds the two equal).
KERNEL_NAMES = (
    "flash_fwd", "flash_bwd_dkv", "flash_bwd_dq",   # ops/pallas/flash_attention.py
    "gmm_fwd", "gmm_dlhs", "gmm_drhs",              # ops/pallas/grouped_gemm.py
)
# Kernels a reader files as it files a fusion, by the scope in their
# ``op_name`` (``attn.qkv`` here): named in every trace, in no reader's table.
SCOPED_KERNEL_NAMES = (
    "qk_norm_rope_fwd", "qk_norm_rope_bwd",         # ops/pallas/qk_norm_rope.py
    "mla_qkv_rope_fwd", "mla_qkv_rope_bwd",         # ops/pallas/mla_qkv_rope.py
)
ALL_KERNEL_NAMES = KERNEL_NAMES + SCOPED_KERNEL_NAMES
