"""KV-cache greedy decode vs full-prefix rescoring parity.

The cache path (models/decode.py) reimplements the layer walk; these tests
anchor it to the training forward (models/transformer.py forward_logits) on
the dialect extremes: qwen3 (GQA+qk_norm), gemma3-style (sandwich norms,
sliding windows, dual rope, embed_scale, softcap), and qwen3_moe."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veomni_tpu.models import TransformerConfig, build_foundation_model
from veomni_tpu.models.decode import greedy_generate, supports_cached_decode
from veomni_tpu.models.transformer import forward_logits


def _rescoring_generate(params, cfg, prompt, n, eos_id=-1):
    ids = list(prompt)
    total = len(ids) + n
    for _ in range(n):
        tokens = np.zeros((1, total), np.int32)
        tokens[0, : len(ids)] = ids
        pos = np.arange(total)[None]
        seg = (np.arange(total) < len(ids)).astype(np.int32)[None]
        logits = forward_logits(
            params, cfg, jnp.asarray(tokens), jnp.asarray(pos), jnp.asarray(seg)
        )
        nxt = int(jnp.argmax(logits[0, len(ids) - 1]))
        ids.append(nxt)
        if nxt == eos_id:
            break
    return ids


CONFIGS = {
    "qwen3": dict(
        model_type="qwen3", vocab_size=128, hidden_size=64,
        intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, qk_norm=True,
    ),
    "gemma3ish": dict(
        model_type="gemma3", vocab_size=128, hidden_size=64,
        intermediate_size=128, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, qk_norm=True,
        sandwich_norms=True, sliding_window=8,
        layer_types=["sliding_attention", "full_attention"] * 2,
        rope_local_base_freq=10000.0,
        query_pre_attn_scalar=16, final_logit_softcap=30.0,
    ),
    "qwen3_moe": dict(
        model_type="qwen3_moe", vocab_size=128, hidden_size=64,
        intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, qk_norm=True, num_experts=4,
        num_experts_per_tok=2, moe_intermediate_size=32,
    ),
    # gpt_oss-style: learned attention sinks + alternating sliding windows —
    # covers the sink softmax-denominator math duplicated between
    # _cache_attend and the training attention impls
    "gpt_oss_ish": dict(
        model_type="gpt_oss", vocab_size=128, hidden_size=64,
        intermediate_size=128, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, attention_sinks=True,
        attention_bias=True, o_bias=True, sliding_window=8,
        layer_types=["sliding_attention", "full_attention"] * 2,
        hidden_act="gpt_oss_glu",
    ),
}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_cached_decode_matches_rescoring(name):
    cfg = TransformerConfig(dtype=jnp.float32, **CONFIGS[name])
    assert supports_cached_decode(cfg)
    model = build_foundation_model(config=cfg)
    params = model.family.init_params(jax.random.PRNGKey(0), cfg)
    prompt = list(np.random.default_rng(0).integers(1, 128, 9))
    got = greedy_generate(params, cfg, prompt, max_new_tokens=6)
    want = _rescoring_generate(params, cfg, prompt, 6)
    assert got == want, (got, want)


def test_cached_decode_rejects_mla():
    cfg = TransformerConfig(
        model_type="deepseek_v3", vocab_size=64, hidden_size=64,
        num_hidden_layers=1, num_attention_heads=4,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8,
    )
    assert not supports_cached_decode(cfg)


def test_sampling_decode_valid_and_greedy_consistent():
    """temperature=0 sampling path == greedy; temperature>0 with top_k
    produces in-vocab tokens and is reproducible per seed."""
    cfg = TransformerConfig(dtype=jnp.float32, **CONFIGS["qwen3"])
    model = build_foundation_model(config=cfg)
    params = model.family.init_params(jax.random.PRNGKey(0), cfg)
    prompt = list(np.random.default_rng(1).integers(1, 128, 7))
    greedy = greedy_generate(params, cfg, prompt, max_new_tokens=5)
    greedy2 = greedy_generate(params, cfg, prompt, max_new_tokens=5,
                              temperature=0.0)
    assert greedy == greedy2
    s1 = greedy_generate(params, cfg, prompt, max_new_tokens=5,
                         temperature=0.8, top_k=10, seed=3)
    s2 = greedy_generate(params, cfg, prompt, max_new_tokens=5,
                         temperature=0.8, top_k=10, seed=3)
    assert s1 == s2  # per-seed reproducible
    assert all(0 <= t < 128 for t in s1[len(prompt):])
    # top_k > vocab clamps to the vocab (HF generate semantics) instead of
    # raising inside lax.top_k
    s3 = greedy_generate(params, cfg, prompt, max_new_tokens=5,
                         temperature=0.8, top_k=10_000, seed=3)
    assert all(0 <= t < 128 for t in s3[len(prompt):])


def test_nucleus_sampling():
    """top_p semantics: a vanishing nucleus collapses to greedy (the top-1
    token always survives the filter); top_p=1.0 keeps the full
    distribution (token-identical to not passing top_p); sampled tokens
    stay in-vocab and per-seed reproducible."""
    cfg = TransformerConfig(dtype=jnp.float32, **CONFIGS["qwen3"])
    model = build_foundation_model(config=cfg)
    params = model.family.init_params(jax.random.PRNGKey(0), cfg)
    prompt = list(np.random.default_rng(4).integers(1, 128, 8))
    greedy = greedy_generate(params, cfg, prompt, max_new_tokens=5)
    tiny_p = greedy_generate(params, cfg, prompt, max_new_tokens=5,
                             temperature=0.8, top_p=1e-6, seed=3)
    assert tiny_p == greedy
    full_p = greedy_generate(params, cfg, prompt, max_new_tokens=5,
                             temperature=0.8, top_k=10, top_p=1.0, seed=3)
    no_p = greedy_generate(params, cfg, prompt, max_new_tokens=5,
                           temperature=0.8, top_k=10, seed=3)
    assert full_p == no_p
    s1 = greedy_generate(params, cfg, prompt, max_new_tokens=5,
                         temperature=0.9, top_p=0.7, seed=5)
    s2 = greedy_generate(params, cfg, prompt, max_new_tokens=5,
                         temperature=0.9, top_p=0.7, seed=5)
    assert s1 == s2
    assert all(0 <= t < 128 for t in s1[len(prompt):])


def test_per_slot_sample_tokens_matches_scalar_semantics():
    """The serving engine's vectorized sampler: greedy rows == argmax
    regardless of batch-mates; per-row top_k<=0 / top_p>=1 keep everything;
    a tiny top_p collapses a sampled row to its argmax."""
    from veomni_tpu.models.decode import sample_tokens

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(4))
    out = sample_tokens(
        logits, keys,
        jnp.asarray([0.0, 0.0, 0.8, 0.9], jnp.float32),
        jnp.asarray([0, 5, 0, 3], jnp.int32),
        jnp.asarray([1.0, 1.0, 1e-6, 0.9], jnp.float32),
    )
    out = np.asarray(out)
    am = np.asarray(jnp.argmax(logits, axis=-1))
    assert out[0] == am[0] and out[1] == am[1]  # temperature<=0 -> greedy
    assert out[2] == am[2]  # vanishing nucleus -> argmax survives alone
    assert 0 <= out[3] < 32
    # per-row keys: the same row resamples identically under the same key
    out2 = np.asarray(sample_tokens(
        logits, keys,
        jnp.asarray([0.0, 0.0, 0.8, 0.9], jnp.float32),
        jnp.asarray([0, 5, 0, 3], jnp.int32),
        jnp.asarray([1.0, 1.0, 1e-6, 0.9], jnp.float32),
    ))
    assert (out == out2).all()


# --------------------------------------------------------------------------
# The sampler does what a call's rows ask for (PR 49): `argmax` alone, the
# categorical draw, or a threshold by value. Held against HF's rule written
# here in float64: temperature, top-k, top-p over the top-k's renormalised
# mass, the crossing token and all its ties kept, top-1 always.

SAMPLER_V = 4099
SAMPLER_ROWS = 6


def _sampler_logits():
    """Six rows, flat to peaked, with ties planted where a filter cuts: two
    equal maxima (rows 0, 3), the 20th to 22nd largest equal, the 50th and
    51st, the 1,024th and 1,025th."""
    rng = np.random.default_rng(49)
    rows = []
    for i, scale in enumerate((0.5, 1.0, 2.0, 3.0, 5.0, 8.0)):
        vals = np.sort(rng.normal(size=SAMPLER_V).astype(np.float32) * np.float32(scale))[::-1].copy()
        if i in (0, 3):
            vals[1] = vals[0]
        vals[20:22] = vals[19]
        vals[50] = vals[49]
        vals[1024] = vals[1023]
        rows.append(vals[rng.permutation(SAMPLER_V)])
    return np.stack(rows)


def _hf_kept(l, top_k, top_p):
    """(kept [V] bool, mass above each token [V]) of one row ``l`` float64."""
    v = l.size
    sl = np.sort(l)[::-1]
    k = top_k if 0 < top_k < v else v
    p = np.exp(sl[:k] - sl[0])
    p /= p.sum()
    before = np.cumsum(p) - p  # the mass strictly ahead of a sorted place
    n = max(int((before < top_p).sum()), 1) if top_p < 1.0 else k
    # the mass of the values above a token's own, which decides its value's
    # fate (1 for a token that top-k has cut already)
    above = np.append(before, 1.0)[np.searchsorted(-sl[:k], -l, side="left")]
    return l >= sl[n - 1], above


def _rows(temperature, top_k, top_p):
    full = lambda x, dt: np.full(SAMPLER_ROWS, x, dt)
    return full(temperature, np.float32), full(top_k, np.int32), full(top_p, np.float32)


SAMPLER_CASES = {
    "greedy": _rows(0.0, 0, 1.0),
    "greedy_whatever_the_filters_say": _rows(0.0, 20, 0.5),
    "unfiltered": _rows(0.7, 0, 1.0),
    "top_k_1": _rows(0.7, 1, 1.0), "top_k_20": _rows(0.6, 20, 1.0), "top_k_50": _rows(1.0, 50, 1.0),
    "top_k_1024": _rows(1.3, 1024, 1.0), "top_k_1025": _rows(1.3, 1025, 1.0),
    "top_k_v": _rows(0.7, SAMPLER_V, 1.0), "top_k_v_plus_1": _rows(0.7, SAMPLER_V + 1, 1.0),
    "top_p_1e-6": _rows(0.7, 0, 1e-6), "top_p_0p5": _rows(1.0, 0, 0.5),
    "top_p_0p95": _rows(0.6, 0, 0.95),
    # hot and flat: the nucleus holds thousands of tokens
    "top_p_wider_than_1024": _rows(4.0, 0, 0.9),
    "top_k_20_top_p_0p95": _rows(0.6, 20, 0.95), "top_k_50_top_p_0p5": _rows(1.0, 50, 0.5),
    "top_k_inside_the_nucleus": _rows(2.0, 5, 0.999),
    # every kind in one call, greedy rows among them
    "mixed": (np.asarray([0.0, 0.7, 0.6, 1.0, 0.0, 4.0], np.float32),
              np.asarray([20, 0, 20, 0, 0, 1025], np.int32),
              np.asarray([0.5, 1.0, 0.95, 0.5, 1.0, 0.9], np.float32)),
}


@pytest.mark.parametrize("case", sorted(SAMPLER_CASES))
def test_sample_tokens_keeps_and_draws_what_hfs_rule_in_float64_does(case):
    from veomni_tpu.models import decode as dm

    temperature, top_k, top_p = SAMPLER_CASES[case]
    logits = _sampler_logits()
    keys = np.asarray(jax.vmap(jax.random.PRNGKey)(jnp.arange(100, 100 + SAMPLER_ROWS)))
    sample = jax.jit(dm.sample_tokens)
    tokens = np.asarray(sample(logits, keys, temperature, top_k, top_p))
    l32 = logits / np.maximum(temperature, np.float32(1e-6))[:, None]
    thresh = np.asarray(jax.jit(dm._filter_threshold)(l32, top_k, top_p))
    want_path = dm.SAMPLER_PATHS[int(dm.sampler_path(temperature, top_k, top_p, SAMPLER_V))]
    assert want_path == {"greedy": "greedy", "greedy_whatever_the_filters_say": "greedy",
                         "unfiltered": "unfiltered", "top_k_v": "unfiltered",
                         "top_k_v_plus_1": "unfiltered"}.get(case, "filtered")
    for i in range(SAMPLER_ROWS):
        if temperature[i] <= 0.0:
            assert tokens[i] == np.argmax(logits[i])
            continue
        kept, above = _hf_kept(l32[i].astype(np.float64), int(top_k[i]), float(top_p[i]))
        got = l32[i] >= thresh[i]
        differ = np.flatnonzero(got != kept)
        # only a token whose fate hangs on the mass above it to within 1e-6
        assert np.all(np.abs(above[differ] - top_p[i]) <= 1e-6), (case, i, differ[:5])
        assert got[np.argmax(logits[i])]  # top-1 always survives
        assert got[tokens[i]]
        if not differ.size:
            alone = jax.random.categorical(keys[i], jnp.where(kept, l32[i], -jnp.inf))
            assert tokens[i] == int(alone), (case, i)
    # a row's answer is its own: the same alone, and beside other rows
    for i in range(SAMPLER_ROWS):
        alone = sample(logits[i:i + 1], keys[i:i + 1], temperature[i:i + 1],
                       top_k[i:i + 1], top_p[i:i + 1])
        assert int(alone[0]) == tokens[i], (case, i)
    turned = np.roll(np.arange(SAMPLER_ROWS), 2)
    again = np.asarray(sample(logits[turned], keys[turned], temperature[turned],
                              top_k[turned], top_p[turned]))
    assert (again == tokens[turned]).all()


def _primitives(jaxpr):
    """Every primitive's name in a jaxpr and in what it calls."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple)) else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names |= _primitives(sub)
    return names


def test_the_greedy_branch_is_argmax_and_no_branch_sorts():
    from veomni_tpu.models import decode as dm

    s, v = 4, 512
    jaxpr = jax.make_jaxpr(dm.sample_tokens)(
        jnp.zeros((s, v)), jnp.zeros((s, 2), jnp.uint32), jnp.zeros(s), jnp.zeros(s, jnp.int32),
        jnp.ones(s)).jaxpr
    switch = [eqn for eqn in jaxpr.eqns if eqn.primitive.name == "cond"]
    assert len(switch) == 1 and len(switch[0].params["branches"]) == len(dm.SAMPLER_PATHS)
    greedy, unfiltered, filtered = (_primitives(b.jaxpr) for b in switch[0].params["branches"])
    order = {"sort", "cumsum", "top_k"}
    draw = {"random_bits", "threefry2x32"}
    loops = {"while", "scan"}
    # argmax is made once, outside the switch, and the greedy branch hands it on
    assert "argmax" in {eqn.primitive.name for eqn in jaxpr.eqns}
    assert not greedy, greedy
    assert unfiltered & draw and not unfiltered & (order | loops)
    assert filtered & draw and filtered & loops and not filtered & order


def test_sampler_path_is_one_predicate_for_numpy_and_traced_arrays():
    from veomni_tpu.models import decode as dm

    v = 64
    rows = [  # temperature, top_k, top_p -> path
        ((0.0, 20, 0.5), 0), ((0.0, 0, 1.0), 0), ((1.0, 0, 1.0), 1), ((1.0, v, 1.0), 1),
        ((1.0, v + 1, 1.0), 1), ((1.0, -1, 2.0), 1), ((1.0, v - 1, 1.0), 2), ((1.0, 0, 0.999), 2),
    ]
    for (t, k, p), want in rows:
        for beside in ((0.0, 20, 0.5), (1.0, 0, 1.0)):  # a greedy row, an unfiltered one
            arrays = (np.asarray([t, beside[0]], np.float32), np.asarray([k, beside[1]], np.int32),
                      np.asarray([p, beside[2]], np.float32))
            expect = max(want, 1 if beside[0] > 0 else 0)
            assert int(dm.sampler_path(*arrays, v)) == expect
            assert int(jax.jit(lambda a, b, c: dm.sampler_path(a, b, c, v))(*arrays)) == expect


def test_prompt_length_bucketing_keeps_compiles_flat():
    """Distinct prompt lengths inside one power-of-two bucket must reuse the
    SAME prefill/decode compilation (each retrace costs 20-40s on TPU) and
    still match full-prefix rescoring exactly."""
    from veomni_tpu.models import decode as decode_mod

    cfg = TransformerConfig(dtype=jnp.float32, **CONFIGS["qwen3"])
    model = build_foundation_model(config=cfg)
    params = model.family.init_params(jax.random.PRNGKey(0), cfg)
    prompt = list(np.random.default_rng(2).integers(1, 128, 9))

    base = dict(decode_mod.TRACE_COUNTS)
    outs = {}
    # lengths 5/6/7 share the prompt bucket (16) AND the cache bucket
    # (5+6..7+6 <= 16): zero extra compiles after the first
    for n in (5, 6, 7):
        outs[n] = greedy_generate(params, cfg, prompt[:n], max_new_tokens=6)
    assert decode_mod.TRACE_COUNTS["prefill"] - base["prefill"] == 1
    assert decode_mod.TRACE_COUNTS["decode"] - base["decode"] == 1
    for n in (5, 6, 7):  # bucketing must not change the tokens
        assert outs[n] == _rescoring_generate(params, cfg, prompt[:n], 6)


# --------------------------------------------------------------------------
# The K/V stack is the layer scan's carry and a layer's index goes into the
# addresses of what it writes and reads (PR 48). The reference is the walk as
# it was (tests/sliced_walk.py): a layer sliced out, stepped, written back.

WALK_CONFIGS = {
    "dense": CONFIGS["qwen3"],
    # alternating window and full layers with sink logits and a local rope:
    # the scanned window and flag travel beside the layer's index
    "window": dict(CONFIGS["gpt_oss_ish"], rope_local_base_freq=10000.0),
    # one dense layer, then two expert layers: two scans over one carried stack
    "dense_then_moe": dict(CONFIGS["qwen3_moe"], num_hidden_layers=3, first_k_dense_replace=1),
}
NB, BS, SLOTS = 12, 4, 3
# slot 0 writes block 7 at offset 1, slot 1 block 4 at offset 0, slot 2 is
# parked: its table is all the null block
TABLES = np.asarray([[3, 5, 7, 0], [2, 4, 0, 0], [0, 0, 0, 0]], np.int32)
POSITIONS = np.asarray([9, 4, 0], np.int32)


def _walk_model(name):
    cfg = TransformerConfig(dtype=jnp.float32, **WALK_CONFIGS[name.removesuffix("_int8")])
    params = build_foundation_model(config=cfg).family.init_params(jax.random.PRNGKey(3), cfg)
    if cfg.attention_sinks:
        params["layers"]["sinks"] = jax.random.normal(
            jax.random.PRNGKey(4), params["layers"]["sinks"].shape, jnp.float32)
    return cfg, params


def _pools(cfg, layers, quantized=False, seed=5):
    from veomni_tpu.ops.quantization import QuantizedKV, quantize_rows

    shape = (layers, NB, BS, cfg.num_key_value_heads, cfg.head_dim)
    pools = tuple(jax.random.normal(k, shape, jnp.float32)
                  for k in jax.random.split(jax.random.PRNGKey(seed)))
    return tuple(QuantizedKV(*quantize_rows(p)) for p in pools) if quantized else pools


def _both_walks(step):
    """(the program's result, the sliced walk's) of one jitted step. A jit
    each, of a function of its own: two jits of one function share a trace."""
    from sliced_walk import sliced

    got = jax.jit(lambda: step())()
    with sliced():
        want = jax.jit(lambda: step())()
    return got, want


def _assert_same_bits(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


PAGED_STEPS = {
    "decode": lambda dm, params, cfg, pools: dm.paged_decode_step(
        params, cfg, pools, jnp.asarray(TABLES), jnp.asarray(POSITIONS),
        jnp.asarray([3, 17, 99], jnp.int32)),
    # a chunk of 8 rows of which 6 are real, from position 5 of a sequence
    # whose blocks are 3, 5, 7, 9: it crosses two block edges, and its two
    # padded rows go to the null block
    "prefill": lambda dm, params, cfg, pools: dm.paged_prefill_step(
        params, cfg, pools, jnp.asarray([3, 5, 7, 9], jnp.int32), jnp.int32(5),
        jnp.asarray([7, 8, 9, 10, 11, 12, 0, 0], jnp.int32), jnp.int32(6), 8),
    # three candidate rows a slot of which 3, 2 and 1 are real
    "verify": lambda dm, params, cfg, pools: dm.paged_verify_step(
        params, cfg, pools, jnp.asarray([[3, 5, 7, 0], [2, 4, 6, 0], [0, 0, 0, 0]], jnp.int32),
        jnp.asarray(POSITIONS), jnp.asarray([[3, 4, 5], [17, 18, 0], [99, 0, 0]], jnp.int32),
        jnp.asarray([3, 2, 1], jnp.int32)),
}


@pytest.mark.parametrize("name", sorted(WALK_CONFIGS) + ["dense_int8"])
@pytest.mark.parametrize("step", sorted(PAGED_STEPS))
def test_a_paged_step_over_the_carried_stack_is_the_sliced_walks_bit_for_bit(step, name):
    """Logits and both pools after one paged step (decode, chunk prefill,
    speculative verify) against slicing each layer out, stepping it and
    writing it back: a dense pool, a ``QuantizedKV`` pool (payload and
    sidecar), a stack with window layers, a dense-then-MoE stack."""
    from veomni_tpu.models import decode as dm

    cfg, params = _walk_model(name)
    pools = _pools(cfg, cfg.num_hidden_layers, quantized=name.endswith("_int8"))
    got, want = _both_walks(lambda: PAGED_STEPS[step](dm, params, cfg, pools))
    _assert_same_bits(got, want)
    # and the step did write: the pools are not the ones it was handed
    assert any((np.asarray(a) != np.asarray(b)).any()
               for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(pools)))


@pytest.mark.parametrize("name", sorted(WALK_CONFIGS))
def test_the_contiguous_walk_over_the_carried_stack_is_the_sliced_walks_bit_for_bit(name):
    """Prefill's logits and caches, then six greedy tokens of the scan decode
    over them: the contiguous cache ``[L, B, M, hkv, d]`` is carried, written
    at ``(layer, :, write_idx)`` and read a layer at a time."""
    from veomni_tpu.models import decode as dm

    cfg, params = _walk_model(name)
    tokens = jnp.zeros((2, 32), jnp.int32).at[:, :11].set(
        jax.random.randint(jax.random.PRNGKey(6), (2, 11), 1, 128))

    def step():
        logits, caches = dm._prefill_impl(params, cfg, tokens, jnp.int32(11), 16, 32)
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = dm._decode_impl(params, cfg, caches, first, 11, jax.random.PRNGKey(0), 6,
                              0.0, 0, 1.0)
        return logits, caches, out

    _assert_same_bits(*_both_walks(step))


KIND_STEPS = {
    "contiguous": lambda dm, params, cfg, pools: dm._prefill_impl(
        params, cfg, jnp.zeros((2, 16), jnp.int32).at[:, :11].set(5), jnp.int32(11), 16, 16),
    "paged_decode": lambda dm, params, cfg, pools: dm.paged_decode_step(
        params, cfg, pools, jnp.asarray(TABLES), jnp.asarray(POSITIONS),
        jnp.asarray([3, 17, 99], jnp.int32)),
    "paged_prefill": lambda dm, params, cfg, pools: dm.paged_prefill_step(
        params, cfg, pools, jnp.asarray([3, 5, 7, 9], jnp.int32), jnp.int32(4),
        jnp.asarray([7, 8, 9, 10, 11, 12, 0, 0], jnp.int32), jnp.int32(6), 8, jnp.int32(1)),
}


@pytest.mark.parametrize("step", sorted(KIND_STEPS))
def test_the_walk_by_kinds_over_the_whole_stack_is_the_sliced_walks_bit_for_bit(step):
    """``_kind_walk``'s three variants (a stack of convolution and attention
    layers): the attention layers address the K/V stack by a static layer
    index where they sliced ``k_all[ia]`` and wrote ``.at[ia].set``."""
    from veomni_tpu.models import decode as dm

    from test_lfm2_moe import CFG as cfg, FAMILY  # the lfm2 cell's configuration at rehearsal size

    params = FAMILY.init_params(jax.random.PRNGKey(7), cfg)
    assert dm.walks_by_kind(cfg) and dm.kv_layers(cfg) >= 2
    pools = _pools(cfg, dm.kv_layers(cfg)) + (dm.init_layer_state(cfg, SLOTS, NB),)
    _assert_same_bits(*_both_walks(lambda: KIND_STEPS[step](dm, params, cfg, pools)))


@pytest.mark.parametrize("quantized", [False, True], ids=["dense", "int8"])
def test_a_parked_slot_writes_its_layers_null_page_and_nowhere_else(quantized):
    """A slot parked on the null block writes, in layer ``l``, into page
    ``l * NB`` of the flat view (block 0 of ITS layer) at its offset; the live
    slots write their own (block, offset) of every layer; no other row of
    either pool moves."""
    from veomni_tpu.models import decode as dm

    cfg, params = _walk_model("dense")
    layers = cfg.num_hidden_layers
    pools = _pools(cfg, layers, quantized=quantized)
    _, (k_after, v_after) = jax.jit(lambda: PAGED_STEPS["decode"](dm, params, cfg, pools))()
    written = {(l, int(TABLES[s, POSITIONS[s] // BS]), int(POSITIONS[s] % BS))
               for l in range(layers) for s in range(SLOTS)}
    assert {(l, 0, 0) for l in range(layers)} <= written
    for before, after in zip(pools, (k_after, v_after)):
        for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
            flat = (np.asarray(b) != np.asarray(a)).reshape(layers * NB, BS, -1).any(-1)
            pages, offs = np.nonzero(flat)
            assert {(p // NB, p % NB, o) for p, o in zip(pages.tolist(), offs.tolist())} == written
