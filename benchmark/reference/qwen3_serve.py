"""What a serving cell compares, through the plain reference of the Qwen3
dense family (``qwen3.py``: float32 at ``highest`` matmul precision, no
cache, no batching; nothing of the program is imported).

The engine hands out tokens, not logits, so the comparison is made in logit
units through the reference: one full forward pass over a request's prompt
with the tokens that were served after it, and at every served position the
reference's best logit beside the logit of the token given. A greedy token
the reference agrees with reads a gap of 0; one that lost to rounding reads
the small gap between the reference's two best; one computed from another
request's context reads what a random token reads.

The weights are the CHECKPOINT's: drawn from the seed in float32 and rounded
to the type the configuration serves them in (bfloat16), as the program's
are, then widened again, so that the arithmetic and not the checkpoint is
what differs between the two sides. ``quant`` puts every projection's
operands into the control's lower format (``qwen3.py::_fake_quant``).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import qwen3 as ref

ROWS = 256  # rows of the head at a time: [ROWS, vocab] float32


def make_reader(cfg: dict, seed: int, *, served_dtype, padded_len: int, out_len: int):
    """``read(ids, n_ids, first, tokens, quant=None) -> (best, argbest, given)``:
    a forward pass over ``ids`` (padded to ``padded_len``; ``n_ids`` real),
    then the head at the ``out_len`` positions from ``first`` on: each
    position's best logit, the token that has it, and the logit of
    ``tokens[j]`` there. One compiled program for every request of a run."""
    assert padded_len % 512 == 0 or padded_len <= 512, padded_len

    @jax.jit
    def weights(key):
        flat = ref.make_params(cfg, key, served_dtype)
        return ref.nest({k: v.astype(jnp.float32) for k, v in flat.items()})

    params = weights(ref.seed_key(seed))

    @functools.partial(jax.jit, static_argnames=("quant",))
    def read(params, ids, n_ids, first, tokens, quant=None):
        with jax.default_matmul_precision("highest"):
            t = jnp.arange(padded_len)
            segments = (t < n_ids).astype(jnp.int32)  # padding attends to padding only
            hidden = ref.hidden_states(params, cfg, ids, t, segments, quant)
            hidden = jnp.pad(hidden, ((0, out_len), (0, 0)))
            rows = jax.lax.dynamic_slice_in_dim(hidden, first, out_len, axis=0)
            n_blocks = -(-out_len // ROWS)
            pad = n_blocks * ROWS - out_len
            rows = jnp.pad(rows, ((0, pad), (0, 0))).reshape(n_blocks, ROWS, -1)
            toks = jnp.pad(tokens, (0, pad)).reshape(n_blocks, ROWS)

            def block(xs):
                h, tok = xs
                logits = ref.logits_at(params, h, quant)
                return (jnp.max(logits, axis=-1), jnp.argmax(logits, axis=-1).astype(jnp.int32),
                        jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0])

            best, arg, given = jax.lax.map(block, (rows, toks))
        return tuple(x.reshape(-1)[:out_len] for x in (best, arg, given))

    def call(ids, n_ids, first, tokens, quant=None):
        return read(params, jnp.asarray(ids, jnp.int32), jnp.int32(n_ids), jnp.int32(first),
                    jnp.asarray(tokens, jnp.int32), quant=quant)

    return call


def served_gaps(reader, requests, *, padded_len: int, out_len: int,
                quant: Optional[str] = None) -> Dict[str, np.ndarray]:
    """``requests``: [(prompt ids, served tokens)]. For every served token,
    the reference's best logit at its position less the logit of the token:
    ``gaps`` (all requests' served positions, one after another) and
    ``which`` (the request of each).

    With ``quant`` the CONTROL is read in the program's place: at each
    position of the same prompts and tokens, the token the lower precision
    puts first, and the float32 reference's gap for that token."""
    gaps, which = [], []
    for r, (prompt, served) in enumerate(requests):
        n, p = len(served), len(prompt)
        ids = np.zeros(padded_len, np.int32)
        ids[:p] = prompt
        ids[p:p + n] = served
        tokens = np.zeros(out_len, np.int32)
        tokens[:n] = served
        # the token served at index j was chosen from the logits at position p - 1 + j
        if quant:
            _, low_first, _ = reader(ids, p + n, p - 1, tokens, quant=quant)
            tokens = np.asarray(low_first)
        best, _, given = reader(ids, p + n, p - 1, tokens)
        gaps.append(np.asarray(best - given, np.float64)[:n])
        which.append(np.full(n, r))
    return {"gaps": np.concatenate(gaps), "which": np.concatenate(which)}
