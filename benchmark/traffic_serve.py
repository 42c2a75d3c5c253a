"""The generator of the ``serve_closed_loop`` traffic kind (``traffic.py`` is
the train kinds'): every number is a parameter of the mix's data file.

As with the train mixes, the SIZES come from the mix's own ``size_seed``:
prompt lengths, output lengths and which prompts start with which shared head
are one fixed stream of ``n_requests`` requests, the same in every run, so
that every seed does the same work in the same order. ``--seed`` draws the
token ids: the heads' and every prompt's own, uniform over [1, vocab).
"""

from __future__ import annotations

from typing import List

import numpy as np

from benchmark import traffic


def request_sizes(mix: dict) -> dict:
    """The stream's sizes: ``prompt`` and ``output`` lengths [n] and ``head``
    [n] (the index of the shared head a prompt starts with, -1 for none)."""
    n = mix["n_requests"]
    prompt = traffic.lognormal_lengths(traffic.rng_for(mix["size_seed"], 0), n, mix["prompt_tokens"])
    output = traffic.lognormal_lengths(traffic.rng_for(mix["size_seed"], 1), n, mix["output_tokens"])
    heads = mix["shared_heads"]
    rng = traffic.rng_for(mix["size_seed"], 2)
    shares = rng.random(n) < heads["share"]
    head = np.where(shares, rng.integers(0, heads["count"], n), -1)
    return {"prompt": prompt, "output": output, "head": head.astype(np.int64)}


def shared_heads(mix: dict, vocab: int, seed: int) -> np.ndarray:
    heads = mix["shared_heads"]
    return traffic.rng_for(seed, 2).integers(1, vocab, (heads["count"], heads["tokens"]),
                                             dtype=np.int32)


def request_stream(mix: dict, vocab: int, seed: int) -> List[dict]:
    """The requests in the order the clients send them: ``prompt_ids`` (an
    int32 array), ``max_new_tokens`` and ``head``. A head counts inside its
    prompt's length: it overwrites the prompt's start, and a prompt shorter
    than the head is the head's start."""
    sizes = request_sizes(mix)
    heads = shared_heads(mix, vocab, seed)
    flat = traffic.rng_for(seed, 1).integers(1, vocab, int(sizes["prompt"].sum()), dtype=np.int32)
    out = []
    for ids, n_out, h in zip(np.split(flat, np.cumsum(sizes["prompt"])[:-1]),
                             sizes["output"], sizes["head"]):
        if h >= 0:
            k = min(len(ids), heads.shape[1])
            ids[:k] = heads[h, :k]
        out.append({"prompt_ids": ids, "max_new_tokens": int(n_out), "head": int(h)})
    return out
