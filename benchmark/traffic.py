"""The one general traffic generator: every mix is a data file of
parameters that these functions read.

Sizes (document lengths) are drawn from the MIX's own fixed ``size_seed``, so
that every run of a cell does the same set of work: seeds that changed the
sizes moved ``train_tokens_per_s`` by 5% through the padding alone (PR 26).
``--seed`` decides the token ids. A seed is any whole number (the driver's
pass 2**31).
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (2 ** 63), stream])


def lognormal_lengths(rng, n: int, spec: dict) -> np.ndarray:
    """``spec``: median, sigma, min, max (clipped, whole numbers)."""
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


# ------------------------------------------------------------ train_packed
def packed_documents(mix: dict, vocab: int, seed: int):
    """Documents of a ``train_packed`` mix: the mix's fixed lengths in the
    mix's fixed order (the job also fixes the loader's shuffle), so that
    every seed packs into the same rows, pads as much and does the same work
    in every step; the seed draws the ids, uniform over [1, vocab)."""
    lengths = lognormal_lengths(rng_for(mix["size_seed"]), mix["n_docs"], mix["doc_tokens"])
    flat = rng_for(seed, 1).integers(1, vocab, int(lengths.sum()), dtype=np.int32)
    return np.split(flat, np.cumsum(lengths)[:-1])


def write_jsonl(docs, path: str) -> int:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for ids in docs:
            f.write('{"input_ids": [' + ",".join(map(str, ids.tolist())) + "]}\n")
    return len(docs)
