"""The one traffic generator: prompts for a closed loop of whole batches.

A mix file (``chipbench/traffic/<name>.json``) gives ``loop``
(``closed_batches``: the next batch starts when the last one has served
all its tokens), ``batch``, ``prompt_tokens``, ``decode_steps`` (greedy
steps after the first token), ``cache_slots`` per sequence,
``token_ids`` (``uniform`` over the vocabulary) and ``trace_batches``
(the batches a ``--trace 1`` run profiles).  Every batch of a run is
the same amount of work; the seed only chooses the token ids, so runs on
different seeds do the same work in a different order.
"""
from __future__ import annotations

from typing import Mapping

import numpy as np

LOOPS = ("closed_batches",)
TOKEN_IDS = ("uniform",)

# independent random streams drawn from one seed
WINDOW, WARMUP, SAMPLE = 1, 2, 3


def validate(mix: Mapping) -> None:
    if mix["loop"] not in LOOPS:
        raise ValueError(f"loop {mix['loop']!r} not in {LOOPS}")
    if mix["token_ids"] not in TOKEN_IDS:
        raise ValueError(f"token_ids {mix['token_ids']!r} not in {TOKEN_IDS}")
    for k in ("batch", "prompt_tokens", "decode_steps", "cache_slots",
              "trace_batches"):
        if int(mix[k]) < 1:
            raise ValueError(f"{k} must be at least 1")


def rng(seed: int, stream: int, index: int = 0) -> np.random.Generator:
    return np.random.default_rng([seed % 2**64, stream, index])


def prompts(mix: Mapping, vocab: int, seed: int, stream: int,
            index: int) -> np.ndarray:
    """Token ids (batch, prompt_tokens) of batch ``index`` of ``stream``."""
    return rng(seed, stream, index).integers(
        0, vocab, (mix["batch"], mix["prompt_tokens"]), dtype=np.int32)
