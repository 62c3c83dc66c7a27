"""What decides ``correct``: served tokens against the float32 reference.

Once the window has closed, a sample of the requests it finished is drawn
from the seed: half from the first half of the batch rows and half from
the second, from any batch of the window.  The family's reference reruns each
sampled prompt with the tokens the program served and gives the logits
at every position that produced a served token.  A served token's gap is
how far its reference logit lies below the reference's best there; the
number compared is the widest gap over the sample (``max_gap``, in logit
units).  Greedy decoding in exact arithmetic gives 0; rounding in the
served dtype gives a little more where the top two logits nearly tie.

The control puts the reference, computed in a lower precision, in the
program's place: at the same positions, the token it ranks first is read
against the float32 reference in the same way.
"""
from __future__ import annotations

from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic

# (tokens (n, S), first, precision) -> logits (n, S - first, vocab)
Reference = Callable[..., jax.Array]


def sample(seed: int, batches: int, batch: int, rows: int
           ) -> List[Tuple[int, int]]:
    """(batch index, row) pairs, half from each half of the rows."""
    rng = traffic.rng(seed, traffic.SAMPLE)
    half = batch // 2
    picks = []
    for lo, hi, n in ((0, half, rows // 2), (half, batch, rows - rows // 2)):
        pool = [(b, r) for b in range(batches) for r in range(lo, hi)]
        take = rng.choice(len(pool), size=min(n, len(pool)), replace=False)
        picks += [pool[i] for i in sorted(take)]
    return picks


def _gaps(ref_logits, tokens) -> np.ndarray:
    ref = jnp.asarray(ref_logits)
    best = ref.max(-1)
    got = jnp.take_along_axis(ref, jnp.asarray(tokens)[..., None], -1)[..., 0]
    return np.asarray(best - got)


def compare(logits: Reference, seqs: np.ndarray, served: np.ndarray,
            block: int, control: Sequence[str] = ()) -> dict:
    """Widest gaps of ``served`` (n, G+1) after prompts ``seqs`` (n, P+G)
    (each prompt followed by its served tokens but the last), and of each
    ``control`` precision's own first choices at the same positions, by
    the reference ``logits``."""
    first = seqs.shape[1] - served.shape[1]
    out = {"max_gap": 0.0}
    out.update({f"control.{p}": 0.0 for p in control})
    for i in range(0, len(seqs), block):
        rows = seqs[i:i + block]
        ref = logits(rows, first)
        out["max_gap"] = max(out["max_gap"],
                             float(_gaps(ref, served[i:i + block]).max()))
        for p in control:
            low = logits(rows, first, precision=p)
            chosen = np.asarray(jnp.argmax(low, -1))
            out[f"control.{p}"] = max(out[f"control.{p}"],
                                      float(_gaps(ref, chosen).max()))
    return out


def gather(prompt_of: Callable[[int], np.ndarray], tokens: Sequence,
           picks) -> Tuple[np.ndarray, np.ndarray]:
    """Reference inputs and served tokens of the sampled requests:
    ``prompt_of(b)`` is batch b's prompts, ``tokens[b]`` its served
    tokens (B, G+1)."""
    seqs, served = [], []
    for b, r in picks:
        t = np.asarray(tokens[b][r])
        seqs.append(np.concatenate([prompt_of(b)[r], t[:-1]]))
        served.append(t)
    return np.stack(seqs).astype(np.int32), np.stack(served).astype(np.int32)
