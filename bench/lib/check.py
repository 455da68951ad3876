"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, the plain
reference (``bench/models/<family>.py``, float32, every product at
HIGHEST) runs once over each sampled request's prompt and served tokens,
with weights it makes itself from the seed. The number compared is the
widest gap by which a served token's logit lies below the reference's
best logit at that position, over every served token of the sample: the
first token (serial prefill and the sampler), and every later one (the
paged decode step with its kernel, the pool writes, the sampler).

The control puts the reference in the program's place one precision step
below the configuration's (``quant="fp8"``): at each position it takes the
token that the float8 computation ranks first and reads that token's gap.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def sample(rng: np.random.Generator, finished: List[int],
           lengths: Dict[int, int], n: int) -> List[int]:
    """``n`` finished requests drawn from the seed, the longest among them."""
    if not finished:
        return []
    longest = max(finished, key=lambda i: (lengths[i], -i))
    rest = sorted(i for i in finished if i != longest)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(pick)]


def _gap_fn(family, s: Dict, quant):
    @jax.jit
    def gaps(w, full, first, last):
        """``full`` [pad+1]: prompt then served tokens, zero-padded. Row r
        of the logits predicts token r+1; rows first..last-1 predict the
        served tokens."""
        seq, target = full[:-1], full[1:]
        ref = family.forward(s, w, seq)
        r = jnp.arange(seq.shape[0])
        valid = (r >= first) & (r < last)
        best = jnp.max(ref, -1)
        served = jnp.take_along_axis(ref, target[:, None], -1)[:, 0]
        g = jnp.max(jnp.where(valid, best - served, 0.0))
        if quant is None:
            return g, g
        top = jnp.argmax(family.forward(s, w, seq, quant=quant), -1)
        picked = jnp.take_along_axis(ref, top[:, None], -1)[:, 0]
        return g, jnp.max(jnp.where(valid, best - picked, 0.0))
    return gaps


def logit_gaps(family, s: Dict, key, requests: List[Tuple[np.ndarray,
                                                          List[int]]],
               pad_to: int, control: bool = False) -> Tuple[float, float]:
    """(widest gap of the served tokens, widest gap of the control's
    tokens; the latter equals the former without ``control``)."""
    w = jax.jit(lambda k: family.init(s, k))(key)
    fn = _gap_fn(family, s, "fp8" if control else None)
    worst, worst_ctl = 0.0, 0.0
    for prompt, out in requests:
        P, n = len(prompt), len(out)
        full = np.zeros(pad_to + 1, np.int32)
        full[:P + n] = np.concatenate([prompt, np.asarray(out, np.int32)])
        g, gc = fn(w, full, np.int32(P - 1), np.int32(P - 1 + n))
        worst, worst_ctl = max(worst, float(g)), max(worst_ctl, float(gc))
    return worst, worst_ctl
