"""The comparison that decides ``correct`` for a served model.

Each sampled request is run once through the reference over its prompt
and the tokens the program served, and every served token is read by
its gap: how far the reference's logit for it lies below the
reference's best logit at that position.  A greedy token that the
program got right reads 0; rounding in the program reads as small gaps
where the reference's two best logits nearly tie; a token altered, a
cache entry lost or a layer skipped reads as a gap of the size of the
logits' spread.  The number compared is the widest gap.

The control (``control=True``) reads, at the same positions, the gap of
the token the fp8 reference puts first.
"""
from __future__ import annotations

import numpy as np

from bench.reference import model

SEQ_BUCKET = 512        # sequences pad to a multiple: few compiled shapes
POS_BUCKET = 256


def _pad(x: np.ndarray, m: int, fill: int) -> np.ndarray:
    n = -(-len(x) // m) * m
    return np.concatenate([x, np.full(n - len(x), fill, x.dtype)])


def served_gaps(w, cfgj: dict, picked: list, control: bool = False) -> dict:
    """``picked`` is a list of (request, served tokens)."""
    import jax.numpy as jnp

    gaps, cgaps = [], []
    for req, served in picked:
        L, n = len(req.tokens), len(served)
        seq = np.concatenate([np.asarray(req.tokens, np.int32),
                              np.asarray(served[:-1], np.int32)])
        pos = _pad(np.arange(L - 1, L - 1 + n, dtype=np.int32), POS_BUCKET,
                   L - 1)
        seq = _pad(seq, SEQ_BUCKET, 0)
        ref = model.logits(w, cfgj, seq, pos)[:n]
        best = ref.max(-1)
        got = ref[jnp.arange(n), jnp.asarray(served, jnp.int32)]
        gaps.append(np.asarray(best - got))
        if control:
            low = model.logits(w, cfgj, seq, pos, precision="fp8")[:n]
            pick = low.argmax(-1)
            cgaps.append(np.asarray(best - ref[jnp.arange(n), pick]))
    out = {"requests": len(picked), **_reading(gaps, "")}
    if control:
        out.update(_reading(cgaps, "control_"))
    return out


def _reading(gaps: list, prefix: str) -> dict:
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    return {prefix + "gap": float(g.max()) if len(g) else 0.0,
            prefix + "mean_gap": float(g.mean()) if len(g) else 0.0,
            prefix + "tokens": int(len(g))}
