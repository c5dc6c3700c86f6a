"""The serving comparison: the plain reference run once over each prompt
with its served tokens, layer by layer (weights regenerated from the seed
one layer at a time), and the widest gap by which a served token's logit
lies below the reference's best at that position. Greedy serving puts the
reference's best first up to rounding, so the gap is near 0 for a sound
program and large for a token altered where it is produced."""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import moe_lm

BUCKET = 512


@partial(jax.jit, static_argnums=(2, 3))
def _layer(x, w, m, quant):
    pos = jnp.arange(x.shape[0])
    return moe_lm.layer(x, w, pos, m, quant)


@partial(jax.jit, static_argnums=(3,))
def _embed(tokens, embed, _unused, m):
    return jnp.take(embed, tokens, axis=0).astype(jnp.float32)


@partial(jax.jit, static_argnums=(3, 4))
def _gaps(x, top, served, m, quant):
    """x: (n, d) hidden states at the positions that predicted ``served``
    (n,). Returns (reference gap per token, the reference's best token,
    the reference logits) — ``quant`` only selects the precision."""
    lg = moe_lm.logits(x, top, m, quant)
    best = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]
    return best - got, jnp.argmax(lg, axis=-1), lg


def served_gaps(m: Dict, init: Dict, seed: int,
                items: Sequence[Tuple[List[int], List[int]]],
                quant: Optional[str] = None) -> Dict:
    """``items``: (prompt, served tokens). Returns {"gap": widest gap of
    the served tokens under the reference, "tokens": count, "gaps": every
    token's gap, and with ``quant``: "control_gap" and "control_gaps", the
    same of the tokens that the control precision puts first, read
    against the reference}."""
    init = moe_lm.frozen(init)
    m = moe_lm.frozen(m)
    seqs = []
    for prompt, served in items:
        toks = list(prompt) + list(served[:-1])
        T = len(toks)
        Tp = -(-T // BUCKET) * BUCKET
        seqs.append((np.pad(np.array(toks, np.int32), (0, Tp - T)),
                     len(prompt), np.array(served, np.int32)))
    if not seqs:
        return {"gap": float("nan"), "tokens": 0, "gaps": []}
    top = moe_lm.top_weights(m, init, seed)
    streams = [None] if quant is None else [None, quant]
    xs = {q: [_embed(jnp.asarray(t), top["embed"], 0, m) for t, _, _ in seqs]
          for q in streams}
    with jax.default_matmul_precision("highest"):
        for l in range(m["n_layers"]):
            w = moe_lm.layer_weights(m, init, seed, l)
            for q in streams:
                xs[q] = [_layer(x, w, m, q) for x in xs[q]]
            del w
        gaps, ctl_gaps = [], []
        for j, (_, P, served) in enumerate(seqs):
            n = len(served)
            rows = slice(P - 1, P - 1 + n)
            gap, _, ref_lg = _gaps(xs[None][j][rows], top,
                                   jnp.asarray(served), m, None)
            gaps.append(np.asarray(gap))
            if quant is not None:
                _, ctl_tok, _ = _gaps(xs[quant][j][rows], top,
                                      jnp.asarray(served), m, quant)
                best = jnp.max(ref_lg, axis=-1)
                pick = jnp.take_along_axis(ref_lg, ctl_tok[:, None],
                                           axis=-1)[:, 0]
                ctl_gaps.append(np.asarray(best - pick))
    gaps = np.concatenate(gaps)
    out = {"gap": float(gaps.max()), "tokens": int(gaps.size),
           "gaps": gaps.tolist()}
    if quant is not None:
        ctl_gaps = np.concatenate(ctl_gaps)
        out["control_gap"] = float(ctl_gaps.max())
        out["control_gaps"] = ctl_gaps.tolist()
    return out
