"""The comparison that decides ``correct`` for a served model.

Once the window has closed, a sample of the requests it finished, drawn
from the seed and always holding the longest, is run through the plain
float32 reference, prompt and served tokens together in one forward
pass.  At every position where the server emitted a token the reference
gives its largest logit over the vocabulary; the number compared is the
widest gap by which a served token's logit lies below that largest,
in units of the standard deviation of the reference's logits at that
position (so the number means the same at every width and depth).
Greedy decoding that matched the reference exactly would read 0.  A
served id outside the vocabulary reads infinity.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.models import reference
from bench.models.common import LOGIT_BLOCK, logit_stats

BATCH = 4


def pick_sample(finished: Sequence[Tuple[np.ndarray, List[int]]],
                n: int, seed: int) -> List[int]:
    """Indices of ``n`` finished requests: the longest (prompt plus
    served tokens) and the rest drawn from the seed."""
    if not finished:
        return []
    total = [len(p) + len(t) for p, t in finished]
    longest = int(np.argmax(total))
    rest = [i for i in range(len(finished)) if i != longest]
    rng = np.random.default_rng(seed)
    chosen = rng.choice(rest, size=min(n - 1, len(rest)), replace=False)
    return [longest] + sorted(int(i) for i in chosen)


def padded_length(seqs) -> int:
    """The reference's one sequence length for ``seqs`` [(prompt, served
    tokens)]: the longest, rounded up to whole logit blocks."""
    longest = max(len(p) + len(t) for p, t in seqs)
    return -(-longest // LOGIT_BLOCK) * LOGIT_BLOCK


def _stats_fn(config: dict):
    m = config["model"]
    ref = reference(config)

    @jax.jit
    def stats(w, tokens, targets):
        h = ref.forward(m, w, tokens)
        return logit_stats(h, ref.output_table(m, w), targets,
                           m["vocab_size"])
    return stats


def served_gaps(config: dict, weights, seqs):
    """Per request of ``seqs`` [(prompt, served tokens)], the normalized
    gap of each served token.  Sequences are padded to one length
    (``padded_length``) and run ``BATCH`` at a time, so the reference
    compiles one shape for a sample."""
    stats = _stats_fn(config)
    length = padded_length(seqs)
    out = []
    for i in range(0, len(seqs), BATCH):
        part = list(seqs[i:i + BATCH])
        tokens = np.zeros((BATCH, length), np.int32)
        targets = np.zeros((BATCH, length), np.int32)
        for r, (p, t) in enumerate(part):
            full = np.concatenate([p, np.asarray(t, np.int32)])
            tokens[r, :len(full)] = full
            targets[r, :len(full) - 1] = full[1:]
        top, picked, std = (np.asarray(a) for a in stats(
            weights, jnp.asarray(tokens), jnp.asarray(targets)))
        for r, (p, t) in enumerate(part):
            at = slice(len(p) - 1, len(p) - 1 + len(t))
            out.append((top[r, at] - picked[r, at]) / std[r, at])
    return out


def summarize(gaps) -> dict:
    """The numbers over all served tokens of a sample: the widest
    normalized gap, the mean normalized gap, and the share of tokens
    that are not the reference's first choice."""
    g = np.concatenate(gaps) if gaps else np.array([np.inf])
    return {"max_gap": float(np.max(g)), "mean_gap": float(np.mean(g)),
            "miss_share": float(np.mean(g > 0)), "tokens": int(g.size)}
