"""The output check: served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the requests finished in the window (the longest among them, the rest drawn
from the seed) is run through the reference once each, prompt and served
tokens together.  At every position where the program served a token, the
number compared is how far that token's reference logit lies below the
reference's best logit there.  The widest such gap over the sample is held
to the cell's limit (``cells/<cell>.json``).

The control (``compare(..., control=True)``) reads, at the same positions,
the gap of the token that the float8 reference puts first.
"""
from __future__ import annotations

import numpy as np

import reference


def choose(finished: list, seed: int, k: int) -> list:
    """The longest of ``finished`` and k - 1 others drawn from the seed.
    Each item is (prompt, served tokens)."""
    if not finished:
        return []
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i][0]) + len(finished[i][1]))
    rest = [i for i in range(len(finished)) if i != longest]
    drawn = np.random.default_rng([seed, 2]).permutation(rest)[:k - 1]
    return [finished[longest]] + [finished[int(i)] for i in sorted(drawn)]


def compare(weights, conf: dict, seqs: list, max_len: int, batch: int,
            control: bool = False) -> dict:
    """Widest logit gap and the share of positions whose token is not the
    reference's first, over the served positions of ``seqs``.  With
    ``control`` the tokens read are the float8 reference's first choices
    instead of the served ones."""
    gaps, missed, n, nonfinite = [], 0, 0, 0
    for b0 in range(0, len(seqs), batch):
        chunk = seqs[b0:b0 + batch]
        toks = np.zeros((batch, max_len), np.int32)
        served = np.zeros((batch, max_len), np.int32)
        spans = []
        for i, (prompt, out) in enumerate(chunk):
            full = np.concatenate([prompt, np.asarray(out, np.int32)])
            if len(full) > max_len:
                raise ValueError(f"{len(full)} tokens exceed {max_len}")
            toks[i, :len(full)] = full
            lo = len(prompt) - 1          # position that served out[0]
            served[i, lo:lo + len(out)] = out
            spans.append(slice(lo, lo + len(out)))
        x = reference.hidden(weights, conf, toks)
        read = served
        if control:
            xq = reference.hidden(weights, conf, toks, quant="fp8")
            best_q, _, read = (np.asarray(a) for a in reference.head_stats(
                weights, conf, xq, served, quant="fp8"))
            nonfinite += int(sum(np.sum(~np.isfinite(best_q[i, sl]))
                                 for i, sl in enumerate(spans)))
            del xq
        best, at, top = (np.asarray(a) for a in
                         reference.head_stats(weights, conf, x, read))
        for i, sl in enumerate(spans):
            gaps.append(float(np.max(best[i, sl] - at[i, sl])))
            missed += int(np.sum(top[i, sl] != read[i, sl]))
            n += sl.stop - sl.start
    return {"max_logit_gap": max(gaps) if gaps else float("nan"),
            "mismatch_share": missed / n if n else float("nan"),
            "tokens": n, "requests": len(seqs), "nonfinite": nonfinite}
