"""Whether what the timed path served is right: the served tokens against
the plain float32 reference.

After the window, a sample of the finished requests (the one with the most
tokens, then others drawn from the seed, until ``sample_tokens`` served
tokens or ``max_requests``) goes through the reference once each, teacher
forced over its prompt and served tokens.  At every served position the
gap is the reference's best logit minus its logit of the served token: 0
where the program chose what the reference chooses, small where rounding
flipped a near-tie, large where the token was wrong.  The widest gap is
the number compared with the cell's limit.

The control puts the reference itself, computed in a lower precision, in
the program's place: at the same positions it reads the gap of the token
the lower precision puts first.
"""
from __future__ import annotations

import numpy as np


def sample(records: list, seed: int, sample_tokens: int,
           max_requests: int) -> list:
    """Finished requests to check, drawn from the seed: the one with the
    longest context first."""
    done = [r for r in records if r.done is not None]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + r.max_new, r.uid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng(seed).permutation(len(rest))
    picked, served = [longest], len(longest.req.out)
    for i in order:
        if served >= sample_tokens or len(picked) >= max_requests:
            break
        picked.append(rest[i])
        served += len(rest[i].req.out)
    return picked


def _gaps(ref_logits: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    best = ref_logits.max(-1)
    return best - ref_logits[np.arange(len(chosen)), chosen]


def logit_gaps(reference, cfg: dict, seed: int, seqs: list,
               controls: tuple = ()) -> dict:
    """``seqs``: (prompt, served tokens) pairs.  Returns the widest gap of
    the served tokens and their number; for each precision in
    ``controls``, also the widest gap of the tokens that precision puts
    first (``control_gap``)."""
    import jax

    import weights

    top = weights.reference_top(reference, cfg, seed)
    layer = weights.reference_layer_fn(reference, cfg, seed)
    worst, n = 0.0, 0
    worst_ctl = {c: 0.0 for c in controls}
    with jax.default_matmul_precision("highest"):
        for prompt, out in seqs:
            out = np.asarray(out, np.int64)
            tokens = np.concatenate([np.asarray(prompt, np.int64), out])
            rows = list(range(len(prompt) - 1, len(tokens) - 1))
            ref = np.asarray(reference.logits(tokens, rows, cfg, top, layer))
            worst = max(worst, float(_gaps(ref, out).max()))
            n += len(out)
            for c in controls:
                ctl = np.asarray(reference.logits(tokens, rows, cfg, top,
                                                  layer, quant=c))
                worst_ctl[c] = max(worst_ctl[c],
                                   float(_gaps(ref, ctl.argmax(-1)).max()))
    res = {"logit_gap": worst, "served_tokens": n}
    if controls:
        res["control_gap"] = worst_ctl
    return res


def judge(got: dict, limits: dict) -> tuple[bool, dict]:
    """Whether one reading is correct, and each number compared beside its
    limit.  ``got`` holds the widest ``logit_gap`` (None where no finished
    request was checked, which is not correct) and the ``served_tokens``
    it was read over.  The program's run and each control go through
    this same comparison."""
    compared = {
        "logit_gap": {"value": got["logit_gap"],
                      "limit": limits["logit_gap"]},
        "served_tokens_checked": {"value": got["served_tokens"],
                                  "limit": limits["min_served_tokens"]},
    }
    correct = (got["logit_gap"] is not None
               and got["logit_gap"] <= limits["logit_gap"]
               and got["served_tokens"] >= limits["min_served_tokens"])
    return bool(correct), compared
