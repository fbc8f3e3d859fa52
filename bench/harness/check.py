"""Whether what the timed path served is correct.

Once the window has closed and the program is freed, a sample of the
requests it finished — drawn from the seed, with the longest among them —
is run through the configuration's plain reference: each prompt with the
tokens that were served after it, in one teacher-forced pass.  At every
served position two numbers are read:

``max_logit_gap``
    how far the served token's reference logit lies below the
    reference's best logit there.  A token altered where it is produced,
    a cache that lost a write or a lower-precision path opens it; greedy
    serving at the stated precision keeps it at rounding level.
``max_logit_err``
    how far the program's own logit of the served token (the largest of
    its row, since sampling is greedy) lies from the reference's logit of
    that token.  It reads every position, not only those where rounding
    flips the top token, so it separates a precision one step lower even
    where no token changes.

The widest of each over the sample is compared with the cell's limit
(``limits/<cell>.json``, set from readings of sound runs and of the
control; see PERF.md).  The control is the reference in the program's
place at the precision below the configuration's: it serves the argmax
of its own logits and reports its own largest logit.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Sequence

import numpy as np

BUCKET = 512          # sequences are padded to a multiple of this length
NUMBERS = ("max_logit_gap", "max_logit_err")


def sample(records: Sequence, seed: int, target_tokens: int,
           max_requests: int) -> List:
    """Finished requests to compare: the one that served the most tokens,
    then others in an order drawn from the seed, until ``target_tokens``
    served tokens or ``max_requests`` requests."""
    done = [r for r in records if r.done]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.max_new, len(r.prompt), r.index))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(int(seed) + 7)
    out, n = [longest], longest.max_new
    for i in rng.permutation(len(rest)):
        if n >= target_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += rest[i].max_new
    return out


def padded(tokens: List[int]) -> np.ndarray:
    n = -(-len(tokens) // BUCKET) * BUCKET
    out = np.zeros((n,), np.int32)
    out[:len(tokens)] = tokens
    return out


def positions(prompt: List[int], n_served: int) -> np.ndarray:
    """Rows of the teacher-forced pass that predict each served token."""
    return np.arange(len(prompt) - 1, len(prompt) - 1 + n_served)


def served_readings(ref_rows: np.ndarray, served: Sequence[int],
                    top: Sequence[float]) -> Dict[str, np.ndarray]:
    """Per served token: its gap below the reference's best, and the
    distance of the program's logit from the reference's."""
    served = np.asarray(served)
    at = ref_rows[np.arange(len(served)), served]
    return {"max_logit_gap": ref_rows.max(axis=-1) - at,
            "max_logit_err": np.abs(np.asarray(top, np.float32) - at)}


def control_readings(ref_rows: np.ndarray,
                     ctl_rows: np.ndarray) -> Dict[str, np.ndarray]:
    """The same numbers for the control serving its own argmax."""
    pick = ctl_rows.argmax(axis=-1)
    return served_readings(ref_rows, pick, ctl_rows.max(axis=-1))


def compare(logits_fn: Callable, picked: Sequence,
            control_fn: Callable = None) -> Dict[str, Dict[str, float]]:
    """Widest readings over the picked requests for the program (and the
    control, when ``control_fn`` is given).  ``logits_fn(tokens) -> (S,
    V)`` runs the reference on one padded sequence."""
    prog: Dict[str, List] = {k: [] for k in NUMBERS}
    ctl: Dict[str, List] = {k: [] for k in NUMBERS}
    for r in picked:
        seq = list(r.prompt) + list(r.tokens[:-1])
        toks = padded(seq)
        pos = positions(r.prompt, len(r.tokens))
        ref = np.asarray(logits_fn(toks), np.float32)[pos]
        top = r.top_logits if len(r.top_logits) == len(r.tokens) \
            else [np.nan] * len(r.tokens)        # a row the tap never saw
        for k, v in served_readings(ref, r.tokens, top).items():
            prog[k].append(v)
        if control_fn is not None:
            c = np.asarray(control_fn(toks), np.float32)[pos]
            for k, v in control_readings(ref, c).items():
                ctl[k].append(v)
    out = {"program": {k: float(np.concatenate(v).max()) for k, v in
                       prog.items()}}
    if control_fn is not None:
        out["control"] = {k: float(np.concatenate(v).max())
                          for k, v in ctl.items()}
    return out


def judge(readings: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Correct when every number compared is within its limit (a NaN is
    never within)."""
    return all(readings[k] <= limits[k] for k in limits)
