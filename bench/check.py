"""What decides ``correct``: served tokens against the plain reference.

After the window has closed, a sample of the finished requests drawn
from the seed (always with the request that served most tokens) is run
through the configuration's reference over each prompt and its served
tokens.  For each served token the reference's logits at the position
that produced it give a gap: the reference's best logit minus its logit
for the served token, 0 where they agree.  The numbers compared are the
statistics of the gaps that the cell's limits file names (the mean gap
for every cell so far), and that no request went unfinished or was cut
short (``verdict``).  Greedy tokens only: the traffic never samples.

The control (``control_gaps``) is the reference in float8 in place of
the program: at each of the same positions, the gap of the token the
float8 logits put first.  It goes through the same ``verdict``.
"""
from __future__ import annotations

import importlib.util
import os
from typing import Dict, List, Sequence, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_reference(name: str):
    path = os.path.join(HERE, "references", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reference_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sample(results, seed: int, target_tokens: int, max_requests: int,
           min_requests: int = 3):
    """The request that served most tokens, then others in an order
    drawn from the seed, until ``target_tokens`` served tokens from at
    least ``min_requests`` requests, or ``max_requests`` requests."""
    done = [r for r in results if len(r.tokens) > 0]
    if not done:
        return []
    first = max(done, key=lambda r: (len(r.tokens), -r.uid))
    rest = [r for r in done if r is not first]
    order = np.random.default_rng([int(seed), 0xC4EC]).permutation(len(rest))
    out, n = [first], len(first.tokens)
    for i in order:
        if ((n >= target_tokens and len(out) >= min_requests)
                or len(out) >= max_requests):
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def sequences(picked) -> Tuple[List[np.ndarray], List[np.ndarray],
                               List[np.ndarray]]:
    """(ids, rows, served) per request: the prompt with its served
    tokens, the positions whose logits produced each served token, and
    the served tokens."""
    seqs, rows, served = [], [], []
    for r in picked:
        prompt = np.asarray(r.prompt, np.int32)
        toks = np.asarray(r.tokens, np.int32)
        seqs.append(np.concatenate([prompt, toks[:-1]]))
        rows.append(np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks)))
        served.append(toks)
    return seqs, rows, served


def gaps(logits: Sequence[np.ndarray], tokens: Sequence[np.ndarray]
         ) -> np.ndarray:
    """Reference best minus reference logit of ``tokens``, per position."""
    out = []
    for lg, t in zip(logits, tokens):
        lg = np.asarray(lg, np.float64)
        out.append(lg.max(-1) - lg[np.arange(len(t)), t])
    return np.concatenate(out) if out else np.zeros((0,))


# the statistics of the gaps a cell's limits file may compare
STATISTICS = {
    "max_logit_gap": lambda g: float(g.max()),
    "mean_logit_gap": lambda g: float(g.mean()),
    "logit_gap_p90": lambda g: float(np.quantile(g, 0.9)),
}


def control_gaps(ref_logits, ctl_logits) -> np.ndarray:
    return gaps(ref_logits, [np.argmax(c, -1) for c in ctl_logits])


def verdict(gap: np.ndarray, limits: Dict, unfinished: int, short: int
            ) -> Tuple[Dict, bool]:
    """``(checks, correct)``: each number compared beside its limit, and
    whether every one is within it.  ``limits`` is a cell's limits file:
    ``{statistic: {"limit": x, ...}}``."""
    checks = {name: {"value": (STATISTICS[name](gap) if gap.size
                               else None), "limit": lim["limit"]}
              for name, lim in limits.items()}
    checks["unfinished_requests"] = {"value": unfinished, "limit": 0}
    checks["requests_cut_short"] = {"value": short, "limit": 0}
    correct = gap.size > 0 and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    return checks, bool(correct)
