"""Whether the window's served tokens are right: a sample of the requests
the window finished, drawn from the seed, goes through the plain reference
once over its prompt and served tokens, and each served token's reference
logit is compared with the reference's best at that position.

Greedy decoding at the reference's precision would give gaps of 0; the
program's bfloat16 arithmetic may pick a token whose reference logit lies a
little below the best where two logits nearly tie.
``bench/checks/<cell>.json`` gives the number of requests sampled and a
limit for each number of :func:`numbers` that is compared.
"""
from __future__ import annotations

import numpy as np

from bench import reference


def sequences(prompts, tokens):
    """Each request's prompt and served tokens but the last: the inputs
    whose logits chose the served tokens."""
    return np.concatenate([prompts, tokens[:, :-1]], axis=1)


def gaps(ref_logits, chosen):
    """Per position: the reference's best logit minus its logit of the
    chosen token.  ref_logits (R, G, V), chosen (R, G)."""
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, chosen[..., None], axis=-1)[..., 0]
    return best - got


def served_gaps(config: dict, params, prompts, tokens,
                precision: str = "f32"):
    """Per served token (R, G): its gap under the reference."""
    ref = reference.logits(config, params, sequences(prompts, tokens),
                           prompts.shape[1] - 1, precision)
    return gaps(ref, tokens)


def control_gaps(config: dict, params, prompts, tokens,
                 precision: str = "fp8"):
    """Per position (R, G): the gap, under the float32 reference, of the
    token that the reference computed in ``precision`` puts first."""
    seqs = sequences(prompts, tokens)
    first = prompts.shape[1] - 1
    ref = reference.logits(config, params, seqs, first, "f32")
    low = reference.logits(config, params, seqs, first, precision)
    return gaps(ref, low.argmax(axis=-1))


def numbers(g) -> dict:
    """What may be compared of a set of gaps: the widest, the mean, and
    the share of tokens that are not the reference's first choice."""
    g = np.asarray(g, np.float64)
    return {"max_logit_gap": float(g.max()),
            "mean_logit_gap": float(g.mean()),
            "not_first_share": float((g > 0).mean())}
