"""`correct`: what the timed path served, against the plain reference.

After the window, a sample of the finished requests, drawn from the
seed and always holding the longest, is run through the reference once
each: prompt plus served tokens. At every served token the number read
is how far that token's reference logit lies below the reference's best
logit at that position. Greedy serving of an exact model reads 0; bf16
rounding lifts it a little; a wrong token, a skipped layer or a lower
precision lifts it far.

The number compared with the cell's limit (bench/limits/<cell>.json) is
that gap's widest value over every served token of the sample; its mean
and upper quantiles are printed beside it. The benchmark's weights give
FFN tiles unequal weight, known to the predictor (bench/weights.py), so
a tile swapped at a near-tie of FastForward's top-k weighs little: with
tiles of equal weight, bf16 rounding alone swapped one in most decode
steps and the served tokens parted from the reference about as far
under bf16 as under fp8 (PERF.md).
"""
from __future__ import annotations

import importlib

import numpy as np


def sample(tracks, seed: int, min_tokens: int, max_requests: int,
           max_positions: int):
    """Finished requests to check: the one holding most positions, then
    others in a seeded order until `min_tokens` served tokens are in
    the sample, at most `max_requests` requests and `max_positions`
    prompt plus served positions in all."""
    done = [t for t in tracks if t.done and t.tokens]
    if not done:
        return []
    done.sort(key=lambda t: t.rid)
    longest = max(done, key=lambda t: (t.prompt_len + len(t.tokens), t.rid))
    rest = [t for t in done if t is not longest]
    order = np.random.default_rng([seed, 7]).permutation(len(rest))
    picked, n_tok = [longest], len(longest.tokens)
    n_pos = longest.prompt_len + n_tok
    for i in order:
        if n_tok >= min_tokens or len(picked) >= max_requests:
            break
        t = rest[i]
        if n_pos + t.prompt_len + len(t.tokens) > max_positions:
            continue
        picked.append(t)
        n_tok += len(t.tokens)
        n_pos += t.prompt_len + len(t.tokens)
    return picked


def _seqs(picked):
    """(tokens, prompt_len, reads): the logits at position
    prompt_len - 1 + j choose served token j."""
    out = []
    for t in picked:
        prompt = t.prompt
        toks = list(prompt) + list(t.tokens[:-1])
        reads = np.arange(len(t.tokens)) + len(prompt) - 1
        out.append((toks, len(prompt), reads))
    return out


def reference_module(config: dict):
    return importlib.import_module(f"bench.references.{config['reference']}")


def gaps(ref_logits, chosen):
    """Per position: reference best minus the reference logit of the
    chosen token."""
    return [np.max(r, axis=-1) - r[np.arange(len(c)), c]
            for r, c in zip(ref_logits, chosen)]


QUANTILES = (0.75, 0.9, 0.95, 0.99)


def summary(g) -> dict:
    """The gaps' mean, widest (the number compared), the largest mean of
    one request's gaps, share of tokens off the reference's first choice
    and upper quantiles."""
    flat = np.concatenate(g) if g else np.zeros(0)
    out = {"mean_logit_gap": None, "max_logit_gap": None,
           "worst_request_mean_gap": None, "mismatch_share": None,
           "n_tokens": int(flat.size)}
    out.update({f"q{int(q * 100)}_logit_gap": None for q in QUANTILES})
    if flat.size:
        out.update(mean_logit_gap=float(flat.mean()),
                   max_logit_gap=float(flat.max()),
                   worst_request_mean_gap=max(float(x.mean())
                                              for x in g if x.size),
                   mismatch_share=float((flat > 0).mean()))
        out.update({f"q{int(q * 100)}_logit_gap": float(np.quantile(flat, q))
                    for q in QUANTILES})
    return out


def served_gap(config: dict, seed: int, picked) -> dict:
    """The gaps of the served tokens of `picked` (summary() keys), the
    sample's size and the reference logits."""
    ref = reference_module(config)
    ref_logits = ref.logits(config, seed, _seqs(picked))
    out = summary(gaps(ref_logits, [np.asarray(t.tokens) for t in picked]))
    out.update(n_requests=len(picked), ref_logits=ref_logits)
    return out


def control_gap(config: dict, seed: int, picked, ref_logits,
                precision: str = "fp8") -> dict:
    """The control: the reference in the precision below the one the
    configuration states, at the same positions; the gaps of the tokens
    it puts first (summary() keys, and "gaps" per sequence)."""
    ref = reference_module(config)
    low = ref.logits(config, seed, _seqs(picked), precision=precision)
    g = gaps(ref_logits, [np.argmax(x, axis=-1) for x in low])
    return dict(summary(g), gaps=g)
