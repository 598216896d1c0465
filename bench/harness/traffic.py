"""One generator for every traffic mix.

A mix file gives the batch, the wave size and the ranges of prompt and
output lengths.  Every wave holds the *same* set of lengths: the wave's
``n`` quantiles ``(i + 0.5) / n`` of the stated distribution.  Their order
(prompts and outputs shuffled apart) is drawn from the wave's index, so it
changes from wave to wave but not from seed to seed; the seed draws the
prompt tokens.  Two seeds then do the same work in the same order, and the
spread between runs is the system's and not the draw's: with the order
drawn from the seed too, one wave of ``reasoning`` on the rns path spread
by 7% in ``gen_tok_s`` and 25% in ``tpot_p90_ms`` between seeds, and by
0.1% between two runs of one seed.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Spec:
    batch: int
    wave: int
    prompt: tuple[int, int]
    output: tuple[int, int]
    prompt_dist: str
    output_dist: str
    vocab_low: int = 1       # token ids are drawn from [vocab_low, vocab)


# Keys of a mix file: what the generator reads, and what only describes.
_READ = {"batch", "wave", "prompt", "output", "check_requests"}
_PROSE = {"sampling", "arrivals", "users"}
# Traffic the generator does not make; a file may state only these values.
_FIXED = {"prefix_sharing": 0, "greedy": True, "eos": None}


def spec_of(mix: dict) -> Spec:
    """The generator's parameters of a mix file; raises ``ValueError`` for
    a key it does not know, and for prefix sharing, sampled tokens or an
    end-of-sequence token, which it does not make."""
    unknown = set(mix) - _READ - _PROSE - set(_FIXED)
    if unknown:
        raise ValueError(f"traffic keys the generator does not read: "
                         f"{sorted(unknown)}")
    bad = {k: mix[k] for k, v in _FIXED.items() if k in mix and mix[k] != v}
    if bad:
        raise ValueError(f"traffic the generator does not make: {bad}; "
                         f"it makes {_FIXED}")
    return Spec(batch=int(mix["batch"]), wave=int(mix["wave"]),
                prompt=(int(mix["prompt"]["min"]), int(mix["prompt"]["max"])),
                output=(int(mix["output"]["min"]), int(mix["output"]["max"])),
                prompt_dist=mix["prompt"]["dist"],
                output_dist=mix["output"]["dist"])


def quantiles(lo: int, hi: int, n: int, dist: str) -> np.ndarray:
    """The ``n`` mid-quantiles of ``dist`` on ``[lo, hi]``, as integers."""
    u = (np.arange(n) + 0.5) / n
    if dist == "log-uniform":
        x = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif dist == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def wave_lengths(spec: Spec) -> tuple[np.ndarray, np.ndarray]:
    """(prompt lengths, output lengths) of one wave, in quantile order."""
    return (quantiles(*spec.prompt, spec.wave, spec.prompt_dist),
            quantiles(*spec.output, spec.wave, spec.output_dist))


def make_wave(spec: Spec, vocab: int, seed: int, index: int):
    """Wave ``index`` of run ``seed``: a list of ``(prompt tokens int32,
    max_new)`` pairs.  Deterministic in ``(seed, index)``."""
    order = np.random.default_rng([index, 0x0D3E])
    plens, olens = wave_lengths(spec)
    plens = plens[order.permutation(spec.wave)]
    olens = olens[order.permutation(spec.wave)]
    rng = np.random.default_rng([seed, index, 0x7AFF1C])
    return [(rng.integers(spec.vocab_low, vocab, int(p)).astype(np.int32),
             int(o)) for p, o in zip(plens, olens)]


def s_max(spec: Spec) -> int:
    """KV positions a slot must hold: the longest prompt plus the longest
    output the mix allows."""
    return spec.prompt[1] + spec.output[1]
