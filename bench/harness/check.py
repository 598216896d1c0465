"""The check that decides ``correct``: what the window served, against the
plain reference.

After the window a sample of finished requests is drawn from the seed, the
longest among them.  The reference runs once, teacher-forced, over each
one's prompt and served tokens, and two numbers are compared:

* ``tok_gap``: the widest gap by which a served token's logit lies below
  the reference's best logit at its position; also its median, its 90th
  percentile over all the sample's tokens, and ``tok_gap_request_median``,
  the largest of the requests' own medians, which a fault confined to the
  slot of one sampled request moves;
* ``kv_err``: the cache rows the window wrote into the page pool, read
  back and decoded by the model's family, against the reference's rows:
  the relative error of each (position, group) row, its median over the
  sample's rows of one layer, of one part (K or V for a dense block), and
  of one writer (the prefill wrote the prompt's rows, decode steps the
  rest), and the worst of those medians.  A median, because with 4-bit
  codes a row a rounding flip has moved stays moved; per writer, so that a
  fault of either is not outvoted by the other's rows.  Only requests whose
  pages no later request was given can be read back.

Each number has a limit of its own, in the configuration file under
``check``.  What is model-specific (weights, the reference's forward pass,
reading rows back) the configuration's family does (``harness.arch``).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class Sample:
    prompt: np.ndarray
    tokens: np.ndarray
    rows: np.ndarray | None      # (L, parts, positions, groups, width)


def choose(done, n: int, seed: int, intact) -> list[int]:
    """Indices of the sample: the request with most served tokens, then
    requests drawn from the seed among those whose pages are intact, then
    any others, ``n`` in all."""
    rng = np.random.default_rng([seed, 0xC4EC])
    longest = max(range(len(done)),
                  key=lambda i: (len(done[i].tokens), len(done[i].prompt)))
    rest = [i for i in rng.permutation(len(done)) if i != longest]
    rest.sort(key=lambda i: not intact(done[i]))      # stable: intact first
    return [longest] + rest[: n - 1]


def samples(sv, done, traffic: dict, seed: int) -> list[Sample]:
    """The sample of a window's finished requests ``done``, with the cache
    rows of those that are intact read back from ``sv``'s engine."""
    from harness.serve import intact

    out = []
    for i in choose(done, int(traffic["check_requests"]), seed,
                    lambda d: intact(sv, d)):
        d = done[i]
        rows = None
        if intact(sv, d):
            rows = sv.family.served_rows(sv.engine, sv.pages[id(d.prompt)],
                                         len(d.prompt) + len(d.tokens) - 1,
                                         sv.config)
        out.append(Sample(d.prompt, d.tokens, rows))
    return out


def pad_len(traffic: dict) -> int:
    """One padded length for every request of a cell: one compile."""
    n = traffic["prompt"]["max"] + traffic["output"]["max"]
    return -(-n // 128) * 128


def row_errors(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Relative error of each (…, width) row."""
    num = np.linalg.norm(got - want, axis=-1)
    den = np.maximum(np.linalg.norm(want, axis=-1), 1e-12)
    return num / den


def measure(family, samples: list[Sample], seed: int, d, p, s_pad: int,
            against=None) -> dict:
    """Numbers of the sample under ``family``'s reference at precision
    ``p`` (``family.stated`` or ``family.control``), with shapes ``d``.

    With ``against`` unset, ``p`` is the reference and the sample's served
    tokens and cache rows are what is judged.  With ``against`` set (the
    control), ``p`` takes the program's place: its own greedy tokens and
    cache rows at the same positions are judged against the reference
    computed at ``against``.
    """
    w = family.make_weights(seed, d)
    gaps = []
    errs = {"prefill": [], "decode": []}   # (L, parts, rows) per request
    for s in samples:
        seq = np.concatenate([s.prompt, s.tokens]).astype(np.int32)
        n = len(seq)
        toks = np.zeros(s_pad, np.int32)
        toks[:n] = seq
        P = len(s.prompt)
        logits, ref_rows = family.forward(w, jnp.asarray(toks),
                                          jnp.int32(P), d=d, p=against or p)
        rows = slice(P - 1, n - 1)          # positions that chose a token
        if against is None:
            chosen = jnp.asarray(seq[1:n])[P - 1:]
            got = s.rows
        else:
            c_logits, c_rows = family.forward(w, jnp.asarray(toks),
                                              jnp.int32(P), d=d, p=p)
            chosen = jnp.argmax(c_logits[rows], axis=-1)
            got = np.asarray(c_rows[:, :, : n - 1])
        lg = logits[rows]
        gap = jnp.max(lg, axis=-1) - jnp.take_along_axis(
            lg, chosen[:, None], axis=-1)[:, 0]
        gaps.append(np.asarray(gap))
        if got is not None:
            want = np.asarray(ref_rows[:, :, : n - 1])
            e = row_errors(got, want)        # (L, parts, pos, groups)
            for phase, rows_ in (("prefill", slice(0, P)),
                                 ("decode", slice(P, None))):
                part = e[:, :, rows_]
                errs[phase].append(part.reshape(*part.shape[:2], -1))
    g = np.concatenate(gaps)
    out = {"tok_gap": float(g.max()), "tok_gap_median": float(np.median(g)),
           "tok_gap_p90": float(np.quantile(g, 0.9)),
           "tok_gap_request_median": float(max(np.median(x) for x in gaps)),
           "served_tokens": int(g.size)}
    worst, worst0, worst1 = [], [], []
    for phase, parts in errs.items():
        rows_ = np.concatenate(parts, axis=-1) if parts else None
        if rows_ is None or not rows_.shape[-1]:
            continue
        per = np.median(rows_, axis=-1)                  # (L, parts)
        worst.append(float(per.max()))
        worst0.append(float(per[0].max()))
        worst1.extend(float(x) for x in per[1:2].max(axis=1))
        out[f"kv_err_{phase}_by_layer"] = [float(x) for x in per.max(axis=1)]
        out[f"kv_rows_{phase}"] = int(rows_.shape[-1])
    if worst:
        out["kv_err"] = max(worst)
        out["kv_err_layer0"] = max(worst0)
    if worst1:
        out["kv_err_layer1"] = max(worst1)
    return out


def verdict(numbers: dict, limits: dict) -> tuple[bool, list]:
    """``(correct, [(name, number, limit), ...])``: every limited number at
    or under its limit; a number that could not be read fails."""
    rows = [(k, numbers.get(k), float(v)) for k, v in limits.items()]
    ok = all(n is not None and np.isfinite(n) and n <= lim
             for _, n, lim in rows)
    return ok, rows
