"""Scalar requests at fractions of each response's lam_max.

Parameters of a mix of this kind:

* ``clients`` -- closed-loop clients;
* ``lam_fracs`` -- the penalties, as fractions of lam_max in (0, 1);
* ``responses`` -- the kind and number of responses (``bench/responses``);
* ``assign`` -- how clients take them:

  * ``pool`` -- every client walks cycles over all (response, fraction)
    pairs, each cycle in its own seeded order, laid out so that every run
    of ``len(lam_fracs)`` consecutive requests holds each fraction once:
    whatever the seed, a window holds the same mix of work;
  * ``own`` -- client c always sends response c, the fractions in a
    seeded order (needs one response per client).

The warm-up covers exactly the programs the mix can reach: each
(response, fraction) pair alone, then (``own``) the coalesced batch sizes
the server can form from the clients, powers of two up to
min(clients, max_batch).
"""
from __future__ import annotations

import itertools
from typing import Iterator, List, Tuple

from bench import traffic


def validate(mix: dict) -> None:
    if mix["assign"] not in ("pool", "own"):
        raise ValueError(f"unknown assign {mix['assign']!r}")
    if mix["assign"] == "own" and \
            int(mix["responses"]["count"]) != int(mix["clients"]):
        raise ValueError("assign=own needs one response per client")
    if not mix["lam_fracs"] or not all(0 < f < 1 for f in mix["lam_fracs"]):
        raise ValueError("lam_fracs must lie in (0, 1)")


def _submission(ctx: traffic.Context, r: int, frac: float
                ) -> traffic.Submission:
    lam = frac * ctx.lam_max[r]
    return traffic.Submission(ctx.problems[r], ctx.api.Scalar(lam),
                              [(r, lam)])


def _pool_cycle(rng, n_resp: int, n_frac: int) -> List[Tuple[int, int]]:
    """All n_resp x n_frac pairs once; block b holds every fraction once,
    with response sigma_f(b) for fraction f."""
    sigma = [rng.permutation(n_resp) for _ in range(n_frac)]
    out = []
    for b in range(n_resp):
        for f in rng.permutation(n_frac):
            out.append((int(sigma[f][b]), int(f)))
    return out


def pairs(mix: dict, seed: int, c: int) -> Iterator[Tuple[int, float]]:
    """Client ``c``'s (response index, fraction) sequence."""
    fracs = [float(f) for f in mix["lam_fracs"]]
    rng = traffic.rng(seed, 1 + c)
    if mix["assign"] == "pool":
        n_resp = int(mix["responses"]["count"])
        while True:
            for r, f in _pool_cycle(rng, n_resp, len(fracs)):
                yield r, fracs[f]
    else:
        while True:
            for f in rng.permutation(len(fracs)):
                yield c, fracs[int(f)]


def client(mix: dict, ctx: traffic.Context, seed: int, c: int
           ) -> Iterator[traffic.Submission]:
    for r, f in pairs(mix, seed, c):
        yield _submission(ctx, r, f)


def warmup_pairs(mix: dict, max_batch: int) -> List[List[Tuple[int, float]]]:
    fracs = [float(f) for f in mix["lam_fracs"]]
    n_resp = int(mix["responses"]["count"])
    out = [[(r, f)] for r, f in itertools.product(range(n_resp), fracs)]
    if mix["assign"] == "own":
        top = min(int(mix["clients"]), int(max_batch))
        b = 2
        while b // 2 < top:          # a batch of b_real pads to next pow2
            out += [[(c, f) for c in range(min(b, top))] for f in fracs]
            b *= 2
    return out


def warmup(mix: dict, ctx: traffic.Context
           ) -> List[List[traffic.Submission]]:
    return [[_submission(ctx, r, f) for r, f in batch]
            for batch in warmup_pairs(mix, ctx.max_batch)]


def answers(result) -> list:
    return [(result.value.beta, result.verdict)]
