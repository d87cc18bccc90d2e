"""The one traffic generator: every mix is a data file it reads.

A mix file (``mixes/<traffic>.json``) holds parameters only:

``mode``
    ``"backlog"``: a queue of ``queued_per_slot`` x slots requests is kept
    waiting; each answered request is replaced by the next one.
    ``"open"``: Poisson arrivals at ``rate_per_s``, sent on schedule
    whether or not earlier requests were answered.
``lengths``
    a mixture, each component ``{"share", "dist", "lo", "hi"}`` with
    ``dist`` ``"uniform"`` (integers lo..hi) or ``"pareto"`` (Pareto of
    shape ``alpha`` truncated to lo..hi, rounded: P(length > x) falls as
    x^-alpha).  A length is the number of tokens in the stream; one
    token is one value on every input arc.
``tenants``
    ``{"count", "zipf_s"}``: each request's tenant is drawn Zipf(s) over
    ``count`` tenants, so the server's fair queue sees a few heavy
    clients and many light ones.
``pool``
    requests drawn per run; a run that needs more reuses them in order,
    under fresh uids.
``origin``
    where each parameter comes from, in words; the generator ignores it.

Token values are uniform over the whole int32 range.  Everything is
drawn from ``--seed``: the same seed gives the same requests, lengths,
tenants, values and arrival times.  Lengths, tenants and the gaps
between arrivals are the same multiset for every seed, in another
order, so that a seed changes which request comes when, not how much
work a run holds.
"""
from __future__ import annotations

import dataclasses

import numpy as np

INT32 = np.iinfo(np.int32)


@dataclasses.dataclass
class Traffic:
    mode: str
    lengths: np.ndarray       # [pool] tokens per request
    tenants: np.ndarray       # [pool] tenant ids
    offsets: np.ndarray       # [pool + 1] start of each request in values
    values: np.ndarray        # [n_in, total tokens] int32
    arrivals: np.ndarray      # open: due times (s from window start)
    queued: int = 0           # backlog: requests kept waiting

    def feeds(self, i: int, input_arcs) -> dict:
        """Arc -> stream of pool request ``i`` (views, no copies)."""
        lo, hi = self.offsets[i], self.offsets[i + 1]
        return {a: self.values[k, lo:hi] for k, a in enumerate(input_arcs)}

    @property
    def max_len(self) -> int:
        return int(self.lengths.max())


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per purpose; any whole-number seed."""
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def grid(n: int) -> np.ndarray:
    """n evenly spaced quantile levels in (0, 1)."""
    return (np.arange(n) + 0.5) / n


def split(shares, n: int) -> np.ndarray:
    """``n`` items over components in proportion to ``shares``, by
    largest remainder: the same counts for every seed."""
    share = np.asarray(shares, float) / np.sum(shares)
    counts = np.floor(share * n).astype(np.int64)
    rest = np.argsort(-(share * n - counts), kind="stable")
    counts[rest[:n - counts.sum()]] += 1
    return counts


def draw_lengths(mix: dict, n: int, rng) -> np.ndarray:
    """The same multiset of ``n`` lengths for every seed, in the seed's
    order: each component takes its share of the requests, at evenly
    spaced quantiles of its distribution."""
    comps = mix["lengths"]
    parts = []
    for c, k in zip(comps, split([c["share"] for c in comps], n)):
        lo, hi, u = int(c["lo"]), int(c["hi"]), grid(int(k))
        if c["dist"] == "uniform":
            parts.append(lo + np.floor(u * (hi - lo + 1)))
        elif c["dist"] == "pareto":
            a = float(c["alpha"])
            top, bot = float(lo) ** -a, float(hi) ** -a
            parts.append(np.clip(np.rint(
                (top - u * (top - bot)) ** (-1.0 / a)), lo, hi))
        else:
            raise ValueError(f"unknown length distribution {c['dist']!r}")
    return rng.permutation(np.concatenate(parts).astype(np.int64))


def draw_tenants(mix: dict, n: int, rng) -> np.ndarray:
    """Zipf(s) tenant ids over ``count`` tenants: the same number of
    requests per tenant for every seed, in the seed's order."""
    t = mix["tenants"]
    w = 1.0 / np.arange(1, int(t["count"]) + 1) ** float(t["zipf_s"])
    return rng.permutation(np.repeat(np.arange(len(w)), split(w, n)))


def poisson_arrivals(rate: float, seconds: float, rng) -> np.ndarray:
    """round(rate x seconds) due times in [0, seconds): a Poisson
    process held to its expected count, its gaps the evenly spaced
    quantiles of the exponential distribution in the seed's order."""
    n = int(round(rate * seconds))
    gaps = rng.permutation(-np.log1p(-grid(n + 1)))
    t = np.cumsum(gaps)
    return t[:n] * (seconds / t[-1])


def generate(mix: dict, n_in: int, slots: int, seed: int,
             seconds: float) -> Traffic:
    pool = int(mix["pool"])
    lengths = draw_lengths(mix, pool, rng_for(seed, 1))
    tenants = draw_tenants(mix, pool, rng_for(seed, 2))
    offsets = np.concatenate([[0], np.cumsum(lengths)])
    values = rng_for(seed, 3).integers(INT32.min, INT32.max, (n_in, int(
        offsets[-1])), dtype=np.int32, endpoint=True)
    mode = mix["mode"]
    if mode == "backlog":
        return Traffic(mode, lengths, tenants, offsets, values,
                       np.zeros(0), queued=int(mix["queued_per_slot"])
                       * slots)
    if mode == "open":
        return Traffic(mode, lengths, tenants, offsets, values,
                       poisson_arrivals(float(mix["rate_per_s"]), seconds,
                                        rng_for(seed, 4)))
    raise ValueError(f"unknown traffic mode {mode!r}")
