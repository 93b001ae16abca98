"""Stratified traffic generator: the same multiset of work for every seed.

A traffic file (benchmarks/traffic/<name>.json) gives, for a serving cell,
the loop kind, the client count or rate, and the length distributions WITH
their stratification: each distribution is cut into `cycle`
equal-probability strata, each stratum contributes its midpoint quantile, and
the cycle repeats. So two runs with different seeds offer the same multiset
of (prompt length, output length) pairs — and, in an open loop, the same
multiset of arrival gaps — in the same order: the order inside each cycle is
a fixed shuffle of the cycle's index, so every seed replays one schedule and
`--seed` draws only the token ids, the weights and the sampling keys. Measured
on the chip (PERF.md, PR 23), an order shuffled by the seed moved a closed
loop's median time to first token by 25 % and its throughput by 3.7 % between
seeds while two runs of one seed agreed within 1 %: which requests share a
scheduling round is decided by the order, so the order is part of the load.

JAX-free (numpy + stdlib); the serving driver in run.py consumes `Request`s.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
import typing as tp

import numpy as np


SCHEDULE = 20260927  # salt of the one order every seed replays


def quantile(dist: dict, p: float) -> float:
    """Inverse CDF of a traffic-file distribution at probability p."""
    kind = dist["dist"]
    if kind == "fixed":
        return float(dist["value"])
    if kind == "uniform":
        return dist["lo"] + p * (dist["hi"] - dist["lo"])
    if kind == "lognormal":
        z = statistics.NormalDist().inv_cdf(p)
        x = dist["median"] * math.exp(dist["sigma"] * z)
        return min(max(x, dist["lo"]), dist["hi"])
    if kind == "exponential":  # mean-1 gaps, scaled by the caller's rate
        return -math.log1p(-p)
    raise ValueError(f"unknown distribution kind {kind!r} in traffic file")


def strata(dist: dict, n: int) -> tp.List[float]:
    """Midpoint quantile of each of n equal-probability strata."""
    return [quantile(dist, (i + 0.5) / n) for i in range(n)]


def int_strata(dist: dict, n: int, multiple: int = 1) -> tp.List[int]:
    out = []
    for x in strata(dist, n):
        v = int(round(x / multiple)) * multiple
        out.append(max(multiple, v))
    return out


@dataclasses.dataclass
class Request:
    index: int  # position in the issued sequence
    prompt: np.ndarray  # (P,) int32 token ids drawn from --seed
    max_new_tokens: int
    due_s: tp.Optional[float] = None  # open loop: offset from loop start


class Traffic:
    """One serving traffic mix, resolved from its file and a seed."""

    def __init__(self, spec: dict, seed: int, vocab_size: int):
        self.spec = spec
        self.loop = spec["loop"]
        if self.loop not in ("closed", "open"):
            raise ValueError(f"traffic loop must be 'closed' or 'open', got {self.loop!r}")
        self.cycle = int(spec["cycle"])
        self.seed = seed
        self.vocab_size = vocab_size
        n = self.cycle
        self.prompt_lens = int_strata(spec["prompt_len"], n)
        outs = int_strata(spec["output_len"], n, int(spec.get("output_multiple", 1)))
        # Pair prompt stratum i with output stratum (i * stride) mod n: a
        # fixed, seed-free scramble, so the joint multiset never changes and
        # long prompts are not systematically paired with long outputs.
        stride = next(s for s in range(max(2, int(n * 0.382)), 2 * n + 3) if math.gcd(s, n) == 1)
        self.output_lens = [outs[(i * stride) % n] for i in range(n)]
        cap = int(spec["max_total"])
        for p, o in zip(self.prompt_lens, self.output_lens):
            if p + o > cap:
                raise ValueError(f"traffic pair prompt {p} + output {o} exceeds max_total {cap}")
        self.clients = int(spec.get("clients", 0))
        self.rate = float(spec.get("rate_per_s", 0.0))
        if self.loop == "closed" and self.clients < 1:
            raise ValueError("closed loop needs clients >= 1")
        if self.loop == "open" and self.rate <= 0:
            raise ValueError("open loop needs rate_per_s > 0")
        self.gaps = (
            [g / self.rate for g in strata({"dist": "exponential"}, n)]
            if self.loop == "open" else None
        )
        self._issued = 0
        self._due = 0.0
        self._order: tp.List[int] = []
        self._gap_order: tp.List[int] = []

    # -- the fixed multiset ------------------------------------------------

    def multiset(self) -> tp.List[tp.Tuple[int, int]]:
        """Sorted (prompt, output) pairs of one cycle — identical for every seed."""
        return sorted(zip(self.prompt_lens, self.output_lens))

    def describe(self) -> dict:
        def q(xs):
            s = sorted(xs)
            return {"min": s[0], "p50": s[len(s) // 2], "p90": s[int(0.9 * (len(s) - 1))], "max": s[-1]}

        d = {
            "loop": self.loop, "cycle": self.cycle,
            "prompt_len": q(self.prompt_lens), "output_len": q(self.output_lens),
            "cycle_prompt_tokens": sum(self.prompt_lens),
            "cycle_output_tokens": sum(self.output_lens),
        }
        if self.loop == "closed":
            d["clients"] = self.clients
        else:
            d["rate_per_s"] = self.rate
            d["gap_ms"] = {k: round(1e3 * v, 3) for k, v in q(self.gaps).items()}
        return d

    # -- the one schedule, and the seeded token ids ---------------------------

    def _rng(self, *salt: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, *salt])

    @staticmethod
    def _shuffle(*salt: int) -> np.random.Generator:
        return np.random.default_rng([SCHEDULE, *salt])

    def next(self, output_scale: float = 1.0) -> Request:
        """Next request of the sequence. `output_scale` < 1 is used only for
        the staggering requests issued before the window (see prime())."""
        i = self._issued
        c, k = divmod(i, self.cycle)
        if k == 0:
            self._order = list(self._shuffle(1, c).permutation(self.cycle))
            if self.gaps is not None:
                self._gap_order = list(self._shuffle(2, c).permutation(self.cycle))
        j = self._order[k]
        p_len, out = self.prompt_lens[j], self.output_lens[j]
        if output_scale != 1.0:
            out = max(1, int(round(out * output_scale)))
        prompt = self._rng(3, i).integers(0, self.vocab_size, p_len, dtype=np.int32)
        due = None
        if self.gaps is not None:
            self._due += self.gaps[self._gap_order[k]]
            due = self._due
        self._issued += 1
        return Request(i, prompt, out, due)

    def prime(self) -> tp.List[Request]:
        """Closed loop: one staggering request per client, issued during
        set-up. Client i's output is scaled by (i+1)/clients, so the clients
        leave the ramp at evenly spread phases instead of in lockstep. The
        scaling is seed-free; inside the window every request is unscaled.
        `"stagger": false` in the traffic file issues them unscaled: the
        clients then start together, as one batch of samples does."""
        if not self.spec.get("stagger", True):
            return [self.next() for _ in range(self.clients)]
        return [self.next((i + 1) / self.clients) for i in range(self.clients)]
