"""The one traffic generator: reads a mix file, returns an open-loop
arrival schedule.

A mix (``bench/traffic/<mix>.json``) has a total ``rate_per_s`` and a list
of ``streams``, each with:

    class          "hp" or "lp"
    share          its part of the total rate, by count
    arrivals       "poisson"; "periodic" (one every 1/rate seconds from a
                   phase drawn from the seed: the paper's video frames,
                   each spawning its LP job); "stratified" (one request
                   in each of N equal bins of the window, at an offset
                   inside its bin: see ``_stratified``; ``cycle`` bins
                   make one cycle, default 1); or "burst" (Poisson burst
                   starts, each burst ``burst_size`` requests spread
                   evenly over ``burst_spread_s`` seconds)
    prompt_lens    prompt lengths, drawn in equal numbers
    new_tokens     tokens to generate
    deadline_s     the relative deadline in seconds from the due time: a
                   fixed number of the mix, the same in every run and for
                   every version of the program (HP: to the first token;
                   LP: to the last)

Every seed gets the same work.  A stream of rate r over a window of T
seconds sends round(r * T) requests (or bursts); their gaps are the
exponential distribution's quantiles at the midpoints of N equal bins,
scaled to fill the window, so their multiset is fixed and only the order
of the gaps and of the prompt lengths comes from the seed.  Seeds then
differ in when requests collide, not in how much work arrives.  A
stratified stream whose cycle is a periodic stream's period goes further:
every seed puts the same requests at the same phases of that period, so
seeds differ only in the order of the periods' contents.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

CLASSES = ("hp", "lp")


@dataclass(frozen=True)
class Arrival:
    t: float                 # due time, seconds from the window's start
    cls: str                 # "hp" | "lp"
    prompt_len: int
    new_tokens: int
    stream: int              # index into the mix's streams


def _starts(n: int, span: float, rng: np.random.Generator) -> np.ndarray:
    """n Poisson-like start times in [0, span): the exponential quantiles
    at the midpoints of n equal bins, in the seed's order, scaled so that
    their sum is ``span``.  The first start is at 0."""
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q))
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return t * (span / gaps.sum())


def _stratified(n: int, cycle: int, span: float,
                rng: np.random.Generator) -> np.ndarray:
    """n start times in [0, span), one in each of n equal bins.  Bin i is
    slot i % cycle of its cycle; the m bins of one slot take the offsets
    (r + 0.5) / m inside their bins, r = 0..m-1 in the seed's order.  So
    the phases within a cycle are the same evenly spaced set for every
    seed: where a cycle lasts one period of a periodic stream, every seed
    puts the same offsets against that stream's arrivals, only dealt to
    other periods."""
    off = np.empty(n)
    for j in range(min(cycle, n)):
        m = len(range(j, n, cycle))
        off[j::cycle] = (rng.permutation(m) + 0.5) / m
    return (np.arange(n) + off) * (span / n)


def stream_times(stream: dict, rate: float, seconds: float,
                 rng: np.random.Generator) -> np.ndarray:
    """Due times in [0, seconds) of one stream at ``rate`` requests/s."""
    kind = stream["arrivals"]
    if kind == "poisson":
        n = int(round(rate * seconds))
        return _starts(n, seconds, rng) if n else np.zeros(0)
    if kind == "periodic":
        n = int(round(rate * seconds))
        period = seconds / n if n else 0.0
        return (rng.uniform(0.0, period) + period * np.arange(n)
                if n else np.zeros(0))
    if kind == "stratified":
        n = int(round(rate * seconds))
        return (_stratified(n, int(stream.get("cycle", 1)), seconds, rng)
                if n else np.zeros(0))
    if kind == "burst":
        size = int(stream["burst_size"])
        spread = float(stream["burst_spread_s"])
        n_b = int(round(rate * seconds / size))
        if n_b == 0:
            return np.zeros(0)
        starts = _starts(n_b, seconds - spread, rng)
        offs = np.linspace(0.0, spread, size)
        return (starts[:, None] + offs[None, :]).ravel()
    raise ValueError(f"unknown arrival process {kind!r}")


def validate(mix: dict) -> None:
    if not mix.get("rate_per_s", 0) > 0:
        raise ValueError("mix needs a positive rate_per_s")
    streams = mix.get("streams") or []
    if not streams:
        raise ValueError("mix needs at least one stream")
    total = sum(float(s["share"]) for s in streams)
    if abs(total - 1.0) > 1e-6:
        raise ValueError(f"stream shares add up to {total}, not 1")
    for s in streams:
        if s["class"] not in CLASSES:
            raise ValueError(f"stream class {s['class']!r} not in {CLASSES}")
        if not s["prompt_lens"] or min(s["prompt_lens"]) < 1:
            raise ValueError("prompt_lens must be positive")
        if int(s["new_tokens"]) < 1:
            raise ValueError("new_tokens must be positive")
        if not float(s["deadline_s"]) > 0:
            raise ValueError("deadline_s must be positive")
        if int(s.get("cycle", 1)) < 1:
            raise ValueError("cycle must be positive")


def generate(mix: dict, seconds: float, seed: int,
             rate_per_s: float | None = None) -> list[Arrival]:
    """The window's arrivals, sorted by due time.  ``rate_per_s``
    overrides the mix's fixed total rate (the knee sweep uses it)."""
    validate(mix)
    rate = float(mix["rate_per_s"] if rate_per_s is None else rate_per_s)
    rng = np.random.default_rng(seed)
    out: list[Arrival] = []
    for i, s in enumerate(mix["streams"]):
        t = stream_times(s, rate * float(s["share"]), seconds, rng)
        lens = rng.permutation(np.resize(np.asarray(s["prompt_lens"]), t.size))
        out += [Arrival(float(ti), s["class"], int(li),
                        int(s["new_tokens"]), i) for ti, li in zip(t, lens)]
    out.sort(key=lambda a: (a.t, a.stream))
    return out


def shapes(mix: dict) -> set[tuple[str, int, int]]:
    """Every (class, prompt length, new tokens) the mix can send: what
    warm-up has to compile."""
    validate(mix)
    return {(s["class"], int(p), int(s["new_tokens"]))
            for s in mix["streams"] for p in s["prompt_lens"]}


def relative_deadline(stream: dict) -> float:
    return float(stream["deadline_s"])
