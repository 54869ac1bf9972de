"""One seeded generator for every traffic mix; a mix is a JSON file beside it.

A mix file names an arrival process and the lengths to draw (all keys are
optional unless stated):

* ``arrivals`` (required): ``{"process": "poisson", "rate_per_s": r, "of":
  "requests" | "sessions"}``, ``{"process": "pareto_burst", "bursts_per_s":
  r, "burst_max": k, "tail": a}`` (clusters of 1..k requests due together,
  Pareto gaps between clusters), or ``{"process": "backlog", "requests":
  n}`` (all due when the window opens);
* ``lead_in_s``: the same traffic runs this long before the window, during
  set-up, so the window opens on a warm cache and a steady batch;
* ``lead_in_step_s`` (default 1): the lead-in's clock advances this much
  per engine step, so that every run reaches the window in the same state
  (``driver.Driver.lead_in``);
* ``tenants``: ``{"prompt_tokens": [...], "zipf_s": s}``, a system prompt per
  tenant, drawn with Zipf(s) popularity by rank;
* ``documents``: ``{"count": n, "tokens_lo": a, "tokens_hi": b}``, shared
  documents prefilled in set-up; each request asks about one of them;
* ``sessions``: ``{"turns_mean": m, "turns_max": k, "think_mean_s": t}``:
  multi-turn chat, geometric turn counts, turn j due an exponential think
  time after turn j-1's due time. Turn j's prompt is the system prompt, the
  session's earlier messages each followed by a synthetic answer of that
  turn's ``max_new`` tokens, then the new message;
* ``message_tokens`` / ``new_tokens``: lognormal ``{"median", "sigma", "lo",
  "hi"}`` lengths of each new message and of each answer (``max_new``);
* ``max_context``: prompt plus ``max_new`` never exceeds it (a session ends
  before the turn that would).

Every seed gets the same schedule of sizes and due times: each quantity is
drawn at the stratified quantiles (i + 1/2)/n of its distribution and
ordered and paired by a fixed draw, the same for every seed. The seed draws
every token id (system prompts, documents, messages, synthetic answers), so
seeds differ in content and not in the amount of work, and the spread of a
metric over seeds is the system's, not the traffic's. Times are wall-clock
seconds from the window's start; lead-in requests are due at negative
times.

Copied from ``benchmarks/bench_serving.py`` (``make_trace``'s Poisson gaps
and ``make_bursty_trace``'s Pareto bursts), with their tick clock replaced
by seconds.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()


@dataclass
class Req:
    due_s: float
    prompt: np.ndarray          # int32 token ids
    max_new: int
    tag: str = ""               # "tenant3/u17/t2": tenant, session (unit), turn


@dataclass
class Schedule:
    requests: list[Req]                                 # sorted by due time
    setup_prompts: list[np.ndarray] = field(default_factory=list)  # run to completion in set-up
    lead_in_s: float = 0.0


def _strata(n: int, ppf, rng) -> np.ndarray:
    """``ppf`` at the n stratified quantiles, in an order drawn by ``rng``."""
    if n <= 0:
        return np.zeros((0,))
    u = (np.arange(n) + 0.5) / n
    return rng.permutation(np.array([ppf(x) for x in u], np.float64))


def _lognormal_ppf(spec: dict):
    def ppf(u):
        x = spec["median"] * math.exp(spec["sigma"] * _NORMAL.inv_cdf(u))
        return float(min(max(round(x), spec["lo"]), spec["hi"]))
    return ppf


def _exp_ppf(mean: float):
    return lambda u: -mean * math.log1p(-u)


def _geometric_ppf(mean: float, cap: int):
    p = 1.0 / mean
    return lambda u: float(min(max(math.ceil(math.log1p(-u) / math.log1p(-p)), 1), cap))


def _pareto_ppf(tail: float):
    return lambda u: (1.0 - u) ** (-1.0 / tail) - 1.0


def _apportion(weights: np.ndarray, n: int) -> np.ndarray:
    """Integer counts summing to ``n`` in proportion to ``weights``
    (largest remainder)."""
    share = weights / weights.sum() * n
    counts = np.floor(share).astype(int)
    rest = n - counts.sum()
    counts[np.argsort(-(share - counts), kind="stable")[:rest]] += 1
    return counts


def _arrival_times(arr: dict, start: float, end: float, rng) -> np.ndarray:
    """Due times in [start, end) of the arrival units (requests or sessions)."""
    horizon = end - start
    proc = arr["process"]
    if proc == "poisson":
        n = int(round(arr["rate_per_s"] * horizon))
        gaps = _strata(n, _exp_ppf(1.0 / arr["rate_per_s"]), rng)
        t = start + np.cumsum(gaps) - gaps[0] if n else gaps
        return t[t < end]
    if proc == "pareto_burst":
        n = int(round(arr["bursts_per_s"] * horizon))
        # Pareto(tail) gaps rescaled so that their mean is 1 / bursts_per_s
        raw = _strata(n, _pareto_ppf(arr["tail"]), rng)
        gaps = raw / max(raw.mean(), 1e-12) / arr["bursts_per_s"]
        sizes = _strata(n, lambda u: float(min(int(u * arr["burst_max"]) + 1, arr["burst_max"])), rng)
        t = start + np.cumsum(gaps) - gaps[0]
        times = np.repeat(t, sizes.astype(int))
        return times[times < end]
    if proc == "backlog":
        return np.full((arr["requests"],), start)
    raise ValueError(f"unknown arrival process {proc!r}")


def generate(mix: dict, *, seed: int, seconds: float, vocab: int) -> Schedule:
    """The schedule of one run: requests due from ``-lead_in_s`` to
    ``seconds``, and the prompts set-up runs to completion first."""
    rng = np.random.default_rng([seed, 0x7AFF1C])      # token ids
    shape = np.random.default_rng(0x5A17)               # times and sizes: the same for every seed
    lead = float(mix.get("lead_in_s", 0.0))

    def tokens(n: int) -> np.ndarray:
        return rng.integers(0, vocab, int(n), dtype=np.int64).astype(np.int32)

    units = _arrival_times(mix["arrivals"], -lead, float(seconds), shape)
    n = len(units)
    msg_ppf = _lognormal_ppf(mix["message_tokens"])
    new_ppf = _lognormal_ppf(mix["new_tokens"])
    max_ctx = int(mix["max_context"])

    prefixes: list[np.ndarray] = []   # per unit: the shared prefix it starts from
    tags: list[str] = []
    setup: list[np.ndarray] = []
    if "tenants" in mix:
        t = mix["tenants"]
        lens = t["prompt_tokens"]
        system = [tokens(k) for k in lens]
        ranks = np.arange(1, len(lens) + 1, dtype=np.float64)
        counts = _apportion(ranks ** -t["zipf_s"], n)
        who = shape.permutation(np.repeat(np.arange(len(lens)), counts))
        prefixes = [system[i] for i in who]
        tags = [f"tenant{i}" for i in who]
    elif "documents" in mix:
        d = mix["documents"]
        lens = np.linspace(d["tokens_lo"], d["tokens_hi"], d["count"]).round().astype(int)
        docs = [tokens(k) for k in lens]
        setup = list(docs)
        who = shape.permutation(np.repeat(np.arange(d["count"]), -(-n // d["count"]))[:n])
        prefixes = [docs[i] for i in who]
        tags = [f"doc{i}" for i in who]
    else:
        prefixes = [np.zeros((0,), np.int32)] * n
        tags = [""] * n

    sess = mix.get("sessions")
    if sess:
        turns = _strata(n, _geometric_ppf(sess["turns_mean"], sess["turns_max"]), shape).astype(int)
    else:
        turns = np.ones((n,), int)
    total = int(turns.sum())
    msgs = _strata(total, msg_ppf, shape).astype(int)
    news = _strata(total, new_ppf, shape).astype(int)
    thinks = (_strata(total - n, _exp_ppf(sess["think_mean_s"]), shape) if sess
              else np.zeros((0,)))

    reqs: list[Req] = []
    first = np.concatenate([[0], np.cumsum(turns)[:-1]]).astype(int)  # unit u's first turn
    for u in range(n):
        due = float(units[u])
        context = prefixes[u]
        for j in range(turns[u]):
            k = first[u] + j
            if j:
                due += float(thinks[k - u - 1])  # unit u has turns[u] - 1 think times
            new = int(news[k])
            prompt = np.concatenate([context, tokens(msgs[k])])
            if len(prompt) + new > max_ctx or due >= seconds:
                break  # the session ends here; later turns are never sent
            reqs.append(Req(due, prompt, new, f"{tags[u]}/u{u}/t{j}".strip("/")))
            # the next turn carries this turn's message and a synthetic answer
            context = np.concatenate([prompt, tokens(new)])
    reqs.sort(key=lambda r: r.due_s)
    return Schedule(reqs, setup, lead)
