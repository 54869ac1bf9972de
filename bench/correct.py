"""Whether what the timed path served is right, by the plain reference.

After the window, a sample of the requests that finished in it is drawn
from the seed, always with the longest of them (prompt plus served tokens),
until it holds the configuration's ``check.sample_tokens`` served tokens.
For each, the reference runs once over the prompt followed by the served
tokens, and at every served position reads how far the served token's
logit lies below the reference's best logit there (the gap). Decoding is
greedy, so a correct server picks the reference's best up to rounding.
The first served token comes from the last prefill chunk (through the
prefix cache where the prompt shares one) and the rest from cached decode,
so the sample covers chunked prefix-cached prefill, decode and sampling at
the sizes the window ran.

Over the sample's gaps three numbers are read: the mean gap, the widest
gap, and the share of positions off the reference's best. Those that the
configuration's ``check`` gives a limit are compared; the others are
printed. Besides, every request that finished in the window must have been
given its whole ``max_new`` (no EOS is set) and only tokens of the
vocabulary, and the sample must hold ``sample_tokens`` served tokens.

With ``control=True`` the reference is also run in float8 (``precision=
"fp8"``) at the same positions, and the gap of the token it puts first is
read the same way: the control, put in the program's place. Its numbers are
then the ones compared, so the verdict says whether the control passes;
a limit must fail it. The program's numbers from the same run are printed
beside them.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

import latency

NUMBERS = ("mean_gap", "widest_gap", "off_best_share")


@dataclass
class Reading:
    """Per served position of the sample: the reference's best logit less
    its logit of the token this side put there."""

    gaps: list = field(default_factory=list)

    def numbers(self) -> dict[str, float | None]:
        g = np.asarray(self.gaps, np.float64)
        if not len(g):
            return dict.fromkeys(NUMBERS)
        return {"mean_gap": float(g.mean()), "widest_gap": float(g.max()),
                "off_best_share": float((g > 0).mean())}


@dataclass
class Verdict:
    limits: dict                         # number -> limit, for the numbers compared
    sample_tokens: int                   # the sample must hold at least this many
    program: Reading = field(default_factory=Reading)
    control: Reading | None = None       # set in control mode, and then judged
    sampled: int = 0
    sampled_tokens: int = 0
    finished: int = 0
    short: int = 0                       # finished with fewer tokens than max_new
    out_of_vocab: int = 0
    per_request: list = field(default_factory=list)

    @property
    def judged(self) -> Reading:
        return self.program if self.control is None else self.control

    @property
    def ok(self) -> bool:
        return all(c["value"] is not None and (c["value"] >= c["limit"] if k == "sampled_tokens"
                                               else c["value"] <= c["limit"])
                   for k, c in self.checks().items())

    def checks(self) -> dict:
        """Each number compared beside its limit; ``sampled_tokens`` is a
        floor, the others ceilings."""
        got = self.judged.numbers()
        out = {k: {"value": got[k], "limit": v} for k, v in self.limits.items()}
        out["short_requests"] = {"value": self.short, "limit": 0}
        out["out_of_vocab"] = {"value": self.out_of_vocab, "limit": 0}
        out["sampled_tokens"] = {"value": self.sampled_tokens, "limit": self.sample_tokens}
        return out

    def lines(self) -> list[str]:
        out = [f"sample: {self.sampled} of {self.finished} finished requests, "
               f"{self.sampled_tokens} served tokens; per request (prompt, served, widest gap): "
               f"{self.per_request}"]
        out.append("program: " + _summary(self.program))
        if self.control is not None:
            out.append("control (float8 reference in the program's place, judged): "
                       + _summary(self.control))
        for k, c in self.checks().items():
            rel = "at least" if k == "sampled_tokens" else "limit"
            out.append(f"check {k}: {c['value']!r} {rel} {c['limit']!r}")
        out.append(f"correct: {self.ok}")
        return out


def _summary(r: Reading) -> str:
    return " ".join(f"{k} {v!r}" for k, v in r.numbers().items()) + f" over {len(r.gaps)}"


def finished_in_window(run) -> list:
    return [r for r in run.records if r.tokens is not None and r.done_s is not None
            and 0.0 <= r.done_s <= run.close_s]


def pick(run, seed: int, tokens: int) -> list:
    """The longest finished request, then others in an order drawn from the
    seed, until the sample holds ``tokens`` served tokens."""
    done = finished_in_window(run)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.tokens), r.index))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 0xC4EC])
    chosen, n = [longest], len(longest.tokens)
    for i in rng.permutation(len(rest)):
        if n >= tokens:
            break
        chosen.append(rest[i])
        n += len(rest[i].tokens)
    return chosen


def gaps(ref_logits: np.ndarray, tokens) -> np.ndarray:
    """Per row: the reference's best logit less its logit of ``tokens``."""
    tokens = np.asarray(tokens)
    return ref_logits.max(axis=1) - ref_logits[np.arange(len(tokens)), tokens]


def check(run, ref, weights, conf: dict, *, seed: int, control: bool = False) -> Verdict:
    lim = conf["check"]
    v = Verdict(limits={k: float(lim[k]) for k in NUMBERS if k in lim},
                sample_tokens=int(lim["sample_tokens"]))
    if control:
        v.control = Reading()
    vocab = conf["vocab_size"]
    done = finished_in_window(run)
    v.finished = len(done)
    v.short = sum(len(r.tokens) != r.max_new for r in done)
    v.out_of_vocab = sum(sum(not 0 <= t < vocab for t in r.tokens) for r in done)
    for r in pick(run, seed, v.sample_tokens):
        toks = r.tokens
        if any(not 0 <= t < vocab for t in toks):
            continue
        seq = np.concatenate([r.prompt, np.asarray(toks[:-1], np.int32)])
        rows = np.arange(len(r.prompt) - 1, len(r.prompt) - 1 + len(toks))
        ref_l = ref.logits(weights, conf, seq, rows)
        g = gaps(ref_l, toks)
        v.program.gaps += g.tolist()
        v.per_request.append((len(r.prompt), len(toks), float(g.max())))
        v.sampled += 1
        v.sampled_tokens += len(toks)
        if control:
            low = ref.logits(weights, conf, seq, rows, precision="fp8")
            v.control.gaps += gaps(ref_l, low.argmax(axis=1)).tolist()
    return v


def describe(run) -> list[str]:
    """Medians, tails and sample counts, for the lines before the last."""
    t, p = latency.ttft_s(run), latency.tpot_s(run)
    late = sum(1 for r in run.window_records
               if r.error is None and (r.first_s is None or r.first_s > run.close_s))

    def ms(x):
        return None if x is None else x * 1e3

    return [
        f"ttft_ms: p50 {ms(latency.quantile(t, 0.5))!r} p90 {ms(latency.quantile(t, 0.9))!r} "
        f"max {ms(max(t, default=None))!r} over {len(t)} requests "
        f"({late} without a first token by the close, counted at their elapsed time)",
        f"tpot_ms: p50 {ms(latency.quantile(p, 0.5))!r} p90 {ms(latency.quantile(p, 0.9))!r} "
        f"over {len(p)} requests",
        f"output tokens in window: {latency.output_tokens(run)} over {run.close_s!r} s; "
        f"{len(run.window_steps())} step_chunk calls",
    ]
