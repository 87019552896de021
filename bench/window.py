"""What a metric reader sees of one run's measured window."""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


def percentile(xs, q: float) -> float | None:
    """The ``q``-th percentile of every sample (linear interpolation)."""
    return float(np.percentile(np.asarray(xs, float), q)) if len(xs) else None


@dataclass
class Window:
    """``t0``/``t1``: the window on the driver's clock (seconds)."""

    t0: float
    t1: float
    setup_s: float
    cfg: dict                    # the configuration file
    turns: dict                  # (pid, turn) -> bench.driver.TurnState
    counters0: dict              # router counters at the window's start
    counters1: dict              # and at its end
    decode_steps: list           # (start, end, rows, sum of context)
    chunks: list                 # (start, end, tokens, context end)
    admitted: list               # times turns entered an engine slot
    peaks: dict = field(default_factory=dict)
    reduced: object = None       # bench.reduce.Reduced in a traced run

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def delta(self, name: str) -> float:
        return self.counters1[name] - self.counters0[name]

    def inside(self, t: float) -> bool:
        return self.t0 <= t <= self.t1

    def ttft_samples(self) -> list[float]:
        """Due time to first token, for every turn whose first token lands
        in the window, and the age at the window's end of every turn still
        waiting for its first token then."""
        out = []
        for ts in self.turns.values():
            first = ts.tokens[0] if ts.tokens else None
            if first is not None and self.inside(first):
                out.append(first - ts.due)
            elif ts.due <= self.t1 and (first is None or first > self.t1):
                out.append(self.t1 - ts.due)
        return out

    def tokens_in_window(self) -> int:
        return sum(1 for ts in self.turns.values() for t in ts.tokens
                   if self.inside(t))

    def token_gaps(self) -> list[float]:
        """Gaps between consecutive tokens of one turn, the later token in
        the window."""
        return [b - a for ts in self.turns.values()
                for a, b in zip(ts.tokens, ts.tokens[1:]) if self.inside(b)]

    def model_flops(self) -> float:
        """Operations the model needs for the tokens prefilled and
        generated in the window: its products' per token, plus attention's
        per token and context position (the configuration's reference
        states both)."""
        from bench import arch

        ref = arch.reference(self.cfg)
        per_token = ref.flops_per_token(self.cfg)
        attn = ref.attention_flops_per_position(self.cfg)
        f = 0.0
        for s, e, rows, ctx in self.decode_steps:
            if self.inside(s) and self.inside(e):
                f += rows * per_token + attn * ctx
        for s, e, n, end in self.chunks:
            if self.inside(s) and self.inside(e):
                positions = n * end - n * (n - 1) / 2
                f += n * per_token + attn * positions
        return f
