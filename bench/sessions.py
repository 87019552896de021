"""Agent sessions for a traffic mix: the benchmark's one load generator.

The tool-call and token model is a copy of the program's trace generator
(``repro.traces.generator``, calibrated against the paper's section 3 by
``tests/test_traces.py``), kept here so that no change to the program can
move the yardstick. Everything a mix varies comes from its data file
under ``bench/traffic/``.

A mix is a closed population of ``sessions`` agents. Each agent sends a
turn, waits for the reply, runs its tool for the trace's recorded number
of real seconds, and sends the next turn. Token lengths are the trace's
divided by ``length_divisor``. The first ``shared_prompt_tokens`` tokens of
every session are one system prompt shared by all sessions of the run. A
session ends when its next turn would not fit ``max_seq`` (the agent
compacts its context) or when its trace ends; a new session then takes
its place, so the population stays at ``sessions``.

The seed of a run changes the order of the work, never its amount: the
session shapes (lengths, outputs, tool calls, starting ages, due times)
come from the mix's fixed ``corpus_seed``; the run's seed permutes which
session gets which first due time and draws every token id.
"""
from __future__ import annotations

import math
import random
from dataclasses import dataclass, field

# ----------------------------------------------------------------------------
# Copied from repro.traces.generator (two-phase semi-Markov tool-call model).
SHORT_KINDS = ["read", "write", "edit", "shell", "grep"]
LONG_KINDS = ["pytest", "compile", "human", "subagent"]


@dataclass
class TraceGenConfig:
    """Calibrated defaults of the program's generator, unchanged."""

    short_median_s: float = 0.40
    short_sigma: float = 0.90
    long_median_s: float = 3.5
    long_sigma: float = 1.05
    long_max_s: float = 600.0
    busy_calls_mean: float = 18.0
    idle_calls_mean: float = 2.2
    min_steps: int = 12
    mean_steps: int = 42
    max_steps: int = 120
    initial_context_mean: int = 9000
    short_result_tokens: tuple[int, int] = (100, 1600)
    long_result_tokens: tuple[int, int] = (400, 4000)
    output_tokens_mean: int = 120
    output_tokens_min: int = 16
    max_context: int = 120_000


@dataclass
class Step:
    input_tokens: int
    output_tokens: int
    tool_duration_s: float


def _lognormal(rng: random.Random, median: float, sigma: float) -> float:
    return median * math.exp(rng.gauss(0.0, sigma))


def _geometric(rng: random.Random, mean: float) -> int:
    p = min(0.999, 1.0 / max(1.0, mean))
    u = rng.random()
    return max(1, int(math.log(max(u, 1e-12)) / math.log(1.0 - p)) + 1)


def generate_program(rng: random.Random, cfg: TraceGenConfig) -> list[Step]:
    n_steps = min(
        cfg.max_steps, cfg.min_steps + _geometric(rng, cfg.mean_steps - cfg.min_steps)
    )
    context = int(rng.gauss(cfg.initial_context_mean, cfg.initial_context_mean * 0.25))
    context = max(2000, context)
    steps: list[Step] = []
    in_busy = True
    calls_left = _geometric(rng, cfg.busy_calls_mean)
    for _ in range(n_steps):
        output = max(
            cfg.output_tokens_min, int(rng.expovariate(1.0 / cfg.output_tokens_mean))
        )
        if in_busy:
            dur = _lognormal(rng, cfg.short_median_s, cfg.short_sigma)
            rng.choice(SHORT_KINDS)   # the tool's kind: drawn, as the original does
            result_lo, result_hi = cfg.short_result_tokens
        else:
            dur = min(
                cfg.long_max_s, _lognormal(rng, cfg.long_median_s, cfg.long_sigma)
            )
            rng.choice(LONG_KINDS)
            result_lo, result_hi = cfg.long_result_tokens
        steps.append(Step(min(context, cfg.max_context), output, dur))
        context = min(
            cfg.max_context, context + output + rng.randint(result_lo, result_hi)
        )
        calls_left -= 1
        if calls_left <= 0:
            in_busy = not in_busy
            mean = cfg.busy_calls_mean if in_busy else cfg.idle_calls_mean
            calls_left = _geometric(rng, mean)
    steps[-1].tool_duration_s = 0.0
    return steps
# ----------------------------------------------------------------------------


@dataclass(frozen=True)
class Turn:
    """One model call of a session, at the cut lengths."""

    new_tokens: int      # prompt tokens this turn appends (the tool result)
    max_new_tokens: int  # output tokens asked for
    tool_s: float        # real seconds of the tool call that follows


@dataclass
class Shape:
    """A session's sizes: its private start and its turns."""

    private_tokens: int
    turns: list[Turn]
    start_turn: int = 0  # turn at which an initial session joins the run


@dataclass
class Mix:
    """A traffic mix's parameters, as its data file states them."""

    sessions: int
    corpus_seed: int
    length_divisor: int
    shared_prompt_tokens: int
    min_private_tokens: int
    ramp_s: float
    first_due_spread_s: float
    generator: TraceGenConfig = field(default_factory=TraceGenConfig)

    @classmethod
    def from_dict(cls, d: dict) -> "Mix":
        d = dict(d)
        gen = TraceGenConfig(**d.pop("generator", {}))
        return cls(generator=gen, **d)


def session_shape(steps: list[Step], mix: Mix, max_seq: int) -> Shape:
    """Cut one generated program to a session that fits ``max_seq``.

    The program's context counts the shared prompt, so the private start is
    the cut first context less the shared prompt (at least
    ``min_private_tokens``). Turn ``i`` appends the tool result that the
    program's context grew by after turn ``i - 1``; outputs are the cut
    output lengths, at least one token. The session stops before the first
    turn whose prompt and output would not fit ``max_seq``."""
    div = mix.length_divisor
    private = max(mix.min_private_tokens,
                  steps[0].input_tokens // div - mix.shared_prompt_tokens)
    ctx = mix.shared_prompt_tokens + private
    turns: list[Turn] = []
    for i, s in enumerate(steps):
        new = 0
        if i:
            prev = steps[i - 1]
            result = s.input_tokens - prev.input_tokens - prev.output_tokens
            new = max(1, result // div)
        out = max(1, s.output_tokens // div)
        # the engine serves at least two tokens a turn (one from prefill,
        # one from the first decode step), and the next prompt holds them
        if ctx + new + max(out, 2) + 1 > max_seq:
            break
        turns.append(Turn(new, out, s.tool_duration_s))
        ctx += new + max(out, 2)
    return Shape(private, turns)


class Population:
    """The sessions of one run: fixed shapes, seeded order and tokens."""

    def __init__(self, mix: Mix, max_seq: int, vocab_size: int, seed: int):
        self.mix = mix
        self.max_seq = max_seq
        self.vocab_size = vocab_size
        self.seed = seed
        corpus = random.Random(mix.corpus_seed)
        self._corpus = corpus
        self.initial: list[tuple[Shape, float]] = []
        for _ in range(mix.sessions):
            shape = self._next_shape()
            shape.start_turn = corpus.randrange(len(shape.turns))
            self.initial.append((shape, corpus.uniform(0.0, mix.first_due_spread_s)))
        # the run's seed decides which session takes which first due time
        order = random.Random(f"{seed}:order")
        dues = [due for _, due in self.initial]
        order.shuffle(dues)
        self.initial = [(shape, due) for (shape, _), due in zip(self.initial, dues)]
        tok = random.Random(f"{seed}:system")
        self.system_prompt = self._tokens(tok, mix.shared_prompt_tokens)
        self._started = 0

    def _next_shape(self) -> Shape:
        while True:
            shape = session_shape(
                generate_program(self._corpus, self.mix.generator), self.mix,
                self.max_seq,
            )
            if shape.turns:
                return shape

    def _tokens(self, rng: random.Random, n: int) -> list[int]:
        return [rng.randrange(2, self.vocab_size) for _ in range(n)]

    def new_session(self, shape: Shape | None = None) -> "Session":
        """The next session: one of the initial population first, then
        replacements in the corpus's order."""
        sid = self._started
        self._started += 1
        if shape is None:
            shape = self._next_shape()
        return Session(sid, shape, self, random.Random(f"{self.seed}:s{sid}"))


class Session:
    """One agent: its token context and where it is in its turns."""

    def __init__(self, sid: int, shape: Shape, pop: Population,
                 rng: random.Random):
        self.sid = sid
        self.pid = f"s{sid:05d}"
        self.shape = shape
        self.rng = rng
        self.pop = pop
        self.turn = shape.start_turn
        # a session that joins mid-life starts from the context its earlier
        # turns would have built, with outputs drawn like tool results
        n = shape.private_tokens
        for t in shape.turns[: shape.start_turn]:
            n += t.new_tokens + max(t.max_new_tokens, 2)
        self.tokens = list(pop.system_prompt) + pop._tokens(rng, n)

    def prompt(self) -> tuple[list[int], int]:
        """Tokens and output length of the current turn."""
        t = self.shape.turns[self.turn]
        self.tokens.extend(self.pop._tokens(self.rng, t.new_tokens))
        return list(self.tokens), t.max_new_tokens

    def served(self, output: list[int]) -> tuple[float, bool]:
        """Record the turn's served tokens; return the seconds of the tool
        call that follows and whether the session has ended (its agent
        then starts a new session once the tool call is over)."""
        self.tokens.extend(output)
        tool_s = self.shape.turns[self.turn].tool_s
        self.turn += 1
        return tool_s, self.turn >= len(self.shape.turns)
