"""What decides ``correct``: served tokens against the plain reference.

Once the window has closed, a sample of sessions drawn from the seed is
taken from those with finished turns: always the one with the longest
served context and, where any turn reloaded pages from the host tier, one
such session. Each sampled session's tokens run once through the plain
reference that the configuration's ``reference`` key names
(:mod:`bench.arch`); at the position before every token the program
served, the reference's best logit less its logit of the served token is
that token's gap. The widest gap over the sample is compared with the
configuration's limit (``bench/limits/<config>.json``).

A greedy server that computes what the configuration states serves tokens
whose gap is rounding at most; a wrong token from a fault in prefill over
a cached prefix, in paged decode or in a reloaded page lies a whole
logit-scale below the best.
"""
from __future__ import annotations

import random
from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

from bench import arch


@dataclass
class TurnRecord:
    """A finished turn: its prompt length and what the program served."""

    prompt_len: int
    served: list[int]
    reloaded: bool = False


@dataclass
class SessionRecord:
    pid: str
    tokens: list[int]
    turns: list[TurnRecord] = field(default_factory=list)

    def compared(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(sequence, logit positions, served tokens) over finished turns."""
        pos, tgt = [], []
        for t in self.turns:
            for j, tok in enumerate(t.served):
                pos.append(t.prompt_len - 1 + j)
                tgt.append(tok)
        end = max(t.prompt_len + len(t.served) for t in self.turns)
        return (np.asarray(self.tokens[:end], np.int32),
                np.asarray(pos, np.int32), np.asarray(tgt, np.int32))


def sample(sessions: list[SessionRecord], seed: int, min_tokens: int,
           max_sessions: int) -> list[SessionRecord]:
    """The sessions to compare: the longest, one that reloaded (if any),
    then others in a seeded order until ``min_tokens`` served tokens."""
    done = [s for s in sessions if s.turns]
    if not done:
        return []
    rng = random.Random(f"{seed}:check")
    order = sorted(done, key=lambda s: s.pid)
    rng.shuffle(order)
    picked = [max(done, key=lambda s: len(s.compared()[0]))]
    reloaded = [s for s in order if any(t.reloaded for t in s.turns)]
    if reloaded and reloaded[0] is not picked[0]:
        picked.append(reloaded[0])
    for s in order:
        n = sum(len(t.served) for p in picked for t in p.turns)
        if n >= min_tokens or len(picked) >= max_sessions:
            break
        if s not in picked:
            picked.append(s)
    return picked


def gaps(cfg: dict, seed: int, picked: list[SessionRecord], pad_to: int,
         control: bool = False) -> dict:
    """Widest gap of the served tokens below the reference's best, and with
    ``control`` the widest gap of the tokens the fp8 control puts first at
    every position of the same prompts and tokens."""
    reference = arch.reference(cfg)
    seqs, pos, tgt = zip(*(s.compared() for s in picked))
    ref = reference.logits_at(cfg, seed, list(seqs), list(pos), pad_to=pad_to)
    out = {"compared_tokens": int(sum(len(t) for t in tgt)),
           "compared_sessions": len(picked),
           "reloaded_turns_compared": sum(t.reloaded for s in picked for t in s.turns)}
    served_gap = 0.0
    for r, t in zip(ref, tgt):
        g = jnp.max(r, axis=-1) - jnp.take_along_axis(r, jnp.asarray(t)[:, None], -1)[:, 0]
        served_gap = max(served_gap, float(jnp.max(g)))
    out["widest_gap"] = served_gap
    if control:
        out["control_widest_gap"] = max(
            _control_gap(reference, cfg, seed, seq, pad_to) for seq in seqs)
    return out


def _control_gap(reference, cfg: dict, seed: int, seq: np.ndarray, pad_to: int) -> float:
    """Widest gap below the float32 reference's best of the tokens the fp8
    control puts first, at every position of one session's prompts and
    tokens (one session at a time: the logits of every position are large)."""
    at = [np.arange(pad_to - 1, dtype=np.int32)]   # one shape for every session
    n = len(seq) - 1
    r = reference.logits_at(cfg, seed, [seq], at, pad_to=pad_to, batch=1)[0][:n]
    c = reference.logits_at(cfg, seed, [seq], at, pad_to=pad_to, batch=1,
                            precision="fp8")[0][:n]
    first = jnp.argmax(c, axis=-1)
    g = jnp.max(r, axis=-1) - jnp.take_along_axis(r, first[:, None], -1)[:, 0]
    return float(jnp.max(g))
