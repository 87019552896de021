"""The session generator: seeded, cut by the divisor, ended at max_seq."""
from __future__ import annotations

import random

import pytest

from bench.sessions import Mix, Population, Step, generate_program, session_shape

MAX_SEQ = 2048


def mix(**kw) -> Mix:
    d = dict(sessions=12, corpus_seed=0, length_divisor=16, shared_prompt_tokens=512,
             min_private_tokens=16, ramp_s=1.0, first_due_spread_s=2.0)
    d.update(kw)
    return Mix.from_dict(d)


def sizes(pop: Population) -> list:
    return sorted((s.private_tokens, s.start_turn, tuple(s.turns)) for s, _ in pop.initial)


def test_same_seed_same_sessions():
    a, b = Population(mix(), MAX_SEQ, 1000, 7), Population(mix(), MAX_SEQ, 1000, 7)
    assert a.initial == b.initial and a.system_prompt == b.system_prompt
    sa, sb = a.new_session(a.initial[0][0]), b.new_session(b.initial[0][0])
    assert sa.tokens == sb.tokens and sa.prompt() == sb.prompt()


@pytest.mark.parametrize("seed", [1, 2**31 + 11, 2**40 + 3])
def test_seeds_change_order_and_tokens_not_sizes(seed):
    base, other = Population(mix(), MAX_SEQ, 1000, 0), Population(mix(), MAX_SEQ, 1000, seed)
    assert sizes(base) == sizes(other)
    assert sorted(d for _, d in base.initial) == sorted(d for _, d in other.initial)
    assert [d for _, d in base.initial] != [d for _, d in other.initial]
    assert base.system_prompt != other.system_prompt


def test_lengths_are_cut_by_the_divisor():
    steps = [Step(9600, 160, 1.0), Step(9600 + 160 + 1600, 40, 2.0), Step(12000, 16, 0.0)]
    shape = session_shape(steps, mix(), MAX_SEQ)
    assert shape.private_tokens == 9600 // 16 - 512
    assert [t.new_tokens for t in shape.turns] == [0, 100, (12000 - 11360 - 40) // 16]
    assert [t.max_new_tokens for t in shape.turns] == [10, 2, 1]
    assert [t.tool_s for t in shape.turns] == [1.0, 2.0, 0.0]


def test_sessions_end_before_max_seq():
    rng = random.Random(3)
    m = mix()
    for _ in range(300):
        steps = generate_program(rng, m.generator)
        shape = session_shape(steps, m, MAX_SEQ)
        ctx = m.shared_prompt_tokens + shape.private_tokens
        for t in shape.turns:
            ctx += t.new_tokens
            assert ctx + t.max_new_tokens <= MAX_SEQ
            ctx += max(t.max_new_tokens, 2)
        if len(shape.turns) < len(steps):   # cut by max_seq, not by the trace
            nxt = steps[len(shape.turns)]
            prev = steps[len(shape.turns) - 1] if shape.turns else None
            new = (max(1, (nxt.input_tokens - prev.input_tokens - prev.output_tokens) // 16)
                   if prev else 0)
            assert ctx + new + max(nxt.output_tokens // 16, 2) + 1 > MAX_SEQ


def test_session_prompts_grow_by_served_tokens_and_results():
    pop = Population(mix(sessions=1), MAX_SEQ, 1000, 5)
    s = pop.new_session(pop.initial[0][0])
    first, _ = s.prompt()
    tool_s, ended = s.served([7, 8, 9])
    assert tool_s == s.shape.turns[s.turn - 1].tool_s
    if not ended:
        nxt, _ = s.prompt()
        assert nxt[: len(first) + 3] == first + [7, 8, 9]
        assert len(nxt) == len(first) + 3 + s.shape.turns[s.turn].new_tokens


def test_system_prompt_is_shared():
    pop = Population(mix(), MAX_SEQ, 1000, 5)
    a = pop.new_session(pop.initial[0][0])
    b = pop.new_session(pop.initial[1][0])
    assert a.tokens[:512] == b.tokens[:512] == pop.system_prompt
    assert a.tokens[512:] != b.tokens[512:]
