"""Trace generator calibration against the paper's §3 statistics, plus IO."""
import math

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.traces import (
    busy_phase_durations,
    generate_corpus,
    generate_program,
    load_corpus,
    percentile,
    phase_stats,
    save_corpus,
    tool_call_cdf,
)


class TestCalibration:
    """The generated corpus must reproduce the paper's trace analysis.

    Bands are deliberately generous — these are reproduction targets for a
    *synthetic* corpus, not exact-match assertions: paper values in comments.
    """

    def setup_method(self):
        self.corpus = generate_corpus(186, seed=0)
        self.stats = phase_stats(self.corpus, threshold_s=2.0)

    def test_short_call_fraction_at_2s(self):
        # paper: 87% of tool calls are short at the 2 s threshold
        assert 0.82 <= self.stats.short_fraction <= 0.93

    def test_long_calls_dominate_tool_time(self):
        # paper: the 13% long calls account for 58% of wall-clock tool time
        assert 0.48 <= self.stats.long_time_share <= 0.70

    def test_busy_phase_median_at_2s(self):
        # paper Fig. 5: median busy phase ~20 s at the 2 s threshold
        assert 12.0 <= self.stats.busy_median_s <= 30.0

    def test_busy_phase_medians_rise_with_threshold(self):
        # paper Fig. 5: medians 4 s / 20 s / 41 s at 1 s / 2 s / 5 s
        m1 = percentile(busy_phase_durations(self.corpus, 1.0), 0.5)
        m2 = self.stats.busy_median_s
        m5 = percentile(busy_phase_durations(self.corpus, 5.0), 0.5)
        assert m1 < m2 < m5
        assert 2.0 <= m1 <= 12.0
        assert 25.0 <= m5 <= 60.0

    def test_duration_spread_three_orders_of_magnitude(self):
        # paper Fig. 3: durations span 3+ orders of magnitude
        assert self.stats.orders_of_magnitude >= 3.0

    def test_heavy_tail_reaches_minutes(self):
        durs = tool_call_cdf(self.corpus)
        assert max(durs) >= 60.0
        assert percentile(durs, 0.5) < 1.0  # median well below a second

    def test_programs_issue_tens_of_steps(self):
        steps = sorted(t.num_steps for t in self.corpus)
        assert 20 <= steps[len(steps) // 2] <= 60

    def test_context_grows_monotonically(self):
        for tr in self.corpus[:20]:
            ctxs = [s.input_tokens for s in tr.steps]
            assert all(a <= b for a, b in zip(ctxs, ctxs[1:]))


class TestDeterminismAndIO:
    def test_same_seed_same_corpus(self):
        a = generate_corpus(5, seed=7)
        b = generate_corpus(5, seed=7)
        assert [
            (s.input_tokens, s.output_tokens, s.tool_duration_s)
            for t in a
            for s in t.steps
        ] == [
            (s.input_tokens, s.output_tokens, s.tool_duration_s)
            for t in b
            for s in t.steps
        ]

    def test_roundtrip(self, tmp_path):
        corpus = generate_corpus(8, seed=3)
        p = tmp_path / "corpus.jsonl"
        save_corpus(corpus, p)
        loaded = load_corpus(p)
        assert len(loaded) == len(corpus)
        for a, b in zip(corpus, loaded):
            assert a.program_id == b.program_id
            for sa, sb in zip(a.steps, b.steps):
                assert sa.input_tokens == sb.input_tokens
                assert sa.output_tokens == sb.output_tokens
                assert math.isclose(sa.tool_duration_s, sb.tool_duration_s, abs_tol=1e-3)


@given(seed=st.integers(0, 1000))
@settings(max_examples=30, deadline=None)
def test_property_every_program_is_well_formed(seed):
    import random

    tr = generate_program("x", random.Random(seed))
    assert tr.num_steps >= 1
    for s in tr.steps:
        assert s.input_tokens > 0
        assert s.output_tokens > 0
        assert s.tool_duration_s >= 0.0
        assert s.reasoning_wall_s > 0.0
    # last step ends the session
    assert tr.steps[-1].tool_duration_s == 0.0
