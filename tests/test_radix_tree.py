"""Tests for the typed radix tree (paper §4.3.2): prefix reuse + the
tier-reversed type-priority eviction order."""
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.radix_tree import TypedRadixTree
from repro.core.types import TypeLabel


def toks(n, base=0):
    return list(range(base, base + n))


class TestInsertMatch:
    def test_insert_then_match(self):
        t = TypedRadixTree(page_tokens=4)
        nodes = t.insert_chain(toks(8), [10, 11], "p1", TypeLabel.BUSY)
        assert [n.device_page for n in nodes] == [10, 11]
        assert [n.device_page for n in t.match_prefix(toks(8))] == [10, 11]

    def test_partial_page_not_matched(self):
        t = TypedRadixTree(page_tokens=4)
        t.insert_chain(toks(8), [1, 2], "p1", TypeLabel.BUSY)
        # only 7 tokens -> one full page
        assert len(t.match_prefix(toks(7))) == 1

    def test_prefix_sharing_between_programs(self):
        t = TypedRadixTree(page_tokens=4)
        t.insert_chain(toks(8), [1, 2], "p1", TypeLabel.BUSY)
        # p2 shares the first 8 tokens, extends by 4 -> only 1 new page
        nodes = t.insert_chain(toks(8) + toks(4, 100), [3], "p2", TypeLabel.BUSY)
        assert [n.device_page for n in nodes] == [1, 2, 3]

    def test_divergent_suffixes_fork(self):
        t = TypedRadixTree(page_tokens=4)
        t.insert_chain(toks(4) + toks(4, 50), [1, 2], "p1", TypeLabel.BUSY)
        t.insert_chain(toks(4) + toks(4, 60), [3], "p2", TypeLabel.BUSY)
        assert len(t.match_prefix(toks(4) + toks(4, 50))) == 2
        assert len(t.match_prefix(toks(4) + toks(4, 60))) == 2

    def test_page_count_mismatch_raises(self):
        t = TypedRadixTree(page_tokens=4)
        with pytest.raises(ValueError):
            t.insert_chain(toks(8), [1], "p1", TypeLabel.BUSY)


class TestTypedEviction:
    def _three_programs(self):
        t = TypedRadixTree(page_tokens=2)
        t.insert_chain(toks(2, 0), [0], "busy", TypeLabel.BUSY)
        t.insert_chain(toks(2, 10), [1], "idle", TypeLabel.IDLE)
        t.insert_chain(toks(2, 20), [2], "inactive", TypeLabel.INACTIVE)
        return t

    def test_gpu_order_inactive_idle_busy(self):
        t = self._three_programs()
        labels = [n.label for n in t.evictable("gpu")]
        assert labels == [TypeLabel.INACTIVE, TypeLabel.IDLE, TypeLabel.BUSY]

    def test_cpu_order_inactive_busy_idle(self):
        t = self._three_programs()
        for n in list(t._iter_nodes()):
            n.host_page = n.device_page  # pretend all offloaded
        labels = [n.label for n in t.evictable("cpu")]
        assert labels == [TypeLabel.INACTIVE, TypeLabel.BUSY, TypeLabel.IDLE]

    def test_lru_breaks_ties_within_type(self):
        t = TypedRadixTree(page_tokens=2)
        t.insert_chain(toks(2, 0), [0], "a", TypeLabel.IDLE)
        t.insert_chain(toks(2, 10), [1], "b", TypeLabel.IDLE)
        t.match_prefix(toks(2, 0))  # touch a -> b is now least recent
        first = t.evictable("gpu")[0]
        assert first.device_page == 1

    def test_pinned_nodes_never_evictable(self):
        t = self._three_programs()
        t.pin("inactive")
        labels = [n.label for n in t.evictable("gpu")]
        assert TypeLabel.INACTIVE not in labels
        t.unpin("inactive")
        assert TypeLabel.INACTIVE in [n.label for n in t.evictable("gpu")]

    def test_children_evicted_before_parents(self):
        t = TypedRadixTree(page_tokens=2)
        t.insert_chain(toks(6), [0, 1, 2], "p", TypeLabel.IDLE)
        order = t.evictable("gpu")
        assert [n.device_page for n in order] == [2]  # only the leaf
        t.evict(order[0], "gpu")
        assert [n.device_page for n in t.evictable("gpu")] == [1]

    def test_restamp_propagates_label(self):
        t = self._three_programs()
        t.restamp("busy", TypeLabel.INACTIVE)
        first = t.evictable("gpu")[:2]
        assert all(n.label is TypeLabel.INACTIVE for n in first)

    def test_evict_frees_and_gcs(self):
        t = TypedRadixTree(page_tokens=2)
        t.insert_chain(toks(4), [0, 1], "p", TypeLabel.INACTIVE)
        for n in list(t.evictable("gpu")):
            t.evict(n, "gpu")
        for n in list(t.evictable("gpu")):
            t.evict(n, "gpu")
        assert t.stats() == {"device_pages": 0, "host_pages": 0}
        assert not t.root.children  # fully garbage-collected


class TestPinUnpinEdges:
    """Refcount discipline at the seams the transfer plane exercises:
    pins racing eviction, repeated teardown, and (under kvsan strict
    mode) the underflow the historical ``max(0, ...)`` clamp hid."""

    def test_unpin_while_reload_holds_nodes(self):
        """A decode release (unpin) while an in-flight reload still holds
        its own acquire must leave the reload's refcount intact — the
        nodes stay unevictable until the stream also releases."""
        t = TypedRadixTree(page_tokens=2)
        nodes = t.insert_chain(toks(4), [0, 1], "p", TypeLabel.BUSY)
        t.pin("p")                 # decode slot
        t.acquire_nodes(nodes)     # in-flight reload stream
        t.unpin("p")               # decode retires first
        assert [n.refcount for n in nodes] == [1, 1]
        assert t.evictable("gpu") == []          # still protected
        t.release_nodes(nodes)     # stream commits
        assert [n.refcount for n in nodes] == [0, 0]
        assert len(t.evictable("gpu")) == 1      # leaf evictable again

    def test_double_release_program_is_idempotent(self):
        t = TypedRadixTree(page_tokens=2)
        t.insert_chain(toks(4), [0, 1], "p", TypeLabel.BUSY)
        t.release_program("p")
        t.release_program("p")                   # second is a no-op
        assert t.program_nodes("p") == []
        # pins after release target an empty node list, harmlessly
        t.pin("p")
        t.unpin("p")

    def test_pin_after_partial_eviction(self):
        """Eviction between a program's runs shrinks its chain on-device;
        a later pin must hold the *surviving* nodes only and balance."""
        t = TypedRadixTree(page_tokens=2)
        nodes = t.insert_chain(toks(6), [0, 1, 2], "p", TypeLabel.IDLE)
        leaf = t.evictable("gpu")[0]
        assert leaf is nodes[2]
        t.evict(leaf, "gpu")                     # tail page gone
        t.pin("p")
        # the evicted node is still in the program's node list (its page
        # is just elsewhere/nowhere); all three refcounts move together
        assert [n.refcount for n in nodes] == [1, 1, 1]
        assert t.evictable("gpu") == []
        t.unpin("p")
        assert [n.refcount for n in nodes] == [0, 0, 0]

    def test_strict_mode_rejects_unbalanced_unpin(self, monkeypatch):
        from repro.analysis import kvsan

        monkeypatch.setenv(kvsan.ENV_VAR, "1")
        t = TypedRadixTree(page_tokens=2)
        t.insert_chain(toks(4), [0, 1], "p", TypeLabel.BUSY)
        t.pin("p")
        t.unpin("p")
        with pytest.raises(kvsan.KvsanError):
            t.unpin("p")


@given(
    seqs=st.lists(
        st.lists(st.integers(0, 3), min_size=2, max_size=16),
        min_size=1,
        max_size=8,
    )
)
@settings(max_examples=100, deadline=None)
def test_property_shared_prefixes_share_pages(seqs):
    """Two programs with a common full-page prefix must map it to the same
    pages, and total allocated pages == number of distinct page-paths."""
    t = TypedRadixTree(page_tokens=2)
    next_page = [0]
    paths = set()
    for i, seq in enumerate(seqs):
        full = seq[: len(seq) // 2 * 2]
        existing = t.match_prefix(full)
        need = len(full) // 2 - len(existing)
        pages = [next_page[0] + j for j in range(need)]
        next_page[0] += need
        t.insert_chain(full, pages, f"p{i}", TypeLabel.BUSY)
        for k in range(2, len(full) + 1, 2):
            paths.add(tuple(full[:k]))
    assert t.stats()["device_pages"] == len(paths)
