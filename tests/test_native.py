"""Native host-kernel tests: C++ results vs numpy references (the reference
tests its Rust algorithm crates the same way, rdx_sort.rs / loser_tree.rs
inline tests)."""

import numpy as np
import pytest

from auron_tpu import native


def np_lexsort(words):
    return np.lexsort(tuple(words[:, i]
                            for i in range(words.shape[1] - 1, -1, -1)))


class TestNativeBuild:
    def test_builds_and_loads(self):
        # the image ships g++ — the native path must actually engage here
        assert native.available()
        how, why = native.status()
        assert how in ("built", "loaded") and why

    def test_rebuilds_when_missing_or_older_than_its_source(
            self, tmp_path, monkeypatch):
        """The library is -march=native and git-ignored: one that is
        missing, or older than auron_host.cc, is never loaded as is."""
        import os
        so, src = tmp_path / "libauron_host.so", tmp_path / "auron_host.cc"
        monkeypatch.setattr(native, "_SO_PATH", str(so))
        monkeypatch.setattr(native, "_SRC_PATH", str(src))
        src.write_text("// source")
        assert native._stale() == "library missing"
        so.write_text("binary")
        os.utime(so, (100, 100))
        os.utime(src, (200, 200))
        assert native._stale() == "library older than auron_host.cc"
        os.utime(so, (300, 300))
        assert native._stale() is None


class TestLexSort:
    @pytest.mark.parametrize("n,w", [(0, 1), (1, 1), (1000, 1), (1000, 3),
                                     (4096, 2)])
    def test_matches_numpy(self, n, w):
        rng = np.random.default_rng(n + w)
        # low-cardinality words force ties → exercises stability
        words = rng.integers(0, 16, (n, w)).astype(np.uint64)
        got = native.lex_sort_words(words)
        want = np_lexsort(words) if n else np.zeros(0, np.int32)
        np.testing.assert_array_equal(got, want)

    def test_full_range_values(self):
        rng = np.random.default_rng(0)
        words = rng.integers(0, 2**63, (500, 2)).astype(np.uint64)
        words[::7] = 0xFFFFFFFFFFFFFFFF
        got = native.lex_sort_words(words)
        np.testing.assert_array_equal(got, np_lexsort(words))


class TestMergeRuns:
    def _runs(self, k, rng, w=2):
        runs = []
        for _ in range(k):
            n = int(rng.integers(0, 200))
            r = rng.integers(0, 1000, (n, w)).astype(np.uint64)
            r = r[np_lexsort(r)]
            runs.append(r)
        words = np.concatenate(runs) if runs else np.zeros((0, w), np.uint64)
        offsets = np.zeros(k + 1, np.int64)
        np.cumsum([len(r) for r in runs], out=offsets[1:])
        return words, offsets

    @pytest.mark.parametrize("k", [1, 2, 3, 7, 16])
    def test_merge_is_sorted_and_complete(self, k):
        rng = np.random.default_rng(k)
        words, offsets = self._runs(k, rng)
        order = native.merge_runs(words, offsets)
        assert sorted(order.tolist()) == list(range(len(words)))
        merged = words[order]
        for i in range(1, len(merged)):
            assert tuple(merged[i - 1]) <= tuple(merged[i])

    def test_ties_stable_by_run(self):
        # equal keys must come out in run order (loser tree tie-break)
        a = np.array([[5], [5]], np.uint64)
        b = np.array([[5]], np.uint64)
        words = np.concatenate([a, b])
        order = native.merge_runs(words, np.array([0, 2, 3], np.int64))
        assert order.tolist() == [0, 1, 2]

    def test_empty_runs(self):
        words = np.array([[1], [2]], np.uint64)
        order = native.merge_runs(words, np.array([0, 0, 2, 2], np.int64))
        assert order.tolist() == [0, 1]


class TestTakeRows:
    def test_matches_numpy(self):
        rng = np.random.default_rng(1)
        src = rng.integers(0, 255, (100, 16)).astype(np.uint8)
        order = rng.permutation(100)[:40].astype(np.int32)
        np.testing.assert_array_equal(native.take_rows(src, order),
                                      src[order])

    def test_non_u8_dtype(self):
        rng = np.random.default_rng(2)
        src = rng.normal(size=(50, 4))
        order = rng.permutation(50).astype(np.int32)
        np.testing.assert_array_equal(native.take_rows(src, order),
                                      src[order])
