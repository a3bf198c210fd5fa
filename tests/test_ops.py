"""Hot-op kernel tests (orleans_tpu.ops) — run on the CPU backend; the
Pallas kernels run in interpret mode because these tests ask for it
(``interpret=True``), never by default. Numerical references are plain
numpy. The compiled kernels are checked on the chip by ``chip_smoke.py``
and compiled for it in ``tests/test_chip_compile.py``."""

import numpy as np
import pytest

import jax.numpy as jnp

from orleans_tpu.ops import (
    DeviceDirectory,
    build_directory_arrays,
    device_lookup,
    masked_reduce,
    pack_by_dest,
    rank_by_dest,
    rank_dense_keys,
    segment_sum,
    segment_sum_onehot,
    segment_sum_pallas,
)


# ---------------------------------------------------------------------------
# masked_reduce (the reduce_actors device half)
# ---------------------------------------------------------------------------

class TestMaskedReduce:
    def test_int_sum_exact_any_layout(self):
        rng = np.random.default_rng(0)
        vals = rng.integers(-500, 500, 64).astype(np.int32)
        expect = int(vals.sum())
        for shape in ((1, 64), (4, 16), (8, 8)):
            v = jnp.asarray(vals.reshape(shape))
            out = masked_reduce(v, jnp.ones(shape, bool), op="sum")
            assert int(out) == expect

    def test_mask_excludes_lanes(self):
        v = jnp.asarray([[1, 2], [4, 8]], jnp.int32)
        m = jnp.asarray([[True, False], [True, True]])
        assert int(masked_reduce(v, m, op="sum")) == 13
        assert int(masked_reduce(v, m, op="max")) == 8
        assert int(masked_reduce(v, m, op="min")) == 1

    def test_tree_and_feature_axes(self):
        vals = {"a": jnp.ones((2, 4, 3), jnp.float32),
                "b": jnp.full((2, 4), 2, jnp.int32)}
        m = jnp.ones((2, 4), bool).at[0, 0].set(False)
        out = masked_reduce(vals, m, op="sum")
        np.testing.assert_allclose(np.asarray(out["a"]), [7.0] * 3)
        assert int(out["b"]) == 14

    def test_bool_sum_counts(self):
        v = jnp.asarray([[True, True, False, True]])
        m = jnp.asarray([[True, True, True, False]])
        assert int(masked_reduce(v, m, op="sum")) == 2

    def test_all_masked_identities(self):
        v = jnp.asarray([[3, 4]], jnp.int32)
        m = jnp.zeros((1, 2), bool)
        assert int(masked_reduce(v, m, op="sum")) == 0
        assert int(masked_reduce(v, m, op="max")) == \
            np.iinfo(np.int32).min
        f = jnp.asarray([[1.5]], jnp.float32)
        assert float(masked_reduce(f, jnp.zeros((1, 1), bool),
                                   op="max")) == -np.inf

    def test_rejects_unknown_op(self):
        with pytest.raises(ValueError):
            masked_reduce(jnp.ones((1, 1)), jnp.ones((1, 1), bool),
                          op="median")


def _np_segment_sum(values, ids, S):
    out = np.zeros((S, *values.shape[1:]), np.float64)
    for i, s in enumerate(ids):
        if 0 <= s < S:
            out[s] += values[i]
    return out


class TestSegmentSum:
    @pytest.mark.parametrize("shape,ints", [
        ((300,), False),
        # integer values far above 256, totals below 2^24: the case a
        # TPU's default (bfloat16-operand) matmul precision breaks —
        # exact by contract, so compared exactly
        ((300,), True), ((2048, 4), True)])
    def test_onehot_matches_numpy_1d(self, shape, ints):
        rng = np.random.default_rng(1)
        ids = rng.integers(0, 40, size=shape[0])
        if ints:
            v = rng.integers(257, 60_000, size=shape).astype(np.float32)
            got = segment_sum_onehot(jnp.asarray(v), jnp.asarray(ids), 40)
            np.testing.assert_array_equal(
                np.asarray(got).astype(np.int64),
                _np_segment_sum(v, ids, 40).astype(np.int64))
            return
        v = rng.normal(size=shape).astype(np.float32)
        got = segment_sum_onehot(jnp.asarray(v), jnp.asarray(ids), 40)
        np.testing.assert_allclose(got, _np_segment_sum(v, ids, 40),
                                   rtol=1e-5)

    def test_onehot_2d_and_out_of_range(self):
        rng = np.random.default_rng(2)
        v = rng.normal(size=(64, 3)).astype(np.float32)
        ids = rng.integers(-2, 10, size=64)  # some out of range
        got = segment_sum_onehot(jnp.asarray(v), jnp.asarray(ids), 8)
        np.testing.assert_allclose(got, _np_segment_sum(v, ids, 8),
                                   rtol=1e-5)

    @pytest.mark.parametrize("B,S,D,ints", [
        (100, 17, 3, False), (1024, 300, 1, False), (513, 8, 5, False),
        # integers >> 256 (see test_onehot_matches_numpy_1d): exact
        (1024, 300, 4, True), (2048, 64, 128, True)])
    def test_pallas_matches_numpy(self, B, S, D, ints):
        rng = np.random.default_rng(3)
        ids = rng.integers(0, S, size=B)
        if ints:
            v = rng.integers(257, 60_000, size=(B, D)).astype(np.float32)
        else:
            v = rng.normal(size=(B, D)).astype(np.float32)
        got = segment_sum_pallas(jnp.asarray(v), jnp.asarray(ids), S,
                                 block_s=64, block_b=128, interpret=True)
        want = _np_segment_sum(v, ids, S)
        if ints:
            np.testing.assert_array_equal(
                np.asarray(got).astype(np.int64), want.astype(np.int64))
        else:
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_pallas_default_never_interprets(self):
        """``interpret`` is something a test asks for by name: the
        default compiles for the backend it runs on, and the CPU backend
        has no Pallas TPU lowering — so off-TPU the bare call refuses
        instead of quietly interpreting."""
        v, ids = jnp.ones((256, 128), jnp.float32), jnp.zeros(256, jnp.int32)
        with pytest.raises(ValueError, match="[Ii]nterpret"):
            segment_sum_pallas(v, ids, 8)

    def test_pallas_1d_values(self):
        v = np.ones(50, np.float32)
        ids = np.arange(50) % 7
        got = segment_sum_pallas(jnp.asarray(v), jnp.asarray(ids), 7,
                                 interpret=True)
        assert got.shape == (7,)
        np.testing.assert_allclose(got, _np_segment_sum(v, ids, 7))

    def test_dispatcher_entrypoint(self):
        v = np.ones((33, 2), np.float32)
        ids = np.zeros(33, np.int64)
        got = segment_sum(jnp.asarray(v), jnp.asarray(ids), 4)
        assert got[0, 0] == 33 and got[1].sum() == 0


class TestRankByDest:
    def _np_rank(self, d):
        seen: dict[int, int] = {}
        out = []
        for x in d:
            out.append(seen.get(x, 0))
            seen[x] = seen.get(x, 0) + 1
        return np.array(out)

    @pytest.mark.parametrize("B,S", [(37, 5), (256, 9), (700, 33)])
    def test_small_path(self, B, S):
        rng = np.random.default_rng(4)
        d = rng.integers(0, S, size=B)
        got = rank_by_dest(jnp.asarray(d), S, use_pallas=False)
        np.testing.assert_array_equal(got, self._np_rank(d))

    @pytest.mark.parametrize("B,S", [(512, 7), (777, 40)])
    def test_pallas_path(self, B, S):
        rng = np.random.default_rng(5)
        d = rng.integers(0, S, size=B)
        got = rank_by_dest(jnp.asarray(d), S, use_pallas=True, block=128,
                           interpret=True)
        np.testing.assert_array_equal(got, self._np_rank(d))


class TestRankDenseKeys:
    def test_matches_rank_by_dest_semantics(self):
        rng = np.random.default_rng(8)
        keys = rng.integers(0, 50_000, size=4096)  # large key space
        got = np.asarray(rank_dense_keys(jnp.asarray(keys)))
        seen: dict[int, int] = {}
        for i, k in enumerate(keys):
            assert got[i] == seen.get(int(k), 0)
            seen[int(k)] = seen.get(int(k), 0) + 1

    def test_all_same_and_all_distinct(self):
        same = rank_dense_keys(jnp.zeros(16, jnp.int32))
        np.testing.assert_array_equal(same, np.arange(16))
        distinct = rank_dense_keys(jnp.arange(16, dtype=jnp.int32))
        np.testing.assert_array_equal(distinct, np.zeros(16))


class TestPackByDest:
    @pytest.mark.parametrize("B,rank_kw", [
        (200, {"use_pallas": False}),
        # the default on the CPU: plain XLA (the sort rank from 512 lanes
        # up), never the Pallas interpreter — see the monkeypatch below
        (200, {}), (1024, {}),
        (1024, {"use_pallas": True, "interpret": True})])
    def test_matches_semantics(self, B, rank_kw, monkeypatch):
        if not rank_kw:
            import orleans_tpu.ops.route as route

            def refuse(*a, **k):
                raise AssertionError("the default path reached Pallas "
                                     "off-TPU")
            monkeypatch.setattr(route.pl, "pallas_call", refuse)
        rng = np.random.default_rng(6)
        S, CAP = 6, 16 * max(1, B // 200)
        d = rng.integers(-1, S + 1, size=B)  # includes out-of-range
        valid = rng.random(B) < 0.8
        payload = {"x": rng.normal(size=(B, 2)).astype(np.float32)}
        out, ovalid, drops = pack_by_dest(
            jnp.asarray(d), jnp.asarray(valid),
            {"x": jnp.asarray(payload["x"])}, S, CAP, **rank_kw)
        ovalid = np.asarray(ovalid)
        outx = np.asarray(out["x"])
        # every valid in-range message appears exactly once, in dest order
        for s in range(S):
            msgs = [payload["x"][i] for i in range(B)
                    if valid[i] and d[i] == s][:CAP]
            assert int(ovalid[s].sum()) == len(msgs)
            for r, m in enumerate(msgs):
                np.testing.assert_allclose(outx[s, r], m)
        # conservation: every valid message is either delivered or counted
        # as a drop (out-of-range valids count as drops too)
        n_ok = int(sum(1 for i in range(B) if valid[i] and 0 <= d[i] < S))
        n_oor = int(np.sum(valid & ((d < 0) | (d >= S))))
        assert int(ovalid.sum()) + int(drops) == n_ok + n_oor

    def test_overflow_drops(self):
        d = np.zeros(10, np.int64)
        valid = np.ones(10, bool)
        out, ovalid, drops = pack_by_dest(
            jnp.asarray(d), jnp.asarray(valid), {"v": jnp.arange(10.0)},
            n_dest=2, capacity=4, use_pallas=False)
        assert int(drops) == 6
        assert int(np.asarray(ovalid).sum()) == 4
        np.testing.assert_allclose(np.asarray(out["v"])[0, :4],
                                   [0, 1, 2, 3])


class TestDeviceDirectory:
    def test_build_and_lookup(self):
        entries = {i * 7 + 1: i for i in range(100)}
        tk, tv = build_directory_arrays(entries, 256)
        keys = jnp.asarray(list(entries) + [9999, 12345])
        vals, found = device_lookup(jnp.asarray(tk), jnp.asarray(tv), keys)
        assert np.asarray(found)[:100].all()
        assert not np.asarray(found)[100:].any()
        np.testing.assert_array_equal(np.asarray(vals)[:100],
                                      list(entries.values()))

    def test_insert_remove_grow(self):
        d = DeviceDirectory(capacity=16)
        for i in range(200):  # forces several growths
            d.insert(i * 13 + 5, i)
        assert d.count == 200
        for i in range(0, 200, 2):
            assert d.remove(i * 13 + 5)
        assert d.count == 100
        vals, found = d.lookup_batch(
            np.array([i * 13 + 5 for i in range(200)]))
        found = np.asarray(found)
        assert found[1::2].all() and not found[0::2].any()
        np.testing.assert_array_equal(np.asarray(vals)[1::2],
                                      np.arange(1, 200, 2))

    def test_update_existing(self):
        d = DeviceDirectory(capacity=16)
        d.insert(42, 1)
        d.insert(42, 2)
        assert d.count == 1
        assert d.lookup(42) == 2
        assert d.remove(42) and not d.remove(42)
        assert d.lookup(42) is None
