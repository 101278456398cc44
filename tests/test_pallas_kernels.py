"""Interpret-mode parity suite for the Pallas kernels vs kernels/ref.py,
plus unit tests for the dispatch policy (kernels/dispatch.py).

Complements test_kernels.py's shape/dtype sweeps with the contract edges
the dispatch layer relies on: padding tails, EMPTY inputs (zero queries /
boundaries / rows / values — the kernels assume a non-empty grid, so the
wrappers must route these to the reference path), out-of-range ids, and
both ``right=`` sides.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import dispatch, ops, ref


# ---------------------------------------------------------------------------
# bucketize
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("right", [True, False])
def test_bucketize_empty_queries(right):
    b = jnp.asarray(np.arange(10, dtype=np.int32))
    q = jnp.zeros((0,), jnp.int32)
    got = ops.bucketize(b, q, right=right, use_pallas=True, interpret=True)
    assert got.shape == (0,)


@pytest.mark.parametrize("right", [True, False])
def test_bucketize_empty_boundaries(right):
    b = jnp.zeros((0,), jnp.int32)
    q = jnp.asarray(np.arange(5, dtype=np.int32))
    got = ops.bucketize(b, q, right=right, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.zeros(5, np.int32))


@pytest.mark.parametrize("right", [True, False])
def test_bucketize_padding_tail_and_duplicates(rng, right):
    """Non-tile query count + duplicate boundary values (ties are where
    the right=/left distinction matters)."""
    nb, nq = 37, 1025  # nq != Q_TILE multiple
    b = np.sort(rng.integers(0, 10, nb)).astype(np.int32)  # heavy duplicates
    q = rng.integers(-2, 12, nq).astype(np.int32)
    got = ops.bucketize(jnp.asarray(b), jnp.asarray(q), right=right,
                        use_pallas=True, interpret=True)
    want = ref.ref_bucketize(jnp.asarray(b), jnp.asarray(q), right)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("right", [True, False])
def test_bucketize_sentinel_padded_boundaries(rng, right):
    """Capacity-model inputs: boundary tail holds int32-max sentinels and
    queries probe beyond every real boundary."""
    b = np.concatenate([np.sort(rng.integers(0, 100, 20)),
                        np.full(12, np.iinfo(np.int32).max)]).astype(np.int32)
    q = rng.integers(-5, 200, 333).astype(np.int32)
    got = ops.bucketize(jnp.asarray(b), jnp.asarray(q), right=right,
                        use_pallas=True, interpret=True)
    want = ref.ref_bucketize(jnp.asarray(b), jnp.asarray(q), right)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# rle_decode
# ---------------------------------------------------------------------------


def test_rle_decode_zero_runs_full_capacity():
    """n == 0 with sentinel-padded capacity: every row is a gap."""
    nrows, cap = 500, 8
    starts = np.full(cap, nrows, np.int32)
    ends = np.full(cap, nrows, np.int32)
    vals = np.zeros(cap, np.int32)
    got = ops.rle_decode(jnp.asarray(vals), jnp.asarray(starts),
                         jnp.asarray(ends), jnp.asarray(0, jnp.int32), nrows,
                         fill=7, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.full(nrows, 7, np.int32))


def test_rle_decode_zero_capacity_and_zero_rows():
    empty = jnp.zeros((0,), jnp.int32)
    got = ops.rle_decode(empty, empty, empty, jnp.asarray(0, jnp.int32), 10,
                         fill=3, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.full(10, 3, np.int32))
    got = ops.rle_decode(empty, empty, empty, jnp.asarray(0, jnp.int32), 0,
                         use_pallas=True, interpret=True)
    assert got.shape == (0,)


def test_rle_decode_nonzero_fill_with_gaps():
    nrows = 3000  # > ROW_TILE, non-multiple handled by grid padding
    starts = np.array([5, 2047, 2900], np.int32)
    ends = np.array([90, 2500, 2999], np.int32)
    vals = np.array([1.5, -2.0, 3.25], np.float32)
    args = (jnp.asarray(vals), jnp.asarray(starts), jnp.asarray(ends),
            jnp.asarray(3, jnp.int32), nrows)
    got = ops.rle_decode(*args, fill=-1, use_pallas=True, interpret=True)
    want = ref.ref_rle_decode(*args, fill=-1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# segment_sum
# ---------------------------------------------------------------------------


def test_segment_sum_empty_values():
    got = ops.segment_reduce(jnp.zeros((0,), jnp.float32),
                             jnp.zeros((0,), jnp.int32), 4,
                             use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.zeros(4, np.float32))


def test_segment_sum_single_group_padding_tail(rng):
    n = 1025  # SEG_TILE + 1: internal pad ids == num_segments must drop
    v = rng.random(n).astype(np.float32)
    ids = np.zeros(n, np.int32)
    got = ops.segment_reduce(jnp.asarray(v), jnp.asarray(ids), 1,
                             use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got)[0], v.sum(), rtol=1e-4)


def test_segment_sum_all_ids_out_of_range(rng):
    n, s = 512, 8
    v = rng.random(n).astype(np.float32)
    ids = np.full(n, s, np.int32)  # every value dropped
    got = ops.segment_reduce(jnp.asarray(v), jnp.asarray(ids), s,
                             use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.zeros(s, np.float32))


# ---------------------------------------------------------------------------
# dispatch policy
# ---------------------------------------------------------------------------


def test_policy_from_env_parsing():
    pol = dispatch.policy_from_env({
        "REPRO_USE_PALLAS": "1",
        "REPRO_PALLAS_INTERPRET": "0",  # no such variable: ignored
        "REPRO_SORT_FREE": "off",
        "REPRO_SORT_FREE_MAX_DOMAIN": "4096",
        "REPRO_BUCKETIZE_MIN_QUERIES": "16",
        "REPRO_SEGSUM_MAX_GROUPS": "128",
    })
    assert pol.use_pallas is True and pol.pallas_enabled()
    # interpret mode is set in code only, never from the environment
    assert pol.interpret is None and pol.interpret_mode()
    assert pol.enable_sort_free is False
    assert pol.sort_free_max_domain == 4096
    assert pol.bucketize_min_queries == 16
    assert pol.segment_sum_max_groups == 128
    auto = dispatch.policy_from_env({})
    assert auto.use_pallas is None and auto.enable_sort_free is True
    # auto on this container (CPU backend): Pallas off, interpret on
    assert not auto.pallas_enabled() and auto.interpret_mode()


def _count_kernel(monkeypatch, name):
    calls = []
    real = getattr(dispatch, name)

    def wrapper(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(dispatch, name, wrapper)
    return calls


def test_dispatch_bucketize_routing(rng, monkeypatch):
    assert "bucketize_kernel" in dispatch.OFF_TPU_ROUTE
    calls = _count_kernel(monkeypatch, "bucketize_count_kernel")
    b = jnp.asarray(np.sort(rng.integers(0, 100, 50)).astype(np.int32))
    q = jnp.asarray(rng.integers(0, 100, 64).astype(np.int32))
    want = np.asarray(jnp.searchsorted(b, q, side="right"))
    # policy off (CPU auto): XLA path
    got = dispatch.bucketize(b, q, right=True)
    assert not calls
    np.testing.assert_array_equal(np.asarray(got), want)
    # forced on, threshold lowered: kernel path, identical result
    with dispatch.overrides(use_pallas=True, interpret=True,
                            bucketize_min_queries=1):
        got = dispatch.bucketize(b, q, right=True)
    assert len(calls) == 1
    np.testing.assert_array_equal(np.asarray(got), want)
    # below the query threshold: stays on XLA even when forced on
    with dispatch.overrides(use_pallas=True, interpret=True,
                            bucketize_min_queries=1000):
        dispatch.bucketize(b, q, right=True)
    assert len(calls) == 1
    # more boundaries than one counting tile: XLA's searchsorted
    wide = jnp.asarray(np.arange(dispatch.COUNT_KERNEL_MAX_BOUNDARIES + 1,
                                 dtype=np.int32))
    with dispatch.overrides(use_pallas=True, interpret=True,
                            bucketize_min_queries=1):
        got = dispatch.bucketize(wide, q, right=True)
    assert len(calls) == 1
    np.testing.assert_array_equal(
        np.asarray(got), np.searchsorted(np.asarray(wide), np.asarray(q),
                                         side="right"))


def test_dispatch_segment_sum_routing(rng, monkeypatch):
    calls = _count_kernel(monkeypatch, "segment_sum_kernel")
    v = jnp.asarray(rng.random(256).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 8, 256).astype(np.int32))
    want = np.zeros(8, np.float32)
    np.add.at(want, np.asarray(ids), np.asarray(v))
    with dispatch.overrides(use_pallas=True, interpret=True):
        got = dispatch.segment_sum(v, ids, 8)
        assert len(calls) == 1
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4)
        # integer values keep exact scatter arithmetic (no f32 matmul)
        got_i = dispatch.segment_sum(ids, ids, 8)
        assert len(calls) == 1 and got_i.dtype == jnp.int32
        # group count beyond the VMEM bound: scatter fallback
        dispatch.segment_sum(v, ids, dispatch.policy().segment_sum_max_groups + 1)
        assert len(calls) == 1


def _rle_expansion_jaxpr(*args):
    """Run expansion as the engine stages it, under the on-chip policy."""
    from repro.core.primitives import rle_to_plain
    with dispatch.overrides(use_pallas=True, interpret=True):
        return str(jax.make_jaxpr(
            lambda v, s, e, n: rle_to_plain(v, s, e, n, args[-1]))(*args[:4]))


def test_dispatch_rle_decode_routing(rng):
    """The RLE kernel is off the route (Mosaic refuses its 1-D gather):
    run expansion stages no kernel even with Pallas forced on."""
    assert "rle_decode_kernel" in dispatch.OFF_TPU_ROUTE
    nrows = 8192
    starts = np.sort(rng.choice(nrows, 16, replace=False)).astype(np.int32)
    ends = np.concatenate([starts[1:] - 1, [nrows - 1]]).astype(np.int32)
    vals = rng.integers(0, 9, 16).astype(np.int32)
    args = (jnp.asarray(vals), jnp.asarray(starts), jnp.asarray(ends),
            jnp.asarray(16, jnp.int32), nrows)
    assert "pallas_call" not in _rle_expansion_jaxpr(*args)
    # the kernel itself still matches its reference in interpret mode
    got = ops.rle_decode(*args, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.ref_rle_decode(*args)))


def test_dispatch_routed_pipeline_matches_unrouted(rng):
    """End-to-end: a filter+groupby query with every dispatch route forced
    through the interpret-mode kernels must equal the pure-XLA run."""
    from repro.core import compress
    from repro.core.plan import Query, col
    from repro.core.table import Table
    n = 20_000
    data = {"k": np.sort(rng.integers(0, 6, n)).astype(np.int32),
            "v": rng.random(n).astype(np.float32)}
    cfg = compress.CompressionConfig(plain_threshold=1000)

    def run_once():
        t = Table.from_arrays(data, cfg=cfg)
        return (Query(t).filter(col("v") > 0.5)
                .groupby(["k"], {"s": ("sum", "v"), "c": ("count", None)},
                         num_groups_cap=8).run())

    base = run_once()
    with dispatch.overrides(use_pallas=True, interpret=True,
                            bucketize_min_queries=1, unpack_min_vals=1):
        routed = run_once()
    assert int(base.num_groups) == int(routed.num_groups)
    np.testing.assert_array_equal(np.asarray(base.keys["k"]),
                                  np.asarray(routed.keys["k"]))
    np.testing.assert_array_equal(np.asarray(base.aggs["c"]),
                                  np.asarray(routed.aggs["c"]))
    np.testing.assert_allclose(np.asarray(base.aggs["s"]),
                               np.asarray(routed.aggs["s"]), rtol=1e-4)
