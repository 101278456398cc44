"""Sub-byte bit-packed columns (DESIGN.md §11).

Four layers, mirroring the structure of tests/test_pallas_kernels.py:

  1. pack/unpack round-trip — hypothesis property across bit widths 1-32
     (width-32 modular passthrough, empty buffers, pow2 padding tails,
     negative centered values) + interpret-mode kernel parity,
  2. dispatch routing units (unpack, bucketize over packed queries, the
     fused kernels off the TPU route, REPRO_PACK* policy parsing),
  3. engine conformance — packed ingest must be BIT-IDENTICAL to the
     unpacked path for all six encodings, single-table and partitioned,
  4. the transfer contract — packed partitions ship strictly fewer H2D
     bytes (>= 1.5x on a dict-heavy schema), the streamed pytree contains
     NO full-width copy of a packed buffer, and ``rows_for_budget`` fits
     strictly more rows per budget with packing on.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import compress, primitives
from repro.core.encodings import PackedColumn, unpack_values
from repro.core.partition import (
    PartitionedQuery,
    PartitionedTable,
    rows_for_budget,
)
from repro.core.plan import Query, col
from repro.core.table import Table
from repro.kernels import dispatch, ops, ref
from repro.kernels import unpack as unpack_mod

# ---------------------------------------------------------------------------
# 1. pack/unpack round-trip
# ---------------------------------------------------------------------------


def _roundtrip_case(b: int, n: int, lo: int, seed: int):
    if b == 32:
        lo, hi = -(2**31), 2**31 - 1  # full-range modular passthrough
    else:
        hi = lo + (1 << b) - 1
    rng = np.random.default_rng(seed)
    v = rng.integers(lo, hi, n, endpoint=True).astype(np.int64)
    words = compress.pack_array(v, lo, b)
    assert words.shape == ((n * b + 31) // 32,)
    got = np.asarray(ref.ref_unpack(jnp.asarray(words), b, lo, n))
    np.testing.assert_array_equal(got, v.astype(np.int32))


def test_pack_unpack_roundtrip_property():
    """Hypothesis (when available): unpack(pack(v)) == v for widths 1-32,
    any offset sign, empty and ragged lengths."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, strategies as st

    @given(st.integers(1, 32), st.integers(0, 300),
           st.integers(-(2**30), 2**30), st.integers(0, 2**16))
    def prop(b, n, lo, seed):
        _roundtrip_case(b, n, lo, seed)

    prop()


@pytest.mark.parametrize("b", list(range(1, 33)))
def test_pack_unpack_roundtrip_sweep(b):
    """Deterministic width sweep 1-32 (runs with or without hypothesis):
    empty, single, ragged tail vs lane boundaries, negative offsets."""
    for n, lo, seed in ((0, 0, 0), (1, -3, 1), (37, -(1 << (b - 1)), 2),
                        (257, 5, 3)):
        _roundtrip_case(b, n, lo if b < 32 else 0, seed)


@pytest.mark.parametrize("b", [1, 5, 9, 13, 24, 31, 32])
def test_unpack_kernel_parity(rng, b):
    """Interpret-mode kernel == jnp ref, non-tile-multiple count, negative
    offset (centered values), straddling lanes."""
    n = 2049  # VAL_TILE + 1: grid padding tail
    lo = -(1 << (b - 1)) if b < 32 else -(2**31)
    v = rng.integers(lo, lo + (1 << b) - 1 if b < 32 else 2**31 - 1,
                     n, endpoint=True).astype(np.int64)
    words = jnp.asarray(compress.pack_array(v, lo, b))
    got = ops.unpack(words, b, lo, n, use_pallas=True, interpret=True)
    want = ref.ref_unpack(words, b, lo, n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_unpack_empty():
    words = jnp.zeros((0,), jnp.uint32)
    assert ops.unpack(words, 7, 0, 0, use_pallas=True, interpret=True).shape == (0,)
    assert ref.ref_unpack(words, 7, 0, 0).shape == (0,)


def test_pack_bit_width_exact():
    assert compress.pack_bit_width(0, 0) == 1
    assert compress.pack_bit_width(0, 1) == 1
    assert compress.pack_bit_width(0, 511) == 9  # the 9-bit dict code
    assert compress.pack_bit_width(-100, 100) == 8
    assert compress.pack_bit_width(-(2**31), 2**31 - 1) == 32
    assert compress.pack_bit_width(5, 4) == 33  # empty domain: never packs


def test_pow2_padding_tail_roundtrip(rng):
    """Partition-style buffers: pow2-padded rows replicating the last value
    round-trip exactly through the packed layout."""
    v = rng.integers(3, 40, 100).astype(np.int64)
    padded = np.concatenate([v, np.repeat(v[-1:], 28)])  # 128 = pow2
    words = compress.pack_array(padded, 3, 6)
    got = np.asarray(ref.ref_unpack(jnp.asarray(words), 6, 3, 128))
    np.testing.assert_array_equal(got, padded.astype(np.int32))


# ---------------------------------------------------------------------------
# 2. dispatch routing
# ---------------------------------------------------------------------------


def _count_kernel(monkeypatch, name):
    calls = []
    real = getattr(dispatch, name)

    def wrapper(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(dispatch, name, wrapper)
    return calls


def _packed(rng, n=100, b=5, lo=-7):
    v = rng.integers(lo, lo + (1 << b) - 1, n, endpoint=True).astype(np.int64)
    words = jnp.asarray(compress.pack_array(v, lo, b))
    return v, PackedColumn(words=words, nrows=n, bit_width=b, offset=lo)


def test_policy_pack_env_knobs():
    pol = dispatch.policy_from_env({
        "REPRO_PACK": "0",
        "REPRO_PACK_MAX_BITS": "16",
        "REPRO_UNPACK_MIN_VALS": "64",
    })
    assert pol.enable_pack is False
    assert pol.pack_max_bits == 16
    assert pol.unpack_min_vals == 64
    auto = dispatch.policy_from_env({})
    assert auto.enable_pack is True and auto.pack_max_bits == 24


def test_dispatch_unpack_routing(rng, monkeypatch):
    calls = _count_kernel(monkeypatch, "unpack_kernel")
    v, pc = _packed(rng)
    got = dispatch.unpack(pc)  # CPU auto: inline XLA expression
    assert not calls
    np.testing.assert_array_equal(np.asarray(got), v.astype(np.int32))
    with dispatch.overrides(use_pallas=True, interpret=True, unpack_min_vals=1):
        got = dispatch.unpack(pc)
    assert len(calls) == 1
    np.testing.assert_array_equal(np.asarray(got), v.astype(np.int32))
    # below the size threshold: stays inline even when forced on
    with dispatch.overrides(use_pallas=True, interpret=True,
                            unpack_min_vals=1000):
        dispatch.unpack(pc)
    assert len(calls) == 1
    assert np.asarray(unpack_values(pc)).dtype == np.int32
    arr = jnp.arange(4)
    assert unpack_values(arr) is arr  # identity on raw buffers


def test_dispatch_bucketize_packed_routing(rng, monkeypatch):
    """Packed queries are unpacked first, then take the unpacked route:
    the fused unpack->bisect kernel is off the route (Mosaic refuses its
    1-D gather), so the counting kernel answers when Pallas is on."""
    assert "bucketize_packed_kernel" in dispatch.OFF_TPU_ROUTE
    calls = _count_kernel(monkeypatch, "bucketize_count_kernel")
    unpacks = _count_kernel(monkeypatch, "unpack_kernel")
    v, pc = _packed(rng, n=200, b=9, lo=0)
    bnd = jnp.asarray(np.sort(rng.integers(0, 512, 37)).astype(np.int32))
    want = np.searchsorted(np.asarray(bnd), v, side="right")
    got = dispatch.bucketize(bnd, pc, right=True)  # CPU auto: XLA
    assert not calls and not unpacks
    np.testing.assert_array_equal(np.asarray(got), want)
    with dispatch.overrides(use_pallas=True, interpret=True,
                            bucketize_min_queries=1, unpack_min_vals=1):
        got = dispatch.bucketize(bnd, pc, right=True)
    assert len(calls) == 1 and len(unpacks) == 1
    np.testing.assert_array_equal(np.asarray(got), want)
    # below the query threshold: no kernel even when forced
    with dispatch.overrides(use_pallas=True, interpret=True,
                            bucketize_min_queries=10_000):
        dispatch.bucketize(bnd, pc, right=True)
    assert len(calls) == 1


def test_dispatch_rle_decode_packed_routing(rng):
    nrows = 8192
    starts = np.sort(rng.choice(nrows, 16, replace=False)).astype(np.int32)
    ends = np.concatenate([starts[1:] - 1, [nrows - 1]]).astype(np.int32)
    vals = rng.integers(-5, 10, 16).astype(np.int64)
    words = jnp.asarray(compress.pack_array(vals, -5, 4))
    pc = PackedColumn(words=words, nrows=16, bit_width=4, offset=-5)
    args = (pc, jnp.asarray(starts), jnp.asarray(ends),
            jnp.asarray(16, jnp.int32), nrows)
    # off the route whatever the policy: run expansion over packed run
    # values stages no kernel (16 values stay below unpack_min_vals)
    assert "rle_decode_packed_kernel" in dispatch.OFF_TPU_ROUTE
    with dispatch.overrides(use_pallas=True, interpret=True):
        jaxpr = str(jax.make_jaxpr(
            lambda w, s, e, n: primitives.rle_to_plain(
                PackedColumn(words=w, nrows=16, bit_width=4, offset=-5),
                s, e, n, nrows))(words, *args[1:4]))
    assert "pallas_call" not in jaxpr
    # the kernel itself still matches its reference in interpret mode
    got = unpack_mod.rle_decode_packed_kernel(
        words, 4, -5, 16, *args[1:], interpret=True)
    want = ref.ref_rle_decode(jnp.asarray(vals.astype(np.int32)),
                              jnp.asarray(starts), jnp.asarray(ends),
                              jnp.asarray(16, jnp.int32), nrows)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# 3. engine conformance: six encodings, packed == unpacked, bit-identical
# ---------------------------------------------------------------------------

SIX_ENCODINGS = ["plain", "plain_dict", "rle", "index", "rle_index",
                 "plain_index"]


def _tables_for(rng, enc, n=12_000):
    """(unpacked, packed) tables with the key/value columns forced to one
    of the six ingest encodings."""
    cfg = compress.CompressionConfig(plain_threshold=1000)
    k = np.repeat(rng.integers(0, 40, n // 8 + 1), 8)[:n].astype(np.int32)
    v = rng.integers(0, 2000, n).astype(np.int32)
    f = rng.random(n).astype(np.float32)
    if enc == "plain_dict":
        vocab = np.array([f"key_{i:03d}" for i in range(40)])
        data = {"k": vocab[k], "v": v, "f": f}
        kwargs = {}
    else:
        if enc == "plain_index":
            v = np.where(rng.random(n) < 0.002, 1_500_000_000, v).astype(np.int32)
        data = {"k": k, "v": v, "f": f}
        kwargs = {"encodings": {"k": enc, "v": enc}}
    t0 = Table.from_arrays(data, cfg=cfg, **kwargs)
    t1 = Table.from_arrays(data, cfg=cfg, pack=True, **kwargs)
    return t0, t1


def _has_packed_leaf(tree) -> bool:
    found = []
    jax.tree_util.tree_map(
        lambda _: None, tree,
        is_leaf=lambda x: found.append(isinstance(x, PackedColumn)) and False)
    return any(found)


@pytest.mark.parametrize("enc", SIX_ENCODINGS)
def test_six_encodings_bit_identical_single(rng, enc):
    t0, t1 = _tables_for(rng, enc)
    assert _has_packed_leaf(t1.columns), f"{enc}: nothing packed"
    for name in t0.columns:
        np.testing.assert_array_equal(t0.decode(name), t1.decode(name))

    def run(t):
        kf = col("k") == ("key_010" if enc == "plain_dict" else 10)
        q = (Query(t).filter(kf | (col("v") > 500))
             .groupby(["k"], {"s": ("sum", "v"), "a": ("avg", "f"),
                              "c": ("count", None)}, num_groups_cap=64))
        return q.run()

    r0, r1 = run(t0), run(t1)
    assert int(r0.num_groups) == int(r1.num_groups)
    for name in ("s", "a", "c"):  # float32 ops identical => bit-identical
        np.testing.assert_array_equal(np.asarray(r0.aggs[name]),
                                      np.asarray(r1.aggs[name]))
    np.testing.assert_array_equal(np.asarray(r0.keys["k"]),
                                  np.asarray(r1.keys["k"]))

    o0 = Query(t0).filter(col("v") > 100).order_by(
        "v", descending=True, limit=9, cols=["k"]).run()
    o1 = Query(t1).filter(col("v") > 100).order_by(
        "v", descending=True, limit=9, cols=["k"]).run()
    np.testing.assert_array_equal(o0.positions, o1.positions)
    for name in o0.columns:
        np.testing.assert_array_equal(o0.columns[name], o1.columns[name])


@pytest.mark.parametrize("enc", SIX_ENCODINGS)
def test_six_encodings_bit_identical_partitioned(rng, enc):
    cfg = compress.CompressionConfig(plain_threshold=1000)
    n = 12_000
    k = np.sort(rng.integers(0, 40, n)).astype(np.int32)
    v = rng.integers(0, 2000, n).astype(np.int32)
    if enc == "plain_index":
        v = np.where(rng.random(n) < 0.002, 1_500_000_000, v).astype(np.int32)
    vocab = np.array([f"key_{i:03d}" for i in range(40)])
    data = {"k": vocab[k] if enc == "plain_dict" else k, "v": v}
    encs = (None if enc == "plain_dict"
            else {"k": enc, "v": enc if enc != "plain_index" else "plain_index"})

    def run(pack):
        pt = PartitionedTable.from_arrays(data, cfg=cfg, num_partitions=4,
                                          encodings=encs, pack=pack)
        q = (PartitionedQuery(pt).filter(col("v") <= 1800)
             .groupby(["k"], {"s": ("sum", "v"), "c": ("count", None)},
                      num_groups_cap=64))
        return q.run(), q.trace_count

    r0, tc0 = run(False)
    r1, tc1 = run(True)
    assert r0.num_groups == r1.num_groups
    np.testing.assert_array_equal(r0.keys["k"], r1.keys["k"])
    np.testing.assert_array_equal(r0.aggs["s"], r1.aggs["s"])
    np.testing.assert_array_equal(r0.aggs["c"], r1.aggs["c"])
    # global pack domains: packing must not add jit cache entries
    assert tc1 <= tc0 + 0


def test_packed_pipeline_forced_kernels_match(rng):
    """Every dispatch route forced through the interpret-mode kernels on a
    packed table equals the pure-XLA run (the §11 fusion points)."""
    t0, t1 = _tables_for(rng, "plain_dict", n=20_000)

    def run():
        return (Query(t1).filter(col("v") > 300)
                .groupby(["k"], {"s": ("sum", "v"), "c": ("count", None)},
                         num_groups_cap=64).run())

    base = run()
    with dispatch.overrides(use_pallas=True, interpret=True,
                            bucketize_min_queries=1, unpack_min_vals=1):
        routed = run()
    np.testing.assert_array_equal(np.asarray(base.keys["k"]),
                                  np.asarray(routed.keys["k"]))
    np.testing.assert_array_equal(np.asarray(base.aggs["c"]),
                                  np.asarray(routed.aggs["c"]))
    np.testing.assert_allclose(np.asarray(base.aggs["s"]),
                               np.asarray(routed.aggs["s"]), rtol=1e-4)


def test_packed_join_semijoin_identical(rng):
    n = 30_000
    data = {"store": rng.integers(0, 500, n).astype(np.int32),
            "units": rng.integers(0, 100, n).astype(np.int32)}
    dim = Table.from_arrays({"store": np.arange(500, dtype=np.int32),
                             "tier": rng.integers(0, 5, 500).astype(np.int32)},
                            pack=True)  # packed dimension side too
    cfg = compress.CompressionConfig(plain_threshold=1000)
    whitelist = rng.choice(500, 40, replace=False).astype(np.int32)

    def run(pack):
        t = Table.from_arrays(data, cfg=cfg, pack=pack)
        return (Query(t).semi_join("store", whitelist)
                .join(dim, fk="store", cols=["tier"])
                .groupby(["tier"], {"s": ("sum", "units"),
                                    "c": ("count", None)},
                         num_groups_cap=8).run())

    r0, r1 = run(False), run(True)
    np.testing.assert_array_equal(np.asarray(r0.keys["tier"]),
                                  np.asarray(r1.keys["tier"]))
    np.testing.assert_array_equal(np.asarray(r0.aggs["s"]),
                                  np.asarray(r1.aggs["s"]))
    np.testing.assert_array_equal(np.asarray(r0.aggs["c"]),
                                  np.asarray(r1.aggs["c"]))


# ---------------------------------------------------------------------------
# 4. transfer contract + footprint accounting
# ---------------------------------------------------------------------------


@pytest.fixture
def transfer_bytes():
    # the SAME counting implementation the CI-gated benches use
    # (benchmarks.common.count_h2d), so metric and test cannot diverge
    from benchmarks.common import count_h2d

    rec = []
    with count_h2d(rec):
        yield rec


def _dict_heavy(rng, n=120_000):
    """The paper's dict-heavy shape: several low-cardinality string columns
    (9-bit codes shipping as int32 without packing) + narrow measures."""
    vocab = np.array([f"v{i:04d}" for i in range(500)])
    return {
        "a": vocab[rng.integers(0, 500, n)],
        "b": vocab[rng.integers(0, 500, n)],
        "c": vocab[rng.integers(0, 500, n)],
        "units": rng.integers(0, 100, n).astype(np.int32),
    }


def test_transfer_bytes_reduced_and_no_fullwidth_leaves(rng, transfer_bytes):
    data = _dict_heavy(rng)
    cfg = compress.CompressionConfig(plain_threshold=1000)

    def run(pack):
        pt = PartitionedTable.from_arrays(data, cfg=cfg, num_partitions=8,
                                          pack=pack)
        q = (PartitionedQuery(pt).filter(col("units") < 90)
             .groupby(["a"], {"s": ("sum", "units"), "c": ("count", None)},
                      num_groups_cap=512))
        transfer_bytes.clear()
        r = q.run()
        return pt, r, sum(transfer_bytes)

    _, r0, b0 = run(False)
    pt1, r1, b1 = run(True)
    np.testing.assert_array_equal(r0.keys["a"], r1.keys["a"])
    np.testing.assert_array_equal(r0.aggs["s"], r1.aggs["s"])
    np.testing.assert_array_equal(r0.aggs["c"], r1.aggs["c"])
    assert b0 / b1 >= 1.5, f"H2D bytes only {b0}/{b1} = {b0/b1:.2f}x"

    # no full-width materialization BEFORE the fused consumers: the pytree
    # device_put streams holds uint32 word buffers strictly smaller than
    # the logical row count for every packed 9-bit code column; the only
    # nrows-sized leaves are genuinely unpackable (none here are float)
    n_part = pt1.partitions[0].padded_rows
    for name in ("a", "b", "c"):
        colv = pt1.partitions[0].table.columns[name]
        leaf = colv.values if hasattr(colv, "values") else colv
        assert isinstance(leaf, PackedColumn)
        assert leaf.words.shape[0] * 32 <= n_part * 10  # 9 bits + lane pad
        assert leaf.words.dtype == jnp.uint32
    # and the byte accounting agrees with what was actually shipped (the
    # scalar n/offset leaves ride along but are noise at any real scale)
    assert abs(b1 - pt1.nbytes()) <= 0.01 * pt1.nbytes()
    assert pt1.nbytes_unpacked() > pt1.nbytes()
    assert pt1.max_partition_nbytes(unpacked=True) > pt1.max_partition_nbytes()


def test_rows_for_budget_packed_fits_more(rng):
    data = _dict_heavy(rng, n=10_000)
    budget = 1 << 20
    plain_rows = rows_for_budget(data, budget)
    packed_rows = rows_for_budget(data, budget, pack=True)
    assert packed_rows > plain_rows
    # 3x 9-bit codes + 7-bit measure = 34 bits vs 128 bits unpacked
    assert packed_rows >= plain_rows * 3
    # and the budget is actually respected by packed ingest: partitions
    # sized by the packed rule must not exceed the budget in packed bytes
    pt = PartitionedTable.from_arrays(data, partition_rows=packed_rows,
                                      cfg=compress.CompressionConfig(
                                          plain_threshold=1000), pack=True)
    assert pt.max_partition_nbytes() <= budget * 1.25  # pow2 padding slack


def test_nbytes_packed_vs_unpacked_side_by_side(rng):
    data = _dict_heavy(rng, n=20_000)
    cfg = compress.CompressionConfig(plain_threshold=1000)
    t0 = Table.from_arrays(data, cfg=cfg)
    t1 = Table.from_arrays(data, cfg=cfg, pack=True)
    assert t1.nbytes() < t0.nbytes()
    # the unpacked accounting is the HONEST reference: what whole-dtype
    # narrowing of the same domains actually occupies — i.e. the real
    # unpacked ingest's footprint, not a flat int32 overstatement
    assert abs(t1.nbytes_unpacked() - t0.nbytes()) <= 0.01 * t0.nbytes()
    assert t1.nbytes_unpacked() > t1.nbytes()


def test_pack_disabled_by_policy_env(rng):
    data = {"k": rng.integers(0, 100, 5000).astype(np.int32)}
    with dispatch.overrides(enable_pack=False):
        t = Table.from_arrays(data, pack=True)
    assert not _has_packed_leaf(t.columns)


def test_rows_for_budget_honors_pack_kill_switch(rng):
    """REPRO_PACK=0 disables packing at ingest, so sizing by packed bits
    would silently overrun the device budget — the kill switch must gate
    rows_for_budget too (regression)."""
    data = _dict_heavy(rng, n=5_000)
    with dispatch.overrides(enable_pack=False):
        assert (rows_for_budget(data, 1 << 20, pack=True)
                == rows_for_budget(data, 1 << 20))


def test_pack_consistent_across_heterogeneous_partitions(rng):
    """Partitions whose LOCAL value ranges narrow to different dtypes
    (int8 vs int16) must still pack identically at the GLOBAL domain
    width — a partition-local profit check would leave one partition
    unpacked (heterogeneous pytrees, one jit trace per structure)
    (regression)."""
    n = 8192
    v = np.concatenate([rng.integers(0, 100, n // 2),    # local int8 range
                        rng.integers(0, 300, n // 2)])   # local int16 range
    data = {"v": v.astype(np.int32), "x": rng.integers(0, 50, n).astype(np.int32)}
    pt = PartitionedTable.from_arrays(
        data, cfg=compress.CompressionConfig(plain_threshold=100),
        num_partitions=2, pack=True)
    leaves = []
    for p in pt.partitions:
        leaf = p.table.columns["v"]
        leaf = leaf.values if hasattr(leaf, "values") else leaf
        leaves.append(leaf)
    assert all(isinstance(x, PackedColumn) for x in leaves), leaves
    assert len({x.bit_width for x in leaves}) == 1  # global 9-bit width
    q = (PartitionedQuery(pt).filter(col("v") < 250)
         .groupby(["x"], {"c": ("count", None)}, num_groups_cap=64))
    r = q.run()
    assert q.trace_count == 1  # one shared program, no structure split
    assert int(sum(np.asarray(r.aggs["c"]))) == int((v < 250).sum())


# ---------------------------------------------------------------------------
# 5. exact-integer ColumnStats / _narrow_int_dtype (satellite regression)
# ---------------------------------------------------------------------------


def test_column_stats_exact_past_2_53():
    """float64 vmin/vmax silently rounds 2**53 + 1 -> 2**53; the stats must
    keep integer min/max in the integer domain."""
    stats = compress.analyze(np.array([2**53, 2**53 + 1], np.int64))
    assert stats.vmax == 2**53 + 1 and isinstance(stats.vmax, int)
    assert stats.vmin == 2**53


def test_narrow_int_dtype_exact_at_domain_edges():
    # huge-magnitude narrow domain: float rounding of the endpoints used to
    # shift the center/span and pick a wider (or wrapping) dtype
    assert compress._narrow_int_dtype(2**60, 2**60 + 200) == np.dtype(np.int8)
    assert compress._narrow_int_dtype(2**60, 2**60 + 2**20) == np.dtype(np.int32)
    assert compress._narrow_int_dtype(-(2**62), 2**62) == np.dtype(np.int64)
    # the exact center makes the centered values round-trip
    lo, hi = 2**60, 2**60 + 200
    center, span = compress._center_span(lo, hi)
    assert center == 2**60 + 100 and span == 100
    vals = np.array([lo, lo + 7, hi], np.int64)
    narrowed = (vals - center).astype(np.int8)
    np.testing.assert_array_equal(narrowed.astype(np.int64) + center, vals)


def test_int32_edge_centering_roundtrip():
    """Values spanning the full int32 domain still encode/decode exactly
    (the decision must be int32, never a wrapping narrow dtype)."""
    vals = np.array([-(2**31), 0, 2**31 - 1], np.int64)
    assert compress._narrow_int_dtype(int(vals.min()),
                                      int(vals.max())) == np.dtype(np.int64)
    t = Table.from_arrays({"v": vals})  # dictionary-encodes the wide ints
    np.testing.assert_array_equal(t.decode("v"), vals)
