"""End-to-end smoke run of the compressed-domain query engine on one TPU.

    python3 chip_smoke.py              # needs a TPU; exits non-zero without one
    python3 chip_smoke.py --rehearse   # toy size on the CPU, kernels interpreted

Phases, in order, in this one process:

1. Refuse to run without a TPU; assert that the default dispatch policy
   turns Pallas on and interpret mode off; place the compile cache.
2. Ingest a seeded production-star fact table (paper §9.2 shape: 15 key
   columns plus a float32 measure, ``benchmarks.bench_production``) into a
   bit-packed ``PartitionedTable``: 2^26 rows in partitions of 2^22.
3. Stream three queries through ``PartitionedQuery.run()``: a filter +
   group-by, the production Q1 template (7 semi-joins, a PK-FK join, a SUM
   group-by) and a ranked ``order_by(..., limit=10)``.
4. Serve four concurrent submissions through one ``QueryServer`` and call
   ``result()`` on every ticket.
5. Run each kernel dispatch routes on the TPU beside its XLA twin.

Every answer is checked against a float64 numpy reference on the same
data: counts, group keys and top-k positions exactly, float sums to
rtol=1e-5. Any failure raises, and the exit code is non-zero. The last
line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

RTOL = 1e-5
Q1_SEMI_JOINS = {"c2": 64, "c3": 256, "c4": 1000, "c5": 4000, "c8": 50,
                 "c9": 200, "c11": 30}


def check(cond, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# float64 numpy reference
# ---------------------------------------------------------------------------


def ref_groupby(keys: np.ndarray, measure: np.ndarray, mask: np.ndarray):
    uniq, inv = np.unique(keys[mask], return_inverse=True)
    sums = np.bincount(inv, weights=measure[mask].astype(np.float64),
                       minlength=len(uniq))
    return uniq, sums, np.bincount(inv, minlength=len(uniq))


def check_groupby(name: str, res, key: str, want) -> None:
    uniq, sums, counts = want
    check(int(res.num_groups) == len(uniq),
          f"{name}: {res.num_groups} groups, reference {len(uniq)}")
    np.testing.assert_array_equal(np.asarray(res.keys[key]), uniq,
                                  err_msg=f"{name}: group keys")
    np.testing.assert_array_equal(np.asarray(res.aggs["c"]), counts,
                                  err_msg=f"{name}: counts")
    np.testing.assert_allclose(np.asarray(res.aggs["s"], np.float64), sums,
                               rtol=RTOL, err_msg=f"{name}: sums")
    say(f"  {name}: {len(uniq)} groups, {int(counts.sum())} rows "
        "match the float64 reference")


# ---------------------------------------------------------------------------
# kernels beside their XLA twins
# ---------------------------------------------------------------------------


def kernel_parity(n: int, interpret: bool, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from functools import partial

    from repro.core import compress
    from repro.kernels import ref
    from repro.kernels.bucketize import bucketize_count_kernel
    from repro.kernels.dispatch import (COUNT_KERNEL_MAX_BOUNDARIES,
                                        MAX_MATMUL_SEGMENTS)
    from repro.kernels.segment_reduce import segment_sum_kernel
    from repro.kernels.topk import MAX_KERNEL_K, topk_kernel
    from repro.kernels.unpack import unpack_kernel

    rng = np.random.default_rng(seed + 2)
    for b in (7, 24):
        v = rng.integers(-5, (1 << b) - 5, n)
        words = jnp.asarray(compress.pack_array(v, -5, b))
        got = jax.jit(partial(unpack_kernel, bit_width=b, nvals=n,
                              interpret=interpret))(words, offset=-5)
        want = jax.jit(partial(ref.ref_unpack, bit_width=b, nvals=n))(
            words, offset=-5)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(got), v.astype(np.int32))
    say(f"  unpack_kernel == ref_unpack, {n} values at 7 and 24 bits")

    nb = COUNT_KERNEL_MAX_BOUNDARIES
    bounds = jnp.asarray(np.sort(rng.integers(0, 10_000, nb)).astype(np.int32))
    qs = jnp.asarray(rng.integers(-10, 10_010, n).astype(np.int32))
    for right in (True, False):
        got = jax.jit(partial(bucketize_count_kernel, right=right,
                              interpret=interpret))(bounds, qs)
        want = jnp.searchsorted(bounds, qs, side="right" if right else "left")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    say(f"  bucketize_count_kernel == searchsorted, {n} queries, "
        f"{nb} boundaries, both sides")

    g = MAX_MATMUL_SEGMENTS
    vals = rng.random(n).astype(np.float32)
    ids = rng.integers(0, g + 1, n).astype(np.int32)  # id g: dropped
    got = jax.jit(partial(segment_sum_kernel, num_segments=g,
                          interpret=interpret))(jnp.asarray(vals),
                                                jnp.asarray(ids))
    twin = jnp.zeros((g,), jnp.float32).at[jnp.asarray(ids)].add(
        jnp.asarray(vals), mode="drop")
    exact = np.bincount(ids, weights=vals.astype(np.float64),
                        minlength=g + 1)[:g]
    np.testing.assert_allclose(np.asarray(got), np.asarray(twin), rtol=RTOL)
    np.testing.assert_allclose(np.asarray(got), exact, rtol=RTOL)
    say(f"  segment_sum_kernel == scatter-add, {n} rows into {g} groups")

    for dtype in (np.float32, np.int32):
        v = jnp.asarray(rng.integers(0, 1000, n).astype(dtype))  # many ties
        for k in (10, MAX_KERNEL_K):
            gv, gi = jax.jit(partial(topk_kernel, k=k, interpret=interpret))(v)
            wv, wi = jax.lax.top_k(v, k)
            np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
            np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    say(f"  topk_kernel == lax.top_k, {n} rows, k=10 and {MAX_KERNEL_K}, "
        "float32 and int32 with ties")


# ---------------------------------------------------------------------------
# main path
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="toy size on the CPU with kernels interpreted")
    ap.add_argument("--seed", type=int, default=0, help="data seed")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX finds no TPU (first device: {dev.platform});"
              " nothing was run", file=sys.stderr)
        return 2

    from repro import compile_cache
    from repro.core import compress, telemetry
    from repro.core.partition import PartitionedQuery, PartitionedTable
    from repro.core.plan import col
    from repro.core.serve import QueryServer
    from repro.core.table import Table
    from repro.kernels import dispatch
    from benchmarks.bench_production import _semi_keys, make_star

    if args.rehearse:
        dispatch.set_policy(dispatch.DispatchPolicy(use_pallas=True,
                                                    interpret=True))
        rows, part_rows, kernel_rows = 1 << 17, 1 << 15, 1 << 14
    else:
        rows, part_rows, kernel_rows = 1 << 26, 1 << 22, 1 << 22
    pol = dispatch.policy()
    check(pol.pallas_enabled(), "dispatch policy routes no Pallas kernel")
    check(pol.interpret_mode() == args.rehearse,
          f"interpret mode is {pol.interpret_mode()}")
    dispatch.set_policy(dataclasses.replace(pol, enable_trace=True))
    say(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"pallas on, interpret {pol.interpret_mode()}")
    say(f"compile cache: {compile_cache.configure()}")

    # -- ingest ------------------------------------------------------------
    t0 = time.perf_counter()
    data = make_star(np.random.default_rng(args.seed), rows)
    t1 = time.perf_counter()
    table = PartitionedTable.from_arrays(
        data, cfg=compress.CompressionConfig(plain_threshold=1000),
        partition_rows=part_rows, pack=True)
    t2 = time.perf_counter()
    raw = rows * 4 * len(data)
    say(f"ingest: {rows} rows x {len(data)} columns ({raw / 2**30:.2f} GiB "
        f"uncompressed) -> {len(table.partitions)} partitions of "
        f"{part_rows} rows, {table.nbytes()} bytes packed "
        f"({table.nbytes_unpacked()} unpacked)")
    say(f"  smoke timings, not a metric: make_star {t1 - t0:.1f} s, "
        f"ingest {t2 - t1:.1f} s")

    measure = data["measure"]
    key_rng = np.random.default_rng(args.seed + 1)
    q1_keys = {c: _semi_keys(key_rng, card, 0.5)
               for c, card in Q1_SEMI_JOINS.items()}
    c9_keys = _semi_keys(key_rng, 200, 0.6)
    dim_c6 = Table.from_arrays({
        "c6": np.arange(16000, dtype=np.int32),
        "d6_cat": (np.arange(16000, dtype=np.int32) % 97).astype(np.int32),
    }, cfg=compress.CompressionConfig(plain_threshold=1000))

    def q_filter_groupby(q):
        return (q.filter(col("c1") < 8)
                .groupby(["c0"], {"s": ("sum", "measure"),
                                  "c": ("count", None)}, num_groups_cap=8))

    def q_production_q1(q):
        for c, keys in q1_keys.items():
            q = q.semi_join(c, keys)
        q = q.join(dim_c6, fk="c6", cols=["d6_cat"])
        return q.groupby(["d6_cat"], {"s": ("sum", "measure"),
                                      "c": ("count", None)},
                         num_groups_cap=128)

    def q_semi_groupby(q):
        return (q.semi_join("c9", c9_keys)
                .groupby(["c12"], {"s": ("sum", "measure"),
                                   "c": ("count", None)}, num_groups_cap=32))

    def q_aggregate(q):
        return (q.filter(col("c13") < 4)
                .aggregate({"s": ("sum", "measure"), "c": ("count", None)}))

    want_fg = ref_groupby(data["c0"], measure, data["c1"] < 8)
    q1_mask = np.ones(rows, bool)
    for c, keys in q1_keys.items():
        q1_mask &= np.isin(data[c], keys)
    want_q1 = ref_groupby(data["c6"] % 97, measure, q1_mask)
    want_sg = ref_groupby(data["c12"], measure, np.isin(data["c9"], c9_keys))
    agg_mask = data["c13"] < 4
    want_agg = (measure[agg_mask].astype(np.float64).sum(),
                int(agg_mask.sum()))
    top = np.lexsort((np.arange(rows), -measure.astype(np.float64)))[:10]

    # -- streamed queries --------------------------------------------------
    say("streamed PartitionedQuery.run():")
    t0 = time.perf_counter()
    check_groupby("filter + group-by", q_filter_groupby(
        PartitionedQuery(table)).run(), "c0", want_fg)
    check_groupby("production Q1 (7 semi-joins, PK-FK join, SUM group-by)",
                  q_production_q1(PartitionedQuery(table)).run(), "d6_cat",
                  want_q1)
    ranked_q = PartitionedQuery(table).order_by("measure", descending=True,
                                                limit=10)
    ranked = ranked_q.run()
    np.testing.assert_array_equal(ranked.positions, top,
                                  err_msg="top-10 positions")
    np.testing.assert_array_equal(ranked.columns["measure"], measure[top],
                                  err_msg="top-10 values")
    say(f"  order_by(measure desc, limit=10): positions exact, "
        f"{ranked_q.last_stats.get('executed')} of "
        f"{len(table.partitions)} partitions executed")
    say(f"  smoke timing, not a metric: {time.perf_counter() - t0:.1f} s "
        "for the three streamed queries, compiles included")

    # -- served queries ----------------------------------------------------
    say("QueryServer, four concurrent submissions:")
    t0 = time.perf_counter()
    with QueryServer(table) as server:
        staged = [f(server.query()) for f in (q_filter_groupby,
                                              q_production_q1,
                                              q_semi_groupby, q_aggregate)]
        with ThreadPoolExecutor(max_workers=len(staged)) as pool:
            futures = [pool.submit(lambda q: server.result(server.submit(q)),
                                   q) for q in staged]
            served = [f.result() for f in futures]
        serve_stats = server.stats()
    check_groupby("served filter + group-by", served[0], "c0", want_fg)
    check_groupby("served production Q1", served[1], "d6_cat", want_q1)
    check_groupby("served semi-join + group-by", served[2], "c12", want_sg)
    check(int(np.asarray(served[3]["c"])) == want_agg[1],
          f"served aggregate count {served[3]['c']} != {want_agg[1]}")
    np.testing.assert_allclose(float(np.asarray(served[3]["s"])),
                               want_agg[0], rtol=RTOL,
                               err_msg="served aggregate sum")
    say(f"  served aggregate: count {want_agg[1]} exact, sum within rtol")
    say(f"  server: {json.dumps(serve_stats, default=str, sort_keys=True)}")
    say(f"  smoke timing, not a metric: {time.perf_counter() - t0:.1f} s "
        "for the four served queries")

    # -- kernels beside their XLA twins ------------------------------------
    say("routed kernels beside their XLA twins:")
    kernel_parity(kernel_rows, pol.interpret_mode(), args.seed)

    counters = telemetry.registry().counters()
    routes = {k: int(v) for k, v in sorted(counters.items())
              if k.startswith("route.")}
    say(f"h2d: {int(counters.get('h2d_calls', 0))} transfers, "
        f"{int(counters.get('h2d_bytes', 0))} bytes")
    say(f"routes taken while tracing the queries: {json.dumps(routes)}")
    mem = dev.memory_stats() or {}
    say(f"peak_bytes_in_use: {mem.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
