"""Training substrate + data pipeline: fault tolerance, checkpoints, resume."""
import math
import os
import tempfile

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.train as T
from repro import configs
from repro.data import (CorpusConfig, DataPipeline, PipelineConfig,
                        build_synthetic_corpus, corpus_stats)
from repro.train.step import init_train_state


@pytest.fixture(scope="module")
def trained():
    cfg = configs.get_smoke_config("smollm_360m")
    tcfg = T.TrainConfig(adamw=T.AdamWConfig(lr=1e-3, warmup_steps=2,
                                             total_steps=60), grad_accum=2)
    state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
    step = jax.jit(T.make_train_step(cfg, tcfg))
    rng = np.random.default_rng(0)
    tok = rng.integers(0, cfg.vocab_size, (4, 16))
    batch = {"tokens": jnp.asarray(tok, jnp.int32),
             "labels": jnp.asarray(tok, jnp.int32)}
    losses = []
    for _ in range(20):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    return cfg, tcfg, state, step, batch, losses


def test_memorization(trained):
    *_, losses = trained
    assert losses[-1] < losses[0] - 0.5


def test_lr_schedule():
    c = T.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_frac=0.1)
    assert float(T.lr_at(c, jnp.asarray(0))) == 0.0
    assert abs(float(T.lr_at(c, jnp.asarray(10))) - 1.0) < 1e-6
    assert abs(float(T.lr_at(c, jnp.asarray(100))) - 0.1) < 1e-6


def test_checkpoint_roundtrip_and_gc(trained):
    cfg, tcfg, state, *_ = trained
    d = tempfile.mkdtemp()
    ck = T.CheckpointManager(d, keep=2)
    for s in (1, 2, 3):
        ck.save(s, state, blocking=True)
    assert ck.all_steps() == [2, 3]  # keep-N gc
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    restored, meta = ck.restore(like)
    assert meta["step"] == 3
    assert all(bool(jnp.all(a == b)) for a, b in
               zip(jax.tree.leaves(state), jax.tree.leaves(restored)))


def test_checkpoint_elastic_reshard(trained):
    """Elastic restore: save unsharded, restore onto an explicit 1-device
    mesh sharding (the k-device case is covered by the subprocess test)."""
    cfg, tcfg, state, *_ = trained
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_host_mesh
    d = tempfile.mkdtemp()
    ck = T.CheckpointManager(d)
    ck.save(7, state.params, blocking=True)
    mesh = make_host_mesh()
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        state.params)
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), like)
    restored, _ = ck.restore(like, shardings=sh)
    assert all(bool(jnp.all(a == b)) for a, b in
               zip(jax.tree.leaves(state.params), jax.tree.leaves(restored)))


def test_checkpoint_tree_mismatch_rejected(trained):
    cfg, tcfg, state, *_ = trained
    d = tempfile.mkdtemp()
    ck = T.CheckpointManager(d)
    ck.save(1, state.params, blocking=True)
    with pytest.raises(ValueError):
        ck.restore({"something": jax.ShapeDtypeStruct((2,), jnp.float32)})


def test_loop_nan_quarantine_and_reload(trained):
    cfg, tcfg, state, step, batch, _ = trained
    calls = {"n": 0}

    def data():
        while True:
            calls["n"] += 1
            yield ("POISON" if calls["n"] in (3, 4) else "OK"), batch

    def wrapped(st, tagged):
        tag, b = tagged
        s2, m = step(st, b)
        if tag == "POISON":
            m = dict(m)
            m["loss"] = jnp.asarray(float("nan"))
        return s2, m

    loop = T.TrainLoop(wrapped, state, data(),
                       ckpt=T.CheckpointManager(tempfile.mkdtemp()),
                       cfg=T.LoopConfig(total_steps=int(state.step) + 10,
                                        checkpoint_every=3, max_strikes=2))
    stats = loop.run()
    assert stats.steps_skipped == 2
    assert stats.reloads == 1
    assert stats.steps_run == 8


def test_loop_straggler_detection(trained):
    cfg, tcfg, state, step, batch, _ = trained
    import time

    calls = {"n": 0}

    def data():
        while True:
            calls["n"] += 1
            yield calls["n"], batch

    def slow_step(st, tagged):
        i, b = tagged
        if i == 15:
            time.sleep(1.0)  # injected straggler
        return step(st, b)

    loop = T.TrainLoop(slow_step, state, data(), ckpt=None,
                       cfg=T.LoopConfig(total_steps=int(state.step) + 20,
                                        straggler_z=3.0, straggler_warmup=3))
    stats = loop.run()
    assert len(stats.stragglers) >= 1


def test_grad_compression_converges(trained):
    cfg, _, _, _, batch, base_losses = trained
    for kind in ("topk_index", "int8_centered"):
        tcfg = T.TrainConfig(adamw=T.AdamWConfig(lr=1e-3, warmup_steps=2,
                                                 total_steps=60),
                             grad_compression=kind, topk_frac=0.25)
        st = init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
        step = jax.jit(T.make_train_step(cfg, tcfg))
        for _ in range(20):
            st, m = step(st, batch)
        assert float(m["loss"]) < base_losses[0] - 0.3, kind


def test_compression_wire_bytes():
    from repro.distributed.compression import estimated_wire_bytes
    params = {"w": jnp.zeros((1000, 100)), "b": jnp.zeros((100,))}
    dense = estimated_wire_bytes(params, "none", 0)
    topk = estimated_wire_bytes(params, "topk_index", 0.01)
    int8 = estimated_wire_bytes(params, "int8_centered", 0)
    assert topk < dense / 10
    assert int8 < dense / 3


# ---- data pipeline ---------------------------------------------------------


@pytest.fixture(scope="module")
def corpus():
    return build_synthetic_corpus(CorpusConfig(n_docs=400, mean_doc_len=150))


def test_corpus_compression(corpus):
    fact, dims = corpus
    assert fact.encoding_of("doc_id") == "RLEColumn"
    assert fact.nbytes() < 5 * 4 * fact.nrows / 5  # >5x on metadata+tokens


def test_corpus_stats_match_oracle(corpus):
    fact, dims = corpus
    stats = corpus_stats(fact)
    assert int(stats["tokens"].sum()) == fact.nrows
    doc_tokens = np.repeat(dims["doc_domain"], dims["doc_lens"])
    for dom, cnt in zip(stats["domain"], stats["tokens"]):
        assert int(cnt) == int((doc_tokens == dom).sum())


def test_selection_matches_oracle(corpus):
    fact, dims = corpus
    cfg = PipelineConfig(seq_len=32, batch_size=2, min_quality=55,
                         domains=[0, 1, 2, 3, 4, 5])
    pipe = DataPipeline(fact, cfg)
    q = np.repeat(dims["doc_quality"], dims["doc_lens"])
    d = np.repeat(dims["doc_domain"], dims["doc_lens"])
    want = np.flatnonzero((q >= 55) & (d <= 5))
    np.testing.assert_array_equal(pipe.selected_positions, want)


def test_doc_whitelist_semijoin(corpus):
    fact, dims = corpus
    wl = np.arange(0, 400, 7)
    cfg = PipelineConfig(seq_len=32, batch_size=2, min_quality=0,
                         doc_whitelist=wl)
    pipe = DataPipeline(fact, cfg)
    doc = np.repeat(np.arange(400), dims["doc_lens"])
    want = np.flatnonzero(np.isin(doc, wl))
    np.testing.assert_array_equal(pipe.selected_positions, want)


def test_shards_disjoint_and_resume_deterministic(corpus):
    fact, _ = corpus
    mk = lambda r: DataPipeline(fact, PipelineConfig(
        seq_len=32, batch_size=2, min_quality=40, dp_rank=r, dp_size=2))
    p0, p1 = mk(0), mk(1)
    b0, b1 = next(p0), next(p1)
    assert not np.array_equal(np.asarray(b0["tokens"]),
                              np.asarray(b1["tokens"]))
    # resume: seek to cursor and replay
    p2 = mk(0)
    _ = next(p2)
    second = next(p2)
    p3 = mk(0)
    p3.seek(1)
    np.testing.assert_array_equal(np.asarray(next(p3)["tokens"]),
                                  np.asarray(second["tokens"]))


def test_labels_are_shifted_tokens(corpus):
    fact, _ = corpus
    pipe = DataPipeline(fact, PipelineConfig(seq_len=32, batch_size=2,
                                             min_quality=40))
    b = next(pipe)
    np.testing.assert_array_equal(np.asarray(b["tokens"])[:, 1:],
                                  np.asarray(b["labels"])[:, :-1])
