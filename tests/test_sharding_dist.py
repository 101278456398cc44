"""Sharding rules (divisibility fallbacks) + multi-device subprocess tests.

Multi-device tests MUST run in a subprocess: the 1-device main test process
cannot re-initialize jax with --xla_force_host_platform_device_count.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.distributed import sharding as Sh
from repro.models import model as M

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeMesh:
    """Duck-typed mesh for spec derivation (no devices needed)."""

    def __init__(self, shape_map):
        self.shape = shape_map
        self.axis_names = tuple(shape_map)


MESH = FakeMesh({"data": 16, "model": 16})


def _specs(arch):
    cfg = configs.get_config(arch)
    shapes = jax.eval_shape(lambda k: M.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    return cfg, Sh.param_shardings(shapes, MESH), shapes


def test_dense_rules_yi():
    cfg, specs, _ = _specs("yi_9b")
    l = specs["layers"]
    assert l["attn"]["wq"] == P(None, "data", "model", None)  # H=32 sharded
    assert l["attn"]["wk"] == P(None, "data", None, "model")  # kv=4 -> hd
    assert l["mlp"]["w_gate"] == P(None, "data", "model")
    assert l["mlp"]["w_down"] == P(None, "model", "data")
    assert specs["embed"] == P("model", "data")


def test_head_fallback_smollm():
    cfg, specs, _ = _specs("smollm_360m")
    # 15 heads, kv=5, hd=64: neither heads nor kv divide 16 -> hd takes model
    assert specs["layers"]["attn"]["wq"] == P(None, "data", None, "model")


def test_moe_ep_qwen3():
    cfg, specs, shapes = _specs("qwen3_moe_235b_a22b")
    assert specs["layers"]["moe"]["w_gate"] == P(None, "model", "data", None)
    assert specs["layers"]["moe"]["w_down"] == P(None, "model", None, "data")


def test_granite_expert_padding_makes_ep_shardable():
    import dataclasses
    cfg = dataclasses.replace(configs.get_config("granite_moe_3b_a800m"),
                              expert_pad_multiple=16)
    assert cfg.padded_experts == 48
    shapes = jax.eval_shape(lambda k: M.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    specs = Sh.param_shardings(shapes, MESH)
    assert specs["layers"]["moe"]["w_gate"][1] == "model"  # 48 % 16 == 0


def test_vocab_padding():
    cfg = configs.get_config("granite_moe_3b_a800m")
    assert cfg.vocab_size == 49155
    assert cfg.padded_vocab % 16 == 0


def test_every_param_of_every_arch_gets_a_spec():
    for arch in configs.ARCHS:
        cfg, specs, shapes = _specs(arch)
        for (path, spec), (_, shape) in zip(
                jax.tree_util.tree_flatten_with_path(specs)[0],
                jax.tree_util.tree_flatten_with_path(shapes)[0]):
            for ax, dim in zip(spec, shape.shape):
                if ax is not None:
                    sz = MESH.shape[ax] if isinstance(ax, str) else int(
                        np.prod([MESH.shape[a] for a in ax]))
                    assert dim % sz == 0, (arch, path, spec, shape.shape)


def test_batch_spec_fallback():
    assert Sh.batch_spec(MESH, 256) == P(("data",), None)
    m3 = FakeMesh({"pod": 2, "data": 16, "model": 16})
    assert Sh.batch_spec(m3, 256) == P(("pod", "data"), None)
    assert Sh.batch_spec(m3, 1) == P(None, None)  # long_500k: replicate


# ---- subprocess multi-device tests -----------------------------------------


def _run_subprocess(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_moe_ep_parity_8dev():
    """EP parity: the shard_map path under an ambient ``set_mesh`` mesh
    matches the local path."""
    out = _run_subprocess("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.models import layers as L
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((4, 16, 16)), jnp.float32)
        mesh = make_mesh((2, 4), ('data', 'model'))
        p = L.init_moe(jax.random.PRNGKey(6), 16, 32, 6, jnp.float32, n_padded=8)
        with jax.sharding.set_mesh(mesh):
            y_ep, _ = jax.jit(lambda p_, x_: L.moe(
                p_, x_, 2, 100.0, group_axes=('data',),
                expert_axis='model'))(p, x)
        y_loc, _ = L.moe(p, x, 2, 100.0)
        err = float(jnp.abs(y_ep - y_loc).max())
        assert err < 1e-4, err
        print('EP_PARITY_OK', err)
    """)
    assert "EP_PARITY_OK" in out


@pytest.mark.slow
def test_mini_dryrun_cell_8dev():
    """Lower+compile a reduced config on a (2,4) mesh end to end."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import configs
        from repro.distributed import sharding as Sh
        from repro.launch.mesh import make_mesh
        from repro.models import model as M
        from repro.train import step as TS, optimizer as opt
        from repro.launch import hlo_cost
        mesh = make_mesh((2, 4), ('data', 'model'))
        cfg = dataclasses.replace(
            configs.get_smoke_config('qwen2_1p5b'), d_model=64, n_heads=4,
            n_kv_heads=2, d_ff=128, act_batch_axes=('data',),
            act_seq_axis='model', vocab_axis='model', remat='full')
        tcfg = TS.TrainConfig(adamw=opt.AdamWConfig())
        ss = jax.eval_shape(lambda k: TS.init_train_state(cfg, tcfg, k),
                            jax.random.PRNGKey(0))
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          Sh.param_shardings(ss, mesh))
        bshape = {'tokens': jax.ShapeDtypeStruct((8, 64), jnp.int32),
                  'labels': jax.ShapeDtypeStruct((8, 64), jnp.int32)}
        bsh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           Sh.batch_shardings(bshape, mesh, 8))
        fn = TS.make_train_step(cfg, tcfg)
        with jax.sharding.set_mesh(mesh):
            compiled = jax.jit(fn, in_shardings=(sh, bsh),
                               out_shardings=(sh, NamedSharding(mesh, P()))
                               ).lower(ss, bshape).compile()
        parsed = hlo_cost.analyze(compiled.as_text())
        assert parsed['flops'] > 0
        assert parsed['collective_bytes_total'] > 0
        print('MINI_DRYRUN_OK', parsed['flops'])
    """)
    assert "MINI_DRYRUN_OK" in out


@pytest.mark.slow
def test_elastic_checkpoint_reshard_8dev():
    """Checkpoint written on 1 device restores sharded onto 8 devices."""
    import tempfile
    import repro.train as T
    from repro.train.step import init_train_state
    cfg = configs.get_smoke_config("smollm_360m")
    tcfg = T.TrainConfig()
    state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
    d = tempfile.mkdtemp()
    T.CheckpointManager(d).save(5, state.params, blocking=True)
    out = _run_subprocess(f"""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding
        import repro.train as T
        from repro import configs
        from repro.launch.mesh import make_mesh
        from repro.models import model as M
        from repro.distributed import sharding as Sh
        mesh = make_mesh((2, 4), ('data', 'model'))
        cfg = configs.get_smoke_config('smollm_360m')
        like = jax.eval_shape(lambda k: M.init_params(cfg, k),
                              jax.random.PRNGKey(0))
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          Sh.param_shardings(like, mesh))
        restored, meta = T.CheckpointManager({d!r}).restore(like, shardings=sh)
        assert meta['step'] == 5
        total = sum(float(jnp.sum(jnp.abs(x.astype(jnp.float32))))
                    for x in jax.tree.leaves(restored))
        assert total > 0
        print('ELASTIC_OK')
    """)
    assert "ELASTIC_OK" in out
