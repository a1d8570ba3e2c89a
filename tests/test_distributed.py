"""Distributed substrate tests: checkpoint roundtrip + elastic restore,
trainer fault tolerance, gradient compression, data determinism, sharding
spec validity, roofline parser vs XLA cost_analysis."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from repro.data.pipeline import SyntheticLM, MemmapDataset, write_synthetic_corpus
from repro.optim import adamw
from repro.optim.compression import CompressionConfig, compress_grads, init_error_state


# ------------------------------------------------------------- checkpointer
def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2, async_save=False)
    tree = dict(a=jnp.arange(12.0).reshape(3, 4),
                b=dict(c=jnp.ones((5,), jnp.int32)))
    ck.save(3, tree)
    ck.save(7, jax.tree.map(lambda x: x * 2, tree))
    assert ck.committed_steps() == [3, 7]
    restored = ck.restore(7, tree)
    np.testing.assert_allclose(np.asarray(restored["a"]),
                               np.asarray(tree["a"]) * 2)


def test_checkpoint_gc_keeps_latest(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2, async_save=False)
    t = dict(x=jnp.zeros(3))
    for s in (1, 2, 3, 4):
        ck.save(s, t)
    assert ck.committed_steps() == [3, 4]


def test_checkpoint_uncommitted_ignored(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=3, async_save=False)
    t = dict(x=jnp.zeros(3))
    ck.save(5, t)
    # simulate crash mid-save: directory without COMMIT
    os.makedirs(tmp_path / "step_000000009/arrays")
    assert ck.latest_step() == 5


def test_checkpoint_elastic_reshard(tmp_path):
    """Restore applies new shardings (elastic resume on a different mesh)."""
    ck = Checkpointer(str(tmp_path), async_save=False)
    t = dict(w=jnp.arange(16.0).reshape(4, 4))
    ck.save(1, t)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = dict(w=NamedSharding(mesh, P("data", None)))
    restored = ck.restore(1, t, shardings=sh)
    np.testing.assert_allclose(np.asarray(restored["w"]), np.asarray(t["w"]))


# ------------------------------------------------------------------ trainer
def test_trainer_resume_identical_stream(tmp_path):
    """Restart-from-checkpoint replays the same data: loss trajectory of a
    30-step run == 20 steps + resume + 10 steps."""
    from repro.configs import registry
    from repro.models import transformer as T
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = registry.get("musicgen-medium").reduced()
    # musicgen embeds-input complicates batches; use tokens-only arch
    cfg = registry.get("gemma-7b").reduced()
    data = SyntheticLM(vocab=cfg.vocab, seq_len=32, global_batch=2, seed=5)

    def mk(ckdir):
        params = T.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        return Trainer(TrainerConfig(total_steps=30, ckpt_every=10,
                                     ckpt_dir=str(ckdir), log_every=1000,
                                     seq_chunk=16),
                       cfg, params, data)

    t1 = mk(tmp_path / "a")
    log1 = t1.run()
    t2 = mk(tmp_path / "b")
    t2.run(n_steps=20)
    t2.ckpt.wait()
    t3 = mk(tmp_path / "b")
    assert t3.maybe_resume() == 20
    log3 = t3.run()
    l1 = [r["loss"] for r in log1][-5:]
    l3 = [r["loss"] for r in log3][-5:]
    np.testing.assert_allclose(l1, l3, rtol=1e-4)


def test_trainer_loss_decreases():
    from repro.configs import registry
    from repro.models import transformer as T
    from repro.train.trainer import Trainer, TrainerConfig
    cfg = registry.get("phi3-medium-14b").reduced()
    params = T.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    data = SyntheticLM(vocab=cfg.vocab, seq_len=64, global_batch=4, seed=1)
    tr = Trainer(TrainerConfig(total_steps=40, ckpt_every=10**9,
                               log_every=10**9, seq_chunk=32),
                 cfg, params, data,
                 opt_cfg=adamw.AdamWConfig(lr=3e-3, warmup_steps=5,
                                           total_steps=40))
    log = tr.run()
    first = np.mean([r["loss"] for r in log[:5]])
    last = np.mean([r["loss"] for r in log[-5:]])
    assert last < first - 0.2, (first, last)


# -------------------------------------------------------------- compression
@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_compression_error_feedback(kind):
    cfg = CompressionConfig(kind=kind, error_feedback=True)
    rng = np.random.default_rng(0)
    g_true = dict(w=jnp.asarray(rng.normal(size=(64, 64)), jnp.float32))
    err = init_error_state(g_true, cfg)
    # accumulated compressed grads ≈ accumulated true grads (EF property)
    acc_c = np.zeros((64, 64))
    for _ in range(20):
        gc, err = compress_grads(cfg, g_true, err)
        acc_c += np.asarray(gc["w"], np.float64)
    acc_t = np.asarray(g_true["w"], np.float64) * 20
    rel = np.abs(acc_c - acc_t).max() / np.abs(acc_t).max()
    assert rel < 0.02, rel


def test_compression_none_passthrough():
    cfg = CompressionConfig(kind="none")
    g = dict(w=jnp.ones((4,)))
    gc, err = compress_grads(cfg, g, None)
    assert gc["w"] is g["w"]


# --------------------------------------------------------------------- data
def test_data_determinism():
    d = SyntheticLM(vocab=100, seq_len=16, global_batch=4, seed=9)
    b1, b2 = d.batch(42), d.batch(42)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(d.batch(43)["tokens"], b1["tokens"])


def test_memmap_dataset(tmp_path):
    path = str(tmp_path / "corpus.bin")
    write_synthetic_corpus(path, 10_000, vocab=50, seed=0)
    d = MemmapDataset(path, vocab=50, seq_len=32, global_batch=4, seed=0)
    b = d.batch(0)
    assert b["tokens"].shape == (4, 32)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


# -------------------------------------------------- sharding specs validity
def test_param_specs_cover_all_archs():
    from repro.configs import registry
    from repro.models import transformer as T
    from repro.models import sharding as Sh
    for name, cfg in registry.ARCHS.items():
        shapes = jax.eval_shape(
            lambda k, c=cfg: T.init_params(c, k, dtype=jnp.bfloat16),
            jax.random.PRNGKey(0))
        specs = Sh.param_specs(cfg, shapes)
        flat_sh, _ = jax.tree_util.tree_flatten(shapes)
        flat_sp, _ = jax.tree_util.tree_flatten(
            specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        assert len(flat_sh) == len(flat_sp), name
        for sh, sp in zip(flat_sh, flat_sp):
            assert len(sp) <= len(sh.shape), (name, sh.shape, sp)


# ------------------------------------------------------------ roofline/HLO
def test_hlo_cost_matches_xla_flat():
    from repro.roofline import hlo_cost

    def f(x, w1, w2):
        return jnp.tanh(x @ w1) @ w2

    c = jax.jit(f).lower(
        jax.ShapeDtypeStruct((32, 64), jnp.float32),
        jax.ShapeDtypeStruct((64, 128), jnp.float32),
        jax.ShapeDtypeStruct((128, 16), jnp.float32)).compile()
    mine = hlo_cost.analyze(c.as_text())
    xla = c.cost_analysis()
    if isinstance(xla, (list, tuple)):        # older jax returns [dict]
        xla = xla[0]
    assert abs(mine.flops - xla["flops"]) / xla["flops"] < 0.05
    assert abs(mine.bytes_accessed - xla["bytes accessed"]) \
        / xla["bytes accessed"] < 0.05


def test_hlo_cost_multiplies_scan_trip_count():
    from repro.roofline import hlo_cost

    def f(x, w):
        def body(c, _):
            return jnp.dot(c, w), ()
        c2, _ = jax.lax.scan(body, x, None, length=11)
        return c2.sum()

    c = jax.jit(f).lower(jax.ShapeDtypeStruct((32, 32), jnp.float32),
                         jax.ShapeDtypeStruct((32, 32), jnp.float32)).compile()
    mine = hlo_cost.analyze(c.as_text())
    expect = 11 * 2 * 32 * 32 * 32
    assert 0.9 < mine.flops / expect < 1.2


# ---------------------------------------------------------- dry-run (smoke)
@pytest.mark.slow
def test_dryrun_smoke_subprocess():
    """Full dryrun machinery on a 16-device fake mesh in a subprocess
    (device count must be set before jax init)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax, jax.numpy as jnp
from repro.configs import registry
from repro.configs.shapes import ShapeCfg
from repro.launch.dryrun import lower_cell
mesh = jax.make_mesh((4, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = registry.get("phi3-medium-14b").reduced()
shape = ShapeCfg("smoke", 64, 8, "train")
rec = lower_cell(cfg, shape, mesh, "mesh4x4", seq_chunk=32)
assert rec["status"] == "ok", rec
shape_d = ShapeCfg("smoke_d", 64, 8, "decode")
rec = lower_cell(cfg, shape_d, mesh, "mesh4x4")
assert rec["status"] == "ok", rec
print("DRYRUN_SMOKE_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))))
    assert "DRYRUN_SMOKE_OK" in r.stdout, r.stderr[-2000:]
