"""System-level tests: fault-tolerant training, elastic checkpointing,
data determinism, HLO roofline analyzer, gradient compression."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.data import DataConfig, make_batch
from repro.models.config import ModelConfig
from repro.optim import OptConfig

_SMOKE = ModelConfig(
    name="sys-smoke", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv=2, d_ff=128, vocab=128, impl="naive", param_dtype="float32",
    compute_dtype="float32", remat=False, logits_chunk=16)


def test_trainer_failure_resume_is_deterministic():
    from repro.train import SimulatedFailure, Trainer
    opt = OptConfig(lr=1e-3, warmup_steps=2, total_steps=20)
    data = DataConfig(vocab=128, seq_len=32, batch_per_host=4, v_eff=64)
    with tempfile.TemporaryDirectory() as td:
        t_ref = Trainer(_SMOKE, opt, data, ckpt_dir=td + "/a",
                        ckpt_every=2).init_or_resume(jax.random.PRNGKey(0))
        h_ref = t_ref.run(6)
        t_f = Trainer(_SMOKE, opt, data, ckpt_dir=td + "/b", ckpt_every=2,
                      inject_failure_at=4).init_or_resume(jax.random.PRNGKey(0))
        with pytest.raises(SimulatedFailure):
            t_f.run(6)
        t_r = Trainer(_SMOKE, opt, data, ckpt_dir=td + "/b",
                      ckpt_every=2).init_or_resume(jax.random.PRNGKey(0))
        # resumes from the latest COMPLETED checkpoint (async saves may
        # legitimately race a crash; atomic rename guarantees integrity)
        assert t_r.step in (2, 4)
        h_res = t_r.run(6)
        np.testing.assert_allclose(h_ref[-2:], h_res[-2:], rtol=1e-5)


def test_checkpoint_atomic_and_elastic_restore():
    from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
    tree = {"a": jnp.arange(12.0).reshape(3, 4),
            "b": {"c": jnp.ones((5,), jnp.int32)}}
    with tempfile.TemporaryDirectory() as td:
        save_checkpoint(td, 3, tree)
        save_checkpoint(td, 7, jax.tree.map(lambda x: x * 2, tree))
        assert latest_step(td) == 7
        like = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
        # restore with explicit shardings = the elastic re-shard path
        mesh = jax.make_mesh((1,), ("model",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = jax.tree.map(lambda x: NamedSharding(mesh, P()), like)
        out = restore_checkpoint(td, 7, like, shardings=sh)
        np.testing.assert_allclose(np.asarray(out["a"]),
                                   np.asarray(tree["a"]) * 2)
        # shape mismatch is rejected
        bad = dict(like, a=jax.ShapeDtypeStruct((4, 3), jnp.float32))
        with pytest.raises(ValueError):
            restore_checkpoint(td, 7, bad)


def test_data_pipeline_determinism_and_structure():
    cfg = DataConfig(vocab=1000, seq_len=64, batch_per_host=4, v_eff=256,
                     noise_k=8)
    b1 = make_batch(cfg, step=5)
    b2 = make_batch(cfg, step=5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = make_batch(cfg, step=6)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])
    # the bigram structure bounds the label entropy: given prev token,
    # next is one of noise_k values
    nxt = (31 * b1["tokens"].astype(np.int64) + 7) % 256
    gap = (b1["labels"] - nxt) % 256
    assert gap.max() < cfg.noise_k


def test_hlo_analyzer_exact_on_nested_scans():
    from repro.parallel import analyze_compiled

    def inner(c, w):
        return c @ w, None

    def outer(c, ws):
        c2, _ = jax.lax.scan(inner, c, ws)
        return c2, None

    def nested(x, ws):
        c, _ = jax.lax.scan(outer, x, ws)
        return c

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((3, 5, 64, 64), jnp.float32)
    costs = analyze_compiled(jax.jit(nested).lower(x, ws).compile())
    true_flops = 2 * 15 * 64 ** 3
    assert abs(costs.flops - true_flops) / true_flops < 1e-6
    assert not costs.unknown_trips
    # bytes must reflect per-iteration slab reads, not LxW overcounts
    assert costs.bytes < 30 * ws.size * 4


def test_ef_int8_quantization_properties():
    from repro.parallel.collectives import _quantize_int8
    x = jax.random.normal(jax.random.PRNGKey(0), (256,)) * 3.0
    q, scale = _quantize_int8(x)
    deq = q.astype(jnp.float32) * scale
    # error bounded by half a quantization step
    assert float(jnp.max(jnp.abs(x - deq))) <= float(scale) * 0.5 + 1e-6
    # error feedback keeps the time-averaged signal unbiased
    err = jnp.zeros_like(x)
    acc = jnp.zeros_like(x)
    for _ in range(50):
        xe = x + err
        q, scale = _quantize_int8(xe)
        deq = q.astype(jnp.float32) * scale
        err = xe - deq
        acc = acc + deq
    np.testing.assert_allclose(np.asarray(acc / 50), np.asarray(x),
                               atol=float(scale))


def test_moe_shardmap_matches_ref_on_4_devices():
    """The expert-parallel shard_map dispatch (separate process: needs
    xla_force_host_platform_device_count, which must NOT leak into this
    test process)."""
    import subprocess
    import sys
    code = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
import sys; sys.path.insert(0, 'src')
import jax, jax.numpy as jnp, numpy as np
import repro.models.moe as M
from repro.parallel.act_sharding import use_activation_sharding
mesh = jax.make_mesh((2, 2), ('data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
spec = M.MoESpec(d_model=32, n_experts=8, top_k=2, d_ff_expert=64,
                 n_shared=1, capacity_factor=8.0)
p = M.init_moe(jax.random.PRNGKey(0), spec, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
y_ref = M.moe_ref(p, x, spec)
with use_activation_sharding(mesh, sp=False):
    y = jax.jit(lambda p, x: M.apply_moe(p, x, spec))(p, x)
    g = jax.jit(jax.grad(lambda p, x: M.apply_moe(p, x, spec).sum()))(p, x)
np.testing.assert_allclose(y, y_ref, atol=1e-5, rtol=1e-5)
g2 = jax.grad(lambda p, x: M._apply_moe_local(p, x, spec).sum())(p, x)
err = max(float(jnp.abs(a-b).max())
          for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g2)))
assert err < 1e-4, err
print('OK')
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "OK" in r.stdout, r.stderr[-2000:]


def test_expert_placement_improves_locality_and_preserves_semantics():
    from repro.core.placement import (_cross_fraction, apply_placement,
                                      place_experts)
    from repro.models.moe import MoESpec, init_moe, moe_ref
    rng = np.random.default_rng(0)
    e, dev, t, k = 32, 4, 1500, 2
    hidden = rng.permutation(e).reshape(dev, e // dev)
    grp = rng.integers(0, dev, t)
    top = hidden[grp[:, None], rng.integers(0, e // dev, (t, k))]
    naive = np.arange(e) // (e // dev)
    pl = place_experts(top, e, dev, max_steps=80)
    assert pl.cross_coactivation < _cross_fraction(top, naive) - 0.3
    counts = np.bincount(pl.expert_to_device, minlength=dev)
    assert counts.max() == counts.min() == e // dev   # exact balance
    spec = MoESpec(d_model=8, n_experts=e, top_k=2, d_ff_expert=16)
    p = init_moe(jax.random.PRNGKey(0), spec, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8))
    np.testing.assert_allclose(
        np.asarray(moe_ref(p, x, spec)),
        np.asarray(moe_ref(apply_placement(p, pl), x, spec)),
        atol=1e-5, rtol=1e-5)


@settings(max_examples=15, deadline=None)
@given(steps=st.integers(1, 30))
def test_lr_schedule_properties(steps):
    from repro.optim import schedule
    cfg = OptConfig(lr=1e-3, warmup_steps=10, total_steps=100)
    s = jnp.asarray(float(steps))
    lr = float(schedule(cfg, s))
    # f32 rounding at the warmup->cosine boundary can exceed lr by 1 ulp
    assert 0.0 <= lr <= cfg.lr * (1 + 1e-5)
    if steps < 10:   # warmup is monotone
        assert lr <= float(schedule(cfg, s + 1.0)) + 1e-12


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_clip_by_global_norm_property(seed):
    from repro.optim import clip_by_global_norm
    key = jax.random.PRNGKey(seed)
    g = {"a": jax.random.normal(key, (17,)) * 10,
         "b": jax.random.normal(jax.random.fold_in(key, 1), (3, 5))}
    clipped, norm = clip_by_global_norm(g, 1.0)
    new_norm = float(jnp.sqrt(sum(jnp.sum(x * x)
                                  for x in jax.tree.leaves(clipped))))
    assert new_norm <= 1.0 + 1e-5


def test_moe_ep2d_matches_ref_on_8_devices():
    """Cross-pod EP (experts over pod x model) — §Perf C3 path."""
    import subprocess
    import sys
    code = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import sys; sys.path.insert(0, 'src')
import jax, jax.numpy as jnp, numpy as np
import repro.models.moe as M
mesh = jax.make_mesh((2, 2, 2), ('pod', 'data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,)*3)
spec = M.MoESpec(d_model=32, n_experts=8, top_k=2, d_ff_expert=64,
                 n_shared=1, capacity_factor=8.0)
p = M.init_moe(jax.random.PRNGKey(0), spec, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
y_ref = M.moe_ref(p, x, spec)
with jax.set_mesh(mesh):
    y = jax.jit(lambda p, x: M._apply_moe_ep2d(p, x, spec, mesh))(p, x)
    g = jax.jit(jax.grad(
        lambda p, x: M._apply_moe_ep2d(p, x, spec, mesh).sum()))(p, x)
np.testing.assert_allclose(y, y_ref, atol=1e-5, rtol=1e-5)
g2 = jax.grad(lambda p, x: M._apply_moe_local(p, x, spec).sum())(p, x)
err = max(float(jnp.abs(a-b).max())
          for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g2)))
assert err < 1e-4, err
print('OK')
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "OK" in r.stdout, r.stderr[-2000:]
