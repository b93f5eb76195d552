"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, output shapes + finite values.  (Full configs are exercised only by the
dry-run — ShapeDtypeStruct, no allocation.)"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, get_arch
from repro.configs.base import ShapeSpec
from repro.models import model as M
from repro.models.io import synthetic_batch

SHAPE = ShapeSpec("smoke_train", 32, 2, "train")


@pytest.fixture(scope="module")
def mesh():
    from repro.launch.mesh import make_smoke_mesh
    return make_smoke_mesh()


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_forward_and_loss(arch, mesh):
    cfg = get_arch(arch).reduced()
    ctx = M.build_ctx(cfg, SHAPE, mesh)
    params = M.init_params(cfg, jax.random.key(0))
    batch = synthetic_batch(cfg, SHAPE, jax.random.key(1))
    with jax.set_mesh(mesh):
        loss, metrics = M.loss_fn(cfg, ctx, params, batch)
    assert jnp.isfinite(loss), (arch, loss)
    assert 1.0 < float(loss) < 20.0, (arch, loss)   # ~ln(vocab) at init
    assert jnp.isfinite(metrics["xent"])


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_train_step_updates_params(arch, mesh):
    from repro.training.optimizer import OptConfig, opt_pspecs
    from repro.training.train_step import build_train_step
    from repro.models import param as PM

    cfg = get_arch(arch).reduced()
    ctx = M.build_ctx(cfg, SHAPE, mesh)
    params = M.init_params(cfg, jax.random.key(0))
    opt = PM.initialize(opt_pspecs(M.model_specs(cfg)), jax.random.key(1))
    batch = synthetic_batch(cfg, SHAPE, jax.random.key(2))
    step = build_train_step(cfg, ctx, OptConfig(schedule=cfg.lr_schedule),
                            accum=2)
    with jax.set_mesh(mesh):
        new_p, new_o, metrics = step(params, opt, batch)
    assert jnp.isfinite(metrics["loss"])
    assert int(new_o["step"]) == 1
    # at least one weight leaf must actually change
    changed = any(
        not jnp.array_equal(a, b)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new_p)))
    assert changed, arch


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_prefill_decode_shapes(arch, mesh):
    cfg = get_arch(arch).reduced()
    total = 16
    shape = ShapeSpec("t", total, 2, "train")
    ctx = M.build_ctx(cfg, shape, mesh)
    params = M.init_params(cfg, jax.random.key(0))
    batch = synthetic_batch(cfg, shape, jax.random.key(1))
    with jax.set_mesh(mesh):
        logits, caches = M.prefill(cfg, ctx, params, batch)
        assert logits.shape == (2, cfg.padded_vocab)
        assert jnp.isfinite(logits).all()
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        pos = total // 2 if cfg.family == "encdec" else total
        from repro.serving.engine import extend_caches
        caches = extend_caches(cfg, caches, pos + 4)
        lg, caches2 = M.decode_step(cfg, ctx, params, caches, tok, pos)
        assert lg.shape == (2, cfg.padded_vocab)
        assert jnp.isfinite(lg).all()
