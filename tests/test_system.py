"""End-to-end behaviour tests: workflows over FaaSTube vs baselines,
serving engine generation, training loop + fault recovery + checkpoints."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.configs.base import ShapeSpec
from repro.core.api import FAASTUBE, SYSTEMS
from repro.core.topology import dgx_a100, dgx_v100
from repro.serving.executor import run_closed_loop
from repro.serving.workflow import WORKFLOWS


# ----------------------------------------------------------- workflows ----

@pytest.mark.parametrize("wname", sorted(WORKFLOWS))
def test_faastube_beats_infless(wname):
    w = WORKFLOWS[wname]
    lat = {}
    for sname in ("infless+", "faastube"):
        eng = run_closed_loop(dgx_v100, SYSTEMS[sname], w, n_requests=1)
        rs = eng.completed[0]
        lat[sname] = rs.t_done - rs.t_arrive
    assert lat["faastube"] < lat["infless+"]


def test_media_workflows_match_paper_band():
    """Paper Fig 11: 86-90% e2e latency reduction on media workflows under
    load.  Single-request lower bound here: >= 75%."""
    for wname in ("traffic", "driving"):
        w = WORKFLOWS[wname]
        li = run_closed_loop(dgx_v100, SYSTEMS["infless+"], w,
                             n_requests=4).completed
        lf = run_closed_loop(dgx_v100, SYSTEMS["faastube"], w,
                             n_requests=4).completed
        p_inf = max(r.t_done - r.t_arrive for r in li)
        p_ft = max(r.t_done - r.t_arrive for r in lf)
        assert 1 - p_ft / p_inf >= 0.75, (wname, p_inf, p_ft)


def test_system_ordering():
    """INFless+ > DeepPlan+ > FaaSTube* > FaaSTube on media workflows."""
    w = WORKFLOWS["driving"]
    lat = {}
    for sname, cfg in SYSTEMS.items():
        rs = run_closed_loop(dgx_v100, cfg, w, n_requests=1).completed[0]
        lat[sname] = rs.t_done - rs.t_arrive
    assert lat["infless+"] > lat["deepplan+"] > lat["faastube"]
    assert lat["faastube*"] > lat["faastube"]


def test_all_requests_complete_under_load():
    w = WORKFLOWS["traffic"]
    eng = run_closed_loop(dgx_v100, FAASTUBE, w, n_requests=16,
                          interarrival_ms=5.0)
    assert len(eng.completed) == 16
    assert all(r.t_done >= r.t_arrive for r in eng.completed)


def test_nvswitch_topology_runs():
    w = WORKFLOWS["video"]
    eng = run_closed_loop(dgx_a100, FAASTUBE, w, n_requests=2)
    assert len(eng.completed) == 2


# ------------------------------------------------------- serving engine ---

def test_engine_generates_tokens(smoke_mesh):
    from repro.serving.engine import Engine
    from repro.models import model as M
    cfg = get_arch("minicpm-2b").reduced()
    shape = ShapeSpec("t", 32, 2, "decode")
    params = M.init_params(cfg, jax.random.key(0))
    eng = Engine(cfg, shape, smoke_mesh, params)
    batch = {"tokens": jnp.ones((2, 8), jnp.int32)}
    toks, caches = eng.generate(batch, max_new_tokens=4)
    assert toks.shape == (2, 4)
    assert (toks >= 0).all() and (toks < cfg.padded_vocab).all()


# ------------------------------------------------- training + recovery ----

def test_checkpoint_roundtrip_bitwise(tmp_path, smoke_mesh):
    from repro.models import model as M
    from repro.training import checkpoint as CKPT
    cfg = get_arch("qwen2-72b").reduced()
    params = M.init_params(cfg, jax.random.key(0))
    CKPT.save(tmp_path, 3, {"params": params})
    restored, manifest = CKPT.restore(tmp_path, 3, {"params": params})
    assert manifest["step"] == 3
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fault_recovery_resumes_from_checkpoint(tmp_path, smoke_mesh):
    from repro.distributed.fault import FaultPolicy, NodeFailure
    from repro.training.train_loop import run_training
    cfg = get_arch("minicpm-2b").reduced()
    shape = ShapeSpec("t", 32, 2, "train")
    fired = {"x": False}

    def injector(i):
        if i == 4 and not fired["x"]:
            fired["x"] = True
            return NodeFailure(2)
        return None

    state, losses, stats = run_training(
        cfg, shape, smoke_mesh, steps=6, accum=1, ckpt_dir=str(tmp_path),
        policy=FaultPolicy(checkpoint_every=2),
        failure_injector=injector, log_every=0)
    assert state.step == 6
    assert stats.restarts == 1
    assert stats.failed_hosts == [2]


def test_pipeline_state_resumes_deterministically():
    from repro.data.pipeline import Pipeline
    cfg = get_arch("minicpm-2b").reduced()
    shape = ShapeSpec("t", 16, 2, "train")
    p1 = Pipeline(cfg, shape)
    b0, b1 = p1.next_batch(), p1.next_batch()
    p2 = Pipeline.from_state(cfg, shape, {"seed": 0, "step": 1})
    b1b = p2.next_batch()
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b1b["tokens"]))


def test_wsd_schedule_shape():
    from repro.training.optimizer import OptConfig, lr_at
    oc = OptConfig(lr=1.0, warmup_steps=10, total_steps=100, schedule="wsd",
                   stable_frac=0.8)
    assert float(lr_at(oc, 0)) == 0.0
    assert abs(float(lr_at(oc, 10)) - 1.0) < 1e-6       # post-warmup peak
    assert abs(float(lr_at(oc, 50)) - 1.0) < 1e-6       # stable plateau
    assert float(lr_at(oc, 90)) < 0.5                    # decaying
    assert float(lr_at(oc, 100)) < 0.05


def test_int8_optimizer_state_tracks_f32():
    from repro.models.param import PSpec, initialize
    from repro.training.optimizer import OptConfig, adamw_update, opt_pspecs
    specs = {"w": PSpec((512, 256), ("embed", "mlp"), jnp.float32)}
    params = initialize(specs, jax.random.key(0))
    g = jax.tree.map(lambda p: 0.01 * jnp.ones_like(p), params)
    oc = OptConfig(lr=1e-2, weight_decay=0.0)
    s_f32 = initialize(opt_pspecs(specs, "f32"), jax.random.key(1))
    s_int8 = initialize(opt_pspecs(specs, "int8"), jax.random.key(1))
    p1, s1, _ = adamw_update(oc, params, g, s_f32)
    p2, s2, _ = adamw_update(oc, params, g, s_int8)
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]),
                               atol=1e-4)


# ------------------------------------------------------- determinism ------

def test_init_process_determinism():
    """Param init must be byte-identical across processes with different
    PYTHONHASHSEED (multi-host init correctness; regression for the
    hash(name) -> crc32(name) fix)."""
    import subprocess
    import sys

    prog = (
        "import jax, numpy as np\n"
        "from repro.configs import get_arch\n"
        "from repro.models import model as M\n"
        "cfg = get_arch('dbrx-132b').reduced()\n"
        "params = M.init_params(cfg, jax.random.key(0))\n"
        "leaves = jax.tree.leaves(params)\n"
        "print(hex(sum(int(np.asarray(l, np.float32).view(np.uint32).sum())"
        " for l in leaves) % (2**61)))\n"
    )
    outs = []
    for seed in ("0", "12345"):
        r = subprocess.run(
            [sys.executable, "-c", prog],
            capture_output=True, text=True, check=True,
            env={"PYTHONHASHSEED": seed, "PYTHONPATH": "src",
                 "PATH": "/usr/bin:/bin"},
        )
        outs.append(r.stdout.strip().splitlines()[-1])
    assert outs[0] == outs[1], outs


def test_w8a16_decode_matches_bf16(smoke_mesh):
    """Weight-only int8 serving must stay within quantization noise of
    the bf16 path (per-channel scales; relnorm bound)."""
    import dataclasses
    import jax.numpy as jnp
    from repro.models import model as M
    from repro.serving.wquant import dequant_tree, quantize_tree
    from repro.configs.base import ShapeSpec

    cfg = dataclasses.replace(get_arch("qwen2-72b").reduced(),
                              cache_dtype="f32")
    shape = ShapeSpec("t", 16, 2, "decode")
    ctx = M.build_ctx(cfg, shape, smoke_mesh)
    params = M.init_params(cfg, jax.random.key(0))
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    qparams = quantize_tree(params, min_size=1024)   # reduced dims are tiny
    # at least the big 2-D weights actually quantized
    n_q = sum(1 for l in jax.tree.leaves(qparams) if l.dtype == jnp.int8)
    assert n_q >= 4, n_q
    deq = dequant_tree(qparams, dtype=jnp.float32)
    from repro.models.io import synthetic_batch
    batch = synthetic_batch(cfg, ShapeSpec("t", 16, 2, "train"),
                            jax.random.key(1))
    batch = jax.tree.map(lambda a: a.astype(jnp.float32)
                         if a.dtype == jnp.bfloat16 else a, batch)
    from repro.models import layers as LY
    from repro.models.blocks import block_pattern, layout_for

    def full_logits(p):
        x = M._embed_decoder_input(cfg, ctx, p, batch["tokens"])
        layout = layout_for(cfg, block_pattern(cfg))
        x, _, _ = M.apply_stack(cfg, ctx, layout, p["blocks"], x,
                                mode="prefill")
        return LY.logits_out(M._norm(cfg, x, p["ln_f"]), p["embed"])

    with jax.set_mesh(smoke_mesh):
        lg_ref = full_logits(params)          # (B, S, V): 32 positions
        lg_q = full_logits(deq)
    rel = float(jnp.linalg.norm(lg_q - lg_ref) /
                jnp.maximum(jnp.linalg.norm(lg_ref), 1e-9))
    # int8 dot noise averages ~1/sqrt(d_model): the reduced model's d=64
    # gives ~16%; the production d=8192 averages ~11x better (~1.5%)
    assert rel < 0.25, rel
    # greedy choice preserved at most positions (near-ties may flip)
    agree = float((jnp.argmax(lg_q, -1) == jnp.argmax(lg_ref, -1)).mean())
    assert agree >= 0.6, agree
