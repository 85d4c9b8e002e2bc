"""End-to-end behaviour tests: trainer with failure injection + restart,
multi-device distributed WMD (subprocess: needs forced device count), and
the serving loop."""
import os
import subprocess
import sys

import jax
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_subprocess(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("REPRO_FAILED_ONCE", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_trainer_failure_restart_loss_decreases():
    code = """
import jax, tempfile
from repro.configs import get_smoke_config
from repro.models import build_model
from repro.optim import adamw, warmup_cosine
from repro.data import TokenPipeline
from repro.train import Trainer
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
cfg = get_smoke_config("gemma-2b")
model = build_model(cfg, q_block=16, kv_block=16)
opt = adamw(warmup_cosine(3e-4, warmup_steps=3, total_steps=20))
pipe = TokenPipeline(cfg, batch=8, seq_len=32)
with tempfile.TemporaryDirectory() as td:
    tr = Trainer(model, opt, mesh, pipe, ckpt_dir=td, ckpt_every=4,
                 log_fn=lambda s: None)
    try:
        tr.run(jax.random.PRNGKey(0), 12, fail_at=6)
        raise SystemExit("expected failure not raised")
    except RuntimeError:
        pass
    tr2 = Trainer(model, opt, mesh, pipe, ckpt_dir=td, ckpt_every=4,
                  log_fn=lambda s: None)
    out = tr2.run(jax.random.PRNGKey(0), 12)
    h = out["history"]
    assert h[0]["step"] == 4, h[0]
    assert h[-1]["step"] == 11
    print("RESUMED_OK", h[0]["loss"], h[-1]["loss"])
"""
    stdout = _run_subprocess(code)
    assert "RESUMED_OK" in stdout
    parts = stdout.strip().split()
    assert float(parts[-1]) < float(parts[-2])  # loss decreased post-restart


def test_distributed_wmd_matches_single_chip():
    code = """
import numpy as np, jax, jax.numpy as jnp
from repro.core import (select_query, sinkhorn_wmd_sparse, ell_from_dense,
                        rebucket_for_vocab_shards)
from repro.core.distributed import (build_wmd_batch_fn, shard_wmd_inputs,
                                    pad_query_batch)
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
rng = np.random.default_rng(2)
V, w, N, vrn = 256, 32, 64, 9
vecs = rng.normal(size=(V, w)).astype(np.float32)
r = np.zeros(V, np.float32); idx = rng.choice(V, vrn, replace=False)
r[idx] = rng.random(vrn).astype(np.float32); r /= r.sum()
c = np.zeros((V, N), np.float32)
for j in range(N):
    widx = rng.choice(V, rng.integers(3, 17), replace=False)
    c[widx, j] = rng.random(widx.size).astype(np.float32)
    c[:, j] /= c[:, j].sum()
sel_idx, r_sel = select_query(r)
ell = ell_from_dense(c)
ref = np.asarray(sinkhorn_wmd_sparse(sel_idx, r_sel, jnp.asarray(ell.cols),
                                     jnp.asarray(ell.vals), vecs, 1.0, 12))
sel_b, r_b, mask_b = pad_query_batch([sel_idx], [r_sel], 16)
rb = rebucket_for_vocab_shards(ell, 2)
# one query; each data shard's 16 docs solved in two chunks of 8
fn = build_wmd_batch_fn(mesh, lamb=1.0, max_iter=12, docs_chunk=8)
vd, cd, vld = shard_wmd_inputs(mesh, vecs, rb.cols, rb.vals)
got = np.asarray(fn(jnp.asarray(vecs[sel_b]), jnp.asarray(r_b),
                    jnp.asarray(mask_b), vd, cd, vld))[0]
err = np.abs(got - ref).max() / np.abs(ref).max()
assert err < 1e-4, err
print("DIST_WMD_OK", err)
"""
    stdout = _run_subprocess(code)
    assert "DIST_WMD_OK" in stdout


def test_wmd_service_end_to_end():
    """Single-device service: corpus load, query, top-k retrieval sanity."""
    from repro.configs import sinkhorn_wmd as wmd_cfg
    from repro.data import make_corpus
    from repro.launch.mesh import make_mesh
    from repro.serving import WMDService
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = wmd_cfg.smoke_config()
    data = make_corpus(vocab_size=cfg.vocab_size, embed_dim=cfg.embed_dim,
                       num_docs=cfg.num_docs, num_queries=2,
                       query_words=cfg.v_r - 2, seed=0)
    svc = WMDService(mesh=mesh, cfg=cfg, vecs=data.vecs, ell=data.ell)
    d = svc.query(data.queries[0])
    assert d.shape == (cfg.num_docs,)
    assert np.isfinite(d).all() and (d > 0).all()
    idx, dist = svc.top_k(data.queries[0], k=5)
    assert np.all(np.diff(dist) >= 0)
    batch = svc.query_batch(data.queries)
    assert batch.shape == (2, cfg.num_docs)


def test_serve_decode_loop_runs():
    """LM serving loop produces tokens without NaN logits."""
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.serving import build_serve_fns
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_smoke_config("starcoder2-3b")
    model = build_model(cfg, q_block=8, kv_block=8)
    jit_prefill, jit_decode = build_serve_fns(model, mesh, max_len=48)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": np.asarray(rng.integers(0, cfg.vocab_size, (2, 16)),
                                  np.int32)}
    with mesh:
        logits, cache = jit_prefill(2)(params, batch)
        dec = jit_decode(2)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        for _ in range(8):
            logits, cache = dec(params, cache, tok)
            assert bool(jnp.isfinite(logits).all())
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
