"""Serving-path correctness: ring-buffer windowed decode vs a full-cache
reference, cache sharding specs, elastic checkpoint reshard."""
import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.models.layers import attention


def test_ring_buffer_decode_matches_full_cache():
    """A windowed (SWA) layer decoded through its ring buffer must equal the
    same layer decoded with an unbounded cache + window mask."""
    cfg = get_smoke_config("mixtral-8x22b")           # swa window=16
    params = attention.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    b, steps = 2, 40                                   # > 2x window: wraps
    xs = jnp.asarray(rng.normal(size=(b, steps, cfg.d_model)) * 0.3,
                     jnp.float32)

    # ring buffer path (buf = window = 16)
    cache = attention.init_cache(cfg, b, max_len=steps, dtype=jnp.float32)
    assert cache.k.shape[1] == cfg.window             # ring sizing
    outs_ring = []
    for t in range(steps):
        y, cache = attention.fwd_decode(cfg, params, xs[:, t:t + 1], cache)
        outs_ring.append(y)

    # reference: full cache with the window enforced by masking
    full_cfg = dataclasses.replace(cfg, attn_kind="full", window=0)
    ref_cache = attention.init_cache(full_cfg, b, max_len=steps,
                                     dtype=jnp.float32)
    # emulate windowed attention on the full cache by re-deriving from
    # fwd_full at each prefix length (teacher-forced windowed attention)
    y_ref_all = attention.fwd_full(cfg, params, xs, q_block=8, kv_block=8)
    ring = jnp.concatenate(outs_ring, axis=1)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(y_ref_all),
                               atol=5e-4)


def test_cache_len_sizing():
    swa = get_smoke_config("mixtral-8x22b")
    assert attention.cache_len(swa, 32768) == swa.window
    full = get_smoke_config("olmo-1b")
    assert attention.cache_len(full, 32768) == 32768


def test_cache_shardings_divisibility_safe():
    """Every cache spec produced must be loadable as explicit jit shardings
    (even divisibility), for every arch at every decode shape."""
    from repro.configs import arch_ids, get_config
    from repro.distributed import partitioning
    from repro.models import build_model
    # abstract mesh: spec-only validation without needing 8 real devices
    mesh = jax.sharding.AbstractMesh(
        (2, 4), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    for arch in arch_ids():
        cfg = get_config(arch)
        model = build_model(cfg)
        cstruct = jax.eval_shape(lambda m=model: m.init_cache(8, 64))
        shards = partitioning.cache_shardings(mesh, cstruct)
        for leaf, sh in zip(jax.tree.leaves(cstruct),
                            jax.tree.leaves(shards,
                                            is_leaf=lambda x: isinstance(
                                                x, jax.sharding.Sharding))):
            for dim, entry in enumerate(sh.spec):
                if entry is None:
                    continue
                axes = entry if isinstance(entry, tuple) else (entry,)
                factor = int(np.prod([mesh.shape[a] for a in axes]))
                assert leaf.shape[dim] % factor == 0, (arch, leaf.shape,
                                                       sh.spec)


def test_checkpoint_elastic_reshard():
    """Save on one mesh factoring, restore onto another."""
    from repro.checkpoint import checkpointer as ckpt
    from repro.launch.mesh import make_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P
    state = {"w": jnp.arange(32, dtype=jnp.float32).reshape(8, 4)}
    with tempfile.TemporaryDirectory() as td:
        ckpt.save(td, 1, state, mesh_signature="data=1xmodel=1")
        mesh = make_mesh((1, 1), ("data", "model"))
        sh = {"w": NamedSharding(mesh, P("data", None))}
        restored = ckpt.restore(td, 1, jax.eval_shape(lambda: state),
                                shardings=sh)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(state["w"]))
        assert restored["w"].sharding.spec == P("data", None)


def test_decode_cache_donation_shape_stable():
    """Repeated decode steps keep cache shapes/dtypes identical (donation
    contract for the serving loop)."""
    from repro.models import build_model
    cfg = get_smoke_config("gemma-2b")
    model = build_model(cfg, q_block=8, kv_block=8)
    params = model.init(jax.random.PRNGKey(0))
    cache = model.init_cache(2, 16)
    struct0 = jax.tree.map(lambda x: (x.shape, x.dtype), cache)
    tok = jnp.zeros((2, 1), jnp.int32)
    for _ in range(3):
        _, cache = model.decode(params, cache, tok)
    struct1 = jax.tree.map(lambda x: (x.shape, x.dtype), cache)
    assert struct0 == struct1
