"""Compile-only checks of the served path's programs for one TPU v5e chip.

The TPU compiler ships with jaxlib and compiles for a chip that is only
described, not attached, so these tests run on the CPU: each one lowers a
program at paper_5k widths (`configs/sinkhorn_wmd.py`: Q=8 queries, v_r=32,
V=100 000, w=300, N=5000 docs, nnz 144, 15 iterations) and compiles it for
one chip of a described ``v5e:2x2`` topology. A pass says the program lowers
and fits the chip; nothing runs, so it says nothing about results or speed.

The batched Pallas bound kernels are strict xfails carrying Mosaic's refusal:
their ``docs_blk = 8`` output blocks put 8 docs on the 128-wide lane axis. A
change that makes one compile flips its case.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and pytest-xdist workers all
import this file. The persistent compilation cache is off around these
tests -- a described-chip executable cannot be read back without a chip.
"""
import os
import re

import numpy as np
import pytest

Q, V_R, V, N, NNZ, W, ITERS = 8, 32, 100_000, 5000, 144, 300, 15
MISS_ROWS = 256          # one precompute miss batch
DOCS_CHUNK = 256         # the service's bound_docs_chunk
RERANK_CHUNK = 64        # the service's prune_chunk

MOSAIC_LANES = ("last two dimensions of your block shape are divisible by 8 "
                "and 128")


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import compilation_cache as cc
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                     # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """Shape factory: ``chip(shape, dtype)`` on one described chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)


@pytest.fixture(scope="module")
def mesh_args(topo):
    """The service's (1, 1) ("data", "model") mesh on one described chip and
    a factory of shapes placed on it with a PartitionSpec."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))

    def on(shape, *spec, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))
    return mesh, on


_CALLED = re.compile(r"\b(calls|to_apply|body|condition)=%?([\w.-]+)")
_GATHER = re.compile(r"\sgather\(")
_WHILE = re.compile(r"\swhile\(")


def _gather_sites(hlo: str, op: re.Pattern = _GATHER) -> tuple[int, int]:
    """(``op`` sites outside every ``while`` body, sites inside one) of a
    compiled HLO text, following fusions and other called computations;
    gathers by default."""
    comps: dict[str, list[str]] = {}
    entry = cur = None
    for line in hlo.splitlines():
        m = re.match(r"(ENTRY )?%?([\w.-]+) .*\{$", line)
        if m and not line.startswith(" "):
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
        elif cur is not None:
            comps[cur].append(line)

    def reach(roots, skip_bodies):
        seen, todo = set(), list(roots)
        while todo:
            c = todo.pop()
            if c not in seen:
                seen.add(c)
                todo += [callee for line in comps[c]
                         for kind, callee in _CALLED.findall(line)
                         if not (skip_bodies and kind == "body")]
        return seen

    bodies = [callee for lines in comps.values() for line in lines
              for kind, callee in _CALLED.findall(line) if kind == "body"]
    inside = reach(bodies, False)
    outside = reach([entry], True) - inside
    return tuple(sum(len(op.findall(line)) for c in cs
                     for line in comps[c]) for cs in (outside, inside))


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 16 * 2**30, used                 # one v5e chip's HBM
    return compiled


def _plain_program(mesh_args, *, q=Q, v_r=V_R, v=V, n=N, nnz=NNZ, **kw):
    import jax.numpy as jnp
    from repro.core.distributed import build_wmd_batch_fn
    mesh, on = mesh_args
    fn = build_wmd_batch_fn(mesh, lamb=1.0, max_iter=ITERS, **kw)
    return _compile(fn, on((q, v_r, W)), on((q, v_r)), on((q, v_r)),
                    on((v, W), "model", None),
                    on((1, n, nnz), "model", "data", None, dtype=jnp.int32),
                    on((1, n, nnz), "model", "data", None))


def _gather_operands(hlo: str) -> list[tuple[tuple[int, ...], ...]]:
    """(operand dims, slice sizes) of every gather of a compiled HLO text."""
    dims = lambda text: tuple(int(d) for d in text.split(",") if d)
    shapes = dict(re.findall(r"%([\w.-]+) = \w+\[([\d,]*)\]", hlo))
    return [(dims(shapes[op]), dims(sizes)) for op, sizes in re.findall(
        r"\sgather\(%([\w.-]+),.*?slice_sizes=\{([\d,]*)\}", hlo)]


def _row_table(v: int, v_r: int) -> re.Pattern:
    """Ops that make a (V+1, Q*v_r) float32 row table (not the parameters
    and tuple reads that pass one on), for `_gather_sites`."""
    return re.compile(rf"= f32\[{v + 1},{Q * v_r}\]\S* "
                      r"(?!parameter\(|get-tuple-element\()")


@pytest.fixture(scope="module")
def plain_5k(mesh_args):
    """The paper_5k plain-request program, compiled once for its tests."""
    return _plain_program(mesh_args)


def _news20_plan(q: int):
    """news20's config, one v5e's budget (16 GiB less the service's slack,
    less the corpus and embeddings) and the service's chunk plan at ``q``
    queries for it: (config, budget, chunk, program shape)."""
    from repro.configs.sinkhorn_wmd import config
    from repro.core.distributed import plan_docs_chunk
    from repro.serving.wmd_service import PLAN_SLACK
    cfg = config("news20")
    shape = dict(q=q, v_r=cfg.v_r, v=cfg.vocab_size, n=cfg.num_docs,
                 nnz=cfg.nnz_max)
    resident = 4 * (cfg.vocab_size * W + 2 * cfg.num_docs * cfg.nnz_max)
    budget = int(16 * 2**30 * (1.0 - PLAN_SLACK)) - resident
    chunk = plan_docs_chunk(q, cfg.v_r, cfg.nnz_max, cfg.num_docs,
                            cfg.vocab_size, budget)
    return cfg, budget, chunk, shape


@pytest.fixture(scope="module")
def news20_planned(mesh_args):
    """news20's program at the plan's chunk on one v5e's budget, compiled
    once for its tests: (config, budget, chunk, compiled)."""
    cfg, budget, chunk, shape = _news20_plan(Q)
    assert chunk
    return cfg, budget, chunk, _plain_program(mesh_args, docs_chunk=chunk,
                                              **shape)


def test_service_plain_program_compiles(plain_5k):
    """The plain-request program the service dispatches by default (no K
    cache: precompute fused into the solve, unchunked, shard_map'd). It
    gathers K at the ELL slots once, before the Sinkhorn loop, and K.*M
    once for the final pass; the loop gathers nothing. The block it
    carries is laid out (Q, v_r, N, nnz): with v_r on the 128-wide lane
    axis it would be padded 4x, and temp would pass 4 GiB."""
    assert _gather_sites(plain_5k.as_text()) == (2, 0)
    assert plain_5k.memory_analysis().temp_size_in_bytes < 3 * 2**30


def test_service_plain_program_gathers_row_table(plain_5k):
    """Both gathers of that program read a (V+1, Q*v_r) row table, one
    contiguous row of Q*v_r floats an ELL slot, not Q strided rows."""
    assert _gather_operands(plain_5k.as_text()) == \
        [((V + 1, Q * V_R), (1, Q * V_R))] * 2


def test_news20_planned_program_compiles(news20_planned):
    """news20 at Q = 8 (`configs/sinkhorn_wmd.py`: v_r 288, V 29 671,
    11 293 docs, ELL 288 wide; 80 GB of K and K.*M blocks unchunked): the
    service's plan on one v5e's budget (16 GiB less its slack, less the
    corpus and embeddings) chunks the solve over documents. At that chunk
    the program fits the chip, takes more than half the budget, and holds
    one Sinkhorn loop, in the body of the rolled chunk loop, not one per
    chunk; both gathers sit in the chunk body. No op takes bfloat16: at
    v_r 288 XLA puts the SDDMM on the MXU, which at default precision
    rounds K and u to bfloat16."""
    cfg, budget, chunk, compiled = news20_planned
    assert -(-cfg.num_docs // chunk) >= 8
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes > budget / 2
    hlo = compiled.as_text()
    assert _gather_sites(hlo, _WHILE) == (1, 1)
    assert _gather_sites(hlo) == (0, 2)
    assert "bf16" not in hlo


def test_news20_planned_program_gathers_row_table(news20_planned):
    """At news20's planned chunk both gathers, still in the chunk body,
    read a (V+1, Q*v_r) row table, which is built outside the rolled chunk
    loop, once a dispatch; and the program stays within the budget that
    the plan counted its chunk against, its temp within the plan's own
    count (`solve_fixed_bytes` beside the chunk's document blocks)."""
    from repro.core.distributed import solve_bytes_per_doc, solve_fixed_bytes
    cfg, budget, chunk, compiled = news20_planned
    hlo = compiled.as_text()
    row = Q * cfg.v_r
    assert _gather_operands(hlo) == [((cfg.vocab_size + 1, row),
                                      (1, row))] * 2
    assert _gather_sites(hlo) == (0, 2)
    built_outside, built_inside = _gather_sites(
        hlo, _row_table(cfg.vocab_size, cfg.v_r))
    assert built_outside > 0 and built_inside == 0
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes <= budget
    assert mem.temp_size_in_bytes <= (
        solve_fixed_bytes(Q, cfg.v_r, cfg.vocab_size)
        + chunk * solve_bytes_per_doc(Q, cfg.v_r, cfg.nnz_max))


def _stripes_program(mesh_args, **kw):
    import jax.numpy as jnp
    from repro.core.distributed import build_wmd_batch_fn_stripes
    mesh, on = mesh_args
    fn = build_wmd_batch_fn_stripes(mesh, max_iter=ITERS, **kw)
    return _compile(fn, on((1, Q, V_R, V + 1), "model"),
                    on((1, Q, V_R, V + 1), "model"), on((Q, V_R)),
                    on((1, N, NNZ), "model", "data", None, dtype=jnp.int32),
                    on((1, N, NNZ), "model", "data", None))


def _news20_q1_program(mesh_args):
    """news20's plain program at Q = 1 with the service's plan, which at
    one query leaves the solve unchunked."""
    cfg, budget, chunk, shape = _news20_plan(1)
    assert chunk is None
    compiled = _plain_program(mesh_args, **shape)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes <= budget
    return compiled


@pytest.mark.parametrize("build,kw", [
    pytest.param(_plain_program, {"tol": 1e-3}, id="plain-early_exit"),
    pytest.param(_stripes_program, {}, id="stripes"),
    pytest.param(_stripes_program, {"tol": 1e-3}, id="stripes-early_exit"),
    pytest.param(_plain_program, {"q": 1}, id="q1"),
    pytest.param(_news20_q1_program, {}, id="q1_news20_planned"),
])
def test_hoisted_programs_gather_outside_loop(mesh_args, build, kw):
    """The early-exit ``while`` loop, the K-cache stripes program and the
    one-query program (the engine `WMDService.query` dispatches) keep the
    K block outside the loop too: two gathers before it (K, K.*M), none
    inside. At paper_5k the lane-dense block's temp stays under 3 GiB."""
    compiled = build(mesh_args, **kw)
    assert _gather_sites(compiled.as_text()) == (2, 0)
    if build is not _news20_q1_program:
        assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2**30


def test_service_rerank_program_compiles(mesh_args):
    """The pruned top-k rerank: one query's stripes against one block."""
    import jax.numpy as jnp
    from repro.core.distributed import build_wmd_batch_fn_stripes
    mesh, on = mesh_args
    fn = build_wmd_batch_fn_stripes(mesh, max_iter=ITERS)
    _compile(fn, on((1, 1, V_R, V + 1), "model"),
             on((1, 1, V_R, V + 1), "model"), on((1, V_R)),
             on((1, RERANK_CHUNK, NNZ), "model", "data", None,
                dtype=jnp.int32),
             on((1, RERANK_CHUNK, NNZ), "model", "data", None))


def test_chunked_stripes_solve_compiles(chip):
    import jax
    import jax.numpy as jnp
    from repro.core.sparse_sinkhorn import sinkhorn_wmd_sparse_batch_stripes
    fn = jax.jit(lambda k, km, r, c, v: sinkhorn_wmd_sparse_batch_stripes(
        k, km, r, c, v, ITERS, docs_chunk=DOCS_CHUNK))
    _compile(fn, chip((Q, V_R, V + 1)), chip((Q, V_R, V + 1)),
             chip((Q, V_R)), chip((N, NNZ), jnp.int32), chip((N, NNZ)))


def test_precompute_rows_compiles(chip):
    import jax
    import jax.numpy as jnp
    from repro.core.sinkhorn import precompute_rows
    fn = jax.jit(lambda ids, vecs, b2: precompute_rows(ids, vecs, 1.0, b2=b2))
    _compile(fn, chip((MISS_ROWS,), jnp.int32), chip((V, W)), chip((V,)))


def test_fused_bound_tiers_compile(chip):
    """LC-RWMD over the corpus in bound_docs_chunk blocks, and the capped
    doc-side RWMD tier over its 4 * prune_chunk doc subset."""
    import jax
    import jax.numpy as jnp
    from repro.core.cascade import lc_rwmd_bound_batch
    from repro.core.rwmd import rwmd_bound_batch
    lc = jax.jit(lambda m, c, v: lc_rwmd_bound_batch(m, c, v,
                                                     docs_chunk=DOCS_CHUNK))
    _compile(lc, chip((Q, V + 1)), chip((N, NNZ), jnp.int32), chip((N, NNZ)))
    sub = 4 * RERANK_CHUNK
    rw = jax.jit(lambda m, c, v: rwmd_bound_batch(m, c, v))
    _compile(rw, chip((Q, V_R, V + 1)), chip((sub, NNZ), jnp.int32),
             chip((sub, NNZ)))


def test_kexp_rows_kernel_compiles(chip):
    """The row-subset fused precompute kernel (w padded to 384 lanes, as
    `kernels.ops.cdist_kexp_rows` pads it)."""
    import jax
    from repro.kernels import kexp
    fn = jax.jit(lambda a, b: kexp.cdist_kexp_rows(a, b, lamb=1.0,
                                                   interpret=False))
    compiled = _compile(fn, chip((MISS_ROWS, 384)), chip((V, 384)))
    assert "tpu_custom_call" in compiled.as_text()


def _rwmd_kernel(chip):
    import jax
    import jax.numpy as jnp
    from repro.kernels import rwmd
    fn = jax.jit(lambda m, c, v: rwmd.rwmd_bound_batch(
        m, c, v, docs_blk=8, q_blk=8, interpret=False))
    return fn, (chip((Q, V_R, V + 1)), chip((N, NNZ), jnp.int32),
                chip((N, NNZ)))


def _lcrwmd_kernel(chip):
    import jax
    import jax.numpy as jnp
    from repro.kernels import lcrwmd
    fn = jax.jit(lambda m, c, v: lcrwmd.lc_rwmd_bound_batch(
        m, c, v, docs_blk=8, q_blk=8, interpret=False))
    return fn, (chip((Q, V + 1)), chip((N, NNZ), jnp.int32),
                chip((N, NNZ)))


_REFUSED = pytest.mark.xfail(
    strict=True, raises=ValueError,
    reason=f"Mosaic: the {MOSAIC_LANES} (docs_blk=8 puts 8 docs on the "
           f"128-wide lane axis)")


@pytest.mark.parametrize("build", [
    pytest.param(_rwmd_kernel, id="rwmd_bound_batch", marks=_REFUSED),
    pytest.param(_lcrwmd_kernel, id="lc_rwmd_bound_batch", marks=_REFUSED),
])
def test_batched_kernel_compiles(chip, build):
    fn, args = build(chip)
    try:
        _compile(fn, *args)
    except ValueError as e:
        assert MOSAIC_LANES in str(e), e            # refused for this reason
        raise
