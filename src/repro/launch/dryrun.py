"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be the very first two lines -- before any other import, including
`from repro...`, since jax locks the device count on first init:
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import (cell_supported, cells, get_config,  # noqa: E402
                           get_shape)
from repro.configs import sinkhorn_wmd as wmd_cfg  # noqa: E402
from repro.data.tokens import batch_struct  # noqa: E402
from repro.distributed import partitioning  # noqa: E402
from repro.launch import costmodel  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.sharding_hints import activation_sharding  # noqa: E402
from repro.optim import adamw, warmup_cosine  # noqa: E402
from repro.serving.serve_step import build_serve_fns  # noqa: E402
from repro.train import step as train_step_mod  # noqa: E402

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _with_shardings(mesh, struct, shardings):
    """Attach NamedShardings to a pytree of ShapeDtypeStructs."""
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        struct, shardings)


def input_specs(arch: str, shape: str, mesh):
    """ShapeDtypeStruct stand-ins for every model input of this cell --
    weak-type-correct, shardable, no device allocation."""
    cfg = get_config(arch)
    sh = get_shape(shape)
    remat = os.environ.get("REPRO_REMAT", "1") == "1"
    model = build_model(cfg, remat=remat)
    bstruct = batch_struct(cfg, sh)
    bshard = partitioning.batch_shardings(mesh, bstruct)
    bstruct = _with_shardings(mesh, bstruct, bshard)

    pstruct = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pshard = partitioning.param_shardings(mesh, pstruct)
    pstruct_s = _with_shardings(mesh, pstruct, pshard)

    if sh.kind == "train":
        # per-arch gradient-accumulation defaults chosen so the train cell
        # fits v5e HBM (16 GiB) -- the §Perf memory iteration; override with
        # REPRO_MICROBATCHES.
        default_mb = {
            "mixtral-8x22b": 16, "deepseek-moe-16b": 16, "paligemma-3b": 8,
            "minicpm3-4b": 8, "whisper-small": 4, "recurrentgemma-9b": 4,
            "starcoder2-3b": 4, "gemma-2b": 2, "olmo-1b": 2, "xlstm-125m": 1,
        }.get(arch, 1)
        microbatches = int(os.environ.get("REPRO_MICROBATCHES",
                                          str(default_mb)))
        opt = adamw(warmup_cosine(1e-4, warmup_steps=100, total_steps=1000))
        sstruct = jax.eval_shape(
            lambda k: train_step_mod.init_state(model, opt, k),
            jax.random.PRNGKey(0))
        sshard = train_step_mod.state_shardings(mesh, sstruct)
        sstruct = jax.tree.map(
            lambda s, sh_: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                sharding=sh_),
            sstruct, sshard)
        return {"kind": "train", "model": model, "opt": opt,
                "microbatches": microbatches, "args": (sstruct, bstruct)}
    if sh.kind == "prefill":
        return {"kind": "prefill", "model": model, "max_len": sh.seq_len,
                "batch_size": sh.global_batch, "args": (pstruct_s, bstruct)}
    # decode: one new token with a KV cache of seq_len
    cstruct = jax.eval_shape(
        lambda: model.init_cache(sh.global_batch, sh.seq_len))
    # pos indicates a full cache
    cshard = partitioning.cache_shardings(mesh, cstruct)
    cstruct = _with_shardings(mesh, cstruct, cshard)
    tok = jax.ShapeDtypeStruct(
        (sh.global_batch, 1), jnp.int32,
        sharding=NamedSharding(mesh, partitioning.sanitize_spec(
            mesh, partitioning.batch_spec(mesh, 2), (sh.global_batch, 1))))
    return {"kind": "decode", "model": model, "max_len": sh.seq_len,
            "batch_size": sh.global_batch, "args": (pstruct_s, cstruct, tok)}


def lower_cell(arch: str, shape: str, mesh):
    spec = input_specs(arch, shape, mesh)
    model = spec["model"]
    mode = "decode" if spec["kind"] == "decode" else "train"
    with mesh, activation_sharding(mesh, mode=mode):
        if spec["kind"] == "train":
            fn = train_step_mod.build_train_step(
                model, spec["opt"], mesh, donate=True,
                microbatches=spec.get("microbatches", 1))
            traced = fn.trace(*spec["args"])
        elif spec["kind"] == "prefill":
            jit_prefill, _ = build_serve_fns(model, mesh,
                                             max_len=spec["max_len"])
            traced = jit_prefill(spec["batch_size"]).trace(*spec["args"])
        else:
            _, jit_decode = build_serve_fns(model, mesh,
                                            max_len=spec["max_len"])
            traced = jit_decode(spec["batch_size"],
                                donate_cache=True).trace(*spec["args"])
    return traced


def lower_wmd(shape: str, mesh):
    """The paper's own workload as a dry-run cell (11th config).

    ``*_opt`` shapes lower the §Perf-optimized engine: doc-sharded /
    K-replicated layout (zero in-loop collectives) + length-bucketed ELL
    (nnz_max 48 instead of 128+rebucket padding).
    """
    from repro.core.distributed import (build_wmd_batch_fn,
                                        build_wmd_fn_docsharded)
    if shape.endswith("_opt"):
        cfg = wmd_cfg.config(shape[:-4])
        doc_par = 1
        for a in mesh.axis_names:
            doc_par *= mesh.shape[a]
        num_docs = -(-cfg.num_docs // doc_par) * doc_par
        nnz = 48  # bucketed mean (bench_padding: 1.38 slots/nnz at mean 35)
        fn = build_wmd_fn_docsharded(mesh, lamb=cfg.lamb,
                                     max_iter=cfg.max_iter)
        sd = jax.ShapeDtypeStruct
        ns = lambda spec: NamedSharding(mesh, spec)
        all_axes = tuple(mesh.axis_names)
        args = (
            sd((cfg.v_r, cfg.embed_dim), jnp.float32, sharding=ns(P())),
            sd((cfg.v_r,), jnp.float32, sharding=ns(P())),
            sd((cfg.v_r,), jnp.float32, sharding=ns(P())),
            sd((cfg.vocab_size, cfg.embed_dim), jnp.float32,
               sharding=ns(P())),
            sd((num_docs, nnz), jnp.int32, sharding=ns(P(all_axes, None))),
            sd((num_docs, nnz), jnp.float32,
               sharding=ns(P(all_axes, None))),
        )
        with mesh, activation_sharding(mesh):
            return fn.trace(*args)
    cfg = wmd_cfg.config(shape)
    model_par = mesh.shape["model"]
    doc_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    doc_par = 1
    for a in doc_axes:
        doc_par *= mesh.shape[a]
    # pad the doc axis to the doc-sharding factor (formats.pad_docs at load
    # time does the same for real data)
    num_docs = -(-cfg.num_docs // doc_par) * doc_par
    nnz_loc = max(cfg.nnz_max // model_par * 2, 16)  # rebucket headroom
    # the served solve at Q = 1
    fn = build_wmd_batch_fn(mesh, lamb=cfg.lamb, max_iter=cfg.max_iter,
                            doc_axes=doc_axes)
    sd = jax.ShapeDtypeStruct
    ns = lambda spec: NamedSharding(mesh, spec)
    args = (
        sd((1, cfg.v_r, cfg.embed_dim), jnp.float32, sharding=ns(P())),
        sd((1, cfg.v_r), jnp.float32, sharding=ns(P())),
        sd((1, cfg.v_r), jnp.float32, sharding=ns(P())),
        sd((cfg.vocab_size, cfg.embed_dim), jnp.float32,
           sharding=ns(P("model", None))),
        sd((model_par, num_docs, nnz_loc), jnp.int32,
           sharding=ns(P("model", doc_axes, None))),
        sd((model_par, num_docs, nnz_loc), jnp.float32,
           sharding=ns(P("model", doc_axes, None))),
    )
    with mesh, activation_sharding(mesh):
        return fn.trace(*args)


def analyze(traced, *, hlo_collectives: bool = True) -> dict:
    t0 = time.perf_counter()
    lowered = traced.lower()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    out = {
        "compile_seconds": compile_s,
        "memory_analysis": {
            k: getattr(mem, k, None) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
                "alias_size_in_bytes")
        } if mem is not None else None,
        "cost_analysis_raw": {k: cost.get(k) for k in ("flops",
                                                       "bytes accessed")}
        if cost else None,
    }
    # exact-trip-count logical cost from the traced jaxpr
    try:
        jc = costmodel.jaxpr_cost(traced.jaxpr)
    except Exception:
        jc = None
    if jc is not None:
        out["jaxpr_cost"] = {"flops": jc.flops, "bytes": jc.bytes,
                             "unknown_loops": jc.unknown_loops}
    if hlo_collectives:
        try:
            out["collectives"] = costmodel.collective_bytes(
                compiled.as_text())
        except Exception as e:  # parser must never fail a cell
            out["collectives"] = {"error": str(e)}
    return out


def run_cell(arch: str, shape: str, *, multi_pod: bool,
             out_dir: str = OUT_DIR) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    os.makedirs(os.path.join(out_dir, mesh_name), exist_ok=True)
    out_path = os.path.join(out_dir, mesh_name, f"{arch}__{shape}.json")
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name}
    try:
        if arch == "sinkhorn-wmd":
            mesh = make_production_mesh(multi_pod=multi_pod)
            traced = lower_wmd(shape, mesh)
        else:
            ok, why = cell_supported(arch, shape)
            if not ok:
                rec.update({"status": "skipped", "reason": why})
                with open(out_path, "w") as f:
                    json.dump(rec, f, indent=1)
                return rec
            mesh = make_production_mesh(multi_pod=multi_pod)
            traced = lower_cell(arch, shape, mesh)
        rec.update(analyze(traced))
        rec["status"] = "ok"
    except Exception as e:
        rec.update({"status": "error", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:]})
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (arch x shape) incl. sinkhorn-wmd cells")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out-dir", default=OUT_DIR)
    args = ap.parse_args()

    todo = []
    if args.all:
        todo = cells() + [("sinkhorn-wmd", "paper_5k"),
                          ("sinkhorn-wmd", "prod_5m"),
                          ("sinkhorn-wmd", "prod_5m_opt")]
    elif args.arch and args.shape:
        todo = [(args.arch, args.shape)]
    else:
        ap.error("--arch/--shape or --all required")

    mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
    for arch, shape in todo:
        out_path = os.path.join(args.out_dir, mesh_name,
                                f"{arch}__{shape}.json")
        if args.skip_existing and os.path.exists(out_path):
            with open(out_path) as f:
                if json.load(f).get("status") in ("ok", "skipped"):
                    print(f"[dryrun] {arch} x {shape}: exists, skipping")
                    continue
        t0 = time.perf_counter()
        rec = run_cell(arch, shape, multi_pod=args.multi_pod,
                       out_dir=args.out_dir)
        dt = time.perf_counter() - t0
        status = rec.get("status")
        extra = ""
        if status == "ok":
            ma = rec.get("memory_analysis") or {}
            extra = (f" temp={ma.get('temp_size_in_bytes', 0) / 2**30:.2f}GiB"
                     f" flops={rec.get('jaxpr_cost', {}).get('flops', 0):.3e}"
                     f" coll={rec.get('collectives', {}).get('total', 0):.3e}B")
        elif status == "error":
            extra = " " + rec.get("error", "")[:160]
        print(f"[dryrun] {arch} x {shape} ({mesh_name}): {status}"
              f" ({dt:.1f}s){extra}", flush=True)


if __name__ == "__main__":
    main()
