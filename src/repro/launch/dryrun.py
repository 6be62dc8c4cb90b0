"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell, builds abstract params / optimizer state / inputs
(ShapeDtypeStruct only — nothing allocated), attaches NamedShardings from
repro.distributed.sharding, then:

    lowered  = jax.jit(step, in_shardings=..., out_shardings=...).lower(...)
    compiled = lowered.compile()
    print(compiled.memory_analysis())   # proves it fits per-device HBM
    print(compiled.cost_analysis())     # FLOPs / bytes for the roofline

plus collective-byte accounting parsed from the partitioned HLO text
(all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute result sizes). Results append to a JSON file consumed
by EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
    python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k \
        --mesh single --out results/dryrun.json
    python -m repro.launch.dryrun --all --mesh both   # every cell
"""
from __future__ import annotations

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import re
import sys
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.distributed import sharding as sh
from repro.launch import shapes as shp
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh, make_train_opt_mesh
from repro.models.registry import get_model
from repro.train import get_optimizer, make_train_step


def _mem_dict(mem) -> dict:
    if mem is None:
        return {}
    keys = ("generated_code_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "temp_size_in_bytes",
            "alias_size_in_bytes", "peak_memory_in_bytes")
    return {k: int(getattr(mem, k)) for k in keys if hasattr(mem, k)}


def build_step(arch: str, shape_name: str, mesh, variant: str = "baseline"):
    """Returns (fn, abstract_args, in_shardings, out_shardings).

    variant="opt" applies the §Perf hillclimb configuration:
      * serve cells: bf16 weights, no remat wrapper, weights replicated
        over the batch axes (no per-step FSDP all-gathers),
      * train cells: Megatron-SP sequence-sharded residual stream.
    """
    cfg = get_config(arch)
    case = shp.SHAPES[shape_name]
    serve_params = False
    if variant == "kvq":
        # §Perf C3: opt serve settings + INT8 nibble-planar K cache with
        # two-stage hierarchical attention (decode cells, dense/vlm only)
        assert case.kind == "decode" and cfg.family in ("dense", "vlm")
        cfg = cfg.with_(param_dtype="bfloat16", remat=False)
        api = get_model(cfg)
        aparams = jax.eval_shape(api.init, jax.random.PRNGKey(0))
        pspec = sh.param_shardings(aparams, mesh, cfg, serve=True)
        from repro.models import dense as dense_mod
        acache = jax.eval_shape(
            lambda: dense_mod.init_quant_cache(cfg, case.batch, case.seq))
        cspec = sh.cache_shardings(acache, mesh, cfg)
        atok = shp.abstract_decode_tokens(case)
        tspec = sh.batch_shardings(atok, mesh)

        def qstep(params, cache, tokens):
            return dense_mod.decode_step_quant(params, cache, tokens, cfg)

        alogits = jax.eval_shape(qstep, aparams, acache, atok)[0]
        lspec = sh.batch_shardings(alogits, mesh)
        return (qstep, (aparams, acache, atok), (pspec, cspec, tspec),
                (lspec, cspec), {"donate_argnums": (1,)})
    if variant == "opt":
        # Per-cell selection from the measured sweep (EXPERIMENTS.md §Perf):
        #  * decode: bf16 weights REPLICATED over batch axes (kills the
        #    per-token FSDP gathers; 9-15x) + donated caches;
        #  * prefill: bf16 weights, BASELINE sharding (replication
        #    regressed the big dense archs 2-3x via forced reshards);
        #  * train: rebalanced (64,4) mesh for non-MoE (2.4-4.1x); MoE
        #    keeps (16,16) (experts need the wide model axis).
        # Megatron-SP was tried and REFUTED (§Perf A3) — plain TP kept.
        if case.kind == "decode":
            cfg = cfg.with_(param_dtype="bfloat16", remat=False)
            serve_params = True
        elif case.kind == "prefill":
            cfg = cfg.with_(param_dtype="bfloat16", remat=False)
    api = get_model(cfg)
    aparams = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    pspec = sh.param_shardings(aparams, mesh, cfg, serve=serve_params)
    repl = NamedSharding(mesh, P())

    if case.kind == "train":
        opt = get_optimizer(cfg.optimizer)
        astate = jax.eval_shape(opt.init, aparams)
        ospec = sh.opt_state_shardings(astate, aparams, mesh, cfg)
        abatch = shp.abstract_batch(cfg, case)
        bspec = sh.batch_shardings(abatch, mesh)
        step = make_train_step(api.loss_fn, opt)
        mspec = {"loss": repl, "grad_norm": repl}
        return (step, (aparams, astate, abatch), (pspec, ospec, bspec),
                (pspec, ospec, mspec), {"donate_argnums": (0, 1)})

    if case.kind == "prefill":
        abatch = shp.abstract_batch(cfg, case)
        abatch.pop("labels", None)
        bspec = sh.batch_shardings(abatch, mesh)

        def step(params, batch):
            return api.prefill(params, batch, max_len=case.seq)

        _, acache = jax.eval_shape(step, aparams, abatch)
        cspec = sh.cache_shardings(acache, mesh, cfg)
        alogits = jax.eval_shape(step, aparams, abatch)[0]
        lspec = sh.batch_shardings(alogits, mesh)
        return (step, (aparams, abatch), (pspec, bspec), (lspec, cspec), {})

    # decode — the cache is DONATED (production decode always aliases the
    # KV buffers in-place; without donation the cache is double-counted
    # and deepseek-67b decode peaks at 21 GB > 16 GB HBM; §Perf C2)
    acache = shp.abstract_cache(cfg, api, case)
    cspec = sh.cache_shardings(acache, mesh, cfg)
    atok = shp.abstract_decode_tokens(case)
    tspec = sh.batch_shardings(atok, mesh)

    def step(params, cache, tokens):
        return api.decode_step(params, cache, tokens)

    alogits = jax.eval_shape(step, aparams, acache, atok)[0]
    lspec = sh.batch_shardings(alogits, mesh)
    return (step, (aparams, acache, atok), (pspec, cspec, tspec),
            (lspec, cspec), {"donate_argnums": (1,)})


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             verbose: bool = True, variant: str = "baseline") -> dict:
    cfg = get_config(arch)
    ok, why = shp.applicable(cfg, shape_name)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind}
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    if (variant == "opt" and shp.SHAPES[shape_name].kind == "train"
            and cfg.family != "moe"):
        mesh = make_train_opt_mesh(multi_pod=(mesh_kind == "multi"))
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    with jax.set_mesh(mesh):                     # activates activation pins
        t0 = time.time()
        fn, args, in_sh, out_sh, jkw = build_step(arch, shape_name, mesh,
                                                  variant=variant)
        lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                          **jkw).lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    mem = _mem_dict(compiled.memory_analysis())
    cost = dict(compiled.cost_analysis() or {})
    cost = {k: float(v) for k, v in cost.items()
            if isinstance(v, (int, float)) and k in
            ("flops", "bytes accessed", "transcendentals",
             "bytes accessed0{}", "bytes accessedout{}")}
    # while-aware per-device dot-FLOPs + collective bytes (hlo_analysis)
    hlo = hlo_analysis.analyze(compiled.as_text())
    rec.update(status="ok", devices=int(mesh.devices.size),
               lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
               memory=mem, cost=cost, dot_flops=hlo["dot_flops"],
               collectives=hlo["collectives"],
               collective_counts=hlo["collective_counts"])
    if verbose:
        print(f"  memory_analysis: {mem}")
        print(f"  cost_analysis:   {cost}")
        print(f"  dot_flops/dev:   {hlo['dot_flops']:.3e}")
        print(f"  collectives:     {hlo['collectives']}")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(shp.SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--variant", choices=("baseline", "opt"),
                    default="baseline")
    ap.add_argument("--out", default="results/dryrun.json")
    args = ap.parse_args(argv)

    cells = []
    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
    if args.all:
        for a in ARCH_IDS:
            for s in shp.SHAPES:
                for m in meshes:
                    cells.append((a, s, m))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape, m) for m in meshes]

    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results
            if r.get("status") in ("ok", "skipped")}

    failures = 0
    for a, s, m in cells:
        if (a, s, m) in done:
            print(f"[cached] {a} x {s} x {m}")
            continue
        print(f"[dryrun] {a} x {s} x {m} ({args.variant})")
        try:
            rec = run_cell(a, s, m, variant=args.variant)
        except Exception as e:  # noqa: BLE001 — record and continue
            traceback.print_exc()
            rec = {"arch": a, "shape": s, "mesh": m, "status": "error",
                   "error": f"{type(e).__name__}: {e}"}
            failures += 1
        results = [r for r in results if
                   (r["arch"], r["shape"], r["mesh"]) != (a, s, m)]
        results.append(rec)
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"  -> {rec['status']}")
    print(f"done: {len(cells)} cells, {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
