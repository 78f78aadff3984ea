import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any other import (jax locks the device
# count and the backend on first init): the dry-run compiles for 512 fake
# host devices and never claims an accelerator. Everything below is ordinary.
"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh) combination
against the production mesh, and extract the roofline terms from the compiled
artifact (no device allocation — inputs are ShapeDtypeStructs).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-0.5b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --out dryrun.json
Options: --multi-pod (2x16x16 mesh), --variant dense|ring (gossip path)
"""
import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo_lint import run_lint
from repro.configs.registry import (ARCH_IDS, cache_len, for_shape, get_config,
                                    shape_by_name)
from repro.dist import serve as serve_mod
from repro.dist import sharding as sh
from repro.dist.sparq_dist import DistSparqConfig, build_sparq
from repro.launch import hlo_walk
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                               make_production_mesh)
from repro.models.config import INPUT_SHAPES, InputShape, ModelConfig

def model_flops(cfg: ModelConfig, shape: InputShape) -> float:
    """MODEL_FLOPS = 6*N*D train / 2*N*D inference (N = active params for MoE,
    D = processed tokens). Attention's quadratic term is intentionally NOT in
    MODEL_FLOPS — the useful_flops ratio therefore reads low for long-context
    prefill, which is informative (it quantifies non-parameter compute)."""
    n_params = active_param_count(cfg)
    if shape.is_decode:
        tokens = shape.global_batch  # one token per sequence
    else:
        tokens = shape.global_batch * shape.seq_len
    factor = 6.0 if shape.kind == "train" else 2.0
    return factor * n_params * tokens


def param_count(cfg: ModelConfig) -> int:
    pshape = jax.eval_shape(
        lambda k: __import__("repro.models.transformer", fromlist=["init_params"]
                             ).init_params(cfg, k), jax.random.PRNGKey(0))
    return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(pshape))


def active_param_count(cfg: ModelConfig) -> int:
    """Active params per token (MoE: top_k of routed experts)."""
    total = param_count(cfg)
    if not cfg.n_experts:
        return total
    # routed expert params per MoE layer
    n_moe_layers = cfg.n_layers - cfg.first_k_dense
    per_expert = 3 * cfg.d_model * cfg.moe_d_ff
    routed = n_moe_layers * cfg.n_experts * per_expert
    active_routed = n_moe_layers * cfg.moe_top_k * per_expert
    return total - routed + active_routed


def analyse(compiled, n_chips: int, cfg: ModelConfig, shape: InputShape,
            seconds_per_step_basis: str = "per-device") -> Dict[str, Any]:
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    ca_flops = float(ca.get("flops", 0.0))
    hlo = compiled.as_text()
    # trip-count-aware walk (cost_analysis counts scan bodies once; see
    # launch/hlo_walk.py) — dot FLOPs and collective bytes are exact,
    # HBM bytes are cost_analysis scaled by the same under-count factor.
    walk = hlo_walk.analyse_hlo(hlo)
    flops = float(walk["dot_flops"])
    coll = {k: float(v) for k, v in walk["collectives"].items()}
    coll_total = float(walk["collective_bytes"])
    trip_factor = (flops / ca_flops) if ca_flops > 0 else 1.0
    bytes_acc = float(walk["hbm_bytes"])
    # all quantities are for the per-device SPMD program
    compute_s = flops / PEAK_FLOPS_BF16
    memory_s = bytes_acc / HBM_BW
    collective_s = coll_total / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=lambda k: terms[k])
    mf = model_flops(cfg, shape)
    mem = compiled.memory_analysis()
    mem_d = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
    }
    return {
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_acc,
        "cost_analysis_flops_raw": ca_flops,
        "trip_factor": trip_factor,
        "collective_bytes_per_device": coll_total,
        "collectives": coll,
        **terms,
        "dominant": dominant,
        "model_flops_global": mf,
        "useful_flops_ratio": (mf / (flops * n_chips)) if flops else None,
        "memory": mem_d,
        "n_chips": n_chips,
    }


def make_batch_sds(cfg: ModelConfig, shape: InputShape,
                   n_nodes: int) -> Dict[str, jax.ShapeDtypeStruct]:
    per_node = shape.global_batch // n_nodes
    use_embeds = cfg.family in ("audio", "vlm")
    b = {"labels": jax.ShapeDtypeStruct((n_nodes, per_node, shape.seq_len),
                                        jnp.int32)}
    if use_embeds:
        b["embeds"] = jax.ShapeDtypeStruct(
            (n_nodes, per_node, shape.seq_len, cfg.d_model), jnp.float32)
    else:
        b["tokens"] = jax.ShapeDtypeStruct((n_nodes, per_node, shape.seq_len),
                                           jnp.int32)
    return b


def dryrun_train(cfg: ModelConfig, shape: InputShape, prod_mesh,
                 variant: str = "dense", opts: str = "",
                 lint: bool = False) -> Dict[str, Any]:
    import dataclasses as _dc
    # expert-dim pinning is opt-in for TRAIN: for 256-expert dsv3 the forced
    # expert-local resharding costs more collectives than it saves (§Perf C.3)
    if cfg.n_experts and "epin" in opts.split(","):
        cfg = _dc.replace(cfg, expert_axis="model")
    for o in filter(None, opts.split(",")):
        if o.startswith("route"):
            cfg = _dc.replace(cfg, moe_route_blocks=int(o[5:]))
    mesh = sh.train_mesh(prod_mesh, cfg)
    n_nodes = mesh.shape["node"]
    kw: Dict[str, Any] = {"variant": variant}
    for o in filter(None, opts.split(",")):
        if o.startswith("micro"):
            kw["microbatches"] = int(o[5:])
        elif o == "xhat_bf16":
            kw["xhat_dtype"] = "bfloat16"
        elif o == "embed_dmodel":
            kw["embed_mode"] = "dmodel"
        elif o.startswith("causal") or o.startswith("route") or \
                o in ("no_epin", "epin", "pod_fsdp", "cache_seq",
                      "cache_inner"):
            pass  # handled on cfg / dispatch flags elsewhere
        else:
            raise ValueError(f"unknown opt {o!r}")
    dcfg = DistSparqConfig(**kw)
    init_fn, train_step, state_specs, pshape = build_sparq(cfg, mesh, dcfg)
    state_sds = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    batch_sds = make_batch_sds(cfg, shape, n_nodes)
    state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs,
                            is_leaf=lambda x: isinstance(x, P))
    batch_specs = sh.train_batch_specs(batch_sds, mesh)
    batch_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), batch_specs,
                            is_leaf=lambda x: isinstance(x, P))
    t0 = time.time()
    with mesh:
        lowered = jax.jit(train_step, in_shardings=(state_sh, batch_sh),
                          donate_argnums=(0,)).lower(state_sds, batch_sds)
    compiled = lowered.compile()
    dt = time.time() - t0
    res = analyse(compiled, prod_mesh.devices.size, cfg, shape)
    res.update(step="train_step", n_nodes=n_nodes, variant=variant,
               compile_seconds=round(dt, 1))
    if lint:
        # donated state leaves are the leading entry params (jit flattens
        # (state, batch) in pytree order, state first)
        res["lint"] = run_lint(
            compiled.as_text(),
            donated_params=range(len(jax.tree.leaves(state_sds))),
            use_kernel=train_step.use_kernel,
            interpret=train_step.interpret,
            lowering=train_step.lowering,
            program=f"dryrun_train[{cfg.arch_id}]")
        # theory-contract leg (R6-R9 + R11) over the same config and module
        from repro.analysis.contracts import run_contract_lint
        contract = run_contract_lint(
            dcfg, d=train_step.d_model_total, n=train_step.n_nodes,
            hlo=compiled.as_text(), mesh_axes=list(mesh.shape.items()),
            program=f"dryrun_train[{cfg.arch_id}]")
        res["lint"]["errors"] += contract["errors"]
        res["lint"]["findings"] += contract["findings"]
    return res


def dryrun_serve(cfg: ModelConfig, shape: InputShape, prod_mesh,
                 opts: str = "", lint: bool = False) -> Dict[str, Any]:
    mesh = sh.serve_mesh(prod_mesh)
    import dataclasses as _dc
    if cfg.n_experts and "no_epin" not in opts:
        cfg = _dc.replace(cfg, expert_axis="model")
    embed_mode = "dmodel" if "embed_dmodel" in opts else "vocab"
    clen = cache_len(cfg, shape)
    pshape, cshape, tok, emb, pos = serve_mod.serve_shapes(cfg, shape, clen)
    t0 = time.time()
    ctx = mesh
    if shape.is_decode:
        cache_mode = "auto"
        if "cache_seq" in opts:
            cache_mode = "seq"
        elif "cache_inner" in opts:
            cache_mode = "inner"  # legacy rule, for before/after comparisons
        decode, shardings = serve_mod.build_decode(cfg, mesh,
                                                   cache_mode=cache_mode)
        ps, cs, ts, es, pos_s = shardings(pshape, cshape, tok, emb)
        in_sh = (ps, cs, ts, es if emb is not None else None, pos_s)
        with ctx:
            lowered = jax.jit(decode, in_shardings=in_sh,
                              donate_argnums=(1,)).lower(
                pshape, cshape, tok, emb, pos)
        step_name = "serve_step(decode)"
    else:
        prefill, shardings = serve_mod.build_prefill(cfg, mesh,
                                                     embed_mode=embed_mode)
        ps, ts, es = shardings(pshape, tok, emb)
        with ctx:
            lowered = jax.jit(prefill, in_shardings=(ps, ts, es)).lower(
                pshape, tok, emb)
        step_name = "serve_step(prefill)"
    compiled = lowered.compile()
    dt = time.time() - t0
    res = analyse(compiled, prod_mesh.devices.size, cfg, shape)
    res.update(step=step_name, cache_len=clen if shape.is_decode else None,
               compile_seconds=round(dt, 1))
    if lint:
        # decode donates argnum 1 (the KV cache): its leaves sit after the
        # param leaves in the flattened entry params; prefill donates nothing
        if shape.is_decode:
            n_p = len(jax.tree.leaves(pshape))
            donated = range(n_p, n_p + len(jax.tree.leaves(cshape)))
        else:
            donated = range(0)
        res["lint"] = run_lint(compiled.as_text(), donated,
                               program=f"{step_name}[{cfg.arch_id}]")
    return res


def skip_reason(cfg: ModelConfig, shape: InputShape) -> Optional[str]:
    return None  # long_500k runs everywhere: SSM/hybrid natively, attn via SWA


def run_one(arch: str, shape_name: str, multi_pod: bool,
            variant: str, opts: str = "",
            lint: bool = False) -> Dict[str, Any]:
    shape = shape_by_name(shape_name)
    cfg = for_shape(get_config(arch), shape)
    import dataclasses as _dc
    for o in filter(None, opts.split(",")):
        if o.startswith("causal"):
            cfg = _dc.replace(cfg, causal_parts=int(o[6:]))
        elif o == "pod_fsdp":
            cfg = _dc.replace(cfg, pod_axis_to="fsdp")
    prod_mesh = make_production_mesh(multi_pod=multi_pod)
    reason = skip_reason(cfg, shape)
    base = {"arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16", "variant": variant,
            "opts": opts}
    if reason:
        return {**base, "skipped": reason}
    try:
        if shape.kind == "train":
            res = dryrun_train(cfg, shape, prod_mesh, variant, opts, lint)
        else:
            res = dryrun_serve(cfg, shape, prod_mesh, opts, lint)
        return {**base, **res, "ok": True}
    except Exception as e:  # noqa: BLE001 - report, don't crash the sweep
        return {**base, "ok": False, "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-2000:]}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--variant", default="dense", choices=["dense", "ring"])
    ap.add_argument("--opts", default="", help="comma list: microN, xhat_bf16,"
                    " embed_dmodel, causalN (perf-iteration knobs)")
    ap.add_argument("--lint", action="store_true",
                    help="run the repro.analysis HLO rules (donation/"
                         "transfer/interpret lint) over each compiled "
                         "module; lint errors fail the sweep")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results = []
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                r = run_one(arch, shape_name, mp, args.variant, args.opts,
                            args.lint)
                status = ("SKIP " + r["skipped"]) if r.get("skipped") else (
                    "OK" if r.get("ok") else "FAIL " + r.get("error", ""))
                print(f"[dryrun] {arch:18s} {shape_name:12s} "
                      f"{r['mesh']:8s} {status}", flush=True)
                if r.get("ok"):
                    print(f"  terms: compute {r['compute_s']:.3e}s  "
                          f"memory {r['memory_s']:.3e}s  "
                          f"collective {r['collective_s']:.3e}s  "
                          f"dominant={r['dominant']}", flush=True)
                    print(f"  memory_analysis: {r['memory']}", flush=True)
                results.append(r)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")
    nfail = sum(1 for r in results if not r.get("ok") and not r.get("skipped"))
    nlint = sum(r.get("lint", {}).get("errors", 0) for r in results)
    if nlint:
        print(f"[dryrun] {nlint} lint error(s)")
    return 1 if (nfail or nlint) else 0


if __name__ == "__main__":
    sys.exit(main())
