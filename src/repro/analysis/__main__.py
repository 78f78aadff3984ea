"""CLI for the static audit: ``python -m repro.analysis``.

Builds the SAME lowered programs the launch stack builds — the chunked-scan
engine runner (`core/engine.make_runner` over a `core/sparq.make_step`
program) and the SPMD dist step (`dist/sparq_dist.build_sparq`, jitted with
the production sharding/donation flags exactly as `launch/dryrun.py` and
`launch/train.py` do) — and runs the R1-R5 rule catalog over their jaxprs
and optimized HLO. Nothing heavy executes: the HLO rules read AOT-compiled
artifacts, and only the retrace gate (R3) runs the programs (twice, on
reduced shapes, by design — that is what it measures).

``--source`` adds the third leg: the S1-S6 source audit
(`source_lint.py` over the `callgraph.py` traced-reachability graph),
which lints the whole tree rather than the programs this CLI happens to
lower, with grandfathered findings suppressed through the committed
``results/SOURCE_BASELINE.json`` (``--baseline`` / ``--regen-baseline``).

``--kernels`` runs the K1-K4 kernel-contract audit (`kernel_lint.py`):
abstract-eval capture of every registered `pallas_call` (grid coverage,
index-map bounds, tail masking), interpret-flag hygiene, the closed-form
VMEM estimate, and the dense-gossip O(n^2) tripwire over the call graph.
``--spmd`` runs the P1-P4 partitioning/memory audit (`spmd_lint.py`) over
the dist train step AND the serve prefill/decode lowerings: declared
PartitionSpecs vs the compiled module's actual sharding annotations,
reshard intent, and the peak-HBM watermark from `memory_analysis()`.

Exit status 0 iff zero unsuppressed errors; findings land in
``results/ANALYSIS.json`` (``--out``) for review-time diffing.
"""
import os

# Before ANY jax import: the dist audit shards over 8 simulated host devices
# (jax locks the device count and the backend at first init, the same reason
# launch/dryrun.py sets its flags at the very top). The audit is compile-only
# and pins the CPU backend so it never claims an accelerator.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse
import dataclasses
import sys
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import hlo_lint, jaxpr_lint
from repro.analysis.rules import (Report, apply_suppressions,
                                  default_suppressions, dump_report,
                                  render_report)
from repro.launch.mesh import make_mesh

CORE_N = 8          # nodes in the core-engine audit ensemble
CORE_D = 64 * 1024  # (CORE_N, CORE_D) f32 = 2 MB per carry leaf: over the
                    # R1 threshold so a dropped donation is a hard error


def _leaf_labels(tree) -> List[str]:
    return [jax.tree_util.keystr(kp)
            for kp, _ in jax.tree_util.tree_leaves_with_path(tree)]


def audit_core(topo_kind: str, steps: int, contracts: bool = False) -> Report:
    from repro.core import engine as engine_mod
    from repro.core import sparq
    from repro.core.compression import TopFrac
    from repro.core.schedule import decaying, fixed
    from repro.core.topology import make_topology

    report = Report(program="core/make_runner",
                    meta={"topology": topo_kind, "n": CORE_N, "d": CORE_D,
                          "T": steps, "backend": jax.default_backend()})
    cfg = sparq.SparqConfig(topology=make_topology(topo_kind, CORE_N),
                            compressor=TopFrac(0.25),
                            threshold=decaying(1.0, 10.0),
                            lr=fixed(0.05), H=2, gamma=0.3, momentum=0.9)
    step = sparq.make_step(cfg, lambda x, t, key: x)  # grad of 0.5*||x||^2
    key = jax.random.PRNGKey(0)

    def make_state():
        return cfg.init_state(jnp.zeros((CORE_N, CORE_D), jnp.float32))

    state0 = make_state()
    runner = engine_mod.make_runner(
        step, steps, record_every=max(steps // 2, 1),
        eval_fn=lambda x: jnp.mean(x * x))

    # R3 first: the runner's own trace counter must stay at 1 over repeat
    # calls (fresh states each call — the carry is donated).
    report.extend(jaxpr_lint.audit_retrace(
        lambda: runner(make_state(), key), runner.trace_count,
        program=report.program))

    # R2 on the step jaxpr (the scanned body — where a silent promotion
    # would multiply by T) plus the runner carry contract.
    closed = jax.make_jaxpr(step)(state0, key)
    report.extend(jaxpr_lint.lint_dtypes(closed, program="core/make_step"))
    report.extend(jaxpr_lint.lint_weak_scalars(closed,
                                               program="core/make_step"))
    out_sds = jax.eval_shape(step, state0, key)
    report.extend(jaxpr_lint.lint_carry_dtypes(
        jax.tree.leaves(state0), jax.tree.leaves(out_sds),
        labels=_leaf_labels(state0), program="core/make_step"))

    # R1/R4 on the optimized HLO of the full T-step runner program.
    hlo = runner.lower(state0, key).compile().as_text()
    n_state = len(jax.tree.leaves(state0))  # donated carry leaves are entry
    report.extend(hlo_lint.lint_donation(    # params 0..n_state-1 (pytree
        hlo, range(n_state), program=report.program))  # flatten order)
    report.extend(hlo_lint.lint_transfers(hlo, program=report.program))
    report.meta["entry_params"] = len(hlo_walk_params(hlo))
    report.meta["donated_params"] = n_state

    if contracts:
        # R6-R9 on the same config the lowering audit just certified
        from repro.analysis import contracts as contracts_mod
        cf, cmeta = contracts_mod.lint_contracts(cfg, CORE_D,
                                                 program=report.program)
        report.extend(cf)
        report.meta["contracts"] = cmeta
    return report


def hlo_walk_params(hlo: str):
    from repro.launch import hlo_walk
    return hlo_walk.entry_parameters(hlo)


def audit_kernels() -> Report:
    """K1-K4 leg: the pallas_call contract audit (see kernel_lint.py)."""
    from repro.analysis import kernel_lint

    findings, meta = kernel_lint.audit_kernels(".")
    report = Report(program="kernels/pallas", meta=meta)
    report.extend(findings)
    return report


def audit_dist(variant: str, arch: str, use_kernel: bool,
               contracts: bool = False, spmd: bool = False) -> Report:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.registry import get_config
    from repro.dist import sharding as sh
    from repro.dist.sparq_dist import DistSparqConfig, build_sparq

    report = Report(program="dist/train_step",
                    meta={"variant": variant, "arch": arch,
                          "use_kernel": use_kernel,
                          "backend": jax.default_backend()})
    cfg = dataclasses.replace(get_config(arch).reduced(), n_nodes=4)
    prod = make_mesh((4, 2), ("data", "model"))
    mesh = sh.train_mesh(prod, cfg)
    dcfg = DistSparqConfig(H=2, variant=variant, frac=0.25,
                           use_kernel=use_kernel)
    init_fn, train_step, state_specs, pshape = build_sparq(cfg, mesh, dcfg)
    report.meta["interpret"] = train_step.interpret
    report.meta["lowering"] = train_step.lowering
    report.meta["d_pad"] = train_step.d_pad

    state_sds = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    n_nodes, per_node, seq = train_step.n_nodes, 2, 32
    batch_sds = {k: jax.ShapeDtypeStruct((n_nodes, per_node, seq), jnp.int32)
                 for k in ("tokens", "labels")}
    ssh = jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs,
                       is_leaf=lambda x: isinstance(x, P))
    bsh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                       sh.train_batch_specs(batch_sds, mesh),
                       is_leaf=lambda x: isinstance(x, P))

    # No `with mesh:` anywhere below — launch/train.py runs the step without
    # a mesh context, and the context is part of the trace-cache key: mixing
    # a mesh-scoped lower with context-free execution double-traces (that is
    # precisely the drift R3 exists to catch).
    counted = jaxpr_lint.TraceCounter(train_step)
    jstep = jax.jit(counted, in_shardings=(ssh, bsh), donate_argnums=(0,))
    lowered = jstep.lower(state_sds, batch_sds)
    compiled = lowered.compile()
    hlo = compiled.as_text()

    # R1: the donated state leaves are the leading entry params (jit
    # flattens (state, batch) in pytree order, state first).
    n_state = len(jax.tree.leaves(state_sds))
    report.extend(hlo_lint.lint_donation(hlo, range(n_state),
                                         program=report.program))
    # R4 / R5 on the same optimized module.
    report.extend(hlo_lint.lint_transfers(hlo, program=report.program))
    report.extend(hlo_lint.lint_pallas(hlo, use_kernel=train_step.use_kernel,
                                       interpret=train_step.interpret,
                                       lowering=train_step.lowering,
                                       program=report.program))

    # R2 on the dist jaxpr + state carry contract ((state, metrics) out).
    closed = jax.make_jaxpr(train_step)(state_sds, batch_sds)
    report.extend(jaxpr_lint.lint_dtypes(closed, program=report.program))
    report.extend(jaxpr_lint.lint_weak_scalars(closed,
                                               program=report.program))
    out_state, _metrics = jax.eval_shape(train_step, state_sds, batch_sds)
    report.extend(jaxpr_lint.lint_carry_dtypes(
        jax.tree.leaves(state_sds), jax.tree.leaves(out_state),
        labels=_leaf_labels(state_sds), program=report.program))

    # R3: two real (reduced-shape) executions through the SAME jit wrapper;
    # the .lower() above primed the trace, so the count must still be 1.
    state = jax.device_put(init_fn(jax.random.PRNGKey(0)), ssh)
    rng = np.random.default_rng(0)
    batch = jax.device_put(
        {k: rng.integers(0, cfg.vocab_size,
                         (n_nodes, per_node, seq)).astype(np.int32)
         for k in ("tokens", "labels")}, bsh)
    state, _ = jstep(state, batch)
    state, _ = jstep(state, batch)
    if counted.count != 1:
        report.extend(jaxpr_lint.audit_retrace(
            lambda: None, counted, calls=0, program=report.program))
    report.meta["traces"] = counted.count
    report.meta["donated_params"] = n_state

    if contracts:
        from repro.analysis import comm_lint
        from repro.analysis import contracts as contracts_mod
        # R6-R9 at the true model dimension and resolved ensemble size
        cf, cmeta = contracts_mod.lint_contracts(
            dcfg, train_step.d_model_total, n=train_step.n_nodes,
            program=report.program)
        report.extend(cf)
        report.meta["contracts"] = cmeta
        # R10 (dist leg): the engine's charged payload vs the flat-buffer
        # closed-form derivation at d = sum(leaf sizes) — both paths now
        # compress the single raveled buffer (kernel: blockwise formula via
        # the BlockTopFrac branch; generic: global top-k on the flat vector)
        report.extend(comm_lint.lint_dist_payload(
            dcfg.effective_compressor(), pshape, train_step.payload_bits,
            program=report.program))
        # R11: node-axis bytes of the compiled module vs the bits model
        f11, m11 = comm_lint.lint_collectives(
            hlo, list(mesh.shape.items()), n_nodes=train_step.n_nodes,
            d_model_total=train_step.d_model_total, program=report.program)
        report.extend(f11)
        report.meta["collectives"] = m11

    if spmd:
        from repro.analysis import spmd_lint
        from repro.core.engine import compiled_memory_stats

        # P1: declared PartitionSpecs, state then batch (jit's flatten
        # order), vs the entry annotations of the optimized module
        def is_spec(x):
            return isinstance(x, P)
        spec_leaves = (
            jax.tree.leaves(state_specs, is_leaf=is_spec)
            + jax.tree.leaves(sh.train_batch_specs(batch_sds, mesh),
                              is_leaf=is_spec))
        sds_leaves = jax.tree.leaves(state_sds) + jax.tree.leaves(batch_sds)
        labels = _leaf_labels(state_sds) + _leaf_labels(batch_sds)
        expected = [(lab, spec, len(s.shape))
                    for lab, spec, s in zip(labels, spec_leaves, sds_leaves)]
        axes = list(mesh.shape.items())
        f1, m1 = spmd_lint.lint_param_shardings(hlo, expected, axes,
                                                program=report.program)
        report.extend(f1)
        report.meta["param_shardings"] = m1
        # P2: the node axis is R11's domain (gossip bits budget); model
        # carries TP contractions, fsdp carries param/grad movement
        f2, m2 = spmd_lint.lint_reshards(
            hlo, axes,
            axis_roles={"node": "gossip", "fsdp": "fsdp", "model": "tensor"},
            program=report.program)
        report.extend(f2)
        report.meta["reshards"] = m2
        # P3: peak-HBM watermark of the compiled step
        f3, m3 = spmd_lint.lint_memory(compiled_memory_stats(compiled),
                                       program=report.program,
                                       label="train_step")
        report.extend(f3)
        report.meta["memory"] = m3
    return report


def audit_serve(arch: str) -> List[Report]:
    """P1-P4 over the serve prefill/decode lowerings: reduced ``arch`` on
    the (4, 2) serve mesh, mirroring launch/dryrun.dryrun_serve exactly —
    lowered under ``with mesh:`` (the with_sharding_constraint calls in the
    model need the context) and decode donating the cache (argnum 1)."""
    from jax.sharding import PartitionSpec as P

    from repro.analysis import spmd_lint
    from repro.configs.registry import get_config
    from repro.core.engine import compiled_memory_stats
    from repro.dist import serve as serve_mod
    from repro.dist import sharding as sh
    from repro.models.config import InputShape

    cfg = get_config(arch).reduced()
    prod = make_mesh((4, 2), ("data", "model"))
    mesh = sh.serve_mesh(prod)
    axes = list(mesh.shape.items())
    roles = {"data": "batch", "model": "tensor"}
    B, S, CLEN = 8, 32, 64
    reports: List[Report] = []

    def spmd_pass(report: Report, compiled, expected, must_shard, label):
        hlo = compiled.as_text()
        f1, m1 = spmd_lint.lint_param_shardings(hlo, expected, axes,
                                                program=report.program)
        report.extend(f1)
        report.meta["param_shardings"] = m1
        f2, m2 = spmd_lint.lint_reshards(hlo, axes, axis_roles=roles,
                                         program=report.program)
        report.extend(f2)
        report.meta["reshards"] = m2
        f3, m3 = spmd_lint.lint_memory(compiled_memory_stats(compiled),
                                       program=report.program, label=label)
        report.extend(f3)
        report.meta["memory"] = m3
        f4, m4 = spmd_lint.lint_serve_layout(hlo, must_shard,
                                             program=report.program)
        report.extend(f4)
        report.meta["serve_layout"] = m4

    # ---------------------------------------------------------- prefill
    pshape, _, tok, emb, _ = serve_mod.serve_shapes(
        cfg, InputShape("audit_prefill", B, S, "prefill"), CLEN)
    prefill, shardings = serve_mod.build_prefill(cfg, mesh)
    ps, ts, es = shardings(pshape, tok, emb)
    rep = Report(program="dist/serve_prefill",
                 meta={"arch": arch, "B": B, "S": S,
                       "backend": jax.default_backend()})
    with mesh:
        compiled = jax.jit(prefill, in_shardings=(ps, ts, es)).lower(
            pshape, tok, emb).compile()
    n_p = len(jax.tree.leaves(pshape))
    expected = [(lab, ns.spec, len(s.shape))
                for lab, ns, s in zip(_leaf_labels(pshape),
                                      jax.tree.leaves(ps),
                                      jax.tree.leaves(pshape))]
    batch_ops = []   # (label, sharding, ndim) of the B-leading operands
    if tok is not None:
        batch_ops.append(("tokens", ts, 2))
    if emb is not None:
        batch_ops.append(("embeds", es, 3))
    expected += [(lab, ns.spec, nd) for lab, ns, nd in batch_ops]
    must = [(n_p + i, lab) for i, (lab, _, _) in enumerate(batch_ops)]
    spmd_pass(rep, compiled, expected, must, "prefill")
    reports.append(rep)

    # ----------------------------------------------------------- decode
    _, cshape, tok_d, emb_d, pos = serve_mod.serve_shapes(
        cfg, InputShape("audit_decode", B, S, "decode"), CLEN)
    decode, dshardings = serve_mod.build_decode(cfg, mesh)
    ps, cs, ts, es, pos_s = dshardings(pshape, cshape, tok_d, emb_d)
    rep = Report(program="dist/serve_decode",
                 meta={"arch": arch, "B": B, "cache_len": CLEN,
                       "backend": jax.default_backend()})
    with mesh:
        compiled = jax.jit(
            decode,
            in_shardings=(ps, cs, ts, es if emb_d is not None else None,
                          pos_s),
            donate_argnums=(1,)).lower(pshape, cshape, tok_d, emb_d,
                                       pos).compile()
    cache_leaves = jax.tree.leaves(cshape)
    n_c = len(cache_leaves)
    cache_specs = [ns.spec for ns in jax.tree.leaves(cs)]
    cache_labels = _leaf_labels(cshape)
    expected = [(lab, ns.spec, len(s.shape))
                for lab, ns, s in zip(_leaf_labels(pshape),
                                      jax.tree.leaves(ps),
                                      jax.tree.leaves(pshape))]
    expected += [(f"cache{lab}", sp, len(s.shape))
                 for lab, sp, s in zip(cache_labels, cache_specs,
                                       cache_leaves)]
    batch_ops = []
    if tok_d is not None:
        batch_ops.append(("tokens", ts, 2))
    if emb_d is not None:
        batch_ops.append(("embeds", es, 3))
    expected += [(lab, ns.spec, nd) for lab, ns, nd in batch_ops]
    expected.append(("pos", P(), 0))
    # P4 floor: batch operands plus every cache leaf whose declared spec
    # puts the batch dim on 'data' (those that fit must actually shard)
    must = [(n_p + i, f"cache{lab}")
            for i, (lab, sp) in enumerate(zip(cache_labels, cache_specs))
            if "data" in tuple(sp)]
    must += [(n_p + n_c + i, lab) for i, (lab, _, _) in enumerate(batch_ops)]
    spmd_pass(rep, compiled, expected, must, "decode")
    reports.append(rep)
    return reports


def audit_source(baseline_path, regen: bool):
    """S1-S6 leg: whole-tree source lint over the traced-reachability call
    graph. Returns ``(report, source_meta)`` — the meta block (call-graph
    census + baseline accounting) rides into ANALYSIS.json as the
    top-level ``source`` key."""
    from repro.analysis import source_lint

    # relative root: the committed report must not embed machine paths
    root = "."
    if regen:
        # Grandfather the CURRENT error findings (curated reasons in the
        # existing file survive), then re-audit against the fresh baseline
        # so the emitted report reflects what CI will see.
        bare = source_lint.audit_repo(root)
        doc = source_lint.write_baseline(bare, baseline_path)
        print(f"[analysis] wrote {baseline_path} "
              f"({len(doc['entries'])} entr{'y' if len(doc['entries']) == 1 else 'ies'})",
              flush=True)
    audit = source_lint.audit_repo(root, baseline_path=baseline_path)
    report = Report(program="source", meta=dict(audit.meta))
    report.extend(audit.report_findings())
    return report, audit.meta


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="Static audit (R1-R5) of the lowered train programs.")
    ap.add_argument("--config", default="ring",
                    help="gossip topology/variant: ring|torus2d|complete|"
                         "expander (core); ring maps to the ring variant, "
                         "anything else to dense, for dist")
    ap.add_argument("--engine", default="both",
                    choices=["core", "dist", "both", "none"],
                    help="which lowered programs to audit; 'none' skips "
                         "the lowering legs entirely (only useful with "
                         "--source and/or --contracts)")
    ap.add_argument("--arch", default="qwen1.5-0.5b",
                    help="dist model arch (reduced variant is audited)")
    ap.add_argument("--steps", type=int, default=8,
                    help="core-engine trajectory length (kept tiny: the "
                         "audit reads artifacts, it does not benchmark)")
    ap.add_argument("--contracts", action="store_true",
                    help="additionally run the theory-contract and "
                         "bit-accounting rules (R6-R11): committed-config "
                         "certification, the closed-form bits oracle, and "
                         "the uncharged-collective walk of the dist "
                         "lowering")
    ap.add_argument("--no-kernel", action="store_true",
                    help="audit the dist step without the Pallas kernel "
                         "path (R5 then has nothing to check)")
    ap.add_argument("--source", action="store_true",
                    help="additionally run the S1-S6 source rules: the "
                         "AST-level whole-tree audit (PRNG lineage, "
                         "host/trace boundary, static-arg hygiene, "
                         "source donation, docs drift, dead seams) over "
                         "the traced-reachability call graph")
    ap.add_argument("--kernels", action="store_true",
                    help="additionally run the K1-K4 kernel-contract rules: "
                         "abstract-eval capture of every registered "
                         "pallas_call (grid coverage, index-map bounds, "
                         "tail masks), interpret-flag hygiene, the "
                         "closed-form VMEM estimate, and the dense-gossip "
                         "O(n^2) tripwire")
    ap.add_argument("--spmd", action="store_true",
                    help="additionally run the P1-P4 partitioning/memory "
                         "rules over the dist train step (with --engine "
                         "dist/both) and the serve prefill/decode "
                         "lowerings: declared specs vs compiled sharding "
                         "annotations, reshard intent, peak-HBM watermark")
    ap.add_argument("--baseline", default="results/SOURCE_BASELINE.json",
                    help="committed fingerprint->reason baseline applied "
                         "to --source findings")
    ap.add_argument("--regen-baseline", action="store_true",
                    help="regenerate --baseline from the current --source "
                         "error findings (curated reasons are preserved); "
                         "same commit-the-diff contract as --regen-golden")
    ap.add_argument("--out", default=None,
                    help="write ANALYSIS.json here (default: print summary "
                         "only)")
    args = ap.parse_args(argv)

    reports: List[Report] = []
    if args.engine in ("core", "both"):
        print(f"[analysis] auditing core/make_runner "
              f"(topology={args.config}, n={CORE_N}, d={CORE_D})",
              flush=True)
        reports.append(audit_core(args.config, args.steps,
                                  contracts=args.contracts))
    if args.engine in ("dist", "both"):
        variant = "ring" if args.config == "ring" else "dense"
        print(f"[analysis] auditing dist/train_step (variant={variant}, "
              f"arch={args.arch}, kernel={not args.no_kernel})", flush=True)
        reports.append(audit_dist(variant, args.arch,
                                  use_kernel=not args.no_kernel,
                                  contracts=args.contracts,
                                  spmd=args.spmd))
    if args.kernels:
        print("[analysis] auditing pallas_call contracts (K1-K4) via "
              "abstract eval", flush=True)
        reports.append(audit_kernels())
    if args.spmd:
        print(f"[analysis] auditing serve prefill/decode partitioning "
              f"(P1-P4, arch={args.arch})", flush=True)
        reports.extend(audit_serve(args.arch))
    if args.contracts:
        from repro.analysis import comm_lint
        from repro.analysis import contracts as contracts_mod
        print("[analysis] certifying committed configs (R6-R9) and the "
              "bits oracle (R10)", flush=True)
        reports.extend(contracts_mod.audit_contracts())
        oracle = Report(program="comm/bits_oracle")
        f10, m10 = comm_lint.lint_bits_oracle(program=oracle.program)
        oracle.extend(f10)
        oracle.meta.update(m10)
        reports.append(oracle)
    extra = {"jax_version": jax.__version__,
             "backend": jax.default_backend(),
             "argv": vars(args)}
    if args.source:
        print("[analysis] source audit (S1-S6) over the traced-reachability "
              "call graph", flush=True)
        src_report, src_meta = audit_source(args.baseline,
                                            regen=args.regen_baseline)
        reports.append(src_report)
        extra["source"] = src_meta

    suppressions = default_suppressions(jax.default_backend())
    for r in reports:
        # source findings arrive with their baseline suppressions already
        # applied; apply_suppressions only ever ADDS suppressions, so
        # running it uniformly is safe.
        apply_suppressions(r.findings, suppressions)

    doc = render_report(reports, suppressions, extra=extra)
    for r in reports:
        c = r.counts()
        print(f"[analysis] {r.program}: {c['errors']} error(s), "
              f"{c['warnings']} warning(s), {c['suppressed']} suppressed",
              flush=True)
        for f in r.findings:
            tag = "suppressed" if f.suppressed else f.severity.upper()
            print(f"  [{f.rule_id}/{tag}] {f.message}"
                  + (f"  ({f.location})" if f.location else ""), flush=True)
    if args.out:
        dump_report(doc, args.out)
        print(f"[analysis] wrote {args.out}", flush=True)
    ok = bool(doc["ok"])
    print(f"[analysis] {'OK' if ok else 'FAIL'}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
