"""End-to-end decentralized training driver.

Runs SPARQ-SGD over the (node, fsdp, model) logical mesh with the synthetic
heterogeneous token pipeline, metrics logging, and checkpointing. On a CPU,
pass ``--devices 8 --reduced`` for a runnable demonstration. On TPU chips,
omit ``--devices`` (jax discovers the chips) and drop ``--reduced``: at
published widths one graph node takes a whole v5e chip (qwen1.5-0.5b's step
peaks near 15 GB of the chip's 16 GB), so pass ``--nodes`` equal to the chip
count. A ``--nodes`` above the device count stacks several node rows on one
device, which is how a reduced-width ring runs on a single chip.
:func:`run` is the CLI's body for in-process callers (``chip_smoke.py``).

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --devices 8 --reduced --steps 40 --log-every 5

The communication graph is pluggable: ``--topology`` picks the static graph
(ring/torus2d/complete/expander with ``--deg``/``--mixing``), ``--dynamic``
switches to a time-varying plan (random matchings, per-round edge-sampled
subgraphs, or a round-robin graph cycle; see core/topology.py make_plan).

Checkpointing covers the FULL train state (params, x_hat, optimizer buffers,
step counter, bits/trigger accounting) so ``--resume`` continues the exact
trajectory instead of silently resetting momentum and the step counter.
"""
import argparse
import dataclasses
import math
import os
import sys
from typing import Any, Dict, NamedTuple, Optional, Sequence

# fixed, git-ignored compile-cache directory of the checkout
CACHE_DIR = os.path.normpath(os.path.join(
    os.path.abspath(__file__), "..", "..", "..", "..", ".jax_cache"))


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (CPU simulation)")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced smoke-test config")
    ap.add_argument("--nodes", type=int, default=0, help="override n_nodes")
    ap.add_argument("--batch-per-node", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--H", type=int, default=5)
    ap.add_argument("--frac", type=float, default=0.1)
    ap.add_argument("--variant", default="ring",
                    choices=["dense", "ring", "shift"],
                    help="mixing impl: dense tensordot, or circulant "
                         "shift/roll lowering (falls back to dense off "
                         "circulant graphs and time-varying plans)")
    ap.add_argument("--topology", default="ring",
                    choices=["ring", "torus2d", "complete", "expander"],
                    help="gossip graph at the resolved node count")
    ap.add_argument("--deg", type=int, default=4,
                    help="expander degree (--topology expander)")
    ap.add_argument("--mixing", default="uniform",
                    choices=["uniform", "metropolis"])
    ap.add_argument("--dynamic", default="none",
                    choices=["none", "matchings", "edges", "cycle"],
                    help="time-varying gossip plan family (none = static)")
    ap.add_argument("--dynamic-rounds", type=int, default=8,
                    help="support size / period R of a --dynamic plan")
    ap.add_argument("--edge-frac", type=float, default=0.5,
                    help="per-round edge keep-probability (--dynamic edges)")
    ap.add_argument("--topo-seed", type=int, default=0,
                    help="graph / plan sampling seed")
    ap.add_argument("--link-drop", type=float, default=0.0,
                    help="per-sync-round iid link-drop probability in [0, 1) "
                         "(core/faults.py; surviving support is repaired "
                         "doubly stochastic)")
    ap.add_argument("--stragglers", default="",
                    help="comma-separated node indices that straggle, e.g. "
                         "'0,3' (skip --straggler-frac of local steps)")
    ap.add_argument("--straggler-frac", type=float, default=0.5,
                    help="fraction of local gradient steps each straggler "
                         "skips (only with --stragglers)")
    ap.add_argument("--dropout-window", action="append", default=[],
                    metavar="NODE:START:END",
                    help="take NODE fully offline for steps START <= t < "
                         "END (repeatable)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="fault-stream PRNG seed (links + stragglers)")
    ap.add_argument("--momentum", type=float, default=0.0,
                    help="SQuARM-SGD momentum beta (0 = plain SPARQ)")
    ap.add_argument("--nesterov", action="store_true",
                    help="Nesterov variant of the SQuARM momentum update")
    ap.add_argument("--lr", type=float, default=0.5)
    ap.add_argument("--threshold", type=float, default=2.0)
    ap.add_argument("--use-kernel", action="store_true",
                    help="Pallas sign-topk compression kernel")
    ap.add_argument("--lint", action="store_true",
                    help="static-audit the compiled step (repro.analysis "
                         "R1/R4/R5: donation, hidden transfers, interpret "
                         "leak; R6-R9: theory contracts; R11: uncharged "
                         "collectives) before training; lint errors abort "
                         "the run")
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="continue from the latest checkpoint in --ckpt-dir "
                         "(full train state: params, x_hat, opt, t, bits)")
    return ap.parse_args(argv)


def setup_compile_cache() -> None:
    """Persistent compile cache: where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX reads it itself and nothing is set here; otherwise the cache lives at
    a fixed directory of the checkout (a path that moved would never hit)."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)


class TrainResult(NamedTuple):
    """What :func:`run` hands an in-process caller."""

    metrics: Optional[Dict[str, float]]  # last step's; None if no step ran
    state: Any                           # final train state (on device)
    train_step: Any                      # build_sparq's step (.lowering ...)
    compiled: Any                        # the AOT-compiled step executable
    compile_seconds: float               # lower + compile of the step
    seconds_per_step: Optional[float]    # steady steps, after the first


def run(args: argparse.Namespace,
        devices: Optional[Sequence[Any]] = None) -> TrainResult:
    """Train per ``args`` (see :func:`parse_args`) on ``devices`` (default: all
    of ``jax.devices()``); the body of the CLI, callable in-process."""
    import time

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.checkpoint import ckpt
    from repro.configs.registry import get_config
    from repro.core.faults import DropoutWindow, FaultPlan
    from repro.core.schedule import decaying
    from repro.core.triggers import constant
    from repro.data.synthetic import TokenPipeline
    from repro.dist import sharding as sh
    from repro.dist.sparq_dist import DistSparqConfig, build_sparq
    from repro.launch.mesh import make_mesh

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.nodes:
        cfg = dataclasses.replace(cfg, n_nodes=args.nodes)

    devices = list(jax.devices() if devices is None else devices)
    ndev = len(devices)
    # factor the devices as (node, fsdp, model): the node axis is the largest
    # factor shared with the node count (more graph nodes than devices stack
    # several node rows on one device), model parallelism gets what is left
    n_nodes = cfg.n_nodes
    rest = ndev // math.gcd(n_nodes, ndev)
    model_par = next(m for m in (16, 8, 4, 2, 1) if rest % m == 0)
    prod_mesh = make_mesh((ndev // model_par, model_par), ("data", "model"),
                          devices=devices)
    mesh = sh.train_mesh(prod_mesh, cfg)

    try:
        windows = tuple(
            DropoutWindow(*(int(p) for p in spec.split(":")))
            for spec in args.dropout_window)
    except (TypeError, ValueError):
        # TypeError: wrong field count; ValueError: non-integer field or an
        # invalid window (DropoutWindow validates start < end)
        raise SystemExit(
            f"[train] --dropout-window needs integer NODE:START:END with "
            f"START < END, got {args.dropout_window!r}") from None
    try:
        straggler_ids = tuple(
            int(i) for i in args.stragglers.split(",") if i)
    except ValueError:
        raise SystemExit(
            f"[train] --stragglers needs comma-separated integer node "
            f"indices, got {args.stragglers!r}") from None
    faults = FaultPlan(
        link_drop=args.link_drop,
        stragglers=straggler_ids,
        straggler_frac=args.straggler_frac if args.stragglers else 0.0,
        dropout=windows, seed=args.fault_seed)

    dcfg = DistSparqConfig(
        H=args.H, frac=args.frac, lr=decaying(args.lr, 100.0),
        threshold=constant(args.threshold), momentum=args.momentum,
        nesterov=args.nesterov, variant=args.variant,
        use_kernel=args.use_kernel,
        topology=args.topology, deg=args.deg, mixing=args.mixing,
        dynamic=args.dynamic, rounds=args.dynamic_rounds,
        edge_frac=args.edge_frac, topo_seed=args.topo_seed,
        faults=faults)
    init_fn, train_step, state_specs, pshape = build_sparq(cfg, mesh, dcfg)
    n_params = sum(np.prod(leaf.shape) for leaf in jax.tree.leaves(pshape))
    plan = init_fn.plan   # the engine's own plan, not a re-resolution
    dev = devices[0]
    print(f"[train] mesh {dict(mesh.shape)}  arch={cfg.arch_id} "
          f"(~{n_params / 1e6:.1f}M params/node, {train_step.n_nodes} nodes) "
          f"on {ndev} x {dev.platform}/{dev.device_kind}")
    print(f"[train] gossip plan {plan.name} (R={plan.R}) "
          f"delta_eff={plan.delta_eff:.4f}")
    if not faults.is_null:
        print(f"[train] faults: link_drop={faults.link_drop} "
              f"stragglers={faults.stragglers}@{faults.straggler_frac} "
              f"dropout={[(w.node, w.start, w.end) for w in faults.dropout]} "
              f"seed={faults.seed}")
    ssh = jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs,
                       is_leaf=lambda x: isinstance(x, P))

    start = 0
    last = None
    if args.resume:
        if not args.ckpt_dir:
            raise SystemExit("[train] --resume needs --ckpt-dir")
        last = ckpt.latest_step(args.ckpt_dir)
        if last is None:
            print(f"[train] --resume: no checkpoint under "
                  f"{args.ckpt_dir!r}, starting fresh")
    if last is not None:
        # the checkpoint carries the FULL train state — params, x_hat,
        # optimizer buffers, t, bits/bits_c, sync_rounds, triggers —
        # restored onto the state shardings. restore only needs the state's
        # structure/shapes, so skip materializing a throwaway random init
        like = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        state = ckpt.restore(args.ckpt_dir, last, like=like, shardings=ssh)
        start = last
        print(f"[train] resumed full train state from step {last} "
              f"(t={int(state['t'])}, bits={float(state['bits']):.3e})")
    else:
        # built in place on its shardings: an eager init would hold the
        # pytree, its raveled copy and the tiled buffers on one device
        state = jax.jit(init_fn, out_shardings=ssh)(jax.random.PRNGKey(0))

    pipe = TokenPipeline(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                         batch_per_node=args.batch_per_node,
                         n_nodes=n_nodes, seed=0)
    b0 = pipe.global_batch(0)
    bspecs = sh.train_batch_specs(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), b0),
        mesh)
    bsh = jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs,
                       is_leaf=lambda x: isinstance(x, P))
    step = jax.jit(train_step, in_shardings=(ssh, bsh),
                   donate_argnums=(0,))
    t0 = time.perf_counter()
    compiled = step.lower(state, b0).compile()
    compile_s = time.perf_counter() - t0
    print(f"[train] step compiled in {compile_s:.2f}s "
          f"(lowering={train_step.lowering})")

    if args.lint:
        from repro.analysis.contracts import run_contract_lint
        from repro.analysis.hlo_lint import run_lint
        hlo = compiled.as_text()
        lint = run_lint(
            hlo, donated_params=range(len(jax.tree.leaves(state))),
            use_kernel=train_step.use_kernel,
            interpret=train_step.interpret,
            lowering=train_step.lowering,
            program=f"train[{cfg.arch_id}]")
        # theory-contract leg (R6-R9) on the exact config being launched,
        # plus the uncharged-collective walk (R11) over the same module
        contract = run_contract_lint(
            dcfg, d=train_step.d_model_total, n=train_step.n_nodes,
            hlo=hlo, mesh_axes=list(mesh.shape.items()),
            program=f"train[{cfg.arch_id}]")
        n_errors = lint["errors"] + contract["errors"]
        if n_errors:
            raise SystemExit(
                f"[train] --lint: {n_errors} static-audit error(s) "
                f"in the compiled step (see findings above)")
        print("[train] --lint: compiled step passes the static audit "
              "(lowering + theory contracts)")

    metrics = None
    t_first = None
    for i in range(start, args.steps):
        batch = jax.device_put(pipe.global_batch(i), bsh)
        state, metrics = compiled(state, batch)
        if t_first is None:
            # steady timing starts once the first step has finished
            jax.block_until_ready(metrics)
            t_first = time.perf_counter()
        if (i + 1) % args.log_every == 0:
            m = {k: float(v) for k, v in metrics.items()}
            print(f"[train] step {i+1:5d} loss {m['loss']:.4f} "
                  f"eta {m['eta']:.4f} bits {m['bits']:.3e} "
                  f"triggers {m['triggers']:.0f}")
        if args.ckpt_dir and args.ckpt_every and \
                (i + 1) % args.ckpt_every == 0:
            path = ckpt.save(args.ckpt_dir, i + 1, jax.device_get(state))
            print(f"[train] checkpoint -> {path}")
    if metrics is None:
        # no steps ran (steps <= start, e.g. --steps 0 or an already-complete
        # resume): there is no final metrics dict to report
        print(f"[train] DONE no steps run (start={start}, "
              f"steps={args.steps})")
        return TrainResult(None, state, train_step, compiled, compile_s, None)
    jax.block_until_ready((state, metrics))
    n_steady = args.steps - start - 1
    step_s = ((time.perf_counter() - t_first) / n_steady
              if n_steady else None)
    m = {k: float(v) for k, v in metrics.items()}
    print(f"[train] DONE loss={m['loss']:.4f} total_bits={m['bits']:.3e} "
          f"trigger_events={m['triggers']:.0f}"
          + (f" ({step_s:.4f}s/step steady)" if step_s is not None else ""))
    return TrainResult(m, state, train_step, compiled, compile_s, step_s)


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = parse_args(argv)
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")
    setup_compile_cache()
    run(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
