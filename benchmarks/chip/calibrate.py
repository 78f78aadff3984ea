"""Readings that set a cell's limits: the program's, and the control's and
the planted faults', each compared with the plain reference.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--controls 3]

The compiled step is built once; for every seed it starts again from that
seed's weights, drives the check's first steps as a run's set-up does, and
compares them with the float32 reference (``program``: the lower readings).
For the first ``--controls`` seeds it then puts in the program's place:

* ``control``: the reference with its matmuls in fp8 (operands in
  float8_e4m3fn, cotangents in float8_e5m2, scaled per tensor), the
  precision below the configuration's bfloat16;
* ``half_batch``: the reference on half of each node's rows, the mean taken
  over them;
* ``unchanged``: a step that returns its state unchanged (no run needed);

and prints, per seed, the numbers ``check.compare`` gives for each, with
the program's worst segments. Only the process that holds the chips may run
it, and it takes no part in the benchmark's runs.
"""
import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE]

import run  # noqa: E402


def faults(files: dict, seed: int, devices, ref: dict = None) -> dict:
    """The control's and the faults' readings of one seed:
    {reading: {number: value}}."""
    import check
    import program
    import traffic as traffic_mod
    from reference import sparq
    cfg, job = files["cfg"], files["job"]
    model = run.reference_model(cfg)
    vocab = program.model_config(cfg, int(job["nodes"])).vocab_size
    gen = traffic_mod.from_spec(job, vocab, seed)
    S = check.sync_steps(int(job["H"]))

    def follow(**kw):
        return sparq.run(model, cfg, job, gen, seed, S, devices, **kw)

    ref = ref or follow()
    return {"control": check.compare(follow(prec="fp8"), ref),
            "half_batch": check.compare(follow(fault="half_batch"), ref),
            "unchanged": check.compare(check.unchanged(ref), ref)}


def program_and_faults(files: dict, seeds, controls: int, devices):
    """Yield, per seed, the program's readings and, for the first
    ``controls`` seeds, the control's and the faults'."""
    import numpy as np
    import check
    import program
    import traffic as traffic_mod
    from reference import sparq
    cfg, job = files["cfg"], files["job"]
    model = run.reference_model(cfg)
    mcfg = program.model_config(cfg, int(job["nodes"]))
    S = check.sync_steps(int(job["H"]))
    b, a = (float(v) for v in job["lr"])
    segs = sparq.layout(model.init_params, cfg)
    prog = None
    for n, seed in enumerate(seeds):
        gen = traffic_mod.from_spec(job, mcfg.vocab_size, seed)
        if prog is None:
            prog = program.build(mcfg, job, devices, seed, gen.global_batch(0))
        else:
            prog.reset(seed)
        got = check.program_readings(prog, gen.global_batch, S,
                                     np.float32(b) / np.float32(a), segs)
        prog.state = None
        gc.collect()
        ref = sparq.run(model, cfg, job, gen, seed, S, devices)
        out = {"program": check.compare(got, ref),
               "worst": check.worst_segments(got, ref, segs)}
        if n < controls:
            out.update(faults(files, seed, devices, ref))
        yield seed, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--controls", type=int, default=3,
                    help="read the control and faults on this many seeds")
    args = ap.parse_args(argv)
    files = run.cell_files(args.workload,
                           run.load_json(os.path.join(ROOT, "BENCHMARK.json")))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[calibrate] no TPU: JAX found {devices[0].platform}",
              file=sys.stderr)
        return run.EXIT_NO_CHIP
    sys.path.insert(0, os.path.join(ROOT, "src"))
    run.setup_compile_cache()
    devices = devices[:int(files["work"]["chips"])]
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed, r in program_and_faults(files, seeds, args.controls, devices):
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "readings": r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
