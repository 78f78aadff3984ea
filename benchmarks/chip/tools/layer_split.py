"""Split the train step's device time by layer scope, on the chip.

    python3 benchmarks/chip/tools/layer_split.py --workload <cell> \\
        --seed <n> [--out FILE]
    python3 benchmarks/chip/tools/layer_split.py --record [--out-dir DIR]

``--workload``: build a benchmark cell's step as ``run.py`` does, run its
first S = max(3, H) steps, trace a window of whole sync periods as the
``--trace 1`` run does, and print (and write to ``--out``) per step the
self ms of each layer scope (``layers.py``), the step's module ms and what
the five top-level scopes leave of it, each layer's median, the ops that
hold no layer scope, and the host seconds the trace takes to read.

``--record``: the same reduced 4-node ring that ``record_testdata.py``
records, six traced steps, written as ``ring4_scoped.xplane.pb`` and
``ring4_scoped.json`` (the window and each layer's median) for the CPU
tests of the scope reduction.
"""
import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(HERE, "metrics"), os.path.join(ROOT, "src"),
                os.path.join(HERE, "tools")]

import run  # noqa: E402


def readings(ctx) -> dict:
    import layers
    return {s: layers.layer_ms(ctx, s, want)
            for s, want in layers.READ_ON.items()}


def split(trace, window, interval) -> dict:
    """Per step, each layer's self ms, the module's ms and what the five
    top-level layers leave of it; the ops that hold none of them, by self
    ms a step."""
    import layers
    import tracefile as tr
    names = layers.TOP + ("sparq_unravel",)
    tops = [layers.matcher(n) for n in layers.TOP]
    steps, unscoped = [], {}
    for dev in trace.devices:
        execs = tr.step_executions(dev, interval)
        for row, sync in zip(layers.step_layers(dev, execs, names),
                             window.sync_flags):
            row["sync"] = sync
            row["rest"] = row["module"] - sum(row[n] for n in layers.TOP)
            steps.append(row)
        for ex in execs:
            for o, ns in layers.self_times(dev.ops, (ex.start, ex.end)):
                if not any(held(o.scope) for held in tops):
                    key = f"{o.name} [{o.scope}]"
                    unscoped[key] = unscoped.get(key, 0.0) + ns * 1e-6
    top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:12]
    return {"steps": steps,
            "rest_share_max": max(r["rest"] / r["module"] for r in steps),
            "unscoped_ms_top": [[k, v / len(steps)] for k, v in top]}


def traced(prog, gen, first: int, H: int):
    """The ``--trace 1`` run's window, its trace read with scopes, and the
    seconds each reading took."""
    import layers
    import tracefile
    tmp = tempfile.mkdtemp(prefix="layer-split-")
    try:
        window, _ = run.traced_window(prog, gen.global_batch, first, H,
                                      keep=tmp)
        path = tracefile.find_xplane(tmp)
        t0 = time.perf_counter()
        tracefile.load(path)
        t1 = time.perf_counter()
        trace = layers.load(path)
        t2 = time.perf_counter()
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    cost = {"tracefile_load_s": t1 - t0, "scoped_load_s": t2 - t1,
            "xplane_bytes": os.path.getsize(path)}
    return window, trace, path, tmp, cost


def workload(name: str, seed: int) -> dict:
    import check
    import program
    import tracefile
    import traffic
    files = run.cell_files(name, run.load_json(
        os.path.join(ROOT, "BENCHMARK.json")))
    import jax
    devices = jax.devices()[:int(files["work"]["chips"])]
    cfg, job = files["cfg"], files["job"]
    mcfg = program.model_config(cfg, int(job["nodes"]))
    gen = traffic.from_spec(job, mcfg.vocab_size, seed)
    H = int(job["H"])
    prog = program.build(mcfg, job, devices, seed, gen.global_batch(0))
    S = check.sync_steps(H)
    program.drive(prog, gen.global_batch, 0, H, steps=S)
    window, trace, _, tmp, cost = traced(prog, gen, S, H)
    shutil.rmtree(tmp, ignore_errors=True)
    ctx = SimpleNamespace(trace=trace, window=window, interval=trace.window)
    out = {"workload": name, "seed": seed,
           "device_kind": devices[0].device_kind, "readings": readings(ctx),
           "module_ms": {"local": tracefile.step_ms(ctx, False),
                         "sync": tracefile.step_ms(ctx, True)},
           "read_cost": cost}
    out.update(split(trace, window, trace.window))
    return out


def record(out_dir: str) -> dict:
    import jax
    import program
    import record_testdata as rt
    import traffic
    devices = jax.devices()
    mcfg = program.model_config(rt.CFG, rt.JOB["nodes"])
    gen = traffic.from_spec(rt.JOB, mcfg.vocab_size, rt.SEED)
    prog = program.build(mcfg, rt.JOB, devices[:1], rt.SEED,
                         gen.global_batch(0))
    program.drive(prog, gen.global_batch, 0, rt.JOB["H"], steps=2)
    window, trace, path, tmp, _ = traced(prog, gen, 2, rt.JOB["H"])
    os.makedirs(out_dir, exist_ok=True)
    shutil.copyfile(path, os.path.join(out_dir, "ring4_scoped.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)
    wdict = dataclasses.asdict(window)
    kind = devices[0].device_kind
    rec = {"device_kind": kind, "window": wdict,
           "readings": readings(rt.context(trace, wdict, kind))}
    with open(os.path.join(out_dir, "ring4_scoped.json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default="")
    ap.add_argument("--record", action="store_true")
    ap.add_argument("--out-dir", default=os.path.join(HERE, "testdata"))
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("[layer_split] no TPU", file=sys.stderr)
        return run.EXIT_NO_CHIP
    run.setup_compile_cache()
    out = record(args.out_dir) if args.record else workload(args.workload,
                                                            args.seed)
    text = json.dumps(out, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(json.dumps(out["readings"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
