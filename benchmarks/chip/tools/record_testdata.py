"""Record the small chip trace the per-layer readers are tested on.

    python3 benchmarks/chip/tools/record_testdata.py [--out DIR] [--summary]

On one TPU chip: a reduced qwen1.5-0.5b (2 layers, d_model 256, vocab 512)
as a 4-node ring stacked on the chip, H = 2, 4 x 64 tokens per node-step,
with the Pallas SignTopK kernel; six traced steps (three syncs). Writes
``ring4_reduced.xplane.pb`` and ``ring4_reduced.json`` (the window the
harness saw and what each reader reads from the trace) to ``--out``
(default: ``benchmarks/chip/testdata``). ``--summary`` prints the trace's
planes, lines and busiest op names, to look at by hand.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile
from types import SimpleNamespace

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(HERE, "metrics"), os.path.join(ROOT, "src")]

import run  # noqa: E402

CFG = {"hidden_size": 256, "intermediate_size": 768, "num_hidden_layers": 2,
       "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 512,
       "rope_theta": 1e6, "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
       "program": {"arch": "qwen1.5-0.5b", "overrides": {
           "n_layers": 2, "d_model": 256, "n_heads": 4, "n_kv_heads": 4,
           "d_ff": 768, "vocab_size": 512, "tie_embeddings": True,
           "rope_theta": 1e6, "remat": False}},
       "reference": "dense_lm"}
JOB = {"nodes": 4, "batch_per_node": 4, "seq_len": 64, "H": 2, "frac": 0.1,
       "threshold": 2.0, "lr": [0.5, 100.0], "variant": "shift",
       "gamma": 0.0016771845011506895}
READERS = ("host_batch_ms", "device_idle_share", "local_step_ms",
           "sync_step_ms", "sign_topk_roofline", "mfu")
SEED = 20251016


def context(trace, window: dict, kind: str) -> SimpleNamespace:
    """The readers' context for the recorded trace and window."""
    import program
    from reference import dense_lm, sparq
    segs = sparq.layout(dense_lm.init_params, CFG)
    w = program.Window(**window)
    return SimpleNamespace(
        trace=trace, window=w, interval=trace.window,
        tokens_per_step=JOB["nodes"] * JOB["batch_per_node"] * JOB["seq_len"],
        chips=1, flops_per_token=dense_lm.flops_per_token(CFG, JOB["seq_len"]),
        peaks=run.peaks_of(kind), d_model=segs[-1][1] + segs[-1][2],
        frac=JOB["frac"], nodes_per_device=JOB["nodes"])


def read_all(ctx) -> dict:
    return {m: run.reader(m).read(ctx) for m in READERS}


def summary(path: str) -> None:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            tot = {}
            for e in evs:
                tot[e.name] = tot.get(e.name, 0.0) + e.duration_ns
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:12]
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for name, ns in top:
                print(f"     {ns / 1e6:10.3f} ms  {name[:110]}")
            for e in evs[:2]:
                print(f"     e.g. {e.name[:60]!r} {e.start_ns} "
                      f"{e.duration_ns} {list(e.stats)[:8]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=os.path.join(HERE, "testdata"))
    ap.add_argument("--summary", action="store_true")
    args = ap.parse_args(argv)
    import dataclasses
    import jax
    import program
    import tracefile
    import traffic
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("[record] no TPU", file=sys.stderr)
        return run.EXIT_NO_CHIP
    run.setup_compile_cache()
    mcfg = program.model_config(CFG, JOB["nodes"])
    gen = traffic.from_spec(JOB, mcfg.vocab_size, SEED)
    prog = program.build(mcfg, JOB, devices[:1], SEED, gen.global_batch(0))
    if prog.train_step.lowering != "pallas":
        print(f"[record] kernels on {prog.train_step.lowering!r}",
              file=sys.stderr)
        return run.EXIT_SETUP
    program.drive(prog, gen.global_batch, 0, JOB["H"], steps=2)
    tmp = tempfile.mkdtemp(prefix="record-")
    window, trace = run.traced_window(prog, gen.global_batch, 2, JOB["H"],
                                      keep=tmp)
    src = tracefile.find_xplane(tmp)
    os.makedirs(args.out, exist_ok=True)
    dst = os.path.join(args.out, "ring4_reduced.xplane.pb")
    shutil.copyfile(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    if args.summary:
        summary(dst)
    wdict = dataclasses.asdict(window)
    kind = devices[0].device_kind
    readings = read_all(context(tracefile.load(dst), wdict, kind))
    with open(os.path.join(args.out, "ring4_reduced.json"), "w") as f:
        json.dump({"device_kind": kind, "window": wdict,
                   "readings": readings}, f, indent=1)
    print(json.dumps(readings))
    return 0


if __name__ == "__main__":
    sys.exit(main())
