"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its files are
found by name: ``configs/<config>.json`` (sizes as run, and the plain
reference it names under ``reference/``), ``traffic/<traffic>.json`` (the
job's rows and SPARQ settings), ``limits/<cell>.json`` (the limits of the
numbers compared) and ``metrics/<metric>.py`` (one reader per per-layer
metric).

Set-up makes the weights on the devices from the seed, compiles the step
(or loads it from the compile cache), and drives the step through its first
steps, which the check compares with the plain reference. With
``--trace 0`` the window then runs ``--seconds`` of steps and the line
carries the cell's end-to-end metrics; with ``--trace 1`` a short window of
whole sync periods runs under the profiler and the line carries the
per-layer metrics read from its trace. Either way the reference runs last,
once the program's state is freed.

The last line of stdout is the JSON result; the numbers compared, each
beside its limit, are the last lines of stderr and the line's last key. No
TPU, fewer chips than the cell asks for, or kernels off the Pallas leg:
exit non-zero with no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(HERE, "metrics")]

EXIT_NO_CHIP = 3
EXIT_SETUP = 4


class BenchError(Exception):
    """A run that cannot give a result (no chip, wrong leg, missing file)."""

    def __init__(self, msg: str, code: int = EXIT_SETUP):
        super().__init__(msg)
        self.code = code


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_files(name: str, bench: dict) -> dict:
    """The cell's entry and the files its names lead to."""
    work = next((w for w in bench["workloads"] if w["name"] == name), None)
    if work is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json", 2)
    cfg = load_json(os.path.join(HERE, "configs", work["config"] + ".json"))
    job = load_json(os.path.join(HERE, "traffic", work["traffic"] + ".json"))
    lim = os.path.join(HERE, "limits", name + ".json")
    limits = load_json(lim) if os.path.exists(lim) else {}
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [name])]
    return {"work": work, "cfg": cfg, "job": job, "limits": limits,
            "end_to_end": e2e, "per_layer": layer}


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_model(cfg: dict):
    return importlib.import_module("reference." + cfg["reference"])


def reader(metric: str):
    return load_module(os.path.join(HERE, "metrics", metric + ".py"),
                       "bench_metric_" + metric)


def peaks_of(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table or kind == "source":
        raise BenchError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def setup_compile_cache() -> None:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where it is
    set, else the fixed ``.jax_cache/`` of the checkout. Every program is
    kept, so that a second run compiles nothing."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_info(devices, peak: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def traced_window(prog, batch_of, first: int, H: int, keep: str = ""):
    """A window of whole sync periods (at least 6 steps) under the
    profiler; returns it with the trace read back."""
    import jax
    import program
    import tracefile
    steps = max(2 * H, 6)
    steps += (-steps) % H
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        window = program.drive(prog, batch_of, first, H, steps=steps)
        jax.profiler.stop_trace()
        if keep:
            shutil.copytree(log_dir, keep, dirs_exist_ok=True)
        trace = tracefile.load(tracefile.find_xplane(log_dir))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return window, trace


def breakdown(trace, interval) -> dict:
    """The device ops that took most time, and the longest idle gaps by
    what the host was doing."""
    import tracefile
    ops = sorted(tracefile.op_totals(trace, interval).items(),
                 key=lambda kv: -kv[1])[:10]
    gaps = []
    if trace.devices:
        gaps = sorted(((tracefile.host_doing(trace, (a + b) / 2),
                        (b - a) * 1e-9)
                       for a, b in tracefile.idle_gaps(trace.devices[0],
                                                       interval)),
                      key=lambda g: -g[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def run_cell(files: dict, *, seed: int, seconds: float, trace: bool,
             devices, require_pallas: bool = True, t_start: float = None,
             peaks: dict = None):
    """One run of one cell on ``devices``; returns the result line and
    what the look needs (every number, the worst segments, the steps).
    ``require_pallas`` and ``peaks`` are for runs off the chip (tests)."""
    import numpy as np
    import check
    import program
    import traffic as traffic_mod
    from reference import sparq

    t_start = T_START if t_start is None else t_start
    cfg, job, limits = files["cfg"], files["job"], files["limits"]
    model = reference_model(cfg)
    mcfg = program.model_config(cfg, int(job["nodes"]))
    if model.sizes(cfg)["V"] != mcfg.vocab_size:
        raise BenchError(f"vocabulary {mcfg.vocab_size} of the program is "
                         f"not the configuration's {model.sizes(cfg)['V']}")
    gen = traffic_mod.from_spec(job, mcfg.vocab_size, seed)
    H = int(job["H"])
    prog = program.build(mcfg, job, devices, seed, gen.global_batch(0))
    if require_pallas and prog.train_step.lowering != "pallas":
        raise BenchError(f"kernels resolved to {prog.train_step.lowering!r},"
                         f" not 'pallas'")
    segs = sparq.layout(model.init_params, cfg)
    d_model = segs[-1][1] + segs[-1][2]
    if d_model != prog.train_step.d_model_total:
        raise BenchError(f"the program's row holds "
                         f"{prog.train_step.d_model_total} parameters, the "
                         f"configuration {d_model}")
    b, a = (float(v) for v in job["lr"])
    S = check.sync_steps(H)
    got = check.program_readings(prog, gen.global_batch, S,
                                 np.float32(b) / np.float32(a), segs)
    setup_s = time.perf_counter() - t_start - got["check_s"]
    compile_s = prog.compile_s

    tokens_per_step = (int(job["nodes"]) * int(job["batch_per_node"])
                       * int(job["seq_len"]))
    kind = devices[0].device_kind
    if trace:
        window, tr = traced_window(prog, gen.global_batch, S, H)
    else:
        window = program.drive(prog, gen.global_batch, S, H, seconds=seconds)
    peak = program.peak_bytes(devices)
    prog.state = None
    del prog
    gc.collect()

    ref = sparq.run(model, cfg, job, gen, seed, S, devices)
    numbers = check.compare(got, ref)
    judged = check.judge(numbers, limits)
    durs = np.asarray(window.durations())
    p90 = float(np.percentile(durs, 90))
    look = {"numbers": numbers,
            "worst": check.worst_segments(got, ref, segs),
            "compile_s": compile_s,
            "steps": {"median_s": float(np.median(durs)),
                      "max_s": float(durs.max()), "max_at": int(durs.argmax()),
                      "beyond_p90": int(np.sum(durs > p90))}}
    failed = sum(1 for v in window.losses if not np.isfinite(v))

    values = {}
    dev = device_info(devices, peak)
    extra = {}
    if trace:
        interval = tr.window
        ctx = SimpleNamespace(
            trace=tr, window=window, interval=interval,
            tokens_per_step=tokens_per_step, chips=len(devices),
            flops_per_token=model.flops_per_token(cfg, int(job["seq_len"])),
            peaks=peaks or peaks_of(kind), d_model=d_model,
            frac=float(job["frac"]),
            nodes_per_device=max(1, int(job["nodes"]) // len(devices)))
        for m in files["per_layer"]:
            v = reader(m["name"]).read(ctx) if interval else None
            if v is None:
                raise BenchError(f"{m['name']} found nothing to read in the "
                                 f"trace of a cell that reports it")
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if interval:
            import tracefile
            busy = [tracefile.busy(d, interval) for d in tr.devices]
            dev["busy_s"] = (sum(busy) / len(busy) * 1e-9) if busy else 0.0
            dev["window_s"] = (interval[1] - interval[0]) * 1e-9
            extra["breakdown"] = breakdown(tr, interval)
    else:
        e2e = {
            "tokens_per_s": window.steps * tokens_per_step / window.seconds,
            "step_s_p90": p90,
            "setup_s": setup_s,
        }
        for m in files["end_to_end"]:
            values[m["name"]] = {"value": float(e2e[m["name"]]),
                                 "unit": m["unit"]}
    result = {"correct": check.is_correct(judged) and failed == 0,
              "attempted": window.steps, "failed": failed,
              "metrics": values, "device": dev}
    result.update(extra)
    result["check"] = judged
    return result, look


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        files = cell_files(args.workload,
                           load_json(os.path.join(ROOT, "BENCHMARK.json")))
        import jax
        devices = jax.devices()
        chips = int(files["work"]["chips"])
        if devices[0].platform != "tpu":
            raise BenchError(f"no TPU: JAX found {devices[0].platform}",
                             EXIT_NO_CHIP)
        if len(devices) < chips:
            raise BenchError(f"the cell needs {chips} chips, JAX found "
                             f"{len(devices)}", EXIT_NO_CHIP)
        peaks_of(devices[0].device_kind)
        sys.path.insert(0, os.path.join(ROOT, "src"))
        setup_compile_cache()
        result, look = run_cell(
            files, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), devices=devices[:chips])
    except BenchError as e:
        print(f"[bench] {e}", file=sys.stderr)
        return e.code
    print(f"[look] {json.dumps(look)}", file=sys.stderr)
    for name, v in result["check"].items():
        print(f"[check] {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
