"""Small versions of the benchmark's cells for CPU tests: the cells' jobs
with few rows, the configurations at toy widths, limits set from the toy
readings of the program (on the CPU, 5 seeds a cell: loss <= 5.2e-5,
change_median <= 6.7e-4) and of its control (3 seeds: loss >= 1.1e-4,
change_median >= 1.2e-3), and the faults a training cell can have, planted
in the program underneath the harness."""
import contextlib
import json
import os

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")

CONFIGS = {
    "qwen1.5-0.5b": (
        {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "vocab_size": 256},
        {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
         "d_ff": 128, "vocab_size": 256}),
    "mamba2-370m": (
        {"d_model": 64, "n_layer": 2, "vocab_size": 256, "d_state": 16,
         "headdim": 16, "chunk_size": 16},
        {"n_layers": 2, "d_model": 64, "vocab_size": 256, "ssm_state": 16,
         "ssm_head_dim": 16, "ssm_chunk": 16}),
}
LIMITS = {"loss": 1e-4, "grad": 0.01, "grad_median": 0.002, "change": 0.008,
          "change_median": 0.001, "xhat": 0.001, "counters": 0.0}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
FAULTS = ("unchanged", "half_batch")


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def files(config: str, traffic: str) -> dict:
    """A cell's files at toy size: the job keeps its nodes, H, compression
    and schedule; rows are 4 x 64 tokens."""
    cfg = _json("configs", config + ".json")
    small, over = CONFIGS[config]
    cfg.update(small)
    overrides = dict(cfg["program"]["overrides"], **over)
    cfg["program"] = {"arch": cfg["program"]["arch"], "overrides": overrides}
    job = _json("traffic", traffic + ".json")
    job.update(batch_per_node=4, seq_len=64)
    return {"cfg": cfg, "job": job,
            "limits": {k: {"limit": v} for k, v in LIMITS.items()},
            "end_to_end": [{"name": n, "unit": "u"} for n in
                           ("tokens_per_s", "step_s_p90", "setup_s")],
            "per_layer": []}


def run(f: dict, seed: int, devices=None) -> dict:
    import run as harness
    return harness.run_cell(f, seed=seed, seconds=0.5, trace=False,
                            devices=devices or jax.devices()[:1],
                            require_pallas=False, peaks=PEAKS)[0]


@contextlib.contextmanager
def planted(monkeypatch, fault: str):
    """The program with ``fault`` planted underneath the harness."""
    from repro.dist import sparq_dist
    real = sparq_dist.build_sparq

    def build(cfg, mesh, dcfg):
        init_fn, step, specs, pshape = real(cfg, mesh, dcfg)

        def broken(state, batch):
            if fault == "unchanged":
                return state, step(state, batch)[1]
            half = jax.tree.map(lambda x: x[:, :x.shape[1] // 2], batch)
            return step(state, half)

        broken.__dict__.update(step.__dict__)
        return init_fn, broken, specs, pshape

    monkeypatch.setattr(sparq_dist, "build_sparq", build)
    yield
