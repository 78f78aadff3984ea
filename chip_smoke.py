"""Smoke check of the SPARQ-SGD train step on TPU chips, in one process.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip ring, and nothing else

One chip:
  (b) ``launch/train.run`` trains qwen1.5-0.5b at its published widths, one
      graph node on the chip, with the fused compression kernel, for 10
      steps at H=5 (two syncs);
  (c) the kernels resolved to the Pallas leg, the compiled step holds a
      ``tpu_custom_call``, the loss is finite, triggers fired and ``x_hat``
      holds only the compressed support;
  (d) the Pallas kernel at the model's real flat size D agrees with the
      ``kernels/ref.py`` oracle: the same support, values within tolerance;
  (e) a reduced-width 4-node ring on the one chip (gossip between rows)
      trains with finite loss and nonzero bits and triggers.

Four chips: a 4-node circulant ring (collective-permutes) at reduced width,
once over the 4 chips and once on one chip, where the integer channels must
be equal and the float state within tolerance; then qwen1.5-0.5b at
published widths on the same ring, one node per chip.

Compile time, steady seconds per step, ``peak_bytes_in_use`` and the
compiled step's peak are printed for information, each with the device. The last stdout line is the JSON
result. Any failure raises and exits non-zero; there is no CPU fallback.
"""
import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen1.5-0.5b"
STEPS = 10                 # H=5 -> syncs after steps 5 and 10
# kernel vs oracle (phase d): the selection is integer-exact; scales are f32
# row means whose summation order may differ between Mosaic and XLA
KERNEL_RTOL, KERNEL_ATOL = 1e-5, 1e-6
# 4 chips vs 1 chip (same program, other layout): f32 state after 10 steps
LAYOUT_RTOL, LAYOUT_ATOL = 1e-4, 1e-5


def _device_label(devices) -> str:
    d = devices[0]
    return f"{d.platform}/{d.device_kind} x{len(devices)}"


def _peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def _program_peak(compiled) -> int:
    """The compiler's per-device peak for one step: arguments, outputs not
    aliased to them, and temporaries."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _train(train, argv, devices=None):
    """Run the train driver in-process and print its informational timings."""
    import jax
    devices = list(jax.devices() if devices is None else devices)
    res = train.run(train.parse_args(["--arch", ARCH, "--steps", str(STEPS),
                                      "--use-kernel"] + argv),
                    devices=devices)
    print(f"[smoke] train {' '.join(argv)}: compile "
          f"{res.compile_seconds:.3f} s, {res.seconds_per_step:.6f} s/step "
          f"steady, peak_bytes_in_use {_peak_bytes(devices)}, compiled step "
          f"peak {_program_peak(res.compiled)} on {_device_label(devices)}",
          flush=True)
    return res


def _check_run(res, *, want_bits: bool) -> None:
    """Phase (c)/(e): Pallas leg in the compiled step, finite loss, syncs ran
    and triggered, bits charged iff the graph has edges."""
    import numpy as np
    m = res.metrics
    assert res.train_step.lowering == "pallas", (
        f"kernels resolved to {res.train_step.lowering!r}, not 'pallas'")
    assert "tpu_custom_call" in res.compiled.as_text(), (
        "no tpu_custom_call in the compiled step")
    assert math.isfinite(m["loss"]), f"loss {m['loss']}"
    assert m["sync_rounds"] >= 2, m
    assert m["triggers"] > 0, m
    if want_bits:
        assert m["bits"] > 0, m
    else:
        # one node has no neighbour: nothing is sent and nothing is charged
        assert m["bits"] == 0, m
    # x_hat only ever receives compressed messages: nonzero, and at most the
    # kernel's per-tile support (k_b of 1024 lanes) per triggered sync
    xh = np.asarray(res.state["x_hat"][0])
    nnz = int(np.count_nonzero(xh))
    k_b = math.ceil(0.1 * 1024)
    assert 0 < nnz <= m["triggers"] * k_b * (xh.size // 1024), nnz


def _kernel_vs_oracle(d_model: int) -> None:
    """Phase (d): the Pallas kernel over the model's whole padded flat buffer
    against kernels/ref.py, chunk by chunk of tiles (the oracle's top_k over
    all tiles at once would not fit beside the kernel's buffers)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref
    from repro.kernels.sign_topk import BLOCK, BLOCK_ROWS, sign_topk_blocks

    # the train step's call pads D to whole BLOCK_ROWS-tile slabs
    slab = BLOCK * BLOCK_ROWS
    n_tiles = -(-d_model // slab) * BLOCK_ROWS
    k_b = math.ceil(0.1 * BLOCK)
    kx, ke = jax.random.split(jax.random.PRNGKey(0))

    @jax.jit
    def inputs():
        # quarter-step grid: many exact ties at each tile's threshold; lanes
        # past D are zero, as in the step's padded tail
        live = (jnp.arange(n_tiles * BLOCK) < d_model).reshape(n_tiles, BLOCK)
        xh = jnp.round(4.0 * jax.random.normal(kx, (n_tiles, BLOCK))) / 4.0
        xe = jnp.round(2.0 * jax.random.normal(ke, (n_tiles, BLOCK))) / 4.0
        return jnp.where(live, xh, 0.0), jnp.where(live, xe, 0.0)

    xh, xe = inputs()
    one = jnp.float32(1.0)
    assert "tpu_custom_call" in sign_topk_blocks.lower(
        xh, xe, one, k_b, lowering="pallas").compile().as_text()
    q, xe_new, scale = sign_topk_blocks(xh, xe, one, k_b, lowering="pallas")
    oracle = jax.jit(ref.sign_topk_ref, static_argnames=("k_b",))
    chunk = 65536
    worst = 0.0
    for lo in range(0, n_tiles, chunk):
        hi = min(n_tiles, lo + chunk)
        q_r, xe_r, _, _ = oracle(xh[lo:hi].reshape(-1),
                                 xe[lo:hi].reshape(-1), one, k_b=k_b)
        qk = np.asarray(q[lo:hi]).reshape(-1)
        qr = np.asarray(q_r)
        mism = int(np.count_nonzero((qk != 0) != (qr != 0)))
        assert mism == 0, f"tiles [{lo}, {hi}): {mism} lanes differ in support"
        np.testing.assert_allclose(qk, qr, rtol=KERNEL_RTOL, atol=KERNEL_ATOL)
        np.testing.assert_allclose(np.asarray(xe_new[lo:hi]).reshape(-1),
                                   np.asarray(xe_r), rtol=KERNEL_RTOL,
                                   atol=KERNEL_ATOL)
        worst = max(worst, float(np.max(np.abs(qk - qr))))
    live_tiles = -(-d_model // BLOCK)
    assert bool(jnp.all(scale[:live_tiles] > 0)), "a live tile got no scale"
    assert not bool(jnp.any(scale[live_tiles:])), "a padded tile got a scale"
    print(f"[smoke] kernel vs oracle at D={d_model} ({n_tiles} tiles, "
          f"k_b={k_b}): support equal, max |dq| {worst:.3e}", flush=True)


def _one_node_per_device(state, devices) -> None:
    shards = state["params"].addressable_shards
    got = sorted((s.index[0].start, s.device.id) for s in shards)
    assert len({d for _, d in got}) == len(devices) == len(got), got
    assert all(s.data.shape[0] == 1 for s in shards), (
        [s.data.shape for s in shards])


def one_chip(train) -> None:
    import jax
    chip = jax.devices()[:1]
    res = _train(train, ["--nodes", "1"], devices=chip)
    _check_run(res, want_bits=False)
    d_model = res.train_step.d_model_total
    del res
    _kernel_vs_oracle(d_model)
    res = _train(train, ["--reduced", "--nodes", "4"], devices=chip)
    _check_run(res, want_bits=True)
    assert res.train_step.n_nodes == 4


def _layout_mismatches(wide, one) -> list:
    """The reduced ring on 4 chips vs on 1 chip: integer channels equal,
    float state within tolerance. Prints every channel's comparison and
    returns the mismatches."""
    import numpy as np
    bad = []
    for k in ("t", "sync_rounds", "triggers", "bits"):
        a, b = np.asarray(wide[k]), np.asarray(one[k])
        print(f"[smoke] reduced ring {k}: {a} on 4 chips, {b} on 1 chip",
              flush=True)
        if a != b:
            bad.append(f"{k}: {a} on 4 chips vs {b} on 1 chip")
    for k in ("params", "x_hat"):
        a, b = np.asarray(wide[k]), np.asarray(one[k])
        off = ~np.isclose(a, b, rtol=LAYOUT_RTOL, atol=LAYOUT_ATOL)
        print(f"[smoke] reduced ring {k}: max |4 chips - 1 chip| "
              f"{float(np.max(np.abs(a - b))):.3e}, "
              f"{int(np.count_nonzero(a != b))} of {a.size} lanes differ, "
              f"{int(np.count_nonzero(off))} beyond rtol {LAYOUT_RTOL} "
              f"atol {LAYOUT_ATOL}", flush=True)
        if off.any():
            bad.append(f"{k}: {int(np.count_nonzero(off))} lanes differ "
                       f"beyond tolerance")
    return bad


def four_chips(train) -> None:
    import jax
    devices = jax.devices()
    assert len(devices) == 4, f"--chips 4 needs 4 chips, found {len(devices)}"
    ring = ["--nodes", "4", "--variant", "shift"]
    wide = _train(train, ["--reduced"] + ring)
    _one_node_per_device(wide.state, devices)
    one = _train(train, ["--reduced"] + ring, devices=devices[:1])
    # the full-width phase still runs after a layout mismatch, so that one
    # run reports both; the mismatch fails the check at the end
    bad = _layout_mismatches(wide.state, one.state)
    del wide, one
    res = _train(train, ring)
    _check_run(res, want_bits=True)
    _one_node_per_device(res.state, devices)
    assert "collective-permute" in res.compiled.as_text()
    assert not bad, "4 chips vs 1 chip: " + "; ".join(bad)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: the four-chip ring only")
    args = ap.parse_args(argv)
    if not __debug__:
        print("[smoke] the checks are asserts: run without -O",
              file=sys.stderr)
        return 1

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[smoke] no TPU: jax found {_device_label(devices)}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch import train
    train.setup_compile_cache()
    print(f"[smoke] compile cache {jax.config.jax_compilation_cache_dir}",
          flush=True)
    (four_chips if args.chips == 4 else one_chip)(train)
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
