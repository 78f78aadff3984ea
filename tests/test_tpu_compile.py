"""Compile rehearsals of the compression kernels for a described TPU v5e chip.

Nothing runs here: each test compiles a kernel's ``pallas`` leg for one chip
of a described ``v5e:2x2`` topology (the TPU compiler is installed with
jaxlib) and checks that Mosaic emitted a ``tpu_custom_call``. This catches
what interpret mode cannot — block shapes Mosaic refuses, primitives it has
no lowering for, a slab that overflows the 16 MiB scoped VMEM — at the real
flat sizes: qwen1.5-0.5b with its untied head (D = 619,570,176 = 605,049
tiles of 1024, which the train step's kernel call pads to 605,184 tiles, a
whole number of BLOCK_ROWS-tile slabs), and the benchmark's rows,
qwen1.5-0.5b tied, mamba2-370m and deepseek-v2-lite's one-chip share; and
the dropless expert layer's grouped products at DeepSeek-V2-Lite's widths.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file. The persistent compile cache is off around these compiles (an
entry compiled for a described chip cannot be read back without one).
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ops import sign_topk_ensemble
from repro.kernels.qsgd import qsgd_blocks
from repro.kernels.sign_topk import (
    BLOCK,
    BLOCK_ROWS,
    KERNEL_NAME,
    sign_topk_blocks,
)

QWEN_D = 619_570_176            # qwen1.5-0.5b raveled, = 605,049 * BLOCK
# sign_topk_ensemble's padded tile count: whole BLOCK_ROWS-tile slabs
QWEN_TILES = -(-QWEN_D // (BLOCK * BLOCK_ROWS)) * BLOCK_ROWS
BENCH_D = {"qwen1.5-0.5b-tied": 463_987_712, "mamba2-370m": 368_338_432,
           "deepseek-v2-lite-share": 535_060_992}
K_B = 103                       # ceil(0.1 * BLOCK)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# 605,056 = 128 * 4727 tiles: a count BLOCK_ROWS does not divide, run in
# shorter slabs
@pytest.mark.parametrize("n_tiles", [16, QWEN_TILES, 605_056])
def test_sign_topk_blocks_compiles_for_v5e(one_chip, n_tiles):
    x = _sds((n_tiles, BLOCK), one_chip)
    _assert_mosaic(sign_topk_blocks.lower(
        x, x, _sds((), one_chip), K_B, lowering="pallas").compile())


@pytest.mark.parametrize("d", [QWEN_D, *BENCH_D.values()],
                         ids=["qwen1.5-0.5b", *BENCH_D])
def test_sign_topk_ensemble_compiles_for_v5e(one_chip, d):
    _assert_mosaic(sign_topk_ensemble.lower(
        _sds((1, d), one_chip), K_B, lowering="pallas").compile())


def test_qsgd_blocks_compiles_for_v5e(one_chip):
    x = _sds((64, BLOCK), one_chip)
    _assert_mosaic(qsgd_blocks.lower(x, x, s=16,
                                     lowering="pallas").compile())


def test_sign_topk_custom_call_carries_the_kernel_name(one_chip):
    """The Pallas call's fixed name is the HLO custom call's (XLA may add a
    ``.N`` suffix), whatever jit wraps it; it starts with ``sign_topk``,
    which the benchmark's kernel reader matches."""
    assert KERNEL_NAME.startswith("sign_topk")
    text = sign_topk_ensemble.lower(
        _sds((2, 64 * BLOCK), one_chip), K_B,
        lowering="pallas").compile().as_text()
    calls = re.findall(r"%([\w.-]+) = .*custom-call\(.*"
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert calls and all(re.fullmatch(re.escape(KERNEL_NAME) + r"(\.\d+)?",
                                      c) for c in calls), calls


@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)],
                         ids=["gate-up", "down"])
def test_gmm_and_its_gradients_compile_for_v5e(one_chip, k, n):
    """The grouped products of deepseek-v2-lite's expert layer at its
    widths: 8 held experts, the buffer of 2 x 4096 tokens x 6 choices in
    whole tiles plus one a group, forward and both backward kernels."""
    from repro.kernels import gmm as G
    rows = (-(-8192 * 6 // G.TILE_M) + 8) * G.TILE_M

    def step(lhs, rhs, tile_group, n_active):
        def f(a, b):
            out = G.gmm(a, b, tile_group, n_active, lowering="pallas")
            return jnp.sum(out.astype(jnp.float32) ** 2)
        return jax.grad(f, argnums=(0, 1))(lhs, rhs)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    hlo = jax.jit(step).lower(
        sds((rows, k), jnp.bfloat16), sds((8, k, n), jnp.bfloat16),
        sds((rows // G.TILE_M,), jnp.int32), sds((1,), jnp.int32)
    ).compile().as_text()
    assert hlo.count("tpu_custom_call") == 3
    assert G.GMM_NAME in hlo and G.TGMM_NAME in hlo


_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = ")


def _split_shape(rest):
    """'shape opcode(operands), attrs' -> (shape, opcode, operands, attrs);
    a tuple shape is parenthesized."""
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(c, 0)
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 2:]
    else:
        shape, _, rest = rest.partition(" ")
    opcode, _, rest = rest.partition("(")
    depth, i = 1, 0
    while depth and i < len(rest):
        depth += {"(": 1, ")": -1}.get(rest[i], 0)
        i += 1
    return shape, opcode, re.findall(r"%([\w.\-]+)", rest[:i]), rest[i:]


def _elements(shape):
    return [math.prod(int(d) for d in dims.split(",") if d)
            for dims in re.findall(r"\w+\[([\d,]*)\]", shape)]


def _row_ops(text, d_pad):
    """Instructions of the optimized HLO outside fused computations whose
    result or an operand holds d_pad elements: (name, opcode or custom-call
    target, result shape, operand shapes, op_name)."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    shapes, instrs, comp = {}, [], ""
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        shape, opcode, operands, attrs = _split_shape(line[m.end():])
        shapes[m.group(1)] = shape
        if comp not in fused:
            scope = re.search(r'op_name="([^"]*)"', attrs)
            target = re.search(r'custom_call_target="([^"]*)"', attrs)
            instrs.append((m.group(1), target.group(1) if target else opcode,
                           shape, operands, scope.group(1) if scope else ""))
    return [(name, op, shape, [shapes.get(o, "") for o in operands], scope)
            for name, op, shape, operands, scope in instrs
            if d_pad in _elements(shape)
            or any(d_pad in _elements(shapes.get(o, "")) for o in operands)]


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "mamba2-370m"])
def test_row_view_is_a_bitcast(one_chip, monkeypatch, arch):
    """The train step of one node per chip (a small transformer, and a small
    SSD model whose leaves do not all start on a 128-lane row) takes its
    (1, D_pad) rows as a (D_pad // 128, 128) view and gives them back flat
    by bitcasts: outside the compression (the kernel's own pad, relayout and
    slice) no copy, transpose or reshape touches a row-sized array, and no
    row-sized result is written in (1, 128) or (2, 128) tiles."""
    from repro.configs.registry import get_config
    from repro.core.triggers import constant
    from repro.dist import sharding as sh
    from repro.dist.sparq_dist import DistSparqConfig, build_sparq
    from repro.launch.mesh import make_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P
    monkeypatch.setenv("REPRO_KERNEL_LOWERING", "pallas")
    cfg = dataclasses.replace(get_config(arch).reduced(
        n_layers=2, d_model=256, vocab=512), n_nodes=1)
    mesh = sh.train_mesh(make_mesh((1, 1), ("data", "model"),
                                   devices=list(one_chip.device_set)), cfg)
    dcfg = DistSparqConfig(H=2, frac=0.1, threshold=constant(2.0),
                           variant="ring", use_kernel=True)
    init_fn, train_step, specs, _ = build_sparq(cfg, mesh, dcfg)
    d_pad = train_step.d_pad
    # the kernel pads the row to whole slabs, so its buffers are not
    # row-sized and only the slice back to D_pad is
    assert (d_pad // BLOCK) % BLOCK_ROWS
    ssh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                       is_leaf=lambda x: isinstance(x, P))
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        jax.eval_shape(init_fn, jax.random.PRNGKey(0)), ssh)
    batch = {k: jax.ShapeDtypeStruct((1, 2, 64), jnp.int32,
                                     sharding=NamedSharding(mesh, P("node")))
             for k in ("tokens", "labels")}
    text = jax.jit(train_step, donate_argnums=(0,)).lower(
        state, batch).compile().as_text()
    ops = [o for o in _row_ops(text, d_pad) if "sparq_compress" not in o[4]]
    assert any(op == "fusion" for _, op, *_ in ops), ops
    moves = [o for o in ops if o[1] in ("copy", "transpose", "reshape")]
    assert not moves, moves
    # (an async start's tuple holds its operand, which it only reads; the
    # compiler joins a row it slices into fast memory by ConcatBitcast)
    sparse = [o for o in ops
              if o[1] not in ("parameter", "bitcast", "tuple",
                              "get-tuple-element", "opt-barrier",
                              "ConcatBitcast")
              and not o[1].endswith("-start")
              and d_pad in _elements(o[2])
              and re.search(r"T\([12],128\)", o[2])]
    assert not sparse, sparse
