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
import os

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
    import re
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
