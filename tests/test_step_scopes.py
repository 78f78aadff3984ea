"""The train step names its layers: each runs under a ``jax.named_scope``
that the compiled HLO carries as ``op_name`` metadata (and the profiler
trace as each op's ``tf_op``), so a trace reduction can split the step's
device time by layer. The step compiles for a reduced qwen1.5-0.5b as a
4-node ring on 8 simulated devices (a subprocess: ``XLA_FLAGS`` must be set
before jax initializes)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis.comm_lint import _INTERPRET_MARKERS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPES = ("sparq_grad", "sparq_unravel", "sparq_optimizer", "sparq_trigger",
          "sparq_compress", "sparq_mix")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json, re, sys
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.registry import get_config
    from repro.dist import sharding as sh
    from repro.dist.sparq_dist import DistSparqConfig, build_sparq
    from repro.launch import hlo_walk
    from repro.launch.mesh import make_mesh

    cfg = dataclasses.replace(get_config("qwen1.5-0.5b").reduced(), n_nodes=4)
    mesh = sh.train_mesh(make_mesh((4, 2), ("data", "model")), cfg)
    out = {}
    for kernel in (False, True):
        dcfg = DistSparqConfig(H=2, variant="shift", frac=0.25,
                               use_kernel=kernel)
        init_fn, train_step, specs, _ = build_sparq(cfg, mesh, dcfg)
        ssh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                           is_leaf=lambda x: isinstance(x, P))
        state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        batch = {k: jax.ShapeDtypeStruct((4, 2, 32), jnp.int32)
                 for k in ("tokens", "labels")}
        bsh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           sh.train_batch_specs(batch, mesh),
                           is_leaf=lambda x: isinstance(x, P))
        hlo = jax.jit(train_step, in_shardings=(ssh, bsh)).lower(
            state, batch).compile().as_text()
        out[str(kernel)] = {
            "op_names": sorted(set(re.findall(r'op_name="([^"]*)"', hlo))),
            "collectives": [[c["kind"], c["op_name"]]
                            for c in hlo_walk.collective_ops(hlo)]}
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def compiled_steps():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [s for s in r.stdout.splitlines() if s.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def _holds(op_name: str, scope: str) -> bool:
    """Whether a path component of op_name is the scope, possibly inside a
    transform wrapper such as ``transpose(jvp(sparq_unravel))``."""
    import re
    rx = re.compile(r"(?<![\w.-])" + re.escape(scope) + r"(?![\w.-])")
    return any(rx.search(part) for part in op_name.split("/"))


@pytest.mark.parametrize("kernel", ["False", "True"])
@pytest.mark.parametrize("scope", SCOPES)
def test_every_layer_scope_reaches_the_compiled_step(compiled_steps, kernel,
                                                     scope):
    names = compiled_steps[kernel]["op_names"]
    assert any(_holds(n, scope) for n in names), scope


@pytest.mark.parametrize("kernel", ["False", "True"])
def test_no_collective_hides_under_an_interpret_marker(compiled_steps,
                                                       kernel):
    """comm_lint and spmd_lint leave out any collective whose op_name holds
    an interpret marker: a scope that named one would drop the gossip out of
    the R11 bit audit."""
    colls = compiled_steps[kernel]["collectives"]
    assert colls, "the 4-node ring gossips over collectives"
    for kind, op_name in colls:
        assert not any(m in op_name.lower() for m in _INTERPRET_MARKERS), (
            kind, op_name)
    # the ring's gossip is the mix layer's
    assert any(kind == "collective-permute" and _holds(op_name, "sparq_mix")
               for kind, op_name in colls), colls


def test_scope_names_avoid_the_interpret_markers():
    for scope in SCOPES:
        assert not any(m in scope for m in _INTERPRET_MARKERS)
