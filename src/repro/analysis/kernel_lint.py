"""Kernel-contract lint (K1-K4): certify every Pallas kernel statically.

BENCH_kernels shows the fused compressors losing to unfused XLA in interpret
mode, so ROADMAP item 1 (compiled Mosaic kernels) is exactly the change most
likely to land next — and a compiled kernel with a grid that under-covers its
operand, an index map that walks off the padded tail, or a tiling that blows
the VMEM budget fails ON THE TPU TARGET while interpret-mode CI stays green.
These rules make that class of drift a lint error before any TPU is involved:

* **K1 pallas-grid-coverage** — every ``pallas_call`` in src/repro/kernels/
  is exercised by a registered probe under ``jax.eval_shape`` (abstract — no
  kernel executes) with ``pl.pallas_call`` monkey-patched to capture the
  (grid, BlockSpecs, operand shapes, interpret flag) of each site, from its
  keywords or its ``grid_spec``; a kernel whose index maps read
  scalar-prefetch operands is probed with concrete values for them. The
  captured tiling must cover each operand exactly: index maps in bounds for
  every grid point, every element visited, and any padded tail (a dim not
  divisible by its block) masked in the kernel body (``pl.when``) — the
  committed wrappers instead *assert* divisibility, so a non-divisible
  capture without a mask is the broken-fixture case. An un-probed
  ``pallas_call`` site (found by AST scan) is itself a K1 error: new kernels
  must register a probe to land.
* **K2 lowering-flag-hygiene** — the AST leg flags any hard-coded
  ``interpret=<bool literal>`` or ``lowering=<str literal>`` call-site
  keyword or signature default in src/repro/kernels and src/repro/dist (the
  leg must thread through ``repro.kernels.resolve_lowering``); the budget
  leg resolves the ambient lowering once and reports an "interpret-only
  lowering" finding per registered kernel only when it resolves to
  ``"interpret"`` — i.e. no compiled leg exists. Since the compiled XLA leg
  (``lowering="xla"``) became the off-TPU default this finding no longer
  fires on CPU, and the old backend-conditional default suppression is gone.
* **K3 vmem-budget** — closed-form per-invocation VMEM estimate from the
  captured BlockSpecs: (input tiles + output tiles) x 2 (double-buffered
  pipeline) + scratch, vs the 16 MiB/core v5e-class budget.
* **K4 dense-gossip-materialization** — walks the PR-8 call graph
  (analysis/callgraph.py) from the dist train step and tags every dense
  mixing-matrix materialization (``jnp.asarray(plan.ws)`` and friends) or
  contraction (``tensordot``/``einsum``/``matmul``/``@``) it can reach with
  the O(n^2) ceiling: at n = 10^4 nodes one (R, n, n) f32 support is
  R x 400 MB and the per-step mix is 10^8 MACs per parameter column —
  ROADMAP item 2's sparse gossip is the fix, this rule is its tripwire
  (severity *warning* until that PR lands).
"""
from __future__ import annotations

import ast
import functools
import inspect
import math
import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis.rules import Finding, finding

KERNEL_DIR = os.path.join("src", "repro", "kernels")
# per-backend VMEM budget for the closed-form K3 estimate; the TPU number is
# the binding one (v4/v5e ~16 MiB/core) — CPU/GPU audits still certify
# against it because the tiling must stay lowerable on the real target
VMEM_BUDGET_BYTES = {"tpu": 16 * 2**20, "gpu": 16 * 2**20, "cpu": 16 * 2**20}
# coverage is checked element-exactly on a boolean grid; probes are reduced
# shapes so anything bigger than this is a mis-registered probe
_COVERAGE_ELEM_CAP = 1 << 22
_GRID_POINT_CAP = 1 << 16

_DTYPE_BYTES = {"float64": 8, "float32": 4, "bfloat16": 2, "float16": 2,
                "int64": 8, "int32": 4, "uint32": 4, "int16": 2, "int8": 1,
                "uint8": 1, "bool": 1}


def _nbytes(shape: Sequence[int], dtype) -> int:
    return math.prod(shape or (1,)) * _DTYPE_BYTES.get(str(dtype), 4)


# ------------------------------------------------------------------ capture

class PallasCapture:
    """One ``pallas_call`` application seen during a probe's abstract eval."""

    __slots__ = ("probe", "site", "kernel_src", "grid", "in_specs",
                 "out_specs", "operands", "outputs", "interpret",
                 "scratch_bytes", "prefetch")

    def __init__(self, probe: str, site: str, kernel_src: str,
                 grid: Tuple[int, ...], in_specs, out_specs,
                 operands: List[Tuple[Tuple[int, ...], str]],
                 outputs: List[Tuple[Tuple[int, ...], str]],
                 interpret: Optional[bool], scratch_bytes: int,
                 prefetch: Sequence[Any] = ()) -> None:
        self.probe = probe
        self.site = site
        self.kernel_src = kernel_src
        self.grid = grid
        self.in_specs = in_specs
        self.out_specs = out_specs
        self.operands = operands
        self.outputs = outputs
        self.interpret = interpret
        self.scratch_bytes = scratch_bytes
        # scalar-prefetch operands, passed to every index map after the
        # grid indices (None where the probe left one abstract)
        self.prefetch = list(prefetch)


def _kernel_site() -> str:
    """file:line of the innermost stack frame inside src/repro/kernels."""
    import traceback
    for fr in reversed(traceback.extract_stack()):
        fn = fr.filename.replace(os.sep, "/")
        if "/repro/kernels/" in fn:
            ix = fn.rindex("/repro/kernels/")
            return f"src{fn[ix:]}:{fr.lineno}"
    return "<unknown>"


def _spec_list(specs) -> list:
    if specs is None:
        return []
    return list(specs) if isinstance(specs, (list, tuple)) else [specs]


def _kernel_source(kernel: Callable) -> str:
    fn = kernel.func if isinstance(kernel, functools.partial) else kernel
    try:
        return inspect.getsource(fn)
    except (OSError, TypeError):
        return ""


def capture_probes(probes: Sequence[Tuple[str, Callable, tuple, dict]]
                   ) -> List[PallasCapture]:
    """Run each ``(name, fn, arg_sds, kwargs)`` probe under ``jax.eval_shape``
    with ``pl.pallas_call`` patched to record every application. ``fn`` is
    unwrapped through its jit decoration first so the probe always retraces
    (a warm jit cache would otherwise skip the pallas_call entirely), and
    the global trace caches are cleared first for the same reason: the
    flat-vector ops.py wrappers call the JITTED block kernels internally,
    so a prior trace at the probe shapes would hide their pallas_call."""
    import jax
    from jax.experimental import pallas as pl

    jax.clear_caches()
    captures: List[PallasCapture] = []
    orig = pl.pallas_call
    current = [""]

    def patched(kernel, *args, **kw):
        out_shape = kw.get("out_shape", args[0] if args else None)
        site = _kernel_site()
        ksrc = _kernel_source(kernel)
        inner = orig(kernel, *args, **kw)
        gs = kw.get("grid_spec")
        tiling = kw if gs is None else {
            "grid": gs.grid, "in_specs": gs.in_specs,
            "out_specs": gs.out_specs,
            "scratch_shapes": getattr(gs, "scratch_shapes", ())}
        n_pre = int(getattr(gs, "num_scalar_prefetch", 0) or 0)

        def applied(*operands):
            scratch = 0
            for s in _spec_list(tiling.get("scratch_shapes", ())):
                shp = getattr(s, "shape", None)
                if shp is not None:
                    scratch += _nbytes(tuple(shp),
                                       getattr(s, "dtype", "float32"))
            outs = jax.tree.leaves(out_shape)
            pre, rest = operands[:n_pre], operands[n_pre:]
            captures.append(PallasCapture(
                probe=current[0], site=site, kernel_src=ksrc,
                grid=tuple(int(g) for g in np.atleast_1d(
                    tiling.get("grid", ()))),
                in_specs=_spec_list(tiling.get("in_specs")),
                out_specs=_spec_list(tiling.get("out_specs")),
                operands=[(tuple(o.shape), str(o.dtype)) for o in rest],
                outputs=[(tuple(o.shape), str(o.dtype)) for o in outs],
                interpret=kw.get("interpret"),
                scratch_bytes=scratch,
                prefetch=[o if isinstance(o, np.ndarray) else None
                          for o in pre]))
            return inner(*operands)

        return applied

    pl.pallas_call = patched
    try:
        for name, fn, args, kwargs in probes:
            current[0] = name
            raw = inspect.unwrap(fn)  # past the jit wrapper: always retrace
            jax.eval_shape(functools.partial(raw, **kwargs), *args)
    finally:
        pl.pallas_call = orig
    return captures


def default_probes() -> List[Tuple[str, Callable, tuple, dict]]:
    """The registered probe per public kernel entry: exact-tile block shapes
    AND a non-multiple flat length (5000 -> 5 x 1024 padded) so both the
    blockwise kernels and the ops.py padding path are captured. Every probe
    pins ``lowering="pallas"`` — capture runs under ``jax.eval_shape`` so no
    kernel executes and the Pallas path traces abstractly even on CPU; the
    ambient default (the compiled XLA leg off-TPU) would otherwise skip the
    ``pallas_call`` sites entirely and K1 would have nothing to check."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import gmm, ops, qsgd, sign_topk

    B = sign_topk.BLOCK

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    key = sds(2, dtype=jnp.uint32)
    # the grouped products' index maps read the tile layout: four groups
    # of one whole tile each, every tile active
    tiles = {"tile_group": np.arange(4, dtype=np.int32),
             "n_active": np.array([4], np.int32), "interpret": False}
    rows = 4 * gmm.TILE_M
    return [
        ("sign_topk_blocks", sign_topk.sign_topk_blocks,
         (sds(8, B), sds(8, B), sds()), {"k_b": 102, "lowering": "pallas"}),
        ("sign_topk_blocks/tall", sign_topk.sign_topk_blocks,
         (sds(32, B), sds(32, B), sds()), {"k_b": 13, "lowering": "pallas"}),
        ("qsgd_blocks", qsgd.qsgd_blocks,
         (sds(8, B), sds(8, B)), {"s": 16, "lowering": "pallas"}),
        ("ops.sign_topk", ops.sign_topk, (sds(5000),),
         {"k": 128, "lowering": "pallas"}),
        ("ops.trigger_compress_update", ops.trigger_compress_update,
         (sds(5000), sds(5000), sds()), {"k_b": 13, "lowering": "pallas"}),
        ("ops.sign_topk_ensemble", ops.sign_topk_ensemble,
         (sds(4, 2 * B + 300),), {"k_b": 13, "lowering": "pallas"}),
        ("ops.qsgd", ops.qsgd, (sds(5000), key),
         {"s": 16, "lowering": "pallas"}),
        ("sign_topk_q", sign_topk.sign_topk_q, (sds(8, B),),
         {"k_b": 102, "lowering": "pallas"}),
        ("gmm", gmm._gmm_call,
         (sds(rows, 64, dtype=jnp.bfloat16),
          sds(4, 64, 96, dtype=jnp.bfloat16)),
         dict(tiles, transpose_rhs=False)),
        ("gmm/transposed", gmm._gmm_call,
         (sds(rows, 96, dtype=jnp.bfloat16),
          sds(4, 64, 96, dtype=jnp.bfloat16)),
         dict(tiles, transpose_rhs=True)),
        ("gmm/tgmm", gmm._tgmm_call,
         (sds(rows, 64, dtype=jnp.bfloat16),
          sds(rows, 96, dtype=jnp.bfloat16)), dict(tiles, groups=4)),
    ]


# ----------------------------------------------------------------------- K1

def _grid_points(grid: Tuple[int, ...]) -> List[Tuple[int, ...]]:
    total = math.prod(grid or (1,))
    if total > _GRID_POINT_CAP:
        raise ValueError(f"grid {grid} too large for exact enumeration")
    pts: List[Tuple[int, ...]] = [()]
    for g in grid:
        pts = [p + (i,) for p in pts for i in range(g)]
    return pts


def _has_tail_mask(kernel_src: str) -> bool:
    return "pl.when" in kernel_src or "@when" in kernel_src or \
        "pl.load" in kernel_src


def lint_coverage(captures: Sequence[PallasCapture], *, program: str
                  ) -> Tuple[List[Finding], Dict[str, Any]]:
    """K1 over captured tilings: in-bounds index maps, exact coverage,
    masked-or-asserted padded tails."""
    out: List[Finding] = []
    meta: Dict[str, Any] = {"captures": len(captures), "operands_checked": 0}
    for cap in captures:
        loc = f"{program}:{cap.probe} ({cap.site})"
        pairs = (list(zip(cap.operands, cap.in_specs))
                 + list(zip(cap.outputs, cap.out_specs)))
        if len(cap.in_specs) != len(cap.operands) or \
                len(cap.out_specs) != len(cap.outputs):
            out.append(finding(
                "K1", f"spec/operand arity mismatch: {len(cap.in_specs)} "
                      f"in_specs for {len(cap.operands)} operands, "
                      f"{len(cap.out_specs)} out_specs for "
                      f"{len(cap.outputs)} outputs", loc))
            continue
        try:
            pts = _grid_points(cap.grid)
        except ValueError as e:
            out.append(finding("K1", str(e), loc))
            continue
        if any(v is None for v in cap.prefetch):
            out.append(finding(
                "K1", "scalar-prefetch operands left abstract: the probe "
                      "must give their values for the index maps", loc))
            continue
        for (shape, _dt), spec in pairs:
            meta["operands_checked"] += 1
            bs = _block(spec)
            if len(bs) != len(shape):
                out.append(finding(
                    "K1", f"block shape {bs} rank != operand rank of "
                          f"{shape}", loc))
                continue
            if math.prod(shape or (1,)) > _COVERAGE_ELEM_CAP:
                out.append(finding(
                    "K1", f"operand {shape} too large for element-exact "
                          f"coverage check — register a reduced probe", loc))
                continue
            nblocks = tuple(-(-s // b) for s, b in zip(shape, bs))
            covered = np.zeros(shape, dtype=bool)
            oob = False
            for p in pts:
                coord = spec.index_map(*p, *cap.prefetch)
                coord = tuple(int(c) for c in np.atleast_1d(coord))
                if len(coord) != len(bs):
                    out.append(finding(
                        "K1", f"index map returns rank-{len(coord)} coord "
                              f"for rank-{len(bs)} block at grid {p}", loc))
                    oob = True
                    break
                if any(c < 0 or c >= nb for c, nb in zip(coord, nblocks)):
                    out.append(finding(
                        "K1", f"index map out of bounds: grid point {p} -> "
                              f"block coord {coord}, valid range "
                              f"{tuple(nb - 1 for nb in nblocks)} for "
                              f"operand {shape} / block {bs}", loc))
                    oob = True
                    break
                sl = tuple(slice(c * b, min((c + 1) * b, s))
                           for c, b, s in zip(coord, bs, shape))
                covered[sl] = True
            if oob:
                continue
            if not covered.all():
                miss = int(covered.size - covered.sum())
                out.append(finding(
                    "K1", f"grid {cap.grid} x block {bs} leaves {miss} of "
                          f"{covered.size} elements of operand {shape} "
                          f"unvisited", loc))
            tail_dims = [d for d, (s, b) in enumerate(zip(shape, bs))
                         if s % b != 0]
            if tail_dims and not _has_tail_mask(cap.kernel_src):
                out.append(finding(
                    "K1", f"padded tail on dim(s) {tail_dims} (operand "
                          f"{shape}, block {bs}) with no pl.when mask in "
                          f"the kernel body and no divisibility assert "
                          f"upstream", loc))
    return out, meta


def uncovered_sites(captures: Sequence[PallasCapture], root: str = ".",
                    *, program: str) -> List[Finding]:
    """K1 completeness: every textual ``pallas_call`` site under
    src/repro/kernels must have been hit by at least one capture."""
    hit = {cap.site.split(":")[0] + ":" + cap.site.split(":")[1]
           for cap in captures if cap.site != "<unknown>"}
    out: List[Finding] = []
    for path, node in _kernel_call_sites(root):
        site = f"{path}:{node.lineno}"
        if site not in hit:
            out.append(finding(
                "K1", f"pallas_call site {site} is not covered by any "
                      f"registered probe (kernel_lint.default_probes)",
                f"{program}:{site}"))
    return out


def _kernel_call_sites(root: str):
    """(relpath, ast.Call) per ``pl.pallas_call(...)`` under kernels/."""
    kdir = os.path.join(root, KERNEL_DIR)
    if not os.path.isdir(kdir):
        return
    for fname in sorted(os.listdir(kdir)):
        if not fname.endswith(".py"):
            continue
        path = os.path.join(kdir, fname)
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "pallas_call":
                yield rel, node


# ----------------------------------------------------------------------- K2

def lint_interpret_ast(root: str = ".", *, program: str,
                       dirs: Sequence[str] = ("src/repro/kernels",
                                              "src/repro/dist")
                       ) -> List[Finding]:
    """K2 (AST leg): no ``interpret=<bool literal>`` and no
    ``lowering=<str literal>`` call-site keyword or signature default
    anywhere in the kernel/dist packages — both flags must thread through
    repro.kernels.resolve_lowering() so env overrides and the per-backend
    default stay authoritative."""

    def _literal(kwname: str, val) -> Optional[str]:
        if kwname == "interpret" and isinstance(val, ast.Constant) and \
                isinstance(val.value, bool):
            return f"interpret={val.value}"
        if kwname == "lowering" and isinstance(val, ast.Constant) and \
                isinstance(val.value, str):
            return f'lowering="{val.value}"'
        return None

    out: List[Finding] = []
    for d in dirs:
        full = os.path.join(root, d)
        if not os.path.isdir(full):
            continue
        for fname in sorted(os.listdir(full)):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(full, fname)
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
            for node in ast.walk(tree):
                if isinstance(node, ast.Call):
                    for kwn in node.keywords:
                        lit = _literal(kwn.arg or "", kwn.value)
                        if lit is not None:
                            out.append(finding(
                                "K2", f"hard-coded {lit} literal at a call "
                                      f"site — thread it from "
                                      f"repro.kernels.resolve_lowering()",
                                f"{program}:{rel}:{node.lineno}"))
                elif isinstance(node, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    args = node.args
                    named = args.posonlyargs + args.args + args.kwonlyargs
                    defaults = ([None] * (len(args.posonlyargs)
                                          + len(args.args)
                                          - len(args.defaults))
                                + list(args.defaults) + list(args.kw_defaults))
                    for a, dflt in zip(named, defaults):
                        lit = _literal(a.arg, dflt)
                        if lit is not None:
                            out.append(finding(
                                "K2", f"literal default {lit} in "
                                      f"{node.name}() signature — default "
                                      f"must be None, resolved via "
                                      f"repro.kernels.resolve_lowering()",
                                f"{program}:{rel}:{node.lineno}"))
    return out


def lint_interpret_budget(captures: Sequence[PallasCapture], *, program: str,
                          backend: str
                          ) -> Tuple[List[Finding], Dict[str, Any]]:
    """K2 (budget leg): every registered kernel must have a COMPILED lowering
    on this backend. The ambient leg is resolved once via
    ``repro.kernels.resolve_lowering()``: ``"pallas"`` (Mosaic/Triton custom
    call, audited by R5 on the lowered module) and ``"xla"`` (the identical
    blockwise math compiled by XLA) both count as compiled; only
    ``"interpret"`` — the Pallas interpreter simulating the kernel op-by-op —
    is the finding. Probes pin ``lowering="pallas"`` for K1 capture, so the
    per-capture flag says nothing about production resolution; the ambient
    default is the contract."""
    from repro.kernels import resolve_lowering

    default_leg = resolve_lowering()
    kernels = sorted({cap.probe.split("/")[0] for cap in captures})
    out: List[Finding] = []
    if default_leg == "interpret":
        for kernel in kernels:
            out.append(finding(
                "K2", f"registered kernel {kernel!r} resolves to an "
                      f"interpret-only lowering on backend {backend!r} "
                      f"(resolve_lowering() -> 'interpret': no compiled "
                      f"leg)", f"{program}:{kernel}"))
    return out, {"default_lowering": default_leg,
                 "kernels": {k: default_leg for k in kernels}}


# ----------------------------------------------------------------------- K3

def _block(spec) -> Tuple[int, ...]:
    """A BlockSpec's block shape, a squeezed (None) dim counted as 1."""
    return tuple(1 if b is None else int(b) for b in spec.block_shape)


def vmem_estimate(cap: PallasCapture) -> int:
    """Closed-form per-invocation VMEM bytes: one input tile + one output
    tile per spec, x2 for the double-buffered pipeline, + scratch."""
    tile = 0
    for (shape, dt), spec in (list(zip(cap.operands, cap.in_specs))
                              + list(zip(cap.outputs, cap.out_specs))):
        bs = _block(spec)
        if len(bs) == len(shape):
            tile += _nbytes(bs, dt)
    return 2 * tile + cap.scratch_bytes


def lint_vmem(captures: Sequence[PallasCapture], *, program: str,
              backend: str = "tpu", budget_bytes: Optional[int] = None
              ) -> Tuple[List[Finding], Dict[str, Any]]:
    budget = budget_bytes if budget_bytes is not None else \
        VMEM_BUDGET_BYTES.get(backend, VMEM_BUDGET_BYTES["tpu"])
    out: List[Finding] = []
    est: Dict[str, int] = {}
    for cap in captures:
        e = vmem_estimate(cap)
        est[cap.probe] = max(est.get(cap.probe, 0), e)
        if e > budget:
            out.append(finding(
                "K3", f"VMEM estimate {e} bytes for probe {cap.probe!r} "
                      f"(double-buffered tiles + scratch) exceeds the "
                      f"{budget}-byte {backend} budget",
                f"{program}:{cap.probe} ({cap.site})"))
    return out, {"budget_bytes": budget, "estimates": est}


# ----------------------------------------------------------------------- K4

_DENSE_CONTRACTIONS = ("tensordot", "einsum", "matmul")
_DENSE_SOURCES = ("ws", "w")  # plan.ws (R,n,n) support, Topology.w (n,n)
# contractions only count as MIXING work inside the gossip modules — a
# transformer layer's x @ W is model compute, not an (n, n) consensus term
_GOSSIP_MODULES = ("repro.core.sparq", "repro.core.topology",
                   "repro.dist.sparq_dist")

# n = 10^4 reference point the finding message quotes (ROADMAP item 2)
_CEILING_N = 10_000


def _dist_reachable(graph) -> set:
    """Functions reachable from the dist train-step builder — traced bodies
    AND the host-side build_sparq closure, where the (R, n, n) support is
    materialized as a device constant the traced step captures."""
    roots = {q for q, fn in graph.functions.items()
             if fn.module == "repro.dist.sparq_dist"}
    seen = set(roots)
    frontier = list(roots)
    while frontier:
        q = frontier.pop()
        fn = graph.functions.get(q)
        if fn is None:
            continue
        for cs in fn.calls:
            for callee in graph.site_callees(cs):
                if callee not in seen:
                    seen.add(callee)
                    frontier.append(callee)
    return seen


def lint_dense_gossip(root: str = ".", *, program: str, graph=None
                      ) -> Tuple[List[Finding], Dict[str, Any]]:
    """K4: tag dense mixing-matrix work reachable from the dist step."""
    from repro.analysis.callgraph import build_repo_callgraph

    if graph is None:
        graph = build_repo_callgraph(root)
    reachable = _dist_reachable(graph)
    out: List[Finding] = []
    sites: set = set()
    gb = 4 * _CEILING_N * _CEILING_N / 2**30  # one (n, n) f32 in GiB
    for q in sorted(reachable):
        fn = graph.functions[q]
        node = fn.node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
            continue
        gossip_mod = fn.module in _GOSSIP_MODULES
        for sub in ast.walk(node):
            desc = None
            if gossip_mod and isinstance(sub, ast.Call) and \
                    isinstance(sub.func, ast.Attribute) and \
                    sub.func.attr in _DENSE_CONTRACTIONS:
                desc = f"dense {sub.func.attr} contraction"
            elif gossip_mod and isinstance(sub, ast.BinOp) and \
                    isinstance(sub.op, ast.MatMult):
                desc = "dense @ contraction"
            elif isinstance(sub, ast.Call) and \
                    isinstance(sub.func, ast.Attribute) and \
                    sub.func.attr in ("asarray", "array") and sub.args and \
                    isinstance(sub.args[0], ast.Attribute) and \
                    sub.args[0].attr in _DENSE_SOURCES:
                desc = (f"dense mixing-matrix materialization "
                        f"(.{sub.args[0].attr} constant)")
            if desc is None:
                continue
            key = (fn.file, getattr(sub, "lineno", fn.lineno))
            if key in sites:
                continue
            sites.add(key)
            rel = os.path.relpath(fn.file, root).replace(os.sep, "/")
            out.append(finding(
                "K4", f"{desc} in {fn.name}() is reachable from the dist "
                      f"train step: O(n^2) in ensemble size — at n={_CEILING_N} "
                      f"one (n, n) f32 mixing matrix is {gb:.1f} GiB per "
                      f"round (ROADMAP item 2: sparse gossip)",
                f"{program}:{rel}:{key[1]}"))
    return out, {"dist_reachable": len(reachable), "dense_sites": len(sites)}


# -------------------------------------------------------------------- driver

def audit_kernels(root: str = ".", *, program: str = "kernels/pallas",
                  backend: Optional[str] = None,
                  probes: Optional[Sequence[Tuple[str, Callable, tuple,
                                                  dict]]] = None
                  ) -> Tuple[List[Finding], Dict[str, Any]]:
    """All four K rules over the committed kernel package."""
    import jax

    backend = backend or jax.default_backend()
    probes = list(probes) if probes is not None else default_probes()
    captures = capture_probes(probes)
    findings: List[Finding] = []
    meta: Dict[str, Any] = {"backend": backend, "probes": len(probes)}

    f1, m1 = lint_coverage(captures, program=program)
    findings += f1
    findings += uncovered_sites(captures, root, program=program)
    meta["coverage"] = m1
    findings += lint_interpret_ast(root, program=program)
    f2, m2 = lint_interpret_budget(captures, program=program,
                                   backend=backend)
    findings += f2
    meta["interpret"] = m2
    f3, m3 = lint_vmem(captures, program=program, backend="tpu")
    findings += f3
    meta["vmem"] = m3
    f4, m4 = lint_dense_gossip(root, program=program)
    findings += f4
    meta["dense_gossip"] = m4
    return findings, meta
