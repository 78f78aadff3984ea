"""Distributed SPARQ-SGD over ONE flat node-stacked parameter buffer, SPMD
over the (node, fsdp, model) logical mesh.

This is the scaled realization of the engine contract whose ground truth is
core/sparq.py's dense (n, d) reference. The model pytree is RAVELED ONCE at
build time into a contiguous ``(n, D_pad)`` float32 buffer (``D_pad`` pads the
true model dimension ``D`` up to whole 1024-element kernel tiles; the tail is
identically zero and stays zero — zero lanes are never selected by the
exact-k compression and carry no gradient). Gossip, the trigger norm,
``x_hat``, the optimizer buffers and the bit accounting ALL operate on that
flat view; the loss alone sees the model structure, through a precomputed
static-slice ``unravel`` applied per node row inside ``value_and_grad``.
Inside the step each row is seen as ``(D_pad // 128, 128)``, the same bytes
in full (8, 128) vector tiles, and the gradient is built in that view.
The trigger / consensus-mixing / bit-accounting primitives are imported from
core (``trigger_mask``, ``gossip_mix``, ``sync_message_bits``) so the two
engines cannot drift — tests/test_dist_equivalence.py pins them equal.

Per sync index (every H steps):

    x^{t+1/2} = x^t - eta_t (m^t or g^t)                       (local SGD)
    trig_i    = [ ||x_i^{t+1/2} - x_hat_i||^2 > c_t eta_t^2 ]  (one row norm)
    q_i       = trig_i * C(x_i^{t+1/2} - x_hat_i)              (flat vector)
    x_hat'    = x_hat + q                                      (line 13)
    x^{t+1}   = x^{t+1/2} + gamma (W x_hat' - x_hat')          (line 15)

Compression runs over the FLAT vector, not per tensor: the generic path vmaps
the registry operator over the ``(n, D)`` rows (one global top-k over the
whole model — matching the full-parameter-vector analyses of Qsparse-local-SGD
and SQuARM-SGD, and deliberately NOT the per-tensor Section 5.2 treatment;
tests pin the divergence), and ``use_kernel=True`` runs ONE fused blockwise
``kernels.ops.sign_topk_ensemble`` dispatch over the whole ``(n, D_pad)``
buffer per sync — no per-leaf loop anywhere. The kernel path's operator
semantics are exactly ``core.compression.BlockTopFrac`` (bit-identical), so
dist-with-kernel == reference-with-BlockTopFrac is directly testable.

The communication graph is pluggable (core.topology.GossipPlan): any static
Topology (ring/torus2d/complete/expander, uniform or Metropolis mixing) or a
time-varying plan (random matchings, edge-sampled subgraphs, a round-robin
graph cycle). The plan's whole ``(R, n, n)`` support is one device constant;
the sync branch looks the active ``W_r`` up by ``sync_rounds % R`` and the
per-node bit accounting charges the *active* round's degrees ``deg_r``.

Mixing implementation (``variant``):

* ``dense`` — mixing materialized as a tensordot over the node axis
  (all-gather along ``node``; exact W X for any W, static or time-varying).
* ``shift`` (alias ``ring``) — circulant lowering: a static circulant W
  (w[i, j] depends only on (j - i) mod n — ring, any shift-symmetric graph)
  decomposes into per-shift ``jnp.roll`` terms, which XLA lowers to
  collective-permutes along ``node``. Falls back to ``dense`` when the plan
  is time-varying, the graph is not circulant, or n <= 2.

The kernel lowering (pallas / interpret / xla) resolves ONCE at build time
through :func:`repro.kernels.resolve_lowering` (env/backend, never a literal)
and is exposed as ``train_step.lowering``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import bits as bits_mod
from repro.core.compression import BlockTopFrac, Compressor, TopFrac
from repro.core.faults import COMPRESS_STREAM, FaultPlan, resolve_faults
from repro.core.schedule import LRSchedule, decaying
from repro.core.sparq import gossip_mix, sync_message_bits, trigger_mask
from repro.core.topology import GossipPlan, Topology, circulant_row, make_plan
from repro.core.triggers import ThresholdSchedule, zero
from repro import kernels as kernels_mod
from repro.kernels import ops as kernel_ops
from repro.kernels.sign_topk import BLOCK
from repro.models.transformer import init_params, lm_loss
from repro.optim.sgd import Optimizer, resolve_optimizer

State = Dict[str, Any]
LANES = 128   # a vector register's lanes; it has 8 sublanes


@dataclasses.dataclass(frozen=True)
class DistSparqConfig:
    """Runtime knobs of the distributed engine (model knobs live on ModelConfig)."""

    H: int = 1                       # gap(I_T): sync every H steps
    variant: str = "dense"           # dense | shift (alias ring): mixing impl
    frac: float = 1.0                # flat-vector SignTopK fraction
    use_kernel: bool = False         # fused blockwise compression kernel
    threshold: ThresholdSchedule = zero()
    lr: LRSchedule = decaying(0.5, 10.0)
    momentum: float = 0.0            # shorthand for optimizer=momentum(beta)
                                     # (Section 5.2 / SQuARM-SGD momentum)
    nesterov: bool = False           # SQuARM Nesterov variant (with momentum)
    optimizer: Optional[Optimizer] = None  # local-update rule; None -> sgd()
    gamma: Optional[float] = None    # None -> gamma* from Lemma 6
    microbatches: int = 1            # grad accumulation within a node
    xhat_dtype: str = "float32"      # public-estimate storage dtype
    # ---- communication graph (core/topology.py) ----
    topology: Union[str, Topology, None] = None
                                     # graph kind ("ring"|"torus2d"|"complete"
                                     # |"expander") built at the resolved
                                     # ensemble size, or an explicit Topology
                                     # (its n must match); None -> "ring"
    deg: int = 4                     # expander degree (kind strings only)
    mixing: str = "uniform"          # uniform | metropolis (kind strings only)
    dynamic: str = "none"            # none | matchings | edges | cycle —
                                     # time-varying plan family (make_plan)
    rounds: int = 8                  # dynamic support size / period R
    edge_frac: float = 0.5           # edge keep-probability (dynamic="edges")
    topo_seed: int = 0               # graph / plan sampling seed
    plan: Optional[GossipPlan] = None  # full override; wins over all of the
                                       # above (its n must match)
    compressor: Optional[Compressor] = None  # flat-vector op; None ->
                                             # TopFrac(frac). Stochastic ops
                                             # are fine: the sync branch folds
                                             # a PRNG key from the step counter
    seed: int = 0                    # base PRNG seed for stochastic compressors
    faults: Optional[FaultPlan] = None  # link-drop / straggler / dropout
                                        # injection (core/faults.py); the
                                        # fault stream is a pure function of
                                        # (seed, t, sync_round), so it is
                                        # IDENTICAL to the reference engine's

    def resolved_optimizer(self) -> Optimizer:
        return resolve_optimizer(self.optimizer, self.momentum,
                                 nesterov=self.nesterov)

    def resolved_plan(self, n: int) -> GossipPlan:
        """Communication plan at ensemble size ``n`` (the mesh-stretched node
        count build_sparq resolves): ``plan=`` verbatim, an explicit Topology
        as a static plan, or a kind string built here via make_plan."""
        if self.plan is not None:
            if self.plan.n != n:
                raise ValueError(
                    f"plan {self.plan.name!r} has n={self.plan.n} but the "
                    f"resolved ensemble size is {n} (cfg.n_nodes stretched "
                    f"over the mesh node axis; see build_sparq.__doc__)")
            return self.plan
        if isinstance(self.topology, Topology):
            if self.dynamic not in ("none", "static", ""):
                raise ValueError(
                    f"dynamic={self.dynamic!r} with an explicit Topology is "
                    f"ambiguous — pass plan= (e.g. GossipPlan.edge_sampled/"
                    f"cycle) or a kind string instead")
            if self.topology.n != n:
                raise ValueError(
                    f"topology {self.topology.name!r} has n={self.topology.n} "
                    f"but the resolved ensemble size is {n}")
            return GossipPlan.from_topology(self.topology)
        return make_plan(self.topology or "ring", n, deg=self.deg,
                         seed=self.topo_seed, mixing=self.mixing,
                         dynamic=self.dynamic, rounds=self.rounds,
                         edge_frac=self.edge_frac)

    def resolved_compressor(self) -> Compressor:
        if self.compressor is not None:
            if self.use_kernel:
                raise ValueError(
                    "use_kernel=True hard-wires the fused blockwise SignTopK "
                    "operator; a custom compressor= cannot ride it")
            return self.compressor
        return TopFrac(frac=self.frac)

    def effective_compressor(self) -> Compressor:
        """The operator the sync path ACTUALLY applies to the flat vector:
        the blockwise kernel operator under ``use_kernel=True`` (bit-identical
        to kernels.ops.sign_topk_ensemble), else ``resolved_compressor()``.
        Payload bits and Lemma-6 gamma* both derive from this."""
        if self.use_kernel:
            return BlockTopFrac(frac=self.frac)
        return self.resolved_compressor()

    def resolved_gamma(self, plan, d: Optional[int] = None) -> float:
        """``plan`` is a GossipPlan or Topology (both expose gamma_star; a
        time-varying plan resolves the worst case over its support)."""
        if self.gamma is not None:
            return float(self.gamma)
        # defer to the effective operator's own omega at the true model
        # dimension (TopFrac.omega: k/d with k = ceil(frac*d), capped at the
        # 2/pi full-sign isotropic retention; BlockTopFrac: k_b/BLOCK per
        # tile), exactly what the reference engine's gamma* resolution uses
        comp = self.effective_compressor()
        if d:
            om = comp.omega(d)
        elif self.compressor is None and not self.use_kernel:
            # TopFrac's omega in the d->inf limit, same 2/pi cap as omega()
            om = min(self.frac, 2.0 / math.pi)
        elif self.use_kernel:
            om = comp.omega(BLOCK)   # per-tile: dimension-independent
        else:
            raise ValueError(
                "resolved_gamma() needs the model dimension d when gamma is "
                "None and a custom compressor= is set: its contraction "
                "omega(d) is dimension-dependent")
        return float(plan.gamma_star(max(om, 1e-3)))


def _flatten_spec(pshape) -> Tuple[Any, Tuple[Tuple[int, int, Any], ...], int]:
    """Static ravel plan for the model pytree: (treedef, per-leaf
    (offset, size, ShapeDtypeStruct) slices, total D)."""
    leaves, treedef = jax.tree.flatten(pshape)
    slices = []
    off = 0
    for leaf in leaves:
        size = int(math.prod(leaf.shape)) if leaf.shape else 1
        slices.append((off, size, leaf))
        off += size
    return treedef, tuple(slices), off


def build_sparq(cfg, mesh, dcfg: DistSparqConfig
                ) -> Tuple[Callable, Callable, State, Any]:
    """Build the distributed engine for one model/mesh/runtime combination.

    Returns ``(init_fn, train_step, state_specs, pshape)``:

    * ``init_fn(key) -> state`` — flat node-stacked train state: ``params``
      and ``x_hat`` are ``(n, D_pad)`` buffers (identical x^0 on every node,
      x_hat = 0, per paper initialization; ``train_step.unravel`` recovers
      one row's model pytree);
    * ``train_step(state, batch) -> (state, metrics)`` — one Algorithm 1 step;
      ``batch`` leaves are ``(n, per_node, ...)`` where ``n`` is the ensemble
      size — ``cfg.n_nodes`` stretched to the smallest common multiple of the
      mesh node axis (== ``cfg.n_nodes`` whenever the node axis divides it;
      exposed as ``init_fn.n_nodes`` / ``train_step.n_nodes``);
    * ``state_specs`` — PartitionSpec tree mirroring ``state`` (pair it with
      ``sharding.train_batch_specs`` for the batch);
    * ``pshape`` — un-stacked single-node parameter ShapeDtypeStruct tree.
    """
    node_ax = dict(mesh.shape).get("node", 1)
    # ensemble size: cfg.n_nodes stretched to stay divisible by the mesh node
    # axis (pod-folded meshes can carry more rows than cfg.n_nodes)
    n = cfg.n_nodes * node_ax // math.gcd(cfg.n_nodes, node_ax)
    plan = dcfg.resolved_plan(n)
    R = plan.R
    Ws = jnp.asarray(plan.ws, jnp.float32)          # (R, n, n) support
    degs = jnp.asarray(plan.degrees, jnp.float32)   # (R, n) active degrees
    comp = dcfg.resolved_compressor()
    comp_eff = dcfg.effective_compressor()
    opt = dcfg.resolved_optimizer()
    H = int(dcfg.H)
    mbs = int(dcfg.microbatches)
    xhat_dt = jnp.dtype(dcfg.xhat_dtype)
    # resolved ONCE at build time (env/backend — repro.kernels), then passed
    # down as a concrete static arg so the trace-cache key stays stable
    lowering = kernels_mod.resolve_lowering()
    k_b = (comp_eff._k_b() if isinstance(comp_eff, BlockTopFrac)
           else max(1, min(BLOCK, int(math.ceil(dcfg.frac * BLOCK)))))
    if dcfg.variant not in ("dense", "ring", "shift"):
        raise ValueError(f"unknown variant {dcfg.variant!r}")
    flt = resolve_faults(dcfg.faults)
    if flt is not None:
        flt.validate_for(n)
    # circulant lowering: static circulant graphs decompose W x - x into
    # per-shift jnp.roll terms (collective-permutes along `node`); anything
    # else — time-varying plans, irregular graphs, n <= 2, or an active
    # fault plan (the repaired per-round W is not circulant) — runs dense
    shift_row = (circulant_row(plan.ws[0])
                 if dcfg.variant in ("ring", "shift") and R == 1 and n > 2
                 and flt is None
                 else None)
    shift_terms = ([(s, float(shift_row[s])) for s in range(1, n)
                    if shift_row[s] > 0.0]
                   if shift_row is not None else None)
    # Domain-tag the compressor stream with the reserved COMPRESS_STREAM
    # fold (core/faults.py owns the stream namespace): a raw PRNGKey(seed)
    # folded directly with t would collide with a same-seed FaultPlan's
    # fold_in(PRNGKey(seed), stream in {0, 1}) draws whenever t is small.
    base_key = jax.random.fold_in(jax.random.PRNGKey(dcfg.seed),
                                  COMPRESS_STREAM)

    pshape = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    # ------------------------------------------------------- flat ravel plan
    # the model pytree is raveled ONCE into a contiguous (n, D_pad) f32
    # buffer; D_pad pads D up to whole kernel tiles so the fused sync is one
    # aligned dispatch with no per-call copy. The [D:D_pad) tail is zero at
    # init and STAYS zero: the loss never reads it (zero gradient), exact-k
    # compression never selects zero lanes, and mixing is linear.
    treedef, slices, D = _flatten_spec(pshape)
    d_model_total = D
    D_pad = max(1, -(-D // BLOCK)) * BLOCK
    # The step sees each node's row as (D_pad // 128, 128). XLA tiles a
    # (1, D_pad) row T(1,128), which fills one of the 8 sublanes of each
    # vector register; an (8, 128) tile of the view holds the same 1024
    # consecutive elements, so the reshape either way is a bitcast. unravel
    # cuts each leaf out of the 128-lane rows that cover it, so a leaf that
    # starts and ends on a row (every leaf of the benchmark's models) is a
    # slice of the view, and its gradient is written into the view too.
    view_rows = D_pad // LANES

    def view(x):
        return x.reshape(x.shape[0], view_rows, LANES)

    def flat(x):
        return x.reshape(x.shape[0], D_pad)

    def unravel(row: jax.Array):
        """One node row of D_pad elements (flat or in view) -> model pytree
        (static slices of the rows that cover each leaf)."""
        with jax.named_scope("sparq_unravel"):
            rows = row.reshape(view_rows, LANES)
            leaves = []
            for off, size, leaf in slices:
                lo, hi = off // LANES, -(-(off + size) // LANES)
                part = rows[lo:hi].reshape(-1)[off - lo * LANES:][:size]
                leaves.append(part.reshape(leaf.shape).astype(leaf.dtype))
            return jax.tree.unflatten(treedef, leaves)

    def ravel(tree) -> jax.Array:
        """Model pytree -> (D,) f32 flat vector (leaf order of pshape)."""
        return jnp.concatenate([
            leaf.reshape(-1).astype(jnp.float32)
            for leaf in jax.tree.leaves(tree)]) if slices else \
            jnp.zeros((0,), jnp.float32)

    gamma = dcfg.resolved_gamma(plan, d_model_total)
    # per-node-per-sync payload: what the effective flat-vector operator
    # actually sends at the TRUE model dimension D (padding is silent —
    # zero lanes are never selected, so they cost no bits)
    payload = float(comp_eff.bits(d_model_total))

    # ------------------------------------------------------- partition specs
    scalar = jax.sharding.PartitionSpec()
    # Rows shard over the node axis only. The raveled column dim interleaves
    # every leaf's bytes, so a model/fsdp column sharding has no layout
    # meaning — GSPMD would emit a model-axis all-to-all/collective-permute
    # per unravel slice (the exact traffic P2 rejects as unexplained).
    row_spec = jax.sharding.PartitionSpec("node")
    opt_shape = jax.eval_shape(
        opt.init, jax.ShapeDtypeStruct((n, D_pad), jnp.float32))
    opt_specs = jax.tree.map(
        lambda l: row_spec if l.shape == (n, D_pad) else scalar, opt_shape)
    state_specs: State = {
        "params": row_spec, "x_hat": row_spec, "opt": opt_specs,
        "t": scalar, "bits": scalar, "bits_c": scalar,
        "sync_rounds": scalar, "triggers": scalar,
    }

    def init_fn(key) -> State:
        p0 = init_params(cfg, key)
        flat0 = jnp.pad(ravel(p0), (0, D_pad - D))
        params = jnp.tile(flat0[None], (n, 1))      # identical x^0 per node
        bits0, bits_c0 = bits_mod.acc_init()
        return {
            "params": params,
            "x_hat": jnp.zeros((n, D_pad), xhat_dt),
            "opt": opt.init(params),
            "t": jnp.int32(0), "bits": bits0, "bits_c": bits_c0,
            "sync_rounds": jnp.int32(0), "triggers": jnp.int32(0),
        }

    def rows_map(f, tree):
        """``f`` on each node-stacked row of an optimizer state (its other
        leaves, such as AdamW's step count, pass through)."""
        return jax.tree.map(lambda l: f(l) if l.ndim > 1 else l, tree)

    def local_rows_compress(rows):
        return kernel_ops.sign_topk_ensemble(rows, k_b, lowering=lowering)

    # Mosaic kernels are not partitioned by GSPMD, so the kernel is mapped
    # over the node axis: each device compresses the node rows it holds
    kernel_rows = jax.shard_map(local_rows_compress, mesh=mesh,
                                in_specs=row_spec, out_specs=row_spec,
                                check_vma=False)

    # the dropless expert layer's counters, per node, over microbatches:
    # routings into held experts add up, the load ratio takes the worst
    moe_stats = ({"moe_rows": jnp.add, "moe_load_max": jnp.maximum}
                 if cfg.n_experts and cfg.dropless else {})

    def loss_fn(row, b):
        loss, m = lm_loss(cfg, unravel(row), b)
        if not moe_stats:
            return loss
        return loss, {k: m[k] for k in moe_stats}

    def node_losses_grads(params, batch):
        """-> per node (losses, grads, counters)."""
        vg = jax.vmap(jax.value_and_grad(loss_fn, has_aux=bool(moe_stats)))

        def one(b):
            out, g = vg(params, b)
            return (*out, g) if moe_stats else (out, {}, g)

        if mbs == 1:
            losses, stats, grads = one(batch)
            return losses, grads, stats

        def split(x):
            nn, per = x.shape[:2]
            return jnp.moveaxis(
                x.reshape((nn, mbs, per // mbs) + x.shape[2:]), 1, 0)

        def body(carry, bmb):
            l_acc, g_acc, s_acc = carry
            li, si, gi = one(bmb)
            s_new = {k: f(s_acc[k], si[k]) for k, f in moe_stats.items()}
            return (l_acc + li, g_acc + gi, s_new), None

        zeros = (jnp.zeros((n,), jnp.float32), jnp.zeros_like(params),
                 {k: jnp.zeros((n,), jnp.float32) for k in moe_stats})
        (l_tot, g_tot, stats), _ = jax.lax.scan(body, zeros,
                                                jax.tree.map(split, batch))
        return l_tot / mbs, g_tot / mbs, stats

    def mix_term(xh, W_r):
        """Consensus term (W_r x_hat - x_hat) over the leading node axis."""
        x = xh.astype(jnp.float32)
        if shift_terms is not None:
            # circulant decomposition: (W x)_i = sum_s c_s x_{(i+s) mod n},
            # so W x - x = (c_0 - 1) x + sum_{s>0, c_s>0} c_s roll(x, -s)
            acc = (float(shift_row[0]) - 1.0) * x
            for s, c_s in shift_terms:
                acc = acc + c_s * jnp.roll(x, -s, axis=0)
            return acc
        return gossip_mix(W_r, x)

    def train_step(state: State, batch) -> Tuple[State, Dict[str, jax.Array]]:
        lead = {leaf.shape[0] for leaf in jax.tree.leaves(batch)}
        if lead != {n}:
            raise ValueError(
                f"batch leading dims {sorted(lead)} != ensemble size {n} "
                f"(cfg.n_nodes={cfg.n_nodes} stretched over a node axis of "
                f"{node_ax}; see build_sparq.__doc__)")
        # Each layer of the step runs under a named scope, which the HLO
        # carries as op_name metadata and the profiler trace as each op's
        # tf_op: sparq_grad (with sparq_unravel inside), sparq_optimizer,
        # and in the sync branch sparq_trigger, sparq_compress, sparq_mix.
        # No scope may name sign_topk or pallas (analysis/comm_lint.py's
        # interpret markers would hide a gossip collective under it).
        # The rows are (n, D_pad) in the state and (n, D_pad // 128, 128)
        # in the step. The barriers where the step takes the view and where
        # it gives the rows back keep XLA from moving those reshapes across
        # the element-wise passes (which would run them on the T(1,128)
        # row) or into the cond's branches (which costs a copy of each row).
        params, x_hat, opt_state = jax.lax.optimization_barrier(
            (view(state["params"]), view(state["x_hat"]),
             rows_map(view, state["opt"])))
        with jax.named_scope("sparq_grad"):
            losses, grads, stats = node_losses_grads(params, batch)
            loss = jnp.mean(losses)
        eta = dcfg.lr(state["t"]).astype(jnp.float32)
        with jax.named_scope("sparq_optimizer"):
            # local update through the shared optimizer seam (optim/sgd.py):
            # plain SGD by default, heavyball/Nesterov for SQuARM-SGD
            x_half, opt_new = opt.update(grads, opt_state, params, eta)
            if flt is not None:
                # stragglers / offline nodes skip this local step: iterate
                # AND optimizer buffers freeze (same step_mask stream as the
                # reference engine — core/faults.py determinism contract)
                act = flt.step_mask(state["t"], n)               # (n,) bool
                x_half = flt.gate_update(act, x_half, params)
                opt_new = flt.gate_update(act, opt_new, opt_state)

        def sync_branch(op):
            xh, xe = op               # (n, D_pad // 128, 128) f32 / xhat_dt
            # active round's graph: static plans bind W_0 so the lowered
            # program is identical to the fixed-topology days
            if R == 1:
                W_r, deg_r = Ws[0], degs[0]
            else:
                r = jax.lax.rem(state["sync_rounds"], jnp.int32(R))
                W_r, deg_r = Ws[r], degs[r]
            with jax.named_scope("sparq_trigger"):
                c_t = dcfg.threshold(state["t"])
                diff = xh.astype(jnp.float32) - xe.astype(jnp.float32)
                trig = trigger_mask(jnp.sum(diff * diff, axis=(1, 2)), c_t,
                                    eta)
                if flt is not None:
                    # faulty round: repaired W over the surviving links,
                    # offline nodes muted, bits charged for live links only
                    W_r, deg_r, live = flt.apply(W_r, state["t"],
                                                 state["sync_rounds"])
                    trig = trig & live
                trigf = trig.astype(jnp.float32)

            with jax.named_scope("sparq_compress"):
                if dcfg.use_kernel:
                    # ONE fused blockwise dispatch per device over its node
                    # rows (kernels/ops.py; == vmapping BlockTopFrac
                    # row-by-row). Trigger gating happens below: q is linear
                    # in the 0/1 gate.
                    q = view(kernel_rows(flat(diff)))
                else:
                    # generic registry operator over the TRUE flat vector
                    # (n, D) rows — one global operator application per
                    # node, matching the reference engine's (n, d) semantics
                    # exactly; per-node keys folded from the step counter
                    # (deterministic operators ignore them)
                    kc = jax.random.fold_in(base_key, state["t"])
                    q_d = jax.vmap(lambda v, k: comp(v, k))(
                        flat(diff)[:, :D], jax.random.split(kc, n))
                    q = view(jnp.pad(q_d, ((0, 0), (0, D_pad - D))))
                q = q * trigf[:, None, None]                     # line 11
            with jax.named_scope("sparq_mix"):
                xe_new = (xe.astype(jnp.float32) + q).astype(xhat_dt)  # 13
                x_new = xh + gamma * mix_term(xe_new, W_r)       # line 15
                new_bits, new_c = bits_mod.acc_add(
                    state["bits"], state["bits_c"],
                    sync_message_bits(trig, deg_r, payload))
                rounds = state["sync_rounds"] + 1
                triggers = state["triggers"] + jnp.sum(trig).astype(jnp.int32)
            return x_new, xe_new, new_bits, new_c, rounds, triggers

        def local_branch(op):
            xh, xe = op
            return (xh, xe, state["bits"], state["bits_c"],
                    state["sync_rounds"], state["triggers"])

        do_sync = ((state["t"] + 1) % H) == 0
        x_new, xe_new, bits, bits_c, rounds, trigs = jax.lax.cond(
            do_sync, sync_branch, local_branch, (x_half, x_hat))
        x_new, xe_new, opt_new = jax.lax.optimization_barrier(
            (x_new, xe_new, opt_new))
        new_state = {"params": flat(x_new), "x_hat": flat(xe_new),
                     "opt": rows_map(flat, opt_new),
                     "t": state["t"] + 1, "bits": bits, "bits_c": bits_c,
                     "sync_rounds": rounds, "triggers": trigs}
        metrics = {"loss": loss, "eta": eta,
                   "bits": bits.astype(jnp.float32),
                   "sync_rounds": rounds.astype(jnp.float32),
                   "triggers": trigs.astype(jnp.float32)}
        if moe_stats:
            # a node's routings into its held experts (the node mean), and
            # the worst node's busiest held expert over the held mean
            metrics["moe_rows"] = jnp.mean(stats["moe_rows"])
            metrics["moe_load_max"] = jnp.max(stats["moe_load_max"])
        return new_state, metrics

    # static-audit metadata (repro.analysis R5/K2): whether the kernel path
    # was requested and which lowering the kernels resolve to on this backend
    init_fn.use_kernel = train_step.use_kernel = bool(dcfg.use_kernel)
    init_fn.lowering = train_step.lowering = str(lowering)
    init_fn.interpret = train_step.interpret = (lowering == "interpret")
    init_fn.n_nodes = train_step.n_nodes = n
    # the ACTUALLY-running plan, for callers that want to log/inspect it
    # without re-resolving (sampled plans are seed-deterministic, but the
    # engine's own object is the source of truth)
    init_fn.plan = train_step.plan = plan
    # communication-model metadata the static bit-accounting oracle
    # (repro.analysis R10/R11) cross-checks: the per-node-per-sync payload
    # this engine charges and the true model dimension behind gamma*
    init_fn.payload_bits = train_step.payload_bits = float(payload)
    init_fn.d_model_total = train_step.d_model_total = int(d_model_total)
    init_fn.d_pad = train_step.d_pad = int(D_pad)
    init_fn.gamma = train_step.gamma = float(gamma)
    # flat-buffer accessors: one node row <-> the model pytree
    init_fn.unravel = train_step.unravel = unravel
    init_fn.ravel = train_step.ravel = ravel
    return init_fn, train_step, state_specs, pshape
