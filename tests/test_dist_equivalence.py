"""The dist engine must match the core/sparq.py reference leaf-for-leaf.

Same communication plan (static ring/expander/torus or a time-varying
matchings plan), same compressor (GLOBAL flat-buffer TopFrac, or the
blockwise BlockTopFrac registry operator on the kernel path), same trigger
schedule, same LR/gamma/H, same per-node batches: the flat-buffer engine
(dist/sparq_dist.py, params raveled once into one (n, D_pad) buffer) and the
dense (n, d) matrix engine (core/sparq.py over the same ravelled vector)
must produce the same parameters, trigger counts and bit totals within
float tolerance. The deliberate global-vs-per-tensor top-k semantic change
of the flat-buffer path is pinned separately below."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.configs.registry import get_config
from repro.core.compression import (BlockTopFrac, TopFrac, compress_tree,
                                    tree_payload_bits)
from repro.core.faults import DropoutWindow, FaultPlan
from repro.core.schedule import fixed
from repro.core.sparq import SparqConfig, gossip_mix, init_state, make_step
from repro.core.topology import GossipPlan, circulant_row, make_topology
from repro.core.triggers import constant, zero
from repro.dist import sharding as sh
from repro.dist.sparq_dist import DistSparqConfig, build_sparq
from repro.launch.mesh import make_mesh
from repro.models.transformer import init_params, lm_loss

N = 4   # decentralized nodes (replicated on this 1-device mesh)
T = 5   # steps


def _setup():
    cfg = dataclasses.replace(
        get_config("qwen1.5-0.5b").reduced(n_layers=1, d_model=128, vocab=256),
        n_nodes=N)
    prod = make_mesh((1, 1), ("data", "model"))
    mesh = sh.train_mesh(prod, cfg)
    rng = np.random.default_rng(0)
    batch = {k: jnp.asarray(
        rng.integers(0, cfg.vocab_size, (N, 2, 16)).astype(np.int32))
        for k in ("tokens", "labels")}
    return cfg, mesh, batch


def _run_both(cfg, mesh, batch, threshold, H, beta, dist_kw, ref_kw):
    """Run T steps on both engines with identical knobs; return
    (dist_state, ref_state, dist_flat_params)."""
    frac, gamma, lr = 0.25, 0.3, fixed(0.05)

    dcfg = DistSparqConfig(H=H, variant="dense", frac=frac,
                           threshold=threshold, lr=lr, gamma=gamma,
                           momentum=beta, **dist_kw)
    init_fn, train_step, _, pshape = build_sparq(cfg, mesh, dcfg)
    state = init_fn(jax.random.PRNGKey(0))
    step = jax.jit(train_step)
    for _ in range(T):
        state, _ = step(state, batch)

    # reference (n, d) engine over the ravelled pytree, same inputs; the
    # SAME registry operator the dist engine resolves (global TopFrac on
    # the flat vector; BlockTopFrac on the kernel path)
    p0 = init_params(cfg, jax.random.PRNGKey(0))
    x0, unravel = ravel_pytree(p0)
    comp = dcfg.effective_compressor()

    def grad_fn(x_nd, t, key):
        def g1(xv, tok, lab):
            g = jax.grad(lambda p: lm_loss(
                cfg, p, {"tokens": tok, "labels": lab})[0])(unravel(xv))
            return ravel_pytree(g)[0]
        return jax.vmap(g1)(x_nd, batch["tokens"], batch["labels"])

    rcfg = SparqConfig(compressor=comp, threshold=threshold, lr=lr, H=H,
                       gamma=gamma, momentum=beta, **ref_kw)
    rstep = jax.jit(make_step(rcfg, grad_fn))
    rstate = init_state(x0, N, rcfg.resolved_optimizer())
    for t in range(T):
        rstate = rstep(rstate, jax.random.PRNGKey(t))

    dist_flat = state["params"][:, :x0.size]   # drop the zero padded tail
    return state, rstate, dist_flat


def _assert_equal(state, rstate, dist_flat):
    np.testing.assert_allclose(np.asarray(dist_flat), np.asarray(rstate.x),
                               atol=5e-4, rtol=0)
    assert int(state["triggers"]) == int(rstate.triggers)
    assert int(state["sync_rounds"]) == int(rstate.sync_rounds)
    np.testing.assert_allclose(float(state["bits"]), float(rstate.bits),
                               rtol=1e-6)


@pytest.mark.parametrize("threshold,H,beta",
                         [(zero(), 2, 0.0), (constant(1e12), 3, 0.0),
                          (zero(), 2, 0.9)],
                         ids=["always-trigger", "never-trigger",
                              "momentum-0.9"])
def test_dist_engine_matches_reference(threshold, H, beta):
    """beta > 0 pins the SQuARM momentum runtime: both engines resolve the
    same optim.momentum update through the shared optimizer seam."""
    cfg, mesh, batch = _setup()
    _assert_equal(*_run_both(cfg, mesh, batch, threshold, H, beta,
                             {}, {"topology": make_topology("ring", N)}))


@pytest.mark.parametrize("which", ["expander", "torus2d", "matchings"])
def test_dist_engine_matches_reference_plans(which):
    """The pluggable communication layer: dist == reference leaf-for-leaf on
    non-ring static graphs (expander, torus) and on a time-varying plan
    (random matchings, W_r looked up by sync round inside both engines,
    per-round deg_r bit accounting included via the bits pin)."""
    cfg, mesh, batch = _setup()
    if which == "matchings":
        plan = GossipPlan.matchings(N, rounds=3, seed=2)
        assert plan.R == 3
        dist_kw, ref_kw = {"plan": plan}, {"plan": plan}
    else:
        topo = make_topology(which, N, deg=2, seed=1)
        dist_kw, ref_kw = {"topology": topo}, {"topology": topo}
    _assert_equal(*_run_both(cfg, mesh, batch, zero(), 2, 0.0,
                             dist_kw, ref_kw))


@pytest.mark.parametrize("beta", [0.0, 0.9], ids=["sgd", "momentum-0.9"])
def test_dist_engine_matches_reference_under_faults(beta):
    """The fault-runtime acceptance pin: dist == reference leaf-for-leaf
    under an IDENTICAL injected fault stream — 30% link drop, one straggler
    skipping half its local steps, and a dropout window that takes node 2
    offline across a sync round. Both engines derive every fault mask as a
    pure function of (seed, t, sync_round), so triggers, live-link bit
    totals and the repaired mixing all agree exactly; beta=0.9 additionally
    pins the frozen-momentum-buffer gating through the optimizer seam."""
    cfg, mesh, batch = _setup()
    fp = FaultPlan(link_drop=0.3, stragglers=(1,), straggler_frac=0.5,
                   dropout=(DropoutWindow(2, 1, 3),), seed=5)
    topo = make_topology("ring", N)
    _assert_equal(*_run_both(cfg, mesh, batch, zero(), 2, beta,
                             {"topology": topo, "faults": fp},
                             {"topology": topo, "faults": fp}))


def test_dist_faults_charge_only_live_links():
    """A dropout window covering every node leaves zero live links, so the
    dist engine charges zero bits over the whole run; a partial link-drop
    run charges strictly fewer bits than the clean run."""
    cfg, mesh, batch = _setup()
    all_down = FaultPlan(
        dropout=tuple(DropoutWindow(i, 0, 1000) for i in range(N)), seed=3)
    totals = {}
    for name, fp in (("clean", None),
                     ("drop", FaultPlan(link_drop=0.4, seed=3)),
                     ("all_down", all_down)):
        dcfg = DistSparqConfig(H=2, variant="dense", frac=0.25,
                               threshold=zero(), lr=fixed(0.05), gamma=0.3,
                               faults=fp)
        init_fn, train_step, _, _ = build_sparq(cfg, mesh, dcfg)
        state = init_fn(jax.random.PRNGKey(0))
        step = jax.jit(train_step)
        for _ in range(T):
            state, _ = step(state, batch)
        totals[name] = float(state["bits"])
        if name == "all_down":
            # every node offline: triggers forced off, nothing ever sent
            assert int(state["triggers"]) == 0
    assert 0 < totals["drop"] < totals["clean"]
    assert totals["all_down"] == 0.0


def test_dist_kind_string_matches_explicit_topology():
    """DistSparqConfig accepts the graph as a kind string and builds it at
    the mesh-resolved ensemble size — identical to passing the Topology."""
    cfg, mesh, batch = _setup()
    s1, r1, f1 = _run_both(cfg, mesh, batch, zero(), 2, 0.0,
                           {"topology": "torus2d"},
                           {"topology": make_topology("torus2d", N)})
    _assert_equal(s1, r1, f1)


def test_circulant_shift_lowering_matches_dense():
    """variant="shift" decomposes a static circulant W into jnp.roll terms
    (collective-permutes on a real mesh). One mix application must agree
    with the dense tensordot to float32 ULP (the sum orders differ per row,
    so exact bitwise equality is not defined), and a full run must keep the
    integer channels (bits, triggers, sync rounds) exactly equal."""
    for kind, n in (("ring", 8), ("complete", 6)):
        topo = make_topology(kind, n)
        row = circulant_row(topo.w)
        assert row is not None
        W = jnp.asarray(topo.w, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(0), (n, 33), jnp.float32)
        shifted = (float(row[0]) - 1.0) * x
        for s in range(1, n):
            if row[s] > 0:
                shifted = shifted + float(row[s]) * jnp.roll(x, -s, axis=0)
        np.testing.assert_allclose(np.asarray(shifted),
                                   np.asarray(gossip_mix(W, x)),
                                   atol=1e-6, rtol=0)
    # non-circulant graphs must report None (the engine then runs dense)
    assert circulant_row(make_topology("expander", 8, deg=3, seed=1).w) is None

    cfg, mesh, batch = _setup()
    out = {}
    for variant in ("shift", "dense"):
        dcfg = DistSparqConfig(H=2, variant=variant, frac=0.25,
                               threshold=zero(), lr=fixed(0.05), gamma=0.3)
        init_fn, train_step, _, _ = build_sparq(cfg, mesh, dcfg)
        state = init_fn(jax.random.PRNGKey(0))
        step = jax.jit(train_step)
        for _ in range(T):
            state, _ = step(state, batch)
        out[variant] = state
    a, b = out["shift"], out["dense"]
    for la, lb in zip(jax.tree.leaves(a["params"]),
                      jax.tree.leaves(b["params"]), strict=True):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=5e-3,
                                   rtol=0)
    assert int(a["triggers"]) == int(b["triggers"])
    assert int(a["sync_rounds"]) == int(b["sync_rounds"])
    assert float(a["bits"]) == float(b["bits"])


def test_trigger_prunes_dist_communication():
    """A huge threshold keeps the dist engine on flag-only bits."""
    cfg, mesh, batch = _setup()
    out = {}
    for name, thr in (("on", constant(1e12)), ("off", zero())):
        dcfg = DistSparqConfig(H=2, variant="dense", frac=0.1, threshold=thr,
                               lr=fixed(0.05), gamma=0.3)
        init_fn, train_step, _, _ = build_sparq(cfg, mesh, dcfg)
        state = init_fn(jax.random.PRNGKey(0))
        step = jax.jit(train_step)
        for _ in range(4):
            state, m = step(state, batch)
        out[name] = (float(m["bits"]), float(m["triggers"]))
    assert out["on"][0] < out["off"][0]
    assert out["on"][1] == 0 and out["off"][1] > 0
    # two sync rounds of flag-only messages: n nodes * deg 2 * 1 bit each
    assert out["on"][0] == pytest.approx(2 * N * 2 * 1.0)


@pytest.mark.parametrize("threshold,beta",
                         [(zero(), 0.0), (zero(), 0.9),
                          (constant(1e12), 0.0)],
                         ids=["always-trigger", "momentum-0.9",
                              "never-trigger"])
def test_dist_kernel_path_matches_reference(threshold, beta):
    """use_kernel=True: ONE fused blockwise dispatch over the whole (n, D_pad)
    ensemble per sync must equal the reference engine running the registry
    ``signtopk_block`` operator on the same flat vectors — params, triggers,
    sync rounds AND charged bits (the blockwise payload formula)."""
    cfg, mesh, batch = _setup()
    _assert_equal(*_run_both(cfg, mesh, batch, threshold, 2, beta,
                             {"use_kernel": True},
                             {"topology": make_topology("ring", N)}))


def test_flat_global_selection_differs_from_per_tensor():
    """The flat-buffer engine deliberately selects top-frac GLOBALLY over the
    raveled buffer, not per tensor (the pre-flat dist engine's semantics).
    Pin the divergence on a two-leaf tree with wildly different leaf scales:
    global selection spends the whole budget on the large leaf, per-tensor
    selection reserves support in the small one — and the payload formulas
    differ too. This is the documented semantic change of the refactor, not
    an accident to be 'fixed'."""
    tree = {"big": jnp.full((64,), 100.0), "small": jnp.full((32,), 0.01)}
    flat, _ = ravel_pytree(tree)
    comp = TopFrac(frac=0.25)
    q_global = comp(flat, jax.random.PRNGKey(0))
    q_per = ravel_pytree(compress_tree(comp, tree, jax.random.PRNGKey(0)))[0]
    # ravel_pytree orders dict keys alphabetically: big then small
    small_slice = slice(64, 96)
    assert int(jnp.sum(q_global[small_slice] != 0)) == 0
    assert int(jnp.sum(q_per[small_slice] != 0)) == 8   # ceil(.25 * 32)
    assert not np.array_equal(np.asarray(q_global), np.asarray(q_per))
    # payload formulas differ too (leaf sizes chosen so the per-leaf index
    # widths differ from the global one: 64 = 40 + 24)
    pshape = {"a": jax.ShapeDtypeStruct((40,), jnp.float32),
              "b": jax.ShapeDtypeStruct((24,), jnp.float32)}
    assert float(comp.bits(64)) != float(tree_payload_bits(comp, pshape))


def test_dist_padded_tail_stays_zero():
    """The flat buffer's padding lanes [D, D_pad) must stay exactly zero in
    params and x_hat through real training steps — the loss never reads
    them, the exact-k kernel never selects them, and the mixing is linear."""
    cfg, mesh, batch = _setup()
    for use_kernel in (False, True):
        dcfg = DistSparqConfig(H=2, variant="dense", frac=0.25,
                               threshold=zero(), lr=fixed(0.05), gamma=0.3,
                               use_kernel=use_kernel)
        init_fn, train_step, _, pshape = build_sparq(cfg, mesh, dcfg)
        D = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(pshape))
        assert train_step.d_pad >= D and train_step.d_pad % 1024 == 0
        state = init_fn(jax.random.PRNGKey(0))
        step = jax.jit(train_step)
        for _ in range(T):
            state, _ = step(state, batch)
        assert not np.any(np.asarray(state["params"][:, D:]))
        assert not np.any(np.asarray(state["x_hat"][:, D:]))


def _flat_unravel(pshape, row):
    """A flat row -> the model pytree, by static slices of the row."""
    leaves, treedef = jax.tree.flatten(pshape)
    offs = np.cumsum([0] + [int(np.prod(l.shape)) for l in leaves])
    return jax.tree.unflatten(treedef, [
        row[o:o + l.size].reshape(l.shape).astype(l.dtype)
        for o, l in zip(offs, leaves)])


def _flat_step(cfg, dcfg, train_step, pshape, state, batch):
    """One SPARQ step written out plainly on the stored (n, D_pad) rows:
    the reference the engine's row view must match bit for bit."""
    from repro.core import bits as bits_mod
    from repro.core.faults import COMPRESS_STREAM, resolve_faults
    from repro.core.sparq import sync_message_bits, trigger_mask
    from repro.kernels.ops import sign_topk_ensemble
    n, D, D_pad = train_step.n_nodes, train_step.d_model_total, \
        train_step.d_pad
    opt, flt, t = dcfg.resolved_optimizer(), resolve_faults(dcfg.faults), \
        state["t"]
    _, grads = jax.vmap(jax.value_and_grad(
        lambda row, b: lm_loss(cfg, _flat_unravel(pshape, row), b)[0]))(
            state["params"], batch)
    eta = dcfg.lr(t).astype(jnp.float32)
    x_half, opt_new = opt.update(grads, state["opt"], state["params"], eta)
    if flt is not None:
        act = flt.step_mask(t, n)
        x_half = flt.gate_update(act, x_half, state["params"])
        opt_new = flt.gate_update(act, opt_new, state["opt"])
    plan = train_step.plan

    def sync(x_half):
        r = state["sync_rounds"] % plan.R
        W = jnp.asarray(plan.ws, jnp.float32)[r]
        deg = jnp.asarray(plan.degrees, jnp.float32)[r]
        diff = x_half - state["x_hat"]
        trig = trigger_mask(jnp.sum(diff * diff, axis=1), dcfg.threshold(t),
                            eta)
        if flt is not None:
            W, deg, live = flt.apply(W, t, state["sync_rounds"])
            trig = trig & live
        if dcfg.use_kernel:
            q = sign_topk_ensemble(diff, dcfg.effective_compressor()._k_b(),
                                   lowering=train_step.lowering)
        else:
            kc = jax.random.fold_in(jax.random.fold_in(
                jax.random.PRNGKey(dcfg.seed), COMPRESS_STREAM), t)
            q = jnp.pad(jax.vmap(dcfg.resolved_compressor())(
                diff[:, :D], jax.random.split(kc, n)),
                ((0, 0), (0, D_pad - D)))
        x_hat = state["x_hat"] + q * trig.astype(jnp.float32)[:, None]
        bits = bits_mod.acc_add(
            state["bits"], state["bits_c"],
            sync_message_bits(trig, deg, train_step.payload_bits))
        return (x_half + train_step.gamma * gossip_mix(W, x_hat), x_hat,
                *bits, state["sync_rounds"] + 1,
                state["triggers"] + jnp.sum(trig).astype(jnp.int32))

    def local(x_half):
        return (x_half, state["x_hat"], state["bits"], state["bits_c"],
                state["sync_rounds"], state["triggers"])

    keys = ("params", "x_hat", "bits", "bits_c", "sync_rounds", "triggers")
    out = jax.lax.cond((t + 1) % dcfg.H == 0, sync, local, x_half)
    return dict(state, **dict(zip(keys, out)), opt=opt_new, t=t + 1)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["generic", "kernel"])
@pytest.mark.parametrize("faults", [False, True], ids=["clean", "faults"])
@pytest.mark.parametrize("beta", [0.0, 0.9], ids=["sgd", "momentum"])
@pytest.mark.parametrize("n", [1, 4])
def test_row_view_step_bit_equal_to_the_flat_step(monkeypatch, n, beta,
                                                  faults, use_kernel):
    """The step runs its row passes, and builds each node's gradient, on a
    (D_pad // 128, 128) view of the (n, D_pad) rows. Over 2 H steps (two
    syncs) params, x_hat and the optimizer rows stay bit-equal to the same
    step written out on the flat rows, the counters equal, the stored
    shapes (n, D_pad) and the padded tail zero; the kernel runs in the
    Pallas interpreter."""
    monkeypatch.setenv("REPRO_KERNEL_LOWERING", "interpret")
    cfg = dataclasses.replace(
        get_config("qwen1.5-0.5b").reduced(n_layers=1, d_model=128,
                                           vocab=256), n_nodes=n)
    mesh = sh.train_mesh(make_mesh((1, 1), ("data", "model")), cfg)
    fp = (FaultPlan(link_drop=0.3, stragglers=(n - 1,), straggler_frac=0.5,
                    dropout=(DropoutWindow(n // 2, 1, 3),), seed=5)
          if faults else None)
    H = 2
    dcfg = DistSparqConfig(H=H, variant="dense", frac=0.25, threshold=zero(),
                           lr=fixed(0.05), gamma=0.3, momentum=beta,
                           use_kernel=use_kernel, faults=fp)
    init_fn, train_step, _, pshape = build_sparq(cfg, mesh, dcfg)
    assert train_step.lowering == "interpret"
    rng = np.random.default_rng(n)
    batch = {k: jnp.asarray(
        rng.integers(0, cfg.vocab_size, (n, 2, 16)).astype(np.int32))
        for k in ("tokens", "labels")}
    state = ref = init_fn(jax.random.PRNGKey(0))
    step = jax.jit(train_step)
    ref_step = jax.jit(functools.partial(_flat_step, cfg, dcfg, train_step,
                                         pshape))
    for _ in range(2 * H):
        state, _ = step(state, batch)
        ref = ref_step(ref, batch)
    D, D_pad = train_step.d_model_total, train_step.d_pad
    rows = [("params", state["params"], ref["params"]),
            ("x_hat", state["x_hat"], ref["x_hat"])]
    rows += [("opt", a, b) for a, b in zip(jax.tree.leaves(state["opt"]),
                                           jax.tree.leaves(ref["opt"]),
                                           strict=True)]
    assert len(rows) == (3 if beta else 2)
    for name, got, want in rows:
        assert got.shape == (n, D_pad), name
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=name)
        assert not np.any(np.asarray(got[:, D:])), name
    for k in ("t", "sync_rounds", "triggers", "bits", "bits_c"):
        assert np.asarray(state[k]) == np.asarray(ref[k]), k
    assert int(state["sync_rounds"]) == 2
