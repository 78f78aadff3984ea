"""Plain reference of the SPARQ-SGD steps a cell runs (Algorithm 1 of
arXiv:1910.14280, the variant the program implements), one node at a time.

Per step t, on every node i:

    x_half_i = x_i - eta_t grad f_i(x_i)                       (plain SGD)

and every H steps (t + 1 a multiple of H) the sync:

    trig_i  = ||x_half_i - x_hat_i||^2 > c eta_t^2
    q_i     = trig_i SignTopK(x_half_i - x_hat_i)
    x_hat_i = x_hat_i + q_i
    x_i     = x_half_i + gamma (sum_j W_ij x_hat_j - x_hat_i)

else x_i = x_half_i. eta_t = b / (t + a), c is constant. SignTopK works on
the flat float32 row (every leaf raveled in the pytree's order, padded with
zeros to whole tiles of 1024): in each tile the k_b = ceil(frac 1024)
entries of largest magnitude (zeros never), each replaced by the tile's mean
selected magnitude times its sign. W is the uniform ring (self and each
neighbour 1 / (max degree + 1)). Bits follow the program's charge: every
node sends each neighbour a one-bit flag, plus, when it triggered, the
payload of sign bits, 10-bit in-tile indices and a 32-bit scale per tile.

Node i's arrays live on ``devices[i % len(devices)]``; x_hat_j is copied to
node i's device for the mix. Everything is float32 at the highest matmul
precision. It imports nothing of the program.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from reference.common import model_key

TILE = 1024
FLAG_BITS = 1.0
FAULTS = ("half_batch",)


def layout(init_params: Callable, cfg: dict) -> List[tuple]:
    """(name, offset, size) of every segment of the flat row: each leaf in
    the pytree's order, a leaf stacked over layers split into its layers."""
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    out, off = [], 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        size = int(math.prod(leaf.shape))
        if name.startswith("seg") and len(leaf.shape) > 1:
            per = size // leaf.shape[0]
            out += [(f"{name}[{i}]", off + i * per, per)
                    for i in range(leaf.shape[0])]
        else:
            out.append((name, off, size))
        off += size
    return out


def initial_row(model, cfg: dict, d_pad: int) -> Callable:
    """seed -> the flat float32 row of that seed's initial weights (every
    leaf raveled in the pytree's order, zero-padded to ``d_pad``)."""
    @jax.jit
    def row(key):
        flat = jnp.concatenate([v.reshape(-1) for v in jax.tree.leaves(
            model.init_params(cfg, key))])
        return jnp.pad(flat, (0, d_pad - flat.size))
    return lambda seed: row(model_key(seed))


def norm64(v: np.ndarray, chunk: int = 1 << 22) -> float:
    """Norm of a float32 vector on the host, its squares summed in float64
    (a float32 sum of 4.6e8 squares reads half a percent low)."""
    tot = 0.0
    for i in range(0, v.size, chunk):
        c = v[i:i + chunk].astype(np.float64)
        tot += float(np.dot(c, c))
    return math.sqrt(tot)


def seg_norms(flat, segs) -> np.ndarray:
    """Norm of each segment of a flat row on the host."""
    v = np.asarray(flat, np.float32)
    return np.array([norm64(v[o:o + s]) for _, o, s in segs])


def device_seg_norms(flat, segs):
    """Norm of each segment of a flat row, traced (static slices)."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(flat[o:o + s])))
                      for _, o, s in segs])


def ring_weights(n: int) -> np.ndarray:
    adj = np.zeros((n, n))
    if n > 1:
        for i in range(n):
            adj[i, (i + 1) % n] = adj[i, (i - 1) % n] = 1.0
    w = adj / (adj.sum(1).max() + 1.0)
    np.fill_diagonal(w, 1.0 - w.sum(1))
    return w


def payload_bits(d: int, frac: float) -> float:
    k_b = max(1, min(TILE, math.ceil(frac * TILE)))
    return -(-d // TILE) * (k_b + k_b * math.ceil(math.log2(TILE)) + 32.0)


def sign_topk(diff, k_b: int):
    """Tile-wise SignTopK of a flat row whose length is whole tiles."""
    t = diff.reshape(-1, TILE)
    av = jnp.abs(t)
    _, idx = jax.lax.top_k(av, k_b)
    rows = jnp.arange(t.shape[0])[:, None]
    sel = jnp.zeros(t.shape, bool).at[rows, idx].set(True) & (av > 0)
    cnt = jnp.sum(sel, axis=1, keepdims=True)
    scale = jnp.sum(jnp.where(sel, av, 0.0), 1, keepdims=True) / jnp.maximum(
        cnt, 1)
    q = jnp.where(sel, scale * jnp.where(t >= 0, 1.0, -1.0), 0.0)
    return q.reshape(diff.shape)


def run(model, cfg: dict, job: dict, traffic, seed: int, steps: int,
        devices: Sequence, prec: str = "f32",
        fault: Optional[str] = None) -> Dict[str, object]:
    """Follow the first ``steps`` steps from the seed. Returns the mean loss
    of each step; per node the segment norms of the first gradient and of
    the change x_steps - x_0, and the norm of x_hat after the last step;
    the counters."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    n, H = int(job["nodes"]), int(job["H"])
    lr_b, lr_a = (float(v) for v in job["lr"])
    c = np.float32(job["threshold"])
    if job.get("gamma") is None and n > 1:
        raise ValueError("a job of several nodes states its gamma")
    gamma = np.float32(job.get("gamma") or 0.0)   # one node mixes nothing
    k_b = max(1, min(TILE, math.ceil(float(job["frac"]) * TILE)))
    segs = layout(model.init_params, cfg)
    d = segs[-1][1] + segs[-1][2]
    d_pad = -(-d // TILE) * TILE
    w = ring_weights(n)
    deg = (w > 0).sum(1) - (np.diagonal(w) > 0)
    payload = payload_bits(d, float(job["frac"]))
    devs = [devices[i % len(devices)] for i in range(n)]
    treedef = jax.tree.structure(jax.eval_shape(
        lambda k: model.init_params(cfg, k), jax.random.PRNGKey(0)))
    shapes = [leaf.shape for leaf in jax.tree.leaves(jax.eval_shape(
        lambda k: model.init_params(cfg, k), jax.random.PRNGKey(0)))]

    def unravel(flat):
        leaves, off = [], 0
        for shp in shapes:
            size = int(math.prod(shp))
            leaves.append(flat[off:off + size].reshape(shp))
            off += size
        return jax.tree.unflatten(treedef, leaves)

    @jax.jit
    def local(x, batch, eta):
        def f(flat):
            return model.loss(cfg, unravel(flat[:d]), batch, prec)
        l, g = jax.value_and_grad(f)(x)
        return l, x - eta * g, device_seg_norms(g, segs)

    @jax.jit
    def change_norms(x, x0):
        return device_seg_norms(x - x0, segs)

    @jax.jit
    def compress(x_half, x_hat, eta):
        diff = x_half - x_hat
        trig = jnp.sum(diff * diff) > c * eta * eta
        return x_hat + trig.astype(jnp.float32) * sign_topk(diff, k_b), trig

    @jax.jit
    def mix(x_half, x_hat_i, ws, nbrs):
        acc = -x_hat_i
        for wj, xj in zip(ws, nbrs):
            acc = acc + wj * xj
        return x_half + gamma * acc

    x0_of = initial_row(model, cfg, d_pad)
    xs = [jax.device_put(x0_of(seed), dv) for dv in devs]
    x_hat = [jax.device_put(jnp.zeros((d_pad,), jnp.float32), dv)
             for dv in devs]
    losses, grads = [], []
    rounds = trigs = 0
    bits = 0.0
    for t in range(steps):
        eta = np.float32(lr_b) / (np.float32(t) + np.float32(lr_a))
        gb = traffic.global_batch(t)
        half, ls = [], []
        for i in range(n):
            rows = {k: v[i] for k, v in gb.items()}
            if fault == "half_batch":
                rows = {k: v[:max(1, v.shape[0] // 2)]
                        for k, v in rows.items()}
            rows = jax.device_put(rows, devs[i])
            l, xh, gn = local(xs[i], rows, eta)
            if t == 0:
                grads.append(np.asarray(gn))
            ls.append(float(l))
            half.append(xh)
        losses.append(float(np.mean(ls)))
        if (t + 1) % H:
            xs = half
            continue
        fired = []
        for i in range(n):
            x_hat[i], trig = compress(half[i], x_hat[i], eta)
            fired.append(bool(trig))
        rounds += 1
        trigs += sum(fired)
        bits += float(sum((FLAG_BITS + f * payload) * deg[i]
                          for i, f in enumerate(fired)))
        new = []
        for i in range(n):
            js = [j for j in range(n) if w[i, j] > 0]
            nbrs = [jax.device_put(x_hat[j], devs[i]) for j in js]
            new.append(mix(half[i], x_hat[i],
                           [np.float32(w[i, j]) for j in js], nbrs))
        xs = new
    change = []
    for x, dv in zip(xs, devs):
        x0 = jax.device_put(x0_of(seed), dv)
        change.append(np.asarray(change_norms(x, x0)))
        del x0
    xhat = [np.array([float(jnp.linalg.norm(v))]) for v in x_hat]
    return {"losses": losses, "grad": grads, "change": change, "xhat": xhat,
            "counters": {"sync_rounds": rounds, "triggers": trigs,
                         "bits": bits}}
