"""Plain reference of the sync FedAuto round, and the comparison that decides
``correct``.

The reference imports nothing of the program. It takes from a run only the
inputs the round drew: which clients connected, and for each local update the
rows it trains on and the key its minibatches come from. From the benchmark's
own weights it then follows the rounds itself:

* each participant's local update: E steps of minibatch SGD on the task's
  mean loss (``tasks/<name>.py``), minibatch rows ``randint(split(key,
  E)[s], (B,), 0, n)``, through the configuration's own forward (float32,
  highest matmul precision);
* the upload: lossless for ``fp32``, so the server holds the client's model;
* the FedAuto weights (paper Eq. 8-9): the server pinned to 1/(1+m), the rest
  on the simplex minimising the chi-square gap of the mixture of the task's
  histogram bins, by the solver the program states (400 FISTA steps from the
  uniform start), here in float64 on the host; the compensatory update's row
  is the task's ``missing_hist`` over the bins no participant holds;
* the aggregate: the beta-weighted sum of the participants' models.

Each number compared is a worst-leaf gap of change norms: for every leaf,
|‖Δ_program‖ − ‖Δ_reference‖| over the larger of ‖Δ_reference‖ for that leaf
and the median leaf's, where Δ is the change of the global parameters from
their start after round 1 (``change_1``) and after round 3 (``change_3``).
Leaves whose reference round-1 change is under a thousandth of the median
leaf's are left out of both.

``dtype=bfloat16`` computes the same rounds in bfloat16 (the control), and
``fault`` plants one of the faults a round can have, for reading the limits.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = ("half_minibatch", "half_cohort", "negated_upload")
EXCLUDE_BELOW = 1e-3        # of the median leaf's round-1 change
QP_ITERS = 400


@dataclasses.dataclass
class Update:
    """One local update as the round drew it."""
    role: str               # "client" | "server" | "comp"
    client: int             # client id, -1 for the server's own updates
    x: Any
    y: Any
    key: Any


@dataclasses.dataclass
class Round:
    updates: List[Update]
    lr: float


def local_update_fn(mod, sizes, task, steps: int, batch: int, dtype, fault: Optional[str]):
    """jit(base, trainable, x, y, key, lr) -> trainable after E SGD steps;
    one per configuration, task and variant in a process, so that seeds after
    the first reuse its compilation."""
    key = (id(mod), json.dumps(sizes, sort_keys=True), id(task), steps, batch,
           jnp.dtype(dtype).name, fault)
    if key not in _LOCAL_UPDATES:
        _LOCAL_UPDATES[key] = (mod, task, _local_update_fn(mod, sizes, task, steps, batch,
                                                          dtype, fault))
    return _LOCAL_UPDATES[key][2]


_LOCAL_UPDATES: Dict[tuple, tuple] = {}


def _local_update_fn(mod, sizes, task, steps, batch, dtype, fault):
    keep = batch // 2 if fault == "half_minibatch" else batch

    def loss(t, base, xb, yb):
        return task.loss(mod.reference_logits(sizes, base, t, xb).astype(dtype), yb)

    @jax.jit
    def run(base, t, x, y, key, lr):
        base = jax.tree.map(lambda a: a.astype(dtype), base)
        t = jax.tree.map(lambda a: a.astype(dtype), t)
        if jnp.issubdtype(x.dtype, jnp.floating):       # token ids stay integers
            x = x.astype(dtype)
        lr = jnp.asarray(lr, dtype)
        def step(t, k):
            idx = jax.random.randint(k, (batch,), 0, x.shape[0])[:keep]
            g = jax.grad(loss)(t, base, x[idx], y[idx])
            return jax.tree.map(lambda p, gg: p - lr * gg, t, g), None

        return jax.lax.scan(step, t, jax.random.split(key, steps))[0]

    return run


def _project(v, free, total):
    vm = np.where(free, v, -np.inf)
    vs = np.sort(vm)[::-1]
    fin = np.isfinite(vs)
    css = np.cumsum(np.where(fin, vs, 0.0))
    j = np.arange(1, len(v) + 1)
    cond = fin & (vs - (css - total) / j > 0)
    rho = max(int(np.max(np.where(cond, j, 0))), 1)
    tau = (css[rho - 1] - total) / rho
    return np.where(free, np.clip(v - tau, 0.0, None), 0.0)


def fedauto_weights(rows: np.ndarray, target: np.ndarray) -> np.ndarray:
    """Eq. 8 with the Eq. 9 pin on row 0, by 400 FISTA steps in float64."""
    rows = rows.astype(np.float64)
    target = target.astype(np.float64)
    j = len(rows)
    pin = 1.0 / j
    free = np.arange(j) != 0
    total = 1.0 - pin
    dinv = 1.0 / np.maximum(target, 1e-12)
    resid = target - pin * rows[0]
    m = (rows * dinv[None]) @ rows.T
    step = 1.0 / (2.0 * np.sqrt(np.sum(m * m)) + 1e-6)
    z = np.where(free, total / max(free.sum(), 1), 0.0)
    y, t = z.copy(), 1.0
    for _ in range(QP_ITERS):
        grad = 2.0 * ((y @ rows - resid) * dinv) @ rows.T
        z_new = _project(y - step * grad, free, total)
        t_new = 0.5 * (1.0 + np.sqrt(1.0 + 4.0 * t * t))
        y = z_new + ((t - 1.0) / t_new) * (z_new - z)
        z, t = z_new, t_new
    z[0] = pin
    return z


def _dist(h):
    h = np.asarray(h, np.float64)
    s = h.sum()
    return h / s if s > 0 else np.full(len(h), 1.0 / len(h))


def follow(mod, sizes, task, base, w0, rounds: Sequence[Round], *, server_hist,
           client_hists, public_y, steps: int, batch: int,
           dtype=jnp.float32, fault: Optional[str] = None) -> List[Any]:
    """The global trainable tree after each of ``rounds``, from ``w0``."""
    update = local_update_fn(mod, sizes, task, steps, batch, dtype, fault)
    target = _dist(server_hist + client_hists.sum(axis=0))
    w = jax.tree.map(lambda a: jnp.asarray(a, dtype), w0)
    out = []
    for rnd in rounds:
        clients = sorted(u.client for u in rnd.updates if u.role == "client")
        if fault == "half_cohort":
            clients = clients[::2]
        server = [u for u in rnd.updates if u.role == "server"][0]
        by_client = {u.client: u for u in rnd.updates if u.role == "client"}
        models = [update(base, w, server.x, server.y, server.key, rnd.lr)]
        rows = [_dist(server_hist)]
        covered = client_hists[clients].sum(axis=0) > 0 if clients else np.zeros(len(target), bool)
        comp = [u for u in rnd.updates if u.role == "comp"]
        if not covered.all() and comp:
            c = comp[0]
            models.append(update(base, w, c.x, c.y, c.key, rnd.lr))
            rows.append(_dist(task.missing_hist(public_y, np.where(~covered)[0], len(target))))
        for i, cid in enumerate(clients):
            u = by_client[cid]
            m = update(base, w, u.x, u.y, u.key, rnd.lr)
            if fault == "negated_upload" and i == 0:
                m = jax.tree.map(lambda a, g: 2 * g - a, m, w)
            models.append(m)
            rows.append(_dist(client_hists[cid]))
        beta = fedauto_weights(np.stack(rows), target)
        w = jax.tree.map(lambda *ls: sum(jnp.asarray(b, dtype) * l for b, l in zip(beta, ls)),
                         *models)
        out.append(w)
    return out


def _leaf_norms(a, b) -> np.ndarray:
    """Per-leaf float64 norms of a - b."""
    return np.array([float(jnp.linalg.norm((x.astype(jnp.float32) - y.astype(jnp.float32)).ravel()))
                     for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


def compare(w0, program: Sequence[Any], reference: Sequence[Any]) -> Dict[str, float]:
    """``change_1`` and ``change_3``: worst-leaf gaps of change norms (see the
    module docstring); ``program`` and ``reference`` hold rounds 1..3."""
    r1 = _leaf_norms(reference[0], w0)
    med1 = float(np.median(r1))
    keep = r1 >= EXCLUDE_BELOW * med1
    out = {}
    for name, r in (("change_1", 0), ("change_3", len(reference) - 1)):
        ref = _leaf_norms(reference[r], w0)[keep]
        prog = _leaf_norms(program[r], w0)[keep]
        floor = np.maximum(ref, float(np.median(ref)))
        out[name] = float(np.max(np.abs(prog - ref) / floor))
    out["leaves_left_out"] = int((~keep).sum())
    return out
