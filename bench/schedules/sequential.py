"""Reference of the paper's sequential server schedule (§III-B).

Each RSU trains its cohort one client batch at a time: its members sorted
by (cut, vehicle), every local step visits them in that order.  A slot's
forward/backward runs the whole stack with the units before its cut taken
from the vehicle's replica and the rest from the RSU's copy; the RSU takes
an Adam step on its units (at or past the cut) and the head at once, and
the vehicle's replica takes its Adam step on its own units once the step's
slots are done.  Optimizer states start fresh each round.  The round ends
with the unit-wise FedAvg at the edge (a unit's replicas weighted by
sample count, the RSU copy standing in for the vehicles that did not own
the unit); the cloud merge weights the edges by their samples.

Parameters live as two flat vectors: the prefix a vehicle can own (units
before the deepest cut) and the rest (the last unit and the head), so each
slot is one call with a handful of arguments.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from harness.reference import adam_leaf


class Trainer:
    def __init__(self, model, cell, dtype, half_batch=False):
        cfg = cell.config
        self.U = U = cfg["n_units"]
        self.CU = CU = U - 1            # the deepest cut a vehicle can own
        lr = cfg["train"]["lr"]
        self.model, self.half_batch, self.lr = model, half_batch, lr
        self.cfg = cfg
        self._fns = None

    def _build(self, units, head):
        CU, lr, model, half = self.CU, self.lr, self.model, self.half_batch
        cfg = self.cfg
        pf, un_p = ravel_pytree(list(units[:CU]))
        rf, un_r = ravel_pytree({"units": list(units[CU:]), "head": head})
        ids = ravel_pytree([jax.tree.map(
            lambda a, _u=u: jnp.full(a.shape, _u, jnp.int32), units[u])
            for u in range(CU)])[0]

        def loss(p, r, x, y):
            if half:
                x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
            rest = un_r(r)
            return model.loss(list(un_p(p)) + list(rest["units"]),
                              rest["head"], x, y, cfg)

        def slot(cu, svp, svr, smp, smr, vvp, vvr, count, cut, images,
                 labels, idx, s, j):
            rows = idx[s, j]
            x, y = images[j][rows], labels[j][rows]
            eff = jnp.where(ids < cut, cu, svp)
            val, (gp, gr) = jax.value_and_grad(loss, argnums=(0, 1))(
                eff, svr, x, y)
            # the RSU's Adam step on the positions it owns and the rest
            p2, m2, v2 = adam_leaf(svp, gp, smp, vvp, count, lr)
            own = ids >= cut
            svp, smp, vvp = (jnp.where(own, p2, svp), jnp.where(own, m2, smp),
                             jnp.where(own, v2, vvp))
            svr, smr, vvr = adam_leaf(svr, gr, smr, vvr, count, lr)
            return val, svp, svr, smp, smr, vvp, vvr, gp, gr

        def client(cu, cm, cv, count, g, cut):
            p2, m2, v2 = adam_leaf(cu, g, cm, cv, count, lr)
            own = ids < cut
            return (jnp.where(own, p2, cu), jnp.where(own, m2, cm),
                    jnp.where(own, v2, cv))

        def edge_add(num, own_w, cu, w, cut):
            wk = jnp.where(ids < cut, w, 0.0)
            return num + wk * cu.astype(jnp.float32), own_w + wk

        def edge_end(num, own_w, svp, total):
            return ((num + (total - own_w) * svp.astype(jnp.float32))
                    / total).astype(svp.dtype)

        self._fns = {"slot": jax.jit(slot), "client": jax.jit(client),
                     "edge_add": jax.jit(edge_add),
                     "edge_end": jax.jit(edge_end)}
        self._ravel = lambda u, h: (ravel_pytree(list(u[:CU]))[0],
                                    ravel_pytree({"units": list(u[CU:]),
                                                  "head": h})[0])
        self._unravel = lambda p, r: (list(un_p(p)) + list(un_r(r)["units"]),
                                      un_r(r)["head"])

    def round(self, units, head, images, labels, lengths, serving, cuts,
              idx, n_rsus):
        if self._fns is None:
            self._build(units, head)
        f = self._fns
        pf, rf = self._ravel(units, head)
        edges, weights, losses, first = [], [], [], None
        for r in range(n_rsus):
            members = sorted(np.nonzero((serving == r) & (cuts > 0))[0],
                             key=lambda j: (cuts[j], j))
            if not members:
                continue
            svp, svr = pf, rf
            smp, vvp = jnp.zeros_like(pf), jnp.zeros_like(pf)
            smr, vvr = jnp.zeros_like(rf), jnp.zeros_like(rf)
            cu = {j: pf for j in members}
            cm = {j: jnp.zeros_like(pf) for j in members}
            cv = {j: jnp.zeros_like(pf) for j in members}
            count = 0
            for s in range(idx.shape[0]):
                grads = {}
                for j in members:
                    count += 1
                    (val, svp, svr, smp, smr, vvp, vvr, grads[j],
                     gr) = f["slot"](cu[j], svp, svr, smp, smr, vvp, vvr,
                                     count, int(cuts[j]), images, labels,
                                     idx, s, int(j))
                    losses.append(val)
                    if first is None:
                        first = (grads[j], gr)
                for j in members:
                    cu[j], cm[j], cv[j] = f["client"](
                        cu[j], cm[j], cv[j], s + 1, grads[j], int(cuts[j]))
            total = float(sum(lengths[j] for j in members))
            num = jnp.zeros(pf.shape, jnp.float32)
            own_w = jnp.zeros(pf.shape, jnp.float32)
            for j in members:
                num, own_w = f["edge_add"](num, own_w, cu[j],
                                           float(lengths[j]), int(cuts[j]))
            edges.append((f["edge_end"](num, own_w, svp, total), svr))
            weights.append(total)
        if not edges:
            return units, head, float("nan"), None
        tot = sum(weights)
        gp = sum(w * e[0].astype(jnp.float32) for e, w in zip(edges, weights))
        gr = sum(w * e[1].astype(jnp.float32) for e, w in zip(edges, weights))
        new_u, new_h = self._unravel((gp / tot).astype(pf.dtype),
                                     (gr / tot).astype(rf.dtype))
        loss = float(np.mean(np.asarray(jnp.stack(losses), np.float64)))
        return new_u, new_h, loss, self._unravel(*first)
