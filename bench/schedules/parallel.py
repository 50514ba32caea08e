"""Reference of the parallel server schedule (arXiv:2405.18707).

Every scheduled vehicle runs its local steps at once.  In each step the
RSU takes one Adam step on the |D_n|-weighted mean of its members'
server-side gradients (every position the member does not own; positions
nobody fed still decay their moments), and each vehicle's replica takes an
Adam step on the units before its cut.  Optimizer states start fresh each
round.  The round ends with the unit-wise FedAvg at the edge and the
sample-weighted cloud merge.

Written over the whole fleet at fixed shapes: unscheduled vehicles carry
zero weight and are masked out, so one program serves every round.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness.reference import adam_leaf


class Trainer:
    def __init__(self, model, cell, dtype, half_batch=False):
        cfg = cell.config
        self.U = cfg["n_units"]
        self.CU = self.U - 1
        self.lr = cfg["train"]["lr"]
        self.dtype = dtype
        self.model = model
        self.half_batch = half_batch
        self.cfg = cfg
        self._round = None

    def _build(self, n_rsus):
        U, CU, lr, dtype, model = self.U, self.CU, self.lr, self.dtype, \
            self.model
        half, cfg = self.half_batch, self.cfg
        R = n_rsus

        def loss(units, head, x, y):
            if half:
                x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
            return model.loss(units, head, x, y, cfg)

        def seg_sum(vals, seg):
            return jax.ops.segment_sum(vals, seg, num_segments=R + 1)[:R]

        @jax.jit
        def round_fn(units, head, images, labels, lengths, seg, cuts, idx):
            sched = cuts > 0
            w = jnp.where(sched, lengths.astype(jnp.float32), 0.0)
            w_r = seg_sum(w, seg)                          # (R,)
            den = jnp.maximum(w_r, 1.0)
            gw = w / den[jnp.minimum(seg, R - 1)]
            active = w_r > 0.0
            bc = lambda t, k: jax.tree.map(
                lambda a: jnp.broadcast_to(a, (k,) + a.shape), t)
            zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
            sv = {"units": bc(list(units), R), "head": bc(head, R)}
            sm, sv2 = zeros(sv), zeros(sv)
            n = cuts.shape[0]
            cu = bc(list(units[:CU]), n)
            cm, cv = zeros(cu), zeros(cu)
            seg_c = jnp.minimum(seg, R - 1)
            total_loss = 0.0
            for s in range(idx.shape[0]):
                x = jax.vmap(lambda im, ix: im[ix])(images, idx[s])
                y = jax.vmap(lambda lb, ix: lb[ix])(labels, idx[s])
                mine = jax.tree.map(lambda a: a[seg_c], sv)

                def one(cu_j, sv_j, cut_j, x_j, y_j):
                    eff = [jax.tree.map(
                        lambda a, b: jnp.where(u < cut_j, a, b),
                        cu_j[u], sv_j["units"][u]) for u in range(CU)] \
                        + list(sv_j["units"][CU:])
                    return jax.value_and_grad(loss, argnums=(0, 1))(
                        eff, sv_j["head"], x_j.astype(dtype), y_j)

                vals, (gu, gh) = jax.vmap(one)(cu, mine, cuts, x, y)
                total_loss = total_loss + jnp.sum(jnp.where(sched, vals, 0.0))
                # RSU: weighted mean of the server-side gradient shares
                srv = {"units": [jax.tree.map(
                    lambda g, _u=u: seg_sum(
                        jnp.where((cuts > _u).reshape((-1,) + (1,) * (g.ndim - 1)),
                                  0.0, g) * gw.reshape((-1,) + (1,) * (g.ndim - 1)),
                        seg), gu[u]) for u in range(U)],
                    "head": jax.tree.map(
                        lambda g: seg_sum(
                            g * gw.reshape((-1,) + (1,) * (g.ndim - 1)), seg),
                        gh)}
                trip = jax.tree.map(
                    lambda p, g, m, v: adam_leaf(p, g, m, v, s + 1, lr),
                    sv, srv, sm, sv2)
                pick = lambda i, old: jax.tree.map(
                    lambda t, o: jnp.where(
                        active.reshape((-1,) + (1,) * (o.ndim - 1)), t[i], o),
                    trip, old, is_leaf=lambda t: isinstance(t, tuple))
                sv, sm, sv2 = pick(0, sv), pick(1, sm), pick(2, sv2)
                # vehicles: Adam on the units before the cut
                new = ([], [], [])
                for u in range(CU):
                    trip = jax.tree.map(
                        lambda p, g, m, v: adam_leaf(p, g, m, v, s + 1, lr),
                        cu[u], gu[u], cm[u], cv[u])
                    keep = sched & (cuts > u)
                    for i, old in enumerate((cu[u], cm[u], cv[u])):
                        new[i].append(jax.tree.map(
                            lambda t, o: jnp.where(
                                keep.reshape((-1,) + (1,) * (o.ndim - 1)),
                                t[i], o),
                            trip, old,
                            is_leaf=lambda t: isinstance(t, tuple)))
                cu, cm, cv = new
            # edge: unit-wise FedAvg over owners, the RSU copy for the rest
            merged = []
            for u in range(U):
                if u >= CU:
                    merged.append(jax.tree.map(
                        lambda a: (w_r.reshape((-1,) + (1,) * (a.ndim - 1))
                                   * a) / den.reshape(
                                       (-1,) + (1,) * (a.ndim - 1)),
                        sv["units"][u]))
                    continue
                wk = w * (cuts > u)
                own_w = seg_sum(wk, seg)

                def merge(c, s_):
                    b = lambda v: v.reshape((-1,) + (1,) * (s_.ndim - 1))
                    num = seg_sum(c * b(wk)[:, ...], seg)
                    return (num + b(w_r - own_w) * s_) / b(den)

                merged.append(jax.tree.map(merge, cu[u], sv["units"][u]))
            head_e = jax.tree.map(
                lambda a: (w_r.reshape((-1,) + (1,) * (a.ndim - 1)) * a)
                / den.reshape((-1,) + (1,) * (a.ndim - 1)), sv["head"])
            edge = {"units": merged, "head": head_e}
            tot = jnp.sum(w_r)
            glob = jax.tree.map(
                lambda e, g: jnp.where(
                    tot > 0.0,
                    (jnp.tensordot(w_r, e.astype(jnp.float32), axes=(0, 0))
                     / jnp.maximum(tot, 1.0)).astype(g.dtype), g),
                edge, {"units": list(units), "head": head})
            cnt = jnp.sum(sched) * idx.shape[0]
            first = (gu, gh)
            return glob["units"], glob["head"], total_loss / cnt, first

        return round_fn

    def round(self, units, head, images, labels, lengths, serving, cuts,
              idx, n_rsus):
        if self._round is None:
            self._round = self._build(n_rsus)
        seg = np.where(cuts > 0, serving, n_rsus).astype(np.int32)
        u, h, loss, (gu, gh) = self._round(
            list(units), head, images, labels, jnp.asarray(lengths),
            jnp.asarray(seg), jnp.asarray(cuts), idx)
        # the first gradient: the first scheduled vehicle's
        j = int(np.argmax(cuts > 0))
        first = jax.tree.map(lambda a: a[j], (list(gu), gh))
        return list(u), h, float(loss), first
