"""Reference of the parallel server schedule (arXiv:2405.18707).

Every scheduled vehicle runs its local steps at once.  In each step the
RSU takes one Adam step on the |D_n|-weighted mean of its members'
server-side gradients (every position the member does not own; positions
nobody fed still decay their moments), and each vehicle's replica takes an
Adam step on the units before its cut.  Optimizer states start fresh each
round.  The round ends with the unit-wise FedAvg at the edge and the
sample-weighted cloud merge.

Only scheduled vehicles are held, grouped by cut: a vehicle's replica and
its Adam moments cover the units before its cut and nothing more.  Each
local step runs block by block, ``BLOCK`` vehicles of one cut at a time,
so that a fleet of full-width ResNet18 replicas fits one chip and one
program per cut serves every round.  A group is padded to whole blocks
with phantom vehicles of weight 0 in a dropped segment.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness.reference import adam_leaf

BLOCK = 8


def _col(v, a):
    """``v`` (per row) shaped to broadcast against the stacked leaf ``a``."""
    return v.reshape((-1,) + (1,) * (a.ndim - 1))


def _seg_sum(vals, seg, n_rsus):
    """Per-RSU sums; segment ``n_rsus`` (phantom vehicles) is dropped."""
    return jax.ops.segment_sum(vals, seg, num_segments=n_rsus + 1)[:n_rsus]


def _split(t, i):
    return jax.tree.map(lambda tr: tr[i], t,
                        is_leaf=lambda x: isinstance(x, tuple))


class Trainer:
    def __init__(self, model, cell, dtype, half_batch=False):
        cfg = cell.config
        self.U = cfg["n_units"]
        self.lr = cfg["train"]["lr"]
        self.dtype = dtype
        self.model = model
        self.half_batch = half_batch
        self.cfg = cfg
        self._blocks = {}
        self._fns = None

    def _block_fn(self, c):
        """One local step of ``BLOCK`` vehicles with cut ``c``: their
        weighted server-side gradients add into the RSUs' accumulator, and
        their replicas take their Adam step."""
        if c in self._blocks:
            return self._blocks[c]
        U, R, lr, dtype, model = self.U, self._R, self.lr, self.dtype, \
            self.model
        half, cfg = self.half_batch, self.cfg

        def loss(cu, su, sh, x, y):
            if half:
                x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
            return model.loss(list(cu) + list(su), sh, x.astype(dtype), y,
                              cfg)

        def fn(acc, tot, sv, cu, cm, cv, m, seg, gw, idx, count, images,
               labels):
            segc = jnp.minimum(seg, R - 1)
            su = [jax.tree.map(lambda a: a[segc], sv["units"][u])
                  for u in range(c, U)]
            sh = jax.tree.map(lambda a: a[segc], sv["head"])
            x, y = images[m[:, None], idx], labels[m[:, None], idx]
            vals, (gc, gsu, gsh) = jax.vmap(jax.value_and_grad(
                loss, argnums=(0, 1, 2)))(cu, su, sh, x, y)
            add = lambda A, g: A + _seg_sum(g * _col(gw, g), seg, R)
            acc = {"units": acc["units"][:c] + [
                jax.tree.map(add, acc["units"][u], gsu[u - c])
                for u in range(c, U)],
                "head": jax.tree.map(add, acc["head"], gsh)}
            tot = tot + jnp.sum(jnp.where(seg < R, vals, 0.0))
            trip = jax.tree.map(
                lambda p, g, m_, v: adam_leaf(p, g, m_, v, count, lr),
                cu, gc, cm, cv)
            first = jax.tree.map(lambda g: g[0],
                                 (list(gc) + list(gsu), gsh))
            return (acc, tot, _split(trip, 0), _split(trip, 1),
                    _split(trip, 2), first)

        self._blocks[c] = jax.jit(fn, donate_argnums=(0, 3, 4, 5))
        return self._blocks[c]

    def _build(self):
        lr, R = self.lr, self._R

        @jax.jit
        def start(units, head):
            sv = {"units": [jax.tree.map(
                lambda a: jnp.broadcast_to(a, (R,) + a.shape), u)
                for u in units],
                "head": jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (R,) + a.shape), head)}
            return sv, jax.tree.map(jnp.zeros_like, sv), \
                jax.tree.map(jnp.zeros_like, sv)

        @jax.jit
        def zero_acc(sv):
            return jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                                sv)

        @jax.jit
        def server_step(sv, sm, sv2, acc, active, count):
            # RSU: weighted mean of the server-side gradient shares
            trip = jax.tree.map(
                lambda p, g, m, v: adam_leaf(p, g, m, v, count, lr),
                sv, acc, sm, sv2)
            pick = lambda i, old: jax.tree.map(
                lambda t, o: jnp.where(_col(active, o), t[i], o),
                trip, old, is_leaf=lambda t: isinstance(t, tuple))
            return pick(0, sv), pick(1, sm), pick(2, sv2)

        @jax.jit
        def merge(units, head, sv, w_r, unit_parts):
            """Edge: unit-wise FedAvg over owners (``unit_parts``: per
            unit, the owners' weighted sum and weight per RSU), the RSU
            copy for the rest; then the sample-weighted cloud merge."""
            den = jnp.maximum(w_r, 1.0)
            merged = [jax.tree.map(
                lambda s_, n_: (n_ + _col(w_r - own_w, s_) * s_)
                / _col(den, s_), sv["units"][u], num)
                for u, (num, own_w) in enumerate(unit_parts)]
            head_e = jax.tree.map(
                lambda a: (_col(w_r, a) * a) / _col(den, a), sv["head"])
            edge = {"units": merged, "head": head_e}
            tot = jnp.sum(w_r)
            glob = jax.tree.map(
                lambda e, g: jnp.where(
                    tot > 0.0,
                    (jnp.tensordot(w_r, e.astype(jnp.float32), axes=(0, 0))
                     / jnp.maximum(tot, 1.0)).astype(g.dtype), g),
                edge, {"units": list(units), "head": head})
            return glob["units"], glob["head"]

        @jax.jit
        def owned(cu_u, w, seg, num, own_w):
            """Add one block's replicas of one unit to the edge numerator."""
            num = jax.tree.map(
                lambda n_, c_: n_ + _seg_sum(c_ * _col(w, c_), seg, R),
                num, cu_u)
            return num, own_w + _seg_sum(w, seg, R)

        return start, zero_acc, server_step, merge, owned

    def round(self, units, head, images, labels, lengths, serving, cuts,
              idx, n_rsus):
        self._R = R = n_rsus
        if self._fns is None:
            self._fns = self._build()
        start, zero_acc, server_step, merge, owned = self._fns
        units = list(units)
        blocks = []                     # (cut, members, segment, weight)
        for c in np.unique(cuts[cuts > 0]):
            m = np.nonzero(cuts == c)[0]
            pad = -len(m) % BLOCK
            m_p = np.concatenate([m, np.zeros(pad, np.int64)])
            seg = np.concatenate([serving[m], np.full(pad, R)])
            w = np.concatenate([lengths[m], np.zeros(pad)])
            for b in range(0, len(m_p), BLOCK):
                blocks.append((int(c), m_p[b:b + BLOCK], seg[b:b + BLOCK],
                               w[b:b + BLOCK]))
        if not blocks:
            return units, head, float("nan"), None
        w_r = np.zeros(R + 1)
        for _, _, seg, w in blocks:
            np.add.at(w_r, seg, w)
        w_r = w_r[:R]
        den = np.maximum(w_r, 1.0)
        active = jnp.asarray(w_r > 0.0)
        dev = [(c, jnp.asarray(m, jnp.int32), jnp.asarray(seg, jnp.int32),
                jnp.asarray(w, jnp.float32),
                jnp.asarray(w / den[np.minimum(seg, R - 1)], jnp.float32))
               for c, m, seg, w in blocks]
        sv, sm, sv2 = start(units, head)
        zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
        clients = [[jax.tree.map(
            lambda a: jnp.broadcast_to(a, (BLOCK,) + a.shape), u)
            for u in units[:c]] for c, *_ in dev]
        clients = [(cu, zeros(cu), zeros(cu)) for cu in clients]
        # the first gradient: the first scheduled vehicle's, which leads
        # the first block of its cut
        j = int(np.argmax(cuts > 0))
        lead = [c for c, *_ in dev].index(int(cuts[j]))
        total, first = 0.0, None
        for s in range(idx.shape[0]):
            acc, tot = zero_acc(sv), jnp.zeros((), jnp.float32)
            for b, (c, m, seg, w, gw) in enumerate(dev):
                cu, cm, cv = clients[b]
                acc, tot, cu, cm, cv, g0 = self._block_fn(c)(
                    acc, tot, sv, cu, cm, cv, m, seg, gw, idx[s][m], s + 1,
                    images, labels)
                clients[b] = (cu, cm, cv)
                if s == 0 and b == lead:
                    first = g0
            sv, sm, sv2 = server_step(sv, sm, sv2, acc, active, s + 1)
            total += float(tot)
        parts = []
        for u in range(self.U):
            num = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                               sv["units"][u])
            own_w = jnp.zeros((R,), jnp.float32)
            for b, (c, m, seg, w, gw) in enumerate(dev):
                if c > u:
                    num, own_w = owned(clients[b][0][u], w, seg, num,
                                       own_w)
            parts.append((num, own_w))
        u_new, h_new = merge(units, head, sv,
                             jnp.asarray(w_r, jnp.float32), parts)
        cnt = int(np.sum(cuts > 0)) * idx.shape[0]
        return list(u_new), h_new, total / cnt, (list(first[0]), first[1])
