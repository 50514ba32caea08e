"""Per-cut communication / computation / energy accounting.

This is the analytic model behind the paper's Fig. 5a (communication overhead
per scheme and cut layer) and Fig. 5b (overall training time), and the input
to the latency-optimal cut selection strategy (beyond-paper, adaptive.py).

A :class:`SplitProfile` abstracts any layer-stack model: per-unit forward
FLOPs, per-unit parameter bytes, and smashed-data bytes at each cut.  Both
ResNet18 (the paper's model) and every assigned ArchConfig provide one.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np

from repro.configs.base import (ATTN, ATTN_LOCAL, ATTN_MOE, MLA_DENSE,
                                MLA_MOE, RGLRU, SSM, ArchConfig)

BYTES_F32 = 4
BWD_FWD_RATIO = 2.0  # backward pass ~ 2x forward FLOPs


def wire_smashed_ratio(profile: "SplitProfile", cuts, wire: str = "none",
                       wire_k: Optional[float] = None, group: int = 128):
    """Dense-fp32 / on-wire bytes for the smashed tensors at each cut.

    ``wire="int8"`` is per-group quant (int8 values + f32 scale per group);
    ``"topk_int8"`` is the packed sparse format (bitmap + scale + int8
    survivors — compression.wire_row_bytes charges every word).  The ratio
    applies to BOTH directions: activations up AND cut-layer gradients down
    ride the same wire (previously the downlink was charged dense fp32 even
    with gradient quantisation on — the effective-bytes helper below routes
    both through this one factor)."""
    from repro.core import compression
    if wire == "none":
        return 1.0
    td = profile.smashed_trailing_dim
    trailing = (None if td is None
                else np.asarray(td)[np.asarray(cuts, dtype=np.int64) - 1])
    if wire_k is None:
        wire_k = compression.WIRE_K
    return compression.wire_compression_ratio(wire, BYTES_F32, group,
                                              trailing, wire_k)


def effective_comm_bytes(profile: "SplitProfile", cuts, steps, batch: int,
                         wire: str = "none", wire_k: Optional[float] = None,
                         include_model_transfer: bool = True,
                         model_upload=True):
    """(up, down) bytes for one round: smashed traffic charged at actual
    on-wire size in both directions, model transfer (aggregation up + fresh
    copy down) always dense fp32 — the wire compresses activations and
    gradients, never parameters.  ``model_upload`` (scalar or bool array
    broadcast over the fleet) drops the aggregation-upload bytes for
    vehicles whose update never made it onto the wire (mid-round dropouts,
    DESIGN.md §13) — the fresh-copy download at round start is still
    charged, as is every smashed exchange in ``steps``."""
    cuts = np.asarray(cuts, dtype=np.int64)
    smashed = (np.asarray(profile.smashed_bytes_per_sample)[cuts - 1] * batch
               / wire_smashed_ratio(profile, cuts, wire, wire_k))
    up = np.asarray(steps) * smashed
    down = np.asarray(steps) * smashed
    if include_model_transfer:
        bytes_cum = np.concatenate([[0], np.cumsum(profile.unit_param_bytes)])
        up = up + bytes_cum[cuts] * np.asarray(model_upload)
        down = down + bytes_cum[cuts]
    return up, down


@dataclasses.dataclass
class SplitProfile:
    name: str
    unit_fwd_flops: List[float]      # per-sample forward FLOPs per unit
    unit_param_bytes: List[int]      # parameter bytes per unit
    smashed_bytes_per_sample: List[float]  # at cut c (index c-1), forward
    head_flops: float = 0.0
    head_param_bytes: int = 0
    # trailing dim of the smashed tensor at cut c (index c-1) — the axis
    # int8 quantisation groups along; None = unknown (assume GROUP-divisible)
    smashed_trailing_dim: Optional[List[int]] = None

    @property
    def n_units(self) -> int:
        return len(self.unit_fwd_flops)

    def client_fwd_flops(self, cut: int) -> float:
        return float(sum(self.unit_fwd_flops[:cut]))

    def server_fwd_flops(self, cut: int) -> float:
        return float(sum(self.unit_fwd_flops[cut:]) + self.head_flops)

    def client_param_bytes(self, cut: int) -> int:
        return int(sum(self.unit_param_bytes[:cut]))

    def full_param_bytes(self) -> int:
        return int(sum(self.unit_param_bytes) + self.head_param_bytes)

    def smashed_bytes(self, cut: int, batch: int) -> float:
        return self.smashed_bytes_per_sample[cut - 1] * batch


def resnet_profile() -> SplitProfile:
    from repro.models import resnet as R
    unit_flops = [float(R.unit_flops(i)) for i in range(R.N_UNITS)]
    unit_bytes = []
    # analytic param bytes per unit
    cin = 3
    # stem
    unit_bytes.append((3 * 3 * 3 * 64 + 2 * 64) * BYTES_F32)
    cin = 64
    for cout, stride in zip(R.STAGE_CHANNELS, R.STAGE_STRIDES):
        n = 3 * 3 * cin * cout + 2 * cout + 3 * 3 * cout * cout + 2 * cout
        if stride != 1 or cin != cout:
            n += cin * cout + 2 * cout
        unit_bytes.append(n * BYTES_F32)
        cin = cout
    smashed = [float(np.prod(R.smashed_shape(c, 1)[1:])) * BYTES_F32
               for c in range(1, R.N_UNITS + 1)]
    return SplitProfile(
        name="resnet18",
        unit_fwd_flops=unit_flops,
        unit_param_bytes=unit_bytes,
        smashed_bytes_per_sample=smashed,
        head_flops=2 * 512 * 10,
        head_param_bytes=(512 * 10 + 10) * BYTES_F32,
        smashed_trailing_dim=[R.smashed_shape(c, 1)[-1]
                              for c in range(1, R.N_UNITS + 1)],
    )


def arch_profile(cfg: ArchConfig, seq: int, param_bytes_per: int = 2
                 ) -> SplitProfile:
    """SplitProfile for an assigned architecture at period granularity.
    smashed data = (seq, d_model) activations at the period boundary."""
    from repro.models import transformer as T
    from repro.models.attention import attn_flops
    from repro.models.mla import mla_flops
    from repro.models.moe import moe_flops
    from repro.models.rglru import rglru_flops
    from repro.models.ssm import ssm_flops
    from repro.models.layers import mlp_flops

    def layer_flops(kind: str) -> float:
        if kind in (ATTN, ATTN_MOE):
            f = attn_flops(cfg, seq)
        elif kind == ATTN_LOCAL:
            f = attn_flops(cfg, seq, cfg.window)
        elif kind in (MLA_DENSE, MLA_MOE):
            f = mla_flops(cfg, seq)
        elif kind == SSM:
            return float(ssm_flops(cfg, seq, "train"))
        elif kind == RGLRU:
            f = rglru_flops(cfg)
        else:
            raise ValueError(kind)
        if kind in (ATTN_MOE, MLA_MOE):
            f += moe_flops(cfg)
        elif kind != SSM:
            f += mlp_flops(cfg.d_model, cfg.d_ff, cfg.mlp_variant)
        return float(f)

    def layer_params(kind: str) -> int:
        # reuse the analytic counter via a 1-layer pseudo-config
        import dataclasses as dc
        one = dc.replace(cfg, n_layers=1, pattern=(kind,), tail=(), lead=())
        base = T.count_params(one)
        emb = one.padded_vocab * one.d_model * (
            one.n_codebooks if one.frontend == "audio" else 1)
        head = one.d_model * one.padded_vocab * (
            one.n_codebooks if one.frontend == "audio" else 1)
        return (base - emb - head - one.d_model) * param_bytes_per

    types = cfg.layer_types
    segs = T.segments_of(cfg)
    unit_flops, unit_bytes = [], []
    li = 0
    for pat, n in segs:
        for _ in range(n):
            f = sum(layer_flops(k) for k in pat) * seq
            b = sum(layer_params(k) for k in pat)
            unit_flops.append(float(f))
            unit_bytes.append(int(b))
            li += len(pat)
    smashed = [float(seq * cfg.d_model * param_bytes_per)] * len(unit_flops)
    vp = cfg.padded_vocab * (cfg.n_codebooks if cfg.frontend == "audio" else 1)
    return SplitProfile(
        name=cfg.name,
        unit_fwd_flops=unit_flops,
        unit_param_bytes=unit_bytes,
        smashed_bytes_per_sample=smashed,
        head_flops=float(2 * cfg.d_model * vp * seq),
        head_param_bytes=2 * vp * cfg.d_model * param_bytes_per,
        smashed_trailing_dim=[cfg.d_model] * len(unit_flops),
    )


# --------------------------------------------------------------------------
# per-round cost model (Fig 5a / 5b)
# --------------------------------------------------------------------------

@dataclasses.dataclass
class RoundCost:
    comm_bytes_up: float
    comm_bytes_down: float
    t_client_compute: float
    t_server_compute: float
    t_comm: float
    energy_j: float

    @property
    def comm_bytes(self) -> float:
        return self.comm_bytes_up + self.comm_bytes_down

    @property
    def latency(self) -> float:
        return self.t_client_compute + self.t_server_compute + self.t_comm


def sfl_client_round_cost(profile: SplitProfile, cut: int, n_batches: int,
                          batch: int, rate_bps: float, client_flops: float,
                          server_flops: float, local_epochs: int = 1,
                          tx_power_w: float = 0.5, compute_power_w: float = 15.0,
                          include_model_transfer: bool = True,
                          wire: str = "none",
                          wire_k: Optional[float] = None) -> RoundCost:
    """One SFL round for ONE client: K local epochs of (client fwd -> smashed
    up -> server fwd/bwd -> grad down -> client bwd), then client-model
    upload for aggregation (and download of the fresh copy).  ``wire``
    charges smashed traffic (activations up, cut-layer gradients down) at
    its actual on-wire bytes."""
    steps = n_batches * local_epochs
    up, down = effective_comm_bytes(profile, cut, steps, batch, wire, wire_k,
                                    include_model_transfer)
    up, down = float(up), float(down)
    c_fwd = profile.client_fwd_flops(cut) * batch
    s_fwd = profile.server_fwd_flops(cut) * batch
    t_client = steps * c_fwd * (1 + BWD_FWD_RATIO) / client_flops
    t_server = steps * s_fwd * (1 + BWD_FWD_RATIO) / server_flops
    t_comm = (up + down) / max(rate_bps / 8, 1e-9)  # rate in bits/s
    energy = compute_power_w * t_client + tx_power_w * (up * 8 / max(rate_bps, 1e-9))
    return RoundCost(up, down, t_client, t_server, t_comm, energy)


@dataclasses.dataclass
class RoundCostArrays:
    """Vectorized :class:`RoundCost`: every field is an np array broadcast
    over the fleet (and optionally a candidate-cut axis).  This makes round
    accounting and cut selection one vector op for 256+ vehicles."""
    comm_bytes_up: np.ndarray
    comm_bytes_down: np.ndarray
    t_client_compute: np.ndarray
    t_server_compute: np.ndarray
    t_comm: np.ndarray
    energy_j: np.ndarray

    @property
    def comm_bytes(self) -> np.ndarray:
        return self.comm_bytes_up + self.comm_bytes_down

    @property
    def latency(self) -> np.ndarray:
        return self.t_client_compute + self.t_server_compute + self.t_comm


def sfl_round_cost_arrays(profile: SplitProfile, cuts, n_batches, batch: int,
                          rates_bps, client_flops, server_flops: float,
                          local_epochs: int = 1, tx_power_w=0.5,
                          compute_power_w=15.0,
                          include_model_transfer: bool = True,
                          wire: str = "none", wire_k: Optional[float] = None,
                          model_upload=True
                          ) -> RoundCostArrays:
    """Vectorized :func:`sfl_client_round_cost`.  ``cuts``, ``n_batches``,
    ``rates_bps``, ``client_flops``, ``tx_power_w``, ``compute_power_w`` may
    be scalars or arrays; everything broadcasts (e.g. rates (n,1) against
    candidate cuts (k,) yields an (n,k) cost matrix for cut selection).
    Smashed traffic is charged at on-wire bytes in BOTH directions via
    :func:`effective_comm_bytes`; latency and radio energy follow from the
    compressed byte counts (the engines no longer rescale post-hoc).  Under
    fault injection, pass per-vehicle *performed* steps as ``n_batches``
    (with ``local_epochs=1``) and a ``model_upload`` mask so dropouts are
    charged only the work they actually did."""
    cuts = np.asarray(cuts, dtype=np.int64)
    fwd_cum = np.concatenate([[0.0], np.cumsum(profile.unit_fwd_flops)])

    steps = np.asarray(n_batches) * local_epochs
    up, down = effective_comm_bytes(profile, cuts, steps, batch, wire,
                                    wire_k, include_model_transfer,
                                    model_upload)
    c_fwd = fwd_cum[cuts] * batch
    s_fwd = (fwd_cum[-1] - fwd_cum[cuts] + profile.head_flops) * batch
    t_client = steps * c_fwd * (1 + BWD_FWD_RATIO) / np.asarray(client_flops)
    t_server = steps * s_fwd * (1 + BWD_FWD_RATIO) / server_flops
    rate = np.asarray(rates_bps, dtype=np.float64)
    t_comm = (up + down) / np.maximum(rate / 8, 1e-9)
    energy = (np.asarray(compute_power_w) * t_client
              + np.asarray(tx_power_w) * (up * 8 / np.maximum(rate, 1e-9)))
    b = np.broadcast_arrays(up, down, t_client, t_server, t_comm, energy)
    return RoundCostArrays(*[np.asarray(a, dtype=np.float64) for a in b])


def fl_round_cost_arrays(profile: SplitProfile, n_batches, batch: int,
                         rates_bps, client_flops, local_epochs: int = 1,
                         tx_power_w=0.5, compute_power_w=15.0
                         ) -> RoundCostArrays:
    """Vectorized :func:`fl_client_round_cost` over the fleet."""
    steps = np.asarray(n_batches) * local_epochs
    full = float(profile.full_param_bytes())
    fwd = (profile.client_fwd_flops(profile.n_units) + profile.head_flops) * batch
    t_client = steps * fwd * (1 + BWD_FWD_RATIO) / np.asarray(client_flops)
    rate = np.asarray(rates_bps, dtype=np.float64)
    t_comm = 2 * full / np.maximum(rate / 8, 1e-9)
    energy = (np.asarray(compute_power_w) * t_client
              + np.asarray(tx_power_w) * (full * 8 / np.maximum(rate, 1e-9)))
    b = np.broadcast_arrays(np.full_like(t_client, full),
                            np.full_like(t_client, full),
                            t_client, np.zeros_like(t_client), t_comm, energy)
    return RoundCostArrays(*[np.asarray(a, dtype=np.float64) for a in b])


def fl_client_round_cost(profile: SplitProfile, n_batches: int, batch: int,
                         rate_bps: float, client_flops: float,
                         local_epochs: int = 1, tx_power_w: float = 0.5,
                         compute_power_w: float = 15.0) -> RoundCost:
    """FL: full model trained on-vehicle; model up+down once per round."""
    steps = n_batches * local_epochs
    full = profile.full_param_bytes()
    fwd = (profile.client_fwd_flops(profile.n_units) + profile.head_flops) * batch
    t_client = steps * fwd * (1 + BWD_FWD_RATIO) / client_flops
    t_comm = 2 * full / max(rate_bps / 8, 1e-9)
    energy = compute_power_w * t_client + tx_power_w * (full * 8 / max(rate_bps, 1e-9))
    return RoundCost(full, full, t_client, 0.0, t_comm, energy)


def sl_round_cost(profile: SplitProfile, cut: int, n_batches_per_client: Sequence[int],
                  batch: int, rates_bps: Sequence[float], client_flops: Sequence[float],
                  server_flops: float, local_epochs: int = 1) -> RoundCost:
    """Sequential SL: clients served one after another; the client-side model
    additionally hops vehicle -> vehicle (via RSU) between turns."""
    up = down = t_c = t_s = t_comm = energy = 0.0
    for nb, r, cf in zip(n_batches_per_client, rates_bps, client_flops):
        c = sfl_client_round_cost(profile, cut, nb, batch, r, cf, server_flops,
                                  local_epochs, include_model_transfer=True)
        up += c.comm_bytes_up
        down += c.comm_bytes_down
        t_c += c.t_client_compute          # sequential: times add up
        t_s += c.t_server_compute
        t_comm += c.t_comm
        energy += c.energy_j
    return RoundCost(up, down, t_c, t_s, t_comm, energy)


def parallel_round_latency(costs: Sequence[RoundCost],
                           survivors: Optional[Sequence[bool]] = None) -> float:
    """SFL/FL round latency: slowest client (straggler) bounds the round.

    ``survivors`` restricts the bound to clients whose update actually made
    the round (DESIGN.md §13): a dropout's partial work and a deadline
    straggler's late upload do not extend the round — the server closes the
    merge without them.  An empty survivor set costs 0 (nothing merged)."""
    if survivors is None:
        return max(c.latency for c in costs)
    lats = [c.latency for c, s in zip(costs, survivors) if s]
    return max(lats) if lats else 0.0
