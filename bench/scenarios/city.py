"""Reference mobility of ``city``: a ``grid_x`` x ``grid_y`` lattice of RSU
cells; each vehicle orbits a home cell drawn from a Zipf popularity law,
its radius breathing across the coverage edge, so vehicles leave coverage
and re-enter it.  The nearest lattice centre within range serves.  Rates
are Shannon capacity with log-distance path loss and a numpy-drawn
shadow fading per round (seed ``seed * 1000 + round``).

All in float64 numpy on the host, as the engine stages this scenario.
"""
from __future__ import annotations

import numpy as np

CELL_M, ORBIT_FRAC, ECCENTRICITY, SPEED_MPS = 900.0, (0.35, 1.15), 0.45, 14.0
RANGE_M, RSU_HEIGHT_M = 400.0, 10.0
BANDWIDTH_HZ, NOISE_DBM_HZ, PATH_LOSS_EXP = 10e6, -174.0, 3.0
REF_GAIN_DB, FADING_STD_DB = -30.0, 4.0


class Scenario:
    def __init__(self, cell):
        tr = cell.traffic
        kw = tr["scenario_kwargs"]
        n, seed = tr["n_vehicles"], kw["seed"]
        self.gx, self.gy = kw.get("grid_x", 16), kw.get("grid_y", 16)
        self.n_rsus = self.gx * self.gy
        self.interval = float(tr["round_interval_s"])
        rng = np.random.default_rng(seed)
        # per-vehicle attributes, one draw per column in this order
        rng.uniform(5e9, 50e9, size=n)
        self.tx = rng.uniform(0.2, 1.0, size=n)
        rng.uniform(8.0, 25.0, size=n)
        rng.uniform(-350.0, -50.0, size=n)
        rng.uniform(8.0, 30.0, size=n)
        gx, gy = np.meshgrid(np.arange(self.gx), np.arange(self.gy),
                             indexing="ij")
        self.rsus = ((np.stack([gx.ravel(), gy.ravel()], -1) + 0.5)
                     * CELL_M).astype(np.float64)
        w = 1.0 / (np.arange(self.n_rsus) + 1.0)
        home = rng.choice(self.n_rsus, size=n, p=w / w.sum())
        self.center = self.rsus[home]
        lo, hi = ORBIT_FRAC
        self.radius = RANGE_M * rng.uniform(lo, hi, size=n)
        self.phase = rng.uniform(0.0, 2.0 * np.pi, size=n)
        speed = SPEED_MPS * rng.uniform(0.85, 1.15, size=n)
        spin = rng.choice(np.array([-1.0, 1.0]), size=n)
        self.omega = spin * speed / np.maximum(self.radius, 1e-9)
        self.nu = np.abs(self.omega) * rng.uniform(0.4, 0.9, size=n)
        self.psi = rng.uniform(0.0, 2.0 * np.pi, size=n)

    def round_state(self, rnd: int, prog_seed: int):
        t = rnd * self.interval
        theta = self.phase + self.omega * t
        r = self.radius * (1.0 + ECCENTRICITY * np.sin(self.nu * t + self.psi))
        pos = self.center + r[:, None] * np.stack([np.cos(theta),
                                                   np.sin(theta)], -1)
        ij = np.clip(np.floor(pos / CELL_M).astype(np.int64), 0,
                     [self.gx - 1, self.gy - 1])
        flat = ij[:, 0] * self.gy + ij[:, 1]
        rel = pos - self.rsus[flat]
        dist = np.sqrt(np.einsum("nd,nd->n", rel, rel))
        serving = np.where(dist <= RANGE_M, flat, -1).astype(np.int32)
        fading = np.random.default_rng(prog_seed * 1000 + rnd).normal(
            0.0, FADING_STD_DB, size=dist.shape)
        d = np.maximum(np.sqrt(dist ** 2 + RSU_HEIGHT_M ** 2), 1.0)
        pl_db = -REF_GAIN_DB + 10 * PATH_LOSS_EXP * np.log10(d) + fading
        p_rx = 10 * np.log10(self.tx * 1e3) - pl_db
        noise = NOISE_DBM_HZ + 10 * np.log10(BANDWIDTH_HZ)
        rates = BANDWIDTH_HZ * np.log2(1.0 + 10 ** ((p_rx - noise) / 10))
        return serving, np.where(serving >= 0, rates, 0.0).astype(np.float32)
