"""Reference mobility of ``highway_corridor``: RSUs every ``rsu_spacing_m``
along a multi-lane road that vehicles drive round; nearest RSU within
range serves; uplink rate is Shannon capacity with log-distance path loss
and log-normal shadow fading.

Positions and rates are computed in float32 on the device, as the engine's
traced mobility computes them, so that association and rate bands agree
exactly; the fading draw of round ``r`` comes from
``fold_in(PRNGKey(seed ^ 0x5EED5EED), r)``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# the deployment's radio and road, as the scenario defines them
N_RSUS, SPACING_M, N_LANES = 4, 700.0, 3
LANE_SPEEDS_MPS, LANE_WIDTH_M = (24.0, 31.0, 38.0), 3.7
RANGE_M, RSU_HEIGHT_M = 400.0, 10.0
BANDWIDTH_HZ, NOISE_DBM_HZ, PATH_LOSS_EXP = 10e6, -174.0, 3.0
REF_GAIN_DB, FADING_STD_DB = -30.0, 4.0
FADING_SALT = 0x5EED5EED


def vehicle_tx_power(n: int, seed: int) -> np.ndarray:
    """Per-vehicle transmit power: the fleet draws five attributes per
    vehicle in turn (compute, power, compute power, start, speed)."""
    rng = np.random.default_rng(seed)
    tx = np.empty(n)
    for i in range(n):
        _, tx[i], _, _, _ = (rng.uniform(5e9, 50e9), rng.uniform(0.2, 1.0),
                             rng.uniform(8.0, 25.0),
                             rng.uniform(-350.0, -50.0),
                             rng.uniform(8.0, 30.0))
    return tx


class Scenario:
    def __init__(self, cell):
        tr = cell.traffic
        kw = tr["scenario_kwargs"]
        n, seed = tr["n_vehicles"], kw["seed"]
        self.n_rsus = kw.get("n_rsus", N_RSUS)
        self.interval = float(tr["round_interval_s"])
        rng = np.random.default_rng(seed)
        self.tx = vehicle_tx_power(n, seed)
        self.road = self.n_rsus * SPACING_M
        rsu_x = (np.arange(self.n_rsus) + 0.5) * SPACING_M
        self.rsus = np.stack([rsu_x, np.zeros_like(rsu_x)], -1)
        lane = rng.integers(0, N_LANES, size=n)
        self.speed = np.asarray(LANE_SPEEDS_MPS)[lane] * rng.uniform(
            0.9, 1.1, size=n)
        self.x0 = rng.uniform(0.0, self.road, size=n)
        self.y = (lane - (N_LANES - 1) / 2.0) * LANE_WIDTH_M
        self._state = jax.jit(self._traced)

    def _traced(self, rnd, fading_key):
        t = rnd.astype(jnp.float32) * self.interval
        speed = jnp.asarray(self.speed, jnp.float32)
        x = (jnp.asarray(self.x0, jnp.float32) + speed * t) % self.road
        y = jnp.asarray(self.y, jnp.float32)
        rsus = jnp.asarray(self.rsus, jnp.float32)
        d2 = (x[:, None] - rsus[None, :, 0]) ** 2 \
            + (y[:, None] - rsus[None, :, 1]) ** 2
        near = jnp.argmin(d2, axis=1).astype(jnp.int32)
        dmin = jnp.sqrt(jnp.take_along_axis(d2, near[:, None], 1)[:, 0])
        serving = jnp.where(dmin <= RANGE_M, near, -1)
        key = jax.random.fold_in(fading_key, rnd)
        fading = FADING_STD_DB * jax.random.normal(key, (x.shape[0],))
        d = jnp.maximum(jnp.sqrt(dmin ** 2 + RSU_HEIGHT_M ** 2), 1.0)
        pl_db = -REF_GAIN_DB + 10.0 * PATH_LOSS_EXP * jnp.log10(d) + fading
        p_rx = 10.0 * jnp.log10(jnp.asarray(self.tx, jnp.float32) * 1e3) \
            - pl_db
        noise = NOISE_DBM_HZ + 10.0 * math.log10(BANDWIDTH_HZ)
        rates = BANDWIDTH_HZ * jnp.log2(1.0 + 10.0 ** ((p_rx - noise) / 10.0))
        return serving, jnp.where(serving >= 0, rates, 0.0)

    def round_state(self, rnd: int, prog_seed: int):
        s, r = self._state(jnp.int32(rnd),
                           jax.random.PRNGKey(prog_seed ^ FADING_SALT))
        return np.asarray(s), np.asarray(r)
