"""The plain reference of a fused ASFL window, independent of the program.

It replays the rounds the program ran, from the same seed, data and
initial weights: mobility and association (``bench/scenarios``), the
paper's rate-banded cut rule, the per-round batch draw, each RSU's local
training under its server schedule (``bench/schedules``), the edge
FedAvg and the cloud merge.  It imports nothing of the program.

``dtype`` selects the precision of parameters and activations: float32 is
the reference, with its matmuls and convolutions at the highest precision
(the chip's default is one bfloat16 pass); bfloat16 is the control (the
nearest precision below what the configuration states).
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

# the paper's Eq. 3 rate bands (text-consistent order: a higher rate takes
# an earlier cut); thresholds in bit/s
CUTS = (2, 4, 6, 8)
THRESHOLDS = (60e6, 110e6, 160e6)
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def paper_cuts(serving: np.ndarray, rates: np.ndarray,
               n_units: int) -> np.ndarray:
    r = np.asarray(rates, np.float32)
    band = (r[:, None] > np.asarray(THRESHOLDS, np.float32)[None, :]).sum(1)
    cuts = np.asarray(CUTS)[len(CUTS) - 1 - band]
    cuts = np.clip(cuts, 1, n_units - 1)
    return np.where(serving >= 0, cuts, 0).astype(np.int32)


def batch_indices(prog_seed: int, rnd: int, lengths: np.ndarray,
                  steps: int, batch: int) -> jnp.ndarray:
    """(steps, n, batch) uniform sample indices of round ``rnd``."""
    key = jax.random.fold_in(jax.random.PRNGKey(prog_seed), rnd)
    ln = jnp.asarray(lengths, jnp.int32)
    u = jax.random.uniform(key, (steps, ln.shape[0], batch))
    return jnp.minimum((u * ln[None, :, None]).astype(jnp.int32),
                       ln[None, :, None] - 1)


def adam_leaf(p, g, m, v, count, lr):
    """One Adam step of one leaf; ``count`` is the step number (1-based)."""
    c = jnp.asarray(count, jnp.float32)
    m2 = ADAM_B1 * m + (1 - ADAM_B1) * g
    v2 = ADAM_B2 * v + (1 - ADAM_B2) * g * g
    u = -lr * (m2 / (1 - ADAM_B1 ** c)) / (jnp.sqrt(v2 / (1 - ADAM_B2 ** c))
                                           + ADAM_EPS)
    return (p + u).astype(p.dtype), m2.astype(m.dtype), v2.astype(v.dtype)


def round_states(cell, scen, prog_seed: int, rounds: int):
    """Per-round (serving, cuts, handover) over the fleet, with the
    mobility-coupled presence gate when the traffic asks for it."""
    tr = cell.traffic
    n = tr["n_vehicles"]
    n_units = cell.config["n_units"]
    present = np.ones(n, bool)
    prev = np.full(n, -1, np.int32)
    out = []
    for rnd in range(rounds):
        serving, rates = scen.round_state(rnd, prog_seed)
        serving = np.asarray(serving, np.int32)
        rates = np.asarray(rates, np.float32)
        if tr["churn_source"] == "mobility":
            present2 = serving >= 0
            admit = present2 & present    # a re-arrival waits one round
            serving = np.where(admit, serving, -1).astype(np.int32)
            rates = np.where(admit, rates, 0.0).astype(np.float32)
            present = present2
        cuts = paper_cuts(serving, rates, n_units)
        sched = cuts > 0
        handover = sched & (prev >= 0) & (prev != serving)
        prev = np.where(serving >= 0, serving, -1).astype(np.int32)
        out.append({"serving": serving, "cuts": cuts,
                    "n_handover": int(handover.sum())})
    return out


def run(cell, data: Dict[str, np.ndarray], units0, head0, prog_seed: int,
        rounds: int, dtype=jnp.float32, half_batch: bool = False
        ) -> Dict[str, Any]:
    """Replay ``rounds`` rounds; returns per-round routes and losses, the
    global model after them, and the first gradient (its leaves decide
    which leaves the comparison counts)."""
    tr, cfg = cell.traffic, cell.config
    model = cell.module("models", cfg["model"])
    scen = cell.module("scenarios", tr["scenario"]).Scenario(cell)
    sched_mod = cell.module("schedules", tr["server_schedule"])
    states = round_states(cell, scen, prog_seed, rounds)
    cast = lambda t: jax.tree.map(lambda a: jnp.asarray(a, dtype), t)
    images = jnp.asarray(data["images"], dtype)
    labels = jnp.asarray(data["labels"])
    lengths = np.full(images.shape[0], images.shape[1], np.int64)
    steps, batch = cfg["local_steps"], cfg["train"]["batch_size"]
    units, head = cast(list(units0)), cast(head0)
    trainer = sched_mod.Trainer(model, cell, dtype, half_batch)
    losses: List[float] = []
    first_grad: Optional[Any] = None
    precision = (jax.default_matmul_precision("highest")
                 if jnp.dtype(dtype) == jnp.float32
                 else contextlib.nullcontext())
    with precision:
        for rnd, st in enumerate(states):
            idx = batch_indices(prog_seed, rnd, lengths, steps, batch)
            units, head, loss, grad = trainer.round(
                units, head, images, labels, lengths, st["serving"],
                st["cuts"], idx, scen.n_rsus)
            losses.append(loss)
            if first_grad is None:
                first_grad = grad
    return {"losses": losses, "states": states, "units": units,
            "head": head, "first_grad": first_grad, "n_rsus": scen.n_rsus}
