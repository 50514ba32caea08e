"""Builds the system under test from a cell and drives its timed path.

The engine is the program's ``ScenarioEngine``, assembled from an
``ExperimentSpec`` as ``repro.api.build_engine`` assembles it, except that
the fleet data and the initial weights are the benchmark's own, made from
the seed: the engine's model is wrapped so that its ``init`` returns them.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import jax
import numpy as np


def program_seed(cell) -> int:
    """The seed of the program's own random draws (batch indices, shadow
    fading), fixed by the traffic mix: the program compiles it into its
    fused window as a constant, so a seed per run would compile a new
    program in every run.  Data and weights come from ``--seed``."""
    return int(cell.traffic["program_seed"])


class SeededModel:
    """The program's model with the benchmark's initial weights."""

    def __init__(self, inner, units, head):
        self._inner, self._units, self._head = inner, list(units), head

    def init(self, key):
        return list(self._units), self._head

    def __getattr__(self, name):
        return getattr(self._inner, name)


def make_spec(cell):
    from repro import api
    cfg, tr = cell.config, cell.traffic
    t = cfg["train"]
    return api.ExperimentSpec(
        model=cfg["model"], model_kwargs=dict(cfg.get("model_kwargs", {})),
        train=api.TrainConfig(
            scheme="asfl", batch_size=t["batch_size"], lr=t["lr"],
            optimizer=t["optimizer"], local_steps=cfg["local_steps"],
            rounds=tr["horizon_rounds"], eval_every=t["eval_every"],
            server_schedule=tr["server_schedule"], wire=tr["wire"]),
        adaptive=api.AdaptiveConfig(strategy=cfg["adaptive_strategy"]),
        fleet=api.FleetConfig(
            n_vehicles=tr["n_vehicles"], scenario=tr["scenario"],
            scenario_kwargs=dict(tr["scenario_kwargs"]),
            cloud_sync_every=tr["cloud_sync_every"],
            round_interval_s=tr["round_interval_s"],
            per_vehicle_samples=tr["per_vehicle_samples"],
            test_samples=tr["test_samples"]),
        runtime=api.RuntimeConfig(
            seed=program_seed(cell), superstep=tr["superstep"],
            slot_capacity=tr["slot_capacity"],
            superstep_layout=tr["superstep_layout"], precompile=True,
            mesh_devices=tr["mesh_devices"], fleet_axis=tr["fleet_axis"],
            page_slots=tr["page_slots"]),
        stream=api.StreamConfig(churn_source=tr["churn_source"]))


def build_engine(spec, data: Dict[str, np.ndarray], units, head):
    """``repro.api.build_engine`` with the benchmark's data and weights."""
    import jax.numpy as jnp
    from repro.api import registry
    from repro.core import fleet_sharding
    from repro.core.fedsim import ScenarioEngine
    from repro.data.pipeline import ClientDataset

    model = SeededModel(registry.model_entry(spec.model).build(
        **spec.model_kwargs), units, head)
    clients = [ClientDataset(data["images"][i], data["labels"][i], i)
               for i in range(data["images"].shape[0])]
    test = {"images": jnp.asarray(data["test_images"]),
            "labels": jnp.asarray(data["test_labels"])}
    cfg = spec.to_sim_config()
    f = spec.fleet
    mesh = fleet_sharding.from_config(cfg, spec.engine_kind,
                                      fleet_size=f.n_vehicles)
    kw = dict(f.scenario_kwargs)
    kw.setdefault("seed", spec.runtime.seed)
    sc = registry.build_scenario(f.scenario, f.n_vehicles, **kw)
    return ScenarioEngine(model, clients, test, cfg, sc,
                          cloud_sync_every=f.cloud_sync_every, mesh=mesh)


def host_tree(units, head):
    from repro.core import fleet_sharding
    u, h = fleet_sharding.host_fetch((list(units), head))
    return list(u), h


def round_record(m) -> Dict[str, Any]:
    return {"round": m.round, "loss": float(m.loss),
            "cuts": list(m.cuts), "rsu_loads": list(m.rsu_loads),
            "n_handover": int(m.n_handover),
            "n_scheduled": int(m.n_scheduled)}


def timed_window(engine, rnd0: int, k: int, horizon: int, seconds: float,
                 annotate: bool = False) -> Dict[str, Any]:
    """Closed loop of ``run_superstep`` windows from ``rnd0``: each window
    starts after the previous one's host pull.  Runs until ``seconds``
    have passed or the planned horizon is reached.  Counts compile events
    inside the loop and rounds attempted / failed (a window that raised,
    or a round whose loss is not finite)."""
    compiles: List[str] = []

    def on_event(name, *args, **kwargs):
        if "compile" in name:
            compiles.append(name)

    records, attempted, failed, windows = [], 0, 0, []
    jax.monitoring.register_event_duration_secs_listener(on_event)
    rnd = rnd0
    t0 = time.perf_counter()
    t_end = t0
    try:
        while rnd + k <= horizon:
            attempted += k
            w0 = time.perf_counter()
            try:
                if annotate:
                    with jax.profiler.TraceAnnotation("bench.window"):
                        ms = engine.run_superstep(rnd, k)
                else:
                    ms = engine.run_superstep(rnd, k)
            except Exception as e:  # a window that raised fails its rounds
                failed += k
                print(f"window at round {rnd} raised "
                      f"{type(e).__name__}: {e}", flush=True)
                t_end = time.perf_counter()
                break
            t_end = time.perf_counter()
            windows.append(t_end - w0)
            for m in ms:
                records.append(round_record(m))
                if not np.isfinite(m.loss):
                    failed += 1
            rnd += k
            if t_end - t0 >= seconds:
                break
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    return {"records": records, "attempted": attempted, "failed": failed,
            "elapsed_s": t_end - t0, "compiles": len(compiles),
            "window_s": windows, "horizon_reached": rnd + k > horizon}


def peak_bytes() -> int:
    """Peak bytes in use on the fullest local device."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))
