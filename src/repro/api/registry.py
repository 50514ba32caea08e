"""String-keyed registries behind the declarative experiment layer.

Four registries unify what the three federation engines can execute, so an
:class:`~repro.api.spec.ExperimentSpec` is pure data (strings + numbers) and
every capability a future PR lands plugs in by registering an entry instead
of growing a fourth bespoke loop:

* :data:`MODELS` — ``UnitModel`` builders paired with a matching fleet-data
  builder: the paper's ``resnet18``, the dispatch-bound ``mlp9`` split MLP,
  and every ``TransformerUnitModel``-eligible architecture config (text
  archs, ``frontend == "none"``).  Arch entries build the **reduced** config
  by default (vehicle-side perception scale — the federation simulator's
  regime; pass ``model_kwargs={"reduced": False}`` for the full stack, which
  is datacenter-sized).
* :data:`SCENARIOS` — reuses :data:`repro.core.scenario.SCENARIOS` and adds
  ``"single_rsu"`` (the :class:`~repro.core.fedsim.FederationSim` drive-by
  channel, equivalent to ``fleet.scenario=None``).
* :data:`STRATEGIES` — every ``adaptive.*`` cut strategy, tagged with the
  engines that can execute it (the fused multi-RSU engine runs cut selection
  on-device, so only traced strategies carry the ``"scenario"`` tag).
* :data:`SCHEDULES` — RSU server schedules (paper §III-B ``sequential``,
  arXiv:2405.18707 ``parallel``).

Spec construction validates against these registries and raises actionable
errors (allowed values listed) instead of failing deep inside engine
dispatch.  Model/scenario *builders* are lazy: registering is metadata-only,
heavy imports happen when :func:`build_model`/:func:`build_scenario` run.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

from repro.core import scenario as _scenario
from repro.core.fedsim import (FEDERATION_STRATEGIES, SCENARIO_STRATEGIES,
                               SERVER_SCHEDULES, WIRE_SCHEMES)

# engine kinds an entry can be executed by
FEDERATION = "federation"   # single-RSU FederationSim / CohortEngine
SCENARIO = "scenario"       # multi-RSU ScenarioEngine (fused super-steps)

SINGLE_RSU = "single_rsu"   # the scenario key that routes to FederationSim


# --------------------------------------------------------------------------
# models
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ModelEntry:
    """A federated model: lazy ``UnitModel`` builder + the fleet-data
    builder that produces compatible client shards.

    ``make_data(n_vehicles, per_vehicle, n_test, seed, model=None)`` must
    be a pure function of its arguments (benchmark warm re-runs and the
    api-vs-direct parity tests rely on identical shards); ``model`` is the
    built model, whose sequence length and vocabulary an LM's shards
    take."""
    name: str
    build: Callable[..., Any]
    make_data: Callable[..., Tuple[list, dict]]
    n_units: int
    description: str = ""


MODELS: Dict[str, ModelEntry] = {}


def register_model(entry: ModelEntry) -> ModelEntry:
    MODELS[entry.name] = entry
    return entry


def model_entry(name: str) -> ModelEntry:
    if name not in MODELS:
        raise ValueError(f"unknown model {name!r}; registered models: "
                         f"{' | '.join(sorted(MODELS))}")
    return MODELS[name]


def build_model(name: str, **kwargs):
    return model_entry(name).build(**kwargs)


def _build_resnet(**kw):
    from repro.core.fedsim import ResNetModel
    return ResNetModel(**kw)


def _resnet_data(n_vehicles, per_vehicle, n_test, seed, model=None):
    from repro.data.pipeline import make_federated_data
    return make_federated_data(seed, n_train=per_vehicle * n_vehicles,
                               n_test=n_test, n_clients=n_vehicles)


def _build_mlp9(**kw):
    from repro.models.mlp_unit import MLPUnitModel
    return MLPUnitModel(**kw)


def _mlp9_data(n_vehicles, per_vehicle, n_test, seed, model=None):
    from repro.models.mlp_unit import make_mlp_fleet_data
    return make_mlp_fleet_data(n_vehicles, per_vehicle, seed=seed,
                               n_test=n_test)


def make_lm_fleet_data(n_vehicles: int, per_vehicle: int, n_test: int,
                       seed: int, vocab_size: int, seq_len: int):
    """Synthetic next-token shards for the LM UnitModels: ``images`` are
    token ids (n, seq), ``labels`` the shifted next tokens — the fedsim
    batch convention (core/lm_unit.py)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.data.pipeline import ClientDataset

    rng = np.random.default_rng(seed)

    def shard(n):
        toks = rng.integers(0, vocab_size, size=(n, seq_len + 1))
        return (toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32))

    clients = []
    for i in range(n_vehicles):
        x, y = shard(per_vehicle)
        clients.append(ClientDataset(x, y, i))
    xt, yt = shard(n_test)
    return clients, {"images": jnp.asarray(xt), "labels": jnp.asarray(yt)}


def _arch_model_entry(arch_id: str) -> ModelEntry:
    from repro.configs import get_config
    from repro.models.transformer import segments_of
    cfg = get_config(arch_id)
    # unit granularity (core/lm_unit.py): embedding + one unit per period
    n_units = 1 + sum(n for _, n in segments_of(cfg.reduced()))

    def build(reduced: bool = True, seq_len: int = 8, **lora):
        """The reduced (default) or published config.  LoRA keywords
        (``n_layers``, ``experts_held``, ``vocab_rows``, ``lora_rank``,
        ``lora_alpha``, ``base_seed``) build split LoRA fine-tuning over
        a frozen base (``SplitLoRAUnitModel``) instead of full training."""
        from repro.configs import get_config
        from repro.core.lm_unit import SplitLoRAUnitModel, TransformerUnitModel
        c = get_config(arch_id)
        c = c.reduced() if reduced else c
        if lora:
            return SplitLoRAUnitModel(c, seq_len=seq_len, **lora)
        return TransformerUnitModel(c, seq_len=seq_len)

    def make_data(n_vehicles, per_vehicle, n_test, seed, model=None):
        m = model or build()
        return make_lm_fleet_data(n_vehicles, per_vehicle, n_test, seed,
                                  vocab_size=m.vocab_size,
                                  seq_len=m.seq_len)

    return ModelEntry(
        name=arch_id, build=build, make_data=make_data, n_units=n_units,
        description=f"{cfg.family} LM ({cfg.source}); reduced config by "
                    f"default, model_kwargs={{'reduced': False}} for full")


def _register_builtin_models():
    from repro.configs import ARCH_IDS, get_config

    register_model(ModelEntry(
        "resnet18", _build_resnet, _resnet_data, n_units=9,
        description="the paper's ResNet18 over 32x32x3 (9 split points)"))
    register_model(ModelEntry(
        "mlp9", _build_mlp9, _mlp9_data, n_units=9,
        description="9-unit split MLP — the dispatch-bound federation "
                    "model (models/mlp_unit.py)"))
    for arch_id in ARCH_IDS:
        if get_config(arch_id).frontend == "none":   # text archs only
            register_model(_arch_model_entry(arch_id))


# --------------------------------------------------------------------------
# scenarios
# --------------------------------------------------------------------------

# name -> builder(n_vehicles, seed=..., **kw) -> Scenario; the SINGLE_RSU
# entry is None: the router dispatches it to FederationSim instead.
# Includes the city scale-out fixture (DESIGN.md §15): an RSU lattice with
# Zipf cell popularity sized for the 2-D mesh + slot-paging paths
SCENARIOS: Dict[str, Optional[Callable[..., Any]]] = {
    SINGLE_RSU: None,
    **_scenario.SCENARIOS,
}


def register_scenario(name: str, builder: Callable[..., Any]) -> None:
    SCENARIOS[name] = builder


def scenario_names() -> str:
    return " | ".join(sorted(SCENARIOS))


def build_scenario(name: str, n_vehicles: int, seed: int = 0, **kw):
    if name not in SCENARIOS or SCENARIOS[name] is None:
        raise ValueError(f"{name!r} is not a multi-RSU scenario; "
                        f"registered: {scenario_names()}")
    return SCENARIOS[name](n_vehicles, seed=seed, **kw)


# --------------------------------------------------------------------------
# cut strategies and server schedules
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StrategyEntry:
    name: str
    engines: Tuple[str, ...]      # subset of (FEDERATION, SCENARIO)
    description: str = ""


STRATEGIES: Dict[str, StrategyEntry] = {}


def register_strategy(entry: StrategyEntry) -> StrategyEntry:
    STRATEGIES[entry.name] = entry
    return entry


@dataclasses.dataclass(frozen=True)
class ScheduleEntry:
    name: str
    engines: Tuple[str, ...]
    description: str = ""


SCHEDULES: Dict[str, ScheduleEntry] = {}


def register_schedule(entry: ScheduleEntry) -> ScheduleEntry:
    SCHEDULES[entry.name] = entry
    return entry


@dataclasses.dataclass(frozen=True)
class WireEntry:
    """A cut-boundary wire scheme (DESIGN.md §11): how smashed activations
    (up) and cut-layer gradients (down) cross the vehicle<->RSU link, and
    what the cost model charges for them."""
    name: str
    engines: Tuple[str, ...]
    description: str = ""


WIRES: Dict[str, WireEntry] = {}


def register_wire(entry: WireEntry) -> WireEntry:
    WIRES[entry.name] = entry
    return entry


def wire_names() -> str:
    return " | ".join(sorted(WIRES))


def _register_builtin_strategies():
    descr = {
        "paper": "Eq. 3 rate banding (text-consistent ordering)",
        "paper-literal": "Eq. 3 as printed (low rate -> cut 2)",
        "latency": "per-vehicle argmin of analytic round latency",
        "energy": "weighted latency+energy objective",
        "memory": "vehicle-side byte budget clamp over the paper rule",
        "residence": "deadline-aware largest-offload cut, SKIP when none "
                     "fits the remaining cell residence",
    }
    for name in sorted(set(FEDERATION_STRATEGIES) | set(SCENARIO_STRATEGIES)):
        engines = tuple(
            kind for kind, names in ((FEDERATION, FEDERATION_STRATEGIES),
                                     (SCENARIO, SCENARIO_STRATEGIES))
            if name in names)
        register_strategy(StrategyEntry(name, engines, descr.get(name, "")))

    register_schedule(ScheduleEntry(
        "sequential", (FEDERATION, SCENARIO),
        "paper §III-B: the RSU consumes the cohort's smashed batches one "
        "at a time, in cohort order"))
    register_schedule(ScheduleEntry(
        "parallel", (SCENARIO,),
        "arXiv:2405.18707: one |D_n|-weighted mean-gradient server step "
        "per local step, batched over the whole cohort"))
    register_schedule(ScheduleEntry(
        "streaming", (SCENARIO,),
        "buffered-asynchronous (FedBuff-style): per-RSU StreamBuffer of "
        "pending deltas, staleness-weighted merge whenever it reaches "
        "stream.buffer_size (core/streaming.py, DESIGN.md §14)"))
    assert set(SCHEDULES) == set(SERVER_SCHEDULES)

    register_wire(WireEntry(
        "none", (FEDERATION, SCENARIO),
        "dense fp32 smashed tensors, uncompressed both directions"))
    register_wire(WireEntry(
        "int8", (FEDERATION, SCENARIO),
        "per-128-group symmetric int8 quant of activations and cut-layer "
        "gradients (~4x fewer bytes; kernels/quant.py)"))
    register_wire(WireEntry(
        "topk_int8", (FEDERATION, SCENARIO),
        "per-group top-k sparsify + int8 pack with per-vehicle error "
        "feedback in the superstep engine (>=4x on top of quant; "
        "kernels/wire.py)"))
    assert set(WIRES) == set(WIRE_SCHEMES)


_register_builtin_models()
_register_builtin_strategies()
