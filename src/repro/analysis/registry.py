"""Layer 2 registry: the trainer × engine × plane × sharding matrix.

The jaxpr auditor needs *live* closures with their exact traced call
signatures — the trainers build them lazily and cache them, so the only
faithful way to enumerate "every registered jitted step closure" is to
run a tiny workload with capture armed (``TrainerBase.capture_jitted``)
and collect what the drivers actually called.

The matrix mirrors the pinned test surface:

* trainer:  single ``RWSADMMTrainer`` / ``FleetRWSADMMTrainer``
  (round-robin), plus one simultaneous-fleet cell so ``_sim_step_impl``
  is covered;
* engine:   ``eager`` (the per-round jitted step) and the compiled
  ``scan`` / ``scan_fused`` chunk drivers;
* plane:    ``dense`` (stacked DeviceData) and ``lazy`` (ClientStore
  rows); both hand their data to the step as a traced argument, which
  the baked-constant budget enforces;
* sharding: unsharded and a 1-device ``FLSharding`` mesh (the in-process
  sharded-path pin from the sharded-plane tests) — the sharded chunk
  must donate its carry, the unsharded one must not.

Workloads are deliberately tiny (8 clients, 400 MNIST-synthetic rows)
so the whole sweep stays in CI-smoke territory.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, Sequence

import jax
import numpy as np

from .jaxpr_audit import ClosureAudit, audit_closure

N_CLIENTS = 8
EAGER_ROUNDS = 2
CHUNK_ROUNDS = 3
SCAN_ENGINES = ("scan", "scan_fused")

@dataclasses.dataclass(frozen=True)
class CellSpec:
    """One cell of the audit matrix."""
    trainer: str          # "single" | "fleet" | "fleet_sim"
    plane: str            # "dense" | "lazy"
    sharded: bool

    @property
    def key(self) -> str:
        shard = "sharded" if self.sharded else "unsharded"
        return f"{self.trainer}/{self.plane}/{shard}"


#: the full audited matrix (fleet_sim covers _sim_step_impl once)
MATRIX: tuple[CellSpec, ...] = tuple(
    CellSpec(trainer, plane, sharded)
    for trainer in ("single", "fleet")
    for plane in ("dense", "lazy")
    for sharded in (False, True)
) + (CellSpec("fleet_sim", "dense", False),)

#: the compile-budget smoke subset (Layer 3) — fixed forever so the
#: golden counts in analysis/compile_budget.json stay comparable
SMOKE: tuple[CellSpec, ...] = (
    CellSpec("single", "dense", False),
    CellSpec("single", "lazy", False),
    CellSpec("fleet", "dense", False),
)


@dataclasses.dataclass
class CapturedClosure:
    """One jitted step call recorded by a trainer, audit-ready."""
    cell: str
    engine: str
    name: str             # trainer-side label, e.g. "chunk:scan"
    fn: object
    args: tuple
    kwargs: dict
    expect_donation: bool | None

    @property
    def key(self) -> str:
        return f"{self.cell}/{self.engine}/{self.name}"

    def audit(self) -> ClosureAudit:
        report = audit_closure(
            self.name, self.fn, self.args, self.kwargs,
            expect_donation=self.expect_donation)
        report.name = self.key
        for i, f in enumerate(report.findings):
            report.findings[i] = dataclasses.replace(
                f, path=f"<jaxpr:{self.key}>")
        return report


@functools.lru_cache(maxsize=1)
def _workload():
    """The shared tiny federated workload (built once per process)."""
    from repro.data import (factory_from_federated, make_image_dataset,
                            pathological_split)
    from repro.data.loader import build_federated
    from repro.fl.base import to_device_data
    from repro.models.small import get_model

    imgs, labels = make_image_dataset(400, seed=0)
    parts = pathological_split(labels, N_CLIENTS, seed=0)
    fed = build_federated(imgs, labels, parts)
    dense = to_device_data(fed)
    factory = factory_from_federated(fed)
    model = get_model("mlr", (28, 28, 1))
    return dense, factory, model


def build_cell(spec: CellSpec):
    """Construct the trainer for one matrix cell (fresh every call —
    the compile-budget sentinel depends on cold jit caches)."""
    import dataclasses as _dc

    from repro.core.rwsadmm import RWSADMMHparams
    from repro.fl.fleet_trainer import FleetRWSADMMTrainer
    from repro.fl.rwsadmm_trainer import RWSADMMTrainer
    from repro.fl.sharding import FLSharding
    from repro.scenarios import get_scenario_config

    dense, factory, model = _workload()
    scen = _dc.replace(get_scenario_config("lossy_links"),
                       graph_backend="dense",
                       neighbor_k_max=N_CLIENTS)
    kw = dict(zone_size=4, batch_size=16, solver="closed_form",
              scenario=scen, seed=0,
              mesh=FLSharding() if spec.sharded else None)
    lazy = spec.plane == "lazy"
    data = factory if lazy else dense
    if lazy:
        kw["store_capacity"] = N_CLIENTS
    hp = RWSADMMHparams(beta=10.0)
    if spec.trainer == "single":
        return RWSADMMTrainer(model, data, hp, **kw)
    mode = "simultaneous" if spec.trainer == "fleet_sim" else "roundrobin"
    return FleetRWSADMMTrainer(model, data, hp, n_walkers=3,
                               sync_every=3, fleet_mode=mode, **kw)


def run_cell(spec: CellSpec,
             engines: Sequence[str] = ("eager",) + SCAN_ENGINES,
             ) -> list[CapturedClosure]:
    """Run one cell's tiny workload with capture armed; return every
    jitted step call the drivers made, audit-ready."""
    captured: list[CapturedClosure] = []
    trainer = build_cell(spec)

    for engine in engines:
        # Fresh state per engine: the sharded chunk donates its carry,
        # so a state that went through one chunk is already consumed.
        state = trainer.init_state(jax.random.PRNGKey(0))
        with trainer.capture_jitted() as entries:
            if engine == "eager":
                rng = np.random.default_rng(0)
                s = state
                for rnd in range(EAGER_ROUNDS):
                    s, _ = trainer.round(s, rnd, rng)
            else:
                rng = np.random.default_rng(1)
                sched = trainer.schedule(CHUNK_ROUNDS, rng)
                trainer.run_chunk(state, sched, engine=engine)
        seen: set[str] = set()
        for name, fn, args, kwargs in entries:
            if name in seen:          # eager records one call per round
                continue
            seen.add(name)
            # Donation is asserted on the chunk drivers only (the eager
            # step is never donated); sharded ⇒ donated, else not.
            expect = spec.sharded if name.startswith("chunk") else None
            captured.append(CapturedClosure(
                cell=spec.key, engine=engine, name=name, fn=fn,
                args=args, kwargs=kwargs, expect_donation=expect))
    return captured


def collect_closures(cells: Iterable[CellSpec] = MATRIX,
                     engines: Sequence[str] = ("eager",) + SCAN_ENGINES,
                     ) -> list[CapturedClosure]:
    out: list[CapturedClosure] = []
    for spec in cells:
        out.extend(run_cell(spec, engines))
    return out


def audit_matrix(cells: Iterable[CellSpec] = MATRIX,
                 engines: Sequence[str] = ("eager",) + SCAN_ENGINES,
                 ) -> list[ClosureAudit]:
    return [c.audit() for c in collect_closures(cells, engines)]
