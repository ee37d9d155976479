"""Event-driven asynchronous federated runtime (RELIEF beyond the barrier).

The synchronous engine (core/engine.py) charges every round to its slowest
device — exactly the straggler coupling the paper identifies as the cost of
system-modality heterogeneity. This runtime removes the barrier: each client
trains continuously against the freshest model it has pulled, completions
arrive on a priority queue of simulated (compute + comm) times
(sim/events.py), and the server applies *buffered, staleness-discounted
cohort aggregation*:

  * a FedBuff-style buffer of size K — the server folds the model forward
    once K completions are queued (K = N + homogeneous fleet degenerates to
    the synchronous engine, the parity anchor in tests/test_async_engine.py);
  * each buffered update is discounted by 1/(1+s)^a where s counts server
    versions elapsed since the client pulled (strategies.AsyncStrategy);
  * aggregation reuses the mdlora.GroupLayout block interface through the
    whole-cohort ``aggregation.cohort_reduce``, so rare-modality blocks still
    aggregate only within their possession cohort and an empty cohort
    freezes its block — no interference, no NaNs, no matter which subset of
    the fleet happens to sit in the buffer.

Simulated time and energy come from the same device model as the sync
engine (sim/timing.py), so bench_async.py's wall-clock/energy comparisons
are apples-to-apples.

Two runtimes share one server flush (``_ServerFlushMixin._flush_arrays``):

``AsyncFedRun``           the reference event loop — a heap of per-client
                          ``_Pending`` objects, gradients computed eagerly
                          at dispatch. Exact, but O(N) Python state: fine
                          for N~100, hopeless at fleet scale.
``VectorizedAsyncFedRun`` the structure-of-arrays fleet simulator
                          (sim/fleet.py): all per-client state in flat
                          NumPy arrays, the heap replaced by vectorized
                          next-K extraction with the same FIFO tie-break,
                          and gradient work decoupled from system
                          simulation via ``grad_mode``:

    "dispatch"  gradients at dispatch time for every dispatched client —
                event-for-event equivalent to AsyncFedRun (the history-
                equivalence anchor in tests/test_fleet.py); small fleets.
    "cohort"    system time/energy/staleness simulated for the FULL fleet
                of N clients, but ``local_update`` runs only for the
                M = buffer_size clients actually flushed (M << N), each
                against the retained snapshot of the model version it
                pulled (a bounded ring of ``snapshot_ring`` versions).
                Batch draws are counter-based (seeded by (seed, client,
                completion ticket)), so results are deterministic and
                independent of event interleaving.
    "none"      pure system simulation — no gradients, loss is NaN; this
                is what lets benchmarks/bench_fleet.py sweep N up to 10^6
                and record staleness/energy distributions at fleet scale.

A ``PopulationModel`` (churn_rate / arrival_rate on AsyncFedConfig) adds
arrivals and churn: departing clients lose in-flight work and stop accruing
energy; arrivals rejoin idle and are redispatched on the next event.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import dist, obs
from repro.core import aggregation as AG
from repro.core import mdlora
from repro.core.engine import (AllocPlan, FedConfig, _rank_gates, allocate,
                               allocate_rows, draw_client_batches,
                               make_local_update, plan_allocation,
                               scenario_fed_kwargs)
from repro.core.strategies import AsyncStrategy
from repro.core.tasks import MMTask
from repro.sim import FaultModel, FaultRuntime, FleetConfig
from repro.sim import timing as T
from repro.sim.events import AsyncTrace, EventQueue, completion_times
from repro.sim.fleet import (FleetState, PopulationModel, pack_group_bits,
                             unpack_group_bits)

Array = jax.Array

GRAD_MODES = ("dispatch", "cohort", "none")


@dataclasses.dataclass(frozen=True)
class AsyncFedConfig(FedConfig):
    """FedConfig + event-runtime knobs. ``rounds`` keeps its meaning as the
    *logical* round budget: the default total work is rounds * N client
    updates, matching the synchronous engine's total local compute."""
    jitter_sigma: float = 0.0  # lognormal compute-time noise (0 = exact)
    total_updates: int | None = None  # overrides rounds * N when set
    agg_impl: str = "xla"  # cohort-agg reduction: "xla" | "pallas"
    agg_interpret: bool | None = None  # Pallas interpret (None = auto: CPU)
    # uplink codec: "none" ships fp32 deltas; "int8" quantizes client-side
    # (dist.quantize_int8 + error feedback) and the server ingests the
    # compressed payload natively — dequantization and the staleness
    # discount are fused into the cohort reduction (cohort_reduce), the
    # fp32 client stack is never rebuilt, and upload bytes drop 4x.
    uplink_codec: str = "none"
    # --- vectorized fleet runtime (VectorizedAsyncFedRun) ---
    grad_mode: str = "dispatch"  # dispatch | cohort | none (see module doc)
    snapshot_ring: int = 8  # retained model versions for cohort gradients
    churn_rate: float = 0.0  # departures per alive client per sim-second
    arrival_rate: float = 0.0  # re-arrivals per departed client per sim-sec
    # fleet fault injection (sim/faults.py): Byzantine delta corruption,
    # mid-round dropout, stalls. None (or byzantine_frac = 0) = fault-free.
    faults: FaultModel | None = None
    # time-varying modality availability (sim/scenarios.StreamingSchedule):
    # when set, each dispatch evaluates the client's LIVE modality mask at
    # the dispatch time — allocation candidates, local-training masks, and
    # the flush's cohort membership all follow it. None = the fleet's
    # static possession mask.
    modality_schedule: Any = None

    @classmethod
    def from_scenario(cls, spec, fleet=None, **overrides):
        """Build the async runtime config a ``sim.scenarios.ScenarioSpec``
        describes (duck-typed). For streaming scenarios the
        ``modality_schedule`` is derived from the spec (pass ``fleet`` to
        reuse an already-built fleet's possession base)."""
        kw = scenario_fed_kwargs(spec) | dict(
            jitter_sigma=spec.jitter_sigma, total_updates=spec.total_updates,
            uplink_codec=spec.uplink_codec, grad_mode=spec.grad_mode,
            faults=spec.faults)
        if (getattr(spec, "missing", None) == "streaming"
                and "modality_schedule" not in overrides):
            from repro.sim.scenarios import schedule_for

            kw["modality_schedule"] = schedule_for(spec, fleet)
        return cls(**(kw | overrides))


@dataclasses.dataclass
class AsyncFedState:
    round: int  # server model version = number of flushes applied
    trainable: Any
    dbar: np.ndarray  # [G] EMA divergence (drives allocation, Eq. 5-6)
    mag_ema: np.ndarray  # [G]
    rng: np.random.Generator
    sim_time: float = 0.0


def _make_state(G: int, trainable0: Any, seed: int) -> AsyncFedState:
    return AsyncFedState(round=0, trainable=trainable0,
                         dbar=np.ones(G) * 1e-6, mag_ema=np.ones(G),
                         rng=np.random.default_rng(seed))


UPLINK_CODECS = ("none", "int8")


def _check_strategy(strategy: AsyncStrategy, fed: AsyncFedConfig,
                    fleet: FleetConfig | None = None) -> None:
    if strategy.personal or strategy.share_only:
        raise ValueError("async runtime keeps one global model; "
                         "personalized strategies are sync-only")
    if strategy.agg not in ("cohort", "fedavg"):
        raise ValueError(f"async runtime supports cohort/fedavg "
                         f"aggregation, not {strategy.agg!r}")
    if fed.uplink_codec not in UPLINK_CODECS:
        raise ValueError(f"uplink_codec must be one of {UPLINK_CODECS}, "
                         f"got {fed.uplink_codec!r}")
    if strategy.robust not in AG.ROBUST_AGGREGATORS:
        raise ValueError(f"robust must be one of {AG.ROBUST_AGGREGATORS}, "
                         f"got {strategy.robust!r}")
    if strategy.selective and not 0.0 < strategy.comm_budget <= 1.0:
        raise ValueError(f"comm_budget must be in (0, 1], "
                         f"got {strategy.comm_budget}")
    sched = fed.modality_schedule
    if sched is not None:
        if strategy.alloc == "random":
            raise ValueError("alloc='random' redraws fleet-shaped noise per "
                             "dispatch; incompatible with a time-varying "
                             "modality schedule")
        if fleet is not None and (sched.N != fleet.N or sched.M != fleet.M):
            raise ValueError(f"modality_schedule shape ({sched.N}, {sched.M})"
                             f" does not match fleet ({fleet.N}, {fleet.M})")


def _make_fault_runtime(fed: AsyncFedConfig,
                        fleet: FleetConfig) -> FaultRuntime | None:
    if fed.faults is not None and fed.faults.active:
        return FaultRuntime(fed.faults, fleet.modality_mask)
    return None


def _selective_upload(layout: mdlora.GroupLayout, deltas: Any,
                      S: np.ndarray, budget: float) -> np.ndarray:
    """FedMFS selective modality communication: which trained blocks to
    upload. Per client, blocks are ranked by Shapley-style utility per byte
    — ||delta_g||^2 / size_g, the marginal-contribution proxy of
    arXiv:2310.07048 — and taken greedily while the cumulative size fits
    ``budget`` x (the client's full trained upload). The top block is always
    taken (an empty upload would stall the protocol); later blocks that
    overflow are skipped, not a hard stop, so the knapsack packs tightly.

    Deterministic in (deltas, S): no rng, stable sort — the heap and
    vectorized runtimes select identical sets for identical dispatches.
    """
    norms = np.asarray(jax.vmap(
        lambda t: mdlora.group_norms(layout, t))(deltas))  # [K, G] squared
    sizes = np.asarray(layout.sizes, np.float64)
    S = np.asarray(S, bool)
    S_up = np.zeros_like(S)
    for k in range(S.shape[0]):
        cand = np.nonzero(S[k])[0]
        if len(cand) == 0:
            continue
        cap = budget * float(sizes[cand].sum())
        density = norms[k, cand] / np.maximum(sizes[cand], 1.0)
        order = cand[np.argsort(-density, kind="stable")]
        spent = 0.0
        for j, g in enumerate(order):
            if j == 0 or spent + sizes[g] <= cap:
                S_up[k, g] = True
                spent += sizes[g]
    return S_up


def _gate_rows(layout: mdlora.GroupLayout, deltas: Any,
               S_up: np.ndarray) -> Any:
    """Zero the non-uploaded blocks of a client-stacked delta pytree."""
    gates = jnp.asarray(S_up, jnp.float32)
    return jax.vmap(lambda t, g: mdlora.group_gate_tree(layout, t, g))(
        deltas, gates)


def _history_init() -> dict:
    return {"flush": [], "loss": [], "sim_time_s": [], "energy_j": [],
            "upload_mb": [], "staleness_mean": [], "f1": [],
            "f1_flush": [], "divergence": [], "selected_frac": []}


@dataclasses.dataclass
class _Pending:
    """One in-flight client update, created at dispatch (the delta is a pure
    function of the pulled model + batch draw, so simulation computes it
    eagerly; only its *arrival time* is event-driven)."""
    client: int
    version: int  # server version pulled at dispatch
    delta: Any  # trainable-shaped update
    loss: float
    S_row: np.ndarray  # [G] groups uploaded (= trained unless selective)
    t_comp: float
    t_comm: float
    upload_bytes: float
    mmask_row: np.ndarray  # [M] live modality mask at dispatch
    # fault-injected mid-round crash: the completion event still fires (it
    # times the client's reboot + redispatch) but is never absorbed — no
    # buffer entry, no energy/upload accounting, no progress
    dropped: bool = False


def make_server_step(layout: mdlora.GroupLayout, strategy: AsyncStrategy,
                     fed: AsyncFedConfig):
    """The server half of a flush as one compiled program, built once per
    run and reused by every flush:

        server_step(trainable, deltas, scales, S, mmask_rows, staleness,
                    fresh, bd=...) -> (new trainable, stats [4, G])

    ``deltas`` is the client stack ([K, ...] leaves; the int8 codes when
    ``scales``, its [K] dequant scales, is given), ``S`` [K, G] the uploaded
    groups (0 for rows dropped as too stale), ``mmask_rows`` [K, M] the
    modality masks the updates were trained with, ``staleness`` [K] and
    ``fresh`` [K] the rows within ``max_staleness``. ``stats`` rows are the
    divergence ``d``, the cohort counts ``cnt``, the mean squared group norm
    of the uploaded rows ``mag`` and the groups any row uploaded ``sel``;
    the caller reads them back in one transfer. ``bd`` is the fusion
    kernel's block size, resolved outside the trace
    (``aggregation.fusion_block``). Everything else static (treedef and
    shapes, int8 or not, the aggregation rule and its robust reducer, the
    kernel, the discount exponent, ``server_lr``) is fixed by the run or is
    part of jit's own cache key.
    """
    a = strategy.staleness_exponent

    def server_step(trainable, deltas, scales, S, mmask_rows, staleness,
                    fresh, bd):
        quant = scales is not None
        trained = S.astype(jnp.float32)
        mmask = mmask_rows.astype(jnp.float32)
        scale = None if a == 0.0 else AG.staleness_discounts(staleness, a)
        # quantized ingest applies the discount *inside* the fused
        # reduction, so keep it out of the numerator (defer_scale)
        wkw = dict(client_scale=scale, defer_scale=quant)
        if strategy.agg == "cohort":
            W = AG.cohort_weights(layout, trained, mmask, **wkw)
        else:  # fedavg: every (fresh) buffered client into every non-empty
            # group — max_staleness drops apply here too
            ones = ((layout.sizes > 0)[None, :] & fresh[:, None]).astype(
                jnp.float32)
            W = AG.cohort_weights(layout, ones, jnp.ones_like(mmask), **wkw)
        # divergence cohort: possession AND trained (paper Eq. 5 on the
        # buffered subset)
        C = (layout.accessible(mmask_rows, jnp) & (S > 0)).astype(
            jnp.float32)
        agg_tree, d, cnt = AG.cohort_reduce(
            layout, deltas, W, C, scales=scales, staleness=staleness,
            exponent=a, robust=strategy.robust, trim_frac=strategy.trim_frac,
            krum_f=strategy.krum_f, impl=fed.agg_impl,
            interpret=fed.agg_interpret, bd=bd)
        new = jax.tree.map(
            lambda t, g: (t.astype(jnp.float32)
                          + fed.server_lr * g).astype(t.dtype),
            trainable, agg_tree)
        # magnitude diagnostic over the buffer: the int8 codes times their
        # scales fuse into the norms, no fp32 stack is kept
        src = dist.dequantize_int8_stacked(deltas, scales) if quant else deltas
        norms = jax.vmap(lambda t: mdlora.group_norms(layout, t))(src)
        mag = (jnp.sum(norms * trained, axis=0)
               / jnp.maximum(jnp.sum(trained, axis=0), 1.0))
        sel = jnp.any(S > 0, axis=0).astype(jnp.float32)
        return new, jnp.stack([d, cnt, mag, sel])

    return jax.jit(server_step, static_argnames="bd")


class _ServerFlushMixin:
    """What both async runtimes share: a dispatch's allocation under a live
    modality schedule, and the server-side flush.

    Expects ``task/strategy/fleet/fed/state/plan/trace/history/
    server_step`` attributes on self. ``server_step`` is the run's
    compiled server program (``make_server_step``): the cohort weights,
    the cohort reduction, the fold-in and the statistics.
    """

    def _allocate(self, idx: np.ndarray, live_mm: np.ndarray) -> np.ndarray:
        """S rows [len(idx), G] for a dispatch, from the run's AllocPlan.
        Under a modality schedule the candidates (for modality-aware
        allocators) and the mandatory blocks follow ``live_mm``, the [B, M]
        masks live at dispatch time; the Eq. 7 budgets stay the plan's."""
        if self.fed.modality_schedule is None:
            return allocate_rows(self.plan, self.strategy, self.state, idx)
        layout = self.task.layout
        unaware = self.strategy.alloc in ("full", "magnitude", "depth")
        return allocate_rows(
            self.plan, self.strategy, self.state, idx,
            cand=None if unaware else layout.accessible(live_mm),
            mandatory=(layout.mandatory(live_mm)
                       if self.strategy.mandatory else None))

    @property
    def _uplink_bytes_per_param(self) -> float:
        """Simulated uplink cost per shipped parameter (int8 = 1 byte)."""
        return 1.0 if self.fed.uplink_codec == "int8" else 4.0

    def _flush_arrays(self, deltas: Any, S: np.ndarray,
                      client_ids: np.ndarray, losses: np.ndarray | None,
                      staleness: np.ndarray,
                      mmask_rows: np.ndarray | None = None) -> dict:
        """Fold one buffered cohort into the global model (one server
        version). ``deltas``: client-stacked pytree ([K, ...] leaves) or an
        ``aggregation.QuantizedStack`` (int8 uplink — ingested natively by
        the compiled server step, the fp32 stack never rebuilt),
        rows aligned with ``S``/``client_ids``/``losses``/``staleness`` —
        all sorted by client id so a full homogeneous buffer reproduces the
        synchronous stack exactly. ``deltas=None`` = system-only flush
        (grad_mode "none"): staleness/energy accounting advances, the model
        and divergence state stay untouched, loss records as NaN. The
        ``fed.aggregate`` span's ``program`` is 1 when the compiled server
        step ran, 0 for the system-only flush."""
        with obs.span("fed.aggregate", program=int(deltas is not None)):
            fleet, fed = self.fleet, self.fed
            layout, state = self.task.layout, self.state
            staleness = np.asarray(staleness, np.float64)
            # cohorts are per-flush: under a streaming schedule each
            # buffered update carries the modality mask it was dispatched
            # with, and both the Eq. 3-4 cohort weights and the Eq. 5
            # divergence cohorts follow it instead of the fleet's static
            # possession
            if mmask_rows is None:
                mmask_rows = fleet.modality_mask[client_ids]
            fresh = np.ones(len(client_ids), bool)
            if self.strategy.max_staleness is not None:
                fresh = staleness <= self.strategy.max_staleness
                S = S * fresh[:, None]

            if deltas is not None:
                with obs.span("fed.ingest"):
                    quant = isinstance(deltas, AG.QuantizedStack)
                    stack, scales = ((deltas.q, deltas.scales) if quant
                                     else (deltas, None))
                    state.trainable, stats = self.server_step(
                        state.trainable, stack, scales,
                        np.asarray(S, np.float32),
                        np.asarray(mmask_rows, bool),
                        staleness.astype(np.float32), fresh,
                        bd=AG.fusion_block(layout, stack, quant=quant,
                                           impl=fed.agg_impl,
                                           interpret=fed.agg_interpret))
                with obs.span("fed.stats"):  # the one host read, and EMAs
                    d_np, cnt, mag, sel = jax.device_get(stats)
                    t = cnt > 0
                    state.dbar[t] = (fed.gamma * d_np
                                     + (1.0 - fed.gamma) * state.dbar)[t]
                    t = sel > 0
                    state.mag_ema[t] = (0.5 * state.mag_ema + 0.5 * mag)[t]
                    loss = float(np.mean(losses))
            else:  # system-only simulation: no gradient work this flush
                d_np = np.zeros(layout.G)
                loss = float("nan")

            state.round += 1
            self.trace.flushes += 1
            rec = {"flush": state.round, "sim_time_s": state.sim_time,
                   "loss": loss, "staleness_mean": float(staleness.mean()),
                   "energy_j": self.trace.energy_j,
                   "upload_mb": self.trace.upload_mb,
                   "selected_frac": float(S.mean()), "divergence": d_np}
            for key in ("flush", "loss", "sim_time_s", "energy_j", "upload_mb",
                        "staleness_mean", "selected_frac", "divergence"):
                self.history[key].append(rec[key])
            return rec

    def _log_and_eval(self, rec: dict, dataset, log_every: int,
                      tag: str) -> None:
        if log_every and rec["flush"] % log_every == 0:
            print(f"[{tag}] flush "
                  f"{rec['flush']:5d} t={rec['sim_time_s']:9.3f}s"
                  f" loss {rec['loss']:.4f} "
                  f"stale {rec['staleness_mean']:.1f}")
        if (self.fed.eval_every and dataset is not None
                and rec["flush"] % self.fed.eval_every == 0):
            self.history["f1"].append(self.evaluate(dataset))
            self.history["f1_flush"].append(rec["flush"])

    # -- evaluation -----------------------------------------------------------

    def evaluate(self, dataset) -> float:
        xs = np.concatenate(dataset.test_x)
        ys = np.concatenate(dataset.test_y)
        return self.task.eval_f1(self.state.trainable, xs, ys)


@dataclasses.dataclass
class AsyncFedRun(_ServerFlushMixin):
    task: MMTask
    strategy: AsyncStrategy
    fleet: FleetConfig
    fed: AsyncFedConfig
    state: AsyncFedState
    local_update: Any
    rank_gate: Any
    queue: EventQueue
    buffer: list
    trace: AsyncTrace
    history: dict
    proto: Any  # trainable prototype (explicit, not an id()-keyed cache)
    server_step: Any  # make_server_step's compiled program
    # client-side error-feedback residuals (uplink_codec="int8"): the
    # quantization error stays on the device and is added to its next
    # update, so the compressed stream telescopes to the uncompressed one
    ef: dict = dataclasses.field(default_factory=dict)
    fx: FaultRuntime | None = None  # fault injection (fed.faults)
    # fleet-static allocation inputs (None for alloc="random", which redraws
    # fleet-shaped noise per dispatch through the legacy allocate() path to
    # preserve its rng stream)
    plan: AllocPlan | None = None

    @classmethod
    def create(cls, task: MMTask, trainable0: Any, strategy: AsyncStrategy,
               fleet: FleetConfig, fed: AsyncFedConfig) -> AsyncFedRun:
        _check_strategy(strategy, fed, fleet)
        state = _make_state(task.layout.G, trainable0, fed.seed)
        trace = AsyncTrace()
        trace.init_fleet(fleet.N)
        plan = (plan_allocation(strategy, task, fleet, fed, task.layout.flops)
                if strategy.alloc != "random" else None)
        return cls(task, strategy, fleet, fed, state,
                   make_local_update(task, fed, strategy.prox_mu),
                   _rank_gates(trainable0, strategy, fleet), EventQueue(),
                   [], trace, _history_init(), trainable0,
                   make_server_step(task.layout, strategy, fed),
                   fx=_make_fault_runtime(fed, fleet), plan=plan)

    # -- client dispatch ------------------------------------------------------

    def _dispatch(self, clients: np.ndarray, now: float, dataset) -> None:
        """Pull the current model to ``clients``, run their local training
        eagerly, and schedule their completion events."""
        task, fed, fleet = self.task, self.fed, self.fleet
        layout, state = task.layout, self.state
        clients = np.asarray(clients, np.int64)
        K = len(clients)
        if K == 0:
            return

        sched = fed.modality_schedule
        live_mm = (sched.masks_at(now, clients) if sched is not None
                   else fleet.modality_mask[clients])
        if self.plan is None:  # alloc="random": legacy full-fleet rng draw
            S_full, _ = allocate(self.strategy, state, task, fleet, fed,
                                 layout.flops)
            S = S_full[clients]  # [K, G]
        else:
            S = self._allocate(clients, live_mm)
        fault = (self.fx.on_dispatch(clients)
                 if self.fx is not None else None)

        steps = fed.local_epochs * fed.steps_per_epoch
        batches = draw_client_batches(state.rng, dataset, clients, steps,
                                      fed.batch_size)
        start = jax.tree.map(
            lambda g: jnp.broadcast_to(g, (K,) + g.shape), state.trainable)
        gates = jnp.asarray(S, jnp.float32)
        mmasks = jnp.asarray(live_mm, jnp.float32)
        rank_gate = jax.tree.map(lambda x: x[clients], self.rank_gate)
        deltas, losses = self.local_update(start, batches, mmasks, gates,
                                           rank_gate, fed.lr)
        if fault is not None:  # corrupt pre-quantization, like a real client
            dropped, slow, byz_rows, tickets = fault
            deltas = self.fx.corrupt(deltas, byz_rows, clients, tickets)
        S_up = S
        if self.strategy.selective:  # FedMFS: shrink the upload, not compute
            S_up = _selective_upload(layout, deltas, S,
                                     self.strategy.comm_budget)
            deltas = _gate_rows(layout, deltas, S_up)

        trained_fl, fixed_fl = T.cycle_flops(layout, fed, S,
                                             task.forward_flops_per_example)
        upload = ((np.asarray(S_up, np.float64) @ layout.sizes)
                  * self._uplink_bytes_per_param)
        dur, t_comp, t_comm = completion_times(
            fleet, clients, trained_fl, fixed_fl, upload, fed.t_overhead,
            fed.utilization, self.fed.jitter_sigma, state.rng)
        if fault is not None:  # stalls stretch compute time (and its energy)
            dur = dur + t_comp * (slow - 1.0)
            t_comp = t_comp * slow

        quantize = fed.uplink_codec == "int8"
        losses_np = np.asarray(losses)
        for i, c in enumerate(clients):
            d_i = jax.tree.map(lambda x, i=i: x[i], deltas)
            if quantize:  # client-side compression, EF residual stays local
                q_i, s_i, resid = dist.quantize_int8_ef(
                    d_i, self.ef.get(int(c)))
                self.ef[int(c)] = resid
                d_i = (q_i, s_i)
            pend = _Pending(int(c), state.round, d_i,
                            float(losses_np[i]), S_up[i], float(t_comp[i]),
                            float(t_comm[i]), float(upload[i]), live_mm[i],
                            dropped=fault is not None and bool(dropped[i]))
            self.queue.push(now + dur[i], int(c), payload=pend)

    # -- server flush ---------------------------------------------------------

    def _flush(self) -> dict:
        """Stack the buffered cohort (client-id order) and fold it into the
        global model through the shared ``_flush_arrays``."""
        entries = sorted(self.buffer, key=lambda e: e.client)
        self.buffer = []
        if self.fed.uplink_codec == "int8":
            deltas = AG.QuantizedStack(
                jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[e.delta[0] for e in entries]),
                jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[e.delta[1] for e in entries]))
        else:
            deltas = jax.tree.map(lambda *xs: jnp.stack(xs),
                                  *[e.delta for e in entries])
        S = np.stack([e.S_row for e in entries])  # [K, G]
        client_ids = np.array([e.client for e in entries])
        staleness = np.array([self.state.round - e.version for e in entries],
                             np.float64)
        losses = np.array([e.loss for e in entries])
        mmask_rows = np.stack([e.mmask_row for e in entries])
        return self._flush_arrays(deltas, S, client_ids, losses, staleness,
                                  mmask_rows=mmask_rows)

    # -- the event loop -------------------------------------------------------

    def run(self, dataset, total_updates: int | None = None,
            log_every: int = 0) -> dict:
        """Process client completions until ``total_updates`` of them have
        been absorbed (default: rounds * N, the sync engine's total work)."""
        fed, fleet = self.fed, self.fleet
        total = (total_updates or fed.total_updates
                 or fed.rounds * fleet.N)
        K = max(1, min(self.strategy.buffer_size, fleet.N))
        if not len(self.queue):
            self._dispatch(np.arange(fleet.N), self.state.sim_time, dataset)
        processed = 0
        while processed < total and self.queue:
            events = self.queue.pop_simultaneous()
            now = events[0].time
            self.state.sim_time = now
            completed = []
            for ev in events:
                pend: _Pending = ev.payload
                completed.append(ev.client)
                if pend.dropped:  # crash: reboot + redispatch, nothing lands
                    continue
                self.buffer.append(pend)
                self.trace.record_completion(fleet, ev.client, pend.t_comp,
                                             pend.t_comm, pend.upload_bytes)
                processed += 1
                if len(self.buffer) >= K:
                    rec = self._flush()
                    self._log_and_eval(rec, dataset, log_every,
                                       self.strategy.name)
                if processed >= total:
                    break
            if processed < total:
                self._dispatch(np.array(completed), now, dataset)
        self.trace.sim_time = self.state.sim_time
        if not self.history["f1"]:
            self.history["f1"].append(self.evaluate(dataset))
            self.history["f1_flush"].append(self.state.round)
        return self.history


# ---------------------------------------------------------------------------
# the vectorized fleet runtime
# ---------------------------------------------------------------------------


class VectorizedAsyncFedRun(_ServerFlushMixin):
    """Structure-of-arrays async runtime for fleet-scale N (sim/fleet.py).

    Same protocol as ``AsyncFedRun`` — FedBuff buffer-K flushes with
    staleness-discounted cohort aggregation — but all per-client system
    state lives in flat arrays, events come from vectorized next-K
    extraction instead of a heap, and gradient computation is decoupled
    from system simulation via ``fed.grad_mode`` (see module docstring).
    With ``grad_mode="dispatch"`` the flush history (loss, staleness,
    selected_frac, sim_time) is event-for-event identical to AsyncFedRun.
    """

    def __init__(self, task: MMTask, strategy: AsyncStrategy,
                 fleet: FleetConfig, fed: AsyncFedConfig,
                 state: AsyncFedState, local_update: Any, plan: AllocPlan,
                 fstate: FleetState, population: PopulationModel | None,
                 trace: AsyncTrace, history: dict, proto: Any,
                 server_step: Any):
        self.task = task
        self.strategy = strategy
        self.fleet = fleet
        self.fed = fed
        self.state = state
        self.local_update = local_update
        self.plan = plan
        self.fstate = fstate
        self.population = population
        self.trace = trace
        self.history = history
        self.proto = proto
        self.server_step = server_step
        self.grad_mode = fed.grad_mode
        self.ring_clamped = 0  # cohort-mode pulls older than the ring
        # fault injection: drop/stall/corruption flags are drawn at dispatch
        # (counter-based, heap-parity) and consulted at absorb/flush time
        self.fx = _make_fault_runtime(fed, fleet)
        self._drop_next = np.zeros(fleet.N, bool)  # in-flight cycle crashes
        self._fault_ticket = np.zeros(fleet.N, np.int64)  # in-flight ticket
        # buffered (completed, not yet flushed) client state — columnar
        self._buf_client: list[np.ndarray] = []
        self._buf_version: list[np.ndarray] = []
        self._buf_bits: list[np.ndarray] = []
        self._buf_mmbits: list[np.ndarray] = []  # live modality masks
        self._buf_ticket: list[np.ndarray] = []
        self._buf_fticket: list[np.ndarray] = []  # fault tickets (fx only)
        self._buf_loss: list[np.ndarray] = []
        self._buf_deltas: list[Any] = []
        self._buf_scales: list[Any] = []  # uplink_codec="int8" only
        self._buf_count = 0
        # dispatch-mode in-flight gradient store ([N, ...] stacked leaves);
        # with uplink_codec="int8" the leaves are int8 (4x less memory),
        # `_pend_scales` holds the [N] per-leaf dequant scales and `_ef`
        # the fp32 [N, ...] client-side error-feedback residuals
        self._pend_deltas: Any = None
        self._pend_loss: np.ndarray | None = None
        self._pend_scales: Any = None
        self._ef: Any = None
        # cohort-mode ring of the last `snapshot_ring` model versions
        self._ring: Any = None
        if fed.grad_mode == "cohort":
            R = max(1, fed.snapshot_ring)
            self._ring = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (R,) + x.shape), proto)
        self._rank_rows_cache: dict[int, Any] = {}
        self._churn_rng = np.random.default_rng([fed.seed, 0x5EED])

    @classmethod
    def create(cls, task: MMTask, trainable0: Any, strategy: AsyncStrategy,
               fleet: FleetConfig, fed: AsyncFedConfig
               ) -> VectorizedAsyncFedRun:
        _check_strategy(strategy, fed, fleet)
        if fed.grad_mode not in GRAD_MODES:
            raise ValueError(f"grad_mode must be one of {GRAD_MODES}, "
                             f"got {fed.grad_mode!r}")
        if strategy.selective and fed.grad_mode != "dispatch":
            raise ValueError("selective upload ranks the actual deltas at "
                             "dispatch; grad_mode='cohort'/'none' never "
                             "materializes them")
        if strategy.rank_caps:
            raise ValueError("rank_caps build an [N, ...]-stacked gate tree "
                             "— unsupported at fleet scale")
        if strategy.alloc == "random":
            raise ValueError("alloc='random' draws fleet-shaped noise per "
                             "dispatch; use the event-loop AsyncFedRun")
        state = _make_state(task.layout.G, trainable0, fed.seed)
        trace = AsyncTrace()
        trace.init_fleet(fleet.N)
        plan = plan_allocation(strategy, task, fleet, fed, task.layout.flops)
        pop = (PopulationModel(fed.churn_rate, fed.arrival_rate)
               if (fed.churn_rate > 0.0 or fed.arrival_rate > 0.0) else None)
        lu = (make_local_update(task, fed, strategy.prox_mu)
              if fed.grad_mode != "none" else None)
        return cls(task, strategy, fleet, fed, state, lu, plan,
                   FleetState.create(fleet.N), pop, trace, _history_init(),
                   trainable0, make_server_step(task.layout, strategy, fed))

    # -- client dispatch ------------------------------------------------------

    def _rank_gate_rows(self, b: int) -> Any:
        """All-ones per-client gate rows (rank_caps are rejected above)."""
        if b not in self._rank_rows_cache:
            self._rank_rows_cache[b] = jax.tree.map(
                lambda x: jnp.ones((b,) + x.shape, x.dtype), self.proto)
        return self._rank_rows_cache[b]

    def _dispatch_vec(self, idx: np.ndarray, now: float, dataset) -> None:
        """Pull the current model to clients ``idx`` and schedule their
        completions — array-resident, O(batch) given the cached AllocPlan."""
        idx = np.asarray(idx, np.int64)
        if len(idx) == 0:
            return
        with obs.span("fed.dispatch", B=len(idx)):
            self._dispatch_rows(idx, now, dataset)

    def _dispatch_rows(self, idx: np.ndarray, now: float, dataset) -> None:
        task, fed, fleet = self.task, self.fed, self.fleet
        layout, state = task.layout, self.state
        B = len(idx)
        sched = fed.modality_schedule
        live_mm = (sched.masks_at(now, idx) if sched is not None
                   else fleet.modality_mask[idx])
        S = self._allocate(idx, live_mm)  # [B, G]
        fault = None
        if self.fx is not None:
            fault = self.fx.on_dispatch(idx)
            self._drop_next[idx] = fault[0]
            self._fault_ticket[idx] = fault[3]

        steps = fed.local_epochs * fed.steps_per_epoch
        S_up = S  # uploaded set (= trained unless selective shrinks it)
        if self.grad_mode == "dispatch":
            batches = draw_client_batches(state.rng, dataset, idx, steps,
                                          fed.batch_size)
            start = jax.tree.map(
                lambda g: jnp.broadcast_to(g, (B,) + g.shape),
                state.trainable)
            gates = jnp.asarray(S, jnp.float32)
            mmasks = jnp.asarray(live_mm, jnp.float32)
            with obs.span("fed.local_update"):
                deltas, losses = self.local_update(
                    start, batches, mmasks, gates, self._rank_gate_rows(B),
                    fed.lr)
            if fault is not None:  # corrupt pre-quantization (heap parity)
                deltas = self.fx.corrupt(deltas, fault[2], idx, fault[3])
            if self.strategy.selective:  # FedMFS: shrink upload, not compute
                S_up = _selective_upload(layout, deltas, S,
                                         self.strategy.comm_budget)
                deltas = _gate_rows(layout, deltas, S_up)
            quantize = fed.uplink_codec == "int8"
            if self._pend_deltas is None:
                store_dtype = jnp.int8 if quantize else jnp.float32
                self._pend_deltas = jax.tree.map(
                    lambda x: jnp.zeros((fleet.N,) + x.shape, store_dtype),
                    self.proto)
                self._pend_loss = np.full(fleet.N, np.nan)
                if quantize:
                    self._pend_scales = jax.tree.map(
                        lambda x: jnp.zeros((fleet.N,), jnp.float32),
                        self.proto)
                    self._ef = jax.tree.map(
                        lambda x: jnp.zeros((fleet.N,) + x.shape,
                                            jnp.float32), self.proto)
            jidx = jnp.asarray(idx)
            if quantize:  # compress client-side, EF residual stays per-row
                q, s, resid = dist.quantize_int8_stacked(
                    deltas, jax.tree.map(lambda r: r[jidx], self._ef))
                self._pend_deltas = jax.tree.map(
                    lambda buf, v: buf.at[jidx].set(v), self._pend_deltas,
                    q)
                self._pend_scales = jax.tree.map(
                    lambda buf, v: buf.at[jidx].set(v), self._pend_scales,
                    s)
                self._ef = jax.tree.map(
                    lambda buf, v: buf.at[jidx].set(v), self._ef, resid)
            else:
                self._pend_deltas = jax.tree.map(
                    lambda buf, d: buf.at[jidx].set(d), self._pend_deltas,
                    deltas)
            self._pend_loss[idx] = np.asarray(losses)

        trained_fl, fixed_fl = T.cycle_flops(layout, fed, S,
                                             task.forward_flops_per_example)
        upload = ((np.asarray(S_up, np.float64) @ layout.sizes)
                  * self._uplink_bytes_per_param)
        dur, t_comp, t_comm = T.cycle_times(
            fleet, idx, trained_fl, fixed_fl, upload, fed.t_overhead,
            fed.utilization, fed.jitter_sigma, state.rng)
        if fault is not None:  # stalls stretch compute time (and energy)
            slow = fault[1]
            dur = dur + t_comp * (slow - 1.0)
            t_comp = t_comp * slow
        self.fstate.dispatch(idx, now, state.round, pack_group_bits(S_up),
                             dur, t_comp, t_comm, upload)
        self.fstate.mod_bits[idx] = pack_group_bits(live_mm)

    # -- completion absorption / flush ----------------------------------------

    def _buf_append(self, chunk: np.ndarray) -> None:
        fs = self.fstate
        self._buf_client.append(chunk.copy())
        self._buf_version.append(fs.version[chunk].copy())
        self._buf_bits.append(fs.group_bits[chunk].copy())
        self._buf_mmbits.append(fs.mod_bits[chunk].copy())
        self._buf_ticket.append(fs.updates[chunk].copy())
        if self.fx is not None:  # cycle's fault ticket, before redispatch
            self._buf_fticket.append(self._fault_ticket[chunk].copy())
        if self.grad_mode == "dispatch":
            self._buf_loss.append(self._pend_loss[chunk].copy())
            jc = jnp.asarray(chunk)
            self._buf_deltas.append(
                jax.tree.map(lambda x: x[jc], self._pend_deltas))
            if self._pend_scales is not None:
                self._buf_scales.append(
                    jax.tree.map(lambda x: x[jc], self._pend_scales))
        self._buf_count += len(chunk)

    def _cohort_update(self, dataset, ids: np.ndarray, versions: np.ndarray,
                       tickets: np.ndarray, S: np.ndarray,
                       mmask_rows: np.ndarray) -> tuple[Any, np.ndarray]:
        """Cohort-sampled gradient computation: local updates for the M
        flushed clients only, each starting from the ring snapshot of the
        version it pulled (pulls older than the ring clamp to the oldest
        retained snapshot; ``ring_clamped`` counts those)."""
        fed, state = self.fed, self.state
        with obs.span("fed.assemble"):
            R = max(1, fed.snapshot_ring)
            vmin = max(0, state.round - R + 1)
            v_eff = np.maximum(versions, vmin)
            self.ring_clamped += int(np.sum(v_eff != versions))
            start = jax.tree.map(lambda x: x[jnp.asarray(v_eff % R)],
                                 self._ring)

            steps = fed.local_epochs * fed.steps_per_epoch
            xs, ys = [], []
            for c, t in zip(ids, tickets):  # counter-based draws: order-free
                r = np.random.default_rng([fed.seed, int(c), int(t)])
                src = int(c) % len(dataset.train_y)
                bidx = r.integers(0, len(dataset.train_y[src]),
                                  size=(steps, fed.batch_size))
                xs.append(dataset.train_x[src][bidx])
                ys.append(dataset.train_y[src][bidx])
            batches = {"x": jnp.asarray(np.stack(xs)),
                       "y": jnp.asarray(np.stack(ys))}
            gates = jnp.asarray(S, jnp.float32)
            mmasks = jnp.asarray(mmask_rows, jnp.float32)
        with obs.span("fed.local_update"):
            deltas, losses = self.local_update(
                start, batches, mmasks, gates, self._rank_gate_rows(len(ids)),
                fed.lr)
            return deltas, np.asarray(losses)

    def _flush_vec(self, dataset) -> dict:
        client = np.concatenate(self._buf_client)
        order = np.argsort(client, kind="stable")  # client-id order (parity)
        ids = client[order]
        versions = np.concatenate(self._buf_version)[order]
        tickets = np.concatenate(self._buf_ticket)[order]
        S = unpack_group_bits(np.concatenate(self._buf_bits)[order],
                              self.task.layout.G)
        mmask_rows = unpack_group_bits(
            np.concatenate(self._buf_mmbits)[order], self.fleet.M)
        staleness = (self.state.round - versions).astype(np.float64)
        quantize = self.fed.uplink_codec == "int8"
        if self.grad_mode == "dispatch":
            losses = np.concatenate(self._buf_loss)[order]
            deltas = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0),
                                  *self._buf_deltas)
            jorder = jnp.asarray(order)
            deltas = jax.tree.map(lambda x: x[jorder], deltas)
            if quantize:  # buffered rows are already the int8 uplink
                scales = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0),
                                      *self._buf_scales)
                deltas = AG.QuantizedStack(
                    deltas, jax.tree.map(lambda x: x[jorder], scales))
        elif self.grad_mode == "cohort":
            deltas, losses = self._cohort_update(dataset, ids, versions,
                                                 tickets, S, mmask_rows)
            if self.fx is not None:  # corrupt with the *buffered* cycle's
                # fault ticket — the client may already be redispatched
                ftickets = np.concatenate(self._buf_fticket)[order]
                deltas = self.fx.corrupt(deltas, self.fx.byz[ids], ids,
                                         ftickets)
            if quantize:  # cohort-sampled gradients quantize at the edge
                # of the simulated uplink (no EF: each (client, ticket)
                # update is drawn exactly once at flush time)
                with obs.span("fed.uplink"):
                    qt, sc, _ = dist.quantize_int8_stacked(deltas)
                deltas = AG.QuantizedStack(qt, sc)
        else:
            deltas, losses = None, None
        for buf in (self._buf_client, self._buf_version, self._buf_bits,
                    self._buf_mmbits, self._buf_ticket, self._buf_fticket,
                    self._buf_loss, self._buf_deltas, self._buf_scales):
            buf.clear()
        self._buf_count = 0

        rec = self._flush_arrays(deltas, S, ids, losses, staleness,
                                 mmask_rows=mmask_rows)
        if self.grad_mode == "cohort":  # retain the new version's snapshot
            R = max(1, self.fed.snapshot_ring)
            slot = self.state.round % R
            self._ring = jax.tree.map(
                lambda ring, t: ring.at[slot].set(t.astype(ring.dtype)),
                self._ring, self.state.trainable)
        return rec

    def _absorb(self, gidx: np.ndarray, dataset, K: int,
                log_every: int) -> None:
        """Absorb one timestamp group of completions: energy accounting,
        buffer append, flushes at every K-th entry — chunked so trace state
        at each flush matches the one-event-at-a-time loop."""
        fleet, fs = self.fleet, self.fstate
        pos = 0
        while pos < len(gidx):
            room = K - self._buf_count
            chunk = gidx[pos:pos + room]
            pos += len(chunk)
            fs.complete(fleet, chunk)
            self.trace.record_completions(fleet, chunk, fs.t_comp[chunk],
                                          fs.t_comm[chunk],
                                          fs.upload_bytes[chunk])
            self._buf_append(chunk)
            if self._buf_count >= K:
                obs.clock()
                with obs.span("fed.flush", K=self._buf_count,
                              round=self.state.round):
                    rec = self._flush_vec(dataset)
                self._log_and_eval(rec, dataset if self.grad_mode != "none"
                                   else None, log_every,
                                   f"vec:{self.strategy.name}")

    # -- the vectorized event loop --------------------------------------------

    @obs.traced("fed.run")
    def run(self, dataset=None, total_updates: int | None = None,
            log_every: int = 0) -> dict:
        """Absorb ``total_updates`` completions (default rounds * N), with
        vectorized next-K event extraction over the completion-time array.
        ``dataset`` may be None with ``grad_mode="none"``."""
        fed, fleet, state = self.fed, self.fleet, self.state
        if self.grad_mode != "none" and dataset is None:
            raise ValueError(f"grad_mode={self.grad_mode!r} needs a dataset")
        total = (total_updates or fed.total_updates
                 or fed.rounds * fleet.N)
        K = max(1, min(self.strategy.buffer_size, fleet.N))
        fs = self.fstate
        if fs.in_flight == 0:
            self._dispatch_vec(np.nonzero(fs.alive)[0], state.sim_time,
                               dataset)
        processed = 0
        last_t = state.sim_time
        while processed < total and fs.in_flight > 0:
            times, cand = fs.peek_window(K, fed.t_overhead)
            remaining = total - processed
            if self.fx is not None:
                # fault-dropped completions never count toward ``total``:
                # cut the window after the ``remaining``-th *absorbable*
                # event, exactly where the heap loop breaks mid-group —
                # a plain prefix cut would split the redispatch batch and
                # desync the jitter rng stream
                kept_c = np.cumsum(~self._drop_next[cand])
                if len(cand) and kept_c[-1] > remaining:
                    cut = int(np.searchsorted(kept_c, remaining)) + 1
                    times, cand = times[:cut], cand[:cut]
            elif len(cand) > remaining:
                times, cand = times[:remaining], cand[:remaining]
            fs.claim(cand)
            arrivals: list[np.ndarray] = []
            gstart = 0
            while gstart < len(cand):
                t0 = float(times[gstart])
                gend = gstart + int(np.searchsorted(
                    times[gstart:], t0, side="right"))
                gidx = cand[gstart:gend]
                gstart = gend
                state.sim_time = t0
                if self.population is not None:
                    _, arrived = self.population.step(self._churn_rng, fs,
                                                      t0 - last_t)
                    if len(arrived):
                        arrivals.append(arrived)
                    # departures lose their update — even if they re-arrive
                    # before their claimed event's group is processed
                    gidx = gidx[fs.alive[gidx] & ~fs.lost[gidx]]
                last_t = t0
                if len(gidx) == 0:
                    continue
                kept = (gidx[~self._drop_next[gidx]]
                        if self.fx is not None else gidx)
                self._absorb(kept, dataset, K, log_every)
                processed += len(kept)
                if processed >= total:
                    break
                # redispatch everything claimed — a dropped client reboots
                # at the time its completion would have fired
                self._dispatch_vec(gidx, t0, dataset)
            if arrivals and processed < total:
                # genuine re-arrivals from population.step() only — claimed
                # events of this window all have t_next=inf, so an idle-scan
                # would double-dispatch clients whose completion is still
                # pending in a later timestamp group. Dispatched after the
                # window resolves, since dispatch clears ``lost``.
                arr = np.unique(np.concatenate(arrivals))
                self._dispatch_vec(arr[fs.alive[arr]], state.sim_time,
                                   dataset)
        self.trace.sim_time = state.sim_time
        self.trace.per_client_updates = fs.updates.copy()
        if (self.grad_mode != "none" and dataset is not None
                and not self.history["f1"]):
            self.history["f1"].append(self.evaluate(dataset))
            self.history["f1_flush"].append(self.state.round)
        return self.history
