"""The RELIEF round engine (paper Algorithm 1) and its baselines.

One round = (1) server allocation [blue]: EMA divergence -> Eq. 7 budgets ->
top-k group selection; (2) parallel local training [green]: clients run E
epochs with gradients gated to their assigned groups (vmapped over the client
axis in slices of ``CLIENT_CHUNK`` — on a TPU mesh this axis is sharded, see
dist/); (3) server aggregation
[orange]: cohort-wise masked means (Eq. 3-4) + divergence update (Eq. 5-6).

Fault tolerance: client participation is a per-round mask — any dropout
pattern yields well-defined aggregation (empty cohorts freeze their block);
the engine state (global trainable, divergence EMA, round index, rng) is
checkpointable via repro.checkpoint.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation as AG
from repro.core import allocation as AL
from repro.core import divergence as DV
from repro.core import mdlora
from repro.core.strategies import Strategy
from repro.core.tasks import MMTask
from repro.optim import adam_init, adam_update
from repro.sim import FleetConfig
from repro.sim import timing as T

Array = jax.Array

# clients trained side by side (vmapped) per slice of a local-update call;
# larger cohorts run as a scan over slices. Bounds device memory: at PAMAP2
# Backbone 2 widths one client's training step keeps ~0.3 GB of TPU-padded
# activations live, so a 64-client flush at once would not fit a 16 GB chip.
CLIENT_CHUNK = 16


@dataclasses.dataclass(frozen=True)
class FedConfig:
    rounds: int = 50
    local_epochs: int = 5  # E (paper VI-A3)
    steps_per_epoch: int = 4
    batch_size: int = 32
    lr: float = 1e-3
    gamma: float = 0.9  # EMA coefficient (Eq. 6)
    server_lr: float = 1.0
    participation: float = 1.0
    t_overhead: float = 0.05
    utilization: float = 0.3
    eval_every: int = 5
    seed: int = 0
    dropout_prob: float = 0.0  # random client failures (fault injection)
    # timing model: "flop_proportional" = the paper's Sec. VI-A3 simulator
    # (compute ~ trained-group FLOPs only; reproduces Tables I-II speedups);
    # "fwd_aware" = the Sec. VII-corrected model charging the fixed
    # full-model forward to everyone (reproduces the real-device gap).
    sim_mode: str = "flop_proportional"

    @classmethod
    def from_scenario(cls, spec, **overrides):
        """Training knobs from a ``sim.scenarios.ScenarioSpec`` (duck-typed:
        anything with the same field names works)."""
        return cls(**(scenario_fed_kwargs(spec) | overrides))


def scenario_fed_kwargs(spec) -> dict:
    """The FedConfig fields a ScenarioSpec carries, as constructor kwargs."""
    return dict(rounds=spec.rounds, local_epochs=spec.local_epochs,
                steps_per_epoch=spec.steps_per_epoch,
                batch_size=spec.batch_size, lr=spec.lr,
                eval_every=spec.eval_every, t_overhead=spec.t_overhead,
                utilization=spec.utilization, seed=spec.seed)


@dataclasses.dataclass
class FedState:
    round: int
    trainable: Any  # global trainable tree
    client_trainable: Any  # [N, ...] stacked (personalized leaves live here)
    dbar: np.ndarray  # [G] EMA divergence
    mag_ema: np.ndarray  # [G] update-magnitude EMA (FedEL-like alloc)
    rng: np.random.Generator


# ---------------------------------------------------------------------------
# compiled local-update kernel (shared by every strategy)
# ---------------------------------------------------------------------------


def make_local_update(task: MMTask, fed: FedConfig, prox_mu: float):
    layout = task.layout

    def one_client(start, batches, mmask, gate, rank_gate, lr):
        opt = adam_init(start)

        def step(carry, batch):
            tr, opt = carry
            b = dict(batch) | {"modality_mask": mmask}
            loss, grads = jax.value_and_grad(task.loss)(tr, b)
            if prox_mu > 0.0:
                grads = jax.tree.map(
                    lambda g, t, t0: g + prox_mu * (
                        t.astype(jnp.float32) - t0.astype(jnp.float32)),
                    grads, tr, start)
            grads = mdlora.group_gate_tree(layout, grads, gate)
            grads = jax.tree.map(lambda g, m: g * m, grads, rank_gate)
            tr, opt = adam_update(tr, grads, opt, lr)
            return (tr, opt), loss

        (tr, _), losses = jax.lax.scan(step, (start, opt), batches)
        delta = jax.tree.map(
            lambda a, b_: a.astype(jnp.float32) - b_.astype(jnp.float32),
            tr, start)
        delta = mdlora.group_gate_tree(layout, delta, gate)
        delta = jax.tree.map(lambda d, m: d * m, delta, rank_gate)
        return delta, jnp.mean(losses)

    def clients(start, batches, mmask, gate, rank_gate, lr):
        return jax.lax.map(lambda c: one_client(*c, lr),
                           (start, batches, mmask, gate, rank_gate),
                           batch_size=CLIENT_CHUNK)

    return jax.jit(clients)


# ---------------------------------------------------------------------------
# data plumbing (shared with the async runtime: identical rng call sequence
# per client => the sync-parity test is bit-for-bit)
# ---------------------------------------------------------------------------


def draw_client_batches(rng: np.random.Generator, dataset, clients,
                        steps: int, batch_size: int) -> dict:
    """Stacked local-training batches for ``clients`` (one rng.integers call
    per client, in iteration order)."""
    xs, ys = [], []
    for n in clients:
        src = n % len(dataset.train_y)
        idx = rng.integers(0, len(dataset.train_y[src]),
                           size=(steps, batch_size))
        xs.append(dataset.train_x[src][idx])
        ys.append(dataset.train_y[src][idx])
    return {"x": jnp.asarray(np.stack(xs)), "y": jnp.asarray(np.stack(ys))}


# ---------------------------------------------------------------------------
# allocation dispatch
# ---------------------------------------------------------------------------


def _depth_order(layout: mdlora.GroupLayout) -> np.ndarray:
    """Shallow-to-deep group ordering for depth-based baselines."""
    def rank(i):
        n, k = layout.names[i], layout.kinds[i]
        if k == mdlora.KIND_ENCODER:
            lay = int(n.split("_L")[-1]) if "_L" in n else 0
            return (0, lay)
        if k == mdlora.KIND_FUSION_BLOCK:
            return (1, 0)
        if k == mdlora.KIND_FUSION_B:
            return (1, 1)
        return (2, 0)
    return np.array(sorted(range(layout.G), key=rank), np.int32)


@dataclasses.dataclass(frozen=True)
class AllocPlan:
    """Fleet-static inputs of allocation, precomputed once per run.

    Everything here depends only on (strategy, layout, fleet, fed): candidate
    and mandatory masks, and the elastic budgets (Eq. 7 — ``t_star`` is a
    fleet-wide binary search, so it must be solved over the FULL fleet even
    when only a dispatch batch is being allocated; caching it here is what
    makes per-batch allocation O(batch) instead of O(N))."""
    cand: np.ndarray  # [N, G] candidate groups
    mandatory: np.ndarray  # [N, G] forced inclusions
    k: np.ndarray  # [N] group budgets
    depth_order: np.ndarray | None = None  # [G] (depth baselines only)


def plan_allocation(strategy: Strategy, task: MMTask, fleet: FleetConfig,
                    fed: FedConfig, group_flops: np.ndarray) -> AllocPlan:
    layout = task.layout
    N, G = fleet.N, layout.G
    accessible = layout.accessible(fleet.modality_mask)
    if strategy.alloc in ("full", "magnitude", "depth"):
        # modality-unaware: every (non-empty) group is a training candidate —
        # classical FL trains absent-sensor parameters too (paper Q2)
        cand = np.tile(layout.sizes[None, :] > 0, (N, 1))
    else:
        cand = accessible
    mandatory = (layout.mandatory(fleet.modality_mask) if strategy.mandatory
                 else np.zeros((N, G), bool))
    n_mand = mandatory.sum(1)
    g_max = cand.sum(1)

    if strategy.budgets == "elastic":
        examples = fed.local_epochs * fed.steps_per_epoch * fed.batch_size
        tau = T.profile_tau(fleet, group_flops, examples, fed.utilization)
        t_star = AL.solve_t_star(tau, fed.t_overhead, n_mand, g_max)
        k = AL.elastic_budgets(tau, t_star, fed.t_overhead, n_mand, g_max)
    else:
        k = g_max.copy()
    order = _depth_order(layout) if strategy.alloc == "depth" else None
    return AllocPlan(cand, mandatory, k, order)


def allocate_rows(plan: AllocPlan, strategy: Strategy, state: FedState,
                  idx: np.ndarray, cand: np.ndarray | None = None,
                  mandatory: np.ndarray | None = None) -> np.ndarray:
    """S rows [len(idx), G] for the client subset ``idx``.

    Row-identical to ``allocate(...)[0][idx]`` for every deterministic
    allocator (scores are shared fleet-wide state, budgets come from the
    plan); ``alloc="random"`` draws fresh noise per call, so only
    whole-fleet calls reproduce the legacy stream.

    ``cand``/``mandatory`` ([len(idx), G]) override the plan's fleet-static
    masks — the hook for time-varying modality availability (streaming
    scenarios), where the candidate set is a function of dispatch time while
    the Eq. 7 budgets ``k`` stay solved over the base fleet."""
    idx = np.asarray(idx)
    cand = plan.cand[idx] if cand is None else np.asarray(cand, bool)
    mandatory = (plan.mandatory[idx] if mandatory is None
                 else np.asarray(mandatory, bool))
    k = plan.k[idx]
    if strategy.alloc in ("full", "accessible"):
        return cand
    if strategy.alloc == "divergence":
        score = state.dbar
    elif strategy.alloc == "magnitude":
        score = state.mag_ema
    elif strategy.alloc == "random":
        return AL.allocate_topk(state.dbar, cand, mandatory, k,
                                rng=state.rng, randomize=True)
    elif strategy.alloc == "depth":
        G = cand.shape[1]
        order = plan.depth_order
        S = np.zeros_like(cand)
        offset = (state.round % max(G, 1)) if strategy.depth_rotate else 0
        for n in range(len(idx)):
            take = [order[(offset + i) % G] for i in range(G)
                    if cand[n, order[(offset + i) % G]]][: int(k[n])]
            S[n, take] = True
        return S
    else:
        raise ValueError(strategy.alloc)
    return AL.allocate_topk(score, cand, mandatory, k)


def allocate(strategy: Strategy, state: FedState, task: MMTask,
             fleet: FleetConfig, fed: FedConfig,
             group_flops: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """-> (S [N, G] bool selection, k [N] budgets)."""
    plan = plan_allocation(strategy, task, fleet, fed, group_flops)
    return allocate_rows(plan, strategy, state, np.arange(fleet.N)), plan.k


# ---------------------------------------------------------------------------
# personalization helpers
# ---------------------------------------------------------------------------


def _personal_leaf_mask(proto: Any, strategy: Strategy) -> Any:
    """pytree of bool: True where the leaf stays local (never aggregated).

    ``proto`` is the run's trainable prototype — passed explicitly (runs
    carry it as an attribute) rather than via the old ``id(task)``-keyed
    global cache, whose ids could dangle once tasks were garbage-collected.
    """
    def is_personal(p: str) -> bool:
        if strategy.share_only:
            return not any(s in p for s in strategy.share_only)
        return any(s in p for s in strategy.personal)

    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        jax.tree.map(lambda x: 0, proto))
    return jax.tree_util.tree_unflatten(
        treedef, [is_personal(mdlora.path_str(p)) for p, _ in leaves])


def _clusters(fleet: FleetConfig) -> np.ndarray:
    """[N] cluster id by identical modality sets (FedLEASE-like)."""
    keys = [tuple(row) for row in fleet.modality_mask.astype(int)]
    uniq = {k: i for i, k in enumerate(dict.fromkeys(keys))}
    return np.array([uniq[k] for k in keys], np.int32)


def _rank_gates(proto: Any, strategy: Strategy, fleet: FleetConfig) -> Any:
    """HeLoRA: [N]-stacked multiplicative masks zeroing LoRA rank tails."""
    N = fleet.N
    if not strategy.rank_caps:
        return jax.tree.map(lambda x: jnp.ones((N,) + x.shape, x.dtype), proto)
    tiers = np.searchsorted([0.5, 2.5], np.argsort(np.argsort(-fleet.tops)))
    # tier by compute rank: top third full rank etc. — use tops quantiles
    q = np.quantile(fleet.tops, [0.34, 0.67])
    tier = np.digitize(-fleet.tops, [-q[1], -q[0]])  # 0=fast..2=slow
    caps = np.array(strategy.rank_caps)[np.clip(tier, 0, len(strategy.rank_caps) - 1)]

    def mk(path, leaf):
        p = mdlora.path_str(path)
        base = np.ones((N,) + leaf.shape, np.float32)
        if "lora" in p and leaf.ndim >= 2 and (p.endswith("['a']") or p.endswith("['b']")):
            r_axis = leaf.ndim - 1 if p.endswith("['a']") else leaf.ndim - 2
            r = leaf.shape[r_axis]
            for n in range(N):
                rn = max(1, int(caps[n] * r))
                sl = [slice(None)] * (leaf.ndim + 1)
                sl[0] = n
                sl[r_axis + 1] = slice(rn, None)
                base[tuple(sl)] = 0.0
        return jnp.asarray(base)

    return jax.tree_util.tree_map_with_path(mk, proto)


# ---------------------------------------------------------------------------
# the round
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FedRun:
    task: MMTask
    strategy: Strategy
    fleet: FleetConfig
    fed: FedConfig
    state: FedState
    local_update: Any
    rank_gate: Any
    personal_mask: Any
    history: dict
    proto: Any  # trainable prototype (zero-round shapes/dtypes)

    @classmethod
    def create(cls, task: MMTask, trainable0: Any, strategy: Strategy,
               fleet: FleetConfig, fed: FedConfig) -> FedRun:
        G = task.layout.G
        state = FedState(
            round=0, trainable=trainable0,
            client_trainable=jax.tree.map(
                lambda x: jnp.broadcast_to(x, (fleet.N,) + x.shape), trainable0),
            dbar=np.ones(G) * 1e-6, mag_ema=np.ones(G),
            rng=np.random.default_rng(fed.seed))
        lu = make_local_update(task, fed, strategy.prox_mu)
        rank_gate = _rank_gates(trainable0, strategy, fleet)
        pmask = _personal_leaf_mask(trainable0, strategy)
        history = {"round": [], "loss": [], "round_time_s": [],
                   "energy_j": [], "upload_mb": [], "f1": [], "f1_round": [],
                   "divergence": [], "selected_frac": []}
        return cls(task, strategy, fleet, fed, state, lu, rank_gate, pmask,
                   history, trainable0)

    # -- data plumbing --------------------------------------------------------

    def _round_batches(self, dataset) -> dict:
        fed, fleet = self.fed, self.fleet
        steps = fed.local_epochs * fed.steps_per_epoch
        return draw_client_batches(self.state.rng, dataset,
                                   range(fleet.N), steps, fed.batch_size)

    # -- one round ------------------------------------------------------------

    def round(self, dataset) -> dict:
        task, strategy, fleet, fed = (self.task, self.strategy, self.fleet,
                                      self.fed)
        layout, state = task.layout, self.state
        N, G = fleet.N, layout.G

        # --- participation / fault injection
        participating = np.ones(N, bool)
        if fed.participation < 1.0:
            m = max(1, int(fed.participation * N))
            participating[:] = False
            participating[state.rng.choice(N, m, replace=False)] = True
        if fed.dropout_prob > 0:
            participating &= state.rng.random(N) > fed.dropout_prob
            if not participating.any():
                participating[state.rng.integers(N)] = True

        # --- server: allocation (blue)
        S, k = allocate(strategy, state, task, fleet, fed, layout.flops)
        S &= participating[:, None]

        # --- clients: local training (green)
        batches = self._round_batches(dataset)
        start = self._start_trainable()
        gates = jnp.asarray(S, jnp.float32)
        mmasks = jnp.asarray(fleet.modality_mask, jnp.float32)
        deltas, losses = self.local_update(start, batches, mmasks, gates,
                                           self.rank_gate, fed.lr)

        # --- server: aggregation (orange)
        trained = jnp.asarray(S, jnp.float32)
        if strategy.agg == "cohort":
            W = AG.cohort_weights(layout, trained, mmasks)
        elif strategy.agg == "dimension":
            # cohort-style masked means but without Eq. 4's B-weighting
            ones_mm = jnp.ones_like(mmasks)
            W = AG.cohort_weights(layout, trained, ones_mm)
        elif strategy.agg == "helora":
            W = AG.cohort_weights(layout, trained, jnp.ones_like(mmasks))
        else:  # fedavg: every participant averaged into every group
            W = AG.fedavg_weights(N, G, jnp.asarray(participating, jnp.float32))

        if strategy.agg == "helora":
            new_trainable = self._helora_aggregate(deltas, trained)
        else:
            new_trainable = AG.aggregate(layout, state.trainable, deltas, W,
                                         fed.server_lr)
        # personalized leaves are NEVER aggregated into the global model
        new_trainable = jax.tree.map(
            lambda old, new, pers: old if pers else new,
            state.trainable, new_trainable, self.personal_mask)

        # personalized leaves: clients keep (or cluster-mix) their own values
        self._update_personal(start, deltas, participating)

        # --- divergence tracking (Eq. 5-6) on possession cohorts
        cohort = jnp.asarray(layout.accessible(fleet.modality_mask)
                             & participating[:, None] & S, jnp.float32)
        d = np.asarray(DV.group_divergence(layout, deltas, cohort))
        state.dbar = np.asarray(DV.ema_update(state.dbar, d, fed.gamma))
        per_client_norms = np.asarray(jax.vmap(
            lambda t: mdlora.group_norms(layout, t))(deltas))
        denom = np.maximum(np.asarray(S).sum(0), 1)
        mag = (per_client_norms * S).sum(0) / denom
        touched = S.any(0)
        state.mag_ema[touched] = (0.5 * state.mag_ema + 0.5 * mag)[touched]

        # --- system simulation (time / energy / comm)
        trained_fl, fixed_fl = T.cycle_flops(layout, fed, S,
                                             task.forward_flops_per_example)
        upload = (np.asarray(S, np.float64) @ layout.sizes) * 4.0
        cost = T.simulate_round(fleet, participating, trained_fl, fixed_fl,
                                upload, fed.t_overhead, fed.utilization)

        state.trainable = new_trainable
        state.round += 1
        rec = {"round": state.round, "loss": float(jnp.mean(losses)),
               **cost.as_dict(), "selected_frac": float(S.mean()),
               "divergence": d}
        for key in ("round", "loss", "round_time_s", "upload_mb"):
            self.history[key].append(rec[key] if key != "round_time_s"
                                     else rec["round_time_s"])
        self.history["energy_j"].append(rec["fleet_energy_j"])
        self.history["divergence"].append(d)
        self.history["selected_frac"].append(rec["selected_frac"])
        return rec

    # -- helpers ---------------------------------------------------------------

    def _start_trainable(self):
        """Per-client starting point: personalized leaves from client state,
        shared leaves broadcast from the global model."""
        def pick(g, c, pers):
            if pers:
                return c
            return jnp.broadcast_to(g, (self.fleet.N,) + g.shape)
        return jax.tree.map(pick, self.state.trainable,
                            self.state.client_trainable, self.personal_mask)

    def _update_personal(self, start, deltas, participating):
        if not jax.tree.reduce(lambda a, b: a or b, self.personal_mask, False):
            return
        part = jnp.asarray(participating, jnp.float32)
        cluster = _clusters(self.fleet)
        onehot = jnp.asarray(
            (cluster[:, None] == np.unique(cluster)[None, :]), jnp.float32)
        onehot = onehot * part[:, None]
        mix = onehot @ (onehot / jnp.maximum(onehot.sum(0, keepdims=True),
                                             1.0)).T  # [N, N] cluster-mean mix

        def upd(c_old, s, d, pers):
            if not pers:
                return c_old
            new = s.astype(jnp.float32) + d
            if self.strategy.cluster_mix:
                new = jnp.einsum("nk,k...->n...", mix, new)
            else:  # keep own value; non-participants keep previous
                new = jnp.where(part.reshape((-1,) + (1,) * (new.ndim - 1)) > 0,
                                new, c_old.astype(jnp.float32))
            return new.astype(c_old.dtype)

        self.state.client_trainable = jax.tree.map(
            upd, self.state.client_trainable, start, deltas,
            self.personal_mask)

    def _helora_aggregate(self, deltas, trained):
        """Elementwise rank-masked mean for LoRA leaves; group mean others."""
        layout = self.task.layout
        W = AG.cohort_weights(layout, trained,
                              jnp.ones_like(jnp.asarray(
                                  self.fleet.modality_mask, jnp.float32)))
        base = mdlora.weighted_combine(layout, deltas, W)

        def fix(path, agg, d_stack, m_stack):
            p = mdlora.path_str(path)
            if "lora" not in p:
                return agg
            num = jnp.sum(d_stack.astype(jnp.float32) * m_stack, axis=0)
            den = jnp.maximum(jnp.sum(m_stack, axis=0), 1e-9)
            return num / den

        agg = jax.tree_util.tree_map_with_path(fix, base, deltas,
                                               self.rank_gate)
        return jax.tree.map(
            lambda t, d: (t.astype(jnp.float32)
                          + self.fed.server_lr * d).astype(t.dtype),
            self.state.trainable, agg)

    # -- evaluation -------------------------------------------------------------

    def evaluate(self, dataset) -> float:
        xs = np.concatenate(dataset.test_x)
        ys = np.concatenate(dataset.test_y)
        if jax.tree.reduce(lambda a, b: a or b, self.personal_mask, False):
            # personalized strategies: per-client models on local test data
            f1s = []
            start = self._start_trainable()
            for n in range(self.fleet.N):
                tr_n = jax.tree.map(lambda x: x[n], start)
                src = n % len(dataset.test_y)
                f1s.append(self.task.eval_f1(tr_n, dataset.test_x[src],
                                             dataset.test_y[src]))
            return float(np.mean(f1s))
        return self.task.eval_f1(self.state.trainable, xs, ys)

    # -- full loop ---------------------------------------------------------------

    def run(self, dataset, rounds: int | None = None,
            log_every: int = 0) -> dict:
        rounds = rounds or self.fed.rounds
        for r in range(rounds):
            rec = self.round(dataset)
            if (r + 1) % self.fed.eval_every == 0 or r == rounds - 1:
                f1 = self.evaluate(dataset)
                self.history["f1"].append(f1)
                self.history["f1_round"].append(rec["round"])
                if log_every and (r + 1) % log_every == 0:
                    print(f"[{self.strategy.name}] round {rec['round']:4d} "
                          f"loss {rec['loss']:.4f} F1 {f1:.4f} "
                          f"t={rec['round_time_s']:.3f}s")
        return self.history
