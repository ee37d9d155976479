"""Plain reference of a RELIEF FedBuff flush, and the inputs it is fed.

Imports nothing of the program. Given the weights and data the benchmark
makes from the seed, and the cohort of each flush (which clients, the model
version each pulled, its completion ticket, the parameter groups it trains
and the modalities it holds), it recomputes the flushes in float32 with
``default_matmul_precision("highest")``:

1. each client draws its local batches by the counter rule of the cohort
   runtime: ``default_rng([fed_seed, client, ticket])`` picks
   ``[steps, batch]`` windows of subject ``client % subjects``;
2. local training: Adam (b1 .9, b2 .999, eps 1e-8, fresh moments) on the
   cross-entropy of the multimodal model, the gradient of each parameter
   group multiplied by the client's 0/1 gate; the delta (trained - start)
   gated again;
3. int8 uplink: one symmetric scale per (client, leaf), max|x| / 127;
4. the flush: each group averaged over the clients that trained it, with
   weights staleness discount 1/(1+s)^a times, for the shared fusion B,
   (modalities held)/M (paper Eq. 3-4); the model moves by the average;
5. the divergence of each group over the clients that hold and trained it
   (Eq. 5, on the int8 uplink), smoothed by the EMA (Eq. 6, gamma, from
   1e-6 everywhere) for the groups some client trained.

``allocation_errors`` applies the paper's allocation rule to every
recorded dispatch: Eq. 7 budgets from the device table of the
configuration (T* the time the fastest device type needs for all its
accessible groups, IV-B2b), the fusion blocks of the modalities held
always, then the accessible groups of highest smoothed divergence at the
version the client pulled.

The model (Sec. III-B, Backbone 2): per modality a patch transformer
(frozen; LoRA on Q, V and the FFN input), features of absent modalities
zeroed, fusion projection w0 plus LoRA (a, b), a two-layer ReLU head.

``dtype=jnp.bfloat16`` computes local training in bfloat16 instead (params,
activations, data; Adam moments in float32): the lower-precision control.
"""
from __future__ import annotations

import functools
import json
import math
from fractions import Fraction

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8


def key_data(seed: int, salt: int) -> np.ndarray:
    """Raw threefry key words from any non-negative integer seed."""
    return np.random.SeedSequence([int(seed), salt]).generate_state(
        2).astype(np.uint32)


# ---------------------------------------------------------------------------
# weights and data from the seed
# ---------------------------------------------------------------------------


def _cfg_key(cfg: dict) -> str:
    return json.dumps(cfg, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _params_fn(cfg_s: str):
    cfg = json.loads(cfg_s)
    d, ff, L, r = cfg["enc_d"], cfg["enc_ff"], cfg["enc_layers"], cfg["lora_rank"]
    mods = cfg["modalities"]
    D = sum(m["d_feat"] for m in mods)
    df, hh, nc = cfg["d_fused"], cfg["head_hidden"], cfg["n_classes"]

    def dense(k, shape):
        return jax.random.normal(k, shape, jnp.float32) / math.sqrt(shape[-2])

    def make(kd):
        key = jax.random.wrap_key_data(kd)
        ks = iter(jax.random.split(key, 64))
        enc, lenc = {}, {}
        for m in mods:
            c = m["channels"]
            layers = {
                "attn": {n: dense(next(ks), (L, d, d))
                         for n in ("wq", "wk", "wv", "wo")},
                "mlp": {"wi": dense(next(ks), (L, d, ff)),
                        "wg": dense(next(ks), (L, d, ff)),
                        "wo": dense(next(ks), (L, ff, d))},
                "ln1": 0.1 * jax.random.normal(next(ks), (L, d)),
                "ln2": 0.1 * jax.random.normal(next(ks), (L, d)),
            }
            enc[m["name"]] = {"patch": dense(next(ks), (cfg["patch"] * c, d)),
                              "layers": layers,
                              "proj": dense(next(ks), (d, m["d_feat"]))}
            lenc[m["name"]] = {"layers": {
                n: {"a": dense(next(ks), (L, d, r)),
                    "b": jnp.zeros((L, r, dout), jnp.float32)}
                for n, dout in (("wq", d), ("wv", d), ("wi", ff))}}
        base = {"encoders": enc, "fusion_w0": dense(next(ks), (D, df)),
                "head": {"w1": dense(next(ks), (df, hh)),
                         "w2": dense(next(ks), (hh, nc))}}
        lora = {"encoders": lenc,
                "fusion": {"a": dense(next(ks), (D, r)),
                           "b": jnp.zeros((r, df), jnp.float32)}}
        return {"base": base, "lora": lora}

    return jax.jit(make)


def make_params(cfg: dict, seed: int) -> dict:
    """All weights of the model, on the device, in one jitted call."""
    return _params_fn(_cfg_key(cfg))(key_data(seed, 1))


def split_params(params: dict) -> tuple[dict, dict]:
    """-> (trainable {head, lora}, frozen base without the head)."""
    trainable = {"head": params["base"]["head"], "lora": params["lora"]}
    static = {k: v for k, v in params["base"].items() if k != "head"}
    return trainable, static


@functools.lru_cache(maxsize=None)
def _data_fn(cfg_s: str):
    cfg = json.loads(cfg_s)
    S, W = cfg["n_clients"], cfg["windows_per_subject"]
    T, nc = cfg["window"], cfg["n_classes"]
    t = jnp.arange(T, dtype=jnp.float32) / 50.0  # 50 Hz

    def make(kd):
        key = jax.random.wrap_key_data(kd)
        kp, ky, kg, kph, kn, knoise = jax.random.split(key, 6)
        prior = jax.random.dirichlet(kp, cfg["alpha"] * jnp.ones(nc), (S,))
        y = jax.random.categorical(ky, jnp.log(prior + 1e-12)[:, None, :],
                                   shape=(S, W)).astype(jnp.int32)
        gain = jnp.exp(0.1 * jax.random.normal(kg, (S, 1, 1)))
        noise = jax.random.uniform(kn, (S, 1, 1), minval=0.12, maxval=0.3)
        cls = y.astype(jnp.float32)[..., None]  # [S, W, 1]
        parts, ch0 = [], 0
        for m in cfg["modalities"]:
            for c in range(m["channels"]):
                ph = jax.random.uniform(jax.random.fold_in(kph, ch0),
                                        (S, W, 1), maxval=2 * math.pi)
                if m["kind"] == "imu":
                    f = (0.6 + 0.37 * cls) * (1.0 + 0.11 * c)
                    sig = (1.0 + 0.3 * cls) * (
                        jnp.sin(2 * math.pi * f * t + ph)
                        + 0.5 * jnp.sin(4 * math.pi * f * t + 1.7 * ph))
                else:  # pulse: a class-dependent level with a slow drift
                    sig = (0.55 + 0.07 * cls) + 0.08 * jnp.sin(
                        2 * math.pi * 0.08 * (1 + 0.2 * cls) * t + ph)
                parts.append(gain * sig)
                ch0 += 1
        x = jnp.stack(parts, -1)  # [S, W, T, C]
        x = x + noise[..., None] * jax.random.normal(knoise, x.shape)
        return x.astype(jnp.float32), y

    return jax.jit(make)


class Dataset:
    """Per-subject windows, the layout the program's runtimes read
    (``train_x[s]`` [n, T, C], ``train_y[s]`` [n]; likewise test)."""

    def __init__(self, x: np.ndarray, y: np.ndarray, test_frac: float):
        n_te = max(1, int(test_frac * x.shape[1]))
        self.train_x = [x[s, n_te:] for s in range(x.shape[0])]
        self.train_y = [y[s, n_te:] for s in range(x.shape[0])]
        self.test_x = [x[s, :n_te] for s in range(x.shape[0])]
        self.test_y = [y[s, :n_te] for s in range(x.shape[0])]


def make_dataset(cfg: dict, seed: int) -> Dataset:
    x, y = _data_fn(_cfg_key(cfg))(key_data(seed, 2))
    return Dataset(np.asarray(x), np.asarray(y), cfg["test_frac"])


def draw_batches(cfg: dict, data: Dataset, fed_seed: int, client: int,
                 ticket: int) -> tuple[np.ndarray, np.ndarray]:
    steps = cfg["local_epochs"] * cfg["steps_per_epoch"]
    r = np.random.default_rng([fed_seed, int(client), int(ticket)])
    src = int(client) % len(data.train_y)
    idx = r.integers(0, len(data.train_y[src]),
                     size=(steps, cfg["batch_size"]))
    return data.train_x[src][idx], data.train_y[src][idx]


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def _rms(w, x):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + 1e-6)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _encoder(cfg, base, lora, x):
    """x [B, T, C] -> [B, d_feat]."""
    B, T, C = x.shape
    P, d, H = cfg["patch"], cfg["enc_d"], cfg["enc_heads"]
    hd = d // H
    n = T // P
    s = cfg["lora_alpha"] / cfg["lora_rank"]
    h = x[:, : n * P].reshape(B, n, P * C) @ base["patch"]
    lay, llay = base["layers"], lora["layers"]
    for i in range(cfg["enc_layers"]):
        def ad(name, v):
            return (v @ llay[name]["a"][i]) @ llay[name]["b"][i] * s
        hn = _rms(lay["ln1"][i], h)
        q = (hn @ lay["attn"]["wq"][i] + ad("wq", hn)).reshape(B, n, H, hd)
        k = (hn @ lay["attn"]["wk"][i]).reshape(B, n, H, hd)
        v = (hn @ lay["attn"]["wv"][i] + ad("wv", hn)).reshape(B, n, H, hd)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
        h = h + o.reshape(B, n, d) @ lay["attn"]["wo"][i]
        hn = _rms(lay["ln2"][i], h)
        up = hn @ lay["mlp"]["wi"][i] + ad("wi", hn)
        h = h + (jax.nn.silu(hn @ lay["mlp"]["wg"][i]) * up) @ lay["mlp"]["wo"][i]
    return jnp.mean(h, axis=1) @ base["proj"]


def forward(cfg, trainable, static, x, mmask):
    """Logits [B, n_classes]; x [B, T, sum C], mmask [M]."""
    feats, off = [], 0
    for i, m in enumerate(cfg["modalities"]):
        xm = x[..., off: off + m["channels"]]
        off += m["channels"]
        h = _encoder(cfg, static["encoders"][m["name"]],
                     trainable["lora"]["encoders"][m["name"]], xm)
        feats.append(h * mmask[i].astype(h.dtype))
    h = jnp.concatenate(feats, -1)
    fl = trainable["lora"]["fusion"]
    s = cfg["lora_alpha"] / cfg["lora_rank"]
    z = jax.nn.relu(h @ static["fusion_w0"] + (h @ fl["a"]) @ fl["b"] * s)
    z = jax.nn.relu(z @ trainable["head"]["w1"])
    return z @ trainable["head"]["w2"]


def _xent(logits, y):
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, -1)
    return jnp.mean(lse - jnp.take_along_axis(logits, y[:, None], -1)[:, 0])


# ---------------------------------------------------------------------------
# parameter groups (paper Sec. III-B): names as in the cohort's gate columns
# ---------------------------------------------------------------------------


def leaf_groups(cfg: dict, trainable: dict) -> dict:
    """path -> how the leaf splits into groups: ("rows", [(s, e, name)]),
    ("layers", [name per layer]) or ("whole", name)."""
    out, off, rows = {}, 0, []
    for m in cfg["modalities"]:
        rows.append((off, off + m["d_feat"], f"A_{m['name']}"))
        off += m["d_feat"]
    for path, _ in jax.tree_util.tree_flatten_with_path(trainable)[0]:
        keys = [p.key for p in path]
        p = jax.tree_util.keystr(path)
        if keys[:2] == ["lora", "fusion"]:
            out[p] = ("rows", rows) if keys[2] == "a" else ("whole", "B_shared")
        elif keys[0] == "head":
            out[p] = ("whole", f"H_{keys[1]}")
        else:  # lora / encoders / <modality> / layers / <target> / a|b
            out[p] = ("layers", [f"E_{keys[2]}_L{i}"
                                 for i in range(cfg["enc_layers"])])
    return out


def gate_tree(cfg, trainable, gates: dict):
    """Per-leaf gate arrays from {group name: 0/1}."""
    groups = leaf_groups(cfg, trainable)

    def one(path, leaf):
        kind, g = groups[jax.tree_util.keystr(path)]
        if kind == "whole":
            return np.float32(gates[g])
        if kind == "rows":
            col = np.zeros((leaf.shape[0], 1), np.float32)
            for s, e, name in g:
                col[s:e] = gates[name]
            return col
        return np.array([gates[n] for n in g], np.float32).reshape(
            (-1,) + (1,) * (leaf.ndim - 1))
    return jax.tree_util.tree_map_with_path(one, trainable)


# ---------------------------------------------------------------------------
# local training
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _train_fn(cfg_s: str, dtype_name: str, chunk: int):
    cfg = json.loads(cfg_s)
    dtype = jnp.dtype(dtype_name)
    lr = cfg["lr"]

    def one(start, static, x, y, mmask, gates):
        cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)  # noqa: E731
        tr0 = cast(start)
        st = cast(static)
        mom = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), tr0)

        def loss_fn(tr, xb, yb):
            return _xent(forward(cfg, tr, st, xb.astype(dtype), mmask), yb)

        def step(carry, batch):
            tr, m, v, t = carry
            loss, g = jax.value_and_grad(loss_fn)(tr, *batch)
            g = jax.tree.map(lambda a, k: a.astype(jnp.float32) * k, g, gates)
            t = t + 1.0
            m = jax.tree.map(lambda a, b: B1 * a + (1 - B1) * b, m, g)
            v = jax.tree.map(lambda a, b: B2 * a + (1 - B2) * b * b, v, g)
            bc1, bc2 = 1 - B1 ** t, 1 - B2 ** t
            tr = jax.tree.map(
                lambda p, a, b: (p.astype(jnp.float32) - lr * (a / bc1) / (
                    jnp.sqrt(b / bc2) + EPS)).astype(dtype), tr, m, v)
            return (tr, m, v, t), loss

        (tr, _, _, _), losses = jax.lax.scan(
            step, (tr0, mom, mom, jnp.float32(0.0)), (x, y))
        delta = jax.tree.map(
            lambda a, b, k: (a.astype(jnp.float32) - b.astype(jnp.float32)) * k,
            tr, tr0, gates)
        return delta, jnp.mean(losses)

    def many(starts, static, x, y, mmask, gates):
        return jax.lax.map(lambda c: one(c[0], static, *c[1:]),
                           (starts, x, y, mmask, gates), batch_size=chunk)

    return jax.jit(many)


def quantize_int8(x: np.ndarray) -> np.ndarray:
    """Symmetric int8 round trip with one scale for the whole array."""
    x = np.asarray(x, np.float32)
    scale = np.float32(max(float(np.abs(x).max()), 1e-12) / 127.0)
    q = np.clip(np.round(x / scale), -127, 127)
    return (q * scale).astype(np.float64)


# ---------------------------------------------------------------------------
# the flushes
# ---------------------------------------------------------------------------


def follow(cfg: dict, seed: int, fed_seed: int, data: Dataset,
           cohorts: list[dict], group_names: list[str], dtype=jnp.float32,
           chunk: int = 16, params: dict | None = None) -> dict:
    """Recompute the flushes of ``cohorts`` from the seed's weights.

    Each cohort: ``ids`` [K] clients, ``versions`` [K] model versions
    pulled, ``tickets`` [K], ``S`` [K, G] 0/1 gates with columns named by
    ``group_names``, ``mmask`` [K, M] modalities held. -> ``losses`` per
    flush and ``models`` (trainable after each flush, host float64 trees,
    the start first)."""
    params = make_params(cfg, seed) if params is None else params
    trainable, static = split_params(params)
    M = len(cfg["modalities"])
    a = cfg["staleness_exponent"]
    to64 = lambda t: jax.tree.map(lambda v: np.asarray(v, np.float64), t)  # noqa: E731
    models = [to64(trainable)]
    losses = []
    groups = leaf_groups(cfg, trainable)
    dbars = [dict.fromkeys(group_names, 1e-6)]
    msq = [dict.fromkeys(group_names, 0.0)]
    fn = _train_fn(_cfg_key(cfg), jnp.dtype(dtype).name, chunk)
    treedef = jax.tree.structure(trainable)
    for f, co in enumerate(cohorts):
        K = len(co["ids"])
        gates = [dict(zip(group_names, map(float, co["S"][k])))
                 for k in range(K)]
        starts = [models[int(v)] for v in co["versions"]]
        batches = [draw_batches(cfg, data, fed_seed, c, t)
                   for c, t in zip(co["ids"], co["tickets"])]
        stack = lambda ts: jax.tree.map(  # noqa: E731
            lambda *v: jnp.asarray(np.stack(v), jnp.float32), *ts)
        with jax.default_matmul_precision("highest"):
            deltas, l = fn(stack(starts), static,
                           jnp.asarray(np.stack([b[0] for b in batches])),
                           jnp.asarray(np.stack([b[1] for b in batches])),
                           jnp.asarray(co["mmask"], jnp.float32),
                           stack([gate_tree(cfg, trainable, g) for g in gates]))
        losses.append(float(np.mean(np.asarray(l, np.float64))))
        dl = [np.asarray(v) for v in jax.tree.leaves(deltas)]
        # int8 uplink, per (client, leaf)
        deq = [jax.tree.unflatten(treedef, [quantize_int8(v[k]) for v in dl])
               for k in range(K)]
        stale = f - np.asarray(co["versions"], np.float64)
        disc = 1.0 / np.power(1.0 + stale, a)
        mcount = np.asarray(co["mmask"], np.float64).sum(1)
        S = np.asarray(co["S"], np.float64)
        u = np.ones_like(S)
        u[:, group_names.index("B_shared")] = (mcount / M)[:]
        w = S * u * disc[:, None]
        den = w.sum(0)
        W = np.where(den > 0, w / np.maximum(den, 1e-300), 0.0)
        Wd = [dict(zip(group_names, W[k])) for k in range(K)]
        agg = None
        for k in range(K):
            gk = gate_tree(cfg, trainable, Wd[k])
            term = jax.tree.map(lambda d_, g_: d_ * np.asarray(g_, np.float64),
                                deq[k], gk)
            agg = term if agg is None else jax.tree.map(np.add, agg, term)
        models.append(jax.tree.map(np.add, models[-1], agg))
        held = np.asarray(co["mmask"], bool)
        C = {g: held[:, accessible_mod(cfg, g)] & (S[:, i] > 0)
             if accessible_mod(cfg, g) is not None else S[:, i] > 0
             for i, g in enumerate(group_names)}
        d, m, n = cohort_divergence(groups, deq, C)
        prev_d, prev_m = dbars[-1], msq[-1]
        g_ = cfg["gamma"]
        dbars.append({g: g_ * d[g] + (1 - g_) * prev_d[g] if n[g] else prev_d[g]
                      for g in group_names})
        msq.append({g: g_ * m[g] + (1 - g_) * prev_m[g] if n[g] else prev_m[g]
                    for g in group_names})
    return {"losses": losses, "models": models, "dbar": dbars, "msq": msq}


def cohort_divergence(groups: dict, deq: list, C: dict):
    """Per group over its cohort ``C[g]`` (bool [K]): the divergence
    E|x - mean|^2 (Eq. 5), the mean squared norm E|x|^2 and the count."""
    d = dict.fromkeys(C, 0.0)
    m = dict.fromkeys(C, 0.0)
    n = {g: int(c.sum()) for g, c in C.items()}
    flat = [jax.tree_util.tree_flatten_with_path(t)[0] for t in deq]
    for j, (path, _) in enumerate(flat[0]):
        V = np.stack([f[j][1] for f in flat])  # [K, ...] float64
        kind, g = groups[jax.tree_util.keystr(path)]
        if kind == "whole":
            parts = [(g, V)]
        elif kind == "rows":
            parts = [(name, V[:, s:e]) for s, e, name in g]
        else:
            parts = [(name, V[:, i]) for i, name in enumerate(g)]
        for name, X in parts:
            if not n[name]:
                continue
            Xc = X[C[name]].reshape(n[name], -1)
            d[name] += float(((Xc - Xc.mean(0)) ** 2).sum()) / n[name]
            m[name] += float((Xc ** 2).sum()) / n[name]
    return d, m, n


# ---------------------------------------------------------------------------
# allocation (paper IV-B2, Eq. 7)
# ---------------------------------------------------------------------------


def accessible_mod(cfg: dict, group: str) -> int | None:
    """Index of the modality a group belongs to (A_<m>, E_<m>_*), else
    None (the shared fusion B and the head belong to every client)."""
    names = [m["name"] for m in cfg["modalities"]]
    parts = group.split("_")
    if parts[0] in ("A", "E") and parts[1] in names:
        return names.index(parts[1])
    return None


def budgets(cfg: dict, group_names: list[str]) -> dict:
    """Eq. 7 per device type: k = clip(max(|M|, floor((T* - T_o) / tau)),
    0, g_max), tau proportional to 1/TOPS (the mean group's profiled
    time), T* - T_o the time in which the fastest type trains all its
    accessible groups. -> {frozenset of modalities held: (k, g_max)}."""
    names = [m["name"] for m in cfg["modalities"]]
    present = [d for d, n in zip(cfg["devices"], cfg["fleet"]) if n > 0]
    out = {}
    for dev in present:
        held = {names.index(x) for x in dev["modalities"]}
        g_max = sum(1 for g in group_names
                    if accessible_mod(cfg, g) in held | {None})
        out[frozenset(held)] = (g_max, len(held), Fraction(str(dev["tops"])))
    t_star = min(g / tops for g, _, tops in out.values())  # x tau * TOPS
    return {h: (min(g, max(nm, math.floor(t_star * tops))), g)
            for h, (g, nm, tops) in out.items()}


def allocation_errors(cfg: dict, group_names: list[str], cohorts: list[dict],
                      dbar: list[dict]) -> int:
    """Recorded dispatches whose gates break the rule: the count is not the
    budget, a mandatory fusion block is off, an inaccessible group is on,
    or (where the smoothed divergence at the version pulled is known and no
    tie decides) the trained groups are not those of highest divergence."""
    k_of = budgets(cfg, group_names)
    mods = [accessible_mod(cfg, g) for g in group_names]
    wrong = 0
    for co in cohorts:
        for S, held, v in zip(np.asarray(co["S"]) > 0,
                              np.asarray(co["mmask"], bool), co["versions"]):
            hs = frozenset(np.flatnonzero(held).tolist())
            if hs not in k_of:
                wrong += 1
                continue
            k, _ = k_of[hs]
            acc = np.array([m is None or m in hs for m in mods])
            mand = np.array([g.startswith("A_") for g in group_names]) & acc
            ok = S.sum() == k and not (mand & ~S).any() and not (S & ~acc).any()
            cand = acc & ~mand
            free = k - int(mand.sum())
            if ok and 0 < free < cand.sum() and int(v) < len(dbar):
                score = np.array([dbar[int(v)][g] for g in group_names])
                order = np.sort(score[cand])[::-1]
                edge = order[free - 1], order[free]
                if edge[0] - edge[1] > 1e-3 * abs(edge[0]):  # no tie decides
                    ok = bool((S[cand] == (score[cand] >= edge[0])).all())
            wrong += not ok
    return wrong
