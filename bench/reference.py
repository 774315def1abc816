"""Plain reference of one MMFL round, written from the paper and the
engine's documented conventions, importing nothing of the program.

It follows the same random streams as the engine (``jax.random`` keys
split and folded in the documented order), so that from the same seed it
draws the same cohorts and minibatches, and it computes everything else
plainly: models in ``jax.numpy`` at the configuration's matmul precision
(each task's from its kind's module, ``bench/kinds/<kind>.py``), the
water-filling solve and the inverse-CDF draw in float64 numpy.

Methods: ``lvr`` (Thm 2/9 sampling, Eq. 3 aggregation) and ``stalevre``
(beta of Eq. 20 measured on active clients and extrapolated by Eq. 21 for
the rest, Eq. 18 aggregation).

A draw of the inverse CDF is near a boundary when its uniform lies within
``SEARCH`` of a boundary of the reference's CDF: the program's float32
sampler may then land on the other side.  Of such draws (the ``MAX_NEAR``
nearest in a round) the reference takes the sides whose H1 lies nearest
the program's round monitor, and records what it took: how many draws it
moved across a boundary in the round (``flips``) and how far the farthest
of them lay from it (``settled_margin``).  Both are numbers of
``correct``, each with its limit (``bench/check.py``), so a program that
draws on the wrong side of a boundary shows there.
"""
from __future__ import annotations

import itertools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from world import load_kind

SEARCH = 1e-4         # |u - cdf| under which a draw's side is looked at
MAX_NEAR = 8          # near draws looked at per round, the nearest first
UTILITY_FLOOR = 1e-8  # Assumption 5: the floor on a utility
PROBE_TAKE = 64


# ---------------------------------------------------------------------------
# models
# ---------------------------------------------------------------------------
def cross_entropy(logits, y):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))


def model_fns(spec: Dict[str, Any]):
    """(init(key), logits(params, x)) for one task's configuration entry:
    its kind's plain model (``bench/kinds/<kind>.py``)."""
    kind = load_kind(spec["kind"])
    return (lambda k: kind.init(k, spec), kind.logits)


# ---------------------------------------------------------------------------
# sampling (float64, host)
# ---------------------------------------------------------------------------
def waterfill(U: np.ndarray, m: float) -> np.ndarray:
    """Thm 8/9: p = U/M_v on the saturated processors (the largest row
    masses M_v), p = U (m - j)/sum(rest) on the others, the split j being
    the smallest with (m - j) M_{j+1} <= sum_{i>j} M_i."""
    U = np.where(U > 0, np.maximum(U, UTILITY_FLOOR), 0.0)
    M = U.sum(axis=1)
    has = M > 0
    if m >= has.sum():
        return np.where(has[:, None], U / np.maximum(M, 1e-300)[:, None], 0.0)
    order = np.argsort(-M, kind="stable")
    Ms = M[order]
    rest = Ms.sum() - np.concatenate([[0.0], np.cumsum(Ms)])
    for j in range(len(M) + 1):
        nxt = Ms[j] if j < len(M) else 0.0
        if m - j > 0 and (m - j) * nxt <= rest[j] * (1 + 1e-12):
            break
    sat = np.zeros(len(M), bool)
    sat[order[:j]] = True
    scale = (m - j) / rest[j] if rest[j] > 0 else 0.0
    p = np.where(sat[:, None], U / np.maximum(M, 1e-300)[:, None], U * scale)
    return np.clip(np.where(has[:, None], p, 0.0), 0.0, 1.0)


def draw(p: np.ndarray, u: np.ndarray):
    """Inverse-CDF choice per processor over (models..., idle), with the
    distance of each uniform to the nearest CDF boundary and the index of
    that boundary."""
    probs = np.concatenate([p, np.clip(1 - p.sum(1), 0, 1)[:, None]], 1)
    probs = probs / np.maximum(probs.sum(1, keepdims=True), 1e-300)
    cdf = np.cumsum(probs, axis=1)
    choice = (u[:, None] >= cdf).sum(axis=1)
    margin = np.abs(u[:, None] - cdf[:, :-1])
    return choice, margin.min(axis=1), np.argmin(margin, axis=1)


# ---------------------------------------------------------------------------
# the reference run
# ---------------------------------------------------------------------------
class Reference:
    """Follows the first ``n_rounds`` rounds of a cell from its seed.

    ``fault`` plants one fault in the reference, so that the reference in
    the program's place stands for a broken program: ``"half_batch"``
    drops half of each local minibatch and takes the mean over the rest;
    ``"drop_update"`` leaves the first active client's update out of each
    task's aggregate."""

    def __init__(self, cfg: Dict[str, Any], traffic: Dict[str, Any],
                 world, precision: str = "highest",
                 fault: Optional[str] = None):
        tasks, B, avail = world
        self.method = traffic["method"]
        if self.method not in ("lvr", "stalevre"):
            raise ValueError(f"no reference for method {self.method!r}")
        self.srv = cfg["server"]
        self.precision, self.fault = precision, fault
        self.S, self.N = len(tasks), len(B)
        self.B = np.asarray(B, np.int64)
        self.avail = np.asarray(avail, bool)
        counts = np.stack([t["data"]["count"] for t in tasks], 1)
        counts = np.where(self.avail, counts, 0).astype(np.float64)
        self.d = counts / np.maximum(counts.sum(0, keepdims=True), 1.0)
        self.proc = np.repeat(np.arange(self.N), self.B)
        self.V = len(self.proc)
        self.m = float(np.float32(self.srv["active_rate"])
                       * np.float32(self.V))
        self.cohort = int(min(self.N, max(8, math.ceil(
            2.5 * self.m / self.S) + 4)))
        self.specs = [t["spec"] for t in tasks]
        self.fns = [model_fns(s) for s in self.specs]
        self.data = [{k: jnp.asarray(v) for k, v in t["data"].items()}
                     for t in tasks]
        self.test = [{k: jnp.asarray(v) for k, v in t["test"].items()}
                     for t in tasks]
        self._jit: Dict[Any, Any] = {}

    # -- jitted pieces, one per task model ----------------------------------
    def _fn(self, name: str, s: int):
        """The jitted ``name`` piece for task s's model (tasks of one model
        share it)."""
        spec = tuple(sorted((k, v) for k, v in self.specs[s].items()
                            if k != "name"))
        key = (name, spec)
        if key not in self._jit:
            self._jit[key] = jax.jit(getattr(self, "_" + name)(s))
        return self._jit[key]

    def _loss(self, s):
        logits = self.fns[s][1]
        return lambda p, x, y: cross_entropy(logits(p, x), y)

    def _probe(self, s):
        """Per client: the mean loss over its first min(cap, 64) rows."""
        loss = self._loss(s)

        def probe(p, x, y):
            take = min(x.shape[1], PROBE_TAKE)
            return jax.vmap(lambda xc, yc: loss(p, xc[:take], yc[:take]))(
                x, y)
        return probe

    def _train(self, s):
        """The clients ``idx``, each from the task's params: E minibatch SGD
        steps on its own key (``fold_in(task_key, slot)``); returns the
        updates G = w0 - w_E, stacked in slot order."""
        loss = self._loss(s)
        E, mb = self.srv["local_epochs"], self.srv["batch_size"]
        half = self.fault == "half_batch"

        def one(p, key, x, y, count, lr):
            w = p
            for k in jax.random.split(key, E):
                i = jax.random.randint(k, (mb,), 0, jnp.maximum(count, 1))
                if half:
                    i = i[: mb // 2]
                g = jax.grad(loss)(w, x[i], y[i])
                w = jax.tree.map(lambda a, b: a - lr * b, w, g)
            return jax.tree.map(lambda a, b: a - b, p, w)

        def train(p, task_key, x, y, count, idx, lr):
            keys = jax.vmap(lambda i: jax.random.fold_in(task_key, i))(
                jnp.arange(idx.shape[0]))
            return jax.vmap(one, in_axes=(None, 0, 0, 0, 0, None))(
                p, keys, x[idx], y[idx], count[idx], lr)
        return train

    def _plain_step(self, s):
        """Eq. 3: w - sum_a P_a G_a."""
        return lambda w, G, coeff: jax.tree.map(
            lambda a, g: a - jnp.tensordot(coeff, g, axes=(0, 0)), w, G)

    def _measure(self, s):
        """Eq. 20 per cohort row: <G_a, h_a> and ||h_a||^2."""
        def measure(G, h, idx):
            dot = nrm = 0.0
            for g, hh in zip(jax.tree.leaves(G), jax.tree.leaves(h)):
                hc = hh[idx]
                ax = tuple(range(1, g.ndim))
                dot = dot + jnp.sum(g * hc, axis=ax)
                nrm = nrm + jnp.sum(hc * hc, axis=ax)
            return dot, nrm
        return measure

    def _stale_step(self, s):
        """Eq. 18: Delta = sum_n d_n beta_n h_n + sum_a P_a (G_a -
        beta_a h_a); then h_a <- G_a for the active cohort rows."""
        def step(w, h, G, idx, coeff, act, beta_c, w_stale):
            def delta(hh, g):
                hc = hh[idx]
                shape = (-1,) + (1,) * (g.ndim - 1)
                return (jnp.tensordot(w_stale, hh, axes=(0, 0))
                        + jnp.tensordot(coeff, g - beta_c.reshape(shape) * hc,
                                        axes=(0, 0)))

            def refresh(hh, g):
                on = act.reshape((-1,) + (1,) * (g.ndim - 1)) > 0
                return hh.at[idx].set(jnp.where(on, g, hh[idx]))

            w = jax.tree.map(lambda a, hh, g: a - delta(hh, g), w, h, G)
            return w, jax.tree.map(refresh, h, G)
        return step

    def _uniforms(self, s):
        return lambda key: jax.vmap(lambda i: jax.random.uniform(
            jax.random.fold_in(key, i)))(jnp.arange(self.V))

    def _accuracy(self, s):
        logits = self.fns[s][1]
        return lambda p, x, y: jnp.mean(
            (jnp.argmax(logits(p, x), -1) == y).astype(jnp.float32))

    # -- one round ----------------------------------------------------------
    def init(self, seed: int):
        key = jax.random.PRNGKey(seed)
        params = []
        for s in range(self.S):
            key, k = jax.random.split(key)
            params.append(self.fns[s][0](k))
        state = [self._init_method_state(params[s]) for s in range(self.S)]
        return {"params": params, "mstate": state, "key": key, "round": 0}

    def _init_method_state(self, params):
        if self.method == "lvr":
            return {}
        return {"h": jax.tree.map(lambda a: np.zeros((self.N,) + a.shape,
                                                     np.float32), params),
                "hv": np.zeros(self.N),
                "beta_hat": np.ones(self.N), "beta_last": np.ones(self.N),
                "t_hat": np.zeros(self.N), "t_last": np.zeros(self.N)}

    def round(self, st, h1_program: Optional[np.ndarray] = None):
        """Advance ``st`` by one round; returns the round's record."""
        with jax.default_matmul_precision(self.precision):
            return self._round(st, h1_program)

    def _round(self, st, h1_program):
        S, N, srv = self.S, self.N, self.srv
        r = st["round"]
        lr = np.float32(srv["lr"])
        keys = jax.random.split(st["key"], 2 + S)
        k_sample, task_keys = keys[1], keys[2:]
        losses = np.stack([np.asarray(self._fn("probe", s)(
            st["params"][s], self.data[s]["x"], self.data[s]["y"]),
            np.float64) for s in range(S)], 1)                   # [N, S]
        util = np.where(self.avail, np.abs(losses) * self.d
                        / np.maximum(self.B, 1)[:, None], 0.0)
        p = waterfill(util[self.proc], self.m)                   # [V, S]
        u = np.asarray(self._fn("uniforms", 0)(k_sample), np.float64)
        choice, margin, near = draw(p, u)
        d_v, B_v = self.d[self.proc], self.B[self.proc].astype(np.float64)

        def coeffs_of(ch):
            act = (ch[:, None] == np.arange(S)[None]).astype(np.float64)
            return act, np.where(act > 0, d_v / (B_v[:, None]
                                                 * np.maximum(p, 1e-30)), 0)

        amb = np.nonzero(margin < SEARCH)[0]
        amb = amb[np.argsort(margin[amb], kind="stable")][:MAX_NEAR]
        settled, flipped = choice, []
        if len(amb) and h1_program is not None:
            best = None
            for flips in itertools.product((0, 1), repeat=len(amb)):
                ch = choice.copy()
                for v, f in zip(amb, flips):
                    if f:   # cross the nearest boundary
                        ch[v] = near[v] + (1 if ch[v] <= near[v] else 0)
                gap = np.max(np.abs(coeffs_of(ch)[1].sum(0) - h1_program))
                if best is None or gap < best[0]:
                    best = (gap, ch, flips)
            settled = best[1]
            flipped = [margin[v] for v, f in zip(amb, best[2]) if f]
        act_v, coeff_v = coeffs_of(settled)
        rec = {"loss": (self.d * losses).sum(0), "H1": coeff_v.sum(0),
               "near": int(len(amb)), "flips": len(flipped),
               "settled_margin": float(max(flipped, default=0.0)),
               "losses": losses, "active": []}
        coeff_c = np.zeros((N, S))
        np.add.at(coeff_c, self.proc, coeff_v)
        act_c = np.zeros((N, S))
        np.add.at(act_c, self.proc, act_v)
        act_c = (act_c > 0).astype(np.float64)

        betas = []
        for s in range(S):
            w = st["params"][s]
            idx = np.argsort(-act_c[:, s], kind="stable")[
                :self.cohort].astype(np.int32)
            G = self._fn("train", s)(
                w, task_keys[s], self.data[s]["x"], self.data[s]["y"],
                self.data[s]["count"], idx, lr)
            coeff, act = coeff_c[idx, s].copy(), act_c[idx, s]
            if self.fault == "drop_update" and act.any():
                coeff[int(np.argmax(act > 0))] = 0.0
            rec["active"].append(idx[act > 0])
            if self.method == "lvr":
                st["params"][s] = self._fn("plain_step", s)(
                    w, G, coeff.astype(np.float32))
            else:
                st["params"][s], beta = self._stale(
                    st["mstate"][s], w, G, coeff, act, idx, s, r)
                betas.append(beta)
        if betas:
            rec["beta"] = np.stack(betas)
        st["key"] = keys[0]
        st["round"] = r + 1
        return rec

    def _stale(self, ms, w, G, coeff, act, idx, s, r):
        """beta per client (Eq. 20 measured on the cohort, the others
        extrapolated by Eq. 21), masked by h_valid, then the Eq. 18
        step."""
        hv = ms["hv"]
        dot, nrm = (np.asarray(a, np.float64) for a in
                    self._fn("measure", s)(G, ms["h"], idx))
        measured = np.where(nrm > 0, dot / np.maximum(nrm, 1e-30), 0.0)
        slope = (ms["beta_hat"] - ms["beta_last"]) / np.maximum(
            ms["t_hat"] - ms["t_last"], 1.0)
        est = np.clip(ms["beta_hat"] - slope * np.maximum(
            r - ms["t_hat"], 0.0), 0.0, 1.0)
        beta = est.copy()
        beta[idx] = np.where(act > 0, measured, est[idx])
        on = np.zeros(self.N, bool)
        on[idx] = (act * hv[idx]) > 0
        meas_n = np.zeros(self.N)
        meas_n[idx] = measured
        ms["t_last"] = np.where(on, ms["t_hat"], ms["t_last"])
        ms["beta_last"] = np.where(on, np.clip(meas_n, 0, 1),
                                   ms["beta_last"])
        ms["beta_hat"] = np.where(on, 1.0, ms["beta_hat"])
        ms["t_hat"] = np.where(on, float(r), ms["t_hat"])
        beta = beta * hv
        f32 = lambda a: np.asarray(a, np.float32)
        w, ms["h"] = self._fn("stale_step", s)(
            w, ms["h"], G, idx, f32(coeff), f32(act), f32(beta[idx]),
            f32(self.d[:, s] * beta))
        hv = hv.copy()
        hv[idx[act > 0]] = 1.0
        ms["hv"] = hv
        return w, beta

    def accuracy(self, st) -> np.ndarray:
        with jax.default_matmul_precision(self.precision):
            return np.asarray([float(self._fn("accuracy", s)(
                st["params"][s], self.test[s]["x"], self.test[s]["y"]))
                for s in range(self.S)])
