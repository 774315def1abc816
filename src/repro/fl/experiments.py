"""Builders + the ``run_experiment`` entry point for the paper's §6.1
experiment settings on synthetic data.

``build_setting(n_models, ...)`` reproduces:
  * 120 clients; each client sees 30% of labels;
  * model-specific high/low data groups (10% / 90%, ≈52.6% of data at the
    high group);
  * availability: 90% of clients can train all S models, 10% only S-1;
  * budgets B_i: 25% |S_i|, 50% ceil(|S_i|/2), 25% 1;
  * 3-model setting: 3x Fashion-MNIST-like CNN tasks;
  * 5-model setting: 2x FMNIST-like + 1x CIFAR-like CNN + 1x EMNIST-like CNN
    + 1x Shakespeare-like LSTM.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import methods
from repro.core.async_engine import AsyncConfig, AsyncRoundEngine
from repro.core.engine import (PROBE_TAKE, RoundEngine, World,
                               build_world_arrays)
from repro.core.server import MMFLServer, ModelAdapter, ServerConfig, Task
from repro.configs.base import ArchConfig
from repro.configs.registry import get_config
from repro.data import partition, synthetic
from repro.models import cnn, lstm, transformer


def _cnn_adapter(n_classes: int, channels: int, in_ch: int = 1,
                 side: int = 28) -> ModelAdapter:
    return ModelAdapter(
        init=lambda key: cnn.init(key, n_classes, channels, in_ch,
                                  side=side),
        loss_fn=cnn.loss_fn,
        accuracy=cnn.accuracy,
    )


def _lstm_adapter(vocab: int, d_embed: int = 24,
                  d_hidden: int = 64) -> ModelAdapter:
    return ModelAdapter(
        init=lambda key: lstm.init(key, vocab, d_embed=d_embed,
                                   d_hidden=d_hidden),
        loss_fn=lstm.loss_fn,
        accuracy=lstm.accuracy,
    )


def _image_task(rng, name: str, n_clients: int, n_classes: int = 10,
                channels: int = 8, n_per_class: int = 200,
                label_frac: float = 0.3, side: int = 28,
                in_ch: int = 1) -> Task:
    x, y = synthetic.make_image_task(rng, n_classes=n_classes,
                                     n_per_class=n_per_class, side=side,
                                     in_ch=in_ch)
    n_test = max(64, len(y) // 10)
    test = {"x": jnp.asarray(x[:n_test]), "y": jnp.asarray(y[:n_test])}
    part = partition.label_shard_partition(rng, x[n_test:], y[n_test:],
                                           n_clients, label_frac=label_frac)
    data = {k: jnp.asarray(v) for k, v in part.items() if k != "high"}
    return Task(name=name,
                model=_cnn_adapter(n_classes, channels, in_ch, side),
                data=data, test=test)


def _char_task(rng, name: str, n_clients: int, vocab: int = 48,
               seq_len: int = 24, stream_lens: Optional[Sequence[int]] = None,
               n_test: int = 128, d_embed: int = 24,
               d_hidden: int = 64) -> Task:
    """One stream per speaker, of ``stream_lens`` characters each (default:
    ``max(n_clients + 16, 64)`` speakers of 256); the first ``n_test``
    sequences are the test set, and each client is one of the remaining
    speakers (``partition.stream_partition``)."""
    if stream_lens is None:
        stream_lens = [256] * max(n_clients + 16, 64)
    x, y, sid = synthetic.make_char_task(rng, vocab=vocab,
                                         n_streams=len(stream_lens),
                                         stream_len=stream_lens,
                                         seq_len=seq_len)
    test = {"x": jnp.asarray(x[:n_test]), "y": jnp.asarray(y[:n_test])}
    part = partition.stream_partition(rng, x[n_test:], y[n_test:],
                                      sid[n_test:], n_clients)
    data = {k: jnp.asarray(v) for k, v in part.items() if k != "high"}
    return Task(name=name, model=_lstm_adapter(vocab, d_embed, d_hidden),
                data=data, test=test)


def align_task_caps(tasks: List[Task]) -> List[Task]:
    """Wrap-pad per-task sample capacities to the max among tasks that
    agree on every OTHER data/test shape, so same-architecture tasks share
    a compile signature and fuse into one vmapped task group
    (``repro.core.engine.group_tasks``).  Partitions draw different caps
    (the sample axis of ``data["x"]``) per task; nothing reads rows beyond
    ``count`` — minibatch indices stay < count and the loss probe takes
    ``min(cap, 64)`` — so for caps >= 64 (every §6.1 world) the aligned
    world trains bit-identically.  That precondition is ENFORCED, not
    assumed: a task whose cap is below the 64-sample probe boundary is
    left unaligned (widening it would widen its loss probe with wrapped
    duplicates and silently shift every sampling stream) — it simply
    stays in its own compile group.  Wrapped rows repeat real rows, the
    partitioner's own padding convention."""
    sig = lambda t: (
        tuple((k, v.shape[:1] + v.shape[2:], str(v.dtype))
              for k, v in sorted(t.data.items()) if k != "count"),
        tuple((k, tuple(v.shape), str(v.dtype))
              for k, v in sorted(t.test.items())))
    cap_to: Dict[Any, int] = {}
    for t in tasks:
        key = sig(t)
        cap_to[key] = max(cap_to.get(key, 0), int(t.data["x"].shape[1]))
    out = []
    for t in tasks:
        cap, target = int(t.data["x"].shape[1]), cap_to[sig(t)]
        if cap == target or cap < PROBE_TAKE:
            # caps under the probe boundary must keep their exact probe
            # slice — alignment would change min(cap, PROBE_TAKE)
            out.append(t)
            continue
        wrap = np.arange(target) % cap
        data = {k: (jnp.asarray(np.asarray(v)[:, wrap])
                    if k in ("x", "y") else v)
                for k, v in t.data.items()}
        out.append(Task(name=t.name, model=t.model, data=data, test=t.test))
    return out


def build_setting(n_models: int = 3, n_clients: int = 120, seed: int = 0,
                  small: bool = False, avail_rate: Optional[float] = None,
                  label_frac: Optional[float] = None
                  ) -> Tuple[List[Task], np.ndarray, np.ndarray]:
    """Returns (tasks, B, avail).  ``small=True`` shrinks everything for
    CI-speed tests while keeping the same structure.

    World axes (None keeps the paper's §6.1 defaults, bit-identically):
    ``avail_rate`` — fraction of clients able to train all S models
    (default 0.9); ``label_frac`` — heterogeneity, the label fraction each
    client sees (default 0.3)."""
    rng = np.random.default_rng(seed)
    if small:
        n_clients = min(n_clients, 24)
    npc = 60 if small else 200
    lf = 0.3 if label_frac is None else float(label_frac)
    tasks: List[Task] = []
    if n_models == 3:
        for i in range(3):
            tasks.append(_image_task(rng, f"fmnist-{i}", n_clients,
                                     n_per_class=npc, label_frac=lf))
    elif n_models == 5:
        tasks.append(_image_task(rng, "fmnist-0", n_clients, n_per_class=npc,
                                 label_frac=lf))
        tasks.append(_image_task(rng, "fmnist-1", n_clients, n_per_class=npc,
                                 label_frac=lf))
        tasks.append(_image_task(rng, "cifar", n_clients, n_classes=10,
                                 channels=12, n_per_class=npc,
                                 label_frac=lf))
        tasks.append(_image_task(rng, "emnist", n_clients, n_classes=26,
                                 n_per_class=max(40, npc // 2),
                                 label_frac=lf))
        tasks.append(_char_task(rng, "shakespeare", n_clients))
    else:
        for i in range(n_models):
            tasks.append(_image_task(rng, f"task-{i}", n_clients,
                                     n_per_class=npc, label_frac=lf))
    avail = partition.availability(
        rng, n_clients, n_models,
        frac_all=0.9 if avail_rate is None else float(avail_rate))
    B = partition.processor_budgets(rng, avail)
    # same-architecture tasks share one compile signature (and therefore
    # one vmapped task group) once their drawn caps agree
    return align_task_caps(tasks), B, avail


def make_server(method: str, n_models: int = 3, seed: int = 0,
                small: bool = False, rounds_cfg: dict | None = None
                ) -> MMFLServer:
    tasks, B, avail = build_setting(n_models, seed=seed, small=small)
    cfg = ServerConfig(method=method, seed=seed, **(rounds_cfg or {}))
    return MMFLServer(tasks, B, avail, cfg)


# ---------------------------------------------------------------------------
# micro setting: linear softmax tasks (seconds-fast compiles)
# ---------------------------------------------------------------------------


def _linear_adapter(n_feat: int, n_classes: int) -> ModelAdapter:
    def init(key):
        k1, _ = jax.random.split(key)
        return {"w": 0.01 * jax.random.normal(k1, (n_feat, n_classes)),
                "b": jnp.zeros((n_classes,))}

    def loss_fn(p, batch):
        logits = batch["x"] @ p["w"] + p["b"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], 1))

    def accuracy(p, batch):
        logits = batch["x"] @ p["w"] + p["b"]
        return jnp.mean(jnp.argmax(logits, -1) == batch["y"])

    return ModelAdapter(init=init, loss_fn=loss_fn, accuracy=accuracy)


def build_linear_setting(n_models: int = 2, n_clients: int = 16,
                         n_feat: int = 16, n_classes: int = 4,
                         cap: int = 32, seed: int = 0,
                         avail_rate: Optional[float] = None
                         ) -> Tuple[List[Task], np.ndarray, np.ndarray]:
    """Tiny separable linear-softmax tasks with heterogeneous budgets.

    Compiles in milliseconds — used by the all-methods registry tests and
    the round-engine benchmark, where the CNN world's compute would mask
    the orchestration costs under measurement.

    ``avail_rate`` (world axis; default None = everyone available) draws a
    §6.1-style availability mask from a rate-keyed side stream, so the
    default world stays bit-identical to the pre-axis builder."""
    rng = np.random.default_rng(seed)
    tasks: List[Task] = []
    for s in range(n_models):
        W = rng.normal(size=(n_feat, n_classes))
        x = rng.normal(size=(n_clients, cap, n_feat)).astype(np.float32)
        y = np.argmax(x @ W + 0.5 * rng.normal(
            size=(n_clients, cap, n_classes)), axis=-1)
        xt = rng.normal(size=(64, n_feat)).astype(np.float32)
        yt = np.argmax(xt @ W, axis=-1)
        tasks.append(Task(
            name=f"linear-{s}", model=_linear_adapter(n_feat, n_classes),
            data={"x": jnp.asarray(x), "y": jnp.asarray(y),
                  "count": jnp.full((n_clients,), cap, jnp.int32)},
            test={"x": jnp.asarray(xt), "y": jnp.asarray(yt)}))
    B = rng.integers(1, 4, n_clients)
    avail = np.ones((n_clients, n_models), bool)
    if avail_rate is not None:
        avail = partition.availability(
            np.random.default_rng((seed, 1)), n_clients, n_models,
            frac_all=float(avail_rate))
    return tasks, B, avail


# ---------------------------------------------------------------------------
# real-model setting: registry archs through the full model stack + kernels
# ---------------------------------------------------------------------------


def _model_cfg(name: str) -> ArchConfig:
    """Test-scale dims for a registry arch: real structure (GQA heads /
    SSM recurrence, RoPE, tied embeddings, the family's block wiring) at
    CI-compilable sizes.  ``.reduced()`` then a further shrink."""
    cfg = get_config(name).reduced()
    return dataclasses.replace(cfg, n_layers=2, d_model=64, d_ff=128,
                               vocab_size=128, n_heads=2, n_kv_heads=1,
                               head_dim=32, ssm_state=8)


def _arch_adapter(cfg: ArchConfig) -> ModelAdapter:
    """Loss/accuracy/init closures over the FULL model stack for one arch.

    The forward pass routes through the Pallas kernels under the model
    gates (``attention.use_flash_kernel`` / ``mamba.use_ssm_kernel``; the
    reference jnp paths otherwise).  Call this ONCE per arch config and
    share the returned adapter across that arch's tasks: ``task_signature``
    compares the closures by identity, so a shared adapter (plus the shared
    ``cfg`` instance inside it) is what lets same-arch tasks fuse into one
    vmapped group — and distinct archs split groups naturally."""

    def init(key):
        return transformer.init(key, cfg)

    def loss_fn(p, batch):
        loss, _ = transformer.forward(p, cfg, {"tokens": batch["x"]})
        return loss

    def accuracy(p, batch):
        lg = transformer.logits(p, cfg, {"tokens": batch["x"]})
        return jnp.mean(jnp.argmax(lg[:, :-1], -1) == batch["x"][:, 1:])

    return ModelAdapter(init=init, loss_fn=loss_fn, accuracy=accuracy)


def build_model_setting(archs: Sequence[str] = ("qwen3-0.6b", "qwen3-0.6b",
                                                "falcon-mamba-7b"),
                        n_clients: int = 8, cap: int = 8, seq_len: int = 16,
                        seed: int = 0, avail_rate: Optional[float] = None
                        ) -> Tuple[List[Task], np.ndarray, np.ndarray]:
    """Real-model task world: one LM task per entry of ``archs``, each
    running the registry architecture (scaled to ``_model_cfg`` dims) with
    its own non-iid token shards.  The default world is the mixed
    transformer+mamba fusion case: two qwen3 tasks share one adapter (one
    vmapped group) while the falcon-mamba task forms a second group.

    Returns (tasks, B, avail) in the exact ``build_linear_setting`` world
    contract, so every engine path (fused, per-task loop, sharded, async)
    runs unchanged on top."""
    rng = np.random.default_rng(seed)
    cfgs: Dict[str, ArchConfig] = {}
    adapters: Dict[str, ModelAdapter] = {}
    tasks: List[Task] = []
    for s, name in enumerate(archs):
        if name not in cfgs:
            cfgs[name] = _model_cfg(name)
            adapters[name] = _arch_adapter(cfgs[name])
        cfg = cfgs[name]
        x, test_x = synthetic.make_token_task(rng, cfg.vocab_size, n_clients,
                                              cap, seq_len)
        # next-token targets live inside "x"; "y" is a schema placeholder
        # (the engine's data contract slices it, the adapter ignores it)
        tasks.append(Task(
            name=f"{name}-{s}", model=adapters[name],
            data={"x": jnp.asarray(x),
                  "y": jnp.zeros((n_clients, cap), jnp.int32),
                  "count": jnp.full((n_clients,), cap, jnp.int32)},
            test={"x": jnp.asarray(test_x),
                  "y": jnp.zeros((test_x.shape[0],), jnp.int32)}))
    B = rng.integers(1, 4, n_clients)
    avail = np.ones((n_clients, len(archs)), bool)
    if avail_rate is not None:
        avail = partition.availability(
            np.random.default_rng((seed, 1)), n_clients, len(archs),
            frac_all=float(avail_rate))
    return tasks, B, avail


# ---------------------------------------------------------------------------
# run_experiment: the functional-engine entry point
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ExperimentSpec:
    """Declarative description of one MMFL experiment.

    ``seeds`` with more than one entry runs a vmapped seed fleet
    (``RoundEngine.run_seeds``) — Table-1 error bars in a single compile.
    ``eval_every`` means the same thing on both paths: a single seed runs
    chunked ``lax.scan`` rollouts with a host evaluation between chunks;
    a fleet with ``eval_every`` < ``rounds`` runs the chunked cadence of
    ``run_seed_fleet`` (stacked accuracy traces, one dispatch per chunk)
    — set ``eval_every=0`` (or >= ``rounds``) for the fully fused
    init+rollout+eval fleet dispatch.  ``linear=True`` swaps the CNN/LSTM
    world for the seconds-fast linear micro-setting (benchmarks, CI).

    ``async_cfg`` is the ASYNC AXIS: ``AsyncConfig`` kwargs (or an
    ``AsyncConfig``) selecting the event-driven engine — e.g.
    ``{"delay": "geometric", "delay_kwargs": {"q": 0.5, "max_lag": 4},
    "window_size": 2}``.  ``rounds`` then counts aggregation WINDOWS; the
    zero-delay default is bit-identical to the synchronous engine, so the
    axis composes with seed fleets and eval cadences unchanged.

    The FAULT AXIS rides in ``server``: ``{"faults": "dropout",
    "fault_kwargs": (("rate", 0.3),), "fault_guard": True}`` selects a
    ``core.faults`` world (kwargs as a tuple of pairs — ``server`` must
    stay hashable for the sweep's engine cache).  ``faults="none"``
    (default) traces no fault ops at all and is bit-identical to the
    fault-free engine; ``fl.sweep.fault_sensitivity_spec`` builds
    failure-rate ladders over this axis."""
    method: str = "lvr"
    n_models: int = 3
    n_clients: int = 120
    rounds: int = 20
    seeds: Sequence[int] = (0,)
    small: bool = False
    linear: bool = False
    data_seed: int = 0
    eval_every: int = 5
    server: Dict[str, Any] = dataclasses.field(default_factory=dict)
    async_cfg: Optional[Any] = None


def build_world(n_models: int, n_clients: int, data_seed: int = 0,
                small: bool = False, linear: bool = False,
                avail_rate: Optional[float] = None,
                label_frac: Optional[float] = None
                ) -> Tuple[List[Task], np.ndarray, np.ndarray]:
    """The (tasks, B, avail) triple an ``ExperimentSpec``/``SweepSetting``
    names.  One world is shared by every method/seed cell evaluated on it
    (the sweep harness builds each setting exactly once).

    ``avail_rate``/``label_frac`` are the world-sensitivity axes (None =
    the builders' §6.1 defaults, bit-identically)."""
    if linear:
        if label_frac is not None:
            # the linear micro tasks have no label shards — silently
            # ignoring the axis would emit identical "heterogeneity" cells
            raise ValueError("label_frac is a CNN-world axis; the linear "
                             "micro setting has no label shards to vary")
        return build_linear_setting(n_models=n_models, n_clients=n_clients,
                                    seed=data_seed, avail_rate=avail_rate)
    return build_setting(n_models, n_clients=n_clients, seed=data_seed,
                         small=small, avail_rate=avail_rate,
                         label_frac=label_frac)


def resolve_async_cfg(async_cfg: Any) -> Optional[AsyncConfig]:
    """Normalize an async-axis value (None / kwargs dict / AsyncConfig)."""
    if async_cfg is None or isinstance(async_cfg, AsyncConfig):
        return async_cfg
    return AsyncConfig(**async_cfg)


def build_engine(spec: ExperimentSpec) -> RoundEngine:
    tasks, B, avail = build_world(spec.n_models, spec.n_clients,
                                  data_seed=spec.data_seed, small=spec.small,
                                  linear=spec.linear)
    cfg = ServerConfig(method=spec.method, seed=spec.seeds[0], **spec.server)
    acfg = resolve_async_cfg(spec.async_cfg)
    if acfg is not None:
        return AsyncRoundEngine(tasks, B, avail, cfg, acfg)
    return RoundEngine(tasks, B, avail, cfg)


def run_experiment(spec: ExperimentSpec) -> Dict[str, Any]:
    """Run a full experiment on the functional engine.

    Returns (single seed)
      {"metrics": {key: [rounds, S] np}, "acc": [(round, [S accs])...],
       "final_acc": [S], "state": ExperimentState, "engine": RoundEngine}
    or (seed fleet)
      {"metrics": {key: [n_seeds, rounds, S] np}, "final_acc": [n_seeds, S],
       "acc_mean"/"acc_std": [S], "engine": RoundEngine; plus "acc":
       [(round, [n_seeds, S])...] when ``eval_every`` < ``rounds`` — the
       chunked fleet cadence of ``run_seed_fleet``}.
    """
    engine = build_engine(spec)
    if len(spec.seeds) > 1:
        out = run_seed_fleet(engine, spec.seeds, spec.rounds,
                             eval_every=spec.eval_every)
        out["engine"] = engine
        return out
    state = engine.init_state(seed=spec.seeds[0])
    ev = max(1, spec.eval_every or spec.rounds)
    chunks: List[Dict[str, np.ndarray]] = []
    acc_hist: List[Tuple[int, List[float]]] = []
    done = 0
    while done < spec.rounds:
        n = min(ev, spec.rounds - done)
        state, mets = engine.rollout(state, n)
        chunks.append({k: np.asarray(v) for k, v in mets.items()})
        done += n
        acc_hist.append((done, engine.evaluate(state)))
    metrics = {k: np.concatenate([c[k] for c in chunks], axis=0)
               for k in chunks[0]}
    return {
        "metrics": metrics, "acc": acc_hist,
        "final_acc": acc_hist[-1][1], "state": state, "engine": engine,
    }


def run_seed_fleet(engine: RoundEngine, seeds: Sequence[int], rounds: int,
                   eval_every: int = 0) -> Dict[str, Any]:
    """Run a vmapped seed fleet on ``engine`` with an optional eval cadence.

    ``eval_every`` in (0, None) or >= ``rounds`` runs the fully fused
    ``run_seeds`` (init+rollout+eval in ONE dispatch); otherwise the fleet
    advances in scanned chunks of ``eval_every`` rounds with a stacked
    evaluation between chunks (``init_states``/``rollout_states``/
    ``evaluate_states``) — per-round accuracy traces (Fig. 4's
    rounds-to-target) at one dispatch per chunk instead of per (seed,
    round).

    Returns {"metrics": {key: [n_seeds, rounds, S]}, "final_acc":
    [n_seeds, S], "acc_mean"/"acc_std": [S], and — when the cadence is
    active — "acc": [(round, [n_seeds, S])...]}.
    """
    seeds_arr = jnp.asarray(list(seeds), jnp.int32)
    out: Dict[str, Any] = {}
    if not eval_every or eval_every >= rounds:
        _, mets, accs = engine.run_seeds(seeds_arr, rounds)
        metrics = {k: np.asarray(v) for k, v in mets.items()}
        accs = np.asarray(accs)
    else:
        states = engine.init_states(seeds_arr)
        chunks: List[Dict[str, np.ndarray]] = []
        acc_hist: List[Tuple[int, np.ndarray]] = []
        done = 0
        while done < rounds:
            n = min(eval_every, rounds - done)
            states, mets = engine.rollout_states(states, n)
            chunks.append({k: np.asarray(v) for k, v in mets.items()})
            done += n
            acc_hist.append((done, np.asarray(
                engine.evaluate_states(states))))
        metrics = {k: np.concatenate([c[k] for c in chunks], axis=1)
                   for k in chunks[0]}
        accs = acc_hist[-1][1]
        out["acc"] = acc_hist
    out.update({
        "metrics": metrics, "final_acc": accs,
        "acc_mean": accs.mean(axis=0), "acc_std": accs.std(axis=0),
    })
    return out


# ---------------------------------------------------------------------------
# padded mask-aware worlds: heterogeneous worlds as ONE vmappable axis
# ---------------------------------------------------------------------------


def pad_world(tasks: Sequence[Task], B: np.ndarray, avail: np.ndarray,
              n_clients: int, cap: Optional[Dict[int, int]] = None
              ) -> Tuple[List[Task], np.ndarray, np.ndarray, np.ndarray]:
    """Pad a built world to ``n_clients`` with masked padding clients.

    Padding clients follow the mask contract (``repro.core.engine.World``):
    zero budget, all-False availability, empty shards (count 0) — so V is
    unchanged and the padded world trains bit-identically to the original
    (tests/test_world_padding.py pins this for every registered method).

    ``cap`` (optional, {task_index: target_cap}) wrap-pads a task's
    per-client sample axis to a common capacity — needed to STACK worlds
    whose partitions drew different caps.  Wrapped rows repeat real rows
    (the partitioner's own convention) and are never sampled (minibatch
    indices stay < count), but the loss-probe slice may widen, so
    cap-padded worlds are statistically, not bitwise, equivalent.

    Returns (tasks, B, avail, client_mask)."""
    N = int(np.asarray(B).shape[0])
    extra = int(n_clients) - N
    if extra < 0:
        raise ValueError(f"cannot pad {N} clients down to {n_clients}")
    mask = np.concatenate([np.ones(N, np.float32),
                           np.zeros(extra, np.float32)])
    out_tasks: List[Task] = []
    for s, t in enumerate(tasks):
        data = {}
        for k, v in t.data.items():
            arr = np.asarray(v)
            if cap and k in ("x", "y") and cap.get(s, arr.shape[1]) != arr.shape[1]:
                wrap = np.arange(int(cap[s])) % arr.shape[1]
                arr = arr[:, wrap]
            if extra:
                pad_rows = np.zeros((extra,) + arr.shape[1:], arr.dtype)
                arr = np.concatenate([arr, pad_rows], axis=0)
            data[k] = jnp.asarray(arr)
        out_tasks.append(Task(name=t.name, model=t.model, data=data,
                              test=t.test))
    B_p = np.concatenate([np.asarray(B, np.int64), np.zeros(extra, np.int64)])
    avail_p = np.concatenate([np.asarray(avail, bool),
                              np.zeros((extra, avail.shape[1]), bool)])
    return out_tasks, B_p, avail_p, mask


@dataclasses.dataclass
class StackedWorlds:
    """The cfg-independent half of a world fleet: padded worlds stacked to
    one template shape.  Build once (``stack_worlds``) and share across
    every method config of a sweep group — the padding and the device
    upload of all task shards happen once, not once per method."""
    stacked: World            # every leaf with a leading [n_worlds] axis
    padded: List[Tuple[List[Task], np.ndarray, np.ndarray, np.ndarray]]
    Ns: List[int]             # real client counts per world
    Vs: List[int]             # real processor totals per world
    i_template: int           # index of the max-V world


def stack_worlds(built: Sequence[Tuple[List[Task], np.ndarray, np.ndarray]]
                 ) -> StackedWorlds:
    """Pad heterogeneous built worlds to one template shape and stack them.

    The template is the max-V world (its static V bounds the grid); every
    other world is padded to its (N, V, cap) shapes, with at least one
    padding client whenever budgets differ so dangling processor rows
    have a masked client to map to."""
    built = list(built)
    if not built:
        raise ValueError("stack_worlds needs at least one built world")
    Ns = [int(np.asarray(B).shape[0]) for _, B, _ in built]
    Vs = [int(np.asarray(B).sum()) for _, B, _ in built]
    S = len(built[0][0])
    if any(len(t) != S for t, _, _ in built):
        raise ValueError("all worlds of a fleet must share n_models")
    v_max = max(Vs)
    n_to = max(Ns) + (1 if min(Vs) < v_max else 0)
    cap_to = {s: max(int(np.asarray(w[0][s].data["x"]).shape[1])
                     for w in built) for s in range(S)}
    padded = [pad_world(t, B, a, n_to, cap=cap_to) for t, B, a in built]
    arrays = [build_world_arrays(t, B, a, m, v_total=v_max)
              for t, B, a, m in padded]
    shapes = [jax.tree.map(lambda x: tuple(x.shape), w) for w in arrays]
    if any(s != shapes[0] for s in shapes[1:]):
        raise ValueError(
            "worlds of a fleet must pad to identical shapes (check test-set "
            f"sizes and sample caps): {shapes}")
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *arrays)
    return StackedWorlds(stacked=stacked, padded=padded, Ns=Ns, Vs=Vs,
                         i_template=int(np.argmax(Vs)))


def world_fleet(built: Sequence[Tuple[List[Task], np.ndarray, np.ndarray]],
                cfg: ServerConfig,
                prepared: Optional[StackedWorlds] = None
                ) -> Tuple[RoundEngine, World]:
    """Template engine + stacked World for ``RoundEngine.run_worlds`` —
    the whole (worlds x seeds) grid then runs as ONE compiled dispatch.

    Pass ``prepared`` (``stack_worlds(built)``) when running several
    method configs over the same worlds, so the padding/stacking work is
    shared.  The cohort capacity is the max over every world's own
    standalone sizing — a world whose standalone capacity is smaller only
    diverges from its per-world run in the rare rounds where IT would
    have overflowed and dropped active clients (the grid trains them
    instead)."""
    prepared = prepared if prepared is not None else stack_worlds(built)
    if len(set(prepared.Vs)) > 1 and methods.get_class(
            cfg.method).static_budget_sizing:
        raise ValueError(
            f"{cfg.method} derives static sample sizes from the budget m, "
            f"which a world-vmapped grid freezes at the template world's — "
            f"worlds with different total budgets "
            f"(V={sorted(set(prepared.Vs))}) would silently sample "
            f"differently than standalone.  Run these worlds as separate "
            f"settings (vmap_worlds=False) or stack equal-budget worlds "
            f"only")
    S = len(prepared.padded[0][0])
    tmpl_tasks, tmpl_B, tmpl_avail, tmpl_mask = \
        prepared.padded[prepared.i_template]
    # cohort capacity covers EVERY world's own standalone sizing, not just
    # the template's (a world with more clients than the max-V world would
    # otherwise truncate active cohorts only inside the grid); m is
    # rounded through f32 exactly as RoundEngine does
    strat = methods.make(cfg.method, cfg)
    cohort = max(strat.cohort_size(
        n, float(np.float32(cfg.active_rate) * np.float32(v)), S)
        for n, v in zip(prepared.Ns, prepared.Vs))
    engine = RoundEngine(tmpl_tasks, tmpl_B, tmpl_avail, cfg,
                         client_mask=tmpl_mask, cohort_size=cohort)
    return engine, prepared.stacked
