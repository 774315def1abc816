"""Functional MMFL round engine: an explicit, immutable ``ExperimentState``
pytree and pure round transitions.

This is the core the paper's multi-seed, multi-round experiments (Tables
1-2, Figs. 3-5) actually need: everything a round touches — per-task
``params``, per-task method ``state`` (stale stores, SCAFFOLD variates,
StaleVRE beta estimators), the PRNG ``key``, the ``round`` counter, and the
cached sampler ``losses_ns`` — lives in ONE portable pytree, and the round
is a pure function of it:

    state' , metrics = round_step(state)

Because the transition is pure and its carry is a pytree,

  * ``rollout(state, n)`` fuses whole chunks of rounds into a single
    ``lax.scan`` dispatch with stacked on-device metrics (no per-round,
    per-task host syncs — see ``benchmarks/engine_bench.py``),
  * ``run_seeds(seeds, n)`` vmaps independent replicates for Table-1 error
    bars in one compile,
  * ``repro.checkpoint`` can save/restore the ENTIRE experiment (not just
    params) and a killed run resumes bit-identically,
  * method state is an ordinary shardable pytree, which is what lets the
    distributed trainer (``launch/train.py``) carry the ``StaleVRFamily``
    stale stores like any other state.

**Task-axis fusion.**  The task axis — the defining axis of multi-model FL
— is itself vmapped: tasks are grouped by *compile signature* (same model
code + identical param/data/test shapes, see ``task_signature``), each
group's params / method state / client shards are STACKED along a leading
task axis, and the stats phase + per-task round run as ONE ``jax.vmap``
over the stacked pytrees.  The Python loop survives only across signature
groups (1-2 groups in the paper's settings), so trace/compile cost stops
growing linearly in S and XLA batches the per-task work instead of
serializing it.  ``ServerConfig(fuse_tasks=False)`` keeps the per-task
loop on the SAME grouped state layout for A/B
(``benchmarks/engine_bench.py::bench_task_fusion``); fused == loop
bit-for-bit is pinned by tests/test_task_fusion.py for every registered
method.  The ``round_step``/``rollout``/fleet dispatches donate their input
state (``donate_argnums``), so the [N, params] all-client update buffers
and StaleVR stale stores update in place instead of doubling peak memory.

**Client-sharded rounds.**  ``RoundEngine(mesh=sharding.client_mesh(k))``
shards the CLIENT axis of the fused round over a 1-D device mesh
(``repro.core.sharding``): the [N, params] stale stores, the all-client
update buffers, ``losses_ns`` and the client mask live as
``NamedSharding(("data",))`` blocks — no client-indexed array ever needs to
fit one device — while the per-client math stays bitwise the single-device
math (the index-keyed RNG makes the client index space shardable by
construction).  Cross-client reductions become explicit collectives:
loss/norm columns ``all_gather`` into the replicated sampling phase (the
water-filling solve and the Sec. 3.3 monitors run on every shard from
identical inputs, bit-identical to the reference), and each strategy's
aggregation contraction ``psum``s its per-shard partial (the documented
ulp-level sharding tolerance; the single-device path never enters the
sharded body and stays the bit-reference).  See ROADMAP.md
§"Client-sharding contract".

**Phase names.**  Each phase of the round carries one ``jax.named_scope``,
which lands in the compiled HLO's op metadata and so in a profiler trace's
device operations: ``mmfl.stats`` (loss probe, needs-all gradient norms),
``mmfl.sample`` (probabilities, draw, masks, Sec. 3.3 monitors; the loss
all-gather when sharded), ``mmfl.local_train`` (cohort gather and local
SGD, the all-client training of needs-all methods too), ``mmfl.aggregate``
(coefficients, fault inject and guard, the strategy's aggregation and its
kernels and psum) and ``mmfl.evaluate`` (test accuracy).  An operation
belongs to the innermost of these in its name stack.  In the fused round
each signature group's stats and round bodies sit inside the scope
``group.<first task name>`` (``group_scope``), outside the phase names,
so an operation keeps its phase and also names its group.  On the host,
``rollout`` and ``evaluate`` run under the ``TraceAnnotation`` spans
``mmfl.rollout`` (with its number of ``rounds``) and ``mmfl.evaluate``;
``bench/trace_scopes.py`` reduces such a trace to device time per phase.
Scopes only name operations: results and compiles are the same with or
without them.

``repro.core.server.MMFLServer`` is a thin stateful facade over this module
(attribute views like ``h_valid``/``beta_state`` preserved); the strategy
protocol is unchanged (``repro.core.methods``).
"""
from __future__ import annotations

import dataclasses
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import (convergence, faults, methods, sampling, sharding,
                        stale)


@dataclasses.dataclass
class ModelAdapter:
    """Functional model interface for the FL engine."""
    init: Callable[[jax.Array], Any]
    loss_fn: Callable[[Any, Dict[str, jnp.ndarray]], jnp.ndarray]
    accuracy: Callable[[Any, Dict[str, jnp.ndarray]], jnp.ndarray]


@dataclasses.dataclass
class Task:
    """One FL model + its federated data.

    data: {"x": [N, cap, ...], "y": [N, cap, ...], "count": [N]} — per-client
    padded arrays; test: {"x": [T, ...], "y": [T]} server-held eval set.
    """
    name: str
    model: ModelAdapter
    data: Dict[str, jnp.ndarray]
    test: Dict[str, jnp.ndarray]


@dataclasses.dataclass
class ServerConfig:
    method: str = "lvr"
    active_rate: float = 0.1          # m = active_rate * V
    local_epochs: int = 5             # E
    batch_size: int = 16
    lr: float = 0.05
    lr_decay: float = 1.0             # eta_tau = lr * decay^tau
    fedstale_beta: float = 0.5        # global beta for fedstale
    eta_cap: Optional[float] = None   # footnote-3 per-client cap sum_s p <= eta
    seed: int = 0
    jit_round: bool = True            # fused whole-round jit (False = legacy)
    fuse_tasks: bool = True           # vmapped task axis (False = per-task loop)
    # fault axis (core.faults): fault model name or instance + constructor
    # kwargs; "none" keeps the engine bit-identical to the fault-free
    # build.  ``fault_kwargs`` accepts a dict or a tuple of (key, value)
    # pairs — the tuple form keeps sweep cache keys hashable
    # (fl.sweep._cached_engine sorts the server kwargs into a tuple).
    faults: Any = "none"
    fault_kwargs: Any = None
    # server-side update guard: mask crashed/non-finite updates out of the
    # aggregation and re-normalize coefficients over the survivors
    # (False = the unguarded server — fault worlds hit it raw)
    fault_guard: bool = True


class ExperimentState(NamedTuple):
    """The complete state of an MMFL experiment as one pytree.

    ``params``/``method_state`` are per-GROUP tuples: tasks sharing a
    compile signature (``task_signature``) are stacked along a leading task
    axis inside one tuple entry, and ``task_group``/``task_slot`` ([S]
    int32 arrays) map task s to its (group, slot) — checkpointed with the
    state, so the per-task surface (facade views, ``launch/serve.py``'s
    ``restore_model_params``) survives the stacked layout.  States built
    with per-task tuples and ``task_group=None`` (the distributed trainer's
    layout, where every model is its own unstacked entry) remain valid:
    None means the identity mapping.  ``round`` is a traced int32 scalar so
    lr schedules and round-robin policies stay scan/vmap-safe;
    ``losses_ns`` caches the latest [N, S] loss reports the sampler saw
    (checkpointed so a resumed run samples from the same view);
    ``client_mask`` [N] records which client rows are real (1) vs padding
    (0) — checkpointed so a padded run resumes with the same world
    contract.  Checkpoints written before the grouped layout cannot restore
    into a current engine template (restore raises a schema error).

    ``async_state`` is the event-driven engine's in-flight surface
    (``core.async_engine``): a per-GROUP tuple of dicts holding the
    [T_g, N, params] in-flight update buffers and the [T_g, N] landing
    timers / staleness counters — None on synchronous engines, threaded
    (and donated / client-sharded) exactly like the stale stores when an
    ``AsyncRoundEngine`` attaches it.  Restoring a pre-async checkpoint
    into an async template raises ``checkpoint.CheckpointSchemaError``
    unless the migration shim (``fill_missing``) zero-fills it."""
    params: Tuple[Any, ...]
    method_state: Tuple[Any, ...]
    key: jax.Array
    round: jax.Array          # int32 scalar
    losses_ns: jax.Array      # [N, S]
    client_mask: Optional[jax.Array] = None   # [N] 1 real / 0 padding
    task_group: Optional[jax.Array] = None    # [S] int32 task -> group
    task_slot: Optional[jax.Array] = None     # [S] int32 task -> slot
    async_state: Optional[Any] = None         # per-group in-flight buffers


# ---------------------------------------------------------------------------
# compile-signature task grouping
# ---------------------------------------------------------------------------


# samples per client the stats-phase loss probe reads: min(cap, PROBE_TAKE)
# (``fl.experiments.align_task_caps`` must not widen a cap across this
# boundary — it would widen the probe itself)
PROBE_TAKE = 64

_PRIMITIVE = (int, float, bool, str, bytes, type(None))


def fn_signature(f: Callable) -> Tuple:
    """Identity of a model function for grouping purposes: the code object
    plus the closure's primitive cell values (``_linear_adapter``'s
    ``init`` closes over (n_feat, n_classes); equal ints == same
    architecture).  Non-primitive cells fall back to object identity —
    conservative: equivalent-but-distinct constants split groups rather
    than silently fusing different math.

    Shared by the training engine (``task_signature``) and the serving
    layer (``repro.serve.adapters.serve_signature``): both batch
    same-signature models into one vmapped dispatch."""
    code = getattr(f, "__code__", None)
    if code is None:
        return ("obj", id(f))
    cells: Tuple = ()
    if getattr(f, "__closure__", None):
        cells = tuple(
            c.cell_contents if isinstance(c.cell_contents, _PRIMITIVE)
            else ("id", id(c.cell_contents))
            for c in f.__closure__)
    return ("code", code, cells)


def _shape_signature(tree: Any) -> Tuple:
    return tuple(sorted(
        ("/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path),
         tuple(leaf.shape), str(leaf.dtype))
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]))


def task_signature(t: Task) -> Tuple:
    """Tasks with equal signatures compile to the same per-task round
    executable: same model code (loss/accuracy/init) and identical
    data/test shapes — the grouping rule of the fused task axis."""
    return (fn_signature(t.model.loss_fn), fn_signature(t.model.accuracy),
            fn_signature(t.model.init),
            _shape_signature(t.data), _shape_signature(t.test))


def group_by_signature(signatures: Sequence[Tuple]) -> List[List[int]]:
    """Partition indices into equal-signature groups, first-occurrence
    ordered (items within a group keep input order — slot j of group g is
    the j-th item of that signature).  The one grouping rule every
    batched-dispatch surface shares: the fused training round
    (``group_tasks``) and the multi-model serving layer
    (``repro.serve``) both consume it."""
    sig_to_g: Dict[Tuple, int] = {}
    groups: List[List[int]] = []
    for i, sig in enumerate(signatures):
        g = sig_to_g.get(sig)
        if g is None:
            g = len(groups)
            sig_to_g[sig] = g
            groups.append([])
        groups[g].append(i)
    return groups


def group_tasks(tasks: Sequence[Task]) -> List[List[int]]:
    """Partition task indices into signature groups (see
    ``group_by_signature``)."""
    return group_by_signature([task_signature(t) for t in tasks])


class World(NamedTuple):
    """Everything world-dependent one round reads, as ONE stackable pytree.

    The engine's own world is closed over as trace constants (exactly the
    pre-mask behaviour); ``run_worlds`` instead passes a STACKED World (one
    leading axis over worlds) as a traced argument and vmaps the rollout
    over it — one compile for a whole (worlds x seeds) grid.

    ``data``/``test`` are per-GROUP tuples (``group_tasks``): each entry
    stacks its signature group's shards/eval sets along a leading task
    axis, matching ``ExperimentState.params`` — the layout the fused task
    vmap consumes directly.

    Mask contract (the padding invariants every layer relies on):
      * padding clients sit in a TRAILING block: ``client_mask`` is 1s then
        0s, their budget rows are 0, their availability rows all-False and
        their data shards empty (count 0);
      * ``d`` is computed HOST-side over the valid prefix only, so a padded
        world's d rows are bit-identical to the unpadded world's;
      * V may exceed sum(B) when a world is stacked next to a bigger one:
        the dangling ``proc_client`` rows point at the LAST client (a
        padding client by the trailing-block rule) and carry
        ``proc_mask`` 0, so they never receive probability or mass."""
    data: Tuple[Dict[str, jnp.ndarray], ...]   # per-group stacked shards
    test: Tuple[Dict[str, jnp.ndarray], ...]   # per-group stacked eval sets
    B: jnp.ndarray            # [N] float32 budgets (0 on padding)
    avail: jnp.ndarray        # [N,S] bool (False on padding)
    d: jnp.ndarray            # [N,S] dataset fractions (0 on padding)
    client_mask: jnp.ndarray  # [N] float32, trailing 0 block = padding
    proc_client: jnp.ndarray  # [V] int32 processor -> client
    proc_mask: jnp.ndarray    # [V] float32 (0 on padding/dangling rows)
    v_real: jnp.ndarray       # scalar f32: true sum(B) (m = rate * v_real)


def _group_stack_trees(trees: Sequence[Any], put: Optional[Callable] = None
                       ) -> Any:
    """Stack a list of identically-shaped pytrees along a new leading axis
    (a group of 1 still gains the axis — the layout is uniform).  ``put``
    (client-sharded engines) stacks on HOST and places each leaf straight
    into its sharded layout, so the stacked array never materializes on a
    single device."""
    if put is None:
        return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
    return jax.tree.map(
        lambda *xs: put(np.stack([np.asarray(x) for x in xs])), *trees)


def build_world_arrays(tasks: Sequence["Task"], B: Any, avail: Any,
                       client_mask: Optional[Any] = None,
                       v_total: Optional[int] = None,
                       data_put: Optional[Callable] = None) -> World:
    """Host-side construction of the ``World`` pytree.

    All derived quantities that must be bit-identical between a world and
    its padded copy (``d``, the processor map) are computed here with
    numpy over the valid prefix — never re-reduced in-trace, where XLA's
    reduction regrouping over a longer axis would wiggle last-ulp bits."""
    B_np = np.asarray(B, np.float32)
    avail_np = np.asarray(avail, bool)
    N = B_np.shape[0]
    mask_np = (np.ones((N,), np.float32) if client_mask is None
               else np.asarray(client_mask, np.float32))
    n_valid = int(mask_np.sum())
    if not (np.all(mask_np[:n_valid] == 1.0)
            and np.all(mask_np[n_valid:] == 0.0)):
        raise ValueError("client_mask must be a trailing padding block "
                         "(1s for real clients, then 0s)")
    if np.any(B_np[n_valid:] != 0) or avail_np[n_valid:].any():
        raise ValueError("padding clients must carry zero budget and zero "
                         "availability")
    counts = np.stack([np.asarray(t.data["count"], np.float32)
                       for t in tasks], axis=1)
    counts = np.where(avail_np, counts, 0.0)
    denom = np.maximum(counts[:n_valid].sum(axis=0, keepdims=True), 1.0)
    d = (counts / denom).astype(np.float32)
    B_int = B_np.astype(np.int64)
    v_real = int(B_int.sum())
    v_total = v_real if v_total is None else int(v_total)
    if v_total < v_real:
        raise ValueError(f"v_total={v_total} < sum(B)={v_real}")
    if v_total > v_real and n_valid == N:
        raise ValueError(
            "a world with budget slack (sum(B) < v_total) needs at least "
            "one padding client for the dangling processor rows to map to")
    proc_client = np.full((v_total,), N - 1, np.int32)
    proc_client[:v_real] = np.repeat(np.arange(N, dtype=np.int32), B_int)
    proc_mask = (mask_np[proc_client]
                 * (np.arange(v_total) < v_real)).astype(np.float32)
    groups = group_tasks(tasks)
    return World(
        data=tuple(_group_stack_trees([tasks[i].data for i in grp],
                                      put=data_put)
                   for grp in groups),
        test=tuple(_group_stack_trees([tasks[i].test for i in grp])
                   for grp in groups),
        B=jnp.asarray(B_np), avail=jnp.asarray(avail_np), d=jnp.asarray(d),
        client_mask=jnp.asarray(mask_np),
        proc_client=jnp.asarray(proc_client),
        proc_mask=jnp.asarray(proc_mask),
        v_real=jnp.asarray(float(v_real), jnp.float32))


class RoundEngine:
    """Builds the pure per-round transition for one (world, method) pair.

    The engine owns the static world (task data, budgets, availability,
    the strategy object, the fused per-task round closures); all mutable
    quantities live in the ``ExperimentState`` it threads."""

    def __init__(self, tasks: Sequence[Task], B: np.ndarray,
                 avail: np.ndarray, cfg: ServerConfig,
                 client_mask: Optional[np.ndarray] = None,
                 cohort_size: Optional[int] = None,
                 mesh: Optional[Any] = None):
        self.tasks = list(tasks)
        self.cfg = cfg
        self.S = len(tasks)
        self.N = int(np.asarray(B).shape[0])
        # client-sharded mode: a 1-D jax.sharding.Mesh over the client axis
        # (``core.sharding.client_mesh``) lays every client-indexed leaf out
        # as NamedSharding blocks and runs the round under shard_map
        self.mesh = mesh
        self.n_shards = (1 if mesh is None
                         else int(np.prod(mesh.devices.shape)))
        data_put = None
        if mesh is not None:
            if tuple(mesh.axis_names) != (sharding.CLIENT_AXIS,):
                raise ValueError(
                    f"mesh must be 1-D over the client axis "
                    f"({sharding.CLIENT_AXIS!r}, core.sharding.client_mesh);"
                    f" got axes {tuple(mesh.axis_names)}")
            if self.N % self.n_shards:
                raise ValueError(
                    f"N={self.N} clients must divide evenly over "
                    f"{self.n_shards} mesh shards — pad the world (the "
                    f"trailing-padding client_mask contract already "
                    f"supports zero-budget padding clients)")
            if not getattr(cfg, "jit_round", True):
                raise ValueError("client-sharded engines require "
                                 "jit_round=True (the legacy eager path is "
                                 "single-device only)")
            # group-stacked client shards are the ONLY data residency:
            # stack on host and place each group straight into its
            # [task, client-sharded] layout — no [N, cap, ...] array ever
            # materializes on one device
            data_sh = NamedSharding(mesh, sharding.spec_for(True, lead=1))
            data_put = lambda a: jax.device_put(a, data_sh)
        self.n_loc = self.N // self.n_shards
        self.world = build_world_arrays(tasks, B, avail, client_mask,
                                        data_put=data_put)
        self.B = self.world.B
        self.B_int = np.asarray(B, np.int64)
        self._B_host = np.asarray(B, np.float32)
        self.client_mask = np.asarray(self.world.client_mask, np.float32)
        self.n_valid = int(self.client_mask.sum())
        self.V = int(self.B_int.sum())
        self.avail = self.world.avail                         # [N,S]
        # m rounded through the f32 product ONCE: the world-vmapped path
        # computes m in-trace as f32(active_rate) * f32(v_real), and every
        # other consumer (facade ctx, cohort sizing, m_host) must see the
        # bit-identical value or a 1-ulp m skews the water-filling between
        # execution paths (the padded-equivalence contract would only hold
        # probabilistically)
        self.m = float(np.float32(cfg.active_rate) * np.float32(self.V))
        # d_{i,s}: dataset fractions among available clients (host-built —
        # padding-stable, see build_world_arrays)
        self.d = self.world.d
        # map processors -> clients
        self.proc_client = self.world.proc_client             # [V]
        self.strategy = methods.make(cfg.method, cfg)
        # fault axis (core.faults): the configured fault model plus the
        # server-side update guard switch.  ``self.faulty`` is a STATIC
        # flag — every injection/guard code path below is Python-gated on
        # it, so faults="none" builds closures byte-identical to the
        # fault-free engine (the bit-identity contract test_faults pins)
        fm = getattr(cfg, "faults", "none")
        if fm is None or fm == "none":
            fm = faults.NoFault()
        elif isinstance(fm, str):
            fkw = getattr(cfg, "fault_kwargs", None) or ()
            fm = faults.make_fault(fm, **dict(fkw))
        self.fault_model = fm
        self.faulty = not fm.fault_free
        self.fault_guard = bool(getattr(cfg, "fault_guard", True))
        if self.faulty and not getattr(cfg, "jit_round", True):
            raise ValueError(
                "fault worlds require jit_round=True — the legacy eager "
                "facade path bypasses the traced fault injection")
        # fixed cohort size for methods where only sampled clients train
        # (sized over REAL clients: a padded world keeps the same cohort).
        # ``cohort_size`` overrides for world grids, where the capacity
        # must cover EVERY stacked world's own sizing (world_fleet)
        self.cohort_size = (cohort_size if cohort_size is not None
                            else self.strategy.cohort_size(self.n_valid,
                                                           self.m, self.S))
        self._d_v = self.d[self.proc_client]                  # [V,S]
        self._B_v = self.B[self.proc_client]                  # [V]
        # sampling-distribution override hook (ctx, losses_ns, norms_ns) ->
        # p [V,S]; the server facade routes its monkeypatchable
        # ``_probabilities`` through this (e.g. Fig. 5's pinned sampler)
        self.probabilities_hook: Optional[Callable] = None
        # signature groups: the vmapped task axis (see module docstring)
        self.groups = group_tasks(self.tasks)
        self.n_groups = len(self.groups)
        self.task_gs: List[Tuple[int, int]] = [(-1, -1)] * self.S
        for g, grp in enumerate(self.groups):
            for j, s in enumerate(grp):
                self.task_gs[s] = (g, j)
        self._task_group_np = np.asarray([g for g, _ in self.task_gs],
                                         np.int32)
        self._task_slot_np = np.asarray([j for _, j in self.task_gs],
                                        np.int32)
        self.fuse_tasks = bool(getattr(cfg, "fuse_tasks", True))
        if mesh is not None:
            if not self.strategy.shardable:
                raise ValueError(
                    f"method {cfg.method!r} sets shardable=False — its "
                    f"aggregation reads cross-client state that is not "
                    f"expressible as a per-shard partial + psum; run it "
                    f"single-device")
            if not self.fuse_tasks:
                raise ValueError(
                    "client-sharded engines require fuse_tasks=True (the "
                    "per-task loop path materializes per-task data views, "
                    "defeating the sharded residency)")
        # lazily-materialized per-task views of the group-stacked World
        # data/test (the single residency authority; only legacy/loop
        # paths and external probes read per-task views)
        self._task_data_views: Dict[int, Any] = {}
        self._task_test_views: Dict[int, Any] = {}
        # per-task pure building blocks (the loop path + the facade's
        # legacy eager mode; the fused path vmaps the group closures below)
        self._local_all = [self._make_local_all(t) for t in self.tasks]
        if mesh is None:
            self._loss_all = [self._make_loss_all(s) for s in range(self.S)]
            self._stats_pure = [self.make_stats_fn(s)
                                for s in range(self.S)]
            self._round_pure = [self.make_round_fn(s)
                                for s in range(self.S)]
            self._g_stats = [self.make_group_stats_fn(g)
                             for g in range(self.n_groups)]
            self._g_round = [self.make_group_round_fn(g)
                             for g in range(self.n_groups)]
            self.loss_all_jit = [jax.jit(f) for f in self._loss_all]
        else:
            # the unsharded closures bind probe slices / per-task views of
            # the (sharded) data stacks — never built under a mesh; every
            # path that would consume them is refused
            self._loss_all = self._stats_pure = self._round_pure = None
            self._g_stats = self._g_round = None
            self.loss_all_jit = None
        self.eval_jit = [jax.jit(jax.named_scope("mmfl.evaluate")(
            lambda params, test, acc=t.model.accuracy: acc(params, test)))
            for t in self.tasks]
        # the input state is donated: the [N, params] stale stores /
        # all-client update buffers update in place instead of doubling
        # peak memory (tests/test_task_fusion.py asserts the donation);
        # under a mesh the donation preserves the sharded buffers in place
        if mesh is None:
            self.round_step = jax.jit(self.round_step_fn, donate_argnums=0)
        else:
            self._build_sharded()
            self.round_step = (
                lambda st: self._sharded_step(st, self.world.data))
        self._rollout_cache: Dict[int, Callable] = {}
        self._run_seeds_cache: Dict[int, Callable] = {}
        self._fleet_init_fn: Optional[Callable] = None
        self._fleet_rollout_cache: Dict[int, Callable] = {}
        self._fleet_eval_fn: Optional[Callable] = None
        self._run_worlds_cache: Dict[int, Callable] = {}

    # ------------------------------------------------------------------
    # grouped-state helpers: the per-task surface over stacked leaves
    # ------------------------------------------------------------------
    def group_stack(self, per_task: Sequence[Any]) -> Tuple[Any, ...]:
        """Per-task list -> per-group tuple of stacked pytrees."""
        return tuple(_group_stack_trees([per_task[i] for i in grp])
                     for grp in self.groups)

    def task_params(self, state: ExperimentState, s: int) -> Any:
        """Task s's params view (slot slice of its group's stack)."""
        g, j = self.task_gs[s]
        return jax.tree.map(lambda a: a[j], state.params[g])

    def task_method_state(self, state: ExperimentState, s: int) -> Any:
        """Task s's method-state view (stale store, variates, ...)."""
        g, j = self.task_gs[s]
        return jax.tree.map(lambda a: a[j], state.method_state[g])

    def per_task_params(self, state: ExperimentState) -> List[Any]:
        return [self.task_params(state, s) for s in range(self.S)]

    def per_task_method_state(self, state: ExperimentState) -> List[Any]:
        return [self.task_method_state(state, s) for s in range(self.S)]

    def task_data(self, s: int) -> Dict[str, jnp.ndarray]:
        """Task s's client shards as a slot view of the group-stacked
        ``World.data`` (the single residency authority — the engine never
        reads ``Task.data`` after ``build_world_arrays``).  Materialized
        lazily and cached: the fused round consumes the stacks directly;
        only the legacy/loop paths and external probes (``MMFLServer``'s
        eager mode, ``server._run_round_legacy``) pay for a per-task
        copy."""
        v = self._task_data_views.get(s)
        if v is None:
            g, j = self.task_gs[s]
            v = jax.tree.map(lambda a: a[j], self.world.data[g])
            self._task_data_views[s] = v
        return v

    def task_test(self, s: int) -> Dict[str, jnp.ndarray]:
        """Task s's server-held eval set (slot view of ``World.test``)."""
        v = self._task_test_views.get(s)
        if v is None:
            g, j = self.task_gs[s]
            v = jax.tree.map(lambda a: a[j], self.world.test[g])
            self._task_test_views[s] = v
        return v

    def _task_data(self, w: World, s: int, explicit: bool):
        """Task s's client shards: a cached slot view of the engine's own
        stacks on the closed-over path, a slot slice of the traced group
        stack under ``run_worlds``."""
        if not explicit:
            return self.task_data(s)
        g, j = self.task_gs[s]
        return jax.tree.map(lambda a: a[j], w.data[g])

    # ------------------------------------------------------------------
    # per-task pure computations
    # ------------------------------------------------------------------
    def _make_local_all(self, t: Task):
        loss_fn = t.model.loss_fn
        E, mb = self.cfg.local_epochs, self.cfg.batch_size

        def local_update(params, key, x, y, count, lr, corr):
            """One client's K=E epochs of minibatch SGD.  Returns
            (G = w0 - w_final, first-epoch loss)."""
            def step(carry, k):
                p, first_loss, i = carry
                idx = jax.random.randint(k, (mb,), 0, jnp.maximum(count, 1))
                batch = {"x": x[idx], "y": y[idx]}
                l, g = jax.value_and_grad(loss_fn)(p, batch)
                if corr is not None:
                    g = jax.tree.map(lambda a, b: a + b, g, corr)
                p = jax.tree.map(lambda a, b: a - lr * b, p, g)
                first_loss = jnp.where(i == 0, l, first_loss)
                return (p, first_loss, i + 1), None

            keys = jax.random.split(key, E)
            (pf, l0, _), _ = jax.lax.scan(step, (params, 0.0, 0), keys)
            G = jax.tree.map(lambda a, b: a - b, params, pf)
            return G, l0

        @jax.named_scope("mmfl.local_train")
        def local_all(params, keys, data, lr, corr=None):
            """vmap over the cohort's clients -> (G [A,...], losses [A])."""
            if corr is None:
                A = keys.shape[0]
                corr = jax.tree.map(
                    lambda a: jnp.zeros((A,) + (1,) * a.ndim), params)
            return jax.vmap(
                lambda k, x, y, c, cr: local_update(params, k, x, y, c, lr, cr)
            )(keys, data["x"], data["y"], data["count"], corr)

        return local_all

    def _make_loss_all(self, s: int):
        t = self.tasks[s]
        loss_fn = t.model.loss_fn
        # probe batch sliced ONCE at build time (from the stacked World
        # authority — ``jnp.stack`` copies exactly, so the slot rows are
        # bitwise ``Task.data``'s): inside jit/scan the task data is a
        # closed-over constant, and slicing it in-trace makes XLA
        # constant-fold a second copy of the dataset into the executable
        g, j = self.task_gs[s]
        stacked = self.world.data[g]
        cap = int(stacked["x"].shape[2])
        take = min(cap, PROBE_TAKE)
        probe_x = stacked["x"][j, :, :take]
        probe_y = stacked["y"][j, :, :take]

        def loss_all(params, data=None):
            """Per-client loss estimate on a (subsampled) local batch.
            Padded rows wrap real rows, so the padded-batch mean is a
            reweighted local loss.  ``data=None`` (the engine's round path)
            uses the build-time probe slice; explicit ``data`` (external
            probes through ``MMFLServer._loss_all``) is honored."""
            if data is None:
                x, y = probe_x, probe_y
            else:
                x, y = data["x"][:, :take], data["y"][:, :take]

            def one(xc, yc):
                return loss_fn(params, {"x": xc, "y": yc})

            return jax.vmap(one)(x, y)

        return loss_all

    def make_stats_fn(self, s: int, loss_all: Optional[Callable] = None,
                      local_all: Optional[Callable] = None) -> Callable:
        """Sampler inputs for task s; for needs-all methods also every
        client's fresh update G (and its norm if the sampler consumes
        gradient magnitudes).  ``loss_all``/``local_all`` default to the
        engine's pure pieces — the facade's legacy mode passes its own
        individually-jitted versions."""
        strat = self.strategy
        N = self.N
        loss_all = loss_all or self._loss_all[s]
        local_all = local_all or self._local_all[s]

        @jax.named_scope("mmfl.stats")
        def stats_fn(params, data, key, lr, explicit_data=False):
            # explicit_data=False -> the probe slice bound at build time
            # (in-trace slicing of the closed-over dataset would
            # constant-fold a second copy of it into the executable);
            # True -> slice ``data`` in-trace (it is a traced World leaf
            # under run_worlds, so there is nothing to constant-fold)
            losses = loss_all(params, data if explicit_data else None)
            if not strat.needs_all_updates:
                return losses, None, None
            # index-keyed per-client streams: client i's key depends only
            # on (key, i), so padded worlds train real clients identically
            keys = sampling.index_keys(key, N)
            G, _ = local_all(params, keys, data, lr)
            norms = None
            if strat.needs_grad_norms:
                norms = jnp.sqrt(jnp.maximum(
                    stale.batched_tree_dot(G, G), 0.0))
            return losses, G, norms

        return stats_fn

    def make_round_fn(self, s: int,
                      local_all: Optional[Callable] = None) -> Callable:
        """The fused per-round work for task s: cohort gather + local
        training + strategy aggregation, as one pure function.  ``view``
        (optional trailing arg) replaces the engine's closed-over world
        columns with traced per-world ones — the run_worlds path; None
        keeps today's static-world trace.  The Sec. 3.3 monitors live in
        ``sampling_metrics`` — computed once at round_step level from the
        shared sampling arrays, so the fused and loop task paths share one
        metric subgraph bit-for-bit."""
        strat = self.strategy
        N, cohort = self.N, self.cohort_size
        static_view = (self.d[:, s], self._d_v[:, s], self._B_v,
                       self.proc_client, self.world.client_mask)
        local_all = local_all or self._local_all[s]
        fault_model, guard_on = self.fault_model, self.fault_guard

        @jax.named_scope("mmfl.aggregate")
        def round_fn(params, state, train_in, p_col, act_v,
                     data, lr, round_idx, view=None, fault=None):
            """``train_in`` is the task's PRNG key (cohort methods train
            here) or the precomputed all-client G (needs-all methods).
            ``fault`` (optional trailing arg, fault worlds only) carries
            the task's traced (crash, poison) [N] columns — None keeps
            the fault-free trace byte-identical."""
            d_col, d_v_col, B_v, proc, cmask = (static_view if view is None
                                                else view)
            coeffs_v = strat.coefficients(d_v_col, B_v, p_col, act_v)
            # client-level activity: l processors of client i on model
            # s behave as one update scaled by l (Remark 1)
            coeff_client = (jnp.zeros((N,)).at[proc].add(coeffs_v))
            act_client = (jnp.zeros((N,)).at[proc]
                          .add(act_v) > 0).astype(jnp.float32)
            if strat.needs_all_updates:
                idx = jnp.arange(N)
                G, coeff, act = train_in, coeff_client, act_client
            else:
                # cohort path: only the sampled clients run training.
                # argsort is stable, so a padded world (trailing inactive
                # zeros) gathers the same cohort; slot-keyed randomness
                # (index_keys) makes the draw capacity-invariant.
                with jax.named_scope("mmfl.local_train"):
                    idx = jnp.argsort(-act_client)[:cohort]
                    keys = sampling.index_keys(train_in, cohort)
                    data_c = jax.tree.map(lambda x: x[idx], data)
                    corr = strat.local_correction(state, idx)
                G, _ = local_all(params, keys, data_c, lr, corr)
                coeff, act = coeff_client[idx], act_client[idx]
            fault_counts = None
            if fault is not None:
                crash_r, poison_r = fault[0][idx], fault[1][idx]
                cm_r = cmask[idx]
                G = faults.inject(G, act, crash_r, poison_r,
                                  fault_model.poison_value)
                if guard_on:
                    G, coeff, act, rejected, survived = faults.guard(
                        G, coeff, act, crash_r, cm_r)
                else:
                    # unguarded server: the fault world hits the
                    # aggregation raw (crashed rows silently bias it
                    # toward zero; poisoned rows NaN the model)
                    rejected = jnp.float32(0.0)
                    survived = convergence.ordered_sum(act * cm_r)
                fault_counts = (rejected, survived)
            new_w, new_st, extras = strat.aggregate(
                params, state, G, coeff, act, idx,
                d_col=d_col, lr=lr, round_idx=round_idx, mask=cmask)
            if fault_counts is not None:
                extras = dict(extras)
                extras["rejected"], extras["survived"] = fault_counts
            return new_w, new_st, extras

        return round_fn

    def sampling_metrics(self, p: jnp.ndarray, active: jnp.ndarray,
                         losses_ns: jnp.ndarray,
                         world: Optional[World] = None
                         ) -> Dict[str, jnp.ndarray]:
        """The Sec. 3.3 monitors ({H1, Zp, Zl, loss}, [S] each) from the
        sampling-phase arrays, as ONE vmap over the task axis.

        Deliberately OUTSIDE the per-task round: the fused and loop task
        paths both call this same closure on bitwise-identical inputs, so
        the monitors compare bit-for-bit between them — metric reductions
        computed inside the per-task bodies compile differently under the
        task vmap than under the loop (XLA merges/regroups reductions
        sharing operands) and wiggle last-ulp bits."""
        strat = self.strategy
        explicit = world is not None
        w = self.world if world is None else world
        d_v = w.d[w.proc_client] if explicit else self._d_v
        B_v = w.B[w.proc_client] if explicit else self._B_v
        proc = w.proc_client if explicit else self.proc_client

        def one(p_col, act_col, d_v_col, d_col, losses_col):
            coeffs_v = strat.coefficients(d_v_col, B_v, p_col, act_col)
            mets = convergence.round_metrics(coeffs_v, losses_col[proc],
                                             d_v_col, B_v)
            mets["loss"] = convergence.ordered_sum(d_col * losses_col)
            return mets

        return jax.vmap(one, in_axes=(1, 1, 1, 1, 1))(
            p, active, d_v, w.d, losses_ns)

    # ------------------------------------------------------------------
    # fused task axis: group-level pure computations (one vmap per group)
    # ------------------------------------------------------------------
    def group_scope(self, g: int) -> str:
        """Name scope of signature group g's stats and round bodies in the
        fused round: ``group.<its first task's name>``."""
        return f"group.{self.tasks[self.groups[g][0]].name}"

    def make_group_stats_fn(self, g: int) -> Callable:
        """The stats phase for signature group g as ONE vmapped dispatch
        over the group's stacked (params, data, keys).  Per-task streams
        are preserved exactly: slot j consumes the SAME ``keys[2 + s]``
        key the per-task loop hands task s = groups[g][j]."""
        grp = self.groups[g]
        strat, N = self.strategy, self.N
        rep = self.tasks[grp[0]]
        loss_fn = rep.model.loss_fn
        local_all = self._local_all[grp[0]]
        stacked = self.world.data[g]
        take = min(int(stacked["x"].shape[2]), PROBE_TAKE)
        # probe slices bound at build time from the stacked group shards
        # (bitwise the per-task probes: jnp.stack copies exactly)
        probe_x = stacked["x"][:, :, :take]
        probe_y = stacked["y"][:, :, :take]

        def one_task(params, px, py, data, key, lr):
            losses = jax.vmap(lambda xc, yc: loss_fn(params,
                                                     {"x": xc, "y": yc})
                              )(px, py)
            if not strat.needs_all_updates:
                return losses, None, None
            keys = sampling.index_keys(key, N)
            G, _ = local_all(params, keys, data, lr)
            norms = None
            if strat.needs_grad_norms:
                norms = jnp.sqrt(jnp.maximum(
                    stale.batched_tree_dot(G, G), 0.0))
            return losses, G, norms

        @jax.named_scope(self.group_scope(g))
        @jax.named_scope("mmfl.stats")
        def stats_g(params_g, data_g, keys_g, lr, explicit=False):
            px, py = ((data_g["x"][:, :, :take], data_g["y"][:, :, :take])
                      if explicit else (probe_x, probe_y))
            if len(grp) == 1:
                # single-task group: bypass the vmap so the trace is the
                # per-task loop's, slot-sliced (fused == loop trivially)
                sq = lambda t: jax.tree.map(lambda a: a[0], t)
                out = one_task(sq(params_g), px[0], py[0], sq(data_g),
                               keys_g[0], lr)
                return jax.tree.map(lambda a: a[None], out)
            return jax.vmap(one_task, in_axes=(0, 0, 0, 0, 0, None))(
                params_g, px, py, data_g, keys_g, lr)

        return stats_g

    def make_group_round_fn(self, g: int) -> Callable:
        """Signature group g's fused per-task round: ONE vmap of the
        per-task ``round_fn`` over the stacked (params, method state,
        training inputs, sampling columns).  The world view rides along
        with per-task axes on (d_col, d_v_col) and broadcast axes on the
        shared (B_v, proc_client, client_mask)."""
        grp = self.groups[g]
        round_one = self.make_round_fn(grp[0],
                                       local_all=self._local_all[grp[0]])

        @jax.named_scope(self.group_scope(g))
        def round_g(params_g, state_g, train_in_g, p_g, act_g,
                    data_g, lr, round_idx, view_g, fault_g=None):
            if len(grp) == 1:
                sq = lambda t: jax.tree.map(lambda a: a[0], t)
                d_col, d_v_col, B_v, proc, cmask = view_g
                f1 = (None if fault_g is None
                      else (fault_g[0][0], fault_g[1][0]))
                out = round_one(sq(params_g), sq(state_g), sq(train_in_g),
                                p_g[0], act_g[0], sq(data_g),
                                lr, round_idx,
                                (d_col[0], d_v_col[0], B_v, proc, cmask),
                                f1)
                return jax.tree.map(lambda a: a[None], out)   # 3-tuple
            if fault_g is None:
                return jax.vmap(
                    round_one,
                    in_axes=(0, 0, 0, 0, 0, 0, None, None,
                             (0, 0, None, None, None)))(
                    params_g, state_g, train_in_g, p_g, act_g,
                    data_g, lr, round_idx, view_g)
            return jax.vmap(
                round_one,
                in_axes=(0, 0, 0, 0, 0, 0, None, None,
                         (0, 0, None, None, None), (0, 0)))(
                params_g, state_g, train_in_g, p_g, act_g,
                data_g, lr, round_idx, view_g, fault_g)

        return round_g

    def _scatter_tasks(self, parts: Sequence[jnp.ndarray],
                       tail_shape: Tuple[int, ...] = ()) -> jnp.ndarray:
        """Reassemble per-group [G_s, ...] rows into task order [S, ...]."""
        out = jnp.zeros((self.S,) + tail_shape, parts[0].dtype)
        for g, grp in enumerate(self.groups):
            out = out.at[np.asarray(grp)].set(parts[g])
        return out

    def _to_task_cols(self, parts: Sequence[jnp.ndarray],
                      n: Optional[int] = None) -> jnp.ndarray:
        """Per-group [G_s, n] stats rows -> the sampler's [n, S] columns
        (``n`` defaults to N; the sharded body assembles shard-local
        [n_loc, S] blocks)."""
        out = jnp.zeros((self.N if n is None else n, self.S),
                        parts[0].dtype)
        for g, grp in enumerate(self.groups):
            out = out.at[:, np.asarray(grp)].set(parts[g].T)
        return out

    # ------------------------------------------------------------------
    # client-sharded round: the same transition over mesh-local blocks
    # ------------------------------------------------------------------
    def _mstate_flags(self, g: int) -> Any:
        """Boolean client-axis flags for group g's (single-task) method
        state, from the strategy's EXPLICIT declaration
        (``MethodStrategy.state_client_axes`` — never shape inference: a
        global params-shaped leaf can collide with N in its first dim)."""
        s0 = self.groups[g][0]
        struct = jax.eval_shape(
            lambda k: self.strategy.init_state(
                self.tasks[s0].model.init(k), self.N),
            jax.random.PRNGKey(0))
        return self.strategy.state_client_axes(struct)

    def _async_state_specs(self, struct: Any) -> Any:
        """PartitionSpecs for ``ExperimentState.async_state`` under the
        client mesh.  The synchronous engine carries None (an empty
        pytree — no specs needed); ``AsyncRoundEngine`` overrides with
        the in-flight buffer layout (every async leaf is client-indexed
        after the group-stack axis, like the stale stores)."""
        return None

    def _build_sharded(self) -> None:
        """State/data PartitionSpecs, NamedShardings, and the jitted
        shard_map step for the client mesh.

        Layout contract (ROADMAP.md §"Client-sharding contract"): params
        and global method-state leaves replicate; method-state leaves the
        strategy flags as client-indexed shard their post-group-stack axis
        (``spec_for(..., lead=1)``); ``losses_ns`` and ``client_mask``
        shard their leading [N] axis; the group-stacked data shards axis 1
        ([task, client, ...])."""
        P = PartitionSpec
        axis = sharding.CLIENT_AXIS
        struct = jax.eval_shape(self._init_from_key, jax.random.PRNGKey(0))
        self.state_specs = ExperimentState(
            params=jax.tree.map(lambda _: P(), struct.params),
            method_state=tuple(
                jax.tree.map(lambda f: sharding.spec_for(bool(f), lead=1),
                             self._mstate_flags(g))
                for g in range(self.n_groups)),
            key=P(), round=P(), losses_ns=P(axis), client_mask=P(axis),
            task_group=P(), task_slot=P(),
            async_state=self._async_state_specs(struct))
        self.state_shardings = sharding.tree_shardings(self.mesh,
                                                       self.state_specs)
        self.data_spec = P(None, axis)
        self._sharded_body = self._make_sharded_body()
        step = jax.shard_map(self._sharded_body, mesh=self.mesh,
                             in_specs=(self.state_specs, self.data_spec),
                             out_specs=(self.state_specs, P()),
                             check_vma=False)
        self._sharded_step = jax.jit(step, donate_argnums=0)
        self._init_sharded = jax.jit(
            lambda params, key: self._assemble_state(params, key),
            out_shardings=self.state_shardings)

    def state_bytes_per_device(self, state: ExperimentState) -> int:
        """Analytic per-device bytes of ``state`` under the engine's layout
        (host CPU meshes expose no ``memory_stats`` to measure against) —
        the quantity ``BENCH_engine.json``'s ``sharded_scaling`` records."""
        if self.mesh is None:
            return sharding.tree_bytes_per_device(
                state, jax.tree.map(lambda _: PartitionSpec(), state), 1)
        return sharding.tree_bytes_per_device(state, self.state_specs,
                                              self.n_shards)

    def _refuse_mesh(self, what: str) -> None:
        if self.mesh is not None:
            raise NotImplementedError(
                f"{what} is not available on a client-sharded engine "
                f"(mesh over {self.n_shards} devices): the seed/world "
                f"fleet axes would multiply every sharded client-state "
                f"leaf; run fleets single-device, or shard one run at a "
                f"time")

    def _make_group_stats_loc(self, g: int) -> Callable:
        """Group g's stats phase over ONE shard's client block.  Identical
        per-client math to ``make_group_stats_fn``: probe rows are
        per-client-independent, and the index-keyed training streams
        depend only on (key, global client index) — ``off`` shifts the key
        index space to the shard's global offset, so the local block
        reproduces bitwise the rows the single-device pass computes for
        those clients.  Probe slicing happens in-trace here: the data is a
        traced shard_map input (nothing to constant-fold)."""
        grp = self.groups[g]
        strat, n_loc = self.strategy, self.n_loc
        loss_fn = self.tasks[grp[0]].model.loss_fn
        local_all = self._local_all[grp[0]]
        take = min(int(self.world.data[g]["x"].shape[2]), PROBE_TAKE)

        def one_task(params, data, key, lr, off):
            px, py = data["x"][:, :take], data["y"][:, :take]
            losses = jax.vmap(lambda xc, yc: loss_fn(params,
                                                     {"x": xc, "y": yc})
                              )(px, py)
            if not strat.needs_all_updates:
                return losses, None, None
            keys = sampling.index_keys(key, n_loc, offset=off)
            G, _ = local_all(params, keys, data, lr)
            norms = None
            if strat.needs_grad_norms:
                norms = jnp.sqrt(jnp.maximum(
                    stale.batched_tree_dot(G, G), 0.0))
            return losses, G, norms

        @jax.named_scope(self.group_scope(g))
        @jax.named_scope("mmfl.stats")
        def stats_g(params_g, data_g, keys_g, lr, off):
            if len(grp) == 1:
                sq = lambda t: jax.tree.map(lambda a: a[0], t)
                out = one_task(sq(params_g), sq(data_g), keys_g[0], lr, off)
                return jax.tree.map(lambda a: a[None], out)
            return jax.vmap(one_task, in_axes=(0, 0, 0, None, None))(
                params_g, data_g, keys_g, lr, off)

        return stats_g

    def _make_group_round_loc(self, g: int) -> Callable:
        """Group g's per-task round over ONE shard's client block.

        Cohort selection matches the single-device ``make_round_fn``
        slot-for-slot: there, stable ``argsort(-act_client)[:cohort]``
        puts active client c in slot rank(c) = #actives with smaller
        index, keyed ``fold_in(train_in, slot)``.  Here every shard
        derives the global ranks from the replicated activity vector
        (exact integer cumsum), trains its LOCAL members of the global
        cohort under their global-rank keys (local capacity ``min(cohort,
        n_loc)``), and zero-weights overflow actives (rank >= cohort)
        exactly as the single-device capacity drop excludes them.
        Per-client updates are bitwise the single-device ones; only the
        cross-shard delta reduction (the strategy's psum) regroups partial
        sums at ulp level."""
        grp = self.groups[g]
        strat = self.strategy
        N, n_loc, cohort = self.N, self.n_loc, self.cohort_size
        cohort_loc = min(cohort, n_loc)
        local_all = self._local_all[grp[0]]
        axis = sharding.CLIENT_AXIS
        fault_model, guard_on = self.fault_model, self.fault_guard

        @jax.named_scope("mmfl.aggregate")
        def round_one(params, state, train_in, p_col, act_v, data,
                      lr, round_idx, view, off, fault=None):
            d_col, d_v_col, B_v, proc, cmask = view    # replicated [N]/[V]
            coeffs_v = strat.coefficients(d_v_col, B_v, p_col, act_v)
            coeff_client = jnp.zeros((N,)).at[proc].add(coeffs_v)
            act_client = (jnp.zeros((N,)).at[proc]
                          .add(act_v) > 0).astype(jnp.float32)
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, off, n_loc)
            coeff_loc, act_loc = sl(coeff_client), sl(act_client)
            d_loc, cmask_loc = sl(d_col), sl(cmask)
            if strat.needs_all_updates:
                idx = jnp.arange(n_loc)
                G, coeff, act = train_in, coeff_loc, act_loc
            else:
                acts_i = act_client.astype(jnp.int32)
                rank = jnp.cumsum(acts_i) - acts_i           # [N] exact
                rank_loc = sl(rank)
                in_cohort = act_loc * (rank_loc < cohort)
                with jax.named_scope("mmfl.local_train"):
                    idx = jnp.argsort(-in_cohort)[:cohort_loc]
                    slot_keys = jax.vmap(
                        lambda i: jax.random.fold_in(train_in, i))(
                        rank_loc[idx])
                    data_c = jax.tree.map(lambda x: x[idx], data)
                    corr = strat.local_correction(state, idx)
                G, _ = local_all(params, slot_keys, data_c, lr, corr)
                coeff = coeff_loc[idx] * in_cohort[idx]
                act = in_cohort[idx]
            fault_counts = None
            if fault is not None:
                # shard-local (crash, poison) columns, drawn offset-keyed
                # so they reproduce the single-device fault world
                crash_r, poison_r = fault[0][idx], fault[1][idx]
                cm_r = cmask_loc[idx]
                G = faults.inject(G, act, crash_r, poison_r,
                                  fault_model.poison_value)
                if guard_on:
                    G, coeff, act, rejected, survived = faults.guard(
                        G, coeff, act, crash_r, cm_r, axis_name=axis)
                else:
                    rejected = jnp.float32(0.0)
                    survived = jax.lax.psum(
                        convergence.ordered_sum(act * cm_r), axis)
                fault_counts = (rejected, survived)
            new_w, new_st, extras = strat.aggregate(
                params, state, G, coeff, act, idx,
                d_col=d_loc, lr=lr, round_idx=round_idx, mask=cmask_loc,
                axis_name=axis)
            if fault_counts is not None:
                extras = dict(extras)
                extras["rejected"], extras["survived"] = fault_counts
            return new_w, new_st, extras

        @jax.named_scope(self.group_scope(g))
        def round_g(params_g, state_g, train_in_g, p_g, act_g,
                    data_g, lr, round_idx, view_g, off, fault_g=None):
            if len(grp) == 1:
                sq = lambda t: jax.tree.map(lambda a: a[0], t)
                d_col, d_v_col, B_v, proc, cmask = view_g
                f1 = (None if fault_g is None
                      else (fault_g[0][0], fault_g[1][0]))
                out = round_one(sq(params_g), sq(state_g), sq(train_in_g),
                                p_g[0], act_g[0], sq(data_g), lr, round_idx,
                                (d_col[0], d_v_col[0], B_v, proc, cmask),
                                off, f1)
                return jax.tree.map(lambda a: a[None], out)
            if fault_g is None:
                return jax.vmap(
                    round_one,
                    in_axes=(0, 0, 0, 0, 0, 0, None, None,
                             (0, 0, None, None, None), None))(
                    params_g, state_g, train_in_g, p_g, act_g,
                    data_g, lr, round_idx, view_g, off)
            return jax.vmap(
                round_one,
                in_axes=(0, 0, 0, 0, 0, 0, None, None,
                         (0, 0, None, None, None), None, (0, 0)))(
                params_g, state_g, train_in_g, p_g, act_g,
                data_g, lr, round_idx, view_g, off, fault_g)

        return round_g

    def _make_sharded_body(self) -> Callable:
        """The whole round — local stats, loss gather, replicated sampling
        and monitors, per-group round — as ONE function of mesh-LOCAL
        client blocks, to be wrapped in ``shard_map``.

        Replicated quantities (the [V, S] sampling arrays, the
        water-filling solve, the Sec. 3.3 monitors) are computed
        identically on every shard from the all-gathered loss/norm columns
        — bit-identical to the single-device sampling phase by
        construction.  Cross-client contractions happen inside the
        strategies as per-shard partials + ``psum``
        (``aggregate(axis_name=)``), which regroups partial sums: the
        documented ulp-level sharding tolerance (tests/test_sharding.py).
        The single-device path never enters this body and stays the
        bit-reference."""
        cfg, S = self.cfg, self.S
        strat = self.strategy
        axis = sharding.CLIENT_AXIS
        n_loc, groups = self.n_loc, self.groups
        # replicated world columns (O(N·S)/O(V·S) vectors — the arrays the
        # sharding exists for, the [N, cap/params] ones, never close over)
        d_full, d_v, B_v = self.d, self._d_v, self._B_v
        proc, proc_mask = self.proc_client, self.world.proc_mask
        cmask_full = self.world.client_mask
        g_stats = [self._make_group_stats_loc(g)
                   for g in range(self.n_groups)]
        g_round = [self._make_group_round_loc(g)
                   for g in range(self.n_groups)]

        def body(state: ExperimentState, data: Tuple[Any, ...]
                 ) -> Tuple[ExperimentState, Dict[str, jnp.ndarray]]:
            off = jax.lax.axis_index(axis) * n_loc
            round_f = state.round.astype(jnp.float32)
            lr = jnp.float32(cfg.lr) * jnp.float32(cfg.lr_decay) ** round_f
            keys = jax.random.split(state.key, 2 + S)
            new_key, k_sample = keys[0], keys[1]
            task_keys = keys[2:]

            # ---- 1) stats on the local client block ---------------------
            stats = [g_stats[g](state.params[g], data[g],
                                task_keys[np.asarray(grp)], lr, off)
                     for g, grp in enumerate(groups)]
            with jax.named_scope("mmfl.sample"):
                losses_loc = self._to_task_cols(
                    [st[0] for st in stats], n=n_loc)          # [n_loc,S]
                losses_ns = jax.lax.all_gather(
                    losses_loc, axis, axis=0, tiled=True)      # [N,S] repl
                norms_ns = None
                if strat.needs_grad_norms:
                    norms_ns = jax.lax.all_gather(
                        self._to_task_cols([st[2] for st in stats],
                                           n=n_loc),
                        axis, axis=0, tiled=True)

                # ---- 2) sampling (replicated: every shard computes the
                # same [V,S] arrays from the same gathered columns) -------
                ctx = self.sampler_ctx(state.round)
                if self.probabilities_hook is not None:
                    p = self.probabilities_hook(ctx, losses_ns, norms_ns)
                else:
                    p = strat.probabilities(ctx, losses_ns, norms_ns)
                p = p * proc_mask[:, None]
                active = strat.sample(k_sample, p, ctx, losses_ns)
                active = active * proc_mask[:, None]

                # ---- 3) Sec. 3.3 monitors (the single-device subgraph on
                # the replicated sampling arrays: bitwise the unsharded
                # metrics) ------------------------------------------------
                metrics = self.sampling_metrics(p, active, losses_ns)

            # ---- 4) per-group round on local blocks ---------------------
            fault_loc = None
            if self.faulty:
                # shard-local fault columns: offset-keyed draws reproduce
                # the single-device fault world block-for-block
                with jax.named_scope("mmfl.aggregate"):
                    fault_loc = self._fault_cols(state.key, state.round,
                                                 n=n_loc, offset=off)
            new_params, new_mstate, beta_parts = [], [], []
            rej_parts, srv_parts = [], []
            for g, grp in enumerate(groups):
                ia = np.asarray(grp)
                train_in = (stats[g][1] if strat.needs_all_updates
                            else task_keys[ia])
                view = (d_full[:, ia].T, d_v[:, ia].T, B_v, proc,
                        cmask_full)
                if fault_loc is None:
                    new_w, new_st, extras = g_round[g](
                        state.params[g], state.method_state[g], train_in,
                        p[:, ia].T, active[:, ia].T, data[g], lr, round_f,
                        view, off)
                else:
                    fg = (fault_loc[0][:, ia].T, fault_loc[1][:, ia].T)
                    new_w, new_st, extras = g_round[g](
                        state.params[g], state.method_state[g], train_in,
                        p[:, ia].T, active[:, ia].T, data[g], lr, round_f,
                        view, off, fg)
                    rej_parts.append(extras["rejected"])
                    srv_parts.append(extras["survived"])
                new_params.append(new_w)
                new_mstate.append(new_st)
                beta_parts.append(extras.get("beta"))
            if beta_parts[0] is not None:
                beta_loc = self._scatter_tasks(beta_parts,
                                               tail_shape=(n_loc,))
                metrics["beta"] = jax.lax.all_gather(
                    beta_loc, axis, axis=1, tiled=True)        # [S,N] repl
            if fault_loc is not None:
                # psum'd inside the guard -> already replicated scalars
                metrics["rejected"] = self._scatter_tasks(rej_parts)
                metrics["survived"] = self._scatter_tasks(srv_parts)
            new_state = ExperimentState(
                params=tuple(new_params), method_state=tuple(new_mstate),
                key=new_key, round=state.round + 1, losses_ns=losses_loc,
                client_mask=state.client_mask, task_group=state.task_group,
                task_slot=state.task_slot, async_state=state.async_state)
            return new_state, metrics

        return body

    def _sharded_rollout(self, n_rounds: int) -> Callable:
        """``rollout``'s lax.scan placed INSIDE the shard_map (collectives
        scan fine; one executable per chunk length, donated carry)."""
        body = self._sharded_body

        def roll(state, data):
            def step(st, _):
                return body(st, data)
            return jax.lax.scan(step, state, None, length=n_rounds)

        fn = jax.shard_map(roll, mesh=self.mesh,
                           in_specs=(self.state_specs, self.data_spec),
                           out_specs=(self.state_specs, PartitionSpec()),
                           check_vma=False)
        return jax.jit(fn, donate_argnums=0)

    # ------------------------------------------------------------------
    # state constructors
    # ------------------------------------------------------------------
    def init_state(self, seed: Optional[int] = None,
                   key: Optional[jax.Array] = None,
                   world: Optional[World] = None) -> ExperimentState:
        """Fresh experiment state.  Key-splitting order matches the
        pre-refactor server exactly (golden metrics stay pinned).  ``seed``
        may be a traced int32 (``run_seeds`` vmaps over it); ``world`` (a
        traced World under ``run_worlds``) supplies the client mask the
        state carries."""
        if key is None:
            key = jax.random.PRNGKey(self.cfg.seed if seed is None else seed)
        if self.mesh is not None:
            if world is not None:
                self._refuse_mesh("init_state(world=...)")
            # params (small, replicated) init EAGERLY — bitwise the
            # single-device init (jit would fuse the RNG scaling by an
            # ulp); the [N, ...] method-state leaves are deterministic
            # constants (zeros/ones — bitwise stable under jit) and are
            # CREATED in their sharded layout by the jitted assembler, so
            # they never materialize on one device
            params, key = self._init_params(key)
            return self._init_sharded(params, key)
        return self._init_from_key(key, world)

    def _init_params(self, key: jax.Array) -> Tuple[List[Any], jax.Array]:
        params: List[Any] = []
        for t in self.tasks:
            key, k = jax.random.split(key)
            params.append(t.model.init(k))
        return params, key

    def _init_from_key(self, key: jax.Array,
                       world: Optional[World] = None) -> ExperimentState:
        params, key = self._init_params(key)
        return self._assemble_state(params, key, world)

    def _assemble_state(self, params: List[Any], key: jax.Array,
                        world: Optional[World] = None) -> ExperimentState:
        mstate = [self.strategy.init_state(params[s], self.N)
                  for s in range(self.S)]
        return ExperimentState(
            params=self.group_stack(params),
            method_state=self.group_stack(mstate), key=key,
            round=jnp.asarray(0, jnp.int32),
            losses_ns=jnp.ones((self.N, self.S), jnp.float32),
            # a copy of the engine's own mask: ``rollout`` donates the
            # state, and the engine's buffer must outlive it
            client_mask=(jnp.copy(self.world.client_mask) if world is None
                         else world.client_mask),
            task_group=jnp.asarray(self._task_group_np),
            task_slot=jnp.asarray(self._task_slot_np))

    def sampler_ctx(self, round_idx: Any,
                    world: Optional[World] = None) -> methods.SamplerContext:
        """Sampler context usable INSIDE a traced round: on the engine's
        own world ``B``/``m`` are host (numpy) values so the strategies'
        client->processor expansion (``processor_budget_utilities``'s
        static repeat lengths) stays concrete under jit/scan/vmap; with a
        traced ``world`` they are per-world leaves and the static sizes
        ride on ``V``/``m_host`` instead."""
        if world is None:
            return methods.SamplerContext(d=self.d, B=self._B_host,
                                          avail=self.avail, m=self.m,
                                          round=round_idx, V=self.V,
                                          m_host=self.m,
                                          mask=self.world.client_mask)
        return methods.SamplerContext(
            d=world.d, B=world.B, avail=world.avail,
            m=self.cfg.active_rate * world.v_real, round=round_idx,
            V=self.V, m_host=self.m, mask=world.client_mask)

    # ------------------------------------------------------------------
    # fault axis: the traced fault world (core.faults)
    # ------------------------------------------------------------------
    def _fault_keys(self, key: jax.Array) -> jnp.ndarray:
        """[S] per-task fault keys folded off the state key on the
        dedicated FAULT_STREAM tag — disjoint from the sync split
        schedule and the async delay stream, so drawing faults never
        perturbs the sampling/training draws."""
        k = jax.random.fold_in(key, faults.FAULT_STREAM)
        return jnp.stack([jax.random.fold_in(k, s) for s in range(self.S)])

    def _fault_cols(self, key: jax.Array, round_idx: Any,
                    n: Optional[int] = None, offset: Any = 0
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(crash, poison) [n, S] columns of this round's fault world.
        Index-keyed draws (``offset`` = the shard's global base) make the
        columns padding- and shard-invariant, like every other per-client
        stream."""
        fkeys = self._fault_keys(key)
        n = self.N if n is None else n
        fm = self.fault_model
        crash = jnp.stack(
            [fm.crash_mask(fkeys[s], round_idx, n, offset=offset)
             for s in range(self.S)], axis=1)
        poison = jnp.stack(
            [fm.poison_mask(fkeys[s], round_idx, n, offset=offset)
             for s in range(self.S)], axis=1)
        return crash, poison

    # ------------------------------------------------------------------
    # the pure round transition
    # ------------------------------------------------------------------
    def round_step_fn(self, state: ExperimentState,
                      world: Optional[World] = None
                      ) -> Tuple[ExperimentState, Dict[str, jnp.ndarray]]:
        """state -> (state', metrics).  Pure and jittable: safe under
        ``jax.jit``, ``lax.scan`` (rollout) and ``jax.vmap`` (seed fleets).

        ``world=None`` closes over the engine's own world as trace
        constants (the classic path); a traced ``World`` argument makes
        the SAME transition a function of the world too — ``run_worlds``
        vmaps it over stacked world pytrees.

        With ``fuse_tasks`` (default) the S-task stats phase and per-task
        round run as one vmap per signature group; ``fuse_tasks=False``
        keeps the per-task Python loop on the same grouped state layout
        (the A/B baseline of ``bench_task_fusion``) — both produce
        bit-identical results (tests/test_task_fusion.py).

        Metrics are [S]-stacked device arrays ({H1, Zp, Zl, loss}; plus
        ``beta`` [S, N] for the stale family) — no host syncs here."""
        cfg, S = self.cfg, self.S
        strat = self.strategy
        explicit = world is not None
        w = self.world if world is None else world
        round_f = state.round.astype(jnp.float32)
        lr = jnp.float32(cfg.lr) * jnp.float32(cfg.lr_decay) ** round_f
        keys = jax.random.split(state.key, 2 + S)
        new_key, k_sample = keys[0], keys[1]
        task_keys = keys[2:]
        fused = self.fuse_tasks

        # ---- 1) stats for the sampler -----------------------------------
        if fused:
            stats = [self._g_stats[g](state.params[g], w.data[g],
                                      task_keys[np.asarray(grp)], lr,
                                      explicit)
                     for g, grp in enumerate(self.groups)]
            losses_ns = self._to_task_cols([st[0] for st in stats])   # [N,S]
            norms_ns = (self._to_task_cols([st[2] for st in stats])
                        if strat.needs_grad_norms else None)
        else:
            stats = [self._stats_pure[s](self.task_params(state, s),
                                         self._task_data(w, s, explicit),
                                         task_keys[s], lr, explicit)
                     for s in range(S)]
            losses_ns = jnp.stack([st[0] for st in stats], axis=1)    # [N,S]
            norms_ns = (jnp.stack([st[2] for st in stats], axis=1)
                        if strat.needs_grad_norms else None)

        # ---- 2) sampling -------------------------------------------------
        with jax.named_scope("mmfl.sample"):
            ctx = self.sampler_ctx(state.round, world)
            if self.probabilities_hook is not None:
                p = self.probabilities_hook(ctx, losses_ns, norms_ns)
            else:
                p = strat.probabilities(ctx, losses_ns, norms_ns)  # [V,S]
            # the engine-level mask guarantee: whatever the strategy (or a
            # pinned probabilities hook) returns, padding processors carry
            # no probability and draw no participation
            p = p * w.proc_mask[:, None]
            active = strat.sample(k_sample, p, ctx, losses_ns)
            active = active * w.proc_mask[:, None]

            # ---- 3) Sec. 3.3 monitors (shared by BOTH task paths) -------
            # computed here, from the sampling arrays the two paths
            # already share bitwise, so fused == loop holds for metrics by
            # construction
            metrics = self.sampling_metrics(p, active, losses_ns, world)

        # ---- 4) fused per-task round ------------------------------------
        d_v_t = w.d[w.proc_client] if explicit else self._d_v
        B_v_t = w.B[w.proc_client] if explicit else self._B_v
        proc_t = w.proc_client if explicit else self.proc_client
        cmask_t = w.client_mask if explicit else self.world.client_mask
        fault_ns = None
        if self.faulty:
            with jax.named_scope("mmfl.aggregate"):
                fault_ns = self._fault_cols(state.key, state.round)
        if fused:
            new_params, new_mstate = [], []
            beta_parts = []
            rej_parts, srv_parts = [], []
            for g, grp in enumerate(self.groups):
                ia = np.asarray(grp)
                train_in = (stats[g][1] if strat.needs_all_updates
                            else task_keys[ia])
                view = (w.d[:, ia].T, d_v_t[:, ia].T, B_v_t, proc_t,
                        cmask_t)
                if fault_ns is None:
                    new_w, new_st, extras = self._g_round[g](
                        state.params[g], state.method_state[g], train_in,
                        p[:, ia].T, active[:, ia].T, w.data[g],
                        lr, round_f, view)
                else:
                    fg = (fault_ns[0][:, ia].T, fault_ns[1][:, ia].T)
                    new_w, new_st, extras = self._g_round[g](
                        state.params[g], state.method_state[g], train_in,
                        p[:, ia].T, active[:, ia].T, w.data[g],
                        lr, round_f, view, fg)
                    rej_parts.append(extras["rejected"])
                    srv_parts.append(extras["survived"])
                new_params.append(new_w)
                new_mstate.append(new_st)
                beta_parts.append(extras.get("beta"))
            if beta_parts[0] is not None:
                metrics["beta"] = self._scatter_tasks(
                    beta_parts, tail_shape=(self.N,))               # [S,N]
            if fault_ns is not None:
                metrics["rejected"] = self._scatter_tasks(rej_parts)
                metrics["survived"] = self._scatter_tasks(srv_parts)
        else:
            new_params = [state.params[g] for g in range(self.n_groups)]
            new_mstate = [state.method_state[g]
                          for g in range(self.n_groups)]
            betas: List[jnp.ndarray] = []
            rej_s: List[jnp.ndarray] = []
            srv_s: List[jnp.ndarray] = []
            for s in range(S):
                g, j = self.task_gs[s]
                train_in = (stats[s][1] if strat.needs_all_updates
                            else task_keys[s])
                view = ((w.d[:, s], d_v_t[:, s], B_v_t, proc_t, cmask_t)
                        if explicit else None)
                if fault_ns is None:
                    new_w, new_st, extras = self._round_pure[s](
                        self.task_params(state, s),
                        self.task_method_state(state, s), train_in,
                        p[:, s], active[:, s],
                        self._task_data(w, s, explicit), lr, round_f,
                        view)
                else:
                    # the loop path needs the explicit view to hand the
                    # fault columns positionally
                    view = (view if view is not None
                            else (w.d[:, s], d_v_t[:, s], B_v_t, proc_t,
                                  cmask_t))
                    new_w, new_st, extras = self._round_pure[s](
                        self.task_params(state, s),
                        self.task_method_state(state, s), train_in,
                        p[:, s], active[:, s],
                        self._task_data(w, s, explicit), lr, round_f,
                        view, (fault_ns[0][:, s], fault_ns[1][:, s]))
                    rej_s.append(extras["rejected"])
                    srv_s.append(extras["survived"])
                new_params[g] = jax.tree.map(
                    lambda a, v: a.at[j].set(v), new_params[g], new_w)
                new_mstate[g] = jax.tree.map(
                    lambda a, v: a.at[j].set(v), new_mstate[g], new_st)
                if "beta" in extras:
                    betas.append(extras["beta"])
            if betas:
                metrics["beta"] = jnp.stack(betas)                    # [S,N]
            if fault_ns is not None:
                metrics["rejected"] = jnp.stack(rej_s)
                metrics["survived"] = jnp.stack(srv_s)
        new_state = ExperimentState(
            params=tuple(new_params), method_state=tuple(new_mstate),
            key=new_key, round=state.round + 1, losses_ns=losses_ns,
            client_mask=state.client_mask, task_group=state.task_group,
            task_slot=state.task_slot, async_state=state.async_state)
        return new_state, metrics

    # ------------------------------------------------------------------
    # scanned rollouts + vmapped seed fleets
    # ------------------------------------------------------------------
    def _rollout_fn(self, n_rounds: int) -> Callable:
        def roll(state):
            def body(st, _):
                return self.round_step_fn(st)
            return jax.lax.scan(body, state, None, length=n_rounds)
        return roll

    def rollout(self, state: ExperimentState, n_rounds: int
                ) -> Tuple[ExperimentState, Dict[str, jnp.ndarray]]:
        """Run ``n_rounds`` rounds as ONE ``lax.scan`` dispatch.  Metrics
        come back stacked on-device ([n_rounds, S] per key) — equivalent to
        n sequential ``round_step`` calls, minus every per-round dispatch
        and host sync.  The input state is DONATED (its buffers are
        reused for the output state): rebind the result, don't reuse the
        argument."""
        n_rounds = int(n_rounds)
        with TraceAnnotation("mmfl.rollout", rounds=n_rounds):
            fn = self._rollout_cache.get(n_rounds)
            if fn is None:
                fn = (self._sharded_rollout(n_rounds)
                      if self.mesh is not None
                      else jax.jit(self._rollout_fn(n_rounds),
                                   donate_argnums=0))
                self._rollout_cache[n_rounds] = fn
            return (fn(state, self.world.data) if self.mesh is not None
                    else fn(state))

    def run_seeds(self, seeds: Any, n_rounds: int
                  ) -> Tuple[ExperimentState, Dict[str, jnp.ndarray],
                             jnp.ndarray]:
        """Vmap independent replicates over seeds in a single compile.

        Returns (final_states, metrics, final_accs) with a leading
        [n_seeds] axis everywhere ([n_seeds, n_rounds, S] metrics,
        [n_seeds, S] accuracies) — Table-1 error bars in one dispatch."""
        self._refuse_mesh("run_seeds")
        seeds = jnp.asarray(seeds, jnp.int32)
        n_rounds = int(n_rounds)
        fn = self._run_seeds_cache.get(n_rounds)
        if fn is None:
            roll = self._rollout_fn(n_rounds)

            def one(seed):
                st0 = self.init_state(key=jax.random.PRNGKey(seed))
                stf, mets = roll(st0)
                return stf, mets, self.evaluate_fn(stf)

            fn = jax.jit(jax.vmap(one))
            self._run_seeds_cache[n_rounds] = fn
        return fn(seeds)

    # ------------------------------------------------------------------
    # stacked seed fleets as composable pieces (sweep harness substrate):
    # ``run_seeds`` fuses init+rollout+eval into one dispatch, but a sweep
    # with an eval CADENCE needs to stop the fleet every ``eval_every``
    # rounds — these hooks expose the same vmapped stages individually so
    # chunked rollouts interleave with stacked evaluations at equal
    # compile cost (one executable per stage, reused across chunks).
    # ------------------------------------------------------------------
    def init_states(self, seeds: Any) -> ExperimentState:
        """Vmapped ``init_state`` over seeds: one ``ExperimentState`` whose
        every leaf carries a leading [n_seeds] axis."""
        self._refuse_mesh("init_states")
        seeds = jnp.asarray(seeds, jnp.int32)
        if self._fleet_init_fn is None:
            self._fleet_init_fn = jax.jit(jax.vmap(
                lambda sd: self.init_state(key=jax.random.PRNGKey(sd))))
        return self._fleet_init_fn(seeds)

    def rollout_states(self, states: ExperimentState, n_rounds: int
                       ) -> Tuple[ExperimentState, Dict[str, jnp.ndarray]]:
        """``rollout`` vmapped over a stacked fleet state: ONE dispatch for
        all seeds x ``n_rounds`` rounds, metrics [n_seeds, n_rounds, S].
        The input fleet state is DONATED (rebind the result)."""
        self._refuse_mesh("rollout_states")
        n_rounds = int(n_rounds)
        fn = self._fleet_rollout_cache.get(n_rounds)
        if fn is None:
            fn = jax.jit(jax.vmap(self._rollout_fn(n_rounds)),
                         donate_argnums=0)
            self._fleet_rollout_cache[n_rounds] = fn
        return fn(states)

    def evaluate_states(self, states: ExperimentState) -> jnp.ndarray:
        """[n_seeds, S] test accuracies for a stacked fleet state."""
        self._refuse_mesh("evaluate_states")
        if self._fleet_eval_fn is None:
            self._fleet_eval_fn = jax.jit(jax.vmap(self.evaluate_fn))
        return self._fleet_eval_fn(states)

    # ------------------------------------------------------------------
    # vmapped world grids: the generalization of ``run_seeds`` to the
    # world axis — stacked world pytrees (client counts, availability,
    # heterogeneity all varying) x seeds in ONE lax.scan dispatch.
    # ------------------------------------------------------------------
    def run_worlds(self, worlds: World, seeds: Any, n_rounds: int
                   ) -> Tuple[ExperimentState, Dict[str, jnp.ndarray],
                              jnp.ndarray]:
        """Run a (worlds x seeds) grid as ONE compiled dispatch.

        ``worlds`` is a World pytree whose every leaf carries a leading
        [n_worlds] axis (``repro.fl.experiments.world_fleet`` builds it
        from heterogeneous worlds by padding them to this engine's
        template shapes).  The engine supplies everything static — model
        adapters, the strategy, cohort capacity, V — so every world must
        be padded to the template's (N, V, S, cap) shapes.

        Returns (final_states, metrics, final_accs) with leading
        [n_worlds, n_seeds] axes everywhere ([n_worlds, n_seeds, n_rounds,
        S] metrics) — the paper's world-sensitivity grids (client counts x
        availability rates) at one compile per grid instead of one per
        world."""
        self._refuse_mesh("run_worlds")
        seeds = jnp.asarray(seeds, jnp.int32)
        n_rounds = int(n_rounds)
        fn = self._run_worlds_cache.get(n_rounds)
        if fn is None:
            def one(world, seed):
                st0 = self.init_state(key=jax.random.PRNGKey(seed),
                                      world=world)

                def body(st, _):
                    return self.round_step_fn(st, world)

                stf, mets = jax.lax.scan(body, st0, None, length=n_rounds)
                return stf, mets, self.evaluate_fn(stf, world)

            def grid(worlds_, seeds_):
                per_world = lambda w: jax.vmap(
                    lambda sd: one(w, sd))(seeds_)
                return jax.vmap(per_world)(worlds_)

            fn = jax.jit(grid)
            self._run_worlds_cache[n_rounds] = fn
        return fn(worlds, seeds)

    # ------------------------------------------------------------------
    @jax.named_scope("mmfl.evaluate")
    def evaluate_fn(self, state: ExperimentState,
                    world: Optional[World] = None) -> jnp.ndarray:
        """[S] test accuracies as a pure function (vmap-safe): one vmapped
        accuracy per signature group over the stacked (params, test)."""
        test = (self.world if world is None else world).test
        accs = jnp.zeros((self.S,), jnp.float32)
        for g, grp in enumerate(self.groups):
            acc_fn = self.tasks[grp[0]].model.accuracy
            if len(grp) == 1:
                sq = lambda t: jax.tree.map(lambda a: a[0], t)
                a = acc_fn(sq(state.params[g]), sq(test[g]))[None]
            else:
                a = jax.vmap(acc_fn)(state.params[g], test[g])
            accs = accs.at[np.asarray(grp)].set(
                jnp.asarray(a, jnp.float32))
        return accs

    def evaluate(self, state: ExperimentState) -> List[float]:
        with TraceAnnotation("mmfl.evaluate"):
            return [float(self.eval_jit[s](self.task_params(state, s),
                                           self.task_test(s)))
                    for s in range(self.S)]
