"""Per-architecture smoke tests: REDUCED variant of each assigned family runs
one forward/train step + prefill/decode on CPU with finite outputs and the
analytic param count matching the actual init."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS, get_config
from repro.models import cnn, transformer

ARCH_IDS = sorted(ARCHS)


def _batch(cfg, key, B=2, S=32):
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    if cfg.n_frontend_tokens:
        batch["frontend"] = jax.random.normal(
            key, (B, cfg.n_frontend_tokens, cfg.frontend_dim))
    return batch


@pytest.fixture(scope="module")
def rng():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_forward_and_param_count(arch, rng):
    cfg = get_config(arch).reduced()
    assert cfg.n_layers == 2 and cfg.d_model <= 512
    if cfg.n_experts:
        assert cfg.n_experts <= 4
    params = transformer.init(rng, cfg)
    n_actual = sum(x.size for x in jax.tree.leaves(params))
    assert n_actual == cfg.param_count()
    batch = _batch(cfg, rng)
    loss, aux = jax.jit(
        lambda p, b: transformer.forward(p, cfg, b, q_chunk=16))(params, batch)
    assert np.isfinite(float(loss))
    assert float(loss) > 0


@pytest.mark.slow
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_train_step(arch, rng):
    """One SGD step decreases nothing catastrophic: loss stays finite and
    grads are finite."""
    cfg = get_config(arch).reduced()
    params = transformer.init(rng, cfg)
    batch = _batch(cfg, rng)

    def loss_fn(p):
        return transformer.forward(p, cfg, batch, q_chunk=16)[0]

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    for g in jax.tree.leaves(grads):
        assert np.all(np.isfinite(np.asarray(g, np.float32)))
    new_params = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)
    loss2 = jax.jit(loss_fn)(new_params)
    assert np.isfinite(float(loss2))


@pytest.mark.slow
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_prefill_decode_roundtrip(arch, rng):
    cfg = get_config(arch).reduced()
    params = transformer.init(rng, cfg)
    B, S = 2, 32
    batch = _batch(cfg, rng, B, S)
    logits, caches = jax.jit(
        lambda p, b: transformer.prefill(p, cfg, b, q_chunk=16))(params, batch)
    assert logits.shape == (B, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))
    ids = jnp.argmax(logits, -1).astype(jnp.int32)
    pos = jnp.int32(S + cfg.n_frontend_tokens)
    for _ in range(3):
        logits, caches = jax.jit(
            lambda p, i, c, t: transformer.decode_step(p, cfg, i, c, t))(
                params, ids, caches, pos)
        assert logits.shape == (B, cfg.vocab_size)
        assert np.all(np.isfinite(np.asarray(logits, np.float32)))
        ids = jnp.argmax(logits, -1).astype(jnp.int32)
        pos = pos + 1


def test_decode_matches_prefill_continuation():
    """Greedy decode after prefill == forward over the extended sequence
    (consistency of the cache path), checked on a dense arch."""
    cfg = get_config("internlm2-1.8b").reduced()
    key = jax.random.PRNGKey(0)
    params = transformer.init(key, cfg)
    B, S = 1, 16
    toks = jax.random.randint(key, (B, S + 1), 0, cfg.vocab_size)
    batch_s = {"tokens": toks[:, :S]}
    batch_s1 = {"tokens": toks}
    logits_p, caches = transformer.prefill(params, cfg, batch_s, q_chunk=16,
                                           cache_len=S + 4)
    logits_d, _ = transformer.decode_step(params, cfg, toks[:, S], caches,
                                          jnp.int32(S))
    logits_full, _ = transformer.prefill(params, cfg, batch_s1, q_chunk=17)
    np.testing.assert_allclose(np.asarray(logits_d), np.asarray(logits_full),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.slow
def test_sliding_window_decode_ring_buffer():
    """Ring-buffer SWA decode stays finite once position wraps the window."""
    import dataclasses
    cfg = dataclasses.replace(get_config("qwen3-0.6b").reduced(),
                              sliding_window=8)
    key = jax.random.PRNGKey(1)
    params = transformer.init(key, cfg)
    B = 2
    caches = transformer.init_caches(cfg, B, 1024, jnp.float32, window=8)
    ids = jnp.zeros((B,), jnp.int32)
    for t in range(20):   # wraps the 8-slot ring twice
        logits, caches = transformer.decode_step(params, cfg, ids, caches,
                                                 jnp.int32(t))
        assert np.all(np.isfinite(np.asarray(logits, np.float32)))
        ids = jnp.argmax(logits, -1).astype(jnp.int32)


@pytest.mark.slow
def test_int8_kv_cache_decode_close_to_bf16():
    """Perf-3 path: int8 KV cache decode stays within 1% of full precision
    and argmax-agrees over several steps."""
    cfg = get_config("qwen3-0.6b").reduced()
    key = jax.random.PRNGKey(7)
    params = transformer.init(key, cfg)
    B = 2
    c_f = transformer.init_caches(cfg, B, 64, jnp.float32)
    c_q = transformer.init_caches(cfg, B, 64, jnp.float32, kv_quant=True)
    idf = idq = jnp.zeros((B,), jnp.int32)
    for t in range(5):
        lf, c_f = transformer.decode_step(params, cfg, idf, c_f, jnp.int32(t))
        lq, c_q = transformer.decode_step(params, cfg, idq, c_q, jnp.int32(t))
        rel = (np.abs(np.asarray(lf) - np.asarray(lq)).max()
               / (np.abs(np.asarray(lf)).max() + 1e-9))
        assert rel < 0.02, rel
        assert np.array_equal(np.asarray(jnp.argmax(lf, -1)),
                              np.asarray(jnp.argmax(lq, -1)))
        idf = jnp.argmax(lf, -1).astype(jnp.int32)
        idq = jnp.argmax(lq, -1).astype(jnp.int32)


def test_moe_load_balance_aux():
    cfg = get_config("llama4-scout-17b-a16e").reduced()
    key = jax.random.PRNGKey(2)
    params = transformer.init(key, cfg)
    batch = {"tokens": jax.random.randint(key, (2, 32), 0, cfg.vocab_size)}
    loss, aux = transformer.forward(params, cfg, batch, q_chunk=16)
    # aux = E * sum(me*ce) >= 1 (perfectly balanced) per layer, summed over L
    assert float(aux) >= 0.9 * cfg.n_layers


# ---------------------------------------------------------------------------
# init determinism + forward-shape contracts (the engine relies on both:
# per-task init keys come from split/fold_in of one seed, and fusion
# stacks same-arch params along a leading task axis)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_init_deterministic_under_index_keys(arch, rng):
    """init must be a pure function of (key, cfg): the same fold_in-derived
    key reproduces params bitwise, a different index gives different params
    with the SAME tree structure (the stacking contract for task fusion)."""
    cfg = get_config(arch).reduced()
    k0, k1 = jax.random.fold_in(rng, 0), jax.random.fold_in(rng, 1)
    p_a = transformer.init(k0, cfg)
    p_b = transformer.init(k0, cfg)
    for la, lb in zip(jax.tree.leaves(p_a), jax.tree.leaves(p_b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    p_c = transformer.init(k1, cfg)
    assert jax.tree.structure(p_a) == jax.tree.structure(p_c)
    assert any(
        not np.array_equal(np.asarray(la), np.asarray(lc))
        for la, lc in zip(jax.tree.leaves(p_a), jax.tree.leaves(p_c)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_logits_shape_contract(arch, rng):
    """``transformer.logits`` covers exactly the token positions for every
    registry entry: [B, S, vocab_size], frontend positions sliced off."""
    cfg = get_config(arch).reduced()
    params = transformer.init(rng, cfg)
    B, S = 2, 16
    batch = _batch(cfg, rng, B, S)
    lg = jax.jit(
        lambda p, b: transformer.logits(p, cfg, b, q_chunk=16))(params, batch)
    assert lg.shape == (B, S, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(lg, np.float32)))


# ---------------------------------------------------------------------------
# the CNN's max-pool, differentiated as a reshape-and-max, pinned against
# reduce_window's VALID 2x2 stride-2 max and that max's own derivative
# ---------------------------------------------------------------------------


def _pool_rw(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def _pool_case_vmapped_grad():
    """A ReLU'd conv of per-task x per-client params (cnn3-like, small),
    pooled under the engine's vmap(vmap(value_and_grad)): the pooled values
    and the gradient that reaches the conv's output.  (The parameter
    gradients sum that one further, and XLA may order those sums
    differently around either form.)"""
    T, A, B = 2, 3, 4
    keys = jax.random.split(jax.random.PRNGKey(3), T * A).reshape(T, A, -1)
    params = jax.vmap(jax.vmap(lambda k: cnn.init(k, 10, 8)["conv1"]))(keys)
    x = jax.random.normal(jax.random.PRNGKey(4), (T, A, B, 28, 28, 1))
    z = jax.vmap(jax.vmap(cnn._conv))(params, x)
    wts = jnp.cos(jnp.arange(B * 14 * 14 * 8.0)).reshape(B, 14, 14, 8)

    def run(pool):
        def loss(zc):
            h = pool(jax.nn.relu(zc))
            return jnp.vdot(h, wts), h
        (_, h), g = jax.jit(jax.vmap(jax.vmap(
            jax.value_and_grad(loss, has_aux=True))))(z)
        return h, g

    return run(cnn._pool), run(_pool_rw)


def _pool_case_odd_side():
    """7x7 -> 3x3: the last row and column are dropped, as VALID does."""
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 7, 7, 3))

    def run(pool):
        y, vjp = jax.vjp(pool, x)
        assert y.shape == (2, 3, 3, 3)
        return y, vjp(jnp.ones_like(y))

    return run(cnn._pool), run(_pool_rw)


def _pool_case_zero_window():
    """A window that ReLU zeroes entirely pools to 0 with a zero gradient."""
    z = jax.random.uniform(jax.random.PRNGKey(6), (1, 4, 4, 2),
                           minval=0.1, maxval=1.0)
    z = z.at[0, :2, :2, :].set(-1.0)

    def run(pool):
        y, g = jax.value_and_grad(
            lambda a: jnp.sum(pool(jax.nn.relu(a))))(z)
        out = pool(jax.nn.relu(z))
        assert np.all(np.asarray(out[0, 0, 0]) == 0.0)
        assert np.all(np.asarray(g[0, :2, :2]) == 0.0)
        return out, (y, g)

    return run(cnn._pool), run(_pool_rw)


@pytest.mark.parametrize("case", [_pool_case_vmapped_grad,
                                  _pool_case_odd_side,
                                  _pool_case_zero_window],
                         ids=["vmapped_grad", "odd_side", "zero_window"])
def test_cnn_pool_matches_reduce_window(case):
    got, want = case()
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
