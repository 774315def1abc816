"""Every Pallas kernel of the main path, compiled by Mosaic for a described
TPU v5e chip at main-path widths — no chip needed, nothing runs.

Interpret mode (tests/test_kernels.py) checks the numbers but not what the
TPU compiler accepts: block shapes off the (8, 128) tiling, 1-D matvecs
and layouts that differ from XLA's are refused only here.  Each case
asserts the compiled program holds the kernel as a ``tpu_custom_call``.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library, and under several test workers only
the worker given this file loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.batched_dot.batched_dot import batched_dot
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.selective_scan.selective_scan import selective_scan
from repro.kernels.stale_agg.stale_agg import stale_agg, stale_agg_refresh
from repro.models import cnn

# cohort / store rows of the engine's Sec. 6.1 world; P=1_000_003 is a
# ~1e6-parameter model whose flattened size is not a multiple of 128
C, N = 8, 120
P_LARGE = 1_000_003


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _cnn_p() -> int:
    """Flattened parameter count of one Sec. 6.1 CNN task."""
    shapes = jax.eval_shape(lambda k: cnn.init(k, 10, 8),
                            jax.random.PRNGKey(0))
    return sum(x.size for x in jax.tree.leaves(shapes))


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sds(chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


@pytest.mark.parametrize("width", ["cnn", "large"])
@pytest.mark.parametrize("kernel", ["batched_dot", "stale_agg",
                                    "stale_agg_refresh"])
def test_aggregation_kernels_compile(one_chip, kernel, width):
    P = _cnn_p() if width == "cnn" else P_LARGE
    s = lambda *shape, dtype=jnp.float32: _sds(one_chip, shape, dtype)
    if kernel == "batched_dot":
        text = _compiled_text(batched_dot, s(C, P), s(C, P))
    elif kernel == "stale_agg":
        text = _compiled_text(stale_agg, s(C), s(C), s(C, P), s(C, P), s(P))
    else:
        text = _compiled_text(stale_agg_refresh, s(C), s(C), s(C),
                              s(C, dtype=jnp.int32), s(C, P), s(N, P), s(P))
    assert "tpu_custom_call" in text


def test_selective_scan_compiles(one_chip):
    """falcon-mamba width: d_inner 8192, state 16."""
    B, S, di, n = 1, 128, 8192, 16
    s = lambda *shape: _sds(one_chip, shape)
    text = _compiled_text(selective_scan, s(B, S, di), s(B, S, di),
                          s(B, S, n), s(B, S, n), s(di, n), s(di))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape,dtype", [((4, 16, 128, 128), jnp.float32),
                                         ((2, 16, 1024, 128), jnp.bfloat16)])
def test_flash_attention_compiles(one_chip, shape, dtype):
    """qwen3-0.6b width: 16 query heads of head_dim 128."""
    x = _sds(one_chip, shape, dtype)
    text = _compiled_text(flash_attention, x, x, x)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("program", ["local_sgd", "loss_probe"])
def test_cnn_pool_lowering(one_chip, program):
    """The Sec. 6.1 CNN's max-pool as the engine runs it.  Local training
    (3 tasks x 24 cohort slots, 5 SGD steps of 16 images, per-client params)
    must not lower it to a lane-strided ``reduce-window`` nor its backward to
    a ``select-and-scatter``; the forward-only loss probe (3 tasks x 120
    clients x 64 rows, shared params) keeps the ``reduce-window`` fused into
    conv1's output, where a reshape-and-max would relayout the activation."""
    T = 3
    params = jax.eval_shape(
        jax.vmap(lambda k: cnn.init(k, 10, 8)),
        jax.random.split(jax.random.PRNGKey(0), T))
    params = jax.tree.map(lambda a: _sds(one_chip, a.shape), params)
    if program == "local_sgd":
        A, E, B = 24, 5, 16

        def local_sgd(p0, x, y):
            def step(p, b):
                g = jax.grad(cnn.loss_fn)(p, {"x": b[0], "y": b[1]})
                return jax.tree.map(lambda a, d: a - 0.05 * d, p, g), None
            return jax.lax.scan(step, p0, (x, y))[0]

        fn = jax.vmap(jax.vmap(local_sgd, in_axes=(None, 0, 0)))
        lead = (T, A, E, B)
    else:
        N, R = 120, 64
        fn = jax.vmap(jax.vmap(
            lambda p, x, y: cnn.loss_fn(p, {"x": x, "y": y}),
            in_axes=(None, 0, 0)))
        lead = (T, N, R)
    text = _compiled_text(fn, params,
                          _sds(one_chip, lead + (28, 28, 1)),
                          _sds(one_chip, lead, jnp.int32))
    assert ("reduce-window" in text) == (program == "loss_probe")
    assert "select-and-scatter" not in text
