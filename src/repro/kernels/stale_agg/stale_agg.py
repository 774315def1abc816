"""Pallas TPU kernel: fused stale variance-reduced aggregation (Eq. 18).

Per parameter element p the server computes

    delta[p] = stale_sum[p] + sum_c coeff_c * (G[c,p] - beta_c * h[c,p])

i.e. a C-way weighted reduction over two [C, P] streams plus one [P] stream.
Unfused, XLA materializes the [C, P] intermediate (G - beta*h) and reads
~5 P-sized tensors; the fused kernel streams G and h exactly once and writes
delta once: arithmetic intensity stays at the memory roofline minimum of
(2C+2)/(2C+2) reads+writes — this is THE paper-specific hot spot at
production scale (C x full-model-size update streams per round).

Layout (``kernels.tiling``): P is viewed as [R, 128] rows.  Grid:
(R // rb,) with the whole cohort resident per [C, rb, 128] tile;
coeff/beta are scalar-prefetched into SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import LANES, padded_rows, row_block, to_rows


def _kernel(coeff_ref, beta_ref, g_ref, h_ref, sum_ref, out_ref):
    """coeff/beta are SMEM scalars; g/h blocks are [C, rb, 128].  The
    cohort reduction is a broadcast-multiply-sum over the leading block
    dim (no 1-D matvec, which Mosaic cannot lower)."""
    def body(c, acc):
        g = g_ref[c].astype(jnp.float32)
        h = h_ref[c].astype(jnp.float32)
        return acc + coeff_ref[c] * (g - beta_ref[c] * h)

    out_ref[...] = jax.lax.fori_loop(0, g_ref.shape[0], body,
                                     sum_ref[...].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def stale_agg(coeff: jnp.ndarray, beta: jnp.ndarray, G: jnp.ndarray,
              h: jnp.ndarray, stale_sum: jnp.ndarray,
              block_p: int | None = None, interpret: bool = False
              ) -> jnp.ndarray:
    """coeff, beta: [C]; G, h: [C, P]; stale_sum: [P] -> delta [P] (f32)."""
    C, P = G.shape
    rb = row_block(C, P, G.dtype, block_p)
    rows = padded_rows(P, rb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(rows // rb,),
        in_specs=[
            pl.BlockSpec((C, rb, LANES), lambda p, *_: (0, p, 0)),
            pl.BlockSpec((C, rb, LANES), lambda p, *_: (0, p, 0)),
            pl.BlockSpec((rb, LANES), lambda p, *_: (p, 0)),
        ],
        out_specs=pl.BlockSpec((rb, LANES), lambda p, *_: (p, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
    )(coeff.astype(jnp.float32), beta.astype(jnp.float32),
      to_rows(G, rows), to_rows(h, rows), to_rows(stale_sum, rows))
    return out.reshape(-1)[:P]


# ---------------------------------------------------------------------------
# extended kernel: Eq. 18 delta + the stale-store refresh in ONE pass
# ---------------------------------------------------------------------------


def _refresh_kernel(idx_ref, coeff_ref, beta_ref, act_ref,
                    g_ref, h_ref, sum_ref, delta_ref, store_ref):
    """Grid (R//rb, C), cohort innermost.  Per (tile, cohort slot c):
    stream G[c] and the store row h[idx[c]] ONCE, accumulate the Eq. 18
    correction into the resident delta tile, and write the refreshed row
    (G if active, the unchanged h otherwise) straight back into the
    aliased store — the refresh scatter rides the same pass instead of a
    second [C, P] read + XLA scatter rebuild."""
    c = pl.program_id(1)
    g = g_ref[...].astype(jnp.float32)               # [rb, 128]
    h = h_ref[...].astype(jnp.float32)
    contrib = coeff_ref[c] * (g - beta_ref[c] * h)

    @pl.when(c == 0)
    def _init():
        delta_ref[...] = sum_ref[...].astype(jnp.float32) + contrib

    @pl.when(c > 0)
    def _accum():
        delta_ref[...] = delta_ref[...] + contrib

    store_ref[...] = jnp.where(act_ref[c] > 0, g, h).astype(store_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def stale_agg_refresh(coeff: jnp.ndarray, beta: jnp.ndarray,
                      act: jnp.ndarray, idx: jnp.ndarray, G: jnp.ndarray,
                      h: jnp.ndarray, stale_sum: jnp.ndarray,
                      block_p: int | None = None, interpret: bool = False
                      ) -> tuple:
    """Fused Eq. 18 delta + in-place stale-store refresh scatter.

    coeff, beta, act: [C]; idx: [C] int (cohort slot -> store row, DISTINCT
    rows — the engine's argsort/arange cohorts guarantee it, and duplicate
    rows would race the aliased scatter); G: [C, P]; h: [N, P] store;
    stale_sum: [P].  Returns (delta [P] f32, refreshed store [N, P]).

    The store operand is aliased to the store output
    (``input_output_aliases``), so rows outside ``idx`` are never copied:
    under the engine's donation contract the refresh is an in-place
    scatter on the live buffer (exactly in-place when P already fills
    whole tiles; otherwise the P-axis padding pays one copy, same
    convention as ``stale_agg``).  idx/coeff/beta/act are
    scalar-prefetched so the store-row DMA addresses are known before the
    tile body runs."""
    C, P = G.shape
    N = h.shape[0]
    rb = row_block(1, P, G.dtype, block_p)
    rows = padded_rows(P, rb)
    row = pl.Squeezed()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(rows // rb, C),
        in_specs=[
            pl.BlockSpec((row, rb, LANES), lambda p, c, idx, *_: (c, p, 0)),
            pl.BlockSpec((row, rb, LANES),
                         lambda p, c, idx, *_: (idx[c], p, 0)),
            pl.BlockSpec((rb, LANES), lambda p, c, idx, *_: (p, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rb, LANES), lambda p, c, idx, *_: (p, 0)),
            pl.BlockSpec((row, rb, LANES),
                         lambda p, c, idx, *_: (idx[c], p, 0)),
        ],
    )
    delta, store = pl.pallas_call(
        _refresh_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((N, rows, LANES), h.dtype)],
        # operand indices count the 4 scalar-prefetch args: G=4, h=5
        input_output_aliases={5: 1},
        interpret=interpret,
        name="stale_agg_refresh",
    )(idx.astype(jnp.int32), coeff.astype(jnp.float32),
      beta.astype(jnp.float32), act.astype(jnp.float32),
      to_rows(G, rows), to_rows(h, rows), to_rows(stale_sum, rows))
    delta = delta.reshape(-1)
    store = store.reshape(N, -1)
    if rows * LANES != P:
        return delta[:P], store[:, :P]
    return delta, store
