"""Pallas TPU kernel: per-client inner products for the StaleVR beta.

beta*_c = <G_c, h_c> / ||h_c||^2  (Thm 3, Eq. 20) needs, per cohort client c,
two reductions over the full flattened parameter vector (size P ~ 1e9 at
production scale).  This is a memory-bound streaming reduction over two
P-sized operands — the exact hot spot the paper's aggregation adds on top of
vanilla FedAvg.  The kernel tiles P into VMEM-resident blocks and accumulates
both reductions in a single pass over HBM (2 reads/element instead of 4 for
the two separate jnp reductions).

Layout (``kernels.tiling``): G, h are viewed as [C, R, 128]; a block holds
the whole cohort and ``rb`` rows.  Grid: (R // rb,), sequential; each step
folds its rows into lane-dense [C, 8, 128] partial sums that stay resident
in the output block, and the wrapper reduces the last (8, 128) tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import LANES, padded_rows, row_block, to_rows


def _fold(x: jnp.ndarray) -> jnp.ndarray:
    """[C, rb, 128] -> [C, 8, 128] by summing whole (8, 128) tiles."""
    C, rb, _ = x.shape
    return jnp.sum(x.reshape(C, rb // 8, 8, LANES), axis=1)


def _kernel(g_ref, h_ref, dot_ref, nrm_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dot_ref[...] = jnp.zeros_like(dot_ref)
        nrm_ref[...] = jnp.zeros_like(nrm_ref)

    g = g_ref[...].astype(jnp.float32)
    h = h_ref[...].astype(jnp.float32)
    dot_ref[...] += _fold(g * h)
    nrm_ref[...] += _fold(h * h)


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def batched_dot(G: jnp.ndarray, h: jnp.ndarray, block_p: int | None = None,
                interpret: bool = False):
    """G, h: [C, P] -> (dots [C], norms [C]) in float32.

    P is padded to whole tiles with zeros (no effect on sums)."""
    C, P = G.shape
    rb = row_block(C, P, G.dtype, block_p)
    rows = padded_rows(P, rb)
    spec = pl.BlockSpec((C, rb, LANES), lambda p: (0, p, 0))
    acc = pl.BlockSpec((C, 8, LANES), lambda p: (0, 0, 0))
    dots, norms = pl.pallas_call(
        _kernel,
        grid=(rows // rb,),
        in_specs=[spec, spec],
        out_specs=[acc, acc],
        out_shape=[jax.ShapeDtypeStruct((C, 8, LANES), jnp.float32)] * 2,
        interpret=interpret,
        name="batched_dot",
    )(to_rows(G, rows), to_rows(h, rows))
    return jnp.sum(dots, axis=(1, 2)), jnp.sum(norms, axis=(1, 2))
