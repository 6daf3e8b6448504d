"""Pallas TPU kernel: which of K unique keys, if any, is each row's?

A lookup join against a small filtered dimension needs, for every probe
row, the build row whose key equals the probe key.  For few build keys
that is K compares a row on the vector unit, not a random access:

    out[i] = rows[k]  where keys[k] == x[i],  else -1

The kernel tiles the probe keys over a 1-D grid; ``keys`` and ``rows``
sit in SMEM (scalar prefetch) and each step broadcasts one scalar pair
against a tile of probe keys held in vector registers: one compare and
one select a (probe key, build key) pair.  ``keys`` must be unique among
the values any ``x`` can take (unused slots hold a value no ``x`` has).

Tests run the interpreter (CPU); the real lowering targets the VPU.
"""

from __future__ import annotations

import functools

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# TPU v5 lite, 4 Mi probe keys x 2048 keys (PR 29's chip run): 3.72 ms
# with these, 4.21 with 512 / 64 / 8, 5.51 with 512 / 32 / 8
_BLOCK_ROWS = 2048        # rows of 128 probe keys a grid step
_TILE_ROWS = 64           # of them held in registers against all keys
_UNROLL = 16


def _round_up(a: int, b: int) -> int:
    return -(-a // b) * b


def _kernel(keys_ref, rows_ref, x_ref, out_ref, *, n_keys: int, tile: int):
    def one_tile(t, carry):
        r0 = pl.multiple_of(t * tile, tile)
        x = x_ref[pl.ds(r0, tile), :]

        def some_keys(j, acc):
            for u in range(_UNROLL):
                k = j * _UNROLL + u
                acc = jnp.where(x == keys_ref[k], rows_ref[k], acc)
            return acc

        out_ref[pl.ds(r0, tile), :] = jax.lax.fori_loop(
            0, n_keys // _UNROLL, some_keys,
            jnp.full((tile, _LANES), -1, jnp.int32))
        return carry

    jax.lax.fori_loop(0, x_ref.shape[0] // tile, one_tile, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def match_rows(x: jnp.ndarray, keys: jnp.ndarray, rows: jnp.ndarray,
               interpret: bool = False) -> jnp.ndarray:
    """``rows[k]`` where ``keys[k] == x[i]``, else -1, for every ``i``.

    All int32; any ``len(x)`` (a capacity that is no power of two, as a
    UNION ALL's, is padded with -1 to whole blocks of near-equal size
    and the result cut back); ``len(keys)`` a multiple of 16 (the alive
    build keys' capacity is a power of two from 256 up)."""
    n_x, n_keys = x.shape[0], keys.shape[0]
    if n_keys % _UNROLL:
        raise ValueError(f"match_rows: {n_keys} keys")
    rows_x = max(-(-n_x // _LANES), 1)      # rows of 128 probe keys
    tile = min(_TILE_ROWS, _round_up(rows_x, 8))
    steps = -(-rows_x // _BLOCK_ROWS)
    block = _round_up(-(-rows_x // steps), tile)
    n = steps * block * _LANES
    x = jnp.pad(x.astype(jnp.int32), (0, n - n_x), constant_values=-1)
    # x64 promotion off while the kernel is traced (see segsum.py)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            functools.partial(_kernel, n_keys=n_keys, tile=tile),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(steps,),
                in_specs=[pl.BlockSpec((block, _LANES),
                                       lambda i, keys, rows: (i, 0))],
                out_specs=pl.BlockSpec((block, _LANES),
                                       lambda i, keys, rows: (i, 0)),
            ),
            out_shape=jax.ShapeDtypeStruct((n // _LANES, _LANES),
                                           jnp.int32),
            interpret=interpret,
            name="keycmp",
        )(keys.astype(jnp.int32), rows.astype(jnp.int32),
          x.reshape(n // _LANES, _LANES))
    return out.reshape(n)[:n_x]
