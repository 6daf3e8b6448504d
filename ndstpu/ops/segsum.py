"""Pallas TPU kernel: grouped aggregation as a one-hot MXU matmul.

The NDS power run's hot operator is the scan→filter→group-by spine
(SURVEY.md §3.1); its inner reduction is a masked segment-sum over a
dense, small key domain (dimension surrogate keys — items, brands,
stores).  XLA lowers ``segment_sum`` to scatter-adds; on TPU the
systolic array gives a faster formulation when the segment count is
small: a one-hot matrix product,

    partial[s] = Σ_i vals[i] · (gid[i] == s)  ==  vals @ one_hot(gid)

which runs on the MXU at matmul throughput instead of the VPU scatter
path.  The kernel tiles rows × segments on a 2-D grid, materializes the
one-hot block in VMEM, and accumulates output tiles across row blocks
(sequential TPU grid).

Two entry points:

* :func:`segment_sum_f32` — float32 data (f32 matmul accumulation).
* :func:`segment_sum_decimal` — EXACT int64 sums: values are biased to
  non-negative and split into 8-bit limbs; each limb's one-hot matmul
  stays within f32's exact-integer range (block_rows · 255 < 2^24), the
  per-limb partials accumulate in int32, and the caller-side combine
  reassembles int64 and removes the bias with the per-segment count.
  Exactness bound: rows ≤ 2^31 / 255 ≈ 8.4M per call (chunk above it).

Tests run the interpreter (CPU); the real lowering targets the MXU.
"""

from __future__ import annotations

import functools

import numpy as np

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl

_LANES = 128
# |value| must stay below the bias so biased values are non-negative
# and fit the limb planes: 2^41 cents ≈ $22B per single value
_BIAS_BITS = 41
_LIMB_BITS = 8
_N_LIMBS = 6              # biased values < 2^42; 6 limbs cover 48 bits


def _pad_to(x, mult: int, fill=0):
    n = x.shape[0]
    m = -(-max(n, 1) // mult) * mult
    if m == n:
        return x
    return jnp.concatenate([x, jnp.full((m - n,), fill, x.dtype)])


def _f32_kernel(vals_ref, gid_ref, out_ref):
    # grid = (segment blocks, row blocks): rows are the REDUCTION dim and
    # must be innermost — TPU Pallas only keeps an output block resident
    # across consecutive same-index grid steps, so accumulating across an
    # outer dim would revisit flushed blocks (wrong results on hardware).
    #
    # Formulated WITHOUT reshapes/transposes: collapsing the (sublane,
    # lane) block into one vector dim is the "unsupported shape cast"
    # Mosaic rejected.  Instead each sublane row r contributes a
    # (1, LANES) x (segs, LANES) dot_general contracting the lane dim —
    # a transposed one-hot product the MXU takes directly; the static
    # python loop unrolls over the block's sublanes.
    j = pl.program_id(0)
    i = pl.program_id(1)
    nseg = out_ref.shape[1]
    # keep index math in int32: under jax_enable_x64 the python-int
    # multiply promotes to int64 and the int64 (nseg, LANES) compare
    # crashes the Mosaic vector-layout pass (the historical
    # "unsupported shape cast" was the same class of failure)
    seg0 = (j * nseg).astype(jnp.int32)
    segs = seg0 + jax.lax.broadcasted_iota(jnp.int32, (nseg, _LANES), 0)
    acc = jnp.zeros((1, nseg), jnp.float32)
    for r in range(vals_ref.shape[0]):
        g = gid_ref[r:r + 1, :]                       # (1, LANES)
        v = vals_ref[r:r + 1, :]                      # (1, LANES)
        onehot_t = (jnp.broadcast_to(g, (nseg, _LANES)) == segs
                    ).astype(jnp.float32)             # (segs, LANES)
        # HIGHEST: the MXU's default bf16 passes would round the VALUE
        # operand (the 0/1 one-hot is bf16-exact; arbitrary f32 values
        # are not — observed ~1e-3 relative drift at default precision)
        acc = acc + jax.lax.dot_general(
            v, onehot_t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)      # (1, segs)

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += acc


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "block_rows",
                                    "block_segs", "interpret"))
def segment_sum_f32(vals: jnp.ndarray, gid: jnp.ndarray,
                    mask: jnp.ndarray, num_segments: int,
                    block_rows: int = 1024, block_segs: int = 512,
                    interpret: bool = False) -> jnp.ndarray:
    """Masked per-segment float32 sums via one-hot MXU matmuls.

    ``gid`` entries outside [0, num_segments) contribute nothing (the
    mask is folded the same way)."""
    v = jnp.where(mask, vals.astype(jnp.float32), 0.0)
    g = jnp.where(mask, gid.astype(jnp.int32), jnp.int32(-1))
    v = _pad_to(v, block_rows)
    g = _pad_to(g, block_rows, fill=-1)
    n = v.shape[0]
    s_pad = -(-max(num_segments, 1) // block_segs) * block_segs
    rows = block_rows // _LANES
    v2 = v.reshape(n // _LANES, _LANES)
    g2 = g.reshape(n // _LANES, _LANES)
    grid = (s_pad // block_segs, n // block_rows)
    # trace the kernel with x64 promotion OFF: under jax_enable_x64 the
    # pallas machinery emits int64 grid/index scalars and the Mosaic
    # vector-layout pass rejects the program (tpu_compile_helper exit 1
    # with no diagnostics); all kernel inputs are explicitly 32-bit
    with jax.enable_x64(False):
        out = pl.pallas_call(
            _f32_kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((rows, _LANES), lambda j, i: (i, 0)),
                pl.BlockSpec((rows, _LANES), lambda j, i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_segs), lambda j, i: (0, j)),
            out_shape=jax.ShapeDtypeStruct((1, s_pad), jnp.float32),
            interpret=interpret,
            name="segsum_f32",
        )(v2, g2)
    return out[0, :num_segments]


def _limb_kernel(limbs_ref, gid_ref, out_ref):
    # same grid orientation and reshape-free formulation as _f32_kernel:
    # rows (reduction) innermost; per sublane row, all limb planes at
    # once via one (nl, LANES) x (segs, LANES) lane-contracting
    # dot_general
    j = pl.program_id(0)
    i = pl.program_id(1)
    nseg = out_ref.shape[1]
    seg0 = (j * nseg).astype(jnp.int32)  # int32: see _f32_kernel note
    nl = limbs_ref.shape[0]
    segs = seg0 + jax.lax.broadcasted_iota(jnp.int32, (nseg, _LANES), 0)
    acc = jnp.zeros((nl, nseg), jnp.float32)
    for r in range(limbs_ref.shape[1]):
        g = gid_ref[r:r + 1, :]                       # (1, LANES)
        lv = limbs_ref[:, r, :]                       # (nl, LANES)
        onehot_t = (jnp.broadcast_to(g, (nseg, _LANES)) == segs
                    ).astype(jnp.float32)             # (segs, LANES)
        # default MXU precision is EXACT here: 8-bit limbs (<=255) and
        # the 0/1 one-hot are both bf16-representable, and the f32
        # accumulator stays within its exact-integer range
        acc = acc + jax.lax.dot_general(
            lv, onehot_t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # (nl, segs)

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += acc.astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "block_rows",
                                    "block_segs", "interpret"))
def segment_sum_decimal(vals: jnp.ndarray, gid: jnp.ndarray,
                        mask: jnp.ndarray, num_segments: int,
                        block_rows: int = 1024, block_segs: int = 256,
                        interpret: bool = False):
    """EXACT per-segment int64 sums + counts for scaled-decimal data.

    Returns ``(sums int64 [num_segments], counts int64 [num_segments])``.
    """
    if vals.shape[0] > (2 ** 31 - 1) // 255:
        raise ValueError("segment_sum_decimal: chunk rows above the "
                         "int32 accumulator bound")
    bias = jnp.int64(1) << _BIAS_BITS
    # enforce the documented |value| < 2^41 bound: an out-of-range input
    # would silently wrap in the limb planes; poison every sum with an
    # unmistakable sentinel instead so validation flags it immediately
    oob = jnp.any(mask & ((vals <= -bias) | (vals >= bias)))
    v = jnp.where(mask, vals.astype(jnp.int64) + bias, jnp.int64(0))
    g = jnp.where(mask, gid.astype(jnp.int32), jnp.int32(-1))
    v = _pad_to(v, block_rows)
    g = _pad_to(g, block_rows, fill=-1)
    n = v.shape[0]
    s_pad = -(-max(num_segments, 1) // block_segs) * block_segs
    rows = block_rows // _LANES
    # 8-bit limb planes (+ one plane of ones for the per-segment count)
    limbs = [((v >> (_LIMB_BITS * k)) & 0xFF).astype(jnp.float32)
             for k in range(_N_LIMBS)]
    limbs.append((v != 0).astype(jnp.float32))   # count plane
    lv = jnp.stack(limbs).reshape(_N_LIMBS + 1, n // _LANES, _LANES)
    g2 = g.reshape(n // _LANES, _LANES)
    grid = (s_pad // block_segs, n // block_rows)
    # x64 promotion off for the kernel trace — see segment_sum_f32
    with jax.enable_x64(False):
        out = pl.pallas_call(
            _limb_kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((_N_LIMBS + 1, rows, _LANES),
                             lambda j, i: (0, i, 0)),
                pl.BlockSpec((rows, _LANES), lambda j, i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((_N_LIMBS + 1, block_segs),
                                   lambda j, i: (0, j)),
            out_shape=jax.ShapeDtypeStruct((_N_LIMBS + 1, s_pad),
                                           jnp.int32),
            interpret=interpret,
            name="segsum_limb",
        )(lv, g2)
    out = out[:, :num_segments].astype(jnp.int64)
    counts = out[_N_LIMBS]
    sums = jnp.zeros(num_segments, jnp.int64)
    for k in range(_N_LIMBS):
        sums = sums + (out[k] << (_LIMB_BITS * k))
    sums = sums - counts * (jnp.int64(1) << _BIAS_BITS)
    sums = jnp.where(oob, jnp.int64(-(2 ** 62)), sums)
    return sums, counts
