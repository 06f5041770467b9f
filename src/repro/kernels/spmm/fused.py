"""Fused gather+SpMM: features stream through VMEM once (DESIGN.md §14).

The unfused pipeline (``ref.spmm_bcsr_ref``) materializes the gathered
operand ``x[tile_cols]`` — an (R, K, B, F) array, K× the size of the batch
feature matrix — before a single multiply runs. That is exactly the access
pattern DGL fuses in ``gather_mm.cu``: the gather is an *address
computation*, not a tensor, so fuse it into the SpMM's operand fetch.

Two implementations share this contract, ``out = A @ x`` over padded
block-CSR tiles, without ever materializing the gathered matrix:

* ``spmm_bcsr_fused_pallas`` — the TPU kernel. ``x`` and the value tiles
  stay in HBM (``memory_space=ANY``); the kernel loops over a row-tile's
  K column tiles, issuing an explicit ``make_async_copy`` per (B, B)
  value tile and per (B, BF) feature stripe into double-buffered VMEM
  scratch, overlapping the next tile's DMAs with the current MXU ``dot``.
  Each feature stripe crosses VMEM exactly once per consuming tile; the
  (R, K, B, F) intermediate never exists. Tested in interpret mode on the
  CPU and compiled for a described TPU v5e in ``tests/test_tpu_compile.py``.

* ``spmm_bcsr_stream`` — the compiled off-TPU production path: a
  ``lax.scan`` over tile slots whose carry is the (R, B, F) accumulator.
  Per step it gathers ONE (R, B, F) operand slice and contracts it — peak
  memory O(R·B·F) instead of O(R·K·B·F), and it is ordinary XLA, so it
  jits fast, runs at compiled speed (the previous CPU fallback ran the
  Pallas kernel in *interpret* mode — the reason bcsr lost to segment),
  and partitions cleanly inside ``shard_map`` bodies (DESIGN.md §9).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def spmm_bcsr_stream(tile_cols: jnp.ndarray, tile_vals: jnp.ndarray,
                     x: jnp.ndarray) -> jnp.ndarray:
    """out = A @ x, streaming one tile slot at a time.

    tile_cols: (R, K) int32; tile_vals: (R, K, B, B); x: (C·B, F).
    Returns (R·B, F). Bitwise-deterministic: the K slots accumulate in
    slot order, matching the Pallas kernels' innermost-K accumulation.
    """
    r, k, b, _ = tile_vals.shape
    f = x.shape[1]
    # device arrays throughout: callers outside jit hand in host numpy, and
    # the scan body fancy-indexes xt with a traced carry index
    tile_cols, tile_vals = jnp.asarray(tile_cols), jnp.asarray(tile_vals)
    xt = jnp.asarray(x).reshape(-1, b, f)           # (C, B, F) view

    def step(acc, slot):
        cols_k, vals_k = slot                       # (R,), (R, B, B)
        acc = acc + jnp.einsum("rij,rjf->rif", vals_k, xt[cols_k],
                               preferred_element_type=acc.dtype)
        return acc, None

    init = jnp.zeros((r, b, f), x.dtype)
    acc, _ = jax.lax.scan(
        step, init, (tile_cols.T, jnp.swapaxes(tile_vals, 0, 1)))
    return acc.reshape(r * b, f)


# Mosaic tiles the last dim of a VMEM/HBM slice in 128 lanes: a DMA stripe
# narrower than that (or not a multiple of it) is refused at compile time.
LANES = 128


def fused_vmem_bytes(block: int, block_f: int, k: int) -> int:
    """VMEM the fused kernel holds for one grid step, in bytes: ``nbuf``
    (B, B) value tiles and ``nbuf`` (B, BF) x stripes of DMA scratch, the
    (B, BF) output block that the pipeline double-buffers, and the (B, BF)
    float32 accumulator. It does not grow with K: value tiles stream
    through the same double buffer as the x stripes."""
    nbuf = 2 if k > 1 else 1
    return 4 * (nbuf * block * block + (nbuf + 2 + 1) * block * block_f)


def _fused_kernel(k, b, bf, nbuf,
                  cols_ref, vals_any, x_any, out_ref, vbuf, xbuf, sem):
    ri = pl.program_id(0)
    fi = pl.program_id(1)

    def tile_copies(ki, slot):
        # the gather, fused: indexed DMAs of value tile (ri, ki) and of x's
        # (B, BF) stripe for column tile cols[ri, ki], HBM → VMEM scratch
        c = cols_ref[ri, ki]
        return (pltpu.make_async_copy(vals_any.at[ri, ki], vbuf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(
                    x_any.at[pl.ds(c * b, b), pl.ds(fi * bf, bf)],
                    xbuf.at[slot], sem.at[1, slot]))

    for cp in tile_copies(0, 0):
        cp.start()

    def body(ki, acc):
        slot = jax.lax.rem(ki, nbuf)

        @pl.when(ki + 1 < k)
        def _prefetch():                 # overlap next DMAs with this dot
            for cp in tile_copies(ki + 1, jax.lax.rem(ki + 1, nbuf)):
                cp.start()

        for cp in tile_copies(ki, slot):
            cp.wait()
        return acc + jnp.dot(vbuf[slot], xbuf[slot],
                             preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(0, k, body, jnp.zeros((b, bf), jnp.float32))
    out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_f", "interpret"))
def spmm_bcsr_fused_pallas(tile_cols: jnp.ndarray, tile_vals: jnp.ndarray,
                           x: jnp.ndarray, block_f: int = 256,
                           interpret: bool = False) -> jnp.ndarray:
    """tile_cols (R, K) int32; tile_vals (R, K, B, B); x (C·B, F) → (R·B, F).

    Grid (R, F/BF): one kernel invocation owns one (B, BF) output block and
    loops K internally, so the output block is written once. Both ``vals``
    and ``x`` stay in HBM; each K step fetches its (B, B) value tile and
    the (B, BF) x stripe it multiplies by explicit double-buffered DMA —
    the fused-gather contract — so VMEM use is independent of K
    (``fused_vmem_bytes``). A feature width that is not a multiple of
    ``LANES`` is zero-padded to one and sliced back off the result.
    """
    r, k, b, _ = tile_vals.shape
    f = x.shape[1]
    fp = -(-f // LANES) * LANES
    bf = min(block_f, fp)
    if fp % bf:
        raise ValueError(f"padded feature dim {fp} not divisible by "
                         f"block_f {bf}")
    if fp != f:
        x = jnp.pad(x, ((0, 0), (0, fp - f)))
    nbuf = 2 if k > 1 else 1

    out = pl.pallas_call(
        functools.partial(_fused_kernel, k, b, bf, nbuf),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r, fp // bf),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((b, bf), lambda ri, fi, cols: (ri, fi)),
            scratch_shapes=[pltpu.VMEM((nbuf, b, b), tile_vals.dtype),
                            pltpu.VMEM((nbuf, b, bf), x.dtype),
                            pltpu.SemaphoreType.DMA((2, nbuf))],
        ),
        out_shape=jax.ShapeDtypeStruct((r * b, fp), x.dtype),
        interpret=interpret,
    )(tile_cols, tile_vals, x)
    return out[:, :f] if fp != f else out
