"""A slot pool's decode-step read of its big K/V caches, each slot to its own
length: one Pallas kernel over the live (slot, row block) pairs.

``serving/cache.SlotKVPool`` maps the model over its slots, so an attention
layer sees one lane and can bound its big-cache read only by one number for
the whole pool. The batching rule of ``models/transformer.
bounded_cache_attention`` sees every lane at once and hands this kernel the
pool's queries, caches and ring bases (each slot's own count of rows in its
big cache). The kernel then reads slot ``i``'s K and V rows up to
``lengths[i]``, rounded up to a row block, and nothing else: a slot of length
0, an idle one among them, costs no byte and no product.

- **Work list.** :func:`work_list` compacts the (slot, block) pairs with
  ``block * rows < lengths[slot]`` in slot order and counts them; the kernel
  gets both by scalar prefetch and walks ``count`` pairs in one loop, fetching
  each pair's K and V block by DMA while the pair before it computes (two
  buffers). The loop's trip count is data: one program serves every mix of
  lengths.
- **Softmax.** Online over a slot's blocks, in float32, per slot ``(m,
  total, acc)``, started from the ring and self terms as the loop it replaces
  starts (the caller computes them). A slot's last block is masked by
  ``key_pos < length``.
- **Layout.** The caches are taken the way the device keeps them: a TPU lays
  a ``(rows, 64)`` cache with its rows along the 128 lanes, so the caller
  hands such caches over as ``(h, 64, rows)`` (``turned``, a bitcast there);
  a head of 128 lies as written, ``(h, rows, 128)``. A layout the operand
  does not have would cost a copy of every cache every step.
- **Compute.** Both products are head-batched ``dot_general``s with float32
  accumulation, the query and the weights in the caches' dtype.

In the tests' interpret mode (``ops.fused_update.force_pallas_interpret``) it
runs on any backend.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_ml_pytorch_tpu.ops.fused_update import _interpret

#: the most bytes of K (or V) one pair fetches: a block is the largest whole
#: number of 128-row lane tiles that divides the allocation and stays under it.
#: Short rows favour small blocks, whole allocations large ones: on a TPU v5e,
#: gpt2-large's layer (20 x 64 heads) with 13 of 32 slots live up to 546 rows
#: read in 101 / 113 / 117 us at blocks of 128 / 256 / 512 rows, every row of
#: every slot in 327 / - / 299 us
BLOCK_BYTES = 512 * 1024


def kernel_runs_here() -> bool:
    """Whether :func:`slot_rows_attention` can run in this process: on a TPU,
    or anywhere in the tests' interpret mode (``force_pallas_interpret``)."""
    return jax.default_backend() == "tpu" or _interpret()


def kv_block_rows(rows: int, heads: int, head_dim: int, itemsize: int) -> int:
    """Rows of a big cache one (slot, block) pair reads: the largest multiple
    of 128 that divides ``rows`` and whose K block of ``heads x head_dim``
    values of ``itemsize`` bytes stays within :data:`BLOCK_BYTES`, else the
    smallest such multiple; an allocation no multiple of 128 divides is one
    block."""
    tiles = [r for r in range(128, rows + 1, 128) if rows % r == 0]
    if not tiles:
        return rows
    fit = [r for r in tiles if r * heads * head_dim * itemsize <= BLOCK_BYTES]
    return max(fit) if fit else tiles[0]


def work_list(lengths, block_rows: int, rows: int):
    """``(count [1], slot [P], block [P])`` int32 for ``lengths`` ``[N]``:
    the pairs ``(slot, block)`` with ``block * block_rows < lengths[slot]``,
    slot by slot, first in the lists; ``P = N * rows / block_rows``, and the
    entries past ``count`` are the last slot's and never read."""
    n = lengths.shape[0]
    blocks = -(-jnp.minimum(lengths, rows) // block_rows)
    ends = jnp.cumsum(blocks)
    p = jnp.arange(n * (rows // block_rows), dtype=jnp.int32)
    slot = jnp.minimum(jnp.sum(p[:, None] >= ends[None, :], axis=1), n - 1)
    return (ends[-1:].astype(jnp.int32), slot.astype(jnp.int32),
            (p - (ends - blocks)[slot]).astype(jnp.int32))


def _kernel(count_ref, slot_ref, block_ref, len_ref, scale_ref,
            q_ref, m_ref, l_ref, acc_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sem, m_s, l_s, acc_s, *, block_rows, turned):
    R = block_rows
    h, d = q_ref.shape[1:]
    m_s[...] = m_ref[...]
    l_s[...] = l_ref[...]
    acc_s[...] = acc_ref[...]
    count = count_ref[0]

    def fetch(p, buf):
        i = slot_ref[p]
        rows = pl.ds(pl.multiple_of(block_ref[p] * R, R), R)
        window = (lambda c: c.at[i, :, :, rows]) if turned else (lambda c: c.at[i, :, rows, :])
        return [pltpu.make_async_copy(window(src), dst.at[buf], sem.at[n, buf])
                for n, (src, dst) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf)))]

    @pl.when(count > 0)
    def _():
        for copy in fetch(0, 0):
            copy.start()

    def pair(p, carry):
        buf = p % 2

        @pl.when(p + 1 < count)
        def _():
            for copy in fetch(p + 1, 1 - buf):
                copy.start()

        for copy in fetch(p, buf):
            copy.wait()
        i = slot_ref[p]
        # heads are the products' batch and the one query row their rows:
        # (h, 1, d) x (h, d, R) or (h, R, d) -> (h, 1, R), the row summed away
        q = jax.lax.broadcast_in_dim(q_ref[i], (h, 1, d), (0, 2)).astype(kbuf.dtype)
        s = jnp.sum(jax.lax.dot_general(
            q, kbuf[buf], (((2,), (1 if turned else 2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32), axis=1)
        key_pos = block_ref[p] * R + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(key_pos < len_ref[i], s / scale_ref[0], -jnp.inf)
        m_old = m_s[i]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        shrink = jnp.exp(m_old - m_new)
        w = jnp.exp(s - m_new)
        l_s[i] = l_s[i] * shrink + jnp.sum(w, axis=1, keepdims=True)
        w = jax.lax.broadcast_in_dim(w, (h, 1, R), (0, 2)).astype(vbuf.dtype)
        acc_s[i] = acc_s[i] * shrink + jnp.sum(jax.lax.dot_general(
            w, vbuf[buf], (((2,), (2 if turned else 1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32), axis=1)
        m_s[i] = m_new
        return carry

    jax.lax.fori_loop(0, count, pair, 0)
    o_ref[...] = acc_s[...] / l_s[...]


def slot_rows_attention(q, m, total, acc, lengths, cache_k, cache_v, scale, *,
                        turned: bool):
    """Each slot's attention over its own big-cache rows, continued from the
    softmax state ``(m, total, acc)`` of the rows it has already seen.

    ``q`` ``[N, h, d]``, rounded to the caches' dtype for the products; ``m``, ``total`` ``[N, h]`` and
    ``acc`` ``[N, h, d]`` float32; ``lengths`` ``[N]``: rows of slot ``i``'s
    big cache that are its; ``cache_k``, ``cache_v`` ``[N, h, rows, d]``, or
    ``[N, h, d, rows]`` with ``turned``; ``scale``: what a score is divided
    by. Returns ``acc / total`` after the rows, float32 ``[N, h, d]``."""
    N, h, d = q.shape
    rows = cache_k.shape[3 if turned else 2]
    R = kv_block_rows(rows, h, d, cache_k.dtype.itemsize)
    count, slot, block = work_list(lengths.astype(jnp.int32), R, rows)
    block_shape = (h, d, R) if turned else (h, R, d)
    f32 = jnp.float32
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        partial(_kernel, block_rows=R, turned=turned),
        out_shape=jax.ShapeDtypeStruct((N, h, d), f32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), vmem, vmem, vmem, vmem,
                      pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((2,) + block_shape, cache_k.dtype),
                pltpu.VMEM((2,) + block_shape, cache_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((N, h, 1), f32), pltpu.VMEM((N, h, 1), f32),
                pltpu.VMEM((N, h, d), f32)]),
        interpret=_interpret(),
    )(count, slot, block, lengths.astype(jnp.int32),
      jnp.reshape(scale, (1,)).astype(f32), q.astype(f32),
      m[..., None].astype(f32), total[..., None].astype(f32), acc.astype(f32),
      cache_k, cache_v)
