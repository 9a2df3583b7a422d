"""Slot-based cache pool — the serving data plane.

``models/generate.py`` decodes ONE prompt batch: every sequence starts
together, shares one cursor, and the whole cache dies with the call. A
serving engine needs the opposite: S long-lived cache *slots*, each holding
an independent sequence at its own length, all advanced by one compiled
decode step per token. This module provides that pool by **vmapping the
existing ring-buffered blocked decode module over the slot axis**:

- every slot is a batch-1 instance of the exact cache ``generate()`` uses
  (big per-layer K/V + per-layer ring + cursor/ring_base), stacked to a
  leading ``(slots, ...)`` axis;
- under ``jax.vmap`` the per-layer ``cursor``/``ring_base`` scalars become
  per-slot vectors — which is precisely the per-slot live-length tracking a
  heterogeneous batch needs. The attention module needed one change for
  it: a single-token step appends its K/V row to the ring with a select
  over the ring's rows, because ``dynamic_update_slice`` at a per-slot
  offset is a scatter, which the TPU compiler runs as a loop over slots;
- decode steps write each slot's ring; once per block the rings merge into
  the big caches at PER-SLOT offsets (``merge_ring_caches`` vmapped with a
  traced ``live``), and ``ring_base`` advances — the same amortization
  that removed the full-cache copies from the decode scan (DESIGN.md §5b);
- a step reads the K/V rows each slot holds, not the allocation: the
  attention's read (``models/transformer.bounded_cache_attention``) has a
  batching rule of its own, so under this pool's ``vmap`` it is handed every
  lane's ``ring_base`` at once and reads slot ``i``'s big cache as far as
  ``ring_base[i]``, in blocks of ``slot_block_rows`` rows, by one kernel for
  the pool (``ops/slot_attention``); an idle slot, whose ``ring_base`` is 0,
  reads nothing. ``_decode_block_jit`` still takes the longest ACTIVE
  ``ring_base`` once a block and hands it to every attention layer as
  ``kv_read/rows`` (one scalar for the pool, closed over by the vmapped
  step): the latent read, int8 caches and any read off a TPU stop there, in
  whole quarters of ``cache_size`` and every slot alike, by a loop whose trip
  count is that bound (``last_read_rows`` counts it). Kernel and loop are
  each in the program once, with a work list or a trip count that is data:
  one program serves every mix of lengths.
- a step computes nothing for a slot that is nobody's where a module can
  tell: the same read-only collection carries ``kv_read/live``, one boolean a
  LANE (the block's ``active``, an argument of the vmapped step), to every
  module that declared ``prefill_len``, i.e. one that no mask shields from
  rows that are not real. Under ``vmap`` a module sees one lane; one that
  must see the pool brings a batching rule (``jax.custom_batching``) that is
  handed every lane's rows and ``live`` at once. The routed experts of
  ``models/moe.py`` do: one sort and one set of grouped products for the pool,
  over the live lanes' choices alone. Nothing here names such a module.

Admission (prefill) runs per request on a FRESH zeroed lane cache and is
scattered into the pool at the target slot. That freshness is what makes
slot REUSE safe under ``kv_quant``: the int8 cache's single-prefill
contract (``init_cache``) requires the first multi-token apply to happen at
cursor 0, and a recycled slot always restarts from a zero lane rather than
the previous occupant's state. Prompts may be right-padded to a bucket
length to bound prefill compile count: padded positions write garbage K/V
past the prompt, but causal masking keeps real logits exact, the cursor is
rewound to the true length, and the ``key_pos < ring_base`` mask hides the
garbage until decode merges overwrite it.

**What a slot holds.** Whatever the model's ``"cache"`` collection declares
for one sequence. For ``TransformerLM`` that is K/V rows (big cache and ring)
and two cursors a layer. A model with latent attention
(``models/latent_moe.py``) holds, in place of per-head K and V, ONE row a
position for all heads: the compressed latent its heads' keys and values are
made from, followed by the one rotated key they share (``cached_latent`` and
``ring_latent``: 576 values where K and V would be 10,240 at that model's
published sizes). It rides the same protocol under its own leaf names: a big
cache the decode scan closes over, a ring the steps append to by a select, one
merge a block, the read bounded by ``kv_read/rows``. A model with recurrent
layers (``models/hybrid.py``) adds leaves that are not rows at all: a
linear-attention layer's ``state`` (float32, fixed size however long the
sequence is) and ``conv_tail``. Nothing here branches on the model's kind; the
leaves are told apart by name: ``split_cache`` puts the big row caches
(``models/generate.BIG_CACHE_LEAVES``) on one side and everything else with
the small leaves the decode scan carries (right for a state: every step
rewrites it whole), ``merge_ring_caches`` merges the ring that goes with the
big leaf it finds, the lane scatter and ``slot_kv`` take every leaf, and a
freed slot's state is simply overwritten by the next occupant's fresh lane.
The padded prefill is the one place a model needs help: a recurrence has no
mask to hide padding behind, and an expert layer must not count the padding's
rows, so ``_admit_jit`` writes the prompt's true length into every cache leaf
called ``prefill_len`` before the prefill (a tree without that leaf,
``TransformerLM``'s, is untouched and compiles to what it did), and the
module that declared the leaf keeps padded positions out of its state or its
counts.

**What a model counts.** A model may write counts into a ``"counters"``
collection (``models/generate.COUNTERS``: an expert layer's choices per expert).
Both programs make that collection mutable and return it with their tokens:
an admission's as the model wrote it, a decode block's summed over the ACTIVE
slots inside the program, a row a step (under the vmap a counter is a slot
each). A model that declares none returns an empty tree and compiles to what
it did. ``SlotKVPool.last_counters`` holds the newest, fetched with the tokens.

Exactness contract (CPU): a request decoded through the pool picks
token-for-token what a standalone ``generate()`` picks for the same
``(params, prompt, rng)`` — the attention is the same module over the same
rows (the masked cache tail contributes exact zeros as far as it is read at
all; the softmax over chunks adds in another order, to float32 rounding),
and the sampler consumes the same folded keys (tested in
``tests/test_serving.py``).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from distributed_ml_pytorch_tpu.models.generate import (
    BIG_CACHE_LEAVES,
    COUNTERS,
    DECODE_BLOCK,
    _decode_model,
    _fuse_qkv_params,
    init_cache,
    join_cache,
    merge_ring_caches,
    sample_tokens_dynamic,
    split_cache,
)
from distributed_ml_pytorch_tpu.models.transformer import KV_READ, kv_read_chunk
from distributed_ml_pytorch_tpu.ops.slot_attention import kv_block_rows
from distributed_ml_pytorch_tpu.utils.tracing import span


#: the cache leaves that are a latent-attention layer's rows (big cache and
#: ring): ONE row a position for all heads, the compressed latent the heads'
#: keys and values are made from followed by the one rotated key they share
#: (``models/latent_moe.py``); told apart from K/V rows by these names alone
_LATENT_LEAVES = ("cached_latent", "ring_latent")
#: the cache leaves that are rows of cached positions: per-head K and V (big
#: caches, rings, int8 scales) and latent rows
_KV_LEAVES = ("cached_k", "cached_v", "ring_k", "ring_v", "scale_k", "scale_v") + _LATENT_LEAVES


def find_cache_leaf(tree, name: str):
    """First leaf called ``name`` in a (possibly stacked) cache pytree.

    Every attention layer carries its own ``cursor``/``ring_base`` and the
    blocked decode advances them in lockstep, so any one leaf is the
    per-slot truth (deterministic traversal order for trace stability).
    """
    if isinstance(tree, dict):
        for key in sorted(tree):
            val = tree[key]
            if key == name and not isinstance(val, dict):
                return val
            if isinstance(val, dict):
                found = find_cache_leaf(val, name)
                if found is not None:
                    return found
    return None


def replace_cache_leaves(tree, mapping):
    """Rebuild a cache pytree with every leaf named in ``mapping`` replaced
    by the mapped value (cast to the leaf's dtype, broadcast to its shape).
    Used to rewind cursors after a padded prefill and to reset freed slots."""
    out = {}
    for name, val in tree.items():
        if isinstance(val, dict):
            out[name] = replace_cache_leaves(val, mapping)
        elif name in mapping:
            out[name] = jnp.broadcast_to(
                jnp.asarray(mapping[name], val.dtype), val.shape)
        else:
            out[name] = val
    return out


def kv_read_hint(cache, rows, live=None):
    """The ``kv_read`` collection, built from the structure of ``cache``. It
    tells every attention layer (a dict that holds a big cache leaf:
    ``cached_k``, or ``cached_latent``) to read ``rows`` rows of its big cache
    and no more, and every module that no mask shields from rows that are
    nobody's (a dict that holds ``prefill_len``: it asked for a padded
    prefill's true length for that reason) whether its lane is ``live``, if
    the caller says."""
    hint = {name: kv_read_hint(val, rows, live)
            for name, val in cache.items() if isinstance(val, dict)}
    hint = {name: val for name, val in hint.items() if val}
    if any(name in BIG_CACHE_LEAVES for name in cache):
        hint["rows"] = rows
    if live is not None and "prefill_len" in cache:
        hint["live"] = live
    return hint


def flat_counters(tree, prefix: str = "") -> dict:
    """A ``"counters"`` collection as ``{"layer_1/moe/expert_choices": leaf}``."""
    out = {}
    for name, val in tree.items():
        path = f"{prefix}/{name}" if prefix else name
        out.update(flat_counters(val, path) if isinstance(val, dict) else {path: val})
    return out


def kv_read_ladder(cache_size: int) -> tuple[int, ...]:
    """The row counts a bounded read of a ``cache_size``-row cache can stop
    at: whole chunks, and the allocation itself."""
    chunk = kv_read_chunk(cache_size)
    return tuple(min(n * chunk, cache_size)
                 for n in range(1, -(-cache_size // chunk) + 1))


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def _admit_jit(dec, params, pool, slot, prompt, real_len, seed,
               temperature, top_k, top_p, gen_offset):
    """Prefill ``prompt`` ([1, bucket] int32, right-padded past ``real_len``)
    on a fresh lane cache, sample the request's first token, and scatter the
    lane into ``pool`` at ``slot``. Returns ``(pool, first_token, counters)``:
    ``counters`` is what the model wrote into its ``"counters"`` collection
    over the prompt's real rows (empty for a model that declares none).

    ``gen_offset`` is the request's position in its own sampling-key
    schedule: token ``g`` is always drawn with ``fold_in(key(seed), g)``,
    so a RESUMED request (fleet migration re-prefills prompt + the tokens
    generated so far on a surviving engine) samples its next token with
    the same key the dead engine would have — stream migration stays
    token-identical even for sampled requests. A fresh admission passes 0.
    """
    lane = jax.tree.map(lambda x: jnp.zeros(x.shape[1:], x.dtype), pool)
    # a recurrent mixer has no mask to hide the padding behind: it declares
    # ``prefill_len`` and counts only that many positions into its state
    lane = replace_cache_leaves(lane, {"prefill_len": real_len})
    bucket = prompt.shape[1]
    positions = jnp.arange(bucket)[None, :]
    logits, mutated = dec.apply(
        {"params": params, "cache": lane}, prompt, positions,
        mutable=["cache", COUNTERS]
    )
    # rewind cursor/ring_base from the padded bucket end to the true prompt
    # length: the pad region's K/V is garbage the ``key_pos < ring_base``
    # mask hides until decode merges overwrite it
    lane = replace_cache_leaves(
        mutated["cache"], {"cursor": real_len, "ring_base": real_len})
    last = jax.lax.dynamic_index_in_dim(logits[0], real_len - 1, keepdims=False)
    keys = jax.vmap(
        lambda s: jax.random.fold_in(jax.random.key(s), gen_offset))(seed[None])
    tok0 = sample_tokens_dynamic(
        last[None], keys, temperature[None], top_k[None], top_p[None],
        jnp.ones((1,), bool))[0]
    pool = jax.tree.map(
        lambda P, L: jax.lax.dynamic_update_slice(
            P, L[None], (slot,) + (0,) * L.ndim),
        pool, lane)
    return pool, tok0.astype(jnp.int32), mutated.get(COUNTERS, {})


@partial(jax.jit, donate_argnums=(0,))
def _reset_slots_jit(pool, mask):
    """Zero the cursor/ring_base of every slot where ``mask`` is True: the
    freed slot's K/V rows become invisible (``key_pos < ring_base``) and its
    live length reads 0 until the next admission overwrites it. A recurrent
    state stays as it is: nothing reads a free slot's, and an admission
    replaces the whole lane."""

    def walk(tree):
        out = {}
        for name, val in tree.items():
            if isinstance(val, dict):
                out[name] = walk(val)
            elif name in ("cursor", "ring_base"):
                out[name] = jnp.where(mask, 0, val)
            else:
                out[name] = val
        return out

    return walk(pool)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def _decode_block_jit(dec, params, pool, tok, n_gen, seeds,
                      temps, top_ks, top_ps, active):
    """One decode block for the whole pool: ``decode_block`` single-token
    steps vmapped over slots, then per-slot ring merges.

    Mirrors ``_generate_blocked_jit``'s structure: the big caches cross the
    scan as constants (only the small ring state is carried), appends hit
    the per-layer rings, and the merge amortizes the big-cache write to
    once per block. Every step reads the big caches as far as the longest
    ACTIVE slot reaches, in whole chunks (``kv_read_hint``; the block
    returns the rows read beside its tokens, and whatever the model counted
    (its ``"counters"`` collection, a slot each under the vmap) summed over the
    ACTIVE slots, a row a step). Slots where ``active`` is
    False decode garbage from a zeroed state (their tokens are discarded by
    the scheduler) and are re-zeroed on exit so their cursors never creep
    toward the cache edge; each lane is told whether it is active
    (``kv_read/live``, an argument of the vmapped step and so a value a lane),
    for a module whose cost follows the rows it is given: one with a batching
    rule of its own sees every lane's at once and leaves the idle ones out.
    Token ``g`` of a request is sampled with ``fold_in(key(seed), g)`` —
    the same per-step key schedule ``generate()`` uses, which is what makes
    engine output bit-match a standalone ``generate`` call on CPU. The
    sampler does what the ``active`` rows' parameters ask for and no more
    (``sample_tokens_dynamic``): a free slot's stale parameters cost nothing.
    """
    T = dec.decode_block
    big, small = split_cache(pool)
    base = find_cache_leaf(small, "ring_base")  # (S,) per-slot block start
    # ``ring_base`` stands still until the merge below, so the longest among
    # the active slots bounds the big-cache rows any step of this block can
    # read (the block's own rows are in the rings): one scalar for the pool
    live = jnp.where(active, base, 0)
    cache_size = dec.cache_size
    chunk = kv_read_chunk(cache_size)
    with jax.named_scope("kv_read"):
        longest = jnp.max(live)
        read_rows = jnp.minimum(-(-longest // chunk) * chunk, cache_size)

    def lane_apply(lane_cache, tok1, pos1, live1):
        # ``longest`` is closed over, so under the vmap it stays one scalar and
        # the loop it bounds one loop over batched operands; ``live1`` is the
        # lane's own, and a module that wants the pool's has a batching rule
        hint = kv_read_hint(lane_cache, longest, live1)
        logits, mutated = dec.apply(
            {"params": params, "cache": lane_cache, KV_READ: hint},
            tok1[None, None], pos1[None, None], mutable=["cache", COUNTERS],
        )
        return logits[0, -1], mutated["cache"], mutated.get(COUNTERS, {})

    def step(carry, _):
        small, tok, g = carry
        cursor = find_cache_leaf(small, "cursor")  # (S,) = absolute position
        logits, cache, counted = jax.vmap(lane_apply)(
            join_cache(big, small), tok, cursor, active)
        counted = jax.tree.map(
            lambda c: jnp.sum(jnp.where(active.reshape((-1,) + (1,) * (c.ndim - 1)), c, 0),
                              axis=0), counted)
        _, small = split_cache(cache)
        keys = jax.vmap(
            lambda s, i: jax.random.fold_in(jax.random.key(s), i))(seeds, g)
        nxt = sample_tokens_dynamic(
            logits, keys, temps, top_ks, top_ps, active).astype(jnp.int32)
        return (small, nxt, g + 1), (nxt, counted)

    (small, _, _), (toks, counted) = jax.lax.scan(
        step, (small, tok, jnp.asarray(n_gen, jnp.int32)), None, length=T)

    big = jax.vmap(merge_ring_caches)(big, small, live)
    cursor = find_cache_leaf(small, "cursor")
    small = replace_cache_leaves(small, {
        "cursor": jnp.where(active, cursor, 0),
        "ring_base": jnp.where(active, base + T, 0),
    })
    return join_cache(big, small), jnp.moveaxis(toks, 0, 1), read_rows, counted  # [S, T]


class SlotKVPool:
    """Fixed-capacity pool of ``slots`` independent cache slots over the
    blocked decode module. A slot holds one sequence's whole state: up to
    ``cache_size`` K/V rows in every attention layer and, for a model with
    recurrent layers, each such layer's fixed-size state beside them
    (:meth:`slot_bytes`). A decode step reads each slot's own K/V rows
    (``slot_block_rows``; the module docstring has the rule).

    The pool is the compiled data plane; the scheduler
    (``serving/engine.py``) owns which slot belongs to which request. All
    per-request sampling state (seed/temperature/top-k/top-p) is traced, so
    one compiled block program serves any mix of greedy and sampled
    requests. What a step of it pays for sampling follows the mix, by a
    conditional inside the program (``sample_tokens_dynamic``): an argmax
    while every active slot is greedy; random bits for every logit of every
    slot once one active slot has a temperature; a sort, a softmax and a
    cumulative sum over ``slots x vocab`` besides once one of those asks for
    top-k or top-p. One sampled request costs every slot that tier for as
    long as it is active, and nothing after its eviction.
    """

    def __init__(self, model, params, *, slots: int, cache_size: int,
                 decode_block: int = DECODE_BLOCK, kv_quant: bool = False):
        if slots < 1:
            raise ValueError(f"need at least one slot, got {slots}")
        if decode_block < 1:
            raise ValueError(
                "the slot pool rides the ring-buffered blocked cache — "
                f"decode_block must be >= 1, got {decode_block}")
        max_len = getattr(model, "max_len", None)
        if (max_len is not None and cache_size > max_len
                and getattr(model, "pos_encoding", "learned") != "rope"):
            raise ValueError(
                f"cache_size {cache_size} exceeds the model's learned "
                f"position table max_len={max_len} (RoPE models have no "
                "such bound)")
        self.slots = int(slots)
        self.cache_size = int(cache_size)
        self.decode_block = int(decode_block)
        self.kv_quant = bool(kv_quant)
        self.model = model
        self.dec = _decode_model(model, cache_size, decode_block=decode_block,
                                 kv_quant=kv_quant)
        self.params = (
            _fuse_qkv_params(params)
            if getattr(self.dec, "fused_qkv", False) else params)
        lane = jax.eval_shape(lambda: init_cache(
            model, 1, self.cache_size, decode_block=self.decode_block,
            kv_quant=self.kv_quant))
        self.cache = jax.tree.map(
            lambda s: jnp.zeros((self.slots,) + s.shape, s.dtype), lane)
        #: the row counts a decode step's read of the big caches can stop at
        self.read_ladder = kv_read_ladder(self.cache_size)
        #: and where the newest decode block's steps stopped (0: no slot was
        #: active, or no block yet)
        self.last_read_rows = 0
        self.slot_block_rows = _slot_block_rows(self.cache, self.cache_size, self.kv_quant)
        #: what the model counted in the newest admission or decode block
        #: (``flat_counters``; a decode block's leaves have a row a step)
        self.last_counters: dict = {}

    def admit(self, slot: int, prompt: np.ndarray, real_len: int, *,
              seed: int = 0, temperature: float = 0.0, top_k: int = 0,
              top_p: float = 1.0, gen_offset: int = 0) -> int:
        """Prefill a (bucketed) prompt into ``slot``; returns the request's
        first sampled token. One compiled program per bucket length.
        ``gen_offset`` resumes the sampling-key schedule at that generated-
        token index (stream migration; 0 for a fresh request)."""
        prompt = jnp.asarray(prompt, jnp.int32)[None, :]
        if prompt.shape[1] < 2:
            # s == 1 is the decode-step discriminator inside the blocked
            # module: a 1-token "prefill" would write the ring, orphaning
            # the prompt's K/V (the hazard uses_block_decode documents) —
            # callers must pad 1-token prompts (ServingEngine._bucket_len)
            raise ValueError(
                "admit() needs a prompt of length >= 2 — pad 1-token "
                "prompts (a length-1 apply is a decode step, not a prefill)")
        self.cache, tok0, counted = _admit_jit(
            self.dec, self.params, self.cache,
            jnp.asarray(slot, jnp.int32), prompt,
            jnp.asarray(real_len, jnp.int32),
            jnp.asarray(seed, jnp.uint32),
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_k, jnp.int32),
            jnp.asarray(top_p, jnp.float32),
            jnp.asarray(gen_offset, jnp.int32))
        tok0, counted = jax.device_get((tok0, counted))  # one fetch
        self.last_counters = flat_counters(counted)
        return int(tok0)

    def decode_block_step(self, tok, n_gen, seeds, temps, top_ks, top_ps,
                          active) -> np.ndarray:
        """Advance every slot by one ``decode_block``-token block; returns
        the sampled tokens ``[slots, decode_block]`` (host array — the
        fetch is the block's device sync point) and leaves in
        ``last_read_rows`` how many rows of each big cache the block's steps
        read, as the device counted them."""
        with span("serve.decode.dispatch"):  # enqueues the program
            self.cache, toks, read_rows, counted = _decode_block_jit(
                self.dec, self.params, self.cache,
                jnp.asarray(tok, jnp.int32), jnp.asarray(n_gen, jnp.int32),
                jnp.asarray(seeds, jnp.uint32),
                jnp.asarray(temps, jnp.float32),
                jnp.asarray(top_ks, jnp.int32),
                jnp.asarray(top_ps, jnp.float32),
                jnp.asarray(active, bool))
        with span("serve.decode.fetch"):  # waits for the device
            toks, read_rows, counted = jax.device_get((toks, read_rows, counted))
        self.last_read_rows = int(read_rows)
        self.last_counters = flat_counters(counted)
        return toks

    def reset_slots(self, slot_indices) -> None:
        """Mark the given slots empty (cursor/ring_base back to 0)."""
        mask = np.zeros(self.slots, bool)
        mask[list(slot_indices)] = True
        self.cache = _reset_slots_jit(self.cache, jnp.asarray(mask))

    def slot_kv(self, slot: int) -> np.ndarray:
        """One slot's lane as a flat float32 vector (every floating cache
        leaf's row for ``slot``, K/V and recurrent state alike, concatenated
        in tree order) — the body a migration handoff ships on the
        ``KvMigrate`` wire (ISSUE 18). With ``kv_quant`` the leaves are
        already the int8+scale recipe; the float32 view is the wire's common
        currency either way."""
        parts = [
            np.asarray(leaf[slot], np.float32).ravel()
            for leaf in jax.tree.leaves(self.cache)
            if jnp.issubdtype(leaf.dtype, jnp.floating)]
        if not parts:
            return np.zeros(0, np.float32)
        return np.concatenate(parts)

    def slot_bytes(self) -> dict:
        """Bytes one slot holds, from the cache tree's leaves:
        ``kv_bytes_per_slot`` (rows of cached positions: big caches, rings,
        int8 scales), ``latent_bytes_per_slot`` (those of them that are latent
        rows: 0 for a model that caches per-head K and V) and
        ``state_bytes_per_slot`` (every other floating leaf: a recurrent
        layer's state and convolution tail; 0 for an attention-only model)."""
        kv = latent = state = 0
        for path, leaf in jax.tree_util.tree_leaves_with_path(self.cache):
            size = leaf.dtype.itemsize * int(np.prod(leaf.shape[1:]))
            if path[-1].key in _KV_LEAVES:
                kv += size
                latent += size * (path[-1].key in _LATENT_LEAVES)
            elif jnp.issubdtype(leaf.dtype, jnp.floating):
                state += size
        return {"kv_bytes_per_slot": kv, "latent_bytes_per_slot": latent,
                "state_bytes_per_slot": state}

    def live_lengths(self) -> np.ndarray:
        """Per-slot live sequence length (prompt + generated), from the
        cache's own cursors — the observability face of slot occupancy."""
        cur = find_cache_leaf(self.cache, "cursor")
        return np.asarray(cur).reshape(self.slots)

    def blocks_needed(self, max_new_tokens: int) -> int:
        """Decode blocks a request of ``max_new_tokens`` occupies a slot for
        (its first token comes from prefill, the rest from whole blocks)."""
        return -(-(max_new_tokens - 1) // self.decode_block)

    def capacity_needed(self, prompt_len: int, bucket_len: int,
                        max_new_tokens: int) -> int:
        """K/V rows the request can touch in each attention layer: the padded
        prefill writes up to ``bucket_len``, and block-granular decode writes
        merges from the true prompt length through the rounded-up tail block.
        Recurrent layers bound nothing: their state has one size whatever the
        sequence's length."""
        decoded = self.blocks_needed(max_new_tokens) * self.decode_block
        return max(bucket_len, prompt_len + decoded)


def _slot_block_rows(cache, cache_size: int, kv_quant: bool):
    """Rows of its K/V caches a slot reads at a time where a decode step reads
    each slot's own rows (``ops/slot_attention.kv_block_rows`` of the cache's
    heads and head size), or None for a pool whose steps do not: latent rows,
    int8 caches."""
    leaf = find_cache_leaf(cache, "cached_k")  # (slots, 1, heads, rows, head_dim)
    if leaf is None or kv_quant:
        return None
    return kv_block_rows(cache_size, leaf.shape[2], leaf.shape[4], leaf.dtype.itemsize)
