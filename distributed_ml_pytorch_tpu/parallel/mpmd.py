"""MPMD pipeline parallelism: stages as independently compiled fleet members
(ISSUE 10 tentpole).

``parallel/pipeline.py`` runs every pipeline schedule inside ONE process as
one jitted ``shard_map`` program — one stage fault kills the whole model.
"Scaling Deep Learning Training with MPMD Pipeline Parallelism"
(arXiv:2412.14374) shows the alternative this module builds: each stage is
its OWN compiled program over its own device group, and activations /
activation-gradients flow between stages as wire messages. That makes a
stage exactly the unit the coordination plane (``coord/``) already knows
how to lease, place, kill-detect and restart:

- :class:`StagePrograms` — the per-stage standalone programs (forward,
  recompute-backward, last-stage fused loss+backward, SGD update), compiled
  with plain ``jax.jit`` + ``jax.vjp``: no ``shard_map``, no mesh, no
  collective. Stage 0 additionally owns the token/positional embeddings,
  the last stage the final LayerNorm + LM head — so the per-stage param
  trees CONCATENATE (in stage order) into one flat vector whose contiguous
  per-stage ranges (:func:`stage_param_ranges`) slot straight into the
  existing ``ShardMap`` / ``FleetManifest`` machinery.
- :class:`MpmdLocal` — the same numerics loopback in one thread (no
  transports): the exactness oracle. Because every stage compiles
  standalone, its gradients are the plain-AD gradients of the reference
  model (``tests/test_pipeline.py`` holds the shard_map schedules to it).
- :class:`MpmdStage` — one stage as a fleet member: a serve loop over a
  :class:`~.messaging.Transport` (so ReliableTransport / chaos / weather
  wrap it unchanged), a ``CoordClient`` lease, per-``(step, microbatch)``
  receive dedup (NO microbatch is ever applied twice — chaos dups,
  reliability redelivery and restart replay all collapse), a retained-send
  buffer for watermark-bounded replay toward restarted neighbors, and a
  per-stage checkpoint (params + optimizer + microbatch watermark) written
  through the ``atomic_write`` discipline and reported into the existing
  ``FleetManifest`` snapshot barrier.
- :class:`MpmdDriver` — the data feeder / loss collector: ships microbatch
  tokens to stage 0 and targets to the last stage (``ActivationShip``
  kinds 1/2), collects per-microbatch ``ce_sum`` reports (kind 3), and
  re-ships retained data to restarted endpoints on placement changes.

Restart contract (the robustness headline): a stage checkpoints after
every optimizer update, so its watermark is ``step * M`` — the global
count of microbatches whose gradients are already inside its params. On
death, the coordinator (``coord/stages.py``) detects the expired lease,
vacates the stage in the versioned ``StagePlacement``, and when a
replacement announces ``StageReady(stage, watermark)``, broadcasts the new
placement. Every member compares entry INCARNATIONS: a changed
incarnation means "this endpoint lost its in-flight state" — neighbors
re-ship exactly the retained ``(step, mb)`` messages at or past the
entry's watermark. Receivers dedup by ``(step, mb)``, so replay +
reliability redelivery can only ever fill holes, never double-apply; the
per-step update is the mb-ordered SUM of per-microbatch gradients, so the
recovered trajectory is numerically the fault-free trajectory.

Scheduling: processing is gated by each stage's OWN step (a stage's
forward for step ``t`` must see its params after update ``t-1``), and
within a step microbatches pipeline freely — stage ``s`` forwards
microbatch ``m+1`` while ``s+1`` works on ``m``, GPipe-style, with
backwards interleaving as cotangents arrive (1F1B-style drain). Straggler
stages get Sandblaster-style speculation: a standby member loads the
victim's checkpoint and races it for the stage (``coord/stages.py``).
"""

from __future__ import annotations

import collections
import json
import logging
import os
import threading
import time
import zlib
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.flatten_util import ravel_pytree

from distributed_ml_pytorch_tpu.parallel.pipeline import (
    PipelineLMConfig,
    _lm_modules,
    _stage_forward,
    init_pp_params,
)
from distributed_ml_pytorch_tpu.utils import obs
from distributed_ml_pytorch_tpu.utils.durability import atomic_write
from distributed_ml_pytorch_tpu.utils import codecs
from distributed_ml_pytorch_tpu.utils.compress import (
    CODEC_DENSE,
    CODEC_INT8,
    CompressionError,
)
from distributed_ml_pytorch_tpu.utils.messaging import (
    MessageCode,
    Transport,
    _join16,
    _split16,
)
from distributed_ml_pytorch_tpu.utils.metrics import Ewma

_LOGGER = logging.getLogger(__name__)

#: ``ActivationShip`` payload kinds (WIRE_SCHEMAS): what the body carries.
SHIP_ACT = 0      # activation tensor, stage s -> s+1
SHIP_TOKENS = 1   # microbatch token ids, driver -> stage 0
SHIP_TARGETS = 2  # microbatch target ids, driver -> last stage
SHIP_LOSS = 3     # [ce_sum] report, last stage -> driver


def replay_covers(step: int, mbi: int, n_microbatches: int,
                  watermark: int) -> bool:
    """The watermark-replay eligibility predicate: a retained ``(step,
    mb)`` hand-off is re-shipped to a restarted neighbor iff its global
    microbatch index is AT OR PAST the neighbor's announced recovery
    watermark. ``>=`` is load-bearing: the checkpoint at watermark ``w``
    covers indices ``< w``, so index ``w`` itself is the restarted
    member's first hole — re-shipping strictly above it leaves a
    permanent gap. This is the exact rule the bounded model checker
    explores (``analysis/distmodel.MpmdModel``; its
    ``watermark_off_by_one`` mutation is this predicate with ``>``), and
    tests/test_distmodel.py tethers the two together."""
    return step * n_microbatches + mbi >= watermark

CKPT_FILE = "stage.ckpt"


# --------------------------------------------------------------- param trees

def stage_layer_slice(cfg: PipelineLMConfig, stage: int,
                      n_stages: int) -> Tuple[int, int]:
    if cfg.n_layers % n_stages:
        raise ValueError(
            f"n_layers={cfg.n_layers} must divide evenly over {n_stages} "
            "stages")
    per = cfg.n_layers // n_stages
    return stage * per, (stage + 1) * per


def stage_param_tree(cfg: PipelineLMConfig, full, stage: int, n_stages: int):
    """Slice the full pipelined param tree (``init_pp_params`` layout) down
    to what ONE stage owns: its contiguous block layers, plus the
    embeddings (stage 0) and final LN + head (last stage)."""
    lo, hi = stage_layer_slice(cfg, stage, n_stages)
    tree = {"blocks": jax.tree.map(lambda x: x[lo:hi], full["blocks"])}
    if stage == 0:
        tree["tok_embed"] = full["tok_embed"]
        tree["pos_embed"] = full["pos_embed"]
    if stage == n_stages - 1:
        tree["ln_f"] = full["ln_f"]
        tree["head"] = full["head"]
    return tree


def init_stage_params(cfg: PipelineLMConfig, rng, stage: int, n_stages: int):
    """Every member inits the FULL tree from the same seed and slices its
    stage — deterministic and identical across processes, so a fleet's
    stage params always assemble into one consistent model."""
    return stage_param_tree(cfg, init_pp_params(cfg, rng), stage, n_stages)


def assemble_full_params(cfg: PipelineLMConfig, stage_trees):
    """Inverse of :func:`stage_param_tree` over all stages (tests compare
    the assembled tree against the single-stage reference)."""
    n_stages = len(stage_trees)
    blocks = jax.tree.map(
        lambda *xs: jnp.concatenate(xs, axis=0),
        *[t["blocks"] for t in stage_trees])
    return {
        "blocks": blocks,
        "tok_embed": stage_trees[0]["tok_embed"],
        "pos_embed": stage_trees[0]["pos_embed"],
        "ln_f": stage_trees[n_stages - 1]["ln_f"],
        "head": stage_trees[n_stages - 1]["head"],
    }


def stage_param_ranges(cfg: PipelineLMConfig,
                       n_stages: int) -> List[Tuple[int, int]]:
    """Contiguous ``[lo, hi)`` of each stage's flat params inside the
    stage-ordered concatenation — the ranges the coordinator's
    ``StagePlacement`` (and the ``FleetManifest`` barrier) carries."""
    shapes = jax.eval_shape(
        lambda rng: init_pp_params(cfg, rng), jax.random.key(0))
    per = cfg.n_layers // n_stages
    stage_layer_slice(cfg, 0, n_stages)  # divisibility check
    # blocks leaves are layer-stacked on their leading axis: a stage's
    # share is `per` rows of each leaf
    blocks_size = sum(per * int(np.prod(leaf.shape[1:]))
                      for leaf in jax.tree.leaves(shapes["blocks"]))

    def tree_size(tree) -> int:
        return sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree.leaves(tree))

    ranges = []
    cursor = 0
    for s in range(n_stages):
        size = blocks_size
        if s == 0:
            size += tree_size(shapes["tok_embed"])
            size += tree_size(shapes["pos_embed"])
        if s == n_stages - 1:
            size += tree_size(shapes["ln_f"]) + tree_size(shapes["head"])
        ranges.append((cursor, cursor + size))
        cursor += size
    return ranges


# ----------------------------------------------------------------- programs

class StagePrograms:
    """One stage's standalone compiled programs (see module docstring).

    ``fwd(params, x) -> h_out`` — x is tokens (stage 0) or the received
    activation. ``bwd(params, x, g) -> (d_params, d_x)`` recomputes the
    stage forward under ``jax.vjp`` (1F1B-with-recompute: residuals are
    never stored across messages, which is what makes watermark replay a
    pure recomputation). The last stage fuses forward + loss + backward in
    ``loss_bwd(params, x, targets) -> (ce_sum, d_params, d_x)`` — its
    cotangent seed is ``1 / (n_mask * M)``, so summing per-microbatch
    gradients yields the gradient of the global mean loss
    (``pipeline.py``'s exact convention: the final position of each
    sequence is masked).
    """

    def __init__(self, cfg: PipelineLMConfig, stage: int, n_stages: int,
                 n_microbatches: int, lr: float):
        self.cfg = cfg
        self.stage = int(stage)
        self.n_stages = int(n_stages)
        self.first = stage == 0
        self.last = stage == n_stages - 1
        M = int(n_microbatches)
        embed, pos_embed, head, ln_f = _lm_modules(cfg)
        first, last = self.first, self.last

        def run(params, x):
            if first:
                positions = jnp.arange(x.shape[1])[None, :]
                h = embed.apply({"params": params["tok_embed"]}, x)
                h = h + pos_embed.apply(
                    {"params": params["pos_embed"]}, positions)
            else:
                h = x
            return _stage_forward(cfg, params["blocks"], h)

        self.fwd = jax.jit(run)

        if last:
            def loss_fn(params, x, targets):
                h_out = run(params, x)
                logits = head.apply(
                    {"params": params["head"]},
                    ln_f.apply({"params": params["ln_f"]}, h_out))
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    logits, targets)
                mask = jnp.ones_like(ce).at[:, -1].set(0.0)
                return jnp.sum(ce * mask)

            def loss_bwd(params, x, targets):
                n_mask = targets.shape[0] * (targets.shape[1] - 1)
                seed = 1.0 / float(n_mask * M)
                if first:  # n_stages == 1: x is int tokens, params-only vjp
                    ce_sum, vjp = jax.vjp(
                        lambda p: loss_fn(p, x, targets), params)
                    (d_params,) = vjp(jnp.asarray(seed, ce_sum.dtype))
                    return ce_sum, d_params, jnp.zeros(())
                ce_sum, vjp = jax.vjp(
                    lambda p, h: loss_fn(p, h, targets), params, x)
                d_params, d_x = vjp(jnp.asarray(seed, ce_sum.dtype))
                return ce_sum, d_params, d_x

            self.loss_bwd = jax.jit(loss_bwd)
        else:
            def bwd(params, x, g):
                if first:  # int tokens: the embedding transposes, no d_x
                    _, vjp = jax.vjp(lambda p: run(p, x), params)
                    (d_params,) = vjp(g)
                    return d_params, jnp.zeros(())
                _, vjp = jax.vjp(run, params, x)
                return vjp(g)

            self.bwd = jax.jit(bwd)

        self.tx = optax.sgd(float(lr))

        def update(params, opt_state, grads):
            updates, new_opt = self.tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), new_opt

        self.update = jax.jit(update)


_PROGRAM_CACHE: Dict[tuple, StagePrograms] = {}
_PROGRAM_LOCK = threading.Lock()


def stage_programs(cfg: PipelineLMConfig, stage: int, n_stages: int,
                   n_microbatches: int, lr: float) -> StagePrograms:
    """Process-wide program cache: a restarted stage member (or a repeat
    scenario run) reuses the already-traced programs — restart MTTR pays
    checkpoint IO, not recompilation."""
    key = (cfg.vocab_size, cfg.d_model, cfg.n_heads, cfg.n_layers, cfg.d_ff,
           cfg.max_len, int(stage), int(n_stages), int(n_microbatches),
           float(lr))
    with _PROGRAM_LOCK:
        prog = _PROGRAM_CACHE.get(key)
        if prog is None:
            prog = _PROGRAM_CACHE[key] = StagePrograms(
                cfg, stage, n_stages, n_microbatches, lr)
        return prog


# -------------------------------------------------------------- local runner

class MpmdLocal:
    """The MPMD step, loopback in one thread — the exactness oracle.

    ``schedule`` controls host execution ORDER only ("gpipe" = all
    microbatch forwards, then all backwards; "1f1b" = per-microbatch
    depth-first forward+backward, the bounded-activation order): the
    per-microbatch values are identical and each stage's update sums its
    per-microbatch gradients in microbatch order either way, so the two
    schedules are value-identical by construction — the property the old
    shard_map 1F1B xfail could only approximate.
    """

    def __init__(self, cfg: PipelineLMConfig, n_stages: int,
                 n_microbatches: int, lr: float, rng,
                 schedule: str = "gpipe"):
        if schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"schedule must be 'gpipe' or '1f1b', got {schedule!r}")
        self.cfg = cfg
        self.S = int(n_stages)
        self.M = int(n_microbatches)
        self.schedule = schedule
        full = init_pp_params(cfg, rng)
        self.params = [stage_param_tree(cfg, full, s, self.S)
                       for s in range(self.S)]
        self.programs = [stage_programs(cfg, s, self.S, self.M, lr)
                         for s in range(self.S)]
        self.opt_states = [p.tx.init(t)
                           for p, t in zip(self.programs, self.params)]

    def _microbatch_pass(self, mbi, tokens_mb, targets_mb, inputs, grads):
        """Forward microbatch ``mbi`` through every stage, then backward —
        recording per-stage inputs and per-stage gradients."""
        x = jnp.asarray(tokens_mb[mbi])
        for s in range(self.S - 1):
            inputs[s][mbi] = x
            x = self.programs[s].fwd(self.params[s], x)
        inputs[self.S - 1][mbi] = x
        ce_sum, d_params, g = self.programs[self.S - 1].loss_bwd(
            self.params[self.S - 1], inputs[self.S - 1][mbi],
            jnp.asarray(targets_mb[mbi]))
        grads[self.S - 1][mbi] = d_params
        for s in range(self.S - 2, -1, -1):
            d_params, g = self.programs[s].bwd(
                self.params[s], inputs[s][mbi], g)
            grads[s][mbi] = d_params
        return float(ce_sum)

    def step(self, tokens_mb, targets_mb) -> float:
        """One optimizer step over ``(M, mb, seq)`` microbatched arrays;
        returns the global mean masked CE (``pipeline.py`` convention)."""
        M, S = self.M, self.S
        mb, seq = tokens_mb.shape[1], tokens_mb.shape[2]
        inputs = [dict() for _ in range(S)]
        grads = [dict() for _ in range(S)]
        ce_total = 0.0
        if self.schedule == "gpipe":
            # all forwards first (the all-M-live profile), backwards after
            for mbi in range(M):
                x = jnp.asarray(tokens_mb[mbi])
                for s in range(S - 1):
                    inputs[s][mbi] = x
                    x = self.programs[s].fwd(self.params[s], x)
                inputs[S - 1][mbi] = x
            for mbi in range(M):
                ce_sum, d_params, g = self.programs[S - 1].loss_bwd(
                    self.params[S - 1], inputs[S - 1][mbi],
                    jnp.asarray(targets_mb[mbi]))
                ce_total += float(ce_sum)
                grads[S - 1][mbi] = d_params
                for s in range(S - 2, -1, -1):
                    d_params, g = self.programs[s].bwd(
                        self.params[s], inputs[s][mbi], g)
                    grads[s][mbi] = d_params
        else:  # 1f1b: depth-first per microbatch (bounded activations)
            for mbi in range(M):
                ce_total += self._microbatch_pass(
                    mbi, tokens_mb, targets_mb, inputs, grads)
        for s in range(S):
            acc = grads[s][0]
            for mbi in range(1, M):  # mb order: deterministic accumulation
                acc = jax.tree.map(jnp.add, acc, grads[s][mbi])
            self.params[s], self.opt_states[s] = self.programs[s].update(
                self.params[s], self.opt_states[s], acc)
        return ce_total / float(mb * (seq - 1) * M)

    def full_params(self):
        return assemble_full_params(self.cfg, self.params)


# ------------------------------------------------------------- checkpointing

def save_stage_checkpoint(ckpt_dir: str, *, stage: int, step: int,
                          watermark: int, lo: int, hi: int,
                          params_flat: np.ndarray,
                          opt_flat: np.ndarray) -> None:
    """Atomic + durable per-stage checkpoint: ONE file (json meta line +
    CRC-covered binary blob) published by ONE ``atomic_write`` rename —
    the meta and the state it describes can never tear apart, even with
    two racing writers (the speculation window: a not-yet-superseded
    victim and its standby briefly share the stage's directory; whole-file
    atomicity makes that last-writer-wins instead of a corrupt mix)."""
    os.makedirs(ckpt_dir, exist_ok=True)
    blob = params_flat.astype(np.float32).tobytes() \
        + opt_flat.astype(np.float32).tobytes()
    meta = {
        "stage": int(stage), "step": int(step), "watermark": int(watermark),
        "lo": int(lo), "hi": int(hi),
        "n_params": int(params_flat.size), "n_opt": int(opt_flat.size),
        "crc": zlib.crc32(blob) & 0xFFFFFFFF,
    }
    atomic_write(os.path.join(ckpt_dir, CKPT_FILE),
                 json.dumps(meta).encode() + b"\n" + blob)


def load_stage_checkpoint(ckpt_dir: str):
    """Read + verify one stage checkpoint; raises ``ValueError`` on a
    missing, torn, or CRC-damaged checkpoint — a restart must never serve
    from state it cannot trust."""
    path = os.path.join(ckpt_dir, CKPT_FILE)
    try:
        with open(path, "rb") as f:
            raw = f.read()
        head, _, blob = raw.partition(b"\n")
        meta = json.loads(head)
    except (OSError, json.JSONDecodeError) as e:
        raise ValueError(f"unreadable stage checkpoint in {ckpt_dir}: "
                         f"{e!r}") from e
    if (zlib.crc32(blob) & 0xFFFFFFFF) != int(meta["crc"]):
        raise ValueError(
            f"stage checkpoint CRC mismatch in {ckpt_dir} — refusing to "
            "restore corrupt state")
    n_params, n_opt = int(meta["n_params"]), int(meta["n_opt"])
    if len(blob) != 4 * (n_params + n_opt):
        raise ValueError(
            f"stage checkpoint size mismatch in {ckpt_dir}: "
            f"{len(blob)} bytes for {n_params}+{n_opt} floats")
    flat = np.frombuffer(blob, np.float32)
    return meta, flat[:n_params].copy(), flat[n_params:].copy()


# -------------------------------------------------------------- fleet member

class MpmdStage:
    """One pipeline stage as a fleet member (see module docstring).

    Threads: the SERVE loop (``run``) owns all training state; the
    ``CoordClient`` listener only deposits placement / snapshot /
    speculation requests into mailboxes guarded by ``_mu``. A ``standby``
    member (``stage=None``) idles until a ``SpeculateTask`` names a victim,
    then loads the victim stage's checkpoint from ``ckpt_root`` and races
    it for the stage (Sandblaster speculation applied to stages).
    """

    def __init__(
        self,
        stage: Optional[int],
        cfg: PipelineLMConfig,
        n_stages: int,
        n_microbatches: int,
        transport: Transport,
        coord,
        *,
        mb_size: int,
        seq_len: int,
        lr: float = 0.1,
        seed: int = 0,
        ckpt_dir: Optional[str] = None,
        ckpt_root: Optional[str] = None,
        driver_rank: int = 0,
        throttle: float = 0.0,
        retain_steps: int = 3,
        step_hook: Optional[Callable[["MpmdStage", int], None]] = None,
        recorder: Optional["obs.SpanRecorder"] = None,
        obs_dir: Optional[str] = None,
        act_codec: str = "dense",
    ):
        self.cfg = cfg
        self.S = int(n_stages)
        self.M = int(n_microbatches)
        self.transport = transport
        self.coord = coord
        self.rank = transport.rank
        self.mb_size = int(mb_size)
        self.seq_len = int(seq_len)
        self.lr = float(lr)
        self.seed = int(seed)
        self.ckpt_dir = ckpt_dir
        self.ckpt_root = ckpt_root
        self.driver_rank = int(driver_rank)
        self.throttle = float(throttle)
        self.retain_steps = int(retain_steps)
        self.step_hook = step_hook
        self.ranges = stage_param_ranges(cfg, self.S)
        #: codec plane (ISSUE 18): activation bodies (SHIP_ACT fwd,
        #: ActivationGrad bwd) ride the registry rung named here; token /
        #: target / loss bodies are always dense (codec 0) by contract.
        #: Retained buffers hold RAW float32 and are re-encoded at ship
        #: time, so replayed frames are byte-identical to the originals.
        if act_codec not in ("dense", "int8"):
            raise ValueError(f"act_codec must be dense|int8, got {act_codec}")
        self._act_cid = CODEC_INT8 if act_codec == "int8" else CODEC_DENSE

        self.stage: Optional[int] = None
        self.programs: Optional[StagePrograms] = None
        self.params = None
        self.opt_state = None
        self.step = 0
        if stage is not None:
            self._install_stage(int(stage))

        # serve-thread-only training state, keyed by step / (step, mb)
        self._inputs: Dict[int, Dict[int, np.ndarray]] = {}
        self._targets: Dict[int, Dict[int, np.ndarray]] = {}
        self._gin: Dict[int, Dict[int, np.ndarray]] = {}
        self._done_fwd: Dict[int, set] = {}
        self._done_bwd: Dict[int, set] = {}
        self._mb_grads: Dict[int, Dict[int, object]] = {}
        #: retained outbound bodies for watermark replay: dirn -> (step, mb)
        self._retained: Dict[str, Dict[Tuple[int, int], np.ndarray]] = {
            "fwd": {}, "bwd": {}}
        #: exactly-once audit of applied (step, mb) pairs — ring sized far
        #: past any acceptance-run horizon so the fencing audit still sees
        #: every key, while a production-length run stays bounded
        self.applied_log = collections.deque(maxlen=4096)
        self._placement = None
        self._superseded = False
        #: per-update busy-ms EWMA — the shared implementation
        #: (``utils/metrics.Ewma``, ISSUE 12; bit-identical to the old
        #: hand-rolled 0.7/0.3 idiom so LeaseRenew floats are unchanged)
        self._ewma = Ewma()
        self._busy_at_update = 0.0
        self.stats = {
            "fwd": 0, "bwd": 0, "updates": 0, "dup_inputs_dropped": 0,
            "dup_grads_dropped": 0, "stale_dropped": 0, "reshipped": 0,
            "send_failed": 0, "snapshots": 0, "malformed_dropped": 0,
            "busy_s": 0.0, "act_dense_floats": 0, "act_wire_floats": 0,
        }
        # --- flight recorder (ISSUE 12) ---------------------------------
        #: spans + exclusive-state attribution for THIS member's serve
        #: loop (compute / wait-act / wait-grad / wire-blocked / ckpt /
        #: idle); dumps ride stage death and normal stop so every MTTR
        #: number ships with its timeline. Purely observational: the
        #: recorder reads clocks only and never steers a decision (the
        #: chaos-determinism guard in tests/test_obs.py).
        self.recorder = recorder
        self.obs_dir = obs_dir
        self._clock = (obs.StateClock(recorder, "idle")
                       if recorder is not None else None)
        #: per-(step, mb) correlation ids: one microbatch = one id across
        #: every member that touches it (adopted from inbound frames,
        #: allocated fresh only at the first touch)
        self._mb_corr: Dict[Tuple[int, int], int] = {}

        #: mailboxes the coord listener thread fills, the serve loop drains
        self._mu = threading.Lock()
        self._placement_mail = None
        self._snap_mail: Optional[Tuple[int, int]] = None
        self._spec_mail: Optional[Tuple[int, int, int]] = None
        if getattr(coord, "on_stage_assign", None) is None:
            coord.on_stage_assign = self._note_placement
        if getattr(coord, "on_snapshot", None) is None:
            coord.on_snapshot = self._note_snapshot
        if getattr(coord, "_on_speculate", None) is None:
            coord._on_speculate = self._note_speculate
        self._stop = threading.Event()
        self._crashed = False
        self.error: Optional[str] = None

    # ------------------------------------------------------------- identity
    @property
    # distcheck: ignore[DC205] step is written only by the serve thread;
    # cross-thread readers (scenario accounting, the restart watcher) take
    # a GIL-atomic int snapshot and tolerate one-step staleness by contract
    def watermark(self) -> int:
        """Global microbatch count this member's params have applied."""
        return self.step * self.M

    @property
    def lo(self) -> int:
        return self.ranges[self.stage][0] if self.stage is not None else 0

    @property
    # distcheck: ignore[DC205] stage is assigned once at install (or on
    # standby adoption, serve thread); advisory readers tolerate the
    # pre-adoption None by construction (lo rides the same contract)
    def hi(self) -> int:
        return self.ranges[self.stage][1] if self.stage is not None else 0

    def _install_stage(self, stage: int) -> None:
        self.stage = stage
        self.programs = stage_programs(
            self.cfg, stage, self.S, self.M, self.lr)
        if self.params is None:
            self.params = init_stage_params(
                self.cfg, jax.random.key(self.seed), stage, self.S)
            self.opt_state = self.programs.tx.init(self.params)

    # ------------------------------------------------------------ lifecycle
    def crash(self) -> None:
        """Chaos-script hook: die SILENTLY — serve loop exits, lease
        renewals stop, no leave is sent; the coordinator must detect the
        death by lease expiry (the acceptance path)."""
        self._crashed = True
        self.coord.stop()
        self._stop.set()

    def stop(self) -> None:
        self.coord.close()
        self._stop.set()

    # ------------------------------------------------------------ mailboxes
    def _note_placement(self, placement) -> None:
        with self._mu:
            if (self._placement_mail is None
                    or placement.version > self._placement_mail.version):
                self._placement_mail = placement

    def _note_snapshot(self, snapshot_id: int, map_version: int) -> None:
        with self._mu:
            self._snap_mail = (int(snapshot_id), int(map_version))

    def _note_speculate(self, task_id: int, victim_rank: int,
                        from_step: int) -> None:
        with self._mu:
            self._spec_mail = (int(task_id), int(victim_rank), int(from_step))

    def _drain_mailboxes(self) -> None:
        with self._mu:
            placement, self._placement_mail = self._placement_mail, None
            snap, self._snap_mail = self._snap_mail, None
            spec, self._spec_mail = self._spec_mail, None
        if placement is not None:
            self._apply_placement(placement)
        if spec is not None:
            self._apply_speculation(*spec)
        if snap is not None:
            self._do_snapshot(*snap)

    # ------------------------------------------------------------ placement
    def _apply_placement(self, placement) -> None:
        old = self._placement
        self._placement = placement
        if self.stage is not None and self.stage < len(placement.entries):
            e = placement.entries[self.stage]
            if e.rank >= 0 and e.rank != self.rank:
                if not self._superseded:
                    self._superseded = True
                    _LOGGER.info(
                        "stage %d member rank %d superseded by rank %d "
                        "(placement v%d) — going passive",
                        self.stage, self.rank, e.rank, placement.version)
            elif e.rank == self.rank:
                self._superseded = False
        from distributed_ml_pytorch_tpu.coord.stages import placement_deltas

        for e in placement_deltas(old, placement):
            self._reship_to(e)

    def _reship_to(self, entry) -> None:
        """A neighbor's member incarnation changed (restart / takeover):
        re-ship retained traffic at or past its watermark. Receivers dedup
        by ``(step, mb)``, so replay is idempotent."""
        if self.stage is None or self._superseded:
            return
        if entry.stage == self.stage + 1:
            dirn, code, kind = "fwd", MessageCode.ActivationShip, SHIP_ACT
        elif entry.stage == self.stage - 1:
            dirn, code, kind = "bwd", MessageCode.ActivationGrad, 0
        else:
            return
        for (step, mbi), body in sorted(self._retained[dirn].items()):
            if not replay_covers(step, mbi, self.M, entry.watermark):
                continue
            self._send_frame(entry.rank, code, step, mbi, kind, body)
            self.stats["reshipped"] += 1

    # ----------------------------------------------------------------- wire
    def _placement_version(self) -> int:
        return self._placement.version if self._placement is not None else 0

    def _rank_of_stage(self, stage: int) -> Optional[int]:
        p = self._placement
        if p is None or not (0 <= stage < len(p.entries)):
            return None
        rank = p.entries[stage].rank
        return rank if rank >= 0 else None

    def _send_frame(self, dst_rank: int, code: MessageCode, step: int,
                    mbi: int, kind: int, body: np.ndarray) -> None:
        ver = self._placement_version()
        # codec plane (ISSUE 18): activations may ride a lossy rung; token
        # / target / loss bodies are exact by contract, so they stay dense.
        lossy_ok = (code == MessageCode.ActivationGrad
                    or (code == MessageCode.ActivationShip
                        and kind == SHIP_ACT))
        want_cid = self._act_cid if lossy_ok else CODEC_DENSE
        cid, coded = codecs.encode_body(code, body, want_cid)
        if lossy_ok:
            self.stats["act_dense_floats"] += int(body.size)
            self.stats["act_wire_floats"] += int(coded.size)
        if code == MessageCode.ActivationShip:
            head = np.asarray(
                [*_split16(step), float(mbi), float(kind), *_split16(ver),
                 float(cid)],
                np.float32)
        else:
            head = np.asarray(
                [*_split16(step), float(mbi), *_split16(ver), float(cid)],
                np.float32)
        body = coded
        # credit-blocked send time is the WIRE's fault, not compute's:
        # carve it out of the serve loop's current state (ISSUE 12)
        stats = getattr(self.transport, "stats", None)
        blocked0 = (stats.get("window_blocked_s", 0.0)
                    if isinstance(stats, dict) else 0.0)
        try:
            self.transport.send(
                code, np.concatenate([head, body.ravel()]), dst=dst_rank)
        except (OSError, ConnectionError, KeyError):
            # a dead/vacant peer: the retained buffer + the placement
            # re-ship own recovery, the send path must not die
            self.stats["send_failed"] += 1
        if self._clock is not None and isinstance(stats, dict):
            blocked = stats.get("window_blocked_s", 0.0) - blocked0
            if blocked > 0:
                self._clock.carve("wire-blocked", blocked)

    def _ship(self, dirn: str, step: int, mbi: int,
              body: np.ndarray) -> None:
        """Retain-then-send one outbound hand-off; holds (retained only)
        when the destination stage is currently vacant. Loss reports are
        NOT retained: the driver never restarts (and a restarted last
        stage recomputes + re-sends them; the driver dedups). The send
        rides the microbatch's correlation id, so the envelope carries it
        to the neighbor (ISSUE 12)."""
        body = np.asarray(body, np.float32).ravel()
        if dirn in ("fwd", "bwd"):
            self._retained[dirn][(step, mbi)] = body
        if self._superseded:
            return
        if dirn == "fwd":
            dst = self._rank_of_stage(self.stage + 1)
            code, kind = MessageCode.ActivationShip, SHIP_ACT
        elif dirn == "bwd":
            dst = self._rank_of_stage(self.stage - 1)
            code, kind = MessageCode.ActivationGrad, 0
        else:  # loss report
            dst = self.driver_rank
            code, kind = MessageCode.ActivationShip, SHIP_LOSS
        if dst is None:
            return
        with obs.corr_scope(self._mb_corr.get((step, mbi), 0)):
            self._send_frame(dst, code, step, mbi, kind, body)

    # -------------------------------------------------------------- receive
    def handle(self, sender: int, code: MessageCode,
               payload: np.ndarray) -> None:
        if code == MessageCode.ActivationShip and payload.size >= 8:
            if not np.isfinite(payload[:7]).all():
                return
            step = _join16(payload[0], payload[1])
            mbi = int(payload[2])
            kind = int(payload[3])
            self._adopt_corr(step, mbi)
            self._on_ship(step, mbi, kind, int(payload[6]), payload[7:])
        elif code == MessageCode.ActivationGrad and payload.size >= 7:
            if not np.isfinite(payload[:6]).all():
                return
            step = _join16(payload[0], payload[1])
            mbi = int(payload[2])
            self._adopt_corr(step, mbi)
            self._on_grad(step, mbi, int(payload[5]), payload[6:])

    def _adopt_corr(self, step: int, mbi: int) -> None:
        """Bind the envelope's correlation id (restored into the thread-
        local by ReliableTransport on delivery) to this (step, mb), so the
        member's own compute spans and onward ships carry the SAME id the
        driver stamped — one microbatch, one timeline (ISSUE 12)."""
        if self.recorder is None:
            return
        corr = obs.current_corr()
        if corr and (step, mbi) not in self._mb_corr:
            self._mb_corr[(step, mbi)] = corr

    def _on_ship(self, step: int, mbi: int, kind: int, cid: int,
                 body: np.ndarray) -> None:
        if self.stage is None or not (0 <= mbi < self.M):
            return
        if step < self.step:
            self.stats["stale_dropped"] += 1
            return
        want = (self.mb_size * self.seq_len
                if kind in (SHIP_TOKENS, SHIP_TARGETS)
                else self.mb_size * self.seq_len * self.cfg.d_model)
        # decode BEFORE the size/finite gates: the gates judge the decoded
        # body, and only SHIP_ACT may ride a lossy rung — a lossy codec id
        # on a token/target frame is malformed, not merely imprecise
        if kind != SHIP_ACT and cid != CODEC_DENSE:
            self.stats["malformed_dropped"] += 1
            return
        try:
            body = codecs.decode_body(
                MessageCode.ActivationShip, cid, body, want)
        except CompressionError:
            self.stats["malformed_dropped"] += 1
            return
        if not np.isfinite(body).all():
            self.stats["malformed_dropped"] += 1
            return
        if kind == SHIP_TARGETS:
            if not self.programs.last:
                return
            tgt = self._targets.setdefault(step, {})
            if mbi in tgt:
                self.stats["dup_inputs_dropped"] += 1
                return
            tgt[mbi] = body
            return
        if kind == SHIP_TOKENS and not self.programs.first:
            return
        if kind == SHIP_ACT and self.programs.first:
            return
        if kind not in (SHIP_TOKENS, SHIP_ACT):
            return
        if mbi in self._done_fwd.get(step, ()):
            self.stats["dup_inputs_dropped"] += 1
            return
        inp = self._inputs.setdefault(step, {})
        if mbi in inp:
            self.stats["dup_inputs_dropped"] += 1
            return
        inp[mbi] = body

    def _on_grad(self, step: int, mbi: int, cid: int,
                 body: np.ndarray) -> None:
        if self.stage is None or self.programs.last or not (0 <= mbi < self.M):
            return
        try:
            body = codecs.decode_body(
                MessageCode.ActivationGrad, cid, body,
                self.mb_size * self.seq_len * self.cfg.d_model)
        except CompressionError:
            self.stats["malformed_dropped"] += 1
            return
        if not np.isfinite(body).all():
            self.stats["malformed_dropped"] += 1
            return
        if step < self.step:
            # replay for an already-applied step: stale, like _on_ship —
            # dup_grads_dropped is reserved for genuine double-delivery
            self.stats["stale_dropped"] += 1
            return
        if mbi in self._done_bwd.get(step, ()):
            self.stats["dup_grads_dropped"] += 1
            return
        gin = self._gin.setdefault(step, {})
        if mbi in gin:
            self.stats["dup_grads_dropped"] += 1
            return
        gin[mbi] = body

    # -------------------------------------------------------------- compute
    def _act_shape(self):
        return (self.mb_size, self.seq_len, self.cfg.d_model)

    def _decode_input(self, body: np.ndarray):
        if self.programs.first:
            return jnp.asarray(
                np.rint(body).astype(np.int32).reshape(
                    self.mb_size, self.seq_len))
        return jnp.asarray(body.reshape(self._act_shape()))

    def _throttle_sleep(self) -> None:
        """Scripted slow compute (the straggler knob): counts as BUSY time
        for the coordinator's straggler telemetry, and keeps servicing the
        transport in slices so a throttled stage still acks its peers —
        a slow stage must read as slow, not as dead."""
        t0 = time.perf_counter()
        deadline = t0 + self.throttle
        while not self._stop.is_set():
            left = deadline - time.perf_counter()
            if left <= 0:
                break
            msg = self.transport.recv(timeout=min(0.02, left))
            if msg is not None:
                try:
                    self.handle(*msg)
                except (ValueError, IndexError, OverflowError):
                    pass
        self.stats["busy_s"] += time.perf_counter() - t0

    def _pump(self) -> None:
        """Drive all compute the buffered messages allow, for the CURRENT
        step only (a stage's forward for step t must see its params after
        update t-1); buffered future-step traffic waits its turn.

        No compute before the first placement: a hand-off computed while
        the member cannot route would be retained-but-unsent, and since
        the NEIGHBOR'S incarnation never changed, no replay would ever
        re-ship it — the restarted-stage race that wedged the pipeline on
        exactly one microbatch."""
        if self.stage is None or self._superseded or self._placement is None:
            return
        progressed = True
        while progressed and not self._stop.is_set():
            progressed = False
            t = self.step
            prog = self.programs
            done_f = self._done_fwd.setdefault(t, set())
            done_b = self._done_bwd.setdefault(t, set())
            inputs = self._inputs.setdefault(t, {})
            grads = self._mb_grads.setdefault(t, {})
            for mbi in range(self.M):
                if self._stop.is_set():
                    return
                if mbi in done_f or mbi not in inputs:
                    continue
                if prog.last:
                    tgt = self._targets.get(t, {}).get(mbi)
                    if tgt is None:
                        continue
                    targets = jnp.asarray(
                        np.rint(tgt).astype(np.int32).reshape(
                            self.mb_size, self.seq_len))
                    if self._clock is not None:
                        self._clock.set("compute",
                                        corr=self._mb_corr.get((t, mbi), 0))
                    t0 = time.perf_counter()
                    ce_sum, d_params, d_x = prog.loss_bwd(
                        self.params, self._decode_input(inputs[mbi]),
                        targets)
                    ce_sum = float(ce_sum)
                    self.stats["busy_s"] += time.perf_counter() - t0
                    grads[mbi] = d_params
                    done_f.add(mbi)
                    done_b.add(mbi)
                    self.stats["fwd"] += 1
                    self.stats["bwd"] += 1
                    if not prog.first:
                        self._ship("bwd", t, mbi, np.asarray(d_x))
                    self._ship("loss", t, mbi,
                               np.asarray([ce_sum], np.float32))
                else:
                    if self._clock is not None:
                        self._clock.set("compute",
                                        corr=self._mb_corr.get((t, mbi), 0))
                    t0 = time.perf_counter()
                    h_out = prog.fwd(
                        self.params, self._decode_input(inputs[mbi]))
                    h_out = np.asarray(h_out)
                    self.stats["busy_s"] += time.perf_counter() - t0
                    done_f.add(mbi)
                    self.stats["fwd"] += 1
                    self._ship("fwd", t, mbi, h_out)
                if self.throttle > 0:
                    self._throttle_sleep()
                progressed = True
            if not prog.last:
                gin = self._gin.setdefault(t, {})
                for mbi in range(self.M):
                    if self._stop.is_set():
                        return
                    if mbi in done_b or mbi not in done_f or mbi not in gin:
                        continue
                    g = jnp.asarray(gin[mbi].reshape(self._act_shape()))
                    if self._clock is not None:
                        self._clock.set("compute",
                                        corr=self._mb_corr.get((t, mbi), 0))
                    t0 = time.perf_counter()
                    d_params, d_x = prog.bwd(
                        self.params, self._decode_input(inputs[mbi]), g)
                    if not prog.first:
                        d_x = np.asarray(d_x)
                    self.stats["busy_s"] += time.perf_counter() - t0
                    grads[mbi] = d_params
                    done_b.add(mbi)
                    self.stats["bwd"] += 1
                    if not prog.first:
                        self._ship("bwd", t, mbi, d_x)
                    if self.throttle > 0:
                        self._throttle_sleep()
                    progressed = True
            if len(done_b) == self.M:
                self._apply_update(t)
                progressed = True

    def _apply_update(self, t: int) -> None:
        grads = self._mb_grads[t]
        acc = grads[0]
        for mbi in range(1, self.M):  # mb order: deterministic sum
            acc = jax.tree.map(jnp.add, acc, grads[mbi])
        if self._clock is not None:
            self._clock.set("compute")
        t0 = time.perf_counter()
        self.params, self.opt_state = self.programs.update(
            self.params, self.opt_state, acc)
        jax.block_until_ready(jax.tree.leaves(self.params)[0])
        self.stats["busy_s"] += time.perf_counter() - t0
        for mbi in range(self.M):
            self.applied_log.append((t, mbi))
        self.stats["updates"] += 1
        self.step = t + 1
        # straggler telemetry: per-update BUSY milliseconds (this stage's
        # own compute, throttle included), NOT wall time — every stage
        # shares the pipeline's wall cadence, so only busy time can tell
        # the coordinator WHICH stage is the straggler
        busy_ms = (self.stats["busy_s"] - self._busy_at_update) * 1e3
        self._busy_at_update = self.stats["busy_s"]
        self._ewma.update(busy_ms)
        if self.recorder is not None:
            self.recorder.event("update", corr=0, step=self.step,
                                busy_ms=round(busy_ms, 3))
            # correlation keys for the retired step are done with
            self._mb_corr = {k: v for k, v in self._mb_corr.items()
                             if k[0] >= self.step - self.retain_steps}
        for d in (self._inputs, self._targets, self._gin, self._mb_grads,
                  self._done_fwd, self._done_bwd):
            d.pop(t, None)
        floor = self.step - self.retain_steps
        for dirn in self._retained.values():
            for key in [k for k in dirn if k[0] < floor]:
                del dirn[key]
        self._save_ckpt()
        self.coord.report(self.watermark, self.step, self._ewma.value)
        if self.step_hook is not None:
            self.step_hook(self, self.step)

    # ---------------------------------------------------------- durability
    def _flat_state(self) -> Tuple[np.ndarray, np.ndarray]:
        pflat, _ = ravel_pytree(self.params)
        oflat, _ = ravel_pytree(self.opt_state)
        return (np.asarray(pflat, np.float32), np.asarray(oflat, np.float32))

    def _save_ckpt(self) -> None:
        if not self.ckpt_dir or self._superseded:
            return
        if self._clock is not None:
            self._clock.set("ckpt")
        pflat, oflat = self._flat_state()
        save_stage_checkpoint(
            self.ckpt_dir, stage=self.stage, step=self.step,
            watermark=self.watermark, lo=self.lo, hi=self.hi,
            params_flat=pflat, opt_flat=oflat)
        if self._clock is not None:
            # back to compute until the loop's next wait classification —
            # attribution stays exclusive (the ckpt stretch just closed)
            self._clock.set("compute")

    def restore(self, manifest=None) -> None:
        """Restore params + optimizer + watermark from this stage's
        checkpoint. With a ``FleetManifest``, the checkpoint must cover the
        manifest's promise for this member: matching range and a watermark
        at or past the recorded apply seq — state BEHIND the promise is
        refused (the drill's restore contract, applied to stages)."""
        if self.stage is None or not self.ckpt_dir:
            raise ValueError("restore needs an assigned stage and ckpt_dir")
        meta, pflat, oflat = load_stage_checkpoint(self.ckpt_dir)
        if int(meta["stage"]) != self.stage:
            raise ValueError(
                f"checkpoint in {self.ckpt_dir} is for stage "
                f"{meta['stage']}, this member serves {self.stage}")
        if manifest is not None:
            rec = manifest.entry_for(self.rank)
            if (rec.lo, rec.hi) != (self.lo, self.hi):
                from distributed_ml_pytorch_tpu.coord.manifest import (
                    ManifestError,
                )

                raise ManifestError(
                    f"manifest assigns rank {self.rank} range "
                    f"[{rec.lo},{rec.hi}) but stage {self.stage} owns "
                    f"[{self.lo},{self.hi})")
            if int(meta["watermark"]) < rec.apply_seq:
                raise ValueError(
                    f"stage checkpoint watermark {meta['watermark']} is "
                    f"BEHIND the manifest's promised apply seq "
                    f"{rec.apply_seq} — refusing to restore stale state")
        flat, p_unravel = ravel_pytree(self.params)
        _, o_unravel = ravel_pytree(self.opt_state)
        if pflat.size != flat.size:
            raise ValueError(
                f"stage checkpoint holds {pflat.size} params, the stage "
                f"tree wants {flat.size}")
        self.params = p_unravel(jnp.asarray(pflat))
        self.opt_state = o_unravel(jnp.asarray(oflat))
        self.step = int(meta["step"])

    def _do_snapshot(self, snapshot_id: int, map_version: int) -> None:
        """Snapshot-barrier participation: checkpoint NOW (the serve loop
        sits at a consistent boundary between compute) and report the
        range + watermark into the coordinator's FleetManifest."""
        if self.stage is None or self._superseded:
            return
        self._save_ckpt()
        self.stats["snapshots"] += 1
        self.coord.snapshot_done(
            snapshot_id, map_version, self.lo, self.hi,
            apply_seq=self.watermark, push_count=self.step)

    # ---------------------------------------------------------- speculation
    def _apply_speculation(self, task_id: int, victim_rank: int,
                           from_step: int) -> None:
        """Standby side of a SpeculateTask: adopt the victim's stage from
        its checkpoint and race it (the coordinator's placement flip is
        the first-wins dedup; the victim goes passive on seeing it)."""
        if self.stage is not None or not self.ckpt_root:
            return  # assigned members just note it; supersession does the rest
        p = self._placement
        entry = p.entry_for_rank(victim_rank) if p is not None else None
        if entry is None:
            return
        victim_stage = entry.stage
        ckpt_dir = os.path.join(self.ckpt_root, f"stage{victim_stage}")
        self._install_stage(victim_stage)
        self.ckpt_dir = ckpt_dir
        try:
            self.restore()
        except ValueError:
            _LOGGER.warning(
                "speculation: standby rank %d cannot read stage %d "
                "checkpoint — staying idle", self.rank, victim_stage)
            self.stage = None
            self.params = None
            self.opt_state = None
            return
        _LOGGER.info(
            "speculation task %d: standby rank %d adopted stage %d at "
            "watermark %d (racing rank %d)",
            task_id, self.rank, victim_stage, self.watermark, victim_rank)
        self.coord.stage_ready(self.stage, self.watermark)

    def _wait_state(self) -> str:
        """Classify what the serve loop is ABOUT to wait on (called when
        :meth:`_pump` found nothing computable): missing activation/data
        inputs -> ``wait-act``; all forwards done but cotangents missing
        -> ``wait-grad``; unassigned / superseded / pre-placement ->
        ``idle``. Exclusive states are what makes bubble attribution sum
        to the wall clock (``analysis/timeline.py``)."""
        if self.stage is None or self._superseded or self._placement is None:
            return "idle"
        t = self.step
        done_f = self._done_fwd.get(t, set())
        if len(done_f) >= self.M:
            return "idle" if self.programs.last else "wait-grad"
        if (self.programs is not None and not self.programs.last
                and len(self._done_bwd.get(t, set())) < len(done_f)):
            # forwards still owed AND cotangents outstanding: the schedule
            # is blocked on the downstream neighbor first (1F1B drain)
            return "wait-grad"
        return "wait-act"

    # ------------------------------------------------------------ serve loop
    def run(self, timeout: Optional[float] = None) -> None:
        """Serve until ``stop()``/``crash()`` (or ``timeout``). A crash of
        the serve logic itself is recorded in ``self.error`` and stops the
        member — a silently dead thread would wedge the whole pipeline
        with no diagnosis. On exit the flight recorder (when attached)
        flushes its attribution and, for a death/crash, dumps the ring to
        ``obs_dir`` — the MTTR number's black box (ISSUE 12)."""
        try:
            self._run(timeout)
        except Exception as e:  # noqa: BLE001 — surfaced via self.error
            self.error = repr(e)
            _LOGGER.exception("stage %s member rank %d serve loop died",
                              self.stage, self.rank)
            self._stop.set()
        finally:
            if self.recorder is not None:
                if self._clock is not None:
                    self._clock.flush()
                # the transport's counters join the ring BEFORE the dump,
                # so the flight file carries the wire attribution inputs
                emit = getattr(self.transport, "emit_wire_stats", None)
                if emit is not None:
                    emit()
                reason = ("error" if self.error is not None
                          else "death" if self._crashed else "stop")
                if self.obs_dir:
                    obs.flight_dump(self.recorder, self.obs_dir, reason)

    def _run(self, timeout: Optional[float] = None) -> None:
        deadline = None if timeout is None else time.monotonic() + timeout
        self.coord.join(timeout=30)
        if self.stage is not None:
            self.coord.stage_ready(self.stage, self.watermark)
        last_announce = time.monotonic()
        while not self._stop.is_set():
            now = time.monotonic()
            if deadline is not None and now >= deadline:
                break
            if self._clock is not None:
                self._clock.set(self._wait_state())
            msg = self.transport.recv(timeout=0.02)
            if msg is not None:
                try:
                    self.handle(*msg)
                except (ValueError, IndexError, OverflowError):
                    pass  # malformed frame: drop, never die
            self._drain_mailboxes()
            self._pump()
            if (self.stage is not None and not self._superseded
                    and now - last_announce > 1.0):
                # self-heal: if the placement does not name us (a dropped
                # StageReady, or our lease briefly expired), re-announce
                p = self._placement
                e = (p.entries[self.stage] if p is not None
                     and self.stage < len(p.entries) else None)
                if e is None or e.rank != self.rank:
                    self.coord.stage_ready(self.stage, self.watermark)
                last_announce = now


# -------------------------------------------------------------------- driver

class MpmdDriver:
    """The data feeder + loss collector of an MPMD pipeline fleet.

    Ships every step's microbatch tokens to stage 0 and targets to the
    last stage up front (``ActivationShip`` kinds 1/2 — the per-channel
    send sequence is then a pure function of the dataset, which is what
    lets the chaos layer fault these channels with byte-identical logs),
    retains the bodies, and re-ships to restarted endpoints on placement
    incarnation changes. Collects per-microbatch ``ce_sum`` reports and
    folds them into the per-step mean loss (``pipeline.py`` convention).
    """

    def __init__(self, transport: Transport, coord, n_stages: int,
                 n_microbatches: int,
                 recorder: Optional["obs.SpanRecorder"] = None,
                 obs_dir: Optional[str] = None):
        self.transport = transport
        self.coord = coord
        self.S = int(n_stages)
        self.M = int(n_microbatches)
        self._placement = None
        self._mu = threading.Lock()
        self._placement_mail = None
        if getattr(coord, "on_stage_assign", None) is None:
            coord.on_stage_assign = self._note_placement
        self._tokens: Dict[Tuple[int, int], np.ndarray] = {}
        self._targets: Dict[Tuple[int, int], np.ndarray] = {}
        self._ce: Dict[Tuple[int, int], float] = {}
        self.losses: List[float] = []
        self.step_times: List[float] = []
        self.stats = {"reshipped": 0, "dup_loss_dropped": 0,
                      "send_failed": 0}
        # --- flight recorder (ISSUE 12): the driver MINTS the microbatch
        # correlation ids — every (step, mb) gets one id that rides the
        # envelope through every stage's fwd/bwd and back on the loss
        # report, which is what lets the timeline analyzer stitch one
        # microbatch's whole journey. The map is PRUNED as steps complete
        # (corr_retain_steps behind the frontier — comfortably past the
        # stages' own retain window) so a day-long run cannot grow it
        # without bound; a re-ship of an already-pruned (step, mb) mints a
        # fresh id, which the analyzer just reads as a new unit of work.
        self.recorder = recorder
        self.obs_dir = obs_dir
        self.corr_retain_steps = 8
        self._mb_corr: Dict[Tuple[int, int], int] = {}

    def _note_placement(self, placement) -> None:
        with self._mu:
            if (self._placement_mail is None
                    or placement.version > self._placement_mail.version):
                self._placement_mail = placement

    def _rank_of_stage(self, stage: int) -> Optional[int]:
        p = self._placement
        if p is None:
            return None
        rank = p.entries[stage].rank
        return rank if rank >= 0 else None

    def _send(self, dst: int, step: int, mbi: int, kind: int,
              body: np.ndarray) -> None:
        ver = self._placement.version if self._placement is not None else 0
        # driver ships tokens/targets — exact by contract, so the
        # registry's dense rung (codec 0, a passthrough) is the only one
        # this site may stamp
        cid, coded = codecs.encode_body(
            MessageCode.ActivationShip, body.ravel(), CODEC_DENSE)
        head = np.asarray(
            [*_split16(step), float(mbi), float(kind), *_split16(ver),
             float(cid)],
            np.float32)
        # one correlation id per (step, mb), minted at first ship and
        # reused by re-ships — the envelope carries it fleet-wide
        corr = self._mb_corr.get((step, mbi))
        if corr is None:
            corr = self._mb_corr[(step, mbi)] = obs.next_corr()
        try:
            with obs.corr_scope(corr):
                self.transport.send(
                    MessageCode.ActivationShip,
                    np.concatenate([head, coded]), dst=dst)
        except (OSError, ConnectionError, KeyError):
            self.stats["send_failed"] += 1

    def _retire_below(self, floor: int) -> None:
        """Drop replay/correlation state for steps retired past the
        restart-replay window. A restarted stage replays from its last
        checkpoint, at most ``corr_retain_steps`` behind the frontier —
        the driver must not hold every (step, mb) body it ever shipped."""
        if floor <= 0:
            return
        for store in (self._tokens, self._targets, self._ce):
            for key in [k for k in store if k[0] < floor]:
                del store[key]
        self._mb_corr = {k: v for k, v in self._mb_corr.items()
                         if k[0] >= floor}

    def _drain_placement(self) -> None:
        with self._mu:
            placement, self._placement_mail = self._placement_mail, None
        if placement is None:
            return
        from distributed_ml_pytorch_tpu.coord.stages import placement_deltas

        old, self._placement = self._placement, placement
        # inc_only: see placement_deltas — the driver never ships into a
        # vacancy, so only a true new life (changed incarnation) has
        # anything to replay, and the faulted burst channels stay
        # byte-identical across same-life re-admissions
        for e in placement_deltas(old, placement, inc_only=True):
            if e.stage == 0:
                store, kind = self._tokens, SHIP_TOKENS
            elif e.stage == self.S - 1:
                store, kind = self._targets, SHIP_TARGETS
            else:
                continue
            for (step, mbi), body in sorted(store.items()):
                if step * self.M + mbi < e.watermark:
                    continue
                self._send(e.rank, step, mbi, kind, body)
                self.stats["reshipped"] += 1

    def wait_ready(self, timeout: float = 30.0) -> bool:
        """Block until a placement with every stage assigned arrives."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            self._drain_placement()
            p = self._placement
            if p is not None and all(e.rank >= 0 for e in p.entries):
                return True
            time.sleep(0.02)
        return False

    def run(self, tokens_steps, targets_steps, *, timeout: float = 180.0,
            step_hook: Optional[Callable[[int, float], None]] = None,
            ) -> List[float]:
        """Feed ``steps`` of ``(M, mb, seq)`` microbatched data through the
        fleet; returns the per-step mean losses. Raises ``TimeoutError``
        if the fleet does not finish in time."""
        steps = len(tokens_steps)
        mb, seq = tokens_steps[0].shape[1], tokens_steps[0].shape[2]
        n_mask = mb * (seq - 1)
        self.coord.join(timeout=30)
        if not self.wait_ready():
            raise TimeoutError("driver: placement never fully assigned")
        first_rank = self._rank_of_stage(0)
        last_rank = self._rank_of_stage(self.S - 1)
        for t in range(steps):
            for mbi in range(self.M):
                tok = np.asarray(tokens_steps[t][mbi], np.float32).ravel()
                tgt = np.asarray(targets_steps[t][mbi], np.float32).ravel()
                self._tokens[(t, mbi)] = tok
                self._targets[(t, mbi)] = tgt
                self._send(first_rank, t, mbi, SHIP_TOKENS, tok)
                self._send(last_rank, t, mbi, SHIP_TARGETS, tgt)
        deadline = time.monotonic() + timeout
        next_step = 0
        while next_step < steps:
            if time.monotonic() >= deadline:
                raise TimeoutError(
                    f"driver: step {next_step}/{steps} never completed "
                    f"({len(self._ce)} ce reports held)")
            msg = self.transport.recv(timeout=0.05)
            self._drain_placement()
            if msg is not None:
                _sender, code, payload = msg
                if (code == MessageCode.ActivationShip and payload.size >= 8
                        and np.isfinite(payload[:7]).all()
                        and int(payload[3]) == SHIP_LOSS
                        and int(payload[6]) == CODEC_DENSE):
                    step = _join16(payload[0], payload[1])
                    mbi = int(payload[2])
                    body = payload[7:]
                    if (step, mbi) in self._ce:
                        self.stats["dup_loss_dropped"] += 1
                    elif (0 <= step < steps and 0 <= mbi < self.M
                          and np.isfinite(body[0])):
                        self._ce[(step, mbi)] = float(body[0])
            while next_step < steps and all(
                    (next_step, mbi) in self._ce for mbi in range(self.M)):
                ce = sum(self._ce[(next_step, mbi)]
                         for mbi in range(self.M))
                loss = ce / float(n_mask * self.M)
                # the training curve IS run()'s product: one entry per
                # step of THIS call, bounded by the caller's steps arg
                self.losses.append(loss)  # distcheck: ignore[DC503] losses/step_times: bounded by run()'s steps argument — the curve is the return value
                self.step_times.append(time.monotonic())
                if self.recorder is not None:
                    self.recorder.event("step-complete", corr=0,
                                        step=next_step,
                                        loss=round(float(loss), 6))
                if step_hook is not None:
                    step_hook(next_step, loss)
                next_step += 1
                self._retire_below(next_step - self.corr_retain_steps)
        if self.recorder is not None and self.obs_dir:
            emit = getattr(self.transport, "emit_wire_stats", None)
            if emit is not None:
                emit()
            obs.flight_dump(self.recorder, self.obs_dir, "stop")
        return self.losses
