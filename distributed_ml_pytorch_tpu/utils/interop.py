"""Torch→JAX weight migration for reference-architecture models.

A user of the reference (torch CNNs, ``example/models.py:5-49``) switching to
this framework brings trained ``state_dict`` checkpoints. This module maps
them onto the flax param trees of ``models/cnn.py``:

- conv kernels: torch ``(O, I, kH, kW)`` → flax ``(kH, kW, I, O)``;
- dense kernels: torch ``(out, in)`` → flax ``(in, out)``;
- biases: unchanged.

Matching contract (stated precisely because it decides correctness):
tensors pair **greedily by transposed shape**, with the flax leaves visited
in natural layer order (numeric-aware, so ``conv10`` follows ``conv2``) and
torch tensors in ``state_dict`` insertion (= definition) order. Layers with
unique shapes always pair correctly; within a group of identically-shaped
layers, correctness relies on both sides enumerating those layers in the
same relative order — true for sequential CNNs like the reference zoo.
Counts and shapes are validated, so a wrong-architecture state_dict raises
rather than half-loading. BatchNorm checkpoints are rejected outright
(running stats live outside flax ``params``; this framework's ResNets use
stateless GroupNorm instead, ``models/resnet.py``).

The converter takes plain numpy-convertible tensors, so callers can feed a
``torch.load(...)`` state_dict without this module importing torch.
:func:`install_flax_alexnet_init` is the other direction, for the one
architecture the parity tests compare; it imports torch when called.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Mapping

import jax
import numpy as np

Pytree = Any


def _to_numpy(t) -> np.ndarray:
    if hasattr(t, "detach"):  # torch tensor without importing torch
        t = t.detach().cpu().numpy()
    return np.asarray(t, dtype=np.float32)


def _convert_leaf(path_names, flax_leaf: np.ndarray, torch_arr: np.ndarray) -> np.ndarray:
    """Transpose one torch tensor into the flax leaf's layout."""
    name = path_names[-1]
    if name == "kernel" and torch_arr.ndim == 4:  # conv OIHW → HWIO
        out = np.transpose(torch_arr, (2, 3, 1, 0))
    elif name == "kernel" and torch_arr.ndim == 2:  # linear (out,in) → (in,out)
        out = np.transpose(torch_arr, (1, 0))
    else:  # bias / anything already layout-free
        out = torch_arr
    if out.shape != flax_leaf.shape:
        raise ValueError(
            "shape mismatch at {}: torch {} (→ {}) vs flax {}".format(
                "/".join(path_names), torch_arr.shape, out.shape, flax_leaf.shape
            )
        )
    return out


def load_torch_state_dict(
    flax_params: Pytree,
    state_dict: Mapping[str, Any],
    flatten_shape: tuple | None = None,
) -> Pytree:
    """Return a params pytree shaped like ``flax_params`` filled from a torch
    ``state_dict`` (reference-architecture CNNs).

    ``flax_params`` is a template (e.g. ``model.init(...)['params']``) that
    provides the target structure and shapes. Entry counts must match
    exactly; shapes are validated leaf-by-leaf after layout transposition.

    ``flatten_shape=(C, H, W)`` handles the conv→dense flatten seam: torch
    flattens NCHW activations to ``C·H·W`` columns while this framework's
    NHWC models flatten to ``H·W·C``, so the FIRST dense weight whose input
    dimension equals ``C·H·W`` gets its columns permuted accordingly.
    Models whose conv output is 1×1 spatial (the reference AlexNet) need no
    permutation; LeNet (16×5×5 flatten) does — pass ``(16, 5, 5)``.
    """
    bn_keys = [
        k for k in state_dict
        if k.endswith(("running_mean", "running_var", "num_batches_tracked"))
    ]
    if bn_keys:
        raise ValueError(
            "BatchNorm checkpoints are not supported (running stats live "
            "outside flax params, and (C,)-shaped gamma/beta would pair "
            f"ambiguously); found: {bn_keys[:3]}..."
        )
    tensors = [_to_numpy(v) for v in state_dict.values()]
    if flatten_shape is not None:
        c, h, w = flatten_shape
        n_in = c * h * w
        for j, t in enumerate(tensors):
            if t.ndim == 2 and t.shape[1] == n_in:
                tensors[j] = (
                    t.reshape(t.shape[0], c, h, w)
                    .transpose(0, 2, 3, 1)
                    .reshape(t.shape[0], n_in)
                )
                break
        else:
            raise ValueError(
                f"flatten_shape {flatten_shape} (C*H*W = {n_in}) matches no "
                "dense weight's input dimension — check the conv output shape"
            )
    flat, treedef = jax.tree_util.tree_flatten_with_path(flax_params)
    if len(tensors) != len(flat):
        raise ValueError(
            f"state_dict has {len(tensors)} tensors but the flax model has "
            f"{len(flat)} params — architectures differ"
        )

    def names_of(path):
        return [getattr(k, "key", str(k)) for k in path]

    def natural_key(path):
        # numeric-aware ordering so conv10 follows conv2 — keeps the relative
        # order of identically-shaped layers aligned with torch's definition
        # order for sequential models
        joined = "/".join(names_of(path))
        return [
            int(tok) if tok.isdigit() else tok
            for tok in re.split(r"(\d+)", joined)
        ]

    order = sorted(range(len(flat)), key=lambda i: natural_key(flat[i][0]))

    # greedy pairing: each flax leaf (in natural layer order) takes the FIRST
    # unused torch tensor (in definition order) whose transposed shape fits —
    # unique shapes pair exactly; identical-shape groups pair positionally
    used = [False] * len(tensors)
    out_leaves: list = [None] * len(flat)
    for i in order:
        path, leaf = flat[i]
        names = names_of(path)
        # _convert_leaf only reads the flax leaf's shape; fetch the host copy
        # once per leaf, not once per candidate tensor probe
        leaf_np = np.asarray(leaf)
        converted = None
        for j in range(len(tensors)):
            if used[j]:
                continue
            try:
                converted = _convert_leaf(names, leaf_np, tensors[j])
            except ValueError:
                continue
            used[j] = True
            break
        if converted is None:
            raise ValueError(
                "no state_dict tensor matches flax param {} with shape {}".format(
                    "/".join(names), leaf_np.shape
                )
            )
        out_leaves[i] = converted
    return jax.tree_util.tree_unflatten(treedef, out_leaves)


def install_flax_alexnet_init(tmodel, flax_params) -> None:
    """Copy a flax AlexNet init into the torch AlexNet (the inverse of
    :func:`load_torch_state_dict`, specialized to the reference
    architecture): conv kernels (kH, kW, I, O) → (O, I, kH, kW), the
    classifier (in, out) → (out, in), biases as-is. Layer order is
    structural (conv1..conv5, classifier), so no shape-matching heuristics
    are needed."""
    import torch

    convs = [m for m in tmodel if isinstance(m, torch.nn.Conv2d)]
    linears = [m for m in tmodel if isinstance(m, torch.nn.Linear)]
    names = [f"conv{i}" for i in range(1, len(convs) + 1)]
    with torch.no_grad():
        # np.array(copy=True): jax exports read-only buffers and
        # torch.from_numpy warns on non-writable sources
        as_t = lambda a: torch.from_numpy(np.array(a, np.float32, copy=True))
        for name, m in zip(names, convs):
            m.weight.copy_(as_t(
                np.asarray(flax_params[name]["kernel"]).transpose(3, 2, 0, 1)))
            m.bias.copy_(as_t(flax_params[name]["bias"]))
        (lin,) = linears
        lin.weight.copy_(as_t(np.asarray(flax_params["classifier"]["kernel"]).T))
        lin.bias.copy_(as_t(flax_params["classifier"]["bias"]))
