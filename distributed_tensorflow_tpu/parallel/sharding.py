"""Parameter/activation sharding rules over the named mesh.

Behavioral model: the reference stack's variable-placement machinery —
``replica_device_setter``'s round-robin PS placement
($TF/python/training/device_setter.py:129,:32), ``ShardedVariable`` +
partitioners ($TF/python/distribute/sharded_variable.py:843,:84,:115,:176),
and DTensor's ``Layout``/``Mesh`` (SURVEY.md §3.1) — re-imagined the XLA way:
a *sharding rule* maps a parameter's tree path to a ``PartitionSpec``, and
``jax.jit`` compiles the data movement.  No placement graph, no per-variable
device strings.

Three levels of API:

- ``ShardingRules``: ordered (regex → PartitionSpec) table, first match wins
  (t5x-style logical-axis rules, flattened to concrete mesh axes).
- ``fsdp_sharding``: automatic ZeRO-3-style rule — shard the largest
  divisible dimension of every parameter over the ``fsdp`` axis.
- TF-compatible partitioners (``FixedShardsPartitioner`` & friends) for the
  embedding path (``parallel.embedding``), which is where PS-style explicit
  sharding genuinely survives on TPU.
"""

from __future__ import annotations

import logging
import math
import re
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

P = PartitionSpec
PyTree = Any

logger = logging.getLogger(__name__)


def _path_str(path) -> str:
    """Render a jax tree path as 'a/b/c'."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


class ShardingRules:
    """Ordered (pattern → PartitionSpec) rules; first match wins.

    Patterns are regexes matched with ``re.search`` against the '/'-joined
    parameter path (e.g. ``"encoder/layers_3/attention/query/kernel"``).
    Unmatched parameters are replicated — the safe default that mirrors
    MirroredVariable semantics ($TF/python/distribute/values.py:1196).
    """

    def __init__(self, rules: Sequence[Tuple[str, PartitionSpec]] = ()):
        self._rules = [(re.compile(pat), spec) for pat, spec in rules]

    def extended(self, rules: Sequence[Tuple[str, PartitionSpec]]) -> "ShardingRules":
        out = ShardingRules()
        out._rules = [(re.compile(p), s) for p, s in rules] + list(self._rules)
        return out

    def spec_for(self, path: str, shape: Tuple[int, ...] = (),
                 mesh: Optional[Mesh] = None) -> PartitionSpec:
        """First matching rule, fitted to ``shape`` (and, given ``mesh``,
        to what its axis sizes divide — see ``_fit_spec``)."""
        for pat, spec in self._rules:
            if pat.search(path):
                return _fit_spec(spec, shape, mesh, path)
        return P()

    def shardings_for(self, mesh: Mesh, tree: PyTree) -> PyTree:
        """Pytree of NamedShardings for a pytree of arrays/ShapeDtypeStructs."""

        def _one(path, leaf):
            shape = tuple(getattr(leaf, "shape", ()) or ())
            return NamedSharding(
                mesh, self.spec_for(_path_str(path), shape, mesh))

        return jax.tree_util.tree_map_with_path(_one, tree)


def spec_ways(mesh: Mesh, *entries) -> int:
    """How many ways the given PartitionSpec entries split a dimension (or,
    given a whole spec's entries, an array) on ``mesh``: the product of the
    sizes of the axes they name."""
    return math.prod(
        mesh.shape.get(a, 1) for e in entries if e
        for a in ((e,) if isinstance(e, str) else e))


def _fit_spec(spec: PartitionSpec, shape: Tuple[int, ...],
              mesh: Optional[Mesh] = None, path: str = "") -> PartitionSpec:
    """Pad/trim a PartitionSpec to a concrete rank (extra dims replicated).

    With ``mesh``, a dimension the named axes do not divide stays whole:
    the rule tables are written for tiny test shapes and for real ones
    (GPT-2's 50257-row ``wte`` under ``tensor=2``), and jit's
    ``out_shardings`` refuses an uneven split.  Nothing changes on meshes
    whose axes divide (or have size 1), so one-chip runs and their
    checkpoints are untouched; the un-split leaf is logged.
    """
    if not shape:
        return P()
    entries = list(spec)[: len(shape)]
    if mesh is not None:
        for d, entry in enumerate(entries):
            ways = spec_ways(mesh, entry)
            if shape[d] % ways:
                logger.info(
                    "sharding rule %s for %r: dim %d (size %d) is not "
                    "divisible by %d; keeping it whole", spec, path, d,
                    shape[d], ways)
                entries[d] = None
    return P(*entries)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, *batch_axes: str) -> NamedSharding:
    """Input-batch sharding: leading dim split over data-parallel axes.

    Default splits over ``('data', 'fsdp')`` — the auto-shard role of TF's
    DistributedDataset ($TF/python/distribute/input_lib.py:729).
    """
    axes = batch_axes or ("data", "fsdp")
    names = tuple(a for a in axes if a in mesh.shape)
    return NamedSharding(mesh, P(names))


def fsdp_sharding(
    mesh: Mesh,
    tree: PyTree,
    *,
    axis: str = "fsdp",
    min_size: int = 2**14,
) -> PyTree:
    """ZeRO-3-style automatic sharding: for each parameter, shard the largest
    dimension divisible by the axis size; small params stay replicated.

    This subsumes the dense-parameter half of the reference's PS placement
    (SURVEY.md §4.2): instead of living on ps tasks, parameters live sharded
    across the mesh and are all-gathered by XLA just-in-time.
    """
    size = mesh.shape.get(axis, 1)

    def _one(leaf):
        shape = tuple(getattr(leaf, "shape", ()) or ())
        if size <= 1 or not shape or int(np.prod(shape)) < min_size:
            return NamedSharding(mesh, P())
        # Largest divisible dim, preferring later (usually feature) dims.
        best = None
        for d in range(len(shape)):
            if shape[d] % size == 0:
                if best is None or shape[d] >= shape[best]:
                    best = d
        if best is None:
            return NamedSharding(mesh, P())
        entries: list = [None] * (best + 1)
        entries[best] = axis
        return NamedSharding(mesh, P(*entries))

    return jax.tree.map(_one, tree)


def apply_shardings(tree: PyTree, shardings: PyTree) -> PyTree:
    """device_put a pytree according to a matching pytree of shardings."""
    return jax.tree.map(lambda x, s: jax.device_put(x, s), tree, shardings)


# -- TF-compatible partitioners (sharded_variable.py:84,:115,:176) -----------

class Partitioner:
    """Returns the number of shards per dimension for a variable shape."""

    def __call__(self, shape: Sequence[int], dtype=None) -> Sequence[int]:
        raise NotImplementedError


class FixedShardsPartitioner(Partitioner):
    """Always ``num_shards`` along dim 0 ($TF sharded_variable.py:84)."""

    def __init__(self, num_shards: int):
        self.num_shards = num_shards

    def __call__(self, shape, dtype=None):
        return [min(self.num_shards, shape[0])] + [1] * (len(shape) - 1)


class MinSizePartitioner(Partitioner):
    """As many shards as possible with each shard >= min_shard_bytes
    ($TF sharded_variable.py:115)."""

    def __init__(self, min_shard_bytes: int = 256 << 10, max_shards: int = 1,
                 bytes_per_string: int = 16):
        self.min_shard_bytes = min_shard_bytes
        self.max_shards = max_shards

    def __call__(self, shape, dtype=None):
        itemsize = np.dtype(dtype or np.float32).itemsize
        total = int(np.prod(shape)) * itemsize
        shards = max(1, min(self.max_shards, total // max(1, self.min_shard_bytes),
                            shape[0]))
        return [int(shards)] + [1] * (len(shape) - 1)


class MaxSizePartitioner(Partitioner):
    """As few shards as possible with each shard <= max_shard_bytes
    ($TF sharded_variable.py:176)."""

    def __init__(self, max_shard_bytes: int, max_shards: Optional[int] = None,
                 bytes_per_string: int = 16):
        self.max_shard_bytes = max_shard_bytes
        self.max_shards = max_shards

    def __call__(self, shape, dtype=None):
        itemsize = np.dtype(dtype or np.float32).itemsize
        total = int(np.prod(shape)) * itemsize
        shards = int(np.ceil(total / max(1, self.max_shard_bytes)))
        if self.max_shards:
            shards = min(shards, self.max_shards)
        return [max(1, min(shards, shape[0]))] + [1] * (len(shape) - 1)


# -- canonical transformer rules (used by gpt2/bert model families) ----------

def transformer_rules() -> ShardingRules:
    """Megatron-style TP rules over the ``tensor`` axis + fsdp fallback.

    Attention qkv/out and MLP in/out projections split over ``tensor``;
    embeddings split over (``tensor``) vocab dim; everything else replicated
    across ``tensor`` and sharded over ``fsdp`` where divisible.
    """
    return ShardingRules(
        [
            (r"(embedding|wte|word_embeddings)/(embedding|kernel)", P("tensor", "fsdp")),
            (r"(query|key|value|qkv|c_attn)/kernel", P("fsdp", "tensor")),
            (r"(attention_out|c_proj|out_proj|attn/out)/kernel", P("tensor", "fsdp")),
            (r"(mlp/(fc_in|c_fc|wi|intermediate)|fc1)/kernel", P("fsdp", "tensor")),
            (r"(mlp/(fc_out|wo|output)|fc2)/kernel", P("tensor", "fsdp")),
            (r"(lm_head|logits|mlm)/kernel", P("fsdp", "tensor")),
            (r"bias$", P()),
            (r"(scale|layernorm|ln_\d|norm)", P()),
        ]
    )
