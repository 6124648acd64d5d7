"""Multi-table embedding configuration — the TPUEmbedding config surface.

Behavioral model: ``TPUEmbedding``'s ``TableConfig``/``FeatureConfig``
($TF/python/tpu/tpu_embedding_v2_utils.py:1319,:1538; tpu_embedding_v2.py:76
— SURVEY.md §4.4): N features map onto M shared tables, each table carries
its own optimizer settings and combiner, tables are sharded across chips and
updated on-device.

TPU-native design:

- Each distinct ``TableConfig`` becomes one row-sharded ``ShardedEmbed``
  living on the ``expert`` mesh axis by default (the reference's ps-shard
  axis for embeddings; dense compute never shards over it).  Features
  sharing a table share parameters, exactly like TPUEmbedding.
- Per-table optimizers are ``optax.multi_transform`` branches keyed by a
  path→table labeling of the parameter tree — the "optimizer runs on-device
  per shard" semantics fall out of the sharding rule covering optimizer
  state too (train_lib.build_state_and_step).
- Multi-valent features combine with the table's ``combiner`` (sum/mean),
  matching the TF surface.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from jax.sharding import Mesh, PartitionSpec as P

from distributed_tensorflow_tpu.parallel.embedding import ShardedEmbed
from distributed_tensorflow_tpu.parallel.sharding import ShardingRules, _path_str


@dataclasses.dataclass(frozen=True)
class TableConfig:
    """One embedding table (tpu_embedding_v2_utils.py:1319 equivalent).

    ``optimizer`` is an optax transformation applied to this table's
    parameters *instead of* the model default (None keeps the default) —
    the per-table-optimizer role of TPUEmbedding's per-table slot variables.
    """

    vocabulary_size: int
    dim: int
    name: str
    # sum | mean, for multi-valent features.  Default "mean" matches the
    # modeled TPUEmbedding TableConfig default (tpu_embedding_v2_utils.py:
    # 1319), so mechanically-ported configs keep their pooling semantics.
    combiner: str = "mean"
    optimizer: Optional[optax.GradientTransformation] = None
    # Stored-row dtype (TPUEmbedding reduced-precision tables role).
    # bfloat16 halves the gather/param bytes of the lookup — slightly
    # SLOWER at emb_dim 64 on v5e in a round that predates the current chip
    # attachment (rows below the HBM granule), but it halves table param
    # bytes — while the optimizer keeps an f32
    # master copy + f32 moments (``f32_master_of``), so update math never
    # accumulates in bf16.  None = inherit MultiTableEmbedding.param_dtype.
    dtype: Any = None

    def __post_init__(self):
        if self.combiner not in ("sum", "mean"):
            raise ValueError(f"combiner must be sum|mean, got {self.combiner!r}")
        if not re.fullmatch(r"[A-Za-z0-9_]+", self.name):
            raise ValueError(f"table name {self.name!r} must be an identifier "
                             "(it becomes a parameter path component)")

    # frozen + eq by identity so two configs with equal fields are still two
    # distinct tables; sharing requires sharing the object (TF semantics).
    def __eq__(self, other):
        return self is other

    def __hash__(self):
        return id(self)


@dataclasses.dataclass(frozen=True)
class FeatureConfig:
    """One lookup feature bound to a table (tpu_embedding_v2_utils.py:1538)."""

    table: TableConfig
    name: str


def unique_tables(feature_configs: Sequence[FeatureConfig]) -> List[TableConfig]:
    """Distinct tables in first-appearance order (shared by identity)."""
    seen: Dict[int, TableConfig] = {}
    for fc in feature_configs:
        seen.setdefault(id(fc.table), fc.table)
    return list(seen.values())


class MultiTableEmbedding(nn.Module):
    """N features → M shared row-sharded tables (TPUEmbedding equivalent).

    ``__call__`` takes ``{feature_name: ids}`` — ids ``(B,)`` single-valent
    or ``(B, K)`` multi-valent (combined per the table's combiner) — and
    returns ``{feature_name: (B, dim)}`` activations.  Ids are hashed into
    the table with a mod (the standard trick for over-range ids).
    """

    feature_configs: Sequence[FeatureConfig]
    mesh: Optional[Mesh] = None
    axis: str = "expert"
    # batch dim of ids lives on the data axes while tables live on `axis`
    batch_axes: Sequence[str] = ("data", "fsdp")
    param_dtype: Any = jnp.float32

    def setup(self):
        by_name = {}
        for t in unique_tables(self.feature_configs):
            if t.name in by_name:
                raise ValueError(f"duplicate table name {t.name!r}")
            by_name[t.name] = ShardedEmbed(
                t.vocabulary_size,
                t.dim,
                mesh=self.mesh,
                axis=self.axis,
                batch_axes=tuple(self.batch_axes),
                param_dtype=t.dtype if t.dtype is not None
                else self.param_dtype,
                name=t.name,
            )
        self._tables = by_name
        names = [fc.name for fc in self.feature_configs]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate feature names in {names}")

    def __call__(self, features: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        # ONE sharded_lookup (all_gather + psum_scatter exchange) per TABLE,
        # not per feature: features sharing a table have their ids
        # concatenated, looked up together, and split back — the batched
        # dequeue of the modeled TPUEmbedding.  With 26 Criteo slots on 3
        # tables this is 3 exchanges per step instead of 26.
        by_table: Dict[str, List] = {}
        for fc in self.feature_configs:
            ids = jnp.asarray(features[fc.name]) % fc.table.vocabulary_size
            by_table.setdefault(fc.table.name, []).append((fc, ids))
        out = {}
        for tname, group in by_table.items():
            flat = jnp.concatenate(
                [ids.reshape(-1) for _, ids in group], axis=0
            )
            rows = self._tables[tname](flat)  # (sum_i B_i*K_i, D)
            offset = 0
            for fc, ids in group:
                n = ids.size
                act = rows[offset:offset + n].reshape(ids.shape + rows.shape[-1:])
                offset += n
                if act.ndim == 3:  # (B, K, D) multi-valent -> combine
                    act = (act.sum(axis=1) if fc.table.combiner == "sum"
                           else act.mean(axis=1))
                out[fc.name] = act
        return out


def multi_table_rules(
    feature_configs: Sequence[FeatureConfig], axis: str = "expert"
) -> ShardingRules:
    """Sharding rules placing every table (and its optimizer moments — the
    regex matches opt_state paths too) row-sharded on ``axis``."""
    # Same (^|/) boundary as multi_table_optimizer's labeling — the two
    # regexes must stay in lockstep or a table name that is a path suffix
    # of another module would shard params its optimizer doesn't own.
    return ShardingRules(
        [(rf"(^|/){t.name}/embedding$", P(axis))
         for t in unique_tables(feature_configs)]
    )


class MasterWeightState(NamedTuple):
    inner: Any
    master: Any  # f32 copy of the (low-precision) params


def f32_master_of(
    tx: optax.GradientTransformation,
) -> optax.GradientTransformation:
    """Master-weight wrapper for low-precision parameters.

    Keeps an f32 copy of the params in the optimizer state; ``tx`` runs
    entirely in f32 (grads are upcast, moments are f32 because they are
    initialized from the f32 master); the emitted update is
    ``(master_new - params)`` cast to the param dtype, so the stored
    low-precision params track the f32 master to within one rounding.  This
    is the same master-weight pattern the bf16 training policy uses for
    dense params (training/step), applied at the optimizer layer so
    bf16-stored embedding TABLES (gather-bandwidth halving) never
    accumulate updates in bf16.  The master shards with the params: its
    state path ends in the same ``.../embedding`` the table rules match.
    """

    def init(params):
        master = jax.tree.map(lambda p: p.astype(jnp.float32), params)
        return MasterWeightState(tx.init(master), master)

    def update(grads, state, params=None):
        if params is None:
            raise ValueError("f32_master_of requires params in update()")
        g32 = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        upd32, inner = tx.update(g32, state.inner, state.master)
        master = optax.apply_updates(state.master, upd32)
        emitted = jax.tree.map(
            lambda m, p: (m - p.astype(jnp.float32)).astype(p.dtype),
            master, params,
        )
        return emitted, MasterWeightState(inner, master)

    return optax.GradientTransformation(init, update)


def multi_table_optimizer(
    feature_configs: Sequence[FeatureConfig],
    default_tx: optax.GradientTransformation,
) -> optax.GradientTransformation:
    """Per-table optimizers over one parameter tree.

    Tables with ``optimizer`` set get their own optax branch; everything
    else (dense layers, tables without an override) uses ``default_tx``.
    Low-precision tables (``dtype=bfloat16``) get their branch wrapped in
    ``f32_master_of`` — with or without a per-table optimizer.
    """
    def needs_branch(t):
        return t.optimizer is not None or t.dtype not in (None, jnp.float32)

    def branch(t):
        tx = t.optimizer if t.optimizer is not None else default_tx
        if t.dtype not in (None, jnp.float32):
            tx = f32_master_of(tx)
        return tx

    tables = [t for t in unique_tables(feature_configs) if needs_branch(t)]
    transforms = {"__default__": default_tx}
    transforms.update({t.name: branch(t) for t in tables})
    patterns = [(t.name, re.compile(rf"(^|/){t.name}/embedding$")) for t in tables]

    def label_fn(params):
        def _one(path, _leaf):
            p = _path_str(path)
            for name, pat in patterns:
                if pat.search(p):
                    return name
            return "__default__"

        return jax.tree_util.tree_map_with_path(_one, params)

    return optax.multi_transform(transforms, label_fn)


def assert_table_residency(
    params,
    feature_configs: Sequence[FeatureConfig],
    *,
    axis: str = "expert",
) -> None:
    """Verify every table parameter is actually row-sharded over ``axis``
    (guards against a rule regression silently replicating a huge table)."""
    flat = {
        _path_str(path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
    }
    for t in unique_tables(feature_configs):
        matches = [
            (p, leaf) for p, leaf in flat.items()
            if re.search(rf"(^|/){t.name}/embedding$", p)
        ]
        if not matches:
            raise AssertionError(f"table {t.name!r} not found in params")
        for p, leaf in matches:
            spec = getattr(getattr(leaf, "sharding", None), "spec", None)
            if spec is None:
                raise AssertionError(f"{p}: no sharding attached")
            dim0 = spec[0] if len(spec) else None
            dim0 = dim0 if isinstance(dim0, tuple) else (dim0,)
            if axis not in dim0:
                raise AssertionError(
                    f"table param {p} is not row-sharded over {axis!r}: "
                    f"spec={spec}"
                )
