"""BERT-base pretraining — reference workload 3 (BASELINE.json: "BERT-base
pretraining — between-graph replication (TF1-style PS/worker)").

Distribution semantics: the reference ran this between-graph over a
PS/worker cluster (SURVEY.md §4.2) — every parameter transit crossed gRPC
RecvTensor.  TPU-native there is no PS: parameters are mesh-sharded (fsdp)
or replicated, and the launcher contract (`--job_name=ps` tasks park in
``server.join()``) is honored by ``train_lib`` so the reference's launch
scripts work unchanged.

Model notes:

- Post-LN encoder (original BERT), gelu, learned position + segment
  embeddings.
- Fused qkv projection ("qkv") for one big MXU matmul; names are chosen to
  hit ``transformer_rules``'s TP patterns (qkv/out_proj/fc1/fc2).
- Pretraining heads: MLM (tied to word embeddings) + NSP on [CLS];
  loss = masked CE + NSP CE, the standard pretraining objective.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from jax.sharding import Mesh

from distributed_tensorflow_tpu.data.pipeline import (
    mlm_max_predictions,
    synthetic_mlm,
)
from distributed_tensorflow_tpu.models import Workload
from distributed_tensorflow_tpu.ops import flash_attention
from distributed_tensorflow_tpu.ops.flash_attention import REMAT_POLICY
from distributed_tensorflow_tpu.parallel.ring_attention import ring_attention
from distributed_tensorflow_tpu.parallel.sharding import (
    P,
    ShardingRules,
    transformer_rules,
)


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    max_positions: int = 512
    type_vocab: int = 2
    d_model: int = 768
    n_layer: int = 12
    n_head: int = 12
    d_ff: int = 3072
    dropout: float = 0.1
    dtype: Any = jnp.bfloat16
    # scan-over-layers + per-layer remat (see GPT2Config for rationale)
    scan_layers: bool = True
    remat: bool = True
    # nn.scan unroll factor (see GPT2Config.scan_unroll: amortizes the
    # stacked-grad dynamic-update-slice writes across unrolled layers).
    scan_unroll: int = 1
    # Pallas fused attention (non-causal); attention-prob dropout runs
    # in-kernel (TPU PRNG), so the recipe matches dense.
    # Default is per-phase, set by make_workload from a measurement that
    # predates the current chip attachment (v5e, masked batches): dense won
    # at seq 128 (the (T,T) tile is small enough that XLA's fused dense path
    # beats the kernel's fixed overheads), flash won clearly at seq 512
    # (phase 2, where the score tile starts to dominate HBM traffic).  The
    # crossover is between those; make_workload enables flash at seq >= 256.
    use_flash_attention: bool = False
    # Ring attention kv-chunk size (0 = whole blocks; see GPT2Config)
    ring_chunk_size: int = 0

    @classmethod
    def base(cls, **kw):
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        return cls(vocab_size=256, max_positions=64, d_model=64, n_layer=2,
                   n_head=4, d_ff=128, dropout=0.0, **kw)


class EncoderLayer(nn.Module):
    cfg: BertConfig
    mesh: Optional[Mesh] = None
    deterministic: bool = True  # attribute (not call arg) so nn.scan can map

    @nn.compact
    def __call__(self, x, input_mask=None):
        cfg = self.cfg
        deterministic = self.deterministic
        d, h = cfg.d_model, cfg.n_head
        head_dim = d // h
        B, T, _unused = x.shape

        qkv = nn.Dense(3 * d, dtype=cfg.dtype, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, h, head_dim)
        k = k.reshape(B, T, h, head_dim)
        v = v.reshape(B, T, h, head_dim)
        if self.mesh is not None and self.mesh.shape.get("context", 1) > 1:
            # Long-context path: non-causal ring attention — sequence
            # sharded over the `context` axis, KV (and the key mask)
            # rotating on the ICI ring.  Exact attention (online softmax)
            # incl. attention-prob dropout (per-block dropout composes
            # exactly under the lse combine).
            drop = 0.0 if deterministic else cfg.dropout
            ctx = ring_attention(
                q, k, v, mesh=self.mesh, causal=False,
                chunk_size=cfg.ring_chunk_size or None,
                kv_mask=input_mask,
                dropout_rate=drop,
                dropout_rng=self.make_rng("dropout") if drop > 0 else None,
            ).reshape(B, T, d)
        elif cfg.use_flash_attention:
            # Attention-prob dropout runs IN-KERNEL (TPU PRNG, identical
            # keep mask regenerated in backward) — the flash path no longer
            # changes the training recipe vs dense.
            drop = 0.0 if deterministic else cfg.dropout
            ctx = flash_attention(
                q, k, v, causal=False, kv_mask=input_mask,
                dropout_rate=drop,
                dropout_rng=self.make_rng("dropout") if drop > 0 else None,
                mesh=self.mesh,
            ).reshape(B, T, d)
        else:
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(head_dim)
            if input_mask is not None:
                # Key-only padding mask (TF attention_mask semantics):
                # padded keys never receive probability; padded queries'
                # rows are garbage the loss never reads.
                scores = jnp.where(
                    (input_mask > 0)[:, None, None, :], scores,
                    jnp.finfo(scores.dtype).min,
                )
            probs = jax.nn.softmax(
                scores.astype(jnp.float32), -1
            ).astype(cfg.dtype)
            probs = nn.Dropout(cfg.dropout, deterministic=deterministic)(probs)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, T, d)
        attn = nn.Dense(d, dtype=cfg.dtype, name="out_proj")(ctx)
        attn = nn.Dropout(cfg.dropout, deterministic=deterministic)(attn)
        # Post-LN (original BERT)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_attn")(x + attn)

        y = nn.Dense(cfg.d_ff, dtype=cfg.dtype, name="fc1")(x)
        y = nn.gelu(y)
        y = nn.Dense(d, dtype=cfg.dtype, name="fc2")(y)
        y = nn.Dropout(cfg.dropout, deterministic=deterministic)(y)
        out = nn.LayerNorm(dtype=jnp.float32, name="ln_mlp")(x + y)
        # carry dtype must be stable across scanned layers (and bf16 is the
        # intended inter-layer activation dtype anyway)
        return out.astype(cfg.dtype), None


class BertPretrain(nn.Module):
    cfg: BertConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, batch: Dict[str, jax.Array], *, deterministic: bool = True):
        cfg = self.cfg
        tokens = batch["tokens"]
        segment_ids = batch.get(
            "segment_ids", jnp.zeros_like(tokens)
        )
        # Key-validity mask from the batch (variable-length padded inputs);
        # absent means all tokens are real (fixed-length synthetic batches).
        input_mask = batch.get("input_mask")
        B, T = tokens.shape
        word = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=jnp.float32,
                        name="word_embeddings")
        pos = self.param("position_embeddings",
                         nn.initializers.normal(0.02),
                         (cfg.max_positions, cfg.d_model), jnp.float32)
        seg = nn.Embed(cfg.type_vocab, cfg.d_model, dtype=jnp.float32,
                       name="segment_embeddings")
        x = word(tokens) + pos[:T] + seg(segment_ids)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_embed")(x)
        x = nn.Dropout(cfg.dropout, deterministic=deterministic)(x)
        x = x.astype(cfg.dtype)
        if cfg.scan_layers:
            body = (nn.remat(EncoderLayer, prevent_cse=False,
                             policy=REMAT_POLICY)
                    if cfg.remat else EncoderLayer)
            Scanned = nn.scan(
                body,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=nn.broadcast,  # the mask is layer-invariant
                length=cfg.n_layer,
                unroll=cfg.scan_unroll,
            )
            x, _ = Scanned(
                cfg, mesh=self.mesh, deterministic=deterministic,
                name="layers",
            )(x, input_mask)
        else:
            for i in range(cfg.n_layer):
                x, _ = EncoderLayer(
                    cfg, mesh=self.mesh, deterministic=deterministic,
                    name=f"layer_{i}",
                )(x, input_mask)

        # MLM head: gather the K prediction positions FIRST (the
        # reference's max_predictions_per_seq format), then transform +
        # tied decoder on (B, K, d) — the vocabulary projection runs on
        # ~15% of positions instead of all T (at seq 128 that is 6.4x less
        # head compute and a (B,K,V) instead of (B,T,V) logit buffer).
        positions = batch["mlm_positions"]  # (B, K)
        gathered = jnp.take_along_axis(x, positions[..., None], axis=1)
        y = nn.Dense(cfg.d_model, dtype=cfg.dtype, name="mlm")(gathered)
        y = nn.gelu(y)
        y = nn.LayerNorm(dtype=jnp.float32, name="mlm_ln")(y)
        # bf16 operands on the MXU, f32 accumulation (see gpt2 head).
        mlm_logits = jnp.einsum(
            "bkd,vd->bkv",
            y.astype(cfg.dtype),
            word.embedding.astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        ) + self.param("mlm_bias", nn.initializers.zeros,
                       (cfg.vocab_size,), jnp.float32)

        # NSP head on position 0 ([CLS]).
        pooled = jnp.tanh(
            nn.Dense(cfg.d_model, dtype=jnp.float32, name="pooler")(
                x[:, 0].astype(jnp.float32)
            )
        )
        nsp_logits = nn.Dense(2, dtype=jnp.float32, name="nsp")(pooled)
        return mlm_logits, nsp_logits


def _loss_fn(module: nn.Module, deterministic: bool, params,
             batch: Dict[str, jax.Array], rng):
    mlm_logits, nsp_logits = module.apply(
        {"params": params},
        batch,
        deterministic=deterministic,
        rngs=None if deterministic else {"dropout": rng},
    )
    weights = batch["mlm_weights"]  # (B, K) prediction-slot weights
    per_tok = optax.softmax_cross_entropy_with_integer_labels(
        mlm_logits, batch["mlm_targets"]
    )
    mlm_loss = jnp.sum(per_tok * weights) / jnp.maximum(jnp.sum(weights), 1.0)
    nsp_loss = jnp.mean(
        optax.softmax_cross_entropy_with_integer_labels(
            nsp_logits, batch["nsp_label"]
        )
    )
    mlm_acc = jnp.sum(
        (jnp.argmax(mlm_logits, -1) == batch["mlm_targets"]) * weights
    ) / jnp.maximum(jnp.sum(weights), 1.0)
    nsp_acc = jnp.mean(
        (jnp.argmax(nsp_logits, -1) == batch["nsp_label"]).astype(jnp.float32)
    )
    return mlm_loss + nsp_loss, {
        "mlm_loss": mlm_loss,
        "nsp_loss": nsp_loss,
        "mlm_accuracy": mlm_acc,
        "nsp_accuracy": nsp_acc,
    }


def bert_rules() -> ShardingRules:
    return transformer_rules().extended(
        [
            # scanned-stack layout (leading layer dim)
            (r"layers/.*qkv/kernel", P(None, "fsdp", "tensor")),
            (r"layers/.*out_proj/kernel", P(None, "tensor", "fsdp")),
            (r"layers/.*fc1/kernel", P(None, "fsdp", "tensor")),
            (r"layers/.*fc2/kernel", P(None, "tensor", "fsdp")),
            (r"layers/.*(bias|scale)", P()),
            # shared / per-layer layout
            (r"word_embeddings/embedding", P("tensor", "fsdp")),
            (r"(segment_embeddings/embedding|position_embeddings)", P()),
        ]
    )


def make_workload(
    *,
    batch_size: int = 256,
    seq_len: int = 128,
    config: Optional[BertConfig] = None,
    ring_chunk_size: Optional[int] = None,
    use_flash_attention: Optional[bool] = None,
    mesh: Optional[Mesh] = None,
    **_unused,
) -> Workload:
    cfg = config or BertConfig.base()
    if ring_chunk_size is not None:
        cfg = dataclasses.replace(cfg, ring_chunk_size=ring_chunk_size)
    if use_flash_attention is None and config is None:
        # Per-phase default from measurement (see BertConfig): dense for
        # phase-1 seq 128, flash for phase-2 seq 512.
        use_flash_attention = seq_len >= 256
    if use_flash_attention is not None:
        cfg = dataclasses.replace(cfg, use_flash_attention=use_flash_attention)
    seq = min(seq_len, cfg.max_positions)
    module = BertPretrain(cfg, mesh=mesh)
    # Init batch must divide over the batch-sharding axes when the mesh
    # forces the ring-attention shard_map path (static per-shard shapes),
    # mirroring gpt2/wide_deep.
    b0 = 2
    if mesh is not None:
        b0 = max(2, mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1))
    K = mlm_max_predictions(seq)
    init_batch = {
        "tokens": np.zeros((b0, seq), np.int32),
        "input_mask": np.ones((b0, seq), np.int32),
        "mlm_positions": np.zeros((b0, K), np.int32),
        "mlm_targets": np.zeros((b0, K), np.int32),
        "mlm_weights": np.zeros((b0, K), np.float32),
        "segment_ids": np.zeros((b0, seq), np.int32),
        "nsp_label": np.zeros((b0,), np.int32),
    }
    return Workload(
        name="bert",
        module=module,
        loss_fn=functools.partial(_loss_fn, module, False),
        eval_loss_fn=functools.partial(_loss_fn, module, True),
        init_batch=init_batch,
        data_fn=lambda per_host_bs: synthetic_mlm(
            batch_size=per_host_bs, seq_len=seq, vocab_size=cfg.vocab_size,
        ),
        eval_data_fn=lambda per_host_bs: synthetic_mlm(
            batch_size=per_host_bs, seq_len=seq, vocab_size=cfg.vocab_size,
            holdout=True,
        ),
        rules=bert_rules(),
        batch_size=batch_size,
        clip_grad_norm=1.0,
        learning_rate=1e-4,
        warmup_steps=1000,
        example_key="tokens",
        init_key=None,  # module consumes the whole batch dict
    )
