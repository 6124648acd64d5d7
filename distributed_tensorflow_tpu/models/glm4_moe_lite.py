"""A latent-attention, sparse-expert decoder (``model_type`` ``glm4_moe_lite``)
for the serving path.

The second decoder family beside ``models/gpt2.py``: RMS norm, rotary
positions, gated MLPs, an untied head, one leading dense layer and then a
scanned stack of expert layers.  Two mechanisms are its own:

- **Latent attention (MLA).**  Queries go through a low-rank bottleneck
  (``q_lora_rank``); keys and values are up-projections (``kv_b``) of one
  normalized latent of ``kv_lora_rank`` values a token, beside one rotary
  key of ``qk_rope_head_dim`` values shared by every head.  The cache holds
  that latent and that key and nothing else: ``kv_lora_rank +
  qk_rope_head_dim`` values a token and layer, whatever the head count, in a
  lane-dense paged pool ``(layers, num_blocks, block_size, pool_width)``
  addressed through the scheduler's block tables (``PagedKVConfig``, the
  geometry every family shares; only the row's content differs).
  Attention over the pool runs one of two ways, chosen by the call's shape
  and recorded like the paged kernel's choice (``ops.paged_attention.
  note_path``): a decode step (one query a row) **absorbs** ``kv_b`` into
  the query and applies its value half after the softmax, so the pool's
  latent rows are the keys and the values of one 576-wide head shared by
  all (``ABSORBED``); every longer call **expands** the gathered latents to
  per-head keys and values first (``EXPANDED``).  Same mathematics, and
  ``tests/test_glm4_moe_lite.py`` holds them to each other.
- **The expert layer, told which experts it holds.**  The router scores all
  ``n_routed_experts`` in float32 (sigmoid; the top ``num_experts_per_tok``
  of score + correction bias; weights the chosen scores, normalized, times
  ``routed_scaling_factor``).  The layer holds ``experts_held`` consecutive
  experts from ``first_expert`` (one chip's share under expert
  parallelism), computes their part of the result for the tokens routed to
  them, and adds the shared expert; what experts held elsewhere would add
  is left out and nothing stands in for the exchange.  No token is
  dropped: every held expert runs over every token of the call and the
  gate is zero where the router chose otherwise.  ``experts_held ==
  n_routed_experts`` is the uncut layer.

  In a cached call the layer also counts, on the device, where the
  router's choices fell: the ``moe_counts`` leaf of the cache collection,
  ``(expert layers, experts_held + 3)`` int32, accumulates per layer the
  tokens assigned to each held expert, the assignments to experts held
  elsewhere, the held experts that got at least one token (summed over
  calls) and the calls counted (``COUNT_ABSENT``...).  Rows masked out by
  ``live`` count nothing.

Precision: parameters and every product's operands are in ``dtype``
(bfloat16), accumulated in float32; the residual stream, the norms, the
rotations, the gates and the router stay float32, and a product's float32
result is rounded once, where the next product takes it as an operand.  A
router that reads a rounded hidden state flips its near ties, and every
later position reads the flipped position's latent: with the stream in
bfloat16 twice as many served tokens left the float32 reference's best
(PERF.md Findings, PR 33).

The multi-token-prediction layer of the published model is not built:
the main model's logits do not depend on it.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from jax import lax
from jax.sharding import Mesh

from distributed_tensorflow_tpu.data.pipeline import synthetic_lm
from distributed_tensorflow_tpu.models import PagedKVConfig, Workload
from distributed_tensorflow_tpu.ops import grouped_matmul, paged_attention
from distributed_tensorflow_tpu.parallel.sharding import ShardingRules

# The module, not the function ``ops`` re-exports: the platform is read
# through it at call time (the described-chip compile tests steer it).
_fa = importlib.import_module("distributed_tensorflow_tpu.ops.flash_attention")

# The latent attention's two implementations, as ``attention_paths()``
# names them.
ABSORBED, EXPANDED = "latent_absorbed", "latent_expanded"

# Columns of a ``moe_counts`` row after the ``experts_held`` token counts.
COUNT_ABSENT, COUNT_ACTIVE, COUNT_CALLS = 0, 1, 2
COUNT_EXTRA = 3

_LANES = 128


@dataclasses.dataclass(frozen=True)
class Glm4MoeLiteConfig:
    """Published keys of ``config.json`` under their own names, plus the
    share of the expert layer this device holds."""

    vocab_size: int = 154880
    hidden_size: int = 2048
    intermediate_size: int = 10240          # the leading dense layers' MLP
    moe_intermediate_size: int = 1536       # one expert, routed or shared
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    n_routed_experts: int = 64              # the router's width, as published
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 202752
    # This device's share: ``experts_held`` consecutive experts starting at
    # ``first_expert``.  None holds them all (the uncut layer).
    experts_held: Optional[int] = None
    first_expert: int = 0
    dtype: Any = jnp.bfloat16               # products' operands, parameters

    router = "sigmoid_bias"                 # ``route``'s kind; no field

    def __post_init__(self):
        held = self.held
        if not 1 <= held <= self.n_routed_experts:
            raise ValueError(
                f"experts_held {held} must be in 1..n_routed_experts "
                f"{self.n_routed_experts}")
        if not 0 <= self.first_expert <= self.n_routed_experts - held:
            raise ValueError(
                f"first_expert {self.first_expert} + experts_held {held} "
                f"passes n_routed_experts {self.n_routed_experts}")
        if not 1 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError(
                "first_k_dense_replace must leave at least one dense layer "
                f"and fit num_hidden_layers {self.num_hidden_layers}, got "
                f"{self.first_k_dense_replace}")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even (rotary pairs)")

    @property
    def held(self) -> int:
        return (self.n_routed_experts if self.experts_held is None
                else int(self.experts_held))

    @property
    def n_dense_layers(self) -> int:
        return self.first_k_dense_replace

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Values cached a token and layer: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_width(self) -> int:
        """``latent_width`` rounded up to whole lane tiles."""
        return -(-self.latent_width // _LANES) * _LANES

    @property
    def n_positions(self) -> int:
        """What the engine checks a slot's length against."""
        return self.max_position_embeddings

    @classmethod
    def flash(cls, **kw):
        """The published GLM-4.7-Flash sizes, every expert held."""
        return cls(**kw)

    @classmethod
    def v5e8_share(cls, **kw):
        """One chip's share of a v5e-8 host on which 8 chips share each
        layer (8 experts a layer, 1/8 of the vocabulary's rows), at the
        depth one chip serves beside its float32 reference: the sizes of
        ``benchmark/configs/glm-4.7-flash.json``."""
        base = dict(num_hidden_layers=21, vocab_size=19360, experts_held=8)
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny(cls, **kw):  # tests
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=3,
            num_attention_heads=4, q_lora_rank=32, kv_lora_rank=64,
            qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=32,
            n_routed_experts=8, num_experts_per_tok=2,
            max_position_embeddings=256)
        base.update(kw)
        return cls(**base)


# -- parameters ----------------------------------------------------------------

def _attention_spec(cfg):
    d, h = cfg.hidden_size, cfg.num_attention_heads
    return (
        ("q_a", (("kernel", (d, cfg.q_lora_rank)),)),
        ("q_a_norm", (("scale", (cfg.q_lora_rank,)),)),
        ("q_b", (("kernel", (cfg.q_lora_rank, h * cfg.qk_head_dim)),)),
        ("kv_a", (("kernel", (d, cfg.latent_width)),)),
        ("kv_a_norm", (("scale", (cfg.kv_lora_rank,)),)),
        ("kv_b", (("kernel", (cfg.kv_lora_rank,
                              h * (cfg.qk_nope_head_dim + cfg.v_head_dim))),)),
        ("o", (("kernel", (h * cfg.v_head_dim, d)),)),
    )


def _mlp_spec(d, width, lead=()):
    """A gated MLP's two leaves: ``gate_up`` holds W_g's rows and then
    W_u's, each (width, d), output-major; ``down`` is (width, d).  Both
    have the model width in the minor dimension: a leaf shaped
    ``(.., d, 1536)`` takes the TPU compiler three times as long to draw
    from a seed as one shaped ``(.., 1536, d)`` (PERF.md Findings, PR 33),
    and every cold start draws them all."""
    return (("gate_up", (("kernel", lead + (2 * width, d)),)),
            ("down", (("kernel", lead + (width, d)),)))


def _layer_spec(cfg, moe: bool):
    d = cfg.hidden_size
    common = (("input_norm", (("scale", (d,)),)),
              ("attn", _attention_spec(cfg)),
              ("post_norm", (("scale", (d,)),)))
    if not moe:
        return common + (("mlp", _mlp_spec(d, cfg.intermediate_size)),)
    shared = cfg.n_shared_experts * cfg.moe_intermediate_size
    return common + (
        ("router", (("kernel", (d, cfg.n_routed_experts)),
                    ("bias", (cfg.n_routed_experts,)))),
        ("shared", _mlp_spec(d, shared)),
        ("experts", _mlp_spec(d, cfg.moe_intermediate_size,
                              lead=(cfg.held,))),
    )


def _stacked(spec, n):
    """``spec`` with a leading layer dimension on every leaf."""
    return tuple((name, _stacked(sub, n) if isinstance(sub[0], tuple)
                  else (n,) + tuple(sub)) for name, sub in spec)


def param_spec(cfg):
    d = cfg.hidden_size
    return (
        ("embed", (cfg.vocab_size, d)),
        ("dense_layers", _stacked(_layer_spec(cfg, False),
                                  cfg.n_dense_layers)),
        ("moe_layers", _stacked(_layer_spec(cfg, True), cfg.n_moe_layers)),
        ("final_norm", (("scale", (d,)),)),
        ("head", (("kernel", (d, cfg.vocab_size)),)),
    )


def _param_dtype(path, cfg):
    # The correction bias only orders float32 scores: it is held in their
    # type.  Everything else is held in the compute type.
    return jnp.float32 if path.endswith("router/bias") else cfg.dtype


def _normal_2d(key, shape, dtype):
    """normal(0, 0.02), drawn as a matrix and then given its shape: the
    TPU compiler takes a third of the time over a stacked leaf drawn so."""
    flat = (int(np.prod(shape[:-1])), shape[-1])
    return (0.02 * jax.random.normal(key, flat, jnp.float32)).astype(
        dtype).reshape(shape)


def _declare(module: nn.Module, spec, cfg, prefix=""):
    out = {}
    for name, sub in spec:
        path = f"{prefix}/{name}"
        if sub and isinstance(sub[0], tuple):
            out[name] = _Group(sub, cfg, path, name=name)()
            continue
        if name == "scale":
            init = nn.initializers.ones
        elif name == "bias":
            init = nn.initializers.zeros
        else:
            init = _normal_2d
        out[name] = module.param(name, init, tuple(sub),
                                 _param_dtype(path, cfg))
    return out


class _Group(nn.Module):
    """A nested group of parameters, declared from its spec."""
    spec: Any
    cfg: Glm4MoeLiteConfig
    prefix: str

    @nn.compact
    def __call__(self):
        return _declare(self, self.spec, self.cfg, self.prefix)


# -- the layer's mathematics, as functions of a parameter tree -----------------

def _dot(spec, a, b, out=None):
    """Operands in the compute type on the MXU, float32 accumulation and
    result (cast to ``out`` where given).  The CPU's dot has no
    bfloat16-in, float32-out form, so there the operands are widened
    first: the same products and the same sums, since a product of two
    bfloat16 values is exact in float32."""
    if _fa._platform() == "cpu":
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    y = jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)
    return y if out is None else y.astype(out)


def rms_norm(x, scale, eps):
    """In float32, and float32 out: the caller rounds where a product's
    operand is wanted."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def rope(x, positions, theta, inv_freq=None, scale=None):
    """Rotary positions over the whole last dimension, dimension ``i`` paired
    with ``i + half`` (the rotate-half convention).  ``x`` is ``(B, T, ...,
    D)``, ``positions`` ``(B, T)``; float32 out.  ``inv_freq`` ``(half,)``
    replaces ``theta``'s plain table and ``scale`` multiplies cos and sin
    (a scaled table, such as YaRN's, is its caller's to compute)."""
    half = x.shape[-1] // 2
    freq = (theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
            if inv_freq is None else jnp.asarray(inv_freq, jnp.float32))
    angle = positions.astype(jnp.float32)[..., None] * freq      # (B, T, half)
    angle = angle.reshape(angle.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def gated_mlp(p, x, dtype):
    """``x`` in the compute type -> float32; the gate's product is taken in
    float32 and rounded once, as the down projection's operand."""
    g, u = jnp.split(
        _dot("...d,gd->...g", x, p["gate_up"]["kernel"]), 2, axis=-1)
    return _dot("...f,fd->...d", (jax.nn.silu(g) * u).astype(dtype),
                p["down"]["kernel"])


def mla_query_latent(cfg, p, xn):
    """The query's normalized low-rank latent ``c_q``, in the compute type
    (a learned indexer projects its own queries from it)."""
    return rms_norm(_dot("btd,dr->btr", xn, p["q_a"]["kernel"]),
                    p["q_a_norm"]["scale"], cfg.rms_norm_eps).astype(cfg.dtype)


def mla_project(cfg, p, xn, positions, cq=None):
    """``xn`` (the normalized input, in the compute type) -> the query's
    two parts, and what is cached of the keys and values: the normalized
    latent and the rotary key.  Norms and rotations are taken in float32 on
    the products' float32 results; each output is rounded once, to the
    compute type.  ``cq`` is ``mla_query_latent``'s result where the caller
    has it already."""
    B, T, _ = xn.shape
    dt = cfg.dtype
    if cq is None:
        cq = mla_query_latent(cfg, p, xn)
    q = _dot("btr,rf->btf", cq, p["q_b"]["kernel"]).reshape(
        B, T, cfg.num_attention_heads, cfg.qk_head_dim)
    q_n, q_r = q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]
    kva = _dot("btd,dc->btc", xn, p["kv_a"]["kernel"])
    latent = rms_norm(kva[..., :cfg.kv_lora_rank], p["kv_a_norm"]["scale"],
                      cfg.rms_norm_eps)
    k_r = rope(kva[..., cfg.kv_lora_rank:], positions, cfg.rope_theta)
    return (q_n.astype(dt), rope(q_r, positions, cfg.rope_theta).astype(dt),
            latent.astype(dt), k_r.astype(dt))


def mla_attend(cfg, p, q_n, q_r, latent, k_r, mask, absorb: bool):
    """Softmax attention of ``(B, T, H, .)`` queries over ``(B, S, .)``
    latents and rotary keys; ``mask`` ``(B, T, S)`` is True where a key may
    be read.  ``absorb`` folds ``kv_b`` into the query and the output (one
    shared 576-wide head); otherwise the latents are expanded to per-head
    keys and values.  -> ``(B, T, H * v_head_dim)`` before ``o``."""
    B, T, H, _ = q_n.shape
    dt = cfg.dtype
    w = p["kv_b"]["kernel"].reshape(
        cfg.kv_lora_rank, H, cfg.qk_nope_head_dim + cfg.v_head_dim)
    w_k, w_v = w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]
    rotary = _dot("bthr,bsr->bhts", q_r, k_r)
    if absorb:
        q_lat = _dot("bthd,chd->bthc", q_n, w_k, dt)
        scores = _dot("bthc,bsc->bhts", q_lat, latent) + rotary
    else:
        k_n = _dot("bsc,chd->bshd", latent, w_k, dt)
        scores = _dot("bthd,bshd->bhts", q_n, k_n) + rotary
    scores = scores / np.sqrt(cfg.qk_head_dim)
    scores = jnp.where(mask[:, None], scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(dt)
    if absorb:
        out_lat = _dot("bhts,bsc->bthc", probs, latent, dt)
        out = _dot("bthc,chv->bthv", out_lat, w_v, dt)
    else:
        v = _dot("bsc,chv->bshv", latent, w_v, dt)
        out = _dot("bhts,bshv->bthv", probs, v, dt)
    return out.reshape(B, T, H * cfg.v_head_dim)


def route(cfg, p, x):
    """The router, in float32 whatever the compute type: -> the chosen
    experts' indices ``(N, k)`` and their weights ``(N, k)``.  Its kind is
    the config's to say (``cfg.router``): ``"sigmoid_bias"`` scores by a
    sigmoid, chooses by score + correction bias and scales the normalized
    weights; ``"softmax"`` scores by a softmax over all the experts and
    chooses by score, with no bias and no scale."""
    logits = jnp.einsum(
        "nd,de->ne", x.astype(jnp.float32),
        p["kernel"].astype(jnp.float32),
        precision=lax.Precision.HIGHEST)
    if cfg.router == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        order, scaling = scores, 1.0
    else:
        scores = jax.nn.sigmoid(logits)
        order = scores + p["bias"].astype(jnp.float32)
        scaling = cfg.routed_scaling_factor
    _, chosen = lax.top_k(order, cfg.num_experts_per_tok)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return chosen, weights * scaling


def expert_form(n: int, k: int, experts: int) -> str:
    """Which form the routed product of a call takes, from the call's
    static shape: ``n`` tokens, ``k`` of ``experts`` a token (how many are
    held here changes neither form's cost an expert).  Every held expert
    over every token reads each stack once and is bound by that read while
    the tokens are fewer than the chip's operations a byte (240 on a v5e):
    nothing is faster where nearly every held expert gets a row anyway, an
    expert's chance of one being ``1 - (1 - k / experts) ** n``.  (On the chip, PERF.md Findings, PR 41: 128
    tokens of 4-of-64 over 8 held, 0.227 ms dense and 0.238 grouped; 384,
    0.375 and 0.266; 16 tokens of 8-of-64 over 16 held, a chance of 0.88,
    0.281 and 0.260.)  Otherwise each assignment is computed once over rows
    grouped by expert, and an expert without a row is not read."""
    every_expert_live = 1.0 - (1.0 - k / experts) ** n > 0.95
    return (grouped_matmul.DENSE if every_expert_live and n < 256
            else grouped_matmul.GROUPED)


def _routed_dense(cfg, ex, xd, gates):
    """Every held expert over every token, weighed by ``gates`` ``(N,
    held)``, zero where the router chose otherwise."""
    g, u = jnp.split(
        _dot("nd,egd->eng", xd, ex["gate_up"]["kernel"]), 2, axis=-1)
    each = _dot("enf,efd->end", (jax.nn.silu(g) * u).astype(cfg.dtype),
                ex["down"]["kernel"])
    # The gates weigh float32 results in float32: no product, a sum of 8.
    return jnp.sum(gates.T[:, :, None] * each, axis=0)


def _routed_grouped(cfg, ex, xd, gates, mine, layer, kernels: bool):
    """Each (token, held expert) assignment once: the rows of ``xd`` that
    ``mine`` ``(N, held)`` assigns, grouped by expert, and each token's
    results weighed by ``gates`` and summed in float32
    (``ops/grouped_matmul.py``).  An expert without a row is not read."""
    lay = grouped_matmul.layout(mine, cfg.num_experts_per_tok)
    grouped_matmul.note_form(grouped_matmul.GROUPED, xd.shape[0], lay.rows)
    product = (grouped_matmul.gated_mlp if kernels
               else grouped_matmul.gated_mlp_reference)
    return product(xd, ex["gate_up"]["kernel"], ex["down"]["kernel"], lay,
                   gates, layer=layer)


def expert_layer(cfg, p, x, live=None, *, layer=None, mesh=None):
    """Held experts' part of the routed result plus the shared expert (where
    the layer has one), for ``x`` ``(N, d)`` float32 (the router reads it
    unrounded; the experts' products take it in the compute type), float32
    out; and the layer's row of ``moe_counts``.  ``live`` ``(N,)`` masks the
    tokens that count: the grouped form gives the others no row, and their
    routed result is zero.  With ``layer`` given, ``p["experts"]`` is the
    stack of all the model's expert layers and ``layer`` the (traced) index
    of this one: a kernel reads the layer's blocks where they lie, and a
    slice handed to it would be copied first.  ``mesh`` is the model's:
    the kernels run on one device."""
    dt = cfg.dtype
    chosen, weights = route(cfg, p["router"], x)
    held = cfg.first_expert + jnp.arange(cfg.held, dtype=chosen.dtype)
    hit = chosen[:, :, None] == held[None, None, :]            # (N, k, held)
    ex = p["experts"]
    xd = x.astype(dt)
    form = expert_form(x.shape[0], cfg.num_experts_per_tok,
                       p["router"]["kernel"].shape[-1])
    kernels = grouped_matmul.supported(
        n=x.shape[0], d=x.shape[-1], f=ex["down"]["kernel"].shape[-2],
        dtype=dt, mesh=mesh)
    gates = jnp.sum(jnp.where(hit, weights[:, :, None], 0.0), axis=1)
    # Off the TPU the grouped form is plain ``jnp`` at a toy's sizes; on a
    # TPU where the kernels do not run (more devices than one, widths that
    # are not whole tiles) the dense form is the only one.
    if form == grouped_matmul.GROUPED and (
            kernels or _fa._platform() != "tpu"):
        mine = hit.any(axis=1)
        if live is not None:
            mine = mine & live.astype(bool)[:, None]
        routed = _routed_grouped(cfg, ex, xd, gates, mine, layer, kernels)
    else:
        grouped_matmul.note_form(grouped_matmul.DENSE, x.shape[0])
        if layer is not None:
            ex = jax.tree.map(lambda w: lax.dynamic_index_in_dim(
                w, layer, keepdims=False), ex)
        routed = _routed_dense(cfg, ex, xd, gates)
    y = routed + gated_mlp(p["shared"], xd, dt) if "shared" in p else routed

    counted = (jnp.ones(x.shape[:1], jnp.int32) if live is None
               else live.astype(jnp.int32))
    tokens = jnp.sum(hit.any(axis=1) * counted[:, None], axis=0,
                     dtype=jnp.int32)                           # (held,)
    assigned = cfg.num_experts_per_tok * jnp.sum(counted)
    extra = jnp.stack([assigned - jnp.sum(tokens),
                       jnp.sum(tokens > 0, dtype=jnp.int32),
                       (jnp.sum(counted) > 0).astype(jnp.int32)])
    return y, jnp.concatenate([tokens, extra.astype(jnp.int32)])


# -- the module ----------------------------------------------------------------

class Glm4MoeLite(nn.Module):
    cfg: Glm4MoeLiteConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, tokens, *, deterministic: bool = True,
                 decode: bool = False, slot_ids=None,
                 paged: Optional[PagedKVConfig] = None, block_tables=None,
                 live=None):
        cfg = self.cfg
        B, T = tokens.shape
        if decode and (paged is None or slot_ids is None
                       or block_tables is None):
            raise ValueError(
                "the latent cache is paged only: decode=True needs "
                "slot_ids, paged=PagedKVConfig(...) and block_tables (the "
                "continuous scheduler's cache_mode='paged'); there is no "
                "dense-row or fixed-batch latent cache")
        if not decode and (paged is not None or slot_ids is not None
                           or block_tables is not None or live is not None):
            raise ValueError(
                "slot_ids, paged, block_tables and live only apply to "
                "decode=True calls")
        if paged is not None and (paged.quantized
                                  or paged.kv_dtype is not None):
            raise ValueError(
                f"kv_dtype {paged.kv_dtype!r}: the latent pool is stored in "
                "the compute type only (a quantized latent needs its own "
                "scale layout and a dequantizing read)")
        if paged is not None and paged.data_shards != 1:
            raise ValueError(
                "per-shard pools are not built for the latent pool")
        params = _declare(self, param_spec(cfg), cfg)
        # The residual stream is float32 from here to the head: what a
        # layer adds is a product's float32 result, and the router reads
        # the stream's norm unrounded (a rounded one flips its near ties).
        x = params["embed"][tokens].astype(jnp.float32)

        if decode:
            n_layers = cfg.num_hidden_layers
            pool = self.variable(
                "cache", "latent_pool", lambda: jnp.zeros(
                    (n_layers, paged.num_blocks, paged.block_size,
                     cfg.pool_width), cfg.dtype))
            index = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((B,), jnp.int32))
            counts = self.variable(
                "cache", "moe_counts", lambda: jnp.zeros(
                    (cfg.n_moe_layers, cfg.held + COUNT_EXTRA), jnp.int32))
            start = index.value[slot_ids]                         # (B,)
            positions = start[:, None] + jnp.arange(T)[None, :]   # (B, T)
            rows_bt = jnp.maximum(block_tables, 0)[slot_ids]
            bs = paged.block_size
            cells = (jnp.take_along_axis(
                rows_bt, positions // bs, axis=1).reshape(-1),
                (positions % bs).reshape(-1))
            span = rows_bt.shape[1] * bs
            mask = (jnp.arange(span)[None, None, :]
                    <= positions[:, :, None])                     # (B, T, S)
            absorb = T == 1
            paged_attention.note_path(ABSORBED if absorb else EXPANDED)
            index.value = index.value.at[slot_ids].set(start + T)
            pool_value = pool.value
        else:
            positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
            mask = jnp.broadcast_to(
                jnp.tril(jnp.ones((T, T), bool))[None], (B, T, T))
            absorb, pool_value, cells, rows_bt = False, None, None, None
        token_live = None if live is None else jnp.repeat(live, T)

        def attention(p, x, pool_value, layer):
            xn = rms_norm(x, p["input_norm"]["scale"],
                          cfg.rms_norm_eps).astype(cfg.dtype)
            q_n, q_r, latent, k_r = mla_project(cfg, p["attn"], xn, positions)
            if pool_value is not None:
                row = jnp.concatenate(
                    [latent, k_r, jnp.zeros(
                        (B, T, cfg.pool_width - cfg.latent_width),
                        cfg.dtype)], axis=-1)
                pool_value = pool_value.at[(layer,) + cells].set(
                    row.reshape(B * T, cfg.pool_width))
                # The slot's whole table row, gathered back: positions past
                # the row's index (and trash entries) are masked.
                rows = pool_value[layer, rows_bt].reshape(
                    B, -1, cfg.pool_width)
                latent = rows[..., :cfg.kv_lora_rank]
                k_r = rows[..., cfg.kv_lora_rank:cfg.latent_width]
            ctx = mla_attend(cfg, p["attn"], q_n, q_r, latent, k_r, mask,
                             absorb)
            out = _dot("btf,fd->btd", ctx, p["attn"]["o"]["kernel"])
            return x + out, pool_value

        def dense_layer(carry, xs):
            x, pool_value = carry
            p, layer = xs
            h, pool_value = attention(p, x, pool_value, layer)
            hn = rms_norm(h, p["post_norm"]["scale"],
                          cfg.rms_norm_eps).astype(cfg.dtype)
            return (h + gated_mlp(p["mlp"], hn, cfg.dtype), pool_value), None

        def moe_layer(carry, xs):
            x, pool_value = carry
            p, layer = xs
            h, pool_value = attention(p, x, pool_value, layer)
            hn = rms_norm(h, p["post_norm"]["scale"], cfg.rms_norm_eps)
            # The experts' stacks whole, and which layer's to read.
            y, row = expert_layer(
                cfg, dict(p, experts=params["moe_layers"]["experts"]),
                hn.reshape(B * T, cfg.hidden_size), token_live,
                layer=layer - nd, mesh=self.mesh)
            return (h + y.reshape(h.shape), pool_value), row

        nd = cfg.n_dense_layers
        carry = (x, pool_value)
        carry, _ = lax.scan(
            dense_layer, carry,
            (params["dense_layers"], jnp.arange(nd, dtype=jnp.int32)))
        carry, rows = lax.scan(
            moe_layer, carry,
            ({name: leaves for name, leaves in params["moe_layers"].items()
              if name != "experts"},
             nd + jnp.arange(cfg.n_moe_layers, dtype=jnp.int32)))
        x, pool_value = carry
        if decode:
            pool.value = pool_value
            counts.value = counts.value + rows
        x = rms_norm(x, params["final_norm"]["scale"],
                     cfg.rms_norm_eps).astype(cfg.dtype)
        return _dot("btd,dv->btv", x, params["head"]["kernel"])


# -- what the engine and the scheduler ask of a decoder family -----------------

def cache_rules(per_shard_pools: bool = False) -> ShardingRules:
    """The cache collection is replicated: the latent is shared by every
    head, so a ``tensor`` axis has nothing of it to split (and the workload
    refuses one)."""
    del per_shard_pools
    return ShardingRules()


def cache_geometry(cfg: Glm4MoeLiteConfig, paged: PagedKVConfig
                   ) -> Dict[str, Any]:
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return {
        "kind": "latent",
        "pools_per_layer": 1,
        "values_per_token_layer": cfg.latent_width,
        "pool_width": cfg.pool_width,
        "padding_values": cfg.pool_width - cfg.latent_width,
        "bytes_per_token_layer": cfg.pool_width * itemsize,
        "bytes_per_token": cfg.num_hidden_layers * cfg.pool_width * itemsize,
        "pool_bytes": (cfg.num_hidden_layers * paged.num_blocks
                       * paged.block_size * cfg.pool_width * itemsize),
    }


# Scheduler features this family cannot serve yet, each with its reason: the
# scheduler refuses them at construction (``ContinuousScheduler``), and
# ``make_workload`` the ``tensor`` mesh before an engine exists.
SERVE_REFUSALS = {
    "dense_cache": (
        "the latent cache is a paged pool only (cache_mode='paged'): there "
        "is no dense-row layout of the latent"),
    "kv_dtype": (
        "the latent pool is stored in the compute type: an int8 or cast "
        "latent needs its own scale layout and a dequantizing read"),
    "per_shard_kv": (
        "the latent pool is replicated: per-shard pools are not built "
        "for it"),
    "slo_scheduling": (
        "host tiering swaps the K and V pools block by block and does not "
        "know the latent pool's leaf; preempting would lose a victim's "
        "cache"),
    "spec_k": (
        "speculative verify over the latent pool (a k+1-wide forward with "
        "roll-back) is not built or tested"),
    "prefix_cache": (
        "sharing latent blocks between requests is not tested yet"),
    "tensor_mesh": (
        "the latent is shared by all heads and the expert stack has no "
        "tensor rule: serve on a mesh without a 'tensor' axis"),
}


def _loss_fn(module, params, batch, rng):
    tokens = batch["tokens"]
    logits = module.apply({"params": params}, tokens)
    loss = jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], tokens[:, 1:]))
    return loss, {"perplexity": jnp.exp(jnp.minimum(loss, 20.0))}


def make_workload(
    *,
    preset: str = "flash",
    batch_size: int = 8,
    seq_len: Optional[int] = None,
    config: Optional[Glm4MoeLiteConfig] = None,
    mesh: Optional[Mesh] = None,
    **_unused,
) -> Workload:
    cfg = config or getattr(Glm4MoeLiteConfig, preset)()
    if mesh is not None and mesh.shape.get("tensor", 1) > 1:
        raise ValueError(
            f"glm4_moe_lite on a mesh with tensor="
            f"{mesh.shape['tensor']}: {SERVE_REFUSALS['tensor_mesh']}")
    seq = seq_len or min(cfg.max_position_embeddings, 128)
    module = Glm4MoeLite(cfg, mesh=mesh)
    data = functools.partial(synthetic_lm, seq_len=seq,
                             vocab_size=cfg.vocab_size)
    return Workload(
        name="glm4_moe_lite",
        module=module,
        loss_fn=functools.partial(_loss_fn, module),
        init_batch={"tokens": np.zeros((2, seq), np.int32)},
        data_fn=lambda per_host_bs: data(batch_size=per_host_bs),
        eval_data_fn=lambda per_host_bs: data(batch_size=per_host_bs,
                                              holdout=True),
        rules=ShardingRules(),
        batch_size=batch_size,
        clip_grad_norm=1.0,
        learning_rate=3e-4,
        example_key="tokens",
        init_key="tokens",
        cache_rules=cache_rules,
        cache_geometry=functools.partial(cache_geometry, cfg),
        serve_refusals=dict(SERVE_REFUSALS),
    )
