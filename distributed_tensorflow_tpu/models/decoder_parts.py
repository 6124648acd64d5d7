"""The layers of the paged decoder families that belong to no one of them.

``glm4_moe_lite``, ``mellum``, ``glm_moe_dsa``, ``solar_open2`` and
``dots3_note`` each write a config, a ``param_spec``, a mixer and a layer
loop, and call what is here: the parameters' declaration from a spec, the
products' one rule of precision (``dot``), RMS norm, rotate-half rotary
positions, the gated MLP, the float32 router and the expert layer that is
told which experts it holds, the latent attention three of them share
(``mla_*``), the learned indexer two of them share (``indexer_*``,
``index_scores``, ``select_mask``, ``select_top``: moved here from
``glm_moe_dsa`` when a second family got one), the grouped-query attention
two of them share (``gqa_attend`` and the four path names), the mask of a
causal or a window read (``attention_mask``), the replicated cache's rules
and the language-model loss.  Nothing here keeps state, and nothing here
knows a family by name: a config says what it is through its fields
(``cfg.router``, ``cfg.held``...).  The cache side of a call is
``models/paged_call.py``.

**A kind's sizes.**  The latent attention reads its head count, both ranks,
the three head sizes, ``rope_theta``, the two latents' scales and whether
its output is gated from an ``MlaSizes``, not from the config: a model whose
window layers have their own ranks and head count has two kinds of latent
layer.  A model with one kind builds its group from its config's published
keys (``mla_sizes``: scales of 1, no gate, and the same program as before
the group existed).

**The expert layer.**  The router scores all the experts in float32
(``route``).  The layer holds ``cfg.held`` consecutive experts from
``cfg.first_expert`` (one chip's share under expert parallelism), computes
their part of the result for the tokens routed to them, and adds the shared
expert where it has one; what experts held elsewhere would add is left out
and nothing stands in for the exchange.  No token is dropped.  In a cached
call the layer also counts, on the device, where the router's choices fell:
a row of the ``moe_counts`` leaf of the cache collection, ``(expert layers,
held + COUNT_EXTRA)`` int32, accumulates per layer the tokens assigned to
each held expert, the assignments to experts held elsewhere, the held
experts that got at least one token (summed over calls) and the calls
counted (``COUNT_ABSENT``...).  Rows masked out by ``live`` count nothing.

**Precision.**  Parameters and every product's operands are in
``cfg.dtype`` (bfloat16), accumulated in float32; the residual stream, the
norms, the rotations, the gates and the router stay float32, and a
product's float32 result is rounded once, where the next product takes it
as an operand.  A router that reads a rounded hidden state flips its near
ties, and every later position reads the flipped position's cache: with the
stream in bfloat16 twice as many served tokens left the float32 reference's
best (PERF.md Findings, PR 33).
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from jax import lax

from distributed_tensorflow_tpu.ops import grouped_matmul, paged_attention
from distributed_tensorflow_tpu.parallel.sharding import ShardingRules

# The module, not the function ``ops`` re-exports: the platform is read
# through it at call time (the described-chip compile tests steer it).
_fa = importlib.import_module("distributed_tensorflow_tpu.ops.flash_attention")

# Columns of a ``moe_counts`` row after the ``held`` token counts.
COUNT_ABSENT, COUNT_ACTIVE, COUNT_CALLS = 0, 1, 2
COUNT_EXTRA = 3

# The grouped-query attention's paths, as ``attention_paths()`` names them:
# each kind of layer by the gather or by the block-table kernel.
GATHER_WINDOW, GATHER_FULL = "gqa_gather_window", "gqa_gather_full"
KERNEL_WINDOW, KERNEL_FULL = paged_attention.GQA_KERNEL_PATHS


def check_share(cfg, experts: int, key: str):
    """A config's share of the expert layer, ``cfg.held`` consecutive experts
    from ``cfg.first_expert``, must lie inside the router's ``experts`` (the
    config's field ``key``)."""
    if not 1 <= cfg.held <= experts:
        raise ValueError(
            f"experts_held {cfg.held} must be in 1..{key} {experts}")
    if not 0 <= cfg.first_expert <= experts - cfg.held:
        raise ValueError(
            f"first_expert {cfg.first_expert} + experts_held {cfg.held} "
            f"passes {key} {experts}")


# -- parameters ----------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MlaSizes:
    """One kind of latent-attention layer's sizes.  A model with one kind
    builds its group from its config's published keys (``mla_sizes``); one
    whose window layers have their own ranks and head count builds two.
    ``q_scale`` and ``kv_scale`` multiply the two normalized latents (in
    float32, with the norm, before the one rounding) and ``gated`` gives
    the layer a sigmoid gate a head on its output (``mla_output``)."""

    heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    q_scale: float = 1.0
    kv_scale: float = 1.0
    gated: bool = False

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Values cached a token and layer: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_width(self) -> int:
        """``latent_width`` rounded up to whole 128-lane tiles."""
        return -(-self.latent_width // 128) * 128


def mla_sizes(cfg) -> MlaSizes:
    """The one group of a config that names its latent attention's sizes by
    the published keys: no scale, no gate."""
    return MlaSizes(
        heads=cfg.num_attention_heads, q_lora_rank=cfg.q_lora_rank,
        kv_lora_rank=cfg.kv_lora_rank, qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rope_theta=cfg.rope_theta)


def mla_spec(cfg, sizes: MlaSizes):
    """The latent attention's leaves."""
    d, h = cfg.hidden_size, sizes.heads
    spec = (
        ("q_a", (("kernel", (d, sizes.q_lora_rank)),)),
        ("q_a_norm", (("scale", (sizes.q_lora_rank,)),)),
        ("q_b", (("kernel", (sizes.q_lora_rank, h * sizes.qk_head_dim)),)),
        ("kv_a", (("kernel", (d, sizes.latent_width)),)),
        ("kv_a_norm", (("scale", (sizes.kv_lora_rank,)),)),
        ("kv_b", (("kernel", (
            sizes.kv_lora_rank,
            h * (sizes.qk_nope_head_dim + sizes.v_head_dim))),)),
        ("o", (("kernel", (h * sizes.v_head_dim, d)),)),
    )
    if sizes.gated:
        spec += (("gate", (("kernel", (d, h)),)),)
    return spec


def indexer_spec(cfg, sizes: MlaSizes):
    """A learned indexer's leaves, beside the latent attention whose query
    latent it reads."""
    d, hi, di = cfg.hidden_size, cfg.index_n_heads, cfg.index_head_dim
    return (
        ("wq_b", (("kernel", (sizes.q_lora_rank, hi * di)),)),
        ("wk", (("kernel", (d, di)),)),
        ("k_norm", (("scale", (di,)), ("bias", (di,)))),
        ("weights_proj", (("kernel", (d, hi)),)),
    )


def mlp_spec(d, width, lead=()):
    """A gated MLP's two leaves: ``gate_up`` holds W_g's rows and then
    W_u's, each (width, d), output-major; ``down`` is (width, d).  Both
    have the model width in the minor dimension: a leaf shaped
    ``(.., d, 1536)`` takes the TPU compiler three times as long to draw
    from a seed as one shaped ``(.., 1536, d)`` (PERF.md Findings, PR 33),
    and every cold start draws them all."""
    return (("gate_up", (("kernel", lead + (2 * width, d)),)),
            ("down", (("kernel", lead + (width, d)),)))


def stacked(spec, n):
    """``spec`` with a leading layer dimension on every leaf."""
    return tuple((name, stacked(sub, n) if isinstance(sub[0], tuple)
                  else (n,) + tuple(sub)) for name, sub in spec)


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


def declare(module: nn.Module, spec, cfg, prefix=""):
    """``spec``'s parameters declared on ``module``, a nested group a nested
    module: leaves named ``scale`` start at one, ``bias`` at zero, the rest
    are drawn; -> the tree of their values."""
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
    cfg: Any
    prefix: str

    @nn.compact
    def __call__(self):
        return declare(self, self.spec, self.cfg, self.prefix)


def layer_leaves(stack, i):
    """Layer ``i``'s leaves of a stacked group, each taken from the stack
    where a product reads it: a period's leaves sliced out together are
    copied, 0.8 GB of expert stacks a period and step (PERF.md Findings,
    PR 39)."""
    return jax.tree.map(
        lambda w: lax.dynamic_index_in_dim(w, i, keepdims=False), stack)


# -- the layers' mathematics, as functions of a parameter tree -----------------

def dot(spec, a, b, out=None):
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
        dot("...d,gd->...g", x, p["gate_up"]["kernel"]), 2, axis=-1)
    return dot("...f,fd->...d", (jax.nn.silu(g) * u).astype(dtype),
               p["down"]["kernel"])


# -- latent attention (MLA) ----------------------------------------------------

def _scaled(y, scale: float):
    return y if scale == 1.0 else y * scale


def mla_query_latent(cfg, sizes: MlaSizes, p, xn):
    """The query's normalized (and scaled) low-rank latent ``c_q``, in the
    compute type (a learned indexer projects its own queries from it)."""
    return _scaled(
        rms_norm(dot("btd,dr->btr", xn, p["q_a"]["kernel"]),
                 p["q_a_norm"]["scale"], cfg.rms_norm_eps),
        sizes.q_scale).astype(cfg.dtype)


def mla_project(cfg, sizes: MlaSizes, p, xn, positions, cq=None):
    """``xn`` (the normalized input, in the compute type) -> the query's
    two parts, and what is cached of the keys and values: the normalized
    (and scaled) latent and the rotary key.  Norms, scales and rotations
    are taken in float32 on the products' float32 results; each output is
    rounded once, to the compute type.  ``cq`` is ``mla_query_latent``'s
    result where the caller has it already."""
    B, T, _ = xn.shape
    dt = cfg.dtype
    if cq is None:
        cq = mla_query_latent(cfg, sizes, p, xn)
    q = dot("btr,rf->btf", cq, p["q_b"]["kernel"]).reshape(
        B, T, sizes.heads, sizes.qk_head_dim)
    q_n, q_r = (q[..., :sizes.qk_nope_head_dim],
                q[..., sizes.qk_nope_head_dim:])
    kva = dot("btd,dc->btc", xn, p["kv_a"]["kernel"])
    latent = _scaled(
        rms_norm(kva[..., :sizes.kv_lora_rank], p["kv_a_norm"]["scale"],
                 cfg.rms_norm_eps), sizes.kv_scale)
    k_r = rope(kva[..., sizes.kv_lora_rank:], positions, sizes.rope_theta)
    return (q_n.astype(dt), rope(q_r, positions, sizes.rope_theta).astype(dt),
            latent.astype(dt), k_r.astype(dt))


def mla_cache_row(cfg, sizes: MlaSizes, latent, k_r):
    """What a position caches, ``(B, T, pool_width)``: the latent, the
    rotary key, and zeros up to whole lane tiles."""
    B, T, _ = latent.shape
    return jnp.concatenate([latent, k_r, jnp.zeros(
        (B, T, sizes.pool_width - sizes.latent_width), cfg.dtype)], axis=-1)


def _kv_b(sizes: MlaSizes, p):
    """``kv_b`` as the keys' and the values' halves, ``(rank, H, .)``."""
    w = p["kv_b"]["kernel"].reshape(
        sizes.kv_lora_rank, sizes.heads,
        sizes.qk_nope_head_dim + sizes.v_head_dim)
    return w[..., :sizes.qk_nope_head_dim], w[..., sizes.qk_nope_head_dim:]


def mla_attend(cfg, sizes: MlaSizes, p, q_n, q_r, latent, k_r, mask,
               absorb: bool):
    """Softmax attention of ``(B, T, H, .)`` queries over ``(B, S, .)``
    latents and rotary keys; ``mask`` ``(B, T, S)`` is True where a key may
    be read.  ``absorb`` folds ``kv_b`` into the query and the output (one
    shared head as wide as the latent row); otherwise the latents are
    expanded to per-head keys and values.  -> ``(B, T, H * v_head_dim)``
    before the gate and ``o`` (``mla_output``)."""
    B, T, H, _ = q_n.shape
    dt = cfg.dtype
    w_k, w_v = _kv_b(sizes, p)
    rotary = dot("bthr,bsr->bhts", q_r, k_r)
    if absorb:
        q_lat = dot("bthd,chd->bthc", q_n, w_k, dt)
        scores = dot("bthc,bsc->bhts", q_lat, latent) + rotary
    else:
        k_n = dot("bsc,chd->bshd", latent, w_k, dt)
        scores = dot("bthd,bshd->bhts", q_n, k_n) + rotary
    scores = scores / np.sqrt(sizes.qk_head_dim)
    scores = jnp.where(mask[:, None], scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(dt)
    if absorb:
        out_lat = dot("bhts,bsc->bthc", probs, latent, dt)
        out = dot("bthc,chv->bthv", out_lat, w_v, dt)
    else:
        v = dot("bsc,chv->bshv", latent, w_v, dt)
        out = dot("bhts,bshv->bthv", probs, v, dt)
    return out.reshape(B, T, H * sizes.v_head_dim)


def mla_expanded_scores(cfg, sizes: MlaSizes, p, q_n, q_r, latent, k_r):
    """The expanded form's scaled scores ``(B, H, T, S)`` float32 and values
    ``(B, S, H, v_head_dim)`` of ``(B, S, .)`` latents and rotary keys: what
    a walk over a long context attends a piece at a time
    (``paged_call.ContextWalk``)."""
    dt = cfg.dtype
    w_k, w_v = _kv_b(sizes, p)
    k_n = dot("bsc,chd->bshd", latent, w_k, dt)
    scores = (dot("bthd,bshd->bhts", q_n, k_n)
              + dot("bthr,bsr->bhts", q_r, k_r)) / np.sqrt(sizes.qk_head_dim)
    return scores, dot("bsc,chv->bshv", latent, w_v, dt)


def mla_output(cfg, sizes: MlaSizes, p, xn, ctx):
    """The heads' outputs ``ctx`` ``(B, T, H * v_head_dim)`` -> the layer's
    ``(B, T, d)`` float32.  A gated kind weighs each head's output by ``g =
    sigmoid(xn W_g)``, one scalar a head and position, in float32, and
    rounds the product once, as ``o``'s operand."""
    if sizes.gated:
        B, T, _ = ctx.shape
        g = jax.nn.sigmoid(dot("btd,dh->bth", xn, p["gate"]["kernel"]))
        ctx = (g[..., None] * ctx.reshape(
            B, T, sizes.heads, sizes.v_head_dim).astype(jnp.float32)
               ).astype(cfg.dtype).reshape(ctx.shape)
    return dot("btf,fd->btd", ctx, p["o"]["kernel"])


# -- a learned indexer and its selection ---------------------------------------

def _rope_first(x, positions, dims: int, theta: float):
    """The first ``dims`` values of the last dimension rotated."""
    return jnp.concatenate(
        [rope(x[..., :dims], positions, theta),
         x[..., dims:].astype(jnp.float32)], axis=-1)


def indexer_project(cfg, sizes: MlaSizes, p, xn, cq, positions):
    """``xn`` (normalized input) and ``cq`` (the query's latent), both in
    the compute type -> the index queries ``(B, T, Hi, Di)`` and the index
    key ``(B, T, Di)``, each rounded once, and the heads' weights ``(B, T,
    Hi)`` float32, both scales folded in.  The first ``qk_rope_head_dim``
    of a query's and a key's dimensions are rotated by the layer's own
    ``rope_theta`` (``sizes``)."""
    B, T, _ = xn.shape
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    rot, theta = sizes.qk_rope_head_dim, sizes.rope_theta
    q = dot("btr,rf->btf", cq, p["wq_b"]["kernel"]).reshape(B, T, hi, di)
    k = dot("btd,df->btf", xn, p["wk"]["kernel"])
    mean = jnp.mean(k, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
    k = ((k - mean) * lax.rsqrt(var + cfg.index_norm_eps)
         * p["k_norm"]["scale"].astype(jnp.float32)
         + p["k_norm"]["bias"].astype(jnp.float32))
    w = dot("btd,dh->bth", xn, p["weights_proj"]["kernel"]) * (
        hi ** -0.5 * di ** -0.5)
    return (_rope_first(q, positions, rot, theta).astype(cfg.dtype),
            _rope_first(k, positions, rot, theta).astype(cfg.dtype), w)


def index_scores(q_i, w, k_i):
    """``I[t, s]`` of ``(B, T, Hi, Di)`` queries with weights ``(B, T, Hi)``
    over ``(B, S, Di)`` keys -> ``(B, T, S)`` float32."""
    s = jax.nn.relu(dot("bthd,bsd->bths", q_i, k_i))
    return jnp.sum(s * w[..., None], axis=2)


def select_mask(scores, k: int):
    """``(..., S)`` float32 scores -> a mask of the ``k`` largest of each
    row, the lower position first on a tie (all of a row shorter than
    ``k``).  The ``k``-th largest is found by bisection on the scores'
    bits, which order as the scores do: 32 counts, and no sort."""
    if scores.shape[-1] <= k:
        return jnp.ones(scores.shape, bool)
    bits = lax.bitcast_convert_type(scores, jnp.uint32)
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def raise_bit(i, floor):
        tried = floor | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = jnp.sum(keys >= tried[..., None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, tried, floor)

    kth = lax.fori_loop(0, 32, raise_bit,
                        jnp.zeros(scores.shape[:-1], jnp.uint32))[..., None]
    above, ties = keys > kth, keys == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    return above | (ties & (jnp.cumsum(ties, axis=-1, dtype=jnp.int32)
                            <= room))


def select_top(scores, k: int):
    """``(B, S)`` scores -> the positions ``(B, k)`` of the ``k`` largest of
    each row, the lower position first on a tie: a decode step's selection,
    which is gathered and so needs the positions themselves."""
    return lax.top_k(scores, k)[1]


# -- grouped-query attention ---------------------------------------------------

def attention_mask(q_pos, k_pos, window: Optional[int]):
    """``(B, T)`` query and ``(B, S)`` key positions -> ``(B, T, S)``, True
    where the key may be read: a position that exists (``>= 0``), not after
    the query, and with ``window`` fewer than that many places before it
    (the query's own place counted)."""
    q, k = q_pos[:, :, None], k_pos[:, None, :]
    ok = (k >= 0) & (k <= q)
    return ok if window is None else ok & (q - k < window)


def gqa_attend(cfg, q, k, v, mask):
    """``q`` ``(B, T, Hkv, G, D)`` over ``k``, ``v`` ``(B, S, Hkv, D)``
    under ``mask`` ``(B, T, S)``; softmax in float32 -> ``(B, T, H * D)``."""
    B, T = q.shape[:2]
    scores = dot("btkgd,bskd->bkgts", q, k) / np.sqrt(cfg.head_dim)
    scores = jnp.where(mask[:, None, None], scores,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
    out = dot("bkgts,bskd->btkgd", probs, v, cfg.dtype)
    return out.reshape(B, T, cfg.num_attention_heads * cfg.head_dim)


# -- the router and the expert layer -------------------------------------------

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
        dot("nd,egd->eng", xd, ex["gate_up"]["kernel"]), 2, axis=-1)
    each = dot("enf,efd->end", (jax.nn.silu(g) * u).astype(cfg.dtype),
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
            ex = layer_leaves(ex, layer)
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


# -- what the engine asks of a family whose cache is replicated ----------------

def cache_rules(per_shard_pools: bool = False) -> ShardingRules:
    """The cache collection is replicated: a latent or a state is shared by
    every head and a handful of K/V heads has no ``tensor`` rule (the
    workload refuses such a mesh)."""
    del per_shard_pools
    return ShardingRules()


def lm_loss(module, params, batch, rng):
    tokens = batch["tokens"]
    logits = module.apply({"params": params}, tokens)
    loss = jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], tokens[:, 1:]))
    return loss, {"perplexity": jnp.exp(jnp.minimum(loss, 20.0))}
