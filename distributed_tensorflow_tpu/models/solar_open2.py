"""A decoder of gated delta-rule linear attention (KDA) with one grouped-query
softmax layer in four, every MLP a layer of sparse experts (``model_type``
``solar_open2``), for the serving path.

The fifth decoder family.  RMS norm, the stacked parameter leaves, the
float32 sigmoid router and the expert layer that is told which experts it
holds are ``models/decoder_parts.py``'s; the grouped-query attention over a
paged K/V pool is that module's and ``models/paged_call.py``'s, a full layer
without rotation (``use_rope`` false: no positional term anywhere).  What is
this family's own:

- **Kimi Delta Attention** (arXiv:2510.26692) on every layer that
  ``gqa_layers`` does not name.  ``q~, k~, v~ = x W_qkv`` (each ``num_heads x
  head_dim`` wide), each through a depthwise causal convolution of
  ``short_conv_kernel_size`` positions and SiLU; ``q`` and ``k``
  L2-normalised a head, ``q`` times ``head_dim ** -0.5``.  A decay a channel
  ``alpha_t = exp(-exp(A_h) softplus(W_a2 (W_a1 x_t) + b_dt))`` and a step a
  head ``beta_t = 2 sigmoid(x_t W_b)`` (the 2 is ``kda_allow_neg_eigval``)
  drive one ``head_dim x head_dim`` state a head:

      S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t
      y   = W_o (RMSNorm_head(o_t) * sigmoid(W_g2 (W_g1 x_t)))

  ``kda_step`` is that recurrence for one position (a decode step, on the
  VPU in float32); ``kda_chunk`` the same mathematics for a run of positions
  in matrix products, chunks of ``KDA_CHUNK`` positions (the paper's WY / UT
  form), which a prefill chunk takes.  They agree at every chunk boundary
  (``tests/test_solar_open2.py``).
- **A third kind of cache leaf, per slot and not per position.**  Beside the
  grouped-query layers' paged pool ``(gqa layers, num_blocks, block_size, 2 x
  kv heads x head_dim)`` the cache collection carries ``kda_state`` ``(kda
  layers, slots, heads, head_dim, head_dim)`` float32 and ``kda_conv``
  ``(kda layers, slots, kernel - 1, 3 x heads x head_dim)``, the
  convolution's last inputs.  Every step OVERWRITES them, so the frozen
  ``cache_index`` that hides a dead row's K/V hides nothing here, and the
  rules are this module's to keep, where the update is computed (a gate
  applied by the engine after the step would copy the whole state once
  more):

  * a row whose ``live`` bit is off leaves state and tail as they were, bit
    for bit (a ``where`` inside the update's own fusion);
  * a call whose row starts at position 0 starts from a zero state and a
    zero tail, whatever the slot held (a new occupant, a cancelled prefill);
    a later chunk starts from what the chunk before it left;
  * positions that pad a run to whole chunks of ``KDA_CHUNK`` change
    nothing (decay 1, step 0).

  A call with ``live`` given is one of the engine's two decode programs: it
  runs over ALL the slots in order (``slot_ids`` is ``arange(slots)``) and
  reads and writes the leaves where they lie.  A call without (a prefill
  chunk) gathers its rows' states and scatters them back.

Precision as ``decoder_parts`` has it: parameters and products' operands in
``dtype`` (bfloat16), float32 accumulation; the residual stream, norms,
gates, router, softmax and logits float32.  The state, the decay (``A_log``,
``dt_bias``: float32 leaves, the group ``kda_decay``), its running sum in
log space and the decode step's arithmetic are float32; the convolution's
tail is held in ``dtype``.  No multi-token head: the row's config has none.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax
from jax.sharding import Mesh

from distributed_tensorflow_tpu.models import PagedKVConfig, Workload
from distributed_tensorflow_tpu.models.decoder_parts import (
    GATHER_FULL, KERNEL_FULL, attention_mask, check_share, declare, dot,
    expert_layer, layer_leaves, mlp_spec, rms_norm, stacked)
from distributed_tensorflow_tpu.models.paged_call import (
    PagedCall, decoder_workload, serve_refusals)
from distributed_tensorflow_tpu.ops import paged_attention

# The linear attention's two forms, as ``attention_paths()`` names them.
KDA_CHUNK_PATH, KDA_STEP_PATH = "kda_chunk", "kda_step"

# Positions a chunk of the chunk-wise rule holds, and the blocks its
# within-chunk decay products are taken in (``_decayed_gram``).
KDA_CHUNK = 64
KDA_BLOCK = 16


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    """Published keys of ``config.json`` under their own names (the nested
    ``linear_attn_config`` group may be given whole: its numbers land in the
    flat ``kda_*`` fields), plus the share of the expert layer this device
    holds."""

    vocab_size: int = 196608
    hidden_size: int = 4096
    intermediate_size: int = 10240      # unused at first_k_dense_replace 0
    moe_intermediate_size: int = 1280   # one expert, routed or shared
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    # None: layers 0, gqa_interval + 1, ... as published.  Entries at or past
    # ``num_hidden_layers`` are dropped (a cut in depth keeps the list).
    gqa_layers: Optional[Tuple[int, ...]] = None
    gqa_interval: int = 3               # KDA layers between two GQA layers
    use_gqa_gate: bool = True
    use_rope: bool = False
    first_k_dense_replace: int = 0
    kda_num_heads: int = 64
    kda_head_dim: int = 128
    kda_conv_size: int = 4
    kda_gate_rank: Optional[int] = None   # None: kda_head_dim (assumed)
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    linear_attn_config: Any = None        # the published group; consumed
    n_routed_experts: int = 320           # the router's width, as published
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
    # This device's share: ``experts_held`` consecutive experts starting at
    # ``first_expert``.  None holds them all (the uncut layer).
    experts_held: Optional[int] = None
    first_expert: int = 0
    dtype: Any = jnp.bfloat16             # products' operands, parameters

    router = "sigmoid_bias"               # ``decoder_parts.route``'s kind

    def __post_init__(self):
        put = lambda name, value: object.__setattr__(self, name, value)
        if self.linear_attn_config is not None:
            group = dict(self.linear_attn_config)
            if group.get("num_kv_heads") not in (None, group["num_heads"]):
                raise ValueError(
                    "linear_attn_config.num_kv_heads must be null (as many "
                    f"K/V heads as heads), got {group['num_kv_heads']}")
            put("kda_num_heads", int(group["num_heads"]))
            put("kda_head_dim", int(group["head_dim"]))
            put("kda_conv_size", int(group["short_conv_kernel_size"]))
            put("linear_attn_config", None)
        n = self.num_hidden_layers
        layers = self.gqa_layers
        if layers is None:
            layers = range(0, n, self.gqa_interval + 1)
        layers = tuple(int(l) for l in layers if int(l) < n)
        if list(layers) != sorted(set(layers)) or (layers and layers[0] < 0):
            raise ValueError(f"gqa_layers must rise from 0 on, got {layers}")
        put("gqa_layers", layers)
        if self.kda_gate_rank is None:
            put("kda_gate_rank", self.kda_head_dim)
        for flag, value, why in (
                ("use_rope", False, "no rotation is written"),
                ("use_gqa_gate", True, "the ungated layer is not written"),
                ("kda_use_full_proj", False,
                 "the full-rank decay projection is not written"),
                ("first_k_dense_replace", 0,
                 "a leading dense layer is not written")):
            if getattr(self, flag) != value:
                raise ValueError(f"{flag} must be {value!r}: {why}")
        check_share(self, self.n_routed_experts, "n_routed_experts")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"num_attention_heads {self.num_attention_heads} must be a "
                f"multiple of num_key_value_heads {self.num_key_value_heads}")
        if self.kda_conv_size < 2:
            raise ValueError("kda_conv_size must be >= 2")

    @property
    def held(self) -> int:
        return (self.n_routed_experts if self.experts_held is None
                else int(self.experts_held))

    @property
    def kinds(self) -> Tuple[bool, ...]:
        """Layer by layer, True where the mixer is grouped-query attention."""
        return tuple(l in self.gqa_layers
                     for l in range(self.num_hidden_layers))

    @property
    def period(self) -> int:
        """Layers in the scanned body: the shortest pattern ``kinds``
        repeats (all of them where it repeats none)."""
        n, kinds = self.num_hidden_layers, self.kinds
        return next(p for p in range(1, n + 1)
                    if n % p == 0 and kinds == kinds[:p] * (n // p))

    @property
    def n_gqa_layers(self) -> int:
        return len(self.gqa_layers)

    @property
    def n_kda_layers(self) -> int:
        return self.num_hidden_layers - self.n_gqa_layers

    @property
    def kv_row(self) -> int:
        """Values cached a token and GQA layer: K and V of every K/V head."""
        return 2 * self.num_key_value_heads * self.head_dim

    @property
    def kda_width(self) -> int:
        return self.kda_num_heads * self.kda_head_dim

    @property
    def n_positions(self) -> int:
        """What the engine checks a slot's length against."""
        return self.max_position_embeddings

    @classmethod
    def published(cls, **kw):
        """Solar-Open2-250B's sizes, every expert held."""
        return cls(**kw)

    @classmethod
    def v5e128_share(cls, **kw):
        """One chip's share of a v5e-128 on which 16 chips share each layer
        (20 experts a layer, 1/8 of the vocabulary's rows), at the depth one
        chip serves beside its float32 reference (one whole period): the
        sizes of ``benchmark/configs/solar-open2-250b.json``."""
        base = dict(num_hidden_layers=4, vocab_size=24576, experts_held=20)
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny(cls, **kw):  # tests
        base = dict(
            vocab_size=256, hidden_size=64, moe_intermediate_size=32,
            num_hidden_layers=8, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, kda_num_heads=4,
            kda_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
            max_position_embeddings=512)
        base.update(kw)
        return cls(**base)


# -- parameters ----------------------------------------------------------------

def _common_spec(cfg):
    d = cfg.hidden_size
    shared = cfg.n_shared_experts * cfg.moe_intermediate_size
    return (
        ("input_norm", (("scale", (d,)),)),
        ("post_norm", (("scale", (d,)),)),
        ("router", (("kernel", (d, cfg.n_routed_experts)),
                    ("bias", (cfg.n_routed_experts,)))),
        ("shared", mlp_spec(d, shared)),
        ("experts", mlp_spec(d, cfg.moe_intermediate_size,
                             lead=(cfg.held,))),
    )


def _gqa_spec(cfg):
    d, hd = cfg.hidden_size, cfg.head_dim
    wide = cfg.num_attention_heads * hd
    return (
        ("q", (("kernel", (d, wide)),)),
        ("k", (("kernel", (d, cfg.num_key_value_heads * hd)),)),
        ("v", (("kernel", (d, cfg.num_key_value_heads * hd)),)),
        ("gate", (("kernel", (d, wide)),)),
        ("o", (("kernel", (wide, d)),)),
    )


def _kda_spec(cfg):
    """``qkv`` holds W_q's columns, then W_k's, then W_v's; ``conv`` one
    depthwise filter a channel of the three, tap ``j`` on the input ``kernel
    - 1 - j`` positions back (named ``scale``: it multiplies a channel, and
    a seeded draw leaves a filter near 1 that passes its input on)."""
    d, f, r = cfg.hidden_size, cfg.kda_width, cfg.kda_gate_rank
    return (
        ("qkv", (("kernel", (d, 3 * f)),)),
        ("conv", (("scale", (cfg.kda_conv_size, 3 * f)),)),
        ("a_down", (("kernel", (d, r)),)),
        ("a_up", (("kernel", (r, f)),)),
        ("beta", (("kernel", (d, cfg.kda_num_heads)),)),
        ("g_down", (("kernel", (d, r)),)),
        ("g_up", (("kernel", (r, f)),)),
        ("o_norm", (("scale", (cfg.kda_head_dim,)),)),
        ("o", (("kernel", (f, d)),)),
    )


def _decay_spec(cfg):
    """The decay's rate a head and offset a channel: float32 leaves
    whatever ``dtype`` (a group of their own, declared so)."""
    return (("A_log", (cfg.kda_num_heads,)), ("dt_bias", (cfg.kda_width,)))


def param_spec(cfg):
    d = cfg.hidden_size
    return (
        ("embed", (cfg.vocab_size, d)),
        # What every layer has, stacked over all of them; then each kind of
        # mixer stacked over the layers of its kind, in their order.
        ("layers", stacked(_common_spec(cfg), cfg.num_hidden_layers)),
        ("gqa", stacked(_gqa_spec(cfg), cfg.n_gqa_layers)),
        ("kda", stacked(_kda_spec(cfg), cfg.n_kda_layers)),
        ("kda_decay", stacked(_decay_spec(cfg), cfg.n_kda_layers)),
        ("final_norm", (("scale", (d,)),)),
        ("head", (("kernel", (d, cfg.vocab_size)),)),
    )


# -- the linear attention's mathematics ----------------------------------------

def kda_project(cfg, p, xn, tail):
    """``xn`` ``(B, T, d)`` (normalized, compute type) and the convolution's
    inputs at the ``kernel - 1`` positions before it ``tail`` ``(B, kernel -
    1, 3F)`` -> ``q``, ``k``, ``v`` ``(B, T, H, D)`` float32 (``q``, ``k``
    normalized, ``q`` scaled), the decay's logarithm ``(B, T, H, D)`` and
    the step ``(B, T, H)`` float32, the output gate ``(B, T, F)`` float32,
    and the tail this call leaves."""
    B, T, _ = xn.shape
    H, D, f = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_width
    taps = cfg.kda_conv_size
    # The convolution reads its inputs as the tail holds them: rounded once.
    pre = dot("btd,df->btf", xn, p["qkv"]["kernel"], cfg.dtype)
    run = jnp.concatenate([tail.astype(cfg.dtype), pre], axis=1)
    w = p["conv"]["scale"].astype(jnp.float32)
    mixed = sum(w[j] * run[:, j:j + T].astype(jnp.float32)
                for j in range(taps))
    q, k, v = (a.reshape(B, T, H, D)
               for a in jnp.split(jax.nn.silu(mixed), 3, axis=-1))
    unit = lambda a: a * lax.rsqrt(
        jnp.sum(jnp.square(a), axis=-1, keepdims=True) + 1e-6)
    q, k = unit(q) * D ** -0.5, unit(k)
    low = lambda down, up: dot(
        "btr,rf->btf", dot("btd,dr->btr", xn, p[down]["kernel"], cfg.dtype),
        p[up]["kernel"])
    rate = jnp.exp(p["decay"]["A_log"])[:, None]                  # (H, 1)
    log_decay = -rate * jax.nn.softplus(
        low("a_down", "a_up") + p["decay"]["dt_bias"]).reshape(B, T, H, D)
    beta = jax.nn.sigmoid(dot("btd,dh->bth", xn, p["beta"]["kernel"]))
    if cfg.kda_allow_neg_eigval:
        beta = 2.0 * beta
    gate = jax.nn.sigmoid(low("g_down", "g_up"))
    return q, k, v, log_decay, beta, gate, run[:, T:]


def kda_step(state, q, k, v, log_decay, beta):
    """The recurrence for one position: ``state`` ``(B, H, D, D)`` float32
    (key channel, then value channel), ``q``, ``k``, ``v``, ``log_decay``
    ``(B, H, D)``, ``beta`` ``(B, H)`` -> the output ``(B, H, D)`` and the
    new state.  Elementwise products and sums over the key channel, all in
    float32: the step is bound by reading and writing the state."""
    decayed = jnp.exp(log_decay)[..., None] * state
    seen = jnp.sum(decayed * k[..., None], axis=-2)         # S^T k, (B, H, D)
    state = decayed + (beta[..., None] * k)[..., None] * (
        v - seen)[..., None, :]
    return jnp.sum(state * q[..., None], axis=-2), state


def _decayed_gram(a, k, g, dtype):
    """``out[t, i] = sum_c a[t, c] k[i, c] exp(g[t, c] - g[i, c])`` for ``i
    <= t`` and 0 above the diagonal, over the last two axes ``(C, D)`` of
    each; ``g`` falls along ``C`` (a running sum of log decays).  ``exp(g_t
    - g_i)`` cannot be split into a factor a row and a factor a column
    without one of them overflowing where a channel decays fast, so a
    ``KDA_BLOCK``-square block on the diagonal takes the differences
    themselves (elementwise, float32), and a block below it splits at the
    row block's first position, where both factors are at most 1: a matrix
    product on operands in ``dtype``."""
    C, D = a.shape[-2:]
    b = min(KDA_BLOCK, C)
    R = C // b
    lead = a.shape[:-2]
    ab, kb, gb = (x.reshape(lead + (R, b, D)) for x in (a, k, g))
    diff = gb[..., :, None, :] - gb[..., None, :, :]          # (R, b, b, D)
    lower = jnp.tril(jnp.ones((b, b), bool))[..., None]
    weight = jnp.exp(jnp.where(lower, diff, -jnp.inf))
    diag = jnp.sum(ab[..., :, None, :] * kb[..., None, :, :] * weight,
                   axis=-1)                                    # (R, b, b)
    rows = []
    for r in range(R):
        parts = []
        if r:
            edge = gb[..., r - 1, b - 1, :]                    # g before block
            left = (ab[..., r, :, :]
                    * jnp.exp(gb[..., r, :, :] - edge[..., None, :]))
            right = (kb[..., :r, :, :] * jnp.exp(
                edge[..., None, None, :] - gb[..., :r, :, :])
            ).reshape(lead + (r * b, D))
            parts.append(dot("...td,...id->...ti", left.astype(dtype),
                              right.astype(dtype)))
        parts.append(diag[..., r, :, :])
        if r < R - 1:
            parts.append(jnp.zeros(lead + (b, (R - 1 - r) * b), jnp.float32))
        rows.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(rows, axis=-2)


def kda_chunk(state, q, k, v, log_decay, beta, dtype=jnp.float32):
    """The recurrence over a run of positions, chunk-wise: ``state`` ``(B,
    H, D, D)`` float32 before the run's first position, ``q``, ``k``, ``v``,
    ``log_decay`` ``(B, T, H, D)``, ``beta`` ``(B, T, H)`` -> the outputs
    ``(B, T, H, D)`` float32 and the state after the run's last position.

    Within a chunk of ``C = KDA_CHUNK`` positions, with ``g_t`` the running
    sum of ``log_decay`` (float32) and ``u_t = beta_t (v_t - S_{t-1}^T
    Diag(alpha_t) k_t)`` the rule's corrected values, ``S_t = Diag(e^{g_t})
    S_0 + sum_{i<=t} Diag(e^{g_t - g_i}) k_i u_i^T`` gives ``(I + A) U =
    beta V - (beta K e^g) S_0`` with ``A[t, i] = beta_t sum_c k_t k_i e^{g_t
    - g_i}`` strictly below the diagonal: one triangular solve a chunk
    (float32) yields ``U = U_v - W S_0`` for any ``S_0``; the chunks then go
    in turn, each three products on its state.  Products take operands in
    ``dtype`` and accumulate in float32.  Positions that pad the run to
    whole chunks have decay 1 and step 0: they change nothing."""
    B, T, H, D = q.shape
    C = KDA_CHUNK
    N = -(-T // C)
    pad = N * C - T

    def chunks(a):                      # (B, T, H, ...) -> (N, B, H, C, ...)
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((B, N, C) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)

    q, k, v, log_decay = (chunks(a.astype(jnp.float32))
                          for a in (q, k, v, log_decay))
    beta = chunks(beta.astype(jnp.float32))[..., None]        # (N, B, H, C, 1)
    g = jnp.cumsum(log_decay, axis=-2)
    a_kk = _decayed_gram(k, k, g, dtype)
    a_qk = _decayed_gram(q, k, g, dtype)
    # ``a_kk`` is zero above the diagonal already; the diagonal is the I.
    system = jnp.where(jnp.eye(C, dtype=bool), 1.0, beta * a_kk)
    rhs = jnp.concatenate([beta * v, beta * k * jnp.exp(g)], axis=-1)
    solved = lax.linalg.triangular_solve(
        system, rhs, left_side=True, lower=True, unit_diagonal=True)
    u_v, w = solved[..., :D], solved[..., D:]
    total = g[..., -1:, :]                                     # (.., 1, D)
    q_in = (q * jnp.exp(g)).astype(dtype)
    k_out = (k * jnp.exp(total - g)).astype(dtype)

    def one_chunk(state, xs):
        q_in, a_qk, u_v, w, k_out, carry = xs
        held = state.astype(dtype)
        u = u_v - dot("bhck,bhkv->bhcv", w.astype(dtype), held)
        out = (dot("bhck,bhkv->bhcv", q_in, held)
               + dot("bhci,bhiv->bhcv", a_qk.astype(dtype), u.astype(dtype)))
        state = carry[..., None] * state + dot(
            "bhck,bhcv->bhkv", k_out, u.astype(dtype))
        return state, out

    state, out = lax.scan(
        one_chunk, state.astype(jnp.float32),
        (q_in, a_qk, u_v, w, k_out, jnp.exp(total[..., 0, :])))
    out = jnp.moveaxis(jnp.moveaxis(out, 0, 2), 1, 3)          # (B, N, C, H, D)
    return out.reshape(B, N * C, H, D)[:, :T], state


def kda_output(cfg, p, out, gate):
    """The heads' outputs ``(B, T, H, D)`` float32, normalized a head and
    gated, through ``W_o`` -> ``(B, T, d)`` float32."""
    B, T = out.shape[:2]
    normed = rms_norm(out, p["o_norm"]["scale"], cfg.rms_norm_eps)
    return dot("btf,fd->btd",
                (normed.reshape(B, T, cfg.kda_width) * gate).astype(cfg.dtype),
                p["o"]["kernel"])


def gqa_project(cfg, p, xn):
    """``xn`` -> ``q`` ``(B, T, Hkv, G, D)``, ``k`` and ``v`` ``(B, T, Hkv,
    D)``, each rounded once (no rotation, no norm), and the output gate
    ``(B, T, H * D)`` float32, elementwise and from the layer's input."""
    B, T, _ = xn.shape
    hkv, hd = cfg.num_key_value_heads, cfg.head_dim
    g = cfg.num_attention_heads // hkv
    q = dot("btd,df->btf", xn, p["q"]["kernel"], cfg.dtype).reshape(
        B, T, hkv, g, hd)
    k = dot("btd,df->btf", xn, p["k"]["kernel"], cfg.dtype).reshape(
        B, T, hkv, hd)
    v = dot("btd,df->btf", xn, p["v"]["kernel"], cfg.dtype).reshape(
        B, T, hkv, hd)
    gate = jax.nn.sigmoid(dot("btd,df->btf", xn, p["gate"]["kernel"]))
    return q, k, v, gate


# -- the module ----------------------------------------------------------------

class SolarOpen2(nn.Module):
    cfg: SolarOpen2Config
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, tokens, *, deterministic: bool = True,
                 decode: bool = False, slot_ids=None,
                 paged: Optional[PagedKVConfig] = None, block_tables=None,
                 live=None):
        cfg = self.cfg
        B, T = tokens.shape
        spec = param_spec(cfg)
        params = declare(
            self, tuple(g for g in spec if g[0] != "kda_decay"), cfg)
        params.update(declare(
            self, tuple(g for g in spec if g[0] == "kda_decay"),
            dataclasses.replace(cfg, dtype=jnp.float32)))
        x = params["embed"][tokens].astype(jnp.float32)
        view = PagedCall(
            self, B, T, decode=decode, slot_ids=slot_ids, paged=paged,
            block_tables=block_tables, live=live, pools="the K/V pool",
            refusals=SERVE_REFUSALS, experts=(cfg.num_hidden_layers, cfg.held))

        n_kda = cfg.n_kda_layers
        H, D = cfg.kda_num_heads, cfg.kda_head_dim
        tail_shape = (cfg.kda_conv_size - 1, 3 * cfg.kda_width)
        pool = view.pool("full_pool", cfg.n_gqa_layers, cfg.kv_row, cfg.dtype)
        state = view.leaf("kda_state", (n_kda, B, H, D, D), jnp.float32)
        conv = view.leaf("kda_conv", (n_kda, B) + tail_shape, cfg.dtype)
        positions = view.positions
        # One of the engine's decode programs: every slot, in order.
        in_place = live is not None
        if decode:
            slots = state.shape[1]
            if in_place and (B != slots or T != 1):
                raise ValueError(
                    f"a call with live is a decode step over all {slots} "
                    f"slots in order, got {B} rows of {T} positions")
        mask = attention_mask(positions, view.key_positions(), None)
        view.advance()
        lengths = view.lengths
        # A row at position 0 has no history, whatever its slot held.
        fresh = view.start == 0 if decode else None
        keep = ~live.astype(bool) if in_place else None
        token_live = view.token_live

        def gqa(p, x, xn, pool_v, layer):
            q, k, v, gate = gqa_project(cfg, p, xn)
            ctx, pool_v = view.gqa(
                pool_v, layer, q, k, v, mask=mask, lengths=lengths,
                paths=(GATHER_FULL, KERNEL_FULL))
            return x + dot("btf,fd->btd", (ctx * gate).astype(cfg.dtype),
                           p["o"]["kernel"]), pool_v

        def kda(p, x, xn, state_v, conv_v, layer):
            if state_v is None:
                s0 = jnp.zeros((B, H, D, D), jnp.float32)
                tail = jnp.zeros((B,) + tail_shape, cfg.dtype)
            else:
                held = lambda leaf: lax.dynamic_index_in_dim(
                    leaf, layer, keepdims=False)
                s_old, tail_old = held(state_v), held(conv_v)
                if not in_place:
                    s_old, tail_old = s_old[slot_ids], tail_old[slot_ids]
                s0 = jnp.where(fresh[:, None, None, None], 0.0, s_old)
                tail = jnp.where(fresh[:, None, None], 0, tail_old)
            q, k, v, log_decay, beta, gate, tail = kda_project(
                cfg, p, xn, tail)
            if T == 1:
                paged_attention.note_path(KDA_STEP_PATH)
                with jax.named_scope("kda_step"):
                    out, s1 = kda_step(s0, q[:, 0], k[:, 0], v[:, 0],
                                       log_decay[:, 0], beta[:, 0])
                    out = out[:, None]
            else:
                paged_attention.note_path(KDA_CHUNK_PATH)
                with jax.named_scope("kda_chunk"):
                    out, s1 = kda_chunk(s0, q, k, v, log_decay, beta,
                                        cfg.dtype)
            if state_v is not None:
                if keep is not None:
                    s1 = jnp.where(keep[:, None, None, None], s_old, s1)
                    tail = jnp.where(keep[:, None, None], tail_old, tail)
                at = (layer,) if in_place else (layer, slot_ids)
                state_v = state_v.at[at].set(s1)
                conv_v = conv_v.at[at].set(tail)
            return x + kda_output(cfg, p, out, gate), state_v, conv_v

        period = cfg.period
        kinds = cfg.kinds[:period]
        gqa_per = sum(kinds)

        def one_period(carry, n):
            x, pool_v, state_v, conv_v = carry
            rows = []
            seen = [0, 0]                   # kda, gqa layers so far
            for i, is_gqa in enumerate(kinds):
                p = layer_leaves(params["layers"], n * period + i)
                xn = rms_norm(x, p["input_norm"]["scale"],
                              cfg.rms_norm_eps).astype(cfg.dtype)
                if is_gqa:
                    layer = n * gqa_per + seen[1]
                    with jax.named_scope("gqa"):
                        h, pool_v = gqa(layer_leaves(params["gqa"], layer),
                                        x, xn, pool_v, layer)
                else:
                    layer = n * (period - gqa_per) + seen[0]
                    mixer = dict(
                        layer_leaves(params["kda"], layer),
                        decay=layer_leaves(params["kda_decay"], layer))
                    h, state_v, conv_v = kda(mixer, x, xn, state_v, conv_v,
                                             layer)
                seen[is_gqa] += 1
                hn = rms_norm(h, p["post_norm"]["scale"], cfg.rms_norm_eps)
                y, row = expert_layer(
                    cfg, dict(p, experts=params["layers"]["experts"]),
                    hn.reshape(B * T, cfg.hidden_size), token_live,
                    layer=n * period + i, mesh=self.mesh)
                x = h + y.reshape(h.shape)
                rows.append(row)
            return (x, pool_v, state_v, conv_v), jnp.stack(rows)

        (x, *carried), rows = lax.scan(
            one_period, (x, pool, state, conv),
            jnp.arange(cfg.num_hidden_layers // period, dtype=jnp.int32))
        view.close(*carried, counts=rows)
        return view.head(params, x)


# -- what the engine and the scheduler ask of a decoder family -----------------

def cache_geometry(cfg: SolarOpen2Config, paged: PagedKVConfig
                   ) -> Dict[str, Any]:
    """Bytes a token (K/V of the grouped-query layers, in the paged pool)
    beside bytes a slot (the linear layers' state and convolution tail,
    whatever the row's length)."""
    itemsize = jnp.dtype(cfg.dtype).itemsize
    per_layer = cfg.kv_row * itemsize
    state = cfg.kda_num_heads * cfg.kda_head_dim ** 2 * 4
    tail = (cfg.kda_conv_size - 1) * 3 * cfg.kda_width * itemsize
    return {
        "kind": "recurrent_state_and_key_value",
        "pools_per_layer": 1,
        "values_per_token_layer": cfg.kv_row,
        "pool_width": cfg.kv_row,
        "padding_values": 0,
        "bytes_per_token_layer": per_layer,
        "bytes_per_token": cfg.n_gqa_layers * per_layer,
        "kv_layers": cfg.n_gqa_layers,
        "state_layers": cfg.n_kda_layers,
        "state_bytes_per_slot_layer": state,
        "conv_bytes_per_slot_layer": tail,
        "state_bytes_per_slot": cfg.n_kda_layers * (state + tail),
        "pool_bytes": (cfg.n_gqa_layers * paged.num_blocks
                       * paged.block_size * per_layer),
    }


SERVE_REFUSALS = serve_refusals(
    "the K/V pool",
    kv_dtype=(
        "the pool is stored in the compute type, and the linear layers' "
        "state is float32: a narrower state is another model's arithmetic"),
    per_shard_kv=(
        "the pool and the per-slot state are replicated: per-shard pools "
        "are not built for them"),
    slo_scheduling=(
        "preemption with swap and host tiering move K/V blocks and have no "
        "snapshot of a slot's recurrent state: a resumed victim would "
        "decode from another request's state"),
    spec_k=(
        "a verify launch advances the recurrent state over every draft "
        "position, and a rejected draft cannot be rolled back out of it"),
    prefix_cache=(
        "a shared prefix is K/V blocks and the state after its last "
        "position, and no snapshot of a state is kept yet"),
    tensor_mesh=(
        "the per-slot state, eight K/V heads and the expert stack have no "
        "tensor rule: serve on a mesh without a 'tensor' axis"))


def _served_dtypes(cfg: SolarOpen2Config, params) -> Any:
    """The type a server holds each parameter in
    (``Workload.served_dtypes``): the compute type, but for the leaves the
    programs read in float32 (the router's bias, the decay).  A checkpoint in
    float32 is rounded once, where it enters the engine."""
    def one(path, leaf):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        return (jnp.dtype(jnp.float32)
                if name.endswith("router/bias") or "kda_decay" in name
                else jnp.dtype(cfg.dtype))

    return jax.tree_util.tree_map_with_path(one, params)


def make_workload(*, preset: str = "published",
                  config: Optional[SolarOpen2Config] = None,
                  mesh: Optional[Mesh] = None, **kw) -> Workload:
    cfg = config or getattr(SolarOpen2Config, preset)()
    return decoder_workload("solar_open2", SolarOpen2, cfg, mesh,
                            cache_geometry, SERVE_REFUSALS, _served_dtypes,
                            **kw)
