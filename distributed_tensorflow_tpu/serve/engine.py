"""Inference engine: checkpoint -> sharded params -> jitted forward.

The serving counterpart of ``train_lib.build_state_and_step``: restore a
checkpoint into INFERENCE-ONLY variables (no optimizer state ever
materializes on device — ``CheckpointManager.restore_params`` reads the raw
tree and keeps only params/model_state), re-shard them to the current mesh
with the workload's ``ShardingRules``, and serve two jitted paths:

- ``generate``: GPT-2 prefill + KV-cache incremental decode
  (``models.gpt2`` ``decode=True``); the cache is preallocated per
  (batch, total_len) geometry and TP-sharded over heads
  (``gpt2_cache_rules``), batch over the data axes.
- ``classify``: single batched forward for the classification workloads
  (mnist / resnet50 / bert), deterministic, BatchNorm on running stats.

Shape discipline: callers go through ``pad_rows``/``bucket_rows`` so each
jitted program sees a small fixed set of batch shapes (the dynamic batcher
bounds the set further by bucketing requests); the batch dim is always a
multiple of the mesh's data-parallel extent so GSPMD never sees an uneven
batch split.
"""

from __future__ import annotations

import contextlib
import functools
import inspect
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from distributed_tensorflow_tpu import cluster as cluster_lib
from distributed_tensorflow_tpu.checkpoint import CheckpointManager
from distributed_tensorflow_tpu.models import Workload, get_workload
from distributed_tensorflow_tpu.obs import metrics as obs_metrics
from distributed_tensorflow_tpu.obs.trace import default_tracer, now, spanned
from distributed_tensorflow_tpu.ops import paged_attention
from distributed_tensorflow_tpu.parallel.sharding import (
    apply_shardings,
    batch_sharding,
)
from distributed_tensorflow_tpu.serve import sampling as sampling_lib

logger = logging.getLogger(__name__)
PyTree = Any

# PROCESS-wide launch serialization for the slot programs (and the hot
# reload's sharded device_put).  Fleet replicas all map onto this
# process's one device set, and XLA runs a collective by parking one
# participant thread per device on a SHARED pool until all arrive — two
# replicas' concurrent launches interleave their participants on that
# pool and deadlock the rendezvous.  One program in flight at a time is
# what the hardware does anyway; the lock just makes the queueing happen
# host-side instead of inside XLA's rendezvous.
#
# THREAD DISCIPLINE for async serving: every compiled-program LAUNCH
# (and every sharded device_put) takes this lock, whatever thread it
# runs on.  A ``jax.device_get`` of a launch's OUTPUT is not a launch —
# it joins the device stream read-only and needs no lock — which is
# what lets the scheduler's dedicated fetch thread resolve in-flight
# outputs while the loop thread dispatches the next program under the
# lock.  Code on the fetch thread must never call anything that
# compiles or launches (no ``jax.jit`` entry, no device_put of sharded
# trees); it only ever touches launch outputs.
_launch_lock = threading.Lock()

# What a launch of a program the caches already hold runs inside, where a
# program's first launch runs inside ``dtt/startup/program_first_launch``.
_WARM = contextlib.nullcontext()


def _named(name: str, fn: Callable, *bound) -> Callable:
    """``fn`` (with ``bound`` leading arguments) under a ``__name__`` of
    its own, so that the program ``jax.jit`` makes of it is ``jit_<name>``
    in the HLO and on the profiler's ``XLA Modules`` line.  A
    ``functools.partial`` has no name (JAX calls every one
    ``jit__unknown``) and a lambda only ``<lambda>``: without this the
    trace cannot say which launch is which program."""
    named = functools.partial(fn, *bound)
    named.__name__ = name
    return named


def _engine_instruments(registry=None):
    """Engine-side families: the program caches' misses (what XLA then
    compiled or read, by program and cache outcome, is
    ``dtt_compiles_total`` of ``compile_cache.py``: a compile during
    steady-state serving is the shape-bucketing bug it surfaces), and
    host-side dispatch timing for the fused launches.  Instrumentation is
    entirely host-side — it never enters the jitted programs, so the
    greedy decode programs stay bit-identical."""
    r = registry or obs_metrics.default_registry()
    return {
        "compile_total": r.counter(
            "dtt_serve_compile_total",
            "Serving program compiles (program-cache misses, all kinds) "
            "since engine start — flat after warmup is the no-recompile "
            "claim under mixed sampling traffic"),
        "programs_cached": r.gauge(
            "dtt_serve_programs_cached",
            "Distinct compiled serving programs resident in the "
            "program caches — ONE set per (family, paged, K/k) "
            "regardless of the sampling parameter mix"),
        "megastep": r.histogram(
            "dtt_serve_megastep_seconds",
            "Host-side megastep dispatch duration (K fused decode steps)"),
        "verify": r.histogram(
            "dtt_serve_verify_seconds",
            "Host-side speculative-verify dispatch duration "
            "(one (num_slots, k+1) forward)"),
        "decode_attention": r.counter(
            "dtt_serve_decode_attention_launches_total",
            "Paged decode launches (decode_slots, decode_megastep) by the "
            "attention path the launched program was traced with: the "
            "block-table kernel (the grouped-query family's by kind of "
            "layer), or the whole-row gather",
            labelnames=("path",)),
        "params_bytes": r.gauge(
            "dtt_serve_params_bytes",
            "Bytes of the served parameter tree by leaf type, as the engine "
            "last placed or installed it",
            labelnames=("dtype",)),
    }


def _select_next_scalar(logits: jax.Array, rng, counter, temperature: float,
                        top_k: int) -> jax.Array:
    """Scalar-config next-token selection over (B, V) last-position
    logits — the fixed-batch ``generate`` family, whose programs stay
    keyed by the (canonicalized) scalar config and anchor the
    vector-vs-scalar bit-parity suite.

    ``temperature <= 0`` is greedy argmax (the default, and what every
    parity test pins).  Otherwise temperature/top-k sampling with the
    in-step RNG pattern (async-loop contract, PR 1): the caller passes ONE
    base key plus a step counter and the per-step key is derived by
    ``fold_in`` INSIDE the compiled program — no host-side split per token,
    so the decode dispatch loop stays sync-free.
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits.astype(jnp.float32) / temperature
    if top_k:
        kth = jnp.sort(scaled, axis=-1)[:, -int(top_k)][:, None]
        scaled = jnp.where(scaled < kth, jnp.finfo(jnp.float32).min, scaled)
    key = jax.random.fold_in(rng, jnp.asarray(counter).astype(jnp.uint32))
    return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)


def _select_next(logits: jax.Array, rng, counter, sampling,
                 counts: jax.Array) -> jax.Array:
    """Vectorized per-ROW next-token selection over (B, V) last-position
    logits — ONE compiled program for any mix of per-request configs.

    ``sampling`` is the per-row vector dict (``serve.sampling.pack``):
    ``temperature``/``top_k``/``top_p``/``presence``/``frequency``/
    ``seed``/``step``, each (B,) and all RUNTIME arrays — varying them
    never recompiles.  ``counts`` is the (B, V) emitted-token count
    matrix the penalties read.  Per-row semantics, each an EXACT no-op
    at its default so a uniform vector is bit-identical to the old
    scalar program:

    - penalties first: ``logits - presence * (count > 0) - frequency *
      count`` (subtracting exact f32 zeros at 0.0 penalties);
    - ``temperature <= 0`` rows take penalized argmax via the final
      ``jnp.where`` — greedy rows ride the same program (greedy-row
      equivalence);
    - per-row top-k keeps the k highest logits (k-th largest via ONE
      ascending sort + ``take_along_axis``; ``k <= 0`` lowers the
      threshold to -inf, keeping all) — the same mask values the scalar
      static-k path computed;
    - per-row top-p keeps the smallest descending-sorted nucleus whose
      EXCLUSIVE cumulative softmax mass is below p (the argmax always
      survives), mapped back through the inverse permutation; ``p = 1``
      rows pass through untouched;
    - rows with ``seed < 0`` draw from the shared
      ``fold_in(rng, counter)`` key over the whole (B, V) batch — the
      categorical the scalar program ran; rows with a seed derive a
      private key from ``fold_in(key(seed), 0x5EED, step)`` so their
      stream depends only on (seed, params, history), never on batch
      composition, counter interleaving, megastep K, or spec k.
    """
    logits = logits.astype(jnp.float32)
    temps = sampling["temperature"]

    def _all_greedy(_):
        # Fast branch: every row greedy AND unpenalized, so the epilogue
        # is exactly the pre-vectorization argmax — no RNG, no sorts.
        # Subtracting the all-zero penalties is bit-exact (x - 0.0 == x),
        # so skipping them changes nothing.
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _mixed(_):
        counts_f = counts.astype(jnp.float32)
        penalized = (logits
                     - sampling["presence"][:, None]
                     * (counts_f > 0).astype(jnp.float32)
                     - sampling["frequency"][:, None] * counts_f)
        greedy_tok = jnp.argmax(penalized, axis=-1).astype(jnp.int32)
        scaled = penalized / jnp.where(temps > 0.0, temps, 1.0)[:, None]
        vocab = scaled.shape[-1]
        srt = jnp.sort(scaled, axis=-1)  # ascending
        tk = jnp.clip(sampling["top_k"], 0, vocab)
        kth = jnp.take_along_axis(
            srt, jnp.clip(vocab - tk, 0, vocab - 1)[:, None], axis=-1)
        kth = jnp.where(tk[:, None] > 0, kth, -jnp.inf)
        scaled = jnp.where(scaled < kth, jnp.finfo(jnp.float32).min, scaled)
        order = jnp.argsort(scaled, axis=-1)[:, ::-1]  # descending
        sorted_probs = jax.nn.softmax(
            jnp.take_along_axis(scaled, order, axis=-1), axis=-1)
        exclusive_cum = jnp.cumsum(sorted_probs, axis=-1) - sorted_probs
        keep = jnp.take_along_axis(
            exclusive_cum < sampling["top_p"][:, None],
            jnp.argsort(order, axis=-1), axis=-1)
        nucleus = (sampling["top_p"] < 1.0)[:, None] & ~keep
        scaled = jnp.where(nucleus, jnp.finfo(jnp.float32).min, scaled)
        key = jax.random.fold_in(rng, jnp.asarray(counter).astype(jnp.uint32))
        shared = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)

        def _seeded_row(seed, step, row):
            rk = jax.random.fold_in(
                jax.random.fold_in(
                    jax.random.key(seed.astype(jnp.uint32)), 0x5EED),
                step.astype(jnp.uint32))
            return jax.random.categorical(rk, row).astype(jnp.int32)

        seeded = jax.vmap(_seeded_row)(
            sampling["seed"], sampling["step"], scaled)
        sampled = jnp.where(sampling["seed"] >= 0, seeded, shared)
        return jnp.where(temps <= 0.0, greedy_tok, sampled)

    # Runtime dispatch INSIDE the one compiled program: an all-greedy
    # batch (the default traffic, and every legacy caller) never executes
    # the RNG/sort epilogue, so vectorization costs greedy decode nothing.
    return jax.lax.cond(
        jnp.all((temps <= 0.0)
                & (sampling["presence"] == 0.0)
                & (sampling["frequency"] == 0.0)),
        _all_greedy, _mixed, None)


def _bump_counts(counts: jax.Array, rows, toks, inc_mask) -> jax.Array:
    """+1 at (row, token) where ``inc_mask`` — the emitted-token
    accounting the presence/frequency penalties read.  Masked rows add 0
    at whatever (garbage) token they carry, leaving their counts exact."""
    return counts.at[rows, toks].add(inc_mask.astype(counts.dtype))


def pad_rows(arr: np.ndarray, target: int) -> np.ndarray:
    """Pad the leading (batch) dim to ``target`` rows by repeating the last
    row — inert filler whose outputs the caller slices off."""
    n = arr.shape[0]
    if n == target:
        return arr
    if n > target:
        raise ValueError(f"batch {n} exceeds padded target {target}")
    pad = np.repeat(arr[-1:], target - n, axis=0)
    return np.concatenate([arr, pad], axis=0)


def _trim_at_eos(row: np.ndarray, eos_token: Optional[int]) -> np.ndarray:
    """Cut a generated row just past its first eos (inclusive); unchanged
    when ``eos_token`` is None or never emitted."""
    if eos_token is None:
        return row
    hits = np.flatnonzero(row == eos_token)
    return row if hits.size == 0 else row[: int(hits[0]) + 1]


class ServeEngine:
    """Checkpoint-backed inference over a mesh.

    ``checkpoint_dir=None`` (or an empty directory) falls back to fresh
    random init — the smoke/benchmark path when no training run preceded.

    THE SERVED WEIGHTS.  ``self.params`` is the one parameter tree: what
    every program takes and what the scheduler's generations pin.  It has
    the checkpoint's paths and shapes, and each leaf in the type the
    programs read it in: a leaf the family says every served program reads
    only through a cast to the compute type (``Workload.served_dtypes``;
    GPT-2's ``Dense`` kernels and biases, ``wte``, ``wpe``) is rounded to
    that type once, where weights enter the engine (the fresh draw or the
    restore here, ``shard_params``, ``install_params``), which gives the
    bits that rounding it in every launch gave and saves every launch the
    pass over all the layers.  Everything else (the layer norms, a family
    that names no leaf, a leaf already in the compute type, a float32
    compute type) stays as it came.  No float32 twin is kept on the device;
    a checkpoint stays float32 on disk and in the trainer.
    """

    @spanned("engine_init", "startup")
    def __init__(
        self,
        model: str = "gpt2",
        *,
        mesh=None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_step: Optional[int] = None,
        seed: int = 0,
        **workload_overrides,
    ):
        self._tracer = default_tracer()
        self.mesh = mesh if mesh is not None else cluster_lib.build_mesh(
            cluster_lib.MeshConfig())
        self.workload: Workload = get_workload(
            model, mesh=self.mesh, **workload_overrides)
        self.model = model
        # Named size of the served config (None = the workload's default):
        # the driver's JSON line reports it next to the device.
        self.preset: Optional[str] = workload_overrides.get("preset")
        self.module = self.workload.module
        # Fail fast on a decode-incompatible mesh: KV-cache decode runs
        # the scanned block stack directly, which a pipeline-split mesh
        # cannot serve — the model would only raise this deep inside its
        # first decode apply, after params were already materialized.
        pipe = self.mesh.shape.get("pipe", 1)
        decodes = "decode" in inspect.signature(
            type(self.module).__call__).parameters
        if pipe > 1 and decodes:
            raise ValueError(
                f"ServeEngine cannot serve model {model!r} on a mesh with "
                f"a 'pipe' axis of size {pipe}: KV-cache decode "
                f"(decode=True) is unsupported under pipeline parallelism "
                f"— re-mesh without the pipe axis (TP/DP shardings apply) "
                f"or dedicate a pipe-free mesh slice to serving")
        self._manager: Optional[CheckpointManager] = None
        self._generate_fns: Dict[Any, Callable] = {}
        # KV-tiering block programs live in their own cache: they donate
        # their FIRST argument (the cache/counts being rewritten), unlike
        # every decode program in _generate_fns (params first, cache
        # donated at position 1) — one dict per donation signature keeps
        # the donated-position story uniform within each cache.
        self._block_fns: Dict[Any, Callable] = {}
        self._cache_init_fns: Dict[Any, Callable] = {}
        # Program-cache key -> {attention path: times traced with it}.
        self._attention_paths: Dict[Any, Dict[str, int]] = {}
        self._obs = _engine_instruments()
        self.restored_step: Optional[int] = None
        # Base sampling key (in-step RNG: folded with a step counter inside
        # the compiled step, never split on the host per token).
        self._sample_rng = jax.random.fold_in(jax.random.key(seed), 0x53)

        def draw():
            init_input = (
                self.workload.init_batch if self.workload.init_key is None
                else self.workload.init_batch[self.workload.init_key]
            )
            return dict(self.module.init(jax.random.key(seed), init_input))

        def init_fn():
            variables = draw()
            variables["params"] = self._in_served_types(variables["params"])
            return variables

        with self._tracer.span("abstract_params", cat="startup"):
            abstract = jax.eval_shape(draw)
        with self._tracer.span("shardings", cat="startup"):
            shardings = self.workload.rules.shardings_for(self.mesh, abstract)
        restored = None
        if checkpoint_dir:
            self._manager = CheckpointManager(checkpoint_dir)
            if self._manager.latest_step() is not None:
                params, model_state = self._manager.restore_params(
                    checkpoint_step)
                restored = dict(model_state or {})
                restored["params"] = params
                self.restored_step = (
                    checkpoint_step if checkpoint_step is not None
                    else self._manager.latest_step())
                logger.info("serving checkpoint step %s from %s",
                            self.restored_step, checkpoint_dir)
            else:
                logger.warning(
                    "no checkpoint under %s — serving FRESH-INIT params",
                    checkpoint_dir)
        # Parameters placed: a checkpoint's laid out over the mesh, or a
        # fresh draw made there (its program's compile falls inside).
        with self._tracer.span("params_placed", cat="startup",
                               args={"restored": restored is not None}):
            if restored is not None:
                restored["params"] = self._served(
                    restored["params"], always=True)
                variables = apply_shardings(restored, shardings)
            else:
                # The cast is the init program's own output types: no
                # program and no time of its own, so the span is a count.
                variables = jax.jit(init_fn, out_shardings=shardings)()
                self._note_cast(abstract["params"], variables["params"],
                                now(), always=True)
        self.params = variables.pop("params")
        self._note_params_bytes()
        self.model_state = variables  # e.g. {"batch_stats": ...} for resnet
        self._predict_fn = jax.jit(_named("predict", self._predict_apply))

    # -- generate (gpt2 KV-cache decode) -------------------------------------

    @property
    def data_parallelism(self) -> int:
        return (self.mesh.shape.get("data", 1)
                * self.mesh.shape.get("fsdp", 1))

    def bucket_rows(self, n: int) -> int:
        """Smallest power-of-two multiple of the data-parallel extent that
        fits ``n`` rows — the padded batch shapes jitted programs see."""
        b = max(1, self.data_parallelism)
        while b < n:
            b *= 2
        return b

    def _decode_apply(self, params, cache, tokens):
        logits, mutated = self.module.apply(
            {"params": params, "cache": cache}, tokens,
            decode=True, mutable=["cache"],
        )
        next_tokens = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return next_tokens, mutated["cache"]

    def _sampled_decode_apply(self, temperature, top_k, params, cache,
                              tokens, rng, counter):
        logits, mutated = self.module.apply(
            {"params": params, "cache": cache}, tokens,
            decode=True, mutable=["cache"],
        )
        nxt = _select_next_scalar(logits[:, -1, :], rng, counter,
                                  temperature, top_k)
        return nxt, mutated["cache"]

    @staticmethod
    def canonical_scalar_key(temperature: float, top_k: int):
        """Canonical (temperature, top_k) for the surviving scalar-keyed
        fixed-batch programs.  Every greedy config collapses to
        ``(0.0, 0)`` — ``temperature <= 0`` ignores both values, so
        ``(-1.0, 5)`` and ``(0.0, 0)`` are the SAME program and must not
        compile twice.  Sampled configs normalize representation only
        (float/int casts, negative top_k clamps to 0 = full vocab)."""
        if temperature <= 0.0:
            return (0.0, 0)
        return (float(temperature), max(0, int(top_k)))

    def _note_miss(self) -> None:
        """Account one program-cache miss: the total that must stay flat
        post-warmup, and the resident-program gauge (every miss inserts
        exactly one never-evicted program, so it advances here, at the
        insert site, and ``compile_stats`` never has to touch the caches
        themselves).  What XLA then compiles or reads is on record by
        program, stage and cache outcome (``compile_cache.py``)."""
        self._obs["compile_total"].inc()
        self._obs["programs_cached"].inc()

    def _first_launch(self, kind: str):
        """A program-cache miss whose site also makes the new program's
        first call: accounted (``_note_miss``), and the span that call runs
        inside until it returns, ``dtt/startup/program_first_launch``: the
        trace, the lowering and the compile or cache read of the shape the
        program is first met with fall inside it (``dtt/compile/*``), and
        what is left is the first dispatch."""
        self._note_miss()
        return self._tracer.span("program_first_launch", cat="startup",
                                 args={"kind": kind})

    def compile_stats(self) -> Dict[str, float]:
        """Compile/program-cache telemetry snapshot.  Reads
        internally-locked obs metrics only — deliberately takes neither
        ``_launch_lock`` nor a peek at the program dicts, so the
        scheduler can call it under its own lock (``stats()``) without a
        lock-order edge against the launch paths or an unlocked
        cross-thread dict read."""
        return {
            "programs_cached": self._obs["programs_cached"].value,
            "compile_total": self._obs["compile_total"].value,
        }

    def _recording_paths(self, key, fn: Callable) -> Callable:
        """``fn``, a program body that runs the model over the slot cache,
        putting on record what its paged attention chose
        (``ops.paged_attention``: the block-table kernel or the whole-row
        gather) under the program's cache key.  The body runs only while
        ``jax.jit`` traces it, and the choice is made there from what the
        call can observe, so this record is the only place it shows."""

        @functools.wraps(fn)
        def body(*args):
            with paged_attention.record_paths() as paths:
                out = fn(*args)
            for path in paged_attention.program_paths(paths):
                traced = self._attention_paths.setdefault(key, {})
                traced[path] = traced.get(path, 0) + 1
            return out

        return body

    def attention_paths(self) -> Dict[str, Dict[str, int]]:
        """Per program kind (``slot_prefill``, ``slot_decode``,
        ``slot_megastep``, ``slot_verify``...: the kinds ``compile_stats``
        counts), how many times a program of that kind was traced with
        each paged attention path.  Empty for programs over the dense
        slot cache."""
        out: Dict[str, Dict[str, int]] = {}
        for key, traced in list(self._attention_paths.items()):
            kind = out.setdefault(key[0], {})
            for path, n in traced.items():
                kind[path] = kind.get(path, 0) + n
        return out

    def decode_attention_launches(self) -> Dict[str, float]:
        """Paged decode launches so far by the launched program's
        attention path (the process-wide counter, like ``compile_stats``)."""
        return {path: self._obs["decode_attention"].labels(path=path).value
                for path in _DECODE_PATHS}

    def _count_decode_launch(self, key) -> None:
        for path in paged_attention.program_paths(
                self._attention_paths.get(key, ())):
            self._obs["decode_attention"].labels(path=path).inc()

    def _decode_step_fn(self, temperature: float, top_k: int) -> Callable:
        """Jitted fixed-batch decode step for one sampling config.  The
        greedy program is EXACTLY the pre-sampling one (no rng/counter
        arguments), so the default path stays bit-identical; greedy keys
        canonicalize to one program regardless of the (ignored) scalar
        values."""
        temperature, top_k = self.canonical_scalar_key(temperature, top_k)
        with _launch_lock:
            if temperature <= 0.0:
                if "step" not in self._generate_fns:
                    self._note_miss()   # generate() makes the first call
                    self._generate_fns["step"] = jax.jit(
                        _named("decode_step", self._decode_apply),
                        donate_argnums=(1,))
                return self._generate_fns["step"]
            key = ("step", temperature, top_k)
            if key not in self._generate_fns:
                self._note_miss()
                self._generate_fns[key] = jax.jit(
                    _named("sampled_decode", self._sampled_decode_apply,
                           temperature, top_k),
                    donate_argnums=(1,))
            return self._generate_fns[key]

    def init_cache(self, batch: int, total_len: int) -> PyTree:
        """Preallocated, sharded KV cache for ``batch`` rows of up to
        ``total_len`` (prompt + generated) tokens."""
        cache_rules = self._cache_rules()  # the workload's, by no model's name

        key = (batch, total_len)
        missed = key not in self._cache_init_fns
        if missed:

            def mk():
                vs = self.module.init(
                    jax.random.key(0),
                    jnp.zeros((batch, total_len), jnp.int32), decode=True)
                return vs["cache"]

            shapes = jax.eval_shape(mk)
            shardings = cache_rules().shardings_for(self.mesh, shapes)
            self._cache_init_fns[key] = jax.jit(
                _named("cache_init", lambda: jax.tree.map(
                    lambda s: jnp.zeros(s.shape, s.dtype), shapes)),
                out_shardings=shardings,
            )
        with self._first_launch("cache_init") if missed else _WARM:
            return self._cache_init_fns[key]()

    # -- resident slot cache (continuous batching) ---------------------------

    def init_slot_cache(self, num_slots: int, total_len: int) -> PyTree:
        """ONE resident KV cache for the continuous scheduler's lifetime:
        ``(num_slots, total_len)`` K/V geometry with PER-SLOT
        ``(num_slots,)`` ``cache_index``/``position`` vectors (the model's
        ``slot_ids`` path), sharded exactly like the fixed-batch cache
        (slots over the data axes, heads over ``tensor``)."""
        cache_rules = self._cache_rules()  # the workload's, by no model's name

        dp = max(1, self.data_parallelism)
        if num_slots < 1 or num_slots % dp:
            raise ValueError(
                f"num_slots {num_slots} must be a positive multiple of the "
                f"data-parallel extent {dp} (slot rows shard over data)")
        cfg = getattr(self.module, "cfg", None)
        if cfg is not None and total_len > cfg.n_positions:
            raise ValueError(
                f"max_total_len {total_len} exceeds n_positions "
                f"{cfg.n_positions}")
        key = ("slots", num_slots, total_len)
        missed = key not in self._cache_init_fns
        if missed:

            def mk():
                vs = self.module.init(
                    jax.random.key(0),
                    jnp.zeros((num_slots, total_len), jnp.int32),
                    decode=True,
                    slot_ids=jnp.arange(num_slots, dtype=jnp.int32))
                return vs["cache"]

            shapes = jax.eval_shape(mk)
            shardings = cache_rules().shardings_for(self.mesh, shapes)
            self._cache_init_fns[key] = jax.jit(
                _named("slot_cache_init", lambda: jax.tree.map(
                    lambda s: jnp.zeros(s.shape, s.dtype), shapes)),
                out_shardings=shardings,
            )
        with self._first_launch("slot_cache_init") if missed else _WARM:
            return self._cache_init_fns[key]()

    def init_paged_cache(self, num_slots: int, total_len: int, *,
                         paged) -> PyTree:
        """ONE resident block-table KV cache (``cache_mode="paged"``):
        per-layer ``(num_blocks, block_size, heads * head_dim)`` K/V pools
        (plus f32 scale tables under ``kv_dtype="int8"``) and the same
        per-slot ``(num_slots,)`` index vectors as the dense slot cache.
        The ``(num_slots, max_blocks_per_slot)`` block table itself is NOT
        part of this tree — the caller owns it host-side and passes it
        into every prefill/decode call.

        ``paged`` is a ``models.PagedKVConfig``; the pool must hold at
        least one maximum-length request plus the reserved trash block.
        """
        dp = max(1, self.data_parallelism)
        if num_slots < 1 or num_slots % dp:
            raise ValueError(
                f"num_slots {num_slots} must be a positive multiple of the "
                f"data-parallel extent {dp} (decode rows shard over data)")
        cfg = getattr(self.module, "cfg", None)
        if cfg is not None and total_len > cfg.n_positions:
            raise ValueError(
                f"max_total_len {total_len} exceeds n_positions "
                f"{cfg.n_positions}")
        if paged.data_shards > 1 and paged.data_shards != dp:
            raise ValueError(
                f"paged.data_shards {paged.data_shards} must equal the "
                f"mesh's data-parallel extent {dp} (each data shard owns "
                f"its own block pool)")
        max_blocks = paged.max_blocks_per_slot(total_len)
        if paged.usable_blocks_per_shard < max_blocks:
            shard_note = (f" per data shard (data_shards "
                          f"{paged.data_shards})"
                          if paged.data_shards > 1 else "")
            raise ValueError(
                f"num_blocks {paged.num_blocks} cannot hold one "
                f"max-length request: need {max_blocks} usable blocks"
                f"{shard_note} "
                f"(block_size {paged.block_size} x max_total_len "
                f"{total_len}) plus the reserved trash block")
        cache_rules = self._cache_rules()  # the workload's, by no model's name

        key = ("paged", num_slots, total_len, paged)
        missed = key not in self._cache_init_fns
        if missed:

            def mk():
                vs = self.module.init(
                    jax.random.key(0),
                    jnp.zeros((num_slots, total_len), jnp.int32),
                    decode=True,
                    slot_ids=jnp.arange(num_slots, dtype=jnp.int32),
                    paged=paged,
                    block_tables=jnp.zeros(
                        (num_slots, paged.table_width(total_len)),
                        jnp.int32))
                return vs["cache"]

            shapes = jax.eval_shape(mk)
            shardings = cache_rules(
                per_shard_pools=paged.data_shards > 1,
            ).shardings_for(self.mesh, shapes)
            self._cache_init_fns[key] = jax.jit(
                _named("paged_cache_init", lambda: jax.tree.map(
                    lambda s: jnp.zeros(s.shape, s.dtype), shapes)),
                out_shardings=shardings,
            )
        with self._first_launch("paged_cache_init") if missed else _WARM:
            return self._cache_init_fns[key]()

    def init_slot_counts(self, num_slots: int) -> jax.Array:
        """Device-resident ``(num_slots, vocab)`` int32 emitted-token
        counts — the per-slot state the presence/frequency penalties read.
        Lives beside the resident KV cache for the scheduler's lifetime,
        donated through every slot launch, and reset per slot by the
        admission prefill (never inherited from a previous occupant).
        Sharded like the batch dim so count rows live with their slots."""
        cfg = getattr(self.module, "cfg", None)
        if cfg is None:
            raise ValueError(
                f"model {self.model!r} has no vocab config — slot sampling "
                f"counts only apply to the decode families")
        with _launch_lock:
            return jax.device_put(
                np.zeros((num_slots, cfg.vocab_size), np.int32),
                batch_sharding(self.mesh))

    @staticmethod
    def _slot_count_of(cache: PyTree) -> int:
        """num_slots of a resident slot/paged cache tree — the trailing
        dim of its per-slot ``cache_index`` vector."""
        leaves = []

        def _grab(path, leaf):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            if name == "cache_index":
                leaves.append(int(leaf.shape[-1]))
            return leaf

        jax.tree_util.tree_map_with_path(_grab, cache)
        if not leaves:
            raise ValueError("cache tree has no cache_index leaf")
        return leaves[0]

    def _uniform_sampling(self, cache: PyTree, temperature: float,
                          top_k: int, rows: Optional[int] = None):
        """Legacy-scalar adapter: the engine-wide (temperature, top_k)
        as a uniform per-row vector dict plus fresh zero counts — what a
        caller that never threads ``sampling``/``counts`` gets.  The
        vector VALUES are runtime data, so every scalar config maps onto
        the same compiled program."""
        n = self._slot_count_of(cache)
        samp = sampling_lib.uniform(rows if rows is not None else n,
                                    temperature, top_k)
        counts = np.zeros((n, int(getattr(self.module, "cfg").vocab_size)),
                          np.int32)
        return samp, counts

    @staticmethod
    def cache_hbm_bytes(cache: PyTree) -> int:
        """GLOBAL resident bytes of a KV cache tree (dense rows or paged
        pools + scales + index vectors) — the serving-capacity denominator
        the block-pool gauges report."""
        return int(sum(
            int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
            for leaf in jax.tree.leaves(cache)))

    @staticmethod
    def cache_hbm_bytes_per_shard(cache: PyTree) -> int:
        """PER-DEVICE resident bytes of a KV cache tree: each leaf counts
        one device's shard (``sharding.shard_shape``), so a pool whose
        block dim is partitioned over the data axes reports
        ``pool_bytes / data`` — the number that answers "how much HBM does
        one chip spend on KV".  Replicated leaves count in full."""
        total = 0
        for leaf in jax.tree.leaves(cache):
            sharding = getattr(leaf, "sharding", None)
            shape = (sharding.shard_shape(leaf.shape)
                     if sharding is not None else leaf.shape)
            total += int(np.prod(shape)) * jnp.dtype(leaf.dtype).itemsize
        return int(total)

    @staticmethod
    def _reset_slot_rows(cache: PyTree, slot_ids, starts) -> PyTree:
        """Set ``cache_index``/``position`` rows for ``slot_ids`` to
        ``starts`` — slot reuse hygiene: a freshly admitted request must
        not inherit the previous occupant's offsets.  ``starts`` is 0 for
        a classic full prefill; prefix caching passes each slot's
        block-aligned first UNCACHED position so the suffix prefill
        writes (and positions) from there, attending over the mapped
        cached blocks below it.  K/V rows need no zeroing (the causal mask
        hides what lies past the reset index); a per-slot recurrent state
        is zeroed by its family at position 0 (``models/solar_open2``)."""
        def _one(path, leaf):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            if name in ("cache_index", "position"):
                return leaf.at[..., slot_ids].set(
                    starts.astype(leaf.dtype))
            return leaf

        return jax.tree_util.tree_map_with_path(_one, cache)

    @staticmethod
    def _paged_kwargs(paged, block_tables, live=None):
        """``live`` is the caller's mask of rows whose step counts: the
        paged attention may skip the others' reads."""
        return ({} if paged is None
                else {"paged": paged, "block_tables": block_tables,
                      "live": live})

    def _prefill_slots_apply(self, paged, params, cache, counts, tokens,
                             slot_ids, block_tables, rng, counter, starts,
                             sampling, commit):
        cache = self._reset_slot_rows(cache, slot_ids, starts)
        # Admission hygiene for the penalty state: a freshly prefilled
        # slot starts from zero counts, never the previous occupant's.
        # Idempotent across prefill chunks — nothing commits until the
        # final chunk, so re-zeroing mid-prefill is a no-op.
        counts = counts.at[slot_ids].set(0)
        logits, mutated = self.module.apply(
            {"params": params, "cache": cache}, tokens,
            decode=True, slot_ids=slot_ids, mutable=["cache"],
            **self._paged_kwargs(paged, block_tables),
        )
        nxt = _select_next(logits[:, -1, :], rng, counter, sampling,
                           counts[slot_ids])
        counts = _bump_counts(counts, slot_ids, nxt, commit)
        return nxt, mutated["cache"], counts

    def prefill_into_slots(self, cache: PyTree, prompts: np.ndarray,
                           slot_ids: np.ndarray, *,
                           temperature: float = 0.0, top_k: int = 0,
                           sampling=None, counts=None, commit=None,
                           rng=None, counter: int = 0,
                           paged=None, block_tables=None, params=None,
                           start_offsets=None):
        """Admit requests: slot-local prefill writing each prompt's K/V
        into its slot's rows of the RESIDENT cache (state rows reset
        first), returning (first generated tokens (n,), updated cache).
        ``prompts`` is (n, T_prompt) shape-uniform; ``slot_ids`` (n,)
        unique free slots.  The cache is donated through the call.

        With ``paged`` (a ``PagedKVConfig``) the cache is the block-pool
        tree from ``init_paged_cache`` and ``block_tables`` the host's
        (num_slots, max_blocks_per_slot) int32 table, whose rows for
        ``slot_ids`` must already cover each prompt's blocks.

        ``start_offsets`` (n,) starts each row's prefill at that logical
        position instead of 0.  Two callers rely on it: prefix caching
        (``prompts`` carries only the UNCACHED suffix; the slot's table
        rows below the offset must already map the cached prefix blocks)
        and CHUNKED prefill (``prompts`` carries the next chunk of the
        same prompt; earlier chunks' K/V already sits below the offset —
        in the slot's dense rows or its allocated blocks — and the
        causal mask attends over it, so dense mode composes too).
        Offsets are a dynamic argument — varying them never recompiles;
        only the chunk/suffix LENGTH is a compile-time shape.

        ``params`` overrides ``self.params`` for this call (hot weight
        reload: the scheduler pins each request to the param generation it
        was admitted with).  Params are the NON-donated first argument of
        the jitted program, so an override with the same avals/shardings
        never recompiles.

        PER-REQUEST SAMPLING: ``sampling`` is an (n,)-row vector dict
        (``serve.sampling.pack``) and ``counts`` the resident
        (num_slots, vocab) emitted-token counts (``init_slot_counts``) —
        both RUNTIME arguments of ONE compiled program per (paged,)
        regardless of the parameter mix.  ``commit`` (n,) bool marks rows
        whose selected token is actually emitted (True for a full or
        FINAL-chunk prefill; False for mid-prefill chunks whose token is
        discarded), gating the count bump.  With ``counts`` the return
        grows to (tokens, cache, counts) and counts is donated alongside
        the cache; without it the engine synthesizes zero counts and
        keeps the legacy (tokens, cache) arity, with the scalar
        ``temperature``/``top_k`` broadcast as a uniform vector — same
        program either way."""
        prompts = np.asarray(prompts, np.int32)
        if prompts.ndim != 2:
            raise ValueError(f"prompts must be (n, T), got {prompts.shape}")
        if (paged is None) != (block_tables is None):
            raise ValueError("paged and block_tables go together")
        n = prompts.shape[0]
        starts = (np.zeros((n,), np.int32)
                  if start_offsets is None
                  else np.asarray(start_offsets, np.int32))
        if starts.shape != (n,):
            raise ValueError(
                f"start_offsets must be ({n},), got {starts.shape}")
        legacy = counts is None
        if legacy:
            sampling, counts = self._uniform_sampling(
                cache, temperature, top_k, rows=n)
        elif sampling is None:
            sampling = sampling_lib.uniform(n, temperature, top_k)
        commit_mask = (np.ones((n,), bool) if commit is None
                       else np.asarray(commit, bool))
        key = ("slot_prefill", paged)
        base = rng if rng is not None else self._sample_rng
        bt = block_tables
        if bt is not None and not isinstance(bt, jax.Array):
            bt = np.asarray(bt, np.int32)
        with _launch_lock:
            missed = key not in self._generate_fns
            if missed:
                self._generate_fns[key] = jax.jit(
                    _named("prefill_slots", self._recording_paths(
                        key, self._prefill_slots_apply), paged),
                    donate_argnums=(1, 2))
            with self._first_launch("slot_prefill") if missed else _WARM:
                nxt, cache, counts = self._generate_fns[key](
                    self.params if params is None else params, cache,
                    counts, prompts, np.asarray(slot_ids, np.int32), bt,
                    base, counter, starts, sampling, commit_mask)
        return (nxt, cache) if legacy else (nxt, cache, counts)

    def _decode_slots_apply(self, paged, params, cache, counts, tokens,
                            active, block_tables, rng, counter, sampling):
        if tokens.ndim == 1:
            # Accept the (num_slots,) device output of a previous step /
            # megastep directly — chaining it costs zero host work.
            tokens = tokens[:, None]
        num_slots = tokens.shape[0]
        slots = jnp.arange(num_slots, dtype=jnp.int32)
        logits, mutated = self.module.apply(
            {"params": params, "cache": cache}, tokens,
            decode=True, slot_ids=slots, mutable=["cache"],
            **self._paged_kwargs(paged, block_tables, active),
        )

        # Active-mask: empty slots are free compute — the step runs over
        # all (num_slots, 1) rows, but inactive slots' index rows must not
        # advance (the garbage K/V an inactive row writes sits beyond its
        # frozen index, where the causal mask never admits it; a per-slot
        # recurrent state is kept by its family, told ``active`` as live).
        def _gate(path, new, old):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            if name in ("cache_index", "position"):
                act = active if new.ndim == 1 else active[None, :]
                return jnp.where(act, new, old)
            return new

        gated = jax.tree_util.tree_map_with_path(
            _gate, mutated["cache"], cache)
        nxt = _select_next(logits[:, -1, :], rng, counter, sampling, counts)
        counts = _bump_counts(counts, slots, nxt, active)
        return nxt, gated, counts

    def decode_slots(self, cache: PyTree, last_tokens: np.ndarray,
                     active: np.ndarray, *, temperature: float = 0.0,
                     top_k: int = 0, sampling=None, counts=None,
                     rng=None, counter: int = 0,
                     paged=None, block_tables=None, params=None):
        """One iteration-level decode step over ALL slots: (num_slots, 1)
        tokens against the resident cache, per-slot offsets, inactive
        slots gated by ``active``.  Returns (next tokens (num_slots,),
        updated cache); the cache is donated through the call.

        Paged mode (``paged`` + ``block_tables``): inactive rows still
        scatter garbage K/V, but their table rows point at trash block 0
        (the scheduler resets them at retirement), so the garbage never
        lands in a block owned by a live request.

        ``params`` overrides ``self.params`` for this call (hot reload:
        rows admitted before a weight swap keep decoding on their own
        generation — same avals/shardings, so no recompile).

        The single-step REFERENCE the tests hold ``decode_megastep`` to:
        the scheduler launches that program for every K, ``steps=1``
        included.  ``last_tokens``/``block_tables``: host or device arrays.

        PER-REQUEST SAMPLING: ``sampling`` is a (num_slots,)-row vector
        dict and ``counts`` the resident (num_slots, vocab) emitted-token
        counts — runtime arguments of the ONE program per (paged,); count
        rows bump at each ACTIVE slot's emitted token.  With ``counts``
        the return grows to (tokens, cache, counts), counts donated;
        without it the scalar config broadcasts uniformly and the legacy
        (tokens, cache) arity holds."""
        if (paged is None) != (block_tables is None):
            raise ValueError("paged and block_tables go together")
        legacy = counts is None
        if legacy:
            sampling, counts = self._uniform_sampling(
                cache, temperature, top_k)
        elif sampling is None:
            sampling = sampling_lib.uniform(
                self._slot_count_of(cache), temperature, top_k)
        key = ("slot_decode", paged)
        base = rng if rng is not None else self._sample_rng
        bt = block_tables
        if bt is not None and not isinstance(bt, jax.Array):
            bt = np.asarray(bt, np.int32)
        with _launch_lock:
            missed = key not in self._generate_fns
            if missed:
                self._generate_fns[key] = jax.jit(
                    _named("decode_slots", self._recording_paths(
                        key, self._decode_slots_apply), paged),
                    donate_argnums=(1, 2))
            tokens_dev = last_tokens
            if not isinstance(tokens_dev, jax.Array):
                tokens_dev = jax.device_put(
                    np.asarray(tokens_dev, np.int32),
                    batch_sharding(self.mesh))
            with self._first_launch("slot_decode") if missed else _WARM:
                nxt, gated, counts = self._generate_fns[key](
                    self.params if params is None else params, cache,
                    counts, tokens_dev, np.asarray(active, bool), bt, base,
                    counter, sampling)
        self._count_decode_launch(key)
        return (nxt, gated) if legacy else (nxt, gated, counts)

    def put_replicated(self, arr) -> jax.Array:
        """Device-put a host array fully replicated over the mesh — the
        scheduler's device-resident block-table cache.  Runs under the
        launch lock (a transfer is a device op; fleet replicas share the
        device set)."""
        from jax.sharding import NamedSharding, PartitionSpec

        with _launch_lock:
            return jax.device_put(
                np.asarray(arr),
                NamedSharding(self.mesh, PartitionSpec()))

    # -- KV tiering: per-block swap to host RAM and back ----------------------

    #: Paged-pool cache leaves the tiering swap path moves per block —
    #: leaf name -> block-axis offset from the END of the shape (pools
    #: are (..., num_blocks, bs, H * hd), scale tables (..., num_blocks,
    #: bs)); counting from the end keeps the slice correct whether or
    #: not the scanned layer stack adds a leading dim.
    _POOL_BLOCK_AXES = {
        "cached_key_pool": 3,
        "cached_value_pool": 3,
        "key_scale": 2,
        "value_scale": 2,
    }

    @classmethod
    def _pool_leaf_paths(cls, cache: PyTree) -> List[Tuple[str, str]]:
        """Deterministic (keystr, leaf name) order of the pool leaves —
        the payload layout contract between gather and scatter."""
        found: List[Tuple[str, str]] = []

        def _grab(path, leaf):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            if name in cls._POOL_BLOCK_AXES:
                found.append((jax.tree_util.keystr(path), name))
            return leaf

        jax.tree_util.tree_map_with_path(_grab, cache)
        found.sort()
        return found

    def _gather_block_apply(self, cache, block):
        """ONE physical block's slice of every pool leaf (K, V, and the
        f32 scale tables under int8) — the per-block swap-out payload."""
        out = []
        slices = {}

        def _grab(path, leaf):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            ax_end = self._POOL_BLOCK_AXES.get(name)
            if ax_end is not None:
                slices[jax.tree_util.keystr(path)] = lax.dynamic_index_in_dim(
                    leaf, block, axis=leaf.ndim - ax_end, keepdims=False)
            return leaf

        jax.tree_util.tree_map_with_path(_grab, cache)
        for keystr in sorted(slices):
            out.append(slices[keystr])
        return out

    def _scatter_block_apply(self, cache, block, payload):
        """Write a gathered block payload back into physical ``block`` of
        every pool leaf — the swap-in restore.  Byte-exact inverse of
        ``_gather_block_apply`` (same leaf order, same dtypes)."""
        order = {k: i for i, (k, _n) in
                 enumerate(self._pool_leaf_paths(cache))}

        def _put(path, leaf):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            ax_end = self._POOL_BLOCK_AXES.get(name)
            if ax_end is None:
                return leaf
            axis = leaf.ndim - ax_end
            update = jnp.expand_dims(
                jnp.asarray(payload[order[jax.tree_util.keystr(path)]],
                            leaf.dtype), axis)
            return lax.dynamic_update_slice_in_dim(leaf, update, block, axis)

        return jax.tree_util.tree_map_with_path(_put, cache)

    def _bind_rows_apply(self, cache, slot_ids, starts):
        return self._reset_slot_rows(cache, slot_ids, starts)

    def _counts_row_apply(self, counts, slot):
        return counts[slot]

    def _counts_bind_apply(self, counts, slot, row):
        return counts.at[slot].set(row)

    def gather_kv_block(self, cache: PyTree, block: int, *, paged) -> list:
        """Fetch ONE physical block of the paged pools to HOST memory:
        a jitted per-leaf slice launch followed by the sanctioned
        ``jax.device_get`` — the KV tiering swap-out unit.  Runs at
        iteration boundaries only (the scheduler calls it after flushing
        any in-flight launch), under the process launch lock like every
        other device op.  Scale tables travel with their blocks, so an
        int8 pool round-trips bit-exactly."""
        key = ("block_gather", paged)
        with _launch_lock:
            missed = key not in self._block_fns
            if missed:
                self._block_fns[key] = jax.jit(
                    _named("block_gather", self._gather_block_apply))
            with self._first_launch("block_gather") if missed else _WARM:
                slices = self._block_fns[key](cache, np.int32(block))
            return jax.device_get(slices)

    def scatter_kv_block(self, cache: PyTree, block: int, payload: list,
                         *, paged) -> PyTree:
        """Write a host payload from ``gather_kv_block`` into physical
        ``block`` — the swap-in restore.  The cache is donated through
        the call; callers rebind (``cache = engine.scatter_kv_block(
        cache, ...)``), exactly the donated-cache chaining discipline."""
        key = ("block_scatter", paged)
        with _launch_lock:
            missed = key not in self._block_fns
            if missed:
                self._block_fns[key] = jax.jit(
                    _named("block_scatter", self._scatter_block_apply),
                    donate_argnums=(0,))
            with self._first_launch("block_scatter") if missed else _WARM:
                return self._block_fns[key](
                    cache, np.int32(block), payload)

    def bind_slot_rows(self, cache: PyTree, slot_ids, starts) -> PyTree:
        """Set ``cache_index``/``position`` rows for ``slot_ids`` to
        ``starts`` as a standalone program — the resume rebind for a
        swapped-in request (its restored blocks already hold positions
        ``< start``; decode continues from there without a prefill).
        The cache is donated; callers rebind."""
        key = ("slot_bind",)
        with _launch_lock:
            missed = key not in self._block_fns
            if missed:
                self._block_fns[key] = jax.jit(
                    _named("slot_bind", self._bind_rows_apply),
                    donate_argnums=(0,))
            with self._first_launch("slot_bind") if missed else _WARM:
                return self._block_fns[key](
                    cache, np.asarray(slot_ids, np.int32),
                    np.asarray(starts, np.int32))

    def gather_counts_row(self, counts: jax.Array, slot: int) -> np.ndarray:
        """One slot's emitted-token count row to host — swapped out with
        the victim's KV so presence/frequency penalties survive a
        preempt/resume round-trip bit-exactly."""
        key = ("counts_gather",)
        with _launch_lock:
            missed = key not in self._block_fns
            if missed:
                self._block_fns[key] = jax.jit(
                    _named("counts_gather", self._counts_row_apply))
            with self._first_launch("counts_gather") if missed else _WARM:
                row = self._block_fns[key](counts, np.int32(slot))
            return np.asarray(jax.device_get(row))

    def scatter_counts_row(self, counts: jax.Array, slot: int,
                           row: np.ndarray) -> jax.Array:
        """Restore a saved count row into ``slot``; counts donated."""
        key = ("counts_bind",)
        with _launch_lock:
            missed = key not in self._block_fns
            if missed:
                self._block_fns[key] = jax.jit(
                    _named("counts_bind", self._counts_bind_apply),
                    donate_argnums=(0,))
            with self._first_launch("counts_bind") if missed else _WARM:
                return self._block_fns[key](
                    counts, np.int32(slot), np.asarray(row, np.int32))

    def _megastep_apply(self, steps, paged, params, cache, counts, tokens,
                        active, horizon, eos_rows, block_tables, rng,
                        counter, sampling, fresh_tokens, fresh, clock):
        """K fused decode iterations as ONE program: a bounded
        ``lax.while_loop`` over the inner step with the whole per-slot
        decode state in the carry, exiting EARLY once every row is dead
        instead of riding out the remaining masked no-op steps.

        Carry: (step index, cache, last token (num_slots,), alive mask,
        remaining horizon, (num_slots, K) token buffer).  A row is alive
        while it is ``active``, has horizon left, and has not emitted its
        eos; a dead row's token stops advancing (``jnp.where`` keeps the
        old one) and its ``cache_index``/``position`` rows are gated
        exactly like the single-step path, so a row finishing at inner
        step j < K is byte-identical to having stopped the loop there.
        Steps past the all-dead exit never execute — their buffer
        columns stay at init, which is safe because the host's
        ``req.done()`` trim walk never reads a column past the step its
        row died at.  Sampling folds ``counter + j`` into the base key
        per EXECUTED inner step — the SAME per-token keys the K=1 loop
        would burn, so sampled output is reproducible across megastep
        sizes too.  The executed-step count rides out as a device
        scalar (``steps_run``) so the scheduler can account the saved
        iterations.

        ASYNC DISPATCH SUPPORT: ``fresh`` (num_slots,) bool marks rows
        whose true last token lives in the HOST vector ``fresh_tokens``
        (a row prefilled while a previous megastep was still in flight,
        so its entry in the device carry is stale); the input token is
        ``where(fresh, fresh_tokens, tokens)`` resolved ON DEVICE.
        ``clock`` is the on-device iteration counter chained
        launch-to-launch; it advances by the EXECUTED inner steps, so
        the host can pin the clock-chaining invariant without a
        synchronous readback between launches.
        """
        num_slots = tokens.shape[0]
        slots = jnp.arange(num_slots, dtype=jnp.int32)
        tok0 = jnp.where(fresh, fresh_tokens, tokens)

        def _body(state):
            j, cache, counts, tok, alive, left, toks = state
            logits, mutated = self.module.apply(
                {"params": params, "cache": cache}, tok[:, None],
                decode=True, slot_ids=slots, mutable=["cache"],
                **self._paged_kwargs(paged, block_tables, alive),
            )

            def _gate(path, new, old):
                name = (path[-1].key if hasattr(path[-1], "key")
                        else str(path[-1]))
                if name in ("cache_index", "position"):
                    act = alive if new.ndim == 1 else alive[None, :]
                    return jnp.where(act, new, old)
                return new

            gated = jax.tree_util.tree_map_with_path(
                _gate, mutated["cache"], cache)
            # Inner step j sees counts updated by steps < j (penalties
            # track within the fused window exactly as the K=1 loop
            # would) and seeded rows advance their per-slot step index.
            samp_j = dict(sampling)
            samp_j["step"] = sampling["step"] + j
            nxt = _select_next(logits[:, -1, :], rng, counter + j,
                               samp_j, counts)
            tok_next = jnp.where(alive, nxt, tok)
            counts = _bump_counts(counts, slots, tok_next, alive)
            hit_eos = (eos_rows >= 0) & (tok_next == eos_rows)
            left_next = jnp.where(alive, left - 1, left)
            alive_next = alive & ~hit_eos & (left_next > 0)
            toks = jax.lax.dynamic_update_slice(
                toks, tok_next[:, None], (jnp.int32(0), j))
            return (j + 1, gated, counts, tok_next, alive_next, left_next,
                    toks)

        def _cond(state):
            j, _, _, _, alive, _, _ = state
            return (j < steps) & jnp.any(alive)

        init = (jnp.int32(0), cache, counts, tok0, active & (horizon > 0),
                horizon, jnp.zeros((num_slots, steps), jnp.int32))
        steps_run, cache, counts, tok_final, _, _, toks = jax.lax.while_loop(
            _cond, _body, init)
        clock_out = clock + steps_run
        return toks, tok_final, steps_run, clock_out, cache, counts

    def decode_megastep(self, cache: PyTree, last_tokens, active: np.ndarray,
                        horizon: np.ndarray, *, steps: int,
                        eos_rows=None, temperature: float = 0.0,
                        top_k: int = 0, sampling=None, counts=None,
                        rng=None, counter: int = 0,
                        paged=None, block_tables=None, params=None,
                        fresh_tokens=None, fresh=None, clock=None):
        """K decode iterations in ONE compiled program (a bounded
        ``lax.while_loop`` over the step).  Returns (tokens
        (num_slots, K), final token (num_slots,), executed inner steps
        (device scalar), updated cache); the cache is donated through
        the call.

        ``horizon`` (num_slots,) int32 is each slot's remaining token
        budget; a row stops advancing once it runs out or emits its eos
        (``eos_rows`` (num_slots,) int32, -1 = no eos for that row), and
        the host trims the tail columns of its output row.  Once EVERY
        row is dead the loop exits early — the executed-step scalar is
        then < K and the untouched tail columns are never read by the
        host trim.  The final token is taken from the GATED carry, so it
        is each row's true last live token — valid to chain into the
        next megastep for every row, including those that died
        mid-loop.

        Paged mode requires the caller to have precomputed block-table
        coverage for all K positions up front (reservation-at-admit
        guarantees the blocks exist); dead and inactive rows keep
        scattering into positions past their frozen index or into the
        trash block, never into a live request's K/V.

        ``steps=1`` compiles a one-iteration loop — same math as
        ``decode_slots``, the single-step reference — and is what the
        scheduler launches at K=1.

        PER-REQUEST SAMPLING: ``sampling``/``counts`` as in
        ``decode_slots`` — ONE program per (steps, paged).  Inside the
        fused window, inner step j selects with ``counter + j`` AND
        counts updated by the earlier inner steps, and seeded rows fold
        ``step + j`` into their private key — so penalties and seeded
        streams are reproducible across megastep sizes.  With ``counts``
        the return grows to (tokens, final token, steps_run, clock_out,
        cache, counts); without it the legacy 4-tuple holds.

        ASYNC DISPATCH: ``fresh``/``fresh_tokens`` resolve rows whose
        device-carried token went stale while a launch was in flight
        (the input token becomes ``where(fresh, fresh_tokens,
        last_tokens)`` on device), and ``clock`` chains the on-device
        iteration counter — pass the previous launch's ``clock_out``
        handle to keep the chain pure device-side.  All three default
        to no-ops (no fresh rows, clock 0).

        EXPERT COUNTS: where the cache carries a ``moe_counts`` leaf (a
        family whose expert layers count the router's choices on the
        device), the per-request return grows by one: what THIS launch
        added to that leaf, ``(expert layers, experts held + 3)`` int32,
        an output of the same program (nothing is launched or fetched
        for it beyond what the tokens' fetch brings)."""
        if (paged is None) != (block_tables is None):
            raise ValueError("paged and block_tables go together")
        steps = int(steps)
        if steps < 1:
            raise ValueError(f"megastep steps must be >= 1, got {steps}")
        legacy = counts is None
        if legacy:
            sampling, counts = self._uniform_sampling(
                cache, temperature, top_k)
        elif sampling is None:
            sampling = sampling_lib.uniform(
                self._slot_count_of(cache), temperature, top_k)
        key = ("slot_megastep", steps, paged)
        base = rng if rng is not None else self._sample_rng
        bt = block_tables
        if bt is not None and not isinstance(bt, jax.Array):
            bt = np.asarray(bt, np.int32)
        n = len(active)
        eos = (np.full((n,), -1, np.int32) if eos_rows is None
               else np.asarray(eos_rows, np.int32))
        if fresh_tokens is None:
            fresh_tokens = np.zeros((n,), np.int32)
        elif not isinstance(fresh_tokens, jax.Array):
            fresh_tokens = np.asarray(fresh_tokens, np.int32).reshape(-1)
        fresh = (np.zeros((n,), bool) if fresh is None
                 else np.asarray(fresh, bool))
        if clock is None:
            clock = np.int32(0)
        t0 = time.perf_counter()
        with _launch_lock:
            missed = key not in self._generate_fns
            if missed:
                counting = moe_counts_of(cache) is not None
                self._generate_fns[key] = jax.jit(
                    _named("decode_megastep", self._recording_paths(
                        key, self._megastep_counting_apply if counting
                        else self._megastep_apply), steps, paged),
                    donate_argnums=(1, 2))
            tokens_dev = last_tokens
            if not isinstance(tokens_dev, jax.Array):
                tokens_dev = jax.device_put(
                    np.asarray(tokens_dev, np.int32).reshape(-1),
                    batch_sharding(self.mesh))
            with self._first_launch("slot_megastep") if missed else _WARM:
                out = self._generate_fns[key](
                    self.params if params is None else params, cache,
                    counts, tokens_dev, np.asarray(active, bool),
                    np.asarray(horizon, np.int32), eos, bt, base, counter,
                    sampling, fresh_tokens, fresh, clock)
        toks, tok_final, steps_run, clock_out, cache, counts = out[:6]
        self._count_decode_launch(key)
        self._obs["megastep"].observe(time.perf_counter() - t0)
        if legacy:
            return toks, tok_final, steps_run, cache
        return (toks, tok_final, steps_run, clock_out, cache, counts) + out[6:]

    def _verify_slots_apply(self, k, paged, params, cache, counts, tokens,
                            active, draft_lens, block_tables, rng, counter,
                            sampling):
        """Speculative verify as ONE program: a (num_slots, k+1) forward
        whose input row is [last token, draft_0 .. draft_{k-1}].

        Position j's logits predict the token AFTER input column j, so
        the per-position target token is selected with the SAME
        ``fold_in`` counter (``counter + j``) the sequential loop would
        burn for that token — which is what makes the emitted stream
        identical to sequential decoding: greedy targets are the exact
        greedy tokens (bit-parity), and sampled targets are the exact
        samples the per-token launches would have drawn, draft agreement
        only deciding how MANY of them this launch gets to keep (the
        point-mass-draft reduction of speculative rejection sampling, so
        sampled output stays distribution-exact).

        A draft token is accepted while every earlier draft matched its
        target (``cumprod`` of the per-position agreement, masked past
        each row's real ``draft_lens``); the emitted row is its accepted
        prefix plus one bonus/correction target.  ``cache_index`` /
        ``position`` advance by accepted+1 per ACTIVE row — computed
        from the pre-apply values, rolling back the k+1-token advance
        the forward performed; the rejected drafts' K/V stays behind the
        rolled-back index where the causal mask (dense) or the slot's
        own blocks (paged) never expose it."""
        num_slots = tokens.shape[0]
        slots = jnp.arange(num_slots, dtype=jnp.int32)
        logits, mutated = self.module.apply(
            {"params": params, "cache": cache}, tokens,
            decode=True, slot_ids=slots, mutable=["cache"],
            **self._paged_kwargs(paged, block_tables),
        )
        # Position j's target must see the counts the sequential loop
        # would have at that token — i.e. with targets 0..j-1 already
        # committed — so the selection walks a PROVISIONAL counts chain.
        # Only accepted+bonus targets actually commit (below, from the
        # ORIGINAL counts), so rejected positions leave no residue.
        target_list = []
        provisional = counts
        for j in range(k + 1):
            samp_j = dict(sampling)
            samp_j["step"] = sampling["step"] + j
            t = _select_next(logits[:, j, :], rng, counter + j,
                             samp_j, provisional)
            provisional = _bump_counts(provisional, slots, t, active)
            target_list.append(t)
        targets = jnp.stack(target_list, axis=1)
        drafts = tokens[:, 1:]
        pos = jnp.arange(k, dtype=jnp.int32)[None, :]
        match = (drafts == targets[:, :k]) & (pos < draft_lens[:, None])
        accepted = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
        accepted = jnp.where(active, accepted, 0)
        advance = jnp.where(active, accepted + 1, 0)
        new_counts = counts
        for j in range(k + 1):
            new_counts = _bump_counts(new_counts, slots, targets[:, j],
                                      active & (j < advance))

        def _gate(path, new, old):
            name = (path[-1].key if hasattr(path[-1], "key")
                    else str(path[-1]))
            if name in ("cache_index", "position"):
                adv = advance.astype(old.dtype)
                return old + (adv if new.ndim == 1 else adv[None, :])
            return new

        gated = jax.tree_util.tree_map_with_path(
            _gate, mutated["cache"], cache)
        return targets, accepted, gated, new_counts

    def _verify_chain_apply(self, k, paged, params, cache, counts, tokens,
                            active, draft_lens, block_tables, rng, counter,
                            sampling, carry, fresh_tokens, fresh, clock):
        """Speculative verify with a DEVICE-RESIDENT column 0 (async
        decode): the host drafted from its stale fetched view, so the
        scored context must NOT trust the host's idea of the last
        token.  Column 0 is replaced on device by ``carry`` — the true
        last token after every launch still in flight — merged with the
        host's ``fresh_tokens`` for rows whose prefill finished while a
        launch was in flight (the same fresh-row mask as the megastep).
        The emitted targets are therefore exactly the sequential tokens
        no matter how stale the drafting view was: staleness can only
        shrink the accepted prefix, never corrupt a token.

        The returned carry holds each ACTIVE row's last kept target
        (``targets[i, accepted[i]]``); inactive rows keep their old
        carry entry, so the carry stays a valid whole-batch input for
        the next chained launch.  ``clock`` advances by one (a verify
        launch is one scheduler iteration), keeping the device clock
        chain pure device-side like the megastep's."""
        col0 = jnp.where(fresh, fresh_tokens, carry)
        tokens = jnp.concatenate([col0[:, None], tokens[:, 1:]], axis=1)
        targets, accepted, gated, new_counts = self._verify_slots_apply(
            k, paged, params, cache, counts, tokens, active, draft_lens,
            block_tables, rng, counter, sampling)
        idx = jnp.clip(accepted, 0, k)
        last_kept = jnp.take_along_axis(targets, idx[:, None], axis=1)[:, 0]
        carry_out = jnp.where(active, last_kept, col0)
        clock_out = clock + 1
        return targets, accepted, carry_out, clock_out, gated, new_counts

    def verify_slots(self, cache: PyTree, tokens: np.ndarray,
                     active: np.ndarray, draft_lens: np.ndarray, *,
                     temperature: float = 0.0, top_k: int = 0,
                     sampling=None, counts=None,
                     rng=None, counter: int = 0,
                     paged=None, block_tables=None, params=None,
                     chain: bool = False, carry=None,
                     fresh_tokens=None, fresh=None, clock=None):
        """One speculative-decoding verify step over ALL slots.

        ``tokens`` is (num_slots, k+1) int32: column 0 is each slot's
        last emitted token, columns 1..k its draft tokens padded past
        ``draft_lens`` (pad values never accepted — the per-slot length
        mask bounds the agreement prefix).  Returns (targets
        (num_slots, k+1), accepted draft count (num_slots,), updated
        cache); row i's emitted tokens are ``targets[i, :accepted[i]+1]``
        — at least one token per active row, so a launch never stalls a
        stream.  The cache is donated through the call.

        The program is cached per (k, paged) and launched under the
        process launch lock like every other slot program; ``params``
        overrides for hot reload without recompiles.  Paged mode needs
        block coverage for all k+1 written positions up front
        (``PagedKVConfig.blocks_for_spec``) — rejected drafts' writes
        land in the slot's own blocks behind its rolled-back index,
        inactive rows' in the trash block.

        PER-REQUEST SAMPLING: ``sampling``/``counts`` as in
        ``decode_slots`` — position j's target draws with each slot's
        OWN params at ``counter + j`` (seeded rows: ``step + j``),
        penalties seeing targets 0..j-1 provisionally committed; only
        the accepted prefix + bonus token commits to the returned
        counts.  With ``counts`` the return grows to (targets, accepted,
        cache, counts); without it the legacy 3-tuple holds.

        CHAIN MODE (``chain=True``, async decode): column 0 of
        ``tokens`` is IGNORED and replaced on device by ``carry`` — the
        device-resident last-token vector chained launch to launch —
        merged with ``fresh_tokens`` at ``fresh`` rows (prefills that
        landed while a launch was in flight), exactly the megastep's
        async-dispatch contract.  ``clock`` chains the device iteration
        counter.  The return grows to (targets, accepted, carry_out,
        clock_out, cache, counts); requires per-request ``counts``."""
        if (paged is None) != (block_tables is None):
            raise ValueError("paged and block_tables go together")
        tokens = np.asarray(tokens, np.int32)
        if tokens.ndim != 2 or tokens.shape[1] < 2:
            raise ValueError(
                f"verify tokens must be (num_slots, k+1) with k >= 1, "
                f"got {tokens.shape} — a k=0 verify is just the plain "
                f"decode step; route it there instead")
        k = tokens.shape[1] - 1
        legacy = counts is None
        if chain and legacy:
            raise ValueError(
                "chain verify needs the per-request sampling state "
                "(counts) — the async scheduler always carries it")
        if chain and carry is None:
            raise ValueError(
                "chain verify needs the device token carry for column 0")
        if legacy:
            sampling, counts = self._uniform_sampling(
                cache, temperature, top_k)
        elif sampling is None:
            sampling = sampling_lib.uniform(
                self._slot_count_of(cache), temperature, top_k)
        key = (("slot_verify_chain" if chain else "slot_verify"), k, paged)
        base = rng if rng is not None else self._sample_rng
        bt = block_tables
        if bt is not None and not isinstance(bt, jax.Array):
            bt = np.asarray(bt, np.int32)
        t0 = time.perf_counter()
        with _launch_lock:
            missed = key not in self._generate_fns
            if missed:
                fn = (self._verify_chain_apply if chain
                      else self._verify_slots_apply)
                self._generate_fns[key] = jax.jit(
                    _named(key[0], self._recording_paths(key, fn), k,
                           paged),
                    donate_argnums=(1, 2))
            with self._first_launch(key[0]) if missed else _WARM:
                tokens_dev = jax.device_put(tokens, batch_sharding(self.mesh))
                if chain:
                    n = tokens.shape[0]
                    carry_dev = carry
                    if not isinstance(carry_dev, jax.Array):
                        carry_dev = jax.device_put(
                            np.asarray(carry_dev, np.int32).reshape(-1),
                            batch_sharding(self.mesh))
                    if fresh_tokens is None:
                        fresh_tokens = np.zeros((n,), np.int32)
                    elif not isinstance(fresh_tokens, jax.Array):
                        fresh_tokens = np.asarray(
                            fresh_tokens, np.int32).reshape(-1)
                    fresh = (np.zeros((n,), bool) if fresh is None
                             else np.asarray(fresh, bool))
                    if clock is None:
                        clock = np.int32(0)
                    (targets, accepted, carry_out, clock_out, gated,
                     counts) = self._generate_fns[key](
                        self.params if params is None else params, cache,
                        counts, tokens_dev, np.asarray(active, bool),
                        np.asarray(draft_lens, np.int32), bt, base, counter,
                        sampling, carry_dev, fresh_tokens, fresh, clock)
                else:
                    targets, accepted, gated, counts = self._generate_fns[key](
                        self.params if params is None else params, cache, counts,
                        tokens_dev, np.asarray(active, bool),
                        np.asarray(draft_lens, np.int32), bt, base, counter,
                        sampling)
        self._obs["verify"].observe(time.perf_counter() - t0)
        if chain:
            return targets, accepted, carry_out, clock_out, gated, counts
        if legacy:
            return targets, accepted, gated
        return targets, accepted, gated, counts

    def generate(self, prompts: np.ndarray, max_new_tokens: int, *,
                 eos_token: Optional[int] = None, eos_check_every: int = 8,
                 temperature: float = 0.0, top_k: int = 0,
                 rng=None) -> np.ndarray:
        """Decode: (B, T_prompt) int32 -> (B, n <= max_new_tokens) int32.

        One prefill call over the whole prompt fills the cache and yields
        the first new token; each further token is a (B, 1) decode step
        against the cache — never a full-sequence forward.  The (B,
        T_prompt) prefill and (B, 1) decode programs compile once per
        shape; the cache is donated through the step so decode updates it
        in place.

        Defaults are greedy argmax for the full horizon — bit-identical to
        the pre-sampling path.  ``temperature > 0`` (optionally with
        ``top_k``) samples via the in-step RNG pattern (one base key, step
        counter folded in on device).  ``eos_token`` enables early exit:
        once every row has emitted it, decoding stops at the next host
        check — checked every ``eos_check_every`` steps so the dispatch
        loop is not synced per token.  Rows that finished earlier still
        carry (ignorable) tokens after their eos.
        """
        prompts = np.asarray(prompts, np.int32)
        if prompts.ndim != 2:
            raise ValueError(f"prompts must be (B, T), got {prompts.shape}")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        B, T = prompts.shape
        cfg = getattr(self.module, "cfg", None)
        total = T + max_new_tokens
        if cfg is not None and total > cfg.n_positions:
            raise ValueError(
                f"prompt {T} + max_new_tokens {max_new_tokens} exceeds "
                f"n_positions {cfg.n_positions}")
        greedy = temperature <= 0.0
        step = self._decode_step_fn(temperature, top_k)
        base = rng if rng is not None else self._sample_rng
        cache = self.init_cache(B, total)
        tokens_dev = jax.device_put(prompts, batch_sharding(self.mesh))
        with _launch_lock:
            if greedy:
                tok, cache = step(self.params, cache, tokens_dev)
            else:
                tok, cache = step(self.params, cache, tokens_dev, base, 0)
        out = [tok]
        done = (tok == eos_token) if eos_token is not None else None
        check_every = max(1, eos_check_every)
        for i in range(1, max_new_tokens):
            if (done is not None and i % check_every == 0
                    and bool(jax.device_get(done).all())):
                break
            with _launch_lock:
                if greedy:
                    tok, cache = step(self.params, cache, tok[:, None])
                else:
                    tok, cache = step(
                        self.params, cache, tok[:, None], base, i)
            out.append(tok)
            if done is not None:
                done = done | (tok == eos_token)
        return np.asarray(jax.device_get(jnp.stack(out, axis=1)))

    def generate_batch(self, prompts: List[np.ndarray],
                       max_new_tokens: int, **gen_kwargs) -> List[np.ndarray]:
        """Batcher adapter: list of same-length 1-D prompts -> list of
        generated 1-D token arrays.  Groups by prompt length defensively
        (the batcher's bucket_fn normally guarantees uniformity) and pads
        the batch dim to the engine's bucketed shapes.  ``gen_kwargs``
        forward to ``generate`` (eos/sampling); with ``eos_token`` each
        row is trimmed just past its own first eos."""
        eos_token = gen_kwargs.get("eos_token")
        by_len: Dict[int, List[int]] = {}
        for i, p in enumerate(prompts):
            by_len.setdefault(len(p), []).append(i)
        results: List[Optional[np.ndarray]] = [None] * len(prompts)
        for _, idxs in by_len.items():
            stacked = np.stack([prompts[i] for i in idxs]).astype(np.int32)
            padded = pad_rows(stacked, self.bucket_rows(len(idxs)))
            gen = self.generate(padded, max_new_tokens, **gen_kwargs)
            for row, i in enumerate(idxs):
                results[i] = _trim_at_eos(gen[row], eos_token)
        return results  # type: ignore[return-value]

    # -- classify (mnist / resnet50 / bert) ----------------------------------

    def _predict_apply(self, params, model_state, batch):
        variables = {"params": params, **model_state}
        if self.model == "resnet50":
            return self.module.apply(variables, batch["image"], train=False)
        if self.model == "mnist":
            return self.module.apply(variables, batch["image"])
        if self.model == "bert":
            # Sentence-level head: the NSP logits are the classify surface.
            _mlm, nsp = self.module.apply(
                variables, batch, deterministic=True)
            return nsp
        raise NotImplementedError(
            f"no serve predict path for model {self.model!r}")

    def classify(self, batch: Dict[str, np.ndarray]) -> np.ndarray:
        """Batched deterministic forward -> host logits array."""
        sh = batch_sharding(self.mesh)
        dev_batch = {k: jax.device_put(np.asarray(v), sh)
                     for k, v in batch.items()}
        with _launch_lock:
            logits = self._predict_fn(self.params, self.model_state,
                                      dev_batch)
        return np.asarray(jax.device_get(logits))

    def classify_batch(self, examples: List[Dict[str, np.ndarray]]
                       ) -> List[int]:
        """Batcher adapter: list of single examples -> list of class ids."""
        keys = examples[0].keys()
        stacked = {k: np.stack([np.asarray(e[k]) for e in examples])
                   for k in keys}
        target = self.bucket_rows(len(examples))
        padded = {k: pad_rows(v, target) for k, v in stacked.items()}
        logits = self.classify(padded)
        return [int(np.argmax(logits[i], axis=-1))
                for i in range(len(examples))]

    # -- the served weights (placing, hot reload) ------------------------------

    def _in_served_types(self, params: PyTree) -> PyTree:
        """``params`` with each leaf in the type the programs read it in
        (class docstring, "The served weights").  A leaf is rounded where
        it lies: a host array on the host, a device array on the device,
        a traced one in the program being traced."""
        typed = self.workload.served_dtypes
        if typed is None:
            return params
        return jax.tree.map(
            lambda leaf, dtype: (
                leaf if leaf.dtype == dtype else leaf.astype(dtype)),
            params, typed(params))

    def _note_cast(self, before: PyTree, after: PyTree, start: float,
                   always: bool = False) -> None:
        """Puts a cast on record as ``dtt/startup/params_cast`` (args
        ``leaves_cast``, ``bytes_before``, ``bytes_after``; a child of the
        span open on this thread): when a leaf was cast, and from the
        constructor (``always``) when none was, which is the reading that
        says a family bypasses the mechanism."""
        pairs = list(zip(jax.tree.leaves(before), jax.tree.leaves(after)))
        cast = sum(b.dtype != a.dtype for b, a in pairs)
        if cast or always:
            nbytes = lambda leaf: leaf.size * leaf.dtype.itemsize
            self._tracer.add_span(
                "params_cast", start=start, end=now(), cat="startup",
                args={"leaves_cast": cast,
                      "bytes_before": sum(nbytes(b) for b, _ in pairs),
                      "bytes_after": sum(nbytes(a) for _, a in pairs)})

    def _served(self, params: PyTree, always: bool = False) -> PyTree:
        """A host or device tree in the served types, the cast on record."""
        start = now()
        served = self._in_served_types(params)
        self._note_cast(params, served, start, always)
        return served

    def _note_params_bytes(self) -> None:
        held: Dict[str, int] = {}
        for leaf in jax.tree.leaves(self.params):
            held[leaf.dtype.name] = held.get(leaf.dtype.name, 0) + leaf.nbytes
        gauge = self._obs["params_bytes"]
        for (name,), child in gauge.samples():
            child.set(float(held.pop(name, 0)))
        for name, nbytes in held.items():
            gauge.labels(dtype=name).set(float(nbytes))

    def params_bytes(self) -> Dict[str, float]:
        """Bytes of the served tree by leaf type (``compile_stats``'s
        sibling: it reads the process-wide gauge
        ``dtt_serve_params_bytes{dtype}`` and takes no lock)."""
        return {name: child.value
                for (name,), child in self._obs["params_bytes"].samples()}

    def shard_params(self, params: PyTree) -> PyTree:
        """Device-put a HOST params tree (a checkpoint's float32 or the
        served types) through the workload's sharding rules — the fleet
        checkpoint watcher's reload path.  The result has the same
        avals/shardings as ``self.params``, so passing it as the
        ``params=`` override of the slot programs never recompiles."""
        params = self._served(params)   # rounded on the host: no launch
        shardings = self.workload.rules.shardings_for(
            self.mesh, {"params": params})
        with _launch_lock:
            return apply_shardings({"params": params}, shardings)["params"]

    def install_params(self, params: PyTree) -> None:
        """Swap the live weights (hot reload): a tree in the served types
        (``shard_params``'s, a draw made over ``self.params``'s avals) or in
        the checkpoint's, which is cast here and not kept.  The cast and
        the assignment run under the launch lock, so every launch path
        that reads ``self.params`` inside the lock sees either the old or
        the new tree — never a swap interleaved with a dispatch."""
        with _launch_lock:
            self.params = self._served(params)
            self._note_params_bytes()

    # -- lifecycle -----------------------------------------------------------

    def close(self) -> None:
        """Release the checkpoint manager (waits out async orbax I/O)."""
        if self._manager is not None:
            self._manager.close()
            self._manager = None

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False

    # -- what a decoder family tells the engine (no model by name) ------------

    def _cache_rules(self):
        """The workload's sharding rules for the "cache" collection."""
        rules = self.workload.cache_rules
        if rules is None:
            raise ValueError(
                f"model {self.model!r} declares no decode cache "
                f"(Workload.cache_rules): only decoder families serve "
                f"through generate and the slot programs")
        return rules

    def cache_geometry(self, paged) -> Dict[str, Any]:
        """What a token costs in the paged pool this family's cache is, as
        the workload reckons it: values and bytes a token and layer, the
        pool's width and its padding, the pool's bytes under ``paged``."""
        geometry = self.workload.cache_geometry
        if geometry is None:
            raise ValueError(
                f"model {self.model!r} declares no cache geometry "
                f"(Workload.cache_geometry)")
        return geometry(paged)

    def _megastep_counting_apply(self, steps, paged, params, cache, *rest):
        """``_megastep_apply`` and, as one more output, what its steps
        added to the cache's ``moe_counts``."""
        before = moe_counts_of(cache)
        out = self._megastep_apply(steps, paged, params, cache, *rest)
        return out + (moe_counts_of(out[4]) - before,)

    def expert_forms_record(self) -> Dict[str, Tuple[str, int]]:
        """The engine's record of the form each traced program's expert
        layers took (``expert_forms``), itself: a scheduler asks for it
        where it is built and hands it to ``ops.grouped_matmul.
        record_forms`` round its launches, the way ``_recording_paths``
        keeps the attention's path.  The choice is made while ``jax.jit``
        traces, from the call's static shape, so this record is the only
        place it shows; it is the engine's because the programs are.
        (Made at the first ask and kept down here: the compile-cache keys
        of the programs with a kernel carry the line numbers of this file
        above ``_megastep_apply``, ``__init__`` among them.)"""
        return self.__dict__.setdefault("_expert_forms", {})

    def expert_forms(self) -> Dict[str, Tuple[str, int]]:
        """Per traced program, ``"<kind>/<tokens a call of its layers
        sees>"``, the form its expert layers took (``grouped``: each
        assignment once over rows grouped by expert; ``dense``: every held
        expert over every token) and the grouped buffer's static rows.
        Empty for a model without expert layers."""
        return dict(self.__dict__.get("_expert_forms", {}))


def moe_counts_of(cache: PyTree):
    """The cache tree's ``moe_counts`` leaf (expert layers that count the
    router's choices on the device carry one), or None."""
    found = [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(cache)
             if getattr(path[-1], "key", None) == "moe_counts"]
    return found[0] if found else None


# Attention paths a traced program may have on record: the paged kernel's
# two (``ops.paged_attention``), the latent attention's two and the
# grouped-query family's four (a program of its has a window and a full
# path, both by the gather or both by the kernel), the learned sparse
# attention's two (a decode step over the selected rows, a chunk under the
# selection's mask), the linear attention's two (the chunk-wise rule, the
# one-step rule), the window latent attention's two (a decode step over the
# ring's live cells, a chunk over itself and the window before it).
_DECODE_PATHS = (paged_attention.KERNEL, paged_attention.GATHER,
                 "latent_absorbed", "latent_expanded",
                 "gqa_gather_window", "gqa_gather_full",
                 ) + paged_attention.GQA_KERNEL_PATHS + (
                 "latent_sparse_selected", "latent_sparse_masked",
                 "kda_chunk", "kda_step",
                 "latent_window_step", "latent_window_chunk")
