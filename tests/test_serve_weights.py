"""The served weights: ``ServeEngine`` holds a leaf that every served
program reads only through a cast to the compute type IN that type, the
cast made once where weights enter the engine (the fresh draw or the
restore, ``shard_params``, ``install_params``).  Rounding once must give
the bits that rounding in every launch gave, the tree must keep the
checkpoint's paths and shapes, a reload must never recompile, and a family
that names no leaf (or holds them in the compute type already, or computes
in float32) must get its tree back as it was."""

import dataclasses
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu import train_lib
from distributed_tensorflow_tpu.models import get_workload
from distributed_tensorflow_tpu.models.bert import BertConfig
from distributed_tensorflow_tpu.models.gpt2 import GPT2Config, PagedKVConfig
from distributed_tensorflow_tpu.obs.trace import default_tracer
from distributed_tensorflow_tpu.parallel.sharding import apply_shardings
from distributed_tensorflow_tpu.serve import ServeEngine

# Widths no other test uses, so that "no float32 array of a named leaf's
# shape is live" cannot be confused by another module's arrays.
ODD = GPT2Config(vocab_size=272, n_positions=136, d_model=72, n_layer=2,
                 n_head=4, dropout=0.0)
NAMED = ("c_attn", "c_proj", "mlp_c_fc", "mlp_c_proj", "wte", "wpe")
SLOTS = (0, 2, 5)


def _names(path):
    return [getattr(k, "key", str(k)) for k in path]


def _is_named(path):
    return any(n in NAMED for n in _names(path))


def _f32_draw(engine, seed):
    """A checkpoint's tree: every leaf float32, on the host."""
    wl = engine.workload
    variables = engine.module.init(
        jax.random.key(seed),
        wl.init_batch if wl.init_key is None else wl.init_batch[wl.init_key])
    return jax.device_get(variables["params"])


def _last_cast():
    return default_tracer().spans(name="dtt/startup/params_cast")[-1][4]


def _avals(tree):
    return jax.tree.map(lambda x: (x.shape, jnp.dtype(x.dtype).name,
                                   getattr(x, "sharding", None)), tree)


@pytest.fixture(scope="module")
def engine(mesh_dp):
    with ServeEngine("gpt2", mesh=mesh_dp, preset="tiny") as eng:
        yield eng


@pytest.mark.parametrize("steps", [1, 4])
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("cache_mode", ["slots", "paged"])
def test_rounding_once_gives_the_bits_of_rounding_every_launch(
        engine, cache_mode, sampled, steps):
    """Float32 weights installed through ``install_params`` against the
    same calls with ``params=`` the float32 tree (every launch casting,
    as before): tokens, the K/V the calls wrote and the next position's
    logits, bit for bit."""
    eng = engine
    host = _f32_draw(eng, seed=7)
    f32 = apply_shardings(host, jax.tree.map(lambda x: x.sharding,
                                             eng.params))
    assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(f32))
    eng.install_params(f32)
    vocab = eng.module.cfg.vocab_size
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, vocab, size=(n,), dtype=np.int32)
               for n in (4, 7, 5)]
    sampling = dict(temperature=0.8, top_k=7) if sampled else {}
    key = jax.random.key(3)
    paged_kw = {}
    if cache_mode == "paged":
        tables = np.zeros((8, 4), np.int32)
        for slot in SLOTS:      # block 0 is the trash block
            tables[slot] = 1 + 4 * slot + np.arange(4)
        paged_kw = dict(paged=PagedKVConfig(block_size=4, num_blocks=33),
                        block_tables=tables)
    active = np.zeros((8,), bool)
    active[list(SLOTS)] = True

    def run(params):
        cache = (eng.init_paged_cache(8, 16, paged=paged_kw["paged"])
                 if paged_kw else eng.init_slot_cache(8, 16))
        last = np.zeros((8,), np.int32)
        for i, (slot, prompt) in enumerate(zip(SLOTS, prompts)):
            tok, cache = eng.prefill_into_slots(
                cache, prompt[None, :], [slot], rng=key, counter=i,
                params=params, **sampling, **paged_kw)
            last[slot] = int(np.asarray(jax.device_get(tok))[0])
        toks, final, steps_run, cache = eng.decode_megastep(
            cache, last, active, np.where(active, 8, 0).astype(np.int32),
            steps=steps, rng=key, counter=10, params=params, **sampling,
            **paged_kw)
        final = jax.device_get(final)
        logits, _ = jax.jit(lambda p, c, t: eng.module.apply(
            {"params": p, "cache": c}, t[:, None], decode=True,
            slot_ids=jnp.arange(8, dtype=jnp.int32), mutable=["cache"],
            **eng._paged_kwargs(paged_kw.get("paged"),
                                paged_kw.get("block_tables"), active)))(
            eng.params if params is None else params, cache, final)
        return jax.device_get((last, toks, final, cache, logits))

    served, parent = run(None), run(f32)
    assert served[4].dtype == np.float32 and np.isfinite(
        served[4][active]).all()
    for got, want in zip(jax.tree.leaves(served), jax.tree.leaves(parent)):
        np.testing.assert_array_equal(got, want)


def test_the_tree_keeps_paths_and_shapes_and_no_float32_twin_stays(mesh_dp):
    with ServeEngine("gpt2", mesh=mesh_dp, config=ODD) as eng:
        host = _f32_draw(eng, seed=3)
        gc.collect()
        f32 = jax.device_put(host)
        eng.install_params(f32)
        cast = _last_cast()
        del f32
        gc.collect()
        assert jax.tree.structure(eng.params) == jax.tree.structure(host)
        named_shapes, other_shapes = [], set()
        for (path, leaf), want in zip(
                jax.tree_util.tree_leaves_with_path(eng.params),
                jax.tree.leaves(host)):
            assert leaf.shape == want.shape, _names(path)
            if _is_named(path):
                assert leaf.dtype == jnp.bfloat16, _names(path)
                named_shapes.append(leaf.shape)
                np.testing.assert_array_equal(
                    np.asarray(leaf), want.astype(jnp.bfloat16))
            else:
                assert _names(path)[-2] in ("ln_1", "ln_2", "ln_f")
                assert leaf.dtype == jnp.float32, _names(path)
                other_shapes.add(leaf.shape)
                np.testing.assert_array_equal(np.asarray(leaf), want)
        assert len(named_shapes) == 10
        # (A projection's bias has a layer norm's shape: those stay out.)
        twins = [a.shape for a in jax.live_arrays()
                 if a.dtype == jnp.float32
                 and a.shape in set(named_shapes) - other_shapes]
        assert not twins
        n_named = sum(int(np.prod(s)) for s in (
            x.shape for p, x in jax.tree_util.tree_leaves_with_path(host)
            if _is_named(p)))
        assert cast["leaves_cast"] == 10
        assert cast["bytes_before"] - cast["bytes_after"] == 2 * n_named
        held = eng.params_bytes()
        assert held["bfloat16"] == 2 * n_named
        assert held["float32"] == cast["bytes_after"] - 2 * n_named


def test_a_reload_gets_the_served_avals_and_never_recompiles(engine):
    eng = engine
    active = np.ones((8,), bool)
    horizon = np.full((8,), 4, np.int32)

    def launch():
        cache = eng.init_slot_cache(8, 16)
        return eng.decode_megastep(cache, np.zeros((8,), np.int32), active,
                                   horizon, steps=2)[0]

    launch()
    program = eng._generate_fns[("slot_megastep", 2, None)]
    before = (eng.compile_stats(), program._cache_size())
    spans = len(default_tracer().spans(name="dtt/startup/params_cast"))
    sharded = eng.shard_params(_f32_draw(eng, seed=11))
    assert _avals(sharded) == _avals(eng.params)
    assert _last_cast()["leaves_cast"] == 10
    eng.install_params(sharded)     # served types already: no cast, no span
    assert len(default_tracer().spans(
        name="dtt/startup/params_cast")) == spans + 1
    launch()
    assert (eng.compile_stats(), program._cache_size()) == before


def _tiny(model):
    if model == "bert":
        return dict(config=BertConfig.tiny(), seq_len=32)
    if model == "gpt2":
        return dict(config=GPT2Config.tiny(dtype=jnp.float32))
    return dict(preset="tiny")


@pytest.mark.parametrize("model", ["bert", "mellum", "glm4_moe_lite", "gpt2"],
                         ids=["bert", "mellum", "glm", "gpt2-float32"])
def test_a_family_with_nothing_to_cast_gets_its_leaves_back(mesh_dp, model):
    """No leaf named (BERT), every named leaf in the compute type already
    (the two sparse-expert families name none and hold bfloat16), or a
    float32 compute type: ``leaves_cast`` 0 at every door."""
    with ServeEngine(model, mesh=mesh_dp, **_tiny(model)) as eng:
        born = _last_cast()
        assert born["leaves_cast"] == 0
        assert born["bytes_before"] == born["bytes_after"] > 0
        wl = eng.workload
        declared = jax.eval_shape(lambda: eng.module.init(
            jax.random.key(0), wl.init_batch if wl.init_key is None
            else wl.init_batch[wl.init_key]))["params"]
        as_declared = jax.tree.map(
            lambda x: (x.shape, jnp.dtype(x.dtype).name), declared)
        assert jax.tree.map(lambda x: (x.shape, jnp.dtype(x.dtype).name),
                            eng.params) == as_declared
        spans = len(default_tracer().spans(name="dtt/startup/params_cast"))
        host = jax.device_get(eng.params)
        sharded = eng.shard_params(host)
        eng.install_params(sharded)
        assert _avals(eng.params) == _avals(sharded)
        for got, want in zip(jax.tree.leaves(eng.params),
                             jax.tree.leaves(host)):
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(np.asarray(got), want)
        assert len(default_tracer().spans(
            name="dtt/startup/params_cast")) == spans
        if model == "bert":
            batch = {k: v for k, v in wl.init_batch.items()}
            assert eng.classify(batch).shape[0] == batch["tokens"].shape[0]


def test_the_training_step_does_not_see_the_list(devices8):
    """``decode=False`` is untouched: the tiny GPT-2 step lowers to the
    same text with the family's list taken away."""
    from distributed_tensorflow_tpu.cluster import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(), devices8[:1])

    def lowered(workload):
        _, abstract, _, step, batch_sh = train_lib.build_step(
            workload, mesh, grad_accum_steps=2)
        batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                         sharding=batch_sh[k])
                 for k, v in next(workload.data_fn(4)).items()}
        rng = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
        return step.lower(abstract, batch, rng).as_text()

    workload = get_workload("gpt2", preset="tiny", batch_size=4, mesh=mesh)
    assert workload.served_dtypes is not None
    assert lowered(workload) == lowered(
        dataclasses.replace(workload, served_dtypes=None))
