"""``models/paged_call.py`` alone: one call's view of the paged cache on a
bare module that declares one pool, held to numpy.  No model is compiled
here; the four family files are the parity tests of what the view replaced
(and hold its argument errors and its ``kv_dtype`` refusal, through four
families)."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from distributed_tensorflow_tpu.analysis.layering import DECODER_FAMILIES
from distributed_tensorflow_tpu.models import PagedKVConfig
from distributed_tensorflow_tpu.models.paged_call import (
    PagedCall, serve_refusals)

SLOTS, BLOCK, WIDTH, LAYERS, HELD = 4, 4, 8, 2, 3
PAGED = PagedKVConfig(block_size=BLOCK, num_blocks=9)
# Slot 2 has three blocks, slot 0 two and a trash entry, slot 1 nothing.
TABLES = np.array([[5, 1, -1], [-1, -1, -1], [7, 3, 4], [2, -1, -1]],
                  np.int32)
INDEX = np.array([3, 0, 5, 1], np.int32)


class OnePool(nn.Module):
    """Writes its input rows into layer 1 of one pool and reads the table
    rows back; returns what the view said of the call."""

    @nn.compact
    def __call__(self, rows, *, decode=False, slot_ids=None, paged=None,
                 block_tables=None, live=None):
        B, T, _ = rows.shape
        view = PagedCall(
            self, B, T, decode=decode, slot_ids=slot_ids, paged=paged,
            block_tables=block_tables, live=live, pools="the test pool",
            refusals=serve_refusals("the test pool"), experts=(LAYERS, HELD))
        pool = view.pool("pool", LAYERS, WIDTH, rows.dtype)
        said = dict(positions=view.positions, start=view.start,
                    lengths=view.lengths, token_live=view.token_live,
                    pool=pool, key_positions=view.key_positions())
        view.advance()
        if pool is not None:
            pool = view.write(pool, 1, rows)
            said["gathered"] = view.gather(pool, 1)
        view.close(pool, counts=jnp.ones((LAYERS, HELD + 3), jnp.int32))
        return said


def fresh_cache():
    cache = OnePool().init(
        jax.random.key(0), jnp.zeros((SLOTS, 1, WIDTH)), decode=True,
        slot_ids=jnp.arange(SLOTS), paged=PAGED,
        block_tables=jnp.zeros((SLOTS, 3), jnp.int32))["cache"]
    assert cache["pool"].shape == (LAYERS, 9, BLOCK, WIDTH)
    assert cache["moe_counts"].shape == (LAYERS, HELD + 3)
    # The engine takes the shapes and starts from zeros, as here: the init
    # call itself has run the body once.
    return dict(jax.tree.map(jnp.zeros_like, cache),
                cache_index=jnp.asarray(INDEX))


def call(rows, slot_ids, live=None):
    return OnePool().apply(
        {"cache": fresh_cache()}, rows, decode=True,
        slot_ids=jnp.asarray(slot_ids), paged=PAGED,
        block_tables=jnp.asarray(TABLES), live=live, mutable=["cache"])


def test_write_then_gather_is_the_tables_cells():
    slot_ids, T = [2, 0], 3
    rows = np.random.default_rng(0).normal(size=(2, T, WIDTH)).astype(
        np.float32)
    said, mutated = call(jnp.asarray(rows), slot_ids)
    clipped = np.maximum(TABLES, 0)
    want = np.zeros((LAYERS, 9, BLOCK, WIDTH), np.float32)
    for b, slot in enumerate(slot_ids):
        for t in range(T):
            p = INDEX[slot] + t
            want[1, clipped[slot, p // BLOCK], p % BLOCK] = rows[b, t]
    np.testing.assert_array_equal(np.asarray(mutated["cache"]["pool"]), want)
    np.testing.assert_array_equal(
        np.asarray(said["gathered"]),
        want[1, clipped[slot_ids]].reshape(2, 3 * BLOCK, WIDTH))
    np.testing.assert_array_equal(np.asarray(said["start"]), [5, 3])
    np.testing.assert_array_equal(np.asarray(said["positions"]),
                                  [[5, 6, 7], [3, 4, 5]])
    np.testing.assert_array_equal(np.asarray(said["key_positions"]),
                                  [list(range(3 * BLOCK))] * 2)
    # Slot 2's row lands where its table says, at positions 5..7.
    np.testing.assert_array_equal(want[1, 3, 1:], rows[0, :3])


def test_a_row_that_is_not_live_reads_nothing():
    live = jnp.asarray([True, False, True, False])
    said, _ = call(jnp.zeros((SLOTS, 1, WIDTH)), np.arange(SLOTS), live)
    np.testing.assert_array_equal(np.asarray(said["lengths"]), [4, 0, 6, 0])
    said, _ = call(jnp.zeros((2, 3, WIDTH)), [2, 0],
                   jnp.asarray([False, True]))
    np.testing.assert_array_equal(np.asarray(said["lengths"]), [0, 6])
    np.testing.assert_array_equal(np.asarray(said["token_live"]),
                                  [False] * 3 + [True] * 3)
    said, _ = call(jnp.zeros((2, 3, WIDTH)), [2, 0])
    np.testing.assert_array_equal(np.asarray(said["lengths"]), [8, 6])
    assert said["token_live"] is None


def test_the_index_advances_in_the_calls_rows_and_nowhere_else():
    _, mutated = call(jnp.zeros((2, 3, WIDTH)), [2, 0])
    np.testing.assert_array_equal(
        np.asarray(mutated["cache"]["cache_index"]), INDEX + [3, 0, 3, 0])
    # A row that is not live advances too: the engine's gate freezes it.
    _, mutated = call(jnp.zeros((SLOTS, 1, WIDTH)), np.arange(SLOTS),
                      jnp.asarray([True, False, False, True]))
    np.testing.assert_array_equal(
        np.asarray(mutated["cache"]["cache_index"]), INDEX + 1)
    np.testing.assert_array_equal(
        np.asarray(mutated["cache"]["moe_counts"]), 1)


def test_a_call_without_a_cache_has_plain_positions_and_no_pool():
    said = OnePool().apply({}, jnp.zeros((2, 5, WIDTH)))
    np.testing.assert_array_equal(np.asarray(said["positions"]),
                                  [list(range(5))] * 2)
    for name in ("start", "lengths", "token_live", "pool"):
        assert said[name] is None, name
    assert said["key_positions"] is said["positions"]
    assert "gathered" not in said


@pytest.mark.parametrize("family", DECODER_FAMILIES)
def test_every_family_refuses_the_same_features_each_with_a_reason(family):
    module = importlib.import_module(
        f"distributed_tensorflow_tpu.models.{family}")
    refusals = module.SERVE_REFUSALS
    assert set(refusals) == {
        "dense_cache", "kv_dtype", "per_shard_kv", "slo_scheduling",
        "spec_k", "prefix_cache", "tensor_mesh"}
    assert set(refusals) == set(serve_refusals("a pool"))
    for feature, reason in refusals.items():
        assert isinstance(reason, str) and len(reason) > 20, feature
