"""The traffic generators: the same seed gives the same traffic, every seed
the same amount of work, lengths as the mix states them."""

import collections
import json
import os

import numpy as np
import pytest

from benchmark.harness import spec, traffic


def _mix(name):
    with open(os.path.join(spec.BENCH_DIR, "traffic", f"{name}.json")) as f:
        return json.load(f)


def test_requests_repeat_for_a_seed_and_keep_the_work_for_another():
    mix = _mix("chat-saturated")
    a = traffic.open_loop_requests(mix, 2**31 + 5, 30.0)
    b = traffic.open_loop_requests(mix, 2**31 + 5, 30.0)
    c = traffic.open_loop_requests(mix, 6, 30.0)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # Another seed is other tokens arriving at other moments; the work and
    # its order are the same.
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    assert [r.due_s for r in a] != [r.due_s for r in c]
    sizes = lambda rs: [(len(r.prompt), r.max_new_tokens) for r in rs]
    assert sizes(a) == sizes(c)
    lead = mix["lead_in_s"]
    gaps = lambda rs: np.sort(np.diff([-lead] + [r.due_s for r in rs]))
    np.testing.assert_allclose(gaps(a), gaps(c), rtol=0, atol=1e-9)


def test_a_seed_moves_arrivals_only_within_runs_of_shuffle_block():
    mix = _mix("chat-saturated")
    block = mix["shuffle_block"]
    a = traffic.open_loop_requests(mix, 7, 30.0)
    c = traffic.open_loop_requests(mix, 8, 30.0)
    for last in range(block - 1, len(a), block):
        assert a[last].due_s == pytest.approx(c[last].due_s, abs=1e-9)
    assert a[0].due_s != c[0].due_s


def test_request_count_and_arrivals_follow_the_rate_from_the_lead_in_on():
    mix = _mix("chat-saturated")
    rate, lead = mix["arrivals"]["rate_per_s"], mix["lead_in_s"]
    requests = traffic.open_loop_requests(mix, 1, 30.0)
    assert len(requests) == round(rate * (lead + 30.0))
    due = [r.due_s for r in requests]
    assert due == sorted(due) and -lead < due[0] < 0.0 and due[-1] < 30.0
    early = sum(d < 0 for d in due)
    assert 0.6 * rate * lead < early < 1.4 * rate * lead


def test_an_arrival_process_no_generator_knows_is_refused():
    mix = _mix("chat-saturated")
    mix["arrivals"]["process"] = "burst"
    with pytest.raises(ValueError, match="arrival process"):
        traffic.open_loop_requests(mix, 1, 30.0)


def test_length_histogram_of_the_chat_mix():
    mix = _mix("chat-saturated")
    mix["arrivals"]["rate_per_s"] = 100.0       # 4500 draws of the same law
    requests = traffic.open_loop_requests(mix, 3, 30.0)
    prompts = collections.Counter(len(r.prompt) for r in requests)
    assert set(prompts) <= {32, 64, 128, 256, 512, 768}
    assert traffic.prompt_lengths(mix) == [32, 64, 128, 256, 512, 768]
    # log-normal, median 128: half the prompts round up to 128 or less,
    # the two middle buckets hold most of them, and the tail is thin.
    share = lambda *ls: sum(prompts[n] for n in ls) / len(requests)
    assert 0.42 < share(32, 64, 128) < 0.58
    assert share(128, 256) > 0.5
    assert share(768) < 0.08
    outputs = np.array([r.max_new_tokens for r in requests])
    assert len(requests) == round(100.0 * (mix["lead_in_s"] + 30.0))
    assert outputs.min() >= 16 and outputs.max() <= 256
    assert 58 <= np.median(outputs) <= 70
    assert all(r.prompt.dtype == np.int32 and r.prompt.min() >= 0
               and r.prompt.max() < 50257 for r in requests[:50])


def test_a_mix_must_state_its_prompt_lengths():
    mix = _mix("chat-saturated")
    del mix["prompt_tokens"]["round_up_to"]
    with pytest.raises(ValueError, match="round"):
        traffic.prompt_lengths(mix)


@pytest.mark.parametrize("name", ["lm-seq1024-b32", "lm-seq1024-b64"])
def test_lm_batches(name):
    mix = _mix(name)
    a, b = traffic.batches(mix, 9), traffic.batches(mix, 9)
    first, again = next(a), next(b)
    assert np.array_equal(first["tokens"], again["tokens"])
    assert first["tokens"].shape == (mix["batch_size"], mix["seq_len"])
    second = next(a)
    rows = np.concatenate([first["tokens"], second["tokens"]])
    assert len({row.tobytes() for row in rows}) == len(rows)   # all differ
    assert not np.array_equal(
        first["tokens"], next(traffic.batches(mix, 10))["tokens"])
    assert traffic.tokens_per_batch(mix) == mix["batch_size"] * mix["seq_len"]
