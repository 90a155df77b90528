"""The request generator: the same seed gives the same inputs, every
seed gets the same set of sizes and gaps in another order, and
lateness is measured from the time a request was due."""

import collections

import numpy as np
import pytest

from benchmark import harness
from benchmark.runners import serve

requests = harness.load_module("generators", "requests")
CHAT = harness.load_json("traffic", "chat-poisson.json")
REPO = harness.load_json("traffic", "repo-closed.json")
BIG_SEED = 3_000_000_019  # more than 32 signed bits hold


def lengths(generated):
    return ([len(r["prompt"]) for r in generated["requests"]],
            [r["max_tokens"] for r in generated["requests"]])


def test_same_seed_same_inputs_other_seed_other_order():
    a = requests.generate(CHAT, seed=BIG_SEED, vocab=1000)
    b = requests.generate(CHAT, seed=BIG_SEED, vocab=1000)
    c = requests.generate(CHAT, seed=7, vocab=1000)
    assert a == b
    assert lengths(a) != lengths(c)
    assert a["requests"][0]["prompt"] != c["requests"][0]["prompt"]
    for x, y in zip(lengths(a), lengths(c)):
        assert collections.Counter(x) == collections.Counter(y)
    gaps = lambda g: np.diff([0.0] + [r["due"] for r in g["requests"]])  # noqa: E731
    assert np.allclose(np.sort(gaps(a)), np.sort(gaps(c)))
    assert np.all(gaps(a) > 0)


def test_lengths_follow_the_traffic_file():
    g = requests.generate(CHAT, seed=1, vocab=1000)
    prompts, outputs = lengths(g)
    spec = CHAT["prompt_tokens"]
    assert min(prompts) == spec["min"] and max(prompts) == spec["max"]
    # free lengths: most are no multiple of the 128-token page
    assert sum(n % 128 != 0 for n in prompts) > 0.9 * len(prompts)
    assert abs(np.median(prompts) - spec["median"]) <= 8
    assert 16 <= min(outputs) and max(outputs) <= 256
    one_round = len(prompts) // CHAT["rounds"]
    rate = one_round / g["requests"][one_round - 1]["due"]
    assert rate == pytest.approx(CHAT["arrivals"]["rate_per_s"], rel=0.03)


def test_a_round_is_the_same_set_in_an_order_that_is_not_stratified():
    """Each round holds the whole set; inside a round the order is a
    plain permutation, so some stretch of 16 requests is all short or
    all long prompts' worth of work away from the mean, and some second
    holds several arrivals (the bursts an open loop is there to send)."""
    g = requests.generate(CHAT, seed=3, vocab=1000)
    prompts, _ = lengths(g)
    n = len(prompts) // CHAT["rounds"]
    assert sorted(prompts[:n]) == sorted(prompts[n:]) and \
        prompts[:n] != prompts[n:]
    sums = [sum(prompts[i:i + 16]) for i in range(0, n - 15)]
    assert max(sums) > 1.5 * min(sums)
    dues = np.array([r["due"] for r in g["requests"]])
    assert np.all(np.diff(dues) > 0)
    per_second = np.bincount(dues.astype(int))
    rate = CHAT["arrivals"]["rate_per_s"]
    assert per_second.max() >= 2 * rate and per_second.min() == 0
    # the counts' variance over their mean: 1 for Poisson arrivals
    assert 0.6 < per_second[:46].var() / per_second[:46].mean() < 1.6


@pytest.mark.parametrize("spec, check", [
    ({"kind": "uniform", "min": 0, "max": 10},
     lambda v: abs(v.mean() - 5) < 0.1),
    ({"kind": "exponential", "mean": 2.0}, lambda v: abs(v.mean() - 2) < 0.1),
    ({"kind": "lognormal", "median": 100.0, "sigma": 0.5},
     lambda v: abs(np.median(v) - 100) < 1 and v.max() > 300),
])
def test_quantile_values(spec, check):
    values = requests.quantile_values(spec, 200)
    assert len(values) == 200 and np.all(np.diff(values) >= 0)
    assert check(values)


def test_an_unknown_distribution_is_an_error():
    with pytest.raises(ValueError, match="unknown distribution"):
        requests.quantile_values({"kind": "gamma", "mean": 1, "cv": 3}, 10)


def test_shared_contexts_are_prefixes_and_evenly_dealt():
    g = requests.generate(REPO, seed=BIG_SEED, vocab=1000)
    assert g["closed_clients"] == 16 and len(g["contexts"]) == 4
    used = collections.Counter()
    for r in g["requests"]:
        ctx = g["contexts"][r["context"]]
        assert r["prompt"][:len(ctx)] == ctx and r["due"] is None
        own = len(r["prompt"]) - len(ctx)
        assert 256 <= own <= 1024
        used[r["context"]] += 1
    assert len(g["requests"]) == REPO["requests"] * REPO["rounds"]
    assert set(used.values()) == {len(g["requests"]) // 4}


def test_chunk_sizes_the_traffic_can_reach():
    engine = {"prefill_chunk": 256}
    assert serve.chunk_sizes(CHAT, engine) == list(range(1, 257))
    assert serve.chunk_sizes(REPO, engine) == list(range(1, 257))
    odd = {"prompt_tokens": {"kind": "uniform", "min": 300, "max": 302}}
    assert serve.chunk_sizes(odd, engine) == [44, 45, 46, 256]
    short = {"prompt_tokens": {"kind": "uniform", "min": 100, "max": 101}}
    assert serve.chunk_sizes(short, engine) == [100, 101]


class FakeEngine:
    """Takes requests, answers nothing: enough for the load's clock."""

    def __init__(self):
        self.added = []
        self.on_token = self.on_finish = None

    def add_request(self, prompt, sampling, request_id):
        if len(prompt) > 5:
            raise ValueError("too long")
        self.added.append(request_id)


def test_open_loop_lateness_is_measured_from_the_due_time():
    now = [100.0]
    clock = lambda: now[0]  # noqa: E731
    specs = [{"id": f"r{i}", "due": d, "prompt": [1] * n, "max_tokens": 2,
              "context": None}
             for i, (d, n) in enumerate([(0.5, 2), (1.0, 9), (4.0, 2)])]
    engine = FakeEngine()
    load = serve.Load(engine, {"requests": specs, "closed_clients": 0},
                      clock, harness.Spans(clock))
    load.start(100.0)
    load.submit_due(100.2)
    assert engine.added == [] and load.next_due() == 100.5
    now[0] = 101.7                      # the loop was stalled for 1.5 s
    load.submit_due(now[0])
    assert engine.added == ["r0"] and load.refused == 1
    rec = load.records["r0"]
    assert rec["due"] == 100.5 and rec["sent"] == 101.7
    # first token at 102.0: TTFT counts the stall, 1.5 s, not 0.3 s
    rec["token_times"] += [102.0, 102.1]
    rec["finished"] = 102.1
    m = serve.serve_metrics(load.records, (100.0, 103.0))
    assert sorted(m["ttft_ms"]) == [pytest.approx(1500.0), float("inf")]
    assert max(m["gen_lag_ms"]) == pytest.approx(1200.0)
    assert m["token_gaps_ms"] == [pytest.approx(100.0)]
    assert m["tpot_p50_ms"] == pytest.approx(100.0)
    assert m["out_tok_per_s"] == pytest.approx(2 / 3.0)
    assert load.waiting_for_first_token() is False  # the other was refused


def test_metrics_count_only_what_the_window_holds():
    rec = lambda due, times, fin: {  # noqa: E731
        "due": due, "sent": due, "token_times": times, "finished": fin,
        "prompt": [1], "tokens": [0] * len(times), "max_tokens": len(times),
        "prefix_cached_tokens": 0}
    records = {
        "done": rec(0.0, [1.0, 1.1, 1.3], 1.3),
        "late": rec(5.0, [9.5, 10.5], 10.5),      # finishes after the end
        "never": rec(9.0, [], None),              # due inside, unanswered
        "after": rec(11.0, [], None),             # due after the end
    }
    m = serve.serve_metrics(records, (0.0, 10.0))
    assert sorted(m["token_gaps_ms"]) == [pytest.approx(100), pytest.approx(200)]
    assert m["request_tpot_ms"] == [pytest.approx(150.0)]  # 0.3 s, 2 gaps
    assert m["tpot_p50_ms"] == pytest.approx(150.0)    # median of 100, 200
    assert m["tpot_mean_ms"] == pytest.approx(150.0)
    assert m["tpot_p90_ms"] == pytest.approx(190.0)
    assert len(m["ttft_ms"]) == 3 and m["ttft_p90_ms"] == float("inf")
    assert m["tokens_out"] == 4 and m["out_tok_per_s"] == pytest.approx(0.4)


@pytest.mark.parametrize("name, key", [
    ("entry.tpot_mean_ms", "tpot_mean_ms"), ("entry.tpot_p90_ms", "tpot_p90_ms")])
def test_pooled_gap_readers_hand_on_the_windows_numbers(name, key):
    reader = harness.load_module("layer_metrics", name)
    assert reader.read({"facts": {"metrics": {key: 12.5}}}) == 12.5
    assert reader.read({"facts": {}}) is None
