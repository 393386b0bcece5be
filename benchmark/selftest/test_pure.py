"""CPU tests of the benchmark's own arithmetic. Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/selftest -q -p no:cacheprovider

Nothing here is under tests/, so tier-1 does not move.
"""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import flops, stats, trace_reduce, traffic_gen  # noqa: E402
from benchmark.trace_reduce import Event  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------- trace


def synthetic_trace():
    d0, d1, ops = "/device:TPU:0", "/device:TPU:1", trace_reduce.OPS_LINE
    return [
        # device 0: [0,100) and [50,150) overlap -> busy [0,150); gap
        # [150,400) of 250 us; then [400,500)
        Event(d0, ops, "fusion.1", 0, 100_000),
        Event(d0, ops, "copy.2 copy bf16[2,4]", 50_000, 100_000),
        # a container: its time is its body's, so it is no top operation
        Event(d0, ops, "while.9 while (s32[], bf16[2,4])", 0, 150_000),
        Event(d0, ops, "fusion.1", 400_000, 100_000),
        # the same time again on another line must not count
        Event(d0, "XLA Modules", "jit_step", 0, 500_000),
        # device 1: busy [0,200) and [300,500)
        Event(d1, ops, "fusion.1", 0, 200_000),
        Event(d1, ops, "all-reduce.3 all-reduce f32[8]", 300_000, 200_000),
        # host: a long span covering everything and a short one in the gap
        Event("/host:CPU", "main", "engine.step", 0, 500_000),
        Event("/host:CPU", "main", "np.asarray(jax.Array)", 160_000, 230_000),
    ]


def test_reduce_synthetic():
    r = trace_reduce.reduce(synthetic_trace())
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(500e-6)
    # device 0 busy 250 us, device 1 busy 400 us -> mean 325 us
    assert r["busy_s"] == pytest.approx(325e-6)
    assert r["idle_share"] == pytest.approx(1 - 325 / 500)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(200e-6)
    assert ops["copy.2 copy bf16[2,4]"] == pytest.approx(100e-6)
    assert not any(k.startswith("while") for k in ops)
    # the gap goes to the most specific host span that covers most of it
    assert r["idle_gaps"] == [["np.asarray(jax.Array)",
                               pytest.approx(250e-6)]]


def test_op_seconds_and_unattributed():
    ev = synthetic_trace()
    assert trace_reduce.op_seconds(ev, r"^copy") == (pytest.approx(100e-6), 1)
    assert trace_reduce.op_label(
        "%copy.43 = bf16[36,512,16,20,64]{4,0,3,2,1:T(8,128)(2,1)} "
        "copy(bf16[36,512,16,20,64]{1,4,3,2,0:T(8,128)(2,1)} %k_pages.1)") \
        == "copy.43 copy bf16[36,512,16,20,64]"
    assert trace_reduce.op_label(
        "%checkpoint.18 = (bf16[384,1024,64]{2,1,0:T(8,128)(2,1)}, "
        "bf16[384,1024,64]{2,1,0:T(8,128)(2,1)}) custom-call(bf16[384,1024,"
        "64]{2,1,0} %b), custom_call_target=\"tpu_custom_call\", x={}") \
        == ("checkpoint.18 custom-call:tpu_custom_call "
            "(bf16[384,1024,64], bf16[384,1024,64])")
    assert trace_reduce.op_label("fusion.1") == "fusion.1"
    assert trace_reduce.op_seconds(ev, r"^all-reduce",
                                   "/device:TPU:1")[1] == 1
    no_host = [e for e in ev if e.plane.startswith("/device")]
    assert trace_reduce.reduce(no_host)["idle_gaps"][0][0] == "unattributed"
    assert trace_reduce.reduce([])["busy_s"] == 0.0


def test_real_trace_if_kept():
    path = os.path.join(os.path.dirname(__file__), "trace_sample.json")
    if not os.path.exists(path):
        pytest.skip("no recorded trace kept yet")
    with open(path) as f:
        sample = json.load(f)
    r = trace_reduce.reduce([Event(*e) for e in sample["events"]])
    assert r["busy_s"] == pytest.approx(sample["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(sample["window_s"], rel=1e-9)
    assert r["device_ops"][0][0] == sample["top_op"]


# ---------------------------------------------------------------- stats


def test_slope_of_a_staircase():
    # 700 tokens every 0.25 s plus single tokens in between: 2816 tokens/s
    times, amounts = [], []
    for i in range(400):
        times.append(i * 0.25)
        amounts.append(700)
        times += [i * 0.25 + 0.1, i * 0.25 + 0.2]
        amounts += [2, 2]
    rate = stats.slope(times, amounts)
    assert rate == pytest.approx(704 / 0.25, rel=2e-3)
    # dropping the step at one edge moves a count by 700, the slope hardly
    assert stats.slope(times[3:], amounts[3:]) == pytest.approx(rate,
                                                                rel=1e-3)


def test_percentile_and_spread():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(101)), 85) == pytest.approx(85)
    assert stats.spread([10, 10, 10, 10, 10, 10]) == 0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


# -------------------------------------------------------------- traffic


def _traffic(name):
    with open(os.path.join(ROOT, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


def test_open_loop_schedule_is_seeded_and_rotated():
    t = _traffic("chat-steady")
    a = traffic_gen.open_loop(t, 2**31 + 7, 51, 50257)
    b = traffic_gen.open_loop(t, 2**31 + 7, 51, 50257)
    c = traffic_gen.open_loop(t, 12345, 51, 50257)
    assert a == b
    assert [r.prompt for r in a] != [r.prompt for r in c]
    for reqs in (a, c):
        m = [r for r in reqs if r.measured]
        assert len(m) == round(t["rate_rps"] * 51)
        assert all(0 <= r.due_s < 51 for r in m)
        assert all(-t["preroll_s"] <= r.due_s < 0
                   for r in reqs if not r.measured)
        assert all(16 <= len(r.prompt) <= 512 and 8 <= r.max_tokens <= 128
                   for r in reqs)
        dues = [r.due_s for r in reqs]
        assert dues == sorted(dues)
    # the same multiset of sizes under every seed, in another order
    size = lambda reqs: sorted((len(r.prompt), r.max_tokens)  # noqa: E731
                               for r in reqs if r.measured)
    assert size(a) == size(c)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]


def test_closed_loop_pool():
    t = _traffic("prefill-sat")
    a = traffic_gen.ClosedPool(t, 1, 50257)
    b = traffic_gen.ClosedPool(t, 2**31 + 9, 50257)
    n = t["cycle_requests"]
    reqs = [a.get(i) for i in range(2 * n)]
    assert all(512 <= len(r.prompt) <= 960 and 4 <= r.max_tokens <= 16
               for r in reqs)
    # sizes cycle, contents never repeat
    assert [len(r.prompt) for r in reqs[:n]] == \
        [len(r.prompt) for r in reqs[n:]]
    assert reqs[0].prompt != reqs[n].prompt
    assert sorted(a.plens) == sorted(b.plens)
    assert a.get(5) == traffic_gen.ClosedPool(t, 1, 50257).get(5)
    # stratified: the cycle's mean is the distribution's, to a token
    assert abs(float(a.plens.mean()) - 736) < 8


def test_stratified_draws_carry_the_distribution():
    import numpy as np

    rng = np.random.default_rng(0)
    x = traffic_gen.draw_lengths(
        {"dist": "lognormal", "median": 96, "sigma": 0.8, "min": 16,
         "max": 512}, 69, rng)
    assert abs(float(np.median(x)) - 96) <= 2
    assert x.min() >= 16 and x.max() <= 512
    y = traffic_gen.draw_lengths({"dist": "uniform", "min": 4, "max": 16},
                                 13, rng)
    assert sorted(y) == list(range(4, 17))


# ---------------------------------------------------------------- flops


def test_flops_by_hand_gpt2_small():
    m = {"n_embd": 768, "n_layer": 12, "n_head": 12, "vocab_size": 50257}
    # 12 layers x 12 x 768^2 = 84,934,656; head 768 x 50257 = 38,597,376
    assert flops.gpt2_matmul_params(m) == 123_532_032
    # 6 x that = 741,192,192; attention 12 x 12 x 1024 x 768 = 113,246,208
    assert flops.gpt2_train_flops_per_token(m, 1024) == 854_438_400
    assert flops.mfu_pct(854_438_400, 90_000, "TPU v5 lite") == \
        pytest.approx(39.03, abs=0.01)
    with pytest.raises(KeyError):
        flops.peaks("TPU v9")
    # flash forward at (32, 12, 1024, 64): 2 matmuls x 2*T*T*D / 2 causal
    assert flops.flash_call_flops("fwd", 32, 12, 1024, 64) == \
        2 * 2 * 32 * 12 * 1024 * 1024 * 64 / 2
    assert flops.flash_call_bytes("fwd", 32, 12, 1024, 64) == \
        4 * 32 * 12 * 1024 * 64 * 2
    assert flops.least_seconds(197e12, 1.0, "TPU v5 lite") == (1.0, "compute")
    assert flops.least_seconds(1.0, 819e9, "TPU v5 lite") == (1.0, "memory")


# ------------------------------------------------------- BENCHMARK.json


def test_names_resolve_and_obey_the_rules(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert 1 <= bench["run_seconds"] <= 51
    cells = [w["name"] for w in bench["workloads"]]
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    names = cells + list(configs) + [
        m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.exists(os.path.join(ROOT, c["file"])), c["file"]
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and 1 <= len(w["why"]) <= 200
        assert "\n" not in w["why"] and "\t" not in w["why"]
        pairs.add((w["config"], w["traffic"]))
        path = os.path.join(ROOT, "benchmark", "traffic",
                            w["traffic"] + ".json")
        with open(path) as f:
            kind = json.load(f)["kind"]
        assert os.path.exists(os.path.join(ROOT, "benchmark", "kinds",
                                           kind + ".py"))
    assert len(pairs) == len(cells)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) \
        <= max(1, len(cells) // 4)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
        moved = next(e for e in bench["end_to_end"]
                     if e["name"] == m["moves"])
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for cell in cells:
        mine = lambda ms: [m for m in ms  # noqa: E731
                           if cell in m.get("workloads", cells)]
        assert len(mine(bench["end_to_end"])) >= 2
        assert len(mine(bench["per_layer"])) >= 1
    for root, _, files in os.walk(os.path.join(ROOT, "benchmark")):
        if "__pycache__" in root:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


def test_result_line_has_the_contracts_keys(bench):
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from benchmark import run as runner

    cell = next(w for w in bench["workloads"]
                if w["name"] == "serve-gpt2-large-prefill-sat") \
        if any(w["name"] == "serve-gpt2-large-prefill-sat"
               for w in bench["workloads"]) else bench["workloads"][0]
    result = {
        "correct": True, "attempted": 10, "failed": 0,
        "end_to_end": {m["name"]: 1.5 for m in bench["end_to_end"]},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 123},
        "compared": {"logprob_gap_max_nats": {"value": 0.01, "limit": 0.05}},
        "observed": {"events": synthetic_trace(), "ready_s": 2.0,
                     "config": json.load(open(os.path.join(
                         ROOT, "benchmark/configs/gpt2-large.json")))},
    }
    line = runner.result_line(bench, cell, result, trace=False)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "compared"}
    # what `correct` compared comes last, each number beside its limit
    assert list(line)[-1] == "compared"
    assert line["compared"] == result["compared"]
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert "setup_s" in line["metrics"]
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
    traced = runner.result_line(bench, cell, result, trace=True)
    assert set(traced) == {"correct", "attempted", "failed", "metrics",
                           "device", "breakdown", "compared"}
    assert list(traced)[-1] == "compared"
    assert {"busy_s", "window_s"} <= set(traced["device"])
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
    # readers with nothing to read are left out, not reported as zero
    assert "replica_ready_s" in traced["metrics"] \
        or "trainer_ready_s" in traced["metrics"]
    json.dumps(traced)
