"""The yardstick's arithmetic: traffic from a seed, percentiles, operations
counted from the graph, and the trace reduction."""
import glob
import os

import numpy as np
import pytest

import flops
from harness import spec as spec_mod, stats, trace, traffic

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


# ------------------------------------------------------------------- traffic
def _requests(mix, seed, rounds=3):
    cs = traffic.callers(mix, seed, vocab=1000)
    return [[c.next_request() for c in cs] for _ in range(rounds)]


@pytest.mark.parametrize("name", ["generate-closed", "score-closed"])
def test_traffic_is_the_seeds_and_nothing_elses(name):
    mix = spec_mod.load_json(os.path.join(spec_mod.BENCH_DIR, "traffic",
                                          name + ".json"))
    mix = dict(mix, callers=8)
    a, b, c = _requests(mix, 7), _requests(mix, 7), _requests(mix, 8)
    flat = lambda rounds: [(r["prompt_len"], r["output_len"],
                            r["tokens"].tolist()) for rs in rounds for r in rs]
    assert flat(a) == flat(b)
    assert flat(a) != flat(c)
    fields = mix["fields"]
    lengths = fields["prompt_len"].get("grid") or fields["prompt_len"]["values"]
    for rs_a, rs_c in zip(a, c):
        # stratified: every round is the same multiset of lengths on every
        # seed, only who gets which (and the tokens) differs
        for key in ("prompt_len", "output_len"):
            assert sorted(r[key] for r in rs_a) == sorted(r[key] for r in rs_c)
            assert sorted(r[key] for r in rs_a) == sorted(
                traffic.quantile(fields[key], (i + 0.5) / 8) for i in range(8))
        for r in rs_a:
            assert r["prompt_len"] in lengths
            assert len(r["tokens"]) == r["prompt_len"]
            assert r["tokens"].min() >= 1 and r["tokens"].max() < 1000
    assert [r["prompt_len"] for r in a[0]] != [r["prompt_len"] for r in a[1]] \
        or [r["prompt_len"] for r in a[0]] != [r["prompt_len"] for r in c[0]]


def test_lognormal_rounds_up_to_the_grid_and_clips():
    f = {"dist": "lognormal", "median": 40, "sigma": 1.5, "grid": [16, 64]}
    qs = [(i + 0.5) / 50 for i in range(50)]
    assert {traffic.quantile(f, q) for q in qs} == {16, 64}
    assert traffic.quantile(f, 0.5) == 64          # 40 rounds UP to 64
    f = {"dist": "lognormal", "median": 100, "sigma": 2.0, "clip": [64, 512]}
    draws = [traffic.quantile(f, q) for q in qs]
    assert min(draws) == 64 and max(draws) == 512 and draws == sorted(draws)
    assert traffic.quantile({"dist": "lognormal", "median": 100,
                             "sigma": 0.5}, 0.5) == 100
    assert traffic.quantile({"dist": "choice", "values": [3, 5]}, 0.75) == 5
    with pytest.raises(ValueError):
        traffic.quantile({"dist": "zipf"}, 0.5)
    q = traffic.strata(3, "prompt_len", 0, 16)
    assert sorted(q) == [(i + 0.5) / 16 for i in range(16)]
    assert list(q) != list(traffic.strata(4, "prompt_len", 0, 16))
    assert list(q) != list(traffic.strata(3, "prompt_len", 1, 16))
    assert list(q) != list(traffic.strata(3, "output_len", 0, 16))


# --------------------------------------------------------------- percentiles
@pytest.mark.parametrize("q", [0, 25, 50, 95, 99, 100])
def test_percentile_is_numpys(q):
    xs = list(np.random.default_rng(q).exponential(size=37))
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert stats.percentile([], q) is None
    assert stats.percentile([3.0], q) == 3.0


# --------------------------------------------------------------------- flops
def test_resnet50_macs_come_from_the_graph():
    from mxnet_tpu import models

    net = models.get_symbol("resnet-50", num_classes=1000,
                            image_shape="3,224,224")
    macs, per_node = flops.graph_macs(net, data=(1, 3, 224, 224),
                                      softmax_label=(1,))
    assert macs == 4_089_184_256       # not bench.py's "2*MACs" = 4.09e9
    assert len(per_node) == 54         # 53 convolutions and the classifier
    assert flops.graph_macs(net, data=(4, 3, 224, 224),
                            softmax_label=(4,))[0] == 4 * macs
    assert flops.train_flops(net, data=(1, 3, 224, 224),
                             softmax_label=(1,)) == 6 * macs


def test_transformer_macs_equal_the_closed_form():
    from mxnet_tpu.models import transformer

    cfg = dict(vocab_size=320, num_layers=3, num_heads=4, model_dim=64,
               ffn_dim=256)
    net = transformer.get_symbol(seq_len=32, **cfg)
    macs, _ = flops.graph_macs(net, data=(2, 32), softmax_label=(2, 32))
    assert macs == flops.transformer_forward_macs(2, 32, 64, 3, 256, 320)
    # one decode step of one lane at context c is the forward's last row
    step = flops.decode_step_flops([32], 64, 3, 256, 320)
    weights = 3 * (4 * 64 * 64 + 2 * 64 * 256) + 64 * 320
    assert step == 2 * weights + 4 * 3 * 32 * 64


# --------------------------------------------------------------------- trace
E = trace.Event


def test_interval_arithmetic():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8), (4, 4)]) == \
        [(0, 3), (5, 8)]
    assert trace.total([(0, 3), (5, 8)]) == 6
    assert trace.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert trace.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert trace.op_name("%fusion.3 = bf16[8]{0} fusion(%p), kind=kLoop") == \
        "fusion.3"


def test_reduction_on_hand_made_events():
    """Two chips over a 100 ns window. Chip 0: a while loop (10..50) holding a
    fusion (10..30) and an all-reduce (30..50) half hidden behind a second
    fusion (40..60, which outlives the loop), then idle, then fusion 80..90."""
    ops0 = [E("while.1", 10, 50), E("fusion.1", 10, 30),
            E("all-reduce.1", 30, 50), E("fusion.2", 40, 60),
            E("fusion.1", 80, 90)]
    ops1 = [E("fusion.1", 0, 100)]
    spans = [E("bench.window", 0, 100), E("bench.train_step", 0, 20),
             E("bench.sync", 20, 75), E("bench.sample", 60, 70)]
    t = {"ops": {0: sorted(ops0, key=lambda e: (e.start, -e.end)), 1: ops1},
         "spans": spans}
    r = trace.reduce(t)
    assert r["window_s"] == pytest.approx(100e-9)
    # chip 0 is busy 10..60 and 80..90, chip 1 always: (60 + 100) / 2
    assert r["busy_s"] == pytest.approx(80e-9)
    assert r["busy_s_by_chip"] == {"0": pytest.approx(60e-9),
                                   "1": pytest.approx(100e-9)}
    assert r["idle_share"] == pytest.approx(0.2)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(30e-9)
    assert ops["all-reduce.1"] == pytest.approx(20e-9)
    assert ops["fusion.2"] == pytest.approx(20e-9)
    assert ops["while.1"] == pytest.approx(0.0)      # all of it is its body
    assert r["device_ops"][0][0] == "fusion.1"        # most first
    # chip 0 idles 0..10 (in train_step), 60..80 and 90..100: 60..70 is
    # bench.sample (innermost), 70..75 bench.sync, the rest nobody's
    gaps = dict(r["idle_gaps"])
    assert gaps == {"bench.train_step": pytest.approx(10e-9),
                    "bench.sample": pytest.approx(10e-9),
                    "bench.sync": pytest.approx(5e-9),
                    trace.UNATTRIBUTED: pytest.approx(15e-9)}
    assert r["collective_s"] == pytest.approx(20e-9)
    assert r["collective_exposed_s"] == pytest.approx(10e-9)  # 30..40
    assert trace.reduce({"ops": {}, "spans": spans}) is None


def test_window_falls_back_to_the_extent_of_the_trace():
    t = {"ops": {0: [E("fusion.1", 5, 9)]}, "spans": [E("bench.step", 2, 6)]}
    assert trace.window_of(t) == (2, 9)
    assert trace.reduce(t)["busy_s"] == pytest.approx(4e-9)


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(FIXTURES, "*.xplane.pb"))), ids=os.path.basename)
def test_reduction_on_a_recorded_trace(path):
    """Small traces recorded on the v5e (PR 22: three steps of a two-matmul
    program between bench.* spans, on one chip and on four)."""
    chips = int(os.path.basename(path)[len("chips"):].split(".")[0])
    t = trace.read(path)
    assert sorted(t["ops"]) == list(range(chips))
    assert {s.name for s in t["spans"]} == {
        "bench.window", "bench.train_step", "bench.sync", "bench.sample"}
    r = trace.reduce(t)
    lo, hi = trace.window_of(t)
    assert r["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert 0 < r["busy_s"] < r["window_s"]
    assert len(r["busy_s_by_chip"]) == chips
    # three steps with a 3 ms pause each: the chip waits on the host
    assert r["idle_share"] > 0.5
    gaps = dict(r["idle_gaps"])
    assert 9e-3 <= gaps["bench.sample"] <= 15e-3   # 3 x sleep(0.003)
    idle0 = r["window_s"] - r["busy_s_by_chip"]["0"]
    assert sum(gaps.values()) == pytest.approx(idle0, rel=1e-6)
    times = [s for _, s in r["device_ops"]]
    assert times == sorted(times, reverse=True) and len(times) <= 10
    assert any("fusion" in n for n, _ in r["device_ops"])
    if chips == 1:
        assert r["collective_s"] == 0
    else:
        assert 0 < r["collective_exposed_s"] <= r["collective_s"]
        assert any(trace.is_collective(n) for n, _ in r["device_ops"])
