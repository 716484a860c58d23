"""The granite-4.0-h-small cell's files: the work counts at the
published sizes, the three per-layer readers on synthetic readings,
and one run of the driver at a small size on the CPU (the program
served through the engine, scored by the plain reference)."""
import importlib.util
import json
import os

import pytest

import benchtest
import harness
import tracereduce
import work_granite_hybrid as W
from tracereduce import Event, Summary

BENCH = benchtest.BENCH
CELL = "granite4h-small-mem.decode"
PEAK = harness.device_peak("TPU v5 lite")


def config():
    with open(os.path.join(BENCH, "configs",
                           "granite-4.0-h-small-d10-mem.json")) as f:
        return json.load(f)


def reader(name):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"m_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def harness_least(w):
    import work
    return work.least_time(w, PEAK, integer=False)


def test_layer_parameters_at_published_sizes():
    c = config()
    assert W.layer_linear_params(c, "mamba") == 800_587_776     # 800.6 M
    assert W.layer_linear_params(c, "attention") == 740_294_656  # 740.3 M
    # the experts are 680 M of a Mamba2 layer's 800 M
    assert 72 * W.expert_params(c) == 679_477_248
    # one period: nine Mamba2 layers and one attention layer
    assert sum(W.layer_linear_params(c, t) for t in c["layer_types"]) \
        == 9 * 800_587_776 + 740_294_656


def test_expert_ffn_call_counts_touched_experts_at_four_bits():
    c = config()
    w = W.expert_ffn_call(c, rows=320, touched=70)
    assert w.ops == 2 * 320 * 3 * 4096 * 768
    # 70 experts' three [4096, 768] matrices at 4 bits, bf16 rows in
    # (4096 wide for gate and up, 768 for down) and bf16 rows out
    weights = 70 * 3 * 4096 * 768 / 2
    acts = 320 * (4096 + 4096 + 768) * 2 + 320 * (768 + 768 + 4096) * 2
    assert w.bytes == weights + acts
    t, bound = harness_least(w)
    assert bound == "memory"
    # an expert no row chose is not read
    assert W.expert_ffn_call(c, 320, 71).bytes - w.bytes \
        == 3 * 4096 * 768 / 2


def test_model_ops_per_token():
    c = config()
    base = W.model_ops_per_token(c, 0)
    # attention grows with the context, once per attention layer
    assert W.model_ops_per_token(c, 100) - base == 2 * 2 * 32 * 128 * 100
    # the active parameters dominate: ten layers' mixers, ten of 72
    # experts and the shared MLP, plus the tied head
    active = sum(W.mixer_linear_params(c, t) for t in c["layer_types"]) \
        + 10 * (10 * W.expert_params(c) + 3 * 4096 * 1536) \
        + 4096 * 100352
    assert active < base / 2 < active * 1.02


def _reading(counters, trace=None, tokens_per_s=100.0):
    return harness.Reading(config=config(), peak=PEAK,
                           end_to_end={"tokens_per_s": tokens_per_s},
                           counters=counters, trace=trace)


MOE = {"moe_calls": 100, "moe_rows": 100 * 320, "moe_peak_rows": 100 * 12,
       "moe_experts_touched": 100 * 70, "moe_load_peak": 100 * 2.7,
       "contexts": [200, 300]}


def _summary(gmm_s):
    dev = "/device:TPU:0"
    ops = [Event(dev, tracereduce.DEVICE_OP_LINE, "moe_gmm", 0.0,
                 gmm_s * 1e9),
           Event(dev, tracereduce.DEVICE_OP_LINE, "fusion", gmm_s * 1e9,
                 1e6)]
    return Summary(window_s=gmm_s * 2, busy_s=gmm_s + 1e-3, devices=1,
                   ops=ops, gaps=[])


def test_moe_roofline_reads_nothing_without_a_trace_or_calls():
    r = reader("moe_roofline.decode")
    assert r.read(_reading(MOE)) is None
    assert r.read(_reading(dict(MOE, moe_calls=0), _summary(1.0))) is None


def test_moe_roofline_on_a_synthetic_summary():
    r = reader("moe_roofline.decode")
    c = config()
    least = harness_least(W.expert_ffn_call(
        c, MOE["moe_rows"], MOE["moe_experts_touched"]))[0]
    # the kernel took twice the least time: 50%
    got = r.read(_reading(MOE, _summary(2 * least)))
    assert got == pytest.approx(50.0)
    assert 0 < r.read(_reading(MOE, _summary(1.0))) <= 100


def test_expert_load_peak_is_one_for_even_routing():
    r = reader("expert_load_peak.decode")
    assert r.read(_reading({"moe_calls": 0})) is None
    # 72 experts, 720 rows, 10 each: the busiest has the even share
    even = {"moe_calls": 3, "moe_load_peak": 3 * (10 * 72 / 720)}
    assert r.read(_reading(even)) == pytest.approx(1.0)
    assert r.read(_reading(MOE)) == pytest.approx(2.7)


def test_mfu_hybrid_decode():
    r = reader("mfu.hybrid_decode")
    c = config()
    assert r.read(_reading({"contexts": []})) is None
    ops = (W.model_ops_per_token(c, 200) + W.model_ops_per_token(c, 300)) / 2
    assert r.read(_reading(MOE, tokens_per_s=100.0)) == pytest.approx(
        100.0 * 100.0 * ops / PEAK["int8_ops_per_s"])


def test_every_new_metric_lists_the_cell_only():
    with open(os.path.join(benchtest.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    by = {m["name"]: m for m in spec["per_layer"]}
    for name in ("moe_roofline.decode", "expert_load_peak.decode",
                 "mfu.hybrid_decode"):
        assert by[name]["workloads"] == [CELL]
        assert os.path.exists(os.path.join(BENCH, "metrics", f"{name}.py"))
    cell = {w["name"]: w for w in spec["workloads"]}[CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "sharegpt_closed"


def test_a_program_without_the_model_fails_at_once(monkeypatch):
    """Asked before any weight is made: a program that replays this
    model's prompts token by token is refused."""
    import drivers.hybrid_decode as hd
    import repro.models
    arch = hd.arch_config("x", config())
    assert arch.layer_period[5] == "attention"
    monkeypatch.setattr(repro.models, "slot_state", lambda cfg: False)
    with pytest.raises(harness.HarnessError, match="token by token"):
        hd.arch_config("x", config())
    with pytest.raises(harness.HarnessError, match="nope"):
        hd.arch_config("x", dict(config(), position_embedding_type="rope"))


def tiny_config():
    cfg = config()
    cfg.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=64, shared_intermediate_size=96,
               mamba_n_heads=4, mamba_d_head=64, mamba_d_state=32,
               num_local_experts=8, num_experts_per_tok=2,
               num_hidden_layers=3,
               layer_types=["mamba", "attention", "mamba"],
               vocab_size=512, attention_multiplier=1 / 32)
    cfg["engine"].update(batch=4, s_max=64, prefill_chunk=8)
    cfg["checks"]["min_tokens_compared"] = 10
    return cfg


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout with a small cell of the same driver, listed by every
    metric the granite4h cell reports."""
    root = benchtest.make_root(tmp_path_factory.mktemp("hybrid"),
                               configs={"tiny-hyb": tiny_config()},
                               mixes={"tiny_mix": benchtest.TINY_MIX})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "tiny.hybrid", "config": "tiny-hyb",
                              "traffic": "tiny_mix", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny.hybrid")
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


def test_small_cell_serves_and_is_scored(root):
    """The whole driver on the CPU: weights made and packed one layer
    at a time, the closed loop through the engine (chunked prefill,
    joins), the MoE counters, and the reference's score.  The program's
    bf16 arithmetic on the CPU differs from the chip's (PERF.md, open
    question 11), so only the mechanics of the check are asserted; its
    limit is set from chip runs."""
    r = benchtest.run(root, "tiny.hybrid", seconds=2.0, trace=True)
    assert r["failed"] == 0 and r["attempted"] >= 4
    w = r["window"]
    assert w["compiles_in_window"] == 0
    assert w["prefill_calls"] > 0 and w["decode_calls"] > 0
    assert r["checks"]["tokens_compared"]["value"] >= 10
    assert list(r["checks"]) == ["mean_logit_error", "tokens_compared"]
    assert r["checks"]["mean_logit_error"]["value"] > 0
    assert 0 <= w["mean_logit_gap"] <= w["max_logit_gap"]
    got = r["metrics"]
    # a per-layer reader that finds nothing on the CPU leaves its
    # metric out: no device trace for the roofline or the idle share
    assert "moe_roofline.decode" not in got
    assert "device_idle_share.decode" not in got
    assert got["expert_load_peak.decode"]["value"] >= 1.0
    assert got["mfu.hybrid_decode"]["value"] > 0
