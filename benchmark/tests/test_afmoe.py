"""Family ``afmoe`` and the readers its cell brings: the widths as
published, the yardstick's counts by hand at the configuration's own
sizes, the readers on a synthetic scope table and synthetic counters
(and None where their source is absent), every new name finding its
file, and the fault of its own, ``window_ignored``, which the
comparison has to see.  Run by hand: ``pytest benchmark/tests``."""

import json
import os

import pytest

from benchmark import checks as C
from benchmark import run as R
from benchmark.layer_metrics import scoped
from benchmark.models import afmoe as M

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "trinity-mini.train-8k"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
         "hbm_bytes": 16e9}
NEW_METRICS = ["attn_window_share_pct.train", "flash_full_roofline",
               "flash_window_roofline", "flash_window_tiles_pct",
               "moe_shared_share_pct.train"]


@pytest.fixture(scope="module")
def config():
    return R.load_json(BENCH, "configs", "trinity-mini-ep8.json")


def test_published_widths_are_uncut(config):
    sz = M.sizes(config)
    assert (sz["hidden"], sz["heads"], sz["head_dim"], sz["kv_heads"],
            sz["dense_ffn"], sz["expert_ffn"], sz["experts"],
            sz["top_k"], sz["window"], sz["rope_theta"], sz["scaling"],
            sz["norm_eps"]) == \
        (2048, 32, 128, 4, 6144, 1024, 128, 8, 2048, 1e4, 2.826, 1e-5)
    assert (sz["held"], sz["vocab"], sz["dense_layers"], sz["blocks"]) \
        == (16, 25024, 1, 5)
    assert sz["embed_scale"] == 2048 ** 0.5
    assert sz["layer_types"] == (M.SLIDING,) * 4 + (M.FULL,)
    assert set(config["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"}
    assert 8 * sz["vocab"] == config["published"]["vocab_size"] == 200192


def test_every_catalog_number_is_in_the_file(config):
    """The catalog row's ``config`` (the published ``config.json``), key
    by key: equal, or named in ``reduced``."""
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_size": 2048, "intermediate_size": 6144,
        "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 32, "num_dense_layers": 2,
        "num_expert_groups": 1, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 32,
        "num_key_value_heads": 4, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "vocab_size": 200192, "model_type": "afmoe",
        "score_func": "sigmoid", "hidden_act": "silu"}
    differs = {k for k, v in published.items() if config.get(k) != v}
    assert differs == set(config["reduced"]) - {"layer_types"}
    assert all(config["published"][k] == published[k] for k in differs)


def test_parameter_count_by_hand(config):
    sz = M.sizes(config)
    E = 2048
    attention = 3 * E * 4096 + 2 * E * 512          # q, gate, o; k, v
    assert attention == 27262976
    norms = 4 * E + 2 * 128                         # sandwich, per head
    dense = 3 * E * 6144
    experts = 16 * 3 * E * 1024 + E * 128 + 3 * E * 1024
    slice_ = 25024 * E
    assert attention + norms + dense == 65020160
    assert attention + norms + experts == 134488320
    by_hand = 2 * slice_ + (attention + norms + dense) + \
        4 * (attention + norms + experts) + E
    assert M.parameter_count(sz) == by_hand == 705473792
    shapes = M.leaf_shapes(sz)
    assert shapes["block1.wg"] == (2048, 4096) and \
        shapes["block1.wo"] == (4096, 2048) and \
        shapes["head.weights"] == (2048, 25024) and \
        shapes["block4.ws2"] == (1024, 2048) and \
        "block0.router" not in shapes


def test_train_flops_per_item_by_hand(config):
    sz = M.sizes(config)
    E, S, W = 2048, 8192, 2048
    attention = 27262976
    met = 25024 * E + 5 * attention + 3 * E * 6144 + \
        4 * (E * 128 + 3 * E * 1024 + 1.0 * 3 * E * 1024)  # 8 x 16 / 128
    assert M.matmul_params_per_token(sz) == met
    assert round(met / 1e6, 1) == 276.7
    # visible (row, key) pairs of one head, counted exactly
    window_pairs = sum(min(i + 1, W) for i in range(S))
    assert M.visible_pairs(sz, M.SLIDING, S) == window_pairs == \
        W * (W + 1) // 2 + (S - W) * W
    assert M.visible_pairs(sz, M.FULL, S) == S * (S + 1) // 2
    full = 4.0 * 4096 * (S * (S + 1) // 2)           # heads x head_dim
    window = 4.0 * 4096 * window_pairs
    assert round(full / S / 1e6, 1) == 67.1
    assert round(window / S / 1e6, 1) == 29.4
    assert M.train_flops_per_item(sz, S) == \
        6 * met + 3 * (4 * window + full) / S
    assert round(M.train_flops_per_item(sz, S) / 1e9, 2) == 2.21


def test_flash_call_cost_by_kind_by_hand(config):
    sz = M.sizes(config)
    S = 8192
    cost = M.flash_call_cost(sz, 1, S)
    assert set(cost) == {"window", "full"}
    assert cost["window"]["units"] == ["block0", "block1", "block2",
                                       "block3"]
    assert cost["full"]["units"] == ["block4"]
    assert (cost["window"]["layers"], cost["full"]["layers"]) == (4, 1)
    pairs = {"window": 2048 * 2049 // 2 + 6144 * 2048,
             "full": S * (S + 1) // 2}
    wide, narrow, rows = S * 4096 * 2, S * 512 * 2, 32 * S * 4
    for kind in ("window", "full"):
        for kernel in ("fwd", "dq", "dkv"):
            assert cost[kind][kernel]["flops"] == \
                2 * 2.0 * 4096 * pairs[kind]
        assert cost[kind]["fwd"]["bytes"] == 2 * wide + 2 * narrow + rows
        assert cost[kind]["dq"]["bytes"] == \
            3 * wide + 2 * narrow + 2 * rows
        assert cost[kind]["dkv"]["bytes"] == \
            2 * wide + 4 * narrow + 2 * rows
    # bound by the MXU: 2.79 and 1.22 ms a forward call at the peak
    assert round(cost["full"]["fwd"]["flops"] / 197e12 * 1e3, 2) == 2.79
    assert round(cost["window"]["fwd"]["flops"] / 197e12 * 1e3, 2) == 1.22
    assert cost["full"]["fwd"]["bytes"] / 819e9 < 1e-3


def test_expert_products_cost_by_hand(config):
    sz = M.sizes(config)
    cost = M.expert_products_cost(sz, 8192)
    assert cost["flops"] == 3 * 3 * 2 * 2048 * 1024 * 8192
    assert cost["bytes"] == 3 * (3 * 16 * 2048 * 1024 * 2 +
                                 8192 * 2048 * (2 + 4))


# -- the readers -------------------------------------------------------------

def read(metric, record):
    return R.find_reader(metric).read(record, metric)


def synthetic(config, monkeypatch):
    """One traced dispatch of 8 ticks: seconds by instruction, the scope
    table that places them, and what the trainer counted."""
    sz = M.sizes(config)
    mark = 'custom-call(), custom_call_target="tpu_custom_call"'
    table = {"flash_fwd.1": ("forward", "block1", "attention"),
             "flash_dq.2": ("backward", "block3", "attention"),
             "flash_dkv.3": ("backward", "block0", "attention"),
             "flash_fwd.4": ("forward", "block4", "attention"),
             "flash_dkv.5": ("backward", "block4", "attention"),
             "fusion.6": ("forward", "block2", "attention"),
             "fusion.7": ("backward", "block4", "attention"),
             "fusion.8": ("forward", "block2", "moe_shared"),
             "fusion.9": ("recompute", "block3", "moe_shared"),
             "gmm.10": ("forward", "block2", "moe_experts"),
             "fusion.11": ("forward", "block1", "attn_gate"),
             "fusion.12": ("forward", "head", None)}
    seconds = {"%flash_fwd.1 = bf16[] " + mark: 0.04,
               "%flash_dq.2 = bf16[] " + mark: 0.05,
               "%flash_dkv.3 = bf16[] " + mark: 0.07,
               "%flash_fwd.4 = bf16[] " + mark: 0.03,
               "%flash_dkv.5 = bf16[] " + mark: 0.05,
               "%fusion.6 = x": 0.02, "%fusion.7 = x": 0.01,
               "%fusion.8 = x": 0.06, "%fusion.9 = x": 0.03,
               "%gmm.10 = f32[] " + mark: 0.10,
               "%fusion.11 = x": 0.01, "%fusion.12 = x": 0.53}
    monkeypatch.setattr(
        scoped, "_program", lambda module, attribute:
        (lambda name: table) if attribute == "scopes" else None)
    return {"trace": {"programs": {"jit_block_step": [1, 1.0]},
                      "op_seconds": seconds, "busy_s": 1.0,
                      "kernel_seconds": {k: v for k, v in seconds.items()
                                         if "custom-call(" in k},
                      "dispatches": 1},
            "peaks": PEAKS,
            "yardstick": {"flash": M.flash_call_cost(sz, 1, 8192),
                          "flash_calls_per_dispatch": 8 * sz["blocks"]},
            "counters": {"attention": {
                "pallas": 5, "xla": 0,
                "window_tiles": {"visited": 4 * 70, "total": 4 * 256}}}}


def test_readers_on_a_synthetic_scope_table(config, monkeypatch):
    record = synthetic(config, monkeypatch)
    # sliding units' attention: three kernels and one fusion
    assert read("attn_window_share_pct.train", record) == \
        pytest.approx(100 * (0.04 + 0.05 + 0.07 + 0.02))
    assert read("moe_shared_share_pct.train", record) == \
        pytest.approx(9.0)
    assert read("flash_window_tiles_pct", record) == \
        pytest.approx(100 * 70 / 256) == pytest.approx(27.34375)
    # 8 ticks x 4 window layers x three kernels of 1.2212 ms at the peak
    pairs = 2048 * 2049 // 2 + 6144 * 2048
    least = 3 * 2 * 2.0 * 4096 * pairs / 197e12
    assert read("flash_window_roofline", record) == \
        pytest.approx(100 * 32 * least / 0.16)
    assert 70 < read("flash_window_roofline", record) < 75
    least = 3 * 2 * 2.0 * 4096 * (8192 * 8193 // 2) / 197e12
    assert read("flash_full_roofline", record) == \
        pytest.approx(100 * 8 * least / 0.08)
    assert 80 < read("flash_full_roofline", record) < 85


def test_readers_return_nothing_without_their_source(config, monkeypatch):
    record = synthetic(config, monkeypatch)
    bare = dict(record, counters={"attention": {"pallas": 0, "xla": 5}})
    assert read("flash_window_tiles_pct", bare) is None
    untraced = dict(record, trace=None)
    for name in NEW_METRICS:
        if name != "flash_window_tiles_pct":
            assert read(name, untraced) is None
    # another family's yardstick (no cost by kind)
    dense = dict(record, yardstick={
        "flash": {"fwd": {"flops": 1.0, "bytes": 1.0}},
        "flash_calls_per_dispatch": 32})
    for name in ("flash_window_roofline", "flash_full_roofline",
                 "attn_window_share_pct.train"):
        assert read(name, dense) is None
    # a program that keeps no scope table (a parent commit)
    monkeypatch.setattr(scoped, "_program", lambda module, attr: None)
    for name in NEW_METRICS:
        if name != "flash_window_tiles_pct":
            assert read(name, record) is None


def test_every_new_name_finds_its_file(config):
    """What ``test_yardstick.py``'s name test asks of every entry, for
    the entries this family brings (that test also asks that a source
    be OPT's and ``assumed`` hold OPT's departures, so it fails on this
    configuration as on ``lfm2-24b-a2b-ep8``: PERF.md section 7)."""
    bench = R.load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    entry = [c for c in bench["configs"] if c["name"] == config["name"]][0]
    assert entry["file"] == "benchmark/configs/%s.json" % config["name"]
    assert (entry["reduced"], entry["source"]) == \
        (config["reduced"], config["source"])
    assert len(entry["why"]) <= 200
    assert os.path.isfile(os.path.join(BENCH, "models",
                                       config["family"] + ".py"))
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    data = R.load_json(BENCH, "workloads", CELL + ".json")
    assert all(data[k] == cell[k] for k in cell) and len(cell["why"]) <= 200
    assert cell["config"] == config["name"] and cell["chips"] == 1
    assert cell["traffic"] == "train-1x8192"
    mix = R.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    assert (mix["traffic"]["batch"], mix["traffic"]["seq"],
            mix["traffic"]["ticks"]) == (1, 8192, 8)
    assert os.path.isfile(os.path.join(BENCH, "drivers",
                                       mix["driver"] + ".py"))
    assert set(data["limits"]) == set(data["rehearsal_limits"])
    assert set(data["control_variants"].split(",")) - \
        {"fp8_e4m3"} <= set(M.FAULTS)


def test_every_new_metric_lists_the_cell():
    bench = R.load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    mine = [m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in mine) == NEW_METRICS
    assert [m["name"] for m in bench["per_layer"][-5:]] == \
        [m["name"] for m in mine]                  # appended, at the end
    assert all(m["moves"] == "train_rate" and R.find_reader(m["name"])
               for m in mine)
    assert {m["name"]: m["source"] for m in mine}[
        "flash_window_tiles_pct"] == "program_counter"
    # the metrics without a list read the new cell as they are
    names = {m["name"] for m in R.metric_lines(bench, "per_layer", CELL)}
    assert set(NEW_METRICS) < names and "train_mfu_pct" in names and \
        "moe_share_pct.train" not in names and \
        "flash_roofline" not in names


# -- the faults ---------------------------------------------------------------

@pytest.mark.parametrize("fault", ["window_ignored", "half_batch"])
def test_fault_reads_not_correct(config, capsys, fault):
    """The reference with the window ignored (the sliding layers see
    the whole prefix), or with half of a one-sequence tick's positions
    left out, in the program's place: the comparison sees it (at the
    rehearsal's size and limits; the chip's readings are in
    PERF.md)."""
    sz = M.sizes(config, rehearse=True)
    mix = R.load_json(BENCH, "traffic", "train-1x8192.json")
    traffic = dict(mix["traffic"], **mix["rehearsal"])
    assert traffic["seq"] > sz["window"] and traffic["batch"] == 1
    limits = R.load_json(BENCH, "workloads",
                         CELL + ".json")["rehearsal_limits"]
    sound = M.reference_train(77, sz, traffic, 2)
    broken = M.reference_train(77, sz, traffic, 2, fault=fault)
    compared = C.train_checks(broken, sound, limits)
    assert not all(c["ok"] for c in compared), compared
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    assert [l["fault"] for l in lines] == [None, fault]
