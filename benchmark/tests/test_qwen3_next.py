"""Family ``qwen3_next`` and the readers its cell brings: the widths as
published, the yardstick's counts by hand at the configuration's own
sizes, the readers on a synthetic scope table and synthetic counters
(and None where their source is absent), every new name finding its
file, the rehearsal run, and the family's own faults, which the
comparison has to see.  Run by hand: ``pytest benchmark/tests``."""

import json
import os

import pytest

from benchmark import checks as C
from benchmark import run as R
from benchmark.layer_metrics import scoped
from benchmark.models import qwen3_next as M

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "qwen3-next.train-8k"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
         "hbm_bytes": 16e9}
NEW_METRICS = ["gated_delta_roofline", "linattn_gates_share_pct.train",
               "linattn_scan_steps", "linattn_share_pct.train"]


@pytest.fixture(scope="module")
def config():
    return R.load_json(BENCH, "configs", "qwen3-next-80b-a3b-ep16.json")


def test_published_widths_are_uncut(config):
    sz = M.sizes(config)
    assert (sz["hidden"], sz["heads"], sz["head_dim"], sz["kv_heads"],
            sz["rope_dim"], sz["rope_theta"], sz["key_heads"],
            sz["key_dim"], sz["value_heads"], sz["value_dim"],
            sz["conv_kernel"], sz["expert_ffn"], sz["shared_ffn"],
            sz["experts"], sz["top_k"], sz["norm_eps"], sz["chunk"]) == \
        (2048, 16, 256, 2, 64, 1e7, 16, 128, 32, 128, 4, 512, 512, 512,
         10, 1e-6, 64)
    assert (sz["held"], sz["vocab"], sz["blocks"]) == (32, 18992, 1)
    assert sz["layer_types"] == (M.LINEAR,) * 3 + (M.FULL,)
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert 8 * sz["vocab"] == config["published"]["vocab_size"] == 151936
    assert 16 * sz["held"] == config["published"]["num_experts"] == 512


def test_every_catalog_number_is_in_the_file(config):
    """The catalog row's ``config`` (the published ``config.json``), key
    by key: equal, or named in ``reduced``."""
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    differs = {k for k, v in published.items() if config.get(k) != v}
    assert differs == set(config["reduced"])
    assert all(config["published"][k] == published[k] for k in differs)


def test_parameter_count_by_hand(config):
    sz = M.sizes(config)
    E = 2048
    linear = E * 12288 + E * 64 + 8192 * 4 + (32 + 32 + 128) + 4096 * E
    assert linear == 33718464
    full = 2 * E * 4096 + 2 * E * 512 + 4096 * E + 2 * 256
    assert full == 27263488
    ffn = E * 512 + 3 * E * 512 + E + 32 * 3 * E * 512 + 2 * E
    assert ffn == 104863744
    assert (linear + ffn, full + ffn) == (138582208, 132127232)
    slice_ = 18992 * E
    by_hand = 3 * (linear + ffn) + (full + ffn) + 2 * slice_ + E
    assert M.parameter_count(sz) == by_hand == 625667136
    shapes = M.leaf_shapes(sz)
    assert shapes["block0.w_qkvz"] == (2048, 12288) and \
        shapes["block0.w_ba"] == (2048, 64) and \
        shapes["block2.w_conv"] == (8192, 4) and \
        shapes["block1.a_log"] == shapes["block1.dt_bias"] == (32,) and \
        shapes["block0.gdn_norm_g"] == (128,) and \
        shapes["block3.wg"] == (2048, 4096) and \
        shapes["block3.wk"] == (2048, 512) and \
        shapes["block3.q_norm_g"] == (256,) and \
        shapes["block3.wsg"] == (2048, 1) and \
        shapes["block0.w1"] == (32, 2048, 512) and \
        shapes["head.weights"] == (2048, 18992) and \
        "block3.w_qkvz" not in shapes and "block0.wq" not in shapes


def test_train_flops_per_item_by_hand(config):
    sz = M.sizes(config)
    E, S = 2048, 8192
    linear = E * 12288 + E * 64 + 4096 * E           # no taps
    full = 3 * E * 4096 + 2 * E * 512
    ffn = E * 512 + 3 * E * 512 + E + 3 * E * 512 * 10 * 32 / 512
    met = 18992 * E + 3 * linear + full + 4 * ffn
    assert M.matmul_params_per_token(sz) == met
    assert round(met / 1e6, 1) == 191.9
    attention = 4.0 * 4096 * (S * (S + 1) // 2)      # heads x head_dim
    rule = 6.0 * 128 * 128 * 32 * S
    assert M.attention_flops_forward(sz, S) == attention
    assert M.rule_flops_forward(sz, S) == rule
    assert M.train_flops_per_item(sz, S) == \
        6 * met + 3 * (attention + 3 * rule) / S
    assert round(6 * met / 1e9, 3) == 1.151
    assert round(3 * attention / S / 1e9, 3) == 0.201
    assert round(9 * rule / S / 1e9, 3) == 0.028
    assert round(M.train_flops_per_item(sz, S) / 1e9, 2) == 1.38


def test_gated_delta_cost_by_hand(config):
    sz = M.sizes(config)
    S = 8192
    cost = M.gated_delta_cost(sz, 1, S)
    assert cost["units"] == ["block0", "block1", "block2"] and \
        cost["layers"] == 3
    assert cost["flops"] == 18 * 128 * 128 * 32 * S
    # q, k at 16 x 128, v and o at 32 x 128 in bfloat16; g, beta float32
    once = S * ((2 * 2048 + 2 * 4096) * 2 + 2 * 32 * 4)
    assert cost["bytes"] == 2 * once == 406847488
    # bound by the HBM: 0.497 ms a layer a tick, 0.392 by the MXU
    assert round(cost["bytes"] / 819e9 * 1e3, 3) == 0.497
    assert round(cost["flops"] / 197e12 * 1e3, 3) == 0.392
    full = M.flash_call_cost(sz, 1, S)
    assert set(full) == {"full"} and full["full"]["units"] == ["block3"]
    assert full["full"]["fwd"]["flops"] == \
        2 * 2.0 * 4096 * (S * (S + 1) // 2)


# -- the readers -------------------------------------------------------------

def read(metric, record):
    return R.find_reader(metric).read(record, metric)


class _Gauge(object):
    value = 128


def synthetic(config, monkeypatch):
    """One traced dispatch of 8 ticks: seconds by instruction, the scope
    table that places them, and what the trainer counted."""
    sz = M.sizes(config)
    table = {"fusion.1": ("forward", "block0", "gated_delta"),
             "while.2": ("backward", "block1", "gated_delta"),
             "fusion.3": ("recompute", "block2", "gated_delta"),
             "fusion.4": ("forward", "block0", "shortconv"),
             "fusion.5": ("backward", "block1", "gdn_gate"),
             "fusion.6": ("recompute", "block2", "gdn_norm"),
             "fusion.7": ("forward", "block3", "attention"),
             "fusion.8": ("forward", "block9", "shortconv"),
             "fusion.9": ("forward", "head", None)}
    seconds = {"%fusion.1 = x": 0.10, "%while.2 = x": 0.20,
               "%fusion.3 = x": 0.10, "%fusion.4 = x": 0.03,
               "%fusion.5 = x": 0.02, "%fusion.6 = x": 0.01,
               "%fusion.7 = x": 0.04, "%fusion.8 = x": 0.05,
               "%fusion.9 = x": 0.45}

    class Registry(object):
        @staticmethod
        def peek(name, label):
            assert label == {"program": "block_step"}
            return _Gauge() if name == "linear_attention.scan_steps" \
                else None

    monkeypatch.setattr(
        scoped, "_program", lambda module, attribute:
        (lambda name: table) if attribute == "scopes" else Registry)
    need = M.gated_delta_cost(sz, 1, 8192)
    need["calls_per_dispatch"] = 3 * 8
    return {"trace": {"programs": {"jit_block_step": [1, 1.0]},
                      "op_seconds": seconds, "busy_s": 1.0,
                      "kernel_seconds": {}, "dispatches": 1},
            "peaks": PEAKS,
            "counters": {"attention": {"pallas": 1, "xla": 0,
                                       "gated_delta": need}}}


def test_readers_on_a_synthetic_scope_table(config, monkeypatch):
    record = synthetic(config, monkeypatch)
    assert read("linattn_share_pct.train", record) == pytest.approx(40.0)
    # the linear layers' units only: block9's convolution is another's
    assert read("linattn_gates_share_pct.train", record) == \
        pytest.approx(6.0)
    # 24 layer-ticks of 0.4968 ms at the HBM peak over 0.4 s
    assert read("gated_delta_roofline", record) == \
        pytest.approx(100 * 24 * 406847488 / 819e9 / 0.4)
    assert 2.9 < read("gated_delta_roofline", record) < 3.0
    assert read("linattn_scan_steps", record) == 128


def test_readers_return_nothing_without_their_source(config, monkeypatch):
    record = synthetic(config, monkeypatch)
    untraced = dict(record, trace=None)
    for name in NEW_METRICS:
        if name != "linattn_scan_steps":
            assert read(name, untraced) is None
    # another family's trainer: no needed work of a rule
    other = dict(record, counters={"attention": {"pallas": 5, "xla": 0}})
    assert read("gated_delta_roofline", other) is None
    assert read("linattn_gates_share_pct.train", other) is None
    # a program that opens no such scope reads nothing, not nought
    table = {"fusion.9": ("forward", "head", None)}
    monkeypatch.setattr(
        scoped, "_program", lambda module, attribute:
        (lambda name: table) if attribute == "scopes" else None)
    for name in NEW_METRICS:
        assert read(name, record) is None
    # a program that keeps no scope table (a parent commit)
    monkeypatch.setattr(scoped, "_program", lambda module, attr: None)
    for name in NEW_METRICS:
        assert read(name, record) is None


def test_every_new_name_finds_its_file(config):
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
    assert bench["workloads"][-1] == cell and bench["configs"][-1] == entry
    assert set(data["limits"]) == set(data["rehearsal_limits"])
    assert data["control_variants"] == "fp8_e4m3,half_batch,state_dropped"
    assert set(data["control_variants"].split(",")) - \
        {"fp8_e4m3"} <= set(M.FAULTS)


def test_every_new_metric_lists_the_cell():
    bench = R.load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    mine = [m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in mine) == NEW_METRICS
    assert [m["name"] for m in bench["per_layer"][-4:]] == \
        [m["name"] for m in mine]                  # appended, at the end
    assert all(m["moves"] == "train_rate" and R.find_reader(m["name"])
               for m in mine)
    assert {m["name"]: (m["source"], m["layer"]) for m in mine} == {
        "linattn_share_pct.train": ("device_trace", "linear attention"),
        "linattn_gates_share_pct.train": ("device_trace",
                                          "linear attention"),
        "gated_delta_roofline": ("device_trace", "kernels"),
        "linattn_scan_steps": ("program_counter", "linear attention")}
    # the metrics without a list read the new cell as they are
    names = {m["name"] for m in R.metric_lines(bench, "per_layer", CELL)}
    assert set(NEW_METRICS) < names and "train_mfu_pct" in names and \
        "attn_pallas_share_pct" in names and \
        "moe_share_pct.train" not in names and \
        "flash_full_roofline" not in names


# -- the rehearsal and the faults ---------------------------------------------

def test_rehearsal_run_reads_correct(capsys):
    assert R.main(["--workload", CELL, "--seed", "3500000021",
                   "--seconds", "0.2", "--trace", "1", "--rehearse"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True
    assert result["metrics"]["linattn_scan_steps"]["value"] == 4  # 64 / 16
    assert result["metrics"]["attn_pallas_share_pct"]["value"] == 0.0


@pytest.mark.parametrize("fault", ["state_dropped", "decay_ignored",
                                   "half_batch"])
def test_fault_reads_not_correct(config, capsys, fault):
    """The reference with the rule's state dropped at every chunk, with
    its decay ignored, or with half of a one-sequence tick's positions
    left out, in the program's place: the comparison sees it (at the
    rehearsal's size and limits; the chip's readings are in
    PERF.md)."""
    sz = M.sizes(config, rehearse=True)
    mix = R.load_json(BENCH, "traffic", "train-1x8192.json")
    traffic = dict(mix["traffic"], **mix["rehearsal"])
    assert traffic["seq"] > sz["chunk"] and traffic["batch"] == 1
    limits = R.load_json(BENCH, "workloads",
                         CELL + ".json")["rehearsal_limits"]
    sound = M.reference_train(77, sz, traffic, 2)
    broken = M.reference_train(77, sz, traffic, 2, fault=fault)
    compared = C.train_checks(broken, sound, limits)
    assert not all(c["ok"] for c in compared), compared
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    assert [l["fault"] for l in lines] == [None, fault]
