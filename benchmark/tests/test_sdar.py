"""Family ``sdar`` and the readers its cell brings: the widths as
published, the yardstick's counts by hand at the configuration's own
sizes, the readers on a synthetic scope table (and None where their
source is absent), every new name finding its file and every new metric
listing the cell — BY NAME, so that a later append does not fail them —
the rehearsal run, and the family's own faults, which the comparison has
to see.  Run by hand: ``pytest benchmark/tests``."""

import json
import os

import pytest

from benchmark import checks as C
from benchmark import run as R
from benchmark.layer_metrics import scoped
from benchmark.models import sdar as M

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "sdar-30b.train-4k"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
         "hbm_bytes": 16e9}
NEW_METRICS = ["bd_noise_share_pct.train", "flash_bd_roofline",
               "flash_bd_tiles_pct"]


@pytest.fixture(scope="module")
def config():
    return R.load_json(BENCH, "configs", "sdar-30b-a3b-ep8.json")


def test_published_widths_are_uncut(config):
    sz = M.sizes(config)
    assert (sz["hidden"], sz["heads"], sz["kv_heads"], sz["head_dim"],
            sz["expert_ffn"], sz["experts"], sz["top_k"], sz["norm_topk"],
            sz["rope_theta"], sz["norm_eps"]) == \
        (2048, 32, 4, 128, 768, 128, 8, True, 1e6, 1e-6)
    assert (sz["held"], sz["vocab"], sz["layers"], sz["blocks"],
            sz["block"], sz["eps"]) == (16, 18992, 6, 6, 4, 1e-3)
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert 8 * sz["vocab"] == config["published"]["vocab_size"] == 151936
    assert 8 * sz["held"] == config["published"]["num_experts"] == 128
    assert M.mask_id(sz) == 18991


def test_every_catalog_number_is_in_the_file(config):
    """The catalog row's ``config`` (the published ``config.json``), key
    by key: equal, or named in ``reduced``."""
    published = {
        "attention_bias": False, "decoder_sparse_step": 1,
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "sdar_moe", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    differs = {k for k, v in published.items() if config.get(k) != v}
    assert differs == set(config["reduced"])
    assert all(config["published"][k] == published[k] for k in differs)


def test_initialisation_is_torchtitans(config):
    """N(0, 1) embedding, N(0, 1/hidden) every other matrix, W_o, W_2,
    W_3 and the router scaled by 1/sqrt(2 x 48) for the published 48
    layers (the rehearsal by its own 2), the layers' gains 1 — and
    ``init_params`` draws them so."""
    import math
    sz = M.sizes(config)
    base = 1.0 / math.sqrt(2048)
    assert sz["published_layers"] == 48
    assert M._leaf_std("embedding.weights", sz) == 1.0
    assert M._leaf_std("head.weights", sz) == base
    # the final norm's gain is a leaf named "weights", drawn as every
    # family here draws it: as a matrix
    assert M._leaf_std("final_norm.weights", sz) == base
    for i in range(6):
        for leaf in ("wq", "wk", "wv", "w1"):
            assert M._leaf_std("block%d.%s" % (i, leaf), sz) == base
        for leaf in ("wo", "w2", "w3", "router"):
            assert M._leaf_std("block%d.%s" % (i, leaf), sz) == \
                base / math.sqrt(96)
        for leaf in ("ln1_g", "ln2_g", "q_norm_g", "k_norm_g"):
            assert M._leaf_std("block%d.%s" % (i, leaf), sz) is None
    small = M.sizes(config, rehearse=True)
    assert small["published_layers"] == 2
    drawn = M.init_params(5, small)
    for name in ("block0.wo", "block1.w3", "block1.router", "block1.wq"):
        want = M._leaf_std(name, small)
        assert abs(float(drawn[name].std()) / want - 1.0) < 0.1, name
    assert float(drawn["block1.ln2_g"].min()) == 1.0


def test_parameter_count_by_hand(config):
    sz = M.sizes(config)
    E = 2048
    attention = E * 4096 + E * 512 + E * 512 + 4096 * E + 2 * 128
    assert attention == 18874624
    layer = attention + E * 128 + 2 * E + 16 * 3 * E * 768
    assert layer == 94638336
    by_hand = 6 * layer + 2 * 18992 * E + E
    assert M.parameter_count(sz) == by_hand == 645623296
    assert round(by_hand * 8 / 1e9, 2) == 5.16       # 32% of 16 GB
    shapes = M.leaf_shapes(sz)
    assert shapes["block0.wq"] == (2048, 4096) and \
        shapes["block5.wk"] == (2048, 512) and \
        shapes["block2.router"] == (2048, 128) and \
        shapes["block3.w2"] == (16, 768, 2048) and \
        shapes["block1.q_norm_g"] == (128,) and \
        shapes["head.weights"] == (2048, 18992)


def test_train_flops_per_item_by_hand(config):
    sz = M.sizes(config)
    E, L = 2048, 4096
    row = 2 * E * 4096 + 2 * E * 512 + E * 128 + 3 * E * 768 * 8 * 16 / 128
    assert M.row_params(sz) == row
    pairs = L * L + L * 4
    assert M.visible_pairs(sz, L) == pairs == 16793600
    attention = 4.0 * 32 * 128 * pairs
    assert M.attention_flops_forward(sz, L) == attention
    want = 6 * 2 * 6 * row + 6 * E * 18992 + 3 * 6 * attention / L
    assert M.train_flops_per_item(sz, L) == want
    assert round(want / 1e9, 2) == 3.16
    # the attention core is about 38% of the work
    assert 0.37 < 3 * 6 * attention / L / want < 0.39


def test_flash_call_cost_by_hand(config):
    sz = M.sizes(config)
    L = 4096
    cost = M.flash_call_cost(sz, 1, L)["block_diffusion"]
    assert cost["units"] == ["block%d" % i for i in range(6)] and \
        cost["layers"] == 6
    assert cost["fwd"]["flops"] == cost["dq"]["flops"] == \
        cost["dkv"]["flops"] == 2 * 2.0 * 32 * 128 * 16793600
    wide, narrow, rows = 8192 * 32 * 128 * 2, 8192 * 4 * 128 * 2, \
        32 * 8192 * 4
    assert cost["fwd"]["bytes"] == 2 * wide + 2 * narrow + rows
    assert cost["dkv"]["bytes"] == 2 * wide + 4 * narrow + 2 * rows
    # bound by the MXU: 1.40 ms a kernel a layer a tick at the peak
    assert round(cost["fwd"]["flops"] / 197e12 * 1e3, 2) == 1.40
    assert cost["dkv"]["bytes"] / 819e9 < cost["dkv"]["flops"] / 197e12


# -- the readers -------------------------------------------------------------

def read(metric, record):
    return R.find_reader(metric).read(record, metric)


def synthetic(config, monkeypatch):
    """One traced dispatch of 8 ticks: seconds by instruction and the
    scope table that places them."""
    sz = M.sizes(config)
    mark = 'custom-call(), custom_call_target="tpu_custom_call"'
    table = {"flash_fwd.1": ("forward", "block1", "attention"),
             "flash_dq.2": ("backward", "block3", "attention"),
             "flash_dkv.3": ("backward", "block5", "attention"),
             "flash_fwd.4": ("forward", "block9", "attention"),
             "fusion.5": ("forward", "embedding", "bd_noise"),
             "fusion.6": ("forward", "embedding", None),
             "fusion.7": ("backward", "block2", "moe_experts"),
             "fusion.8": ("forward", "head", None)}
    seconds = {"%flash_fwd.1 = bf16[] " + mark: 0.02,
               "%flash_dq.2 = bf16[] " + mark: 0.03,
               "%flash_dkv.3 = bf16[] " + mark: 0.05,
               "%flash_fwd.4 = bf16[] " + mark: 0.05,
               "%fusion.5 = x": 0.01, "%fusion.6 = x": 0.03,
               "%fusion.7 = x": 0.51, "%fusion.8 = x": 0.30}
    monkeypatch.setattr(
        scoped, "_program", lambda module, attribute:
        (lambda name: table) if attribute == "scopes" else None)
    return {"trace": {"programs": {"jit_block_step": [1, 1.0]},
                      "op_seconds": seconds, "busy_s": 1.0,
                      "kernel_seconds": {k: v for k, v in seconds.items()
                                         if "custom-call(" in k},
                      "dispatches": 1},
            "peaks": PEAKS,
            "yardstick": {"flash": M.flash_call_cost(sz, 1, 4096),
                          "flash_calls_per_dispatch": 8 * sz["blocks"]},
            "counters": {"attention": {
                "pallas": 6, "xla": 0,
                "bd_tiles": {"visited": 80, "total": 256}}}}


def test_readers_on_a_synthetic_scope_table(config, monkeypatch):
    record = synthetic(config, monkeypatch)
    assert read("bd_noise_share_pct.train", record) == pytest.approx(1.0)
    assert read("flash_bd_tiles_pct", record) == 31.25
    # 48 layer-ticks of three kernels at the MXU peak over 0.1 s: block9
    # is nobody's layer
    need = M.flash_call_cost(M.sizes(config), 1, 4096)["block_diffusion"]
    least = 3 * need["fwd"]["flops"] / 197e12
    assert read("flash_bd_roofline", record) == \
        pytest.approx(100 * 48 * least / 0.1)


def test_readers_return_nothing_without_their_source(config, monkeypatch):
    record = synthetic(config, monkeypatch)
    untraced = dict(record, trace=None,
                    counters={"attention": {"pallas": 0, "xla": 6}})
    for name in NEW_METRICS:
        assert read(name, untraced) is None
    # another family's yardstick: no block-diffusion kind
    other = dict(record, yardstick={
        "flash": {"full": {"fwd": {}, "layers": 1, "units": ["block3"]}},
        "flash_calls_per_dispatch": 8})
    assert read("flash_bd_roofline", other) is None
    # a program that opens no such scope
    table = {"fusion.8": ("forward", "head", None)}
    monkeypatch.setattr(
        scoped, "_program", lambda module, attribute:
        (lambda name: table) if attribute == "scopes" else None)
    assert read("bd_noise_share_pct.train", record) is None
    assert read("flash_bd_roofline", record) is None
    # a program that keeps no scope table (a parent commit)
    monkeypatch.setattr(scoped, "_program", lambda module, attr: None)
    assert read("bd_noise_share_pct.train", record) is None
    assert read("flash_bd_roofline", record) is None


def test_every_new_name_finds_its_file(config):
    bench = R.load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    entry = {c["name"]: c for c in bench["configs"]}[config["name"]]
    assert entry["file"] == "benchmark/configs/%s.json" % config["name"]
    assert (entry["reduced"], entry["source"]) == \
        (config["reduced"], config["source"])
    assert len(entry["why"]) <= 200
    assert os.path.isfile(os.path.join(BENCH, "models",
                                       config["family"] + ".py"))
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    data = R.load_json(BENCH, "workloads", CELL + ".json")
    assert all(data[k] == cell[k] for k in cell) and len(cell["why"]) <= 200
    assert cell["config"] == config["name"] and cell["chips"] == 1
    assert cell["traffic"] == "train-1x4096"
    mix = R.load_json(BENCH, "traffic", "train-1x4096.json")
    assert (mix["traffic"]["batch"], mix["traffic"]["seq"],
            mix["traffic"]["ticks"]) == (1, 4096, 8)
    assert set(data["limits"]) == set(data["rehearsal_limits"])
    assert data["control_variants"] == "fp8_e4m3,half_batch,block_leak"
    assert set(data["control_variants"].split(",")) - \
        {"fp8_e4m3"} <= set(M.FAULTS)


def test_every_new_metric_lists_the_cell():
    bench = R.load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    mine = {m["name"]: m for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert sorted(mine) == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_rate"
               and R.find_reader(name) for name, m in mine.items())
    assert {name: (m["source"], m["layer"]) for name, m in mine.items()} \
        == {"flash_bd_roofline": ("device_trace", "kernels"),
            "flash_bd_tiles_pct": ("program_counter",
                                   "attention dispatch"),
            "bd_noise_share_pct.train": ("device_trace",
                                         "block diffusion")}
    names = {m["name"] for m in R.metric_lines(bench, "per_layer", CELL)}
    assert set(NEW_METRICS) < names and "train_mfu_pct" in names and \
        "attn_share_pct.train" in names and \
        "moe_share_pct.train" not in names and \
        "flash_mla_roofline" not in names


# -- the rehearsal and the faults ---------------------------------------------

def test_rehearsal_run_reads_correct(capsys):
    assert R.main(["--workload", CELL, "--seed", "4100000021",
                   "--seconds", "0.2", "--trace", "1", "--rehearse"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True
    assert result["metrics"]["attn_pallas_share_pct"]["value"] == 0.0


@pytest.mark.parametrize("fault", ["block_leak", "half_batch",
                                   "state_unchanged"])
def test_fault_reads_not_correct(config, capsys, fault):
    """The reference with a block's clean keys leaked to its noised
    rows, with half of a one-sequence tick's positions left out, or
    with the state left as it was, in the program's place: the
    comparison sees it (at the rehearsal's size and limits; the chip's
    readings are in PERF.md)."""
    sz = M.sizes(config, rehearse=True)
    mix = R.load_json(BENCH, "traffic", "train-1x4096.json")
    traffic = dict(mix["traffic"], **mix["rehearsal"])
    limits = R.load_json(BENCH, "workloads",
                         CELL + ".json")["rehearsal_limits"]
    sound = M.reference_train(77, sz, traffic, 2)
    broken = M.reference_train(77, sz, traffic, 2, fault=fault)
    compared = C.train_checks(broken, sound, limits)
    assert not all(c["ok"] for c in compared), compared
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    assert [l["fault"] for l in lines] == [None, fault]
