"""Family ``lfm2_moe`` and the readers its cell brings: the yardstick's
counts by hand at the configuration's own sizes, the readers on a
synthetic scope table and synthetic counters (and None where their
source is absent), and the fault of its own, ``capacity_drop``, which
the comparison has to see.  Run by hand: ``pytest benchmark/tests``."""

import json
import os

import pytest

from benchmark import checks as C
from benchmark import run as R
from benchmark.layer_metrics import scoped
from benchmark.models import lfm2_moe as M

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "lfm2-24b-a2b.train"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
         "hbm_bytes": 16e9}


@pytest.fixture(scope="module")
def config():
    return R.load_json(BENCH, "configs", "lfm2-24b-a2b-ep8.json")


def test_published_widths_are_uncut(config):
    sz = M.sizes(config)
    assert (sz["hidden"], sz["heads"], sz["kv_heads"], sz["dense_ffn"],
            sz["expert_ffn"], sz["experts"], sz["top_k"],
            sz["conv_kernel"], sz["rope_theta"]) == \
        (2048, 32, 8, 11776, 1536, 64, 4, 3, 1e6)
    assert (sz["held"], sz["vocab"], sz["dense_layers"], sz["blocks"]) \
        == (8, 8192, 1, 2)
    assert sz["layer_types"] == ("conv",) + \
        ("full_attention", "conv", "conv", "conv") * 2
    assert set(config["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"}


def test_parameter_count_by_hand(config):
    sz = M.sizes(config)
    E = 2048
    conv = E * 3 * E + E * E + E * 3            # in, out, taps
    attention = 2 * E * E + 2 * E * 8 * 64 + 2 * 64
    dense = 3 * E * 11776
    experts = 8 * 3 * E * 1536 + E * 64
    norms = 2 * E
    by_hand = 8192 * E + (conv + dense + norms) + \
        2 * (attention + 3 * conv + 4 * (experts + norms)) + E
    assert M.parameter_count(sz) == by_hand == 832651520


def test_train_flops_per_item_by_hand(config):
    sz = M.sizes(config)
    E = 2048
    conv, attention = 4 * E * E, 2 * E * E + 2 * E * 512
    met = 8192 * E + 7 * conv + 2 * attention + 3 * E * 11776 + \
        8 * (E * 64 + 0.5 * 3 * E * 1536)       # 4 x 8 / 64 of an expert
    assert M.matmul_params_per_token(sz) == met
    assert round(met / 1e6, 1) == 266.3
    scores = 3 * (4 * 2048 * 2048 * E / 2) * 2 / 2048    # two layers
    assert M.train_flops_per_item(sz, 2048) == 6 * met + scores
    # the flash kernels see 32 heads of 64, as at opt-1.3b.train
    cost = M.flash_call_cost(sz, 8, 2048)
    assert cost["fwd"]["flops"] == 2 * (2.0 * 2048 * 2048 * E / 2 * 8)


def test_expert_products_cost_by_hand(config):
    sz = M.sizes(config)
    cost = M.expert_products_cost(sz, 8192)
    assert cost["flops"] == 3 * 3 * 2 * 2048 * 1536 * 8192
    weights = 3 * 8 * 2048 * 1536 * 2
    rows = 8192 * 2048 * (2 + 4)
    assert cost["bytes"] == 3 * (weights + rows)
    # bound by the products, not by the memory: 2.35 ms against 0.92
    assert cost["flops"] / PEAKS["bf16_flops_per_s"] > \
        cost["bytes"] / PEAKS["hbm_bytes_per_s"]


# -- the readers -------------------------------------------------------------

def read(metric, record):
    return R.find_reader(metric).read(record, metric)


def synthetic(config, monkeypatch):
    """One traced dispatch of 8 ticks: seconds by instruction, the scope
    table that places them, and what the trainer counted."""
    sz = M.sizes(config)
    table = {"fusion.1": ("forward", "block3", "moe_experts"),
             "gmm.2": ("backward", "block3", "moe_experts"),
             "gmm.3": ("recompute", "block5", "moe_experts"),
             "sort.4": ("forward", "block3", "moe_route"),
             "gather.5": ("recompute", "block3", "moe_dispatch"),
             "scatter.6": ("backward", "block4", "moe_combine"),
             "fusion.7": ("forward", "block0", "shortconv"),
             "fusion.8": ("backward", "block0", None),
             "fusion.9": ("forward", "head", None)}
    seconds = {"%fusion.1 = f32[] fusion()": 0.10, "%gmm.2 = x": 0.20,
               "%gmm.3 = x": 0.10, "%sort.4 = x": 0.02,
               "%gather.5 = x": 0.03, "%scatter.6 = x": 0.05,
               "%fusion.7 = x": 0.04, "%fusion.8 = x": 0.40,
               "%fusion.9 = x": 0.06}
    moe = {"assignments_made": 8 * 16384 * 4 * 8.0,
           "assignments_landed": 8 * 8192 * 8.0, "ticks": 8.0,
           "max_load_frac": 0.131,
           "products": M.expert_products_cost(sz, 8192),
           "products_per_dispatch": 8 * 8}
    monkeypatch.setattr(
        scoped, "_program", lambda module, attribute:
        (lambda name: table) if attribute == "scopes" else None)
    return {"trace": {"programs": {"jit_block_step": [1, 1.0]},
                      "op_seconds": seconds, "busy_s": 1.0,
                      "dispatches": 1},
            "peaks": PEAKS,
            "counters": {"attention": {"pallas": 8, "xla": 0,
                                       "moe": moe}}}


def test_readers_on_a_synthetic_scope_table(config, monkeypatch):
    record = synthetic(config, monkeypatch)
    assert read("moe_share_pct.train", record) == pytest.approx(50.0)
    assert read("moe_route_share_pct.train", record) == \
        pytest.approx(10.0)
    assert read("shortconv_share_pct.train", record) == \
        pytest.approx(4.0)
    assert read("moe_landed_pct", record) == pytest.approx(12.5)
    assert read("moe_max_load_frac", record) == 0.131
    # 64 layer-ticks x 2.354 ms at the peak over 0.4 s under moe_experts
    least = 9 * 2 * 2048 * 1536 * 8192 / 197e12
    assert read("moe_experts_roofline", record) == \
        pytest.approx(100 * 64 * least / 0.4)
    assert 30 < read("moe_experts_roofline", record) < 45


def test_readers_return_nothing_without_their_source(config, monkeypatch):
    record = synthetic(config, monkeypatch)
    bare = dict(record, counters={"attention": {"pallas": 0, "xla": 8}})
    for name in ("moe_landed_pct", "moe_max_load_frac",
                 "moe_experts_roofline"):
        assert read(name, bare) is None
    untraced = dict(record, trace=None)
    for name in ("moe_share_pct.train", "moe_route_share_pct.train",
                 "shortconv_share_pct.train", "moe_experts_roofline"):
        assert read(name, untraced) is None
    # a program that keeps no scope table (a parent commit)
    monkeypatch.setattr(scoped, "_program", lambda module, attr: None)
    assert read("moe_share_pct.train", record) is None
    assert read("moe_experts_roofline", record) is None


def test_every_new_name_finds_its_file(config):
    """What ``test_yardstick.py``'s name test asks of every entry, for
    the entries this family brings (that test also asks that a source
    be OPT's and ``assumed`` hold OPT's departures: a ``benchmark`` PR's
    to loosen, PERF.md section 7)."""
    bench = R.load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    entry = [c for c in bench["configs"] if c["name"] == config["name"]][0]
    assert entry["file"] == "benchmark/configs/%s.json" % config["name"]
    assert (entry["reduced"], entry["source"]) == \
        (config["reduced"], config["source"])
    assert os.path.isfile(os.path.join(BENCH, "models",
                                       config["family"] + ".py"))
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    data = R.load_json(BENCH, "workloads", CELL + ".json")
    assert all(data[k] == cell[k] for k in cell) and len(cell["why"]) <= 200
    assert cell["config"] == config["name"] and cell["chips"] == 1
    mix = R.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    assert os.path.isfile(os.path.join(BENCH, "drivers",
                                       mix["driver"] + ".py"))
    assert set(data["limits"]) == set(data["rehearsal_limits"])


def test_every_new_metric_lists_the_cell():
    bench = R.load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    mine = [m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in mine) == [
        "moe_experts_roofline", "moe_landed_pct", "moe_max_load_frac",
        "moe_route_share_pct.train", "moe_share_pct.train",
        "shortconv_share_pct.train"]
    assert all(m["moves"] == "train_rate" and R.find_reader(m["name"])
               for m in mine)


# -- the fault of its own ----------------------------------------------------

def test_capacity_drop_reads_not_correct(config, capsys):
    """The reference with each held expert's assignments cut at 0.75 x
    the mean load, in the program's place: the comparison sees the
    dropped tokens (at the rehearsal's limits; the chip's readings are
    in PERF.md)."""
    sz = M.sizes(config, rehearse=True)
    traffic = dict(R.load_json(BENCH, "traffic",
                               "train-8x2048.json")["traffic"],
                   batch=2, seq=32, ticks=2)
    limits = R.load_json(BENCH, "workloads",
                         CELL + ".json")["rehearsal_limits"]
    sound = M.reference_train(77, sz, traffic, 2)
    dropped = M.reference_train(77, sz, traffic, 2,
                                fault="capacity_drop")
    assert sum(dropped["landed"]) < 0.9 * sum(sound["landed"])
    compared = C.train_checks(dropped, sound, limits)
    assert not all(c["ok"] for c in compared), compared
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    assert [l["fault"] for l in lines] == [None, "capacity_drop"]
