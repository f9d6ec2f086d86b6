"""The yardstick's pure parts: trace reduction on a recorded trace,
FLOP and byte counts against a count by hand, the seeded weights and
rows, the control's rounding, the worst-leaf gap, and that every name
in ``BENCHMARK.json`` finds its file."""

import json
import os

import pytest

from benchmark import checks as C
from benchmark import run as R
from benchmark import trace as T
from benchmark.models import dense_lm as M

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- trace -------------------------------------------------------------------

def test_reduction_on_recorded_trace():
    planes = T.load_planes(os.path.join(
        BENCH, "testdata", "trace_opt-6.7b.train.json.gz"))
    # (the recording keeps the first 4000 operations: one dispatch)
    reduced = T.reduce_planes(planes)
    assert reduced["devices"] == 1
    # one block_step dispatch of 3.86 s with sub-millisecond gaps
    assert 3.8 < reduced["window_s"] < 3.9
    assert 0.99 * reduced["window_s"] < reduced["busy_s"] <= \
        reduced["window_s"]
    # self time: the ops' self times add up to the busy union
    assert sum(reduced["op_seconds"].values()) == pytest.approx(
        reduced["busy_s"], rel=1e-6)
    assert len(reduced["device_ops"]) == T.TOP
    assert all(len(name) <= 120 for name, _s in reduced["device_ops"])
    # the flash kernels are the tpu_custom_call events and nothing else
    from benchmark.layer_metrics import flash_roofline as F
    kernels = {k: v for k, v in reduced["kernel_seconds"].items()
               if F.KERNEL_MARK in k}
    assert len(kernels) == 16           # 4 blocks x (fwd, remat, dq, dkv)
    assert all(0.007 < v < 0.011 for v in kernels.values())
    # gaps inside a dispatch are charged to the benchmark's annotation
    assert reduced["idle_gaps"][0][0] == "bench.dispatch"
    # the modules line: three executions of the block program, 3.86 s each
    count, seconds = reduced["programs"]["jit_block_step"]
    assert count == pytest.approx(3)
    assert seconds / count == pytest.approx(3.863, abs=0.005)
    assert "pallas kernel (tpu_custom_call)" in reduced["kinds"]


def test_self_time_takes_children_off_the_parent():
    events = [("while", 0.0, 100.0), ("a", 10.0, 30.0), ("b", 50.0, 20.0),
              ("b", 200.0, 5.0)]
    assert T.self_times(events) == {"while": 50e-9, "a": 30e-9,
                                    "b": 25e-9}
    assert T.union([(0, 5), (3, 8), (10, 12)]) == [[0, 8], [10, 12]]


def test_idle_gap_named_by_the_host_annotation_that_covers_it():
    planes = {"devices": {"/device:TPU:0": [("op", 0.0, 10.0),
                                            ("op", 30.0, 10.0),
                                            ("op", 100.0, 10.0)]},
              "modules": {},
              "host": [("bench.request", 5.0, 30.0)]}
    reduced = T.reduce_planes(planes)
    assert dict(map(tuple, reduced["idle_gaps"])) == {
        "bench.request": pytest.approx(20e-9),
        "between bench.* spans": pytest.approx(60e-9)}


# -- FLOPs and bytes by hand -------------------------------------------------

TINY = {"hidden": 8, "heads": 2, "ffn": 32, "vocab": 11, "positions": 6,
        "blocks": 3}


def test_parameter_and_flop_counts_by_hand():
    E, F, V, S, L = 8, 32, 11, 6, 3
    per_block = 4 * E * E + 4 * E + 2 * E * F + F + E + 4 * E
    assert M.parameter_count(TINY, S) == V * E + S * E + L * per_block
    # a token meets q, k, v, o (4 E^2), the MLP (2 E F = 8 E^2) and the
    # tied head (V E): forward 2 FLOPs each, backward twice that
    matmul = L * (4 * E * E + 2 * E * F) + V * E
    assert M.matmul_params_per_token(TINY) == matmul
    # attention, one sequence, one block, forward: QK^T and PV are
    # 2 S^2 E each; the causal mask needs half; backward twice forward
    attention = 3 * (4 * S * S * E / 2) * L / S
    assert M.train_flops_per_item(TINY, S) == 6 * matmul + attention


def test_kernel_costs_by_hand():
    B, S, E, H = 2, 6, 8, 2
    cost = M.flash_call_cost(TINY, B, S)
    one_matmul = 2 * S * S * E / 2 * B
    tensor, rows = B * S * E * 2, B * H * S * 4
    assert cost["fwd"] == {"flops": 2 * one_matmul,
                           "bytes": 4 * tensor + rows}
    assert cost["dq"]["bytes"] == 5 * tensor + 2 * rows
    assert cost["dkv"]["bytes"] == 6 * tensor + 2 * rows


def test_weights_and_tokens_are_a_function_of_the_seed():
    import numpy
    big = 3000000019                    # past 32 signed bits
    a = M.init_params(big, TINY, 6)
    b = M.init_params(big, TINY, 6)
    c = M.init_params(big + 1, TINY, 6)
    assert all(numpy.array_equal(a[k], b[k]) for k in a)
    assert not numpy.array_equal(a["block0.wq"], c["block0.wq"])
    assert float(a["block1.ln1_g"].min()) == 1.0
    assert float(abs(a["block1.bq"]).max()) == 0.0
    t1, l1 = M.make_tokens(big, 4, 6, 11)
    t2, _ = M.make_tokens(big, 4, 6, 11)
    assert numpy.array_equal(t1, t2)
    assert numpy.array_equal(l1[:, :-1], t1[:, 1:])


# -- the control's rounding --------------------------------------------------

def test_control_operands_sit_on_the_fp8_grid():
    """Inside a jitted program, where a compiler may drop a narrowing
    cast that is widened again: every element keeps at most four
    significant bits and is off by at most a sixteenth (three mantissa
    bits, not bfloat16's seven), and the cotangent passes unrounded."""
    import jax
    import numpy
    x = 0.0156 * jax.random.normal(jax.random.PRNGKey(5), (64, 256))
    r = numpy.asarray(jax.jit(lambda v: M._round_operand(v, 4, 3))(x))
    x = numpy.asarray(x)
    mantissa, _ = numpy.frexp(r)
    assert numpy.array_equal(mantissa * 16, numpy.round(mantissa * 16))
    # (elements under a 4096th of the largest fall out of e4m3's range)
    inside = numpy.abs(x) > numpy.abs(x).max() / 4096
    error = numpy.abs(r - x)[inside] / numpy.abs(x)[inside]
    assert float(error.max()) <= 0.0625 + 1e-6
    assert float(numpy.median(error)) > 0.01
    g = jax.grad(lambda v: (M._round_operand(v, 4, 3) ** 2).sum())(
        jax.numpy.asarray(x))
    assert numpy.allclose(numpy.asarray(g), 2 * r)


# -- correct ----------------------------------------------------------------

def test_worst_leaf_gap_is_measured_against_leaf_or_median():
    reference = {"a": 10.0, "b": 1.0, "c": 1e-6}
    program = {"a": 10.1, "b": 1.05, "c": 2e-6}
    gap, leaf = C.worst_leaf_gap(program, reference)
    # c doubled, but against the median leaf (1.0) that is 1e-6
    assert leaf == "b" and gap == pytest.approx(0.05)
    gap, leaf = C.worst_leaf_gap({"a": 10.0, "b": float("nan"), "c": 0},
                                 reference)
    assert leaf == "b" and gap != gap
    assert not C.check("x", float("nan"), 1.0)["ok"]
    assert not C.check("x", None, 1.0)["ok"]
    assert C.check("x", 0.5, 1.0)["ok"]
    # a leaf the program left unmoved reads 1
    gap, _ = C.worst_leaf_gap({"a": 0.0, "b": 1.0, "c": 1e-6}, reference)
    assert gap == pytest.approx(1.0)


# -- names ------------------------------------------------------------------

def test_every_name_in_benchmark_json_finds_its_file():
    b = bench()
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            data = json.load(f)
        assert data["name"] == c["name"]
        assert data["reduced"] == c["reduced"]
        assert data["source"] == c["source"]
        assert data["source"].startswith("https://huggingface.co/facebook")
        assert os.path.isfile(os.path.join(BENCH, "models",
                                           data["family"] + ".py"))
        assert set(("no_final_layer_norm", "position_offset",
                    "initialisation")) <= set(data["assumed"])
    for w in b["workloads"]:
        with open(os.path.join(BENCH, "workloads",
                               w["name"] + ".json")) as f:
            data = json.load(f)
        assert data["config"] == w["config"] in configs
        assert data["chips"] == w["chips"]
        assert data["traffic"] == w["traffic"]
        with open(os.path.join(BENCH, "traffic",
                               w["traffic"] + ".json")) as f:
            mix = json.load(f)
        assert os.path.isfile(os.path.join(BENCH, "drivers",
                                           mix["driver"] + ".py"))
        assert set(data["limits"]) == set(data["rehearsal_limits"])
        assert data["why"] == w["why"]
        reported = [m for m in b["end_to_end"]
                    if "workloads" not in m or w["name"] in m["workloads"]]
        assert len(reported) >= 2 and "setup_s" in [m["name"]
                                                    for m in reported]
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert R.find_reader(m["name"]) is not None, m["name"]
        assert m["moves"] in e2e
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
