"""The readers of what the program says about itself (PR 26,
``layer_metrics/scoped.py``): device shares by phase, unit and inner
scope from a recorded trace joined with a scope table, the three
kernel rooflines by kernel name, the host's split of a dispatch from
the program's dispatch records — and that each returns None where its
source is absent.  Run by hand: ``pytest benchmark/tests``."""

import json
import os

import pytest

from benchmark import run as R
from benchmark import trace as T
from benchmark.layer_metrics import scoped
from benchmark.models import dense_lm as M

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
         "hbm_bytes": 16e9}
SZ = {"hidden": 4096, "heads": 32, "ffn": 16384, "vocab": 50272,
      "positions": 2048, "blocks": 4}
SHARES = ("fwd_share_pct.train", "remat_share_pct.train",
          "bwd_share_pct.train", "update_share_pct.train",
          "unscoped_share_pct.train")


def record_from(recording, dispatches, peaks=PEAKS):
    trace = T.reduce_planes(T.load_planes(os.path.join(
        BENCH, "testdata", recording)))
    trace["dispatches"] = dispatches
    return {"trace": trace, "peaks": peaks,
            "window": {"dispatches": 3, "seconds": 11.6, "items": 3},
            "yardstick": {"flash": M.flash_call_cost(SZ, 4, 2048),
                          "flash_calls_per_dispatch": 8 * 4}}


def read(metric, record):
    return R.find_reader(metric).read(record, metric)


def give_program(monkeypatch, table=None, recent=None):
    """Stands in for the program's two sources."""
    def program(module, attribute):
        if attribute == "scopes" and table is not None:
            return lambda name: table if name == "block_step" else None
        if attribute == "recent" and recent is not None:
            return lambda: recent
        return None
    monkeypatch.setattr(scoped, "_program", program)


def hand_table(record):
    """A small hand-written scope table over PR 25's recording (made
    before the program named anything): the heaviest operations placed
    by hand, everything else left out of the table."""
    ops = sorted(record["trace"]["op_seconds"].items(),
                 key=lambda kv: -kv[1])
    names = [k.split(" ", 1)[0].lstrip("%") for k, _s in ops]
    assert names[0].startswith("while")
    placings = [("forward", "block0", "attention"),
                ("recompute", "block0", "mlp"),
                ("backward", "head", None),
                ("update", "update", None),
                ("backward", "evaluator", None),
                (None, None, None)]
    return {name: placings[i % len(placings)]
            for i, name in enumerate(names[1:40])}, ops


def test_shares_add_to_100_and_the_unplaced_land_in_unscoped(monkeypatch):
    record = record_from("trace_opt-6.7b.train.json.gz", 1)
    table, ops = hand_table(record)
    give_program(monkeypatch, table=table)
    shares = {m: read(m, record) for m in SHARES}
    assert all(v is not None and v >= 0.0 for v in shares.values())
    assert sum(shares.values()) == pytest.approx(100.0, abs=1e-6)
    # the while (61% of this cut recording: its body's later operations
    # were cut off) is in no table: unscoped, with the sixth placing
    busy = record["trace"]["busy_s"]
    assert shares["unscoped_share_pct.train"] > 100.0 * ops[0][1] / busy
    by_hand = sum(s for (k, s) in ops[1:40:6]) / busy * 100.0
    assert shares["fwd_share_pct.train"] == pytest.approx(by_hand)
    assert read("attn_share_pct.train", record) == pytest.approx(by_hand)
    head = sum(s for i, (k, s) in enumerate(ops[1:40])
               if i % 6 in (2, 4)) / busy * 100.0
    assert read("head_share_pct.train", record) == pytest.approx(head)


def test_share_readers_return_none_where_the_source_is_absent(
        monkeypatch):
    record = record_from("trace_opt-6.7b.train.json.gz", 1)
    table, _ops = hand_table(record)
    # a program from before PR 26: no scope table, no dispatch records
    give_program(monkeypatch)
    for metric in SHARES + ("head_share_pct.train",
                            "attn_share_pct.train",
                            "loader_host_ms.train", "enqueue_ms.train",
                            "host_gc_ms.train"):
        assert read(metric, record) is None, metric
    # a rehearsal: no peaks, no trace
    give_program(monkeypatch, table=table, recent=[])
    rehearsal = dict(record, peaks=None, trace=None)
    for metric in [m["name"] for m in bench_metrics()]:
        assert read(metric, rehearsal) is None, metric
    # a program that never dispatched block_step
    give_program(monkeypatch, table=None, recent=[])
    assert read("fwd_share_pct.train", record) is None
    assert read("loader_host_ms.train", record) is None
    # a trace in which another program holds over 1% of the device
    give_program(monkeypatch, table=table)
    record["trace"]["programs"]["jit_other"] = [1.0, 1.0]
    assert read("fwd_share_pct.train", record) is None


def bench_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    names = [m["name"] for m in per_layer]
    first = names.index("fwd_share_pct.train")
    assert names[:first] == [
        "dispatch_ms.train", "train_mfu_pct", "attn_pallas_share_pct",
        "flash_roofline", "device_idle_pct.train", "hbm_peak_pct.train"]
    return per_layer[first:]


def test_new_metrics_are_listed_after_the_old_and_find_their_readers():
    new = bench_metrics()
    assert len(new) == 13
    for metric in new:
        assert metric["moves"] == "train_rate"
        assert R.find_reader(metric["name"]) is not None
        if metric["name"].endswith("_roofline"):
            assert metric["workloads"] == ["opt-6.7b.train"]
            assert metric["unit"] == "%"
        else:
            assert "workloads" not in metric


def test_dispatch_records_give_the_hosts_split(monkeypatch):
    recent = [{"program": "block_step", "serve_s": 9.0, "upload_s": 9.0,
               "enqueue_s": 9.0, "gc_s": 9.0}]       # a warm dispatch
    recent += [{"program": "infer_step", "serve_s": 5.0,
                "upload_s": 5.0, "enqueue_s": 5.0, "gc_s": 5.0}]
    recent += [{"program": "block_step", "serve_s": 0.001 * i,
                "upload_s": 0.002, "enqueue_s": 0.003,
                "gc_s": 0.004 if i == 2 else 0.0} for i in (1, 2, 3)]
    give_program(monkeypatch, recent=recent)
    record = record_from("trace_opt-6.7b.train.json.gz", 1)
    assert read("loader_host_ms.train", record) == pytest.approx(2.0)
    assert read("enqueue_ms.train", record) == pytest.approx(5.0)
    assert read("host_gc_ms.train", record) == pytest.approx(4.0)
    # a window of more dispatches than the program kept: no reading,
    # where a sum over what is left would undercount
    record["window"]["dispatches"] = 5
    assert read("host_gc_ms.train", record) is None
    assert read("loader_host_ms.train", record) is None


def test_kernel_rooflines_on_a_recording_with_kernel_names():
    """``trace_opt-6.7b.train.pr26.json.gz``: the first 4000 operations
    of a traced dispatch of this PR's program, whose flash kernels show
    as ``%flash_fwd.N``, ``%flash_dq.N``, ``%flash_dkv.N``."""
    record = record_from("trace_opt-6.7b.train.pr26.json.gz", 1)
    names = {op.split(" ", 1)[0].rsplit(".", 1)[0]
             for op in record["trace"]["kernel_seconds"]
             if 'custom_call_target="tpu_custom_call"' in op}
    assert names == {"%flash_fwd", "%flash_dq", "%flash_dkv"}
    # the recording is cut after 4000 operations, some ticks into its
    # dispatch: the calls it NEEDED are the dq events it holds (one a
    # block a tick; the forward shows twice as often, with the remat's)
    events = next(iter(T.load_planes(os.path.join(
        BENCH, "testdata", "trace_opt-6.7b.train.pr26.json.gz"))
        ["devices"].values()))
    calls = {kernel: sum(1 for e in events
                         if e[0].startswith("%" + kernel + "."))
             for kernel in ("flash_fwd", "flash_dq", "flash_dkv")}
    assert calls == {"flash_fwd": 25, "flash_dq": 12, "flash_dkv": 12}
    record["yardstick"]["flash_calls_per_dispatch"] = calls["flash_dq"]
    fwd = read("flash_fwd_roofline", record)
    dq = read("flash_dq_roofline", record)
    dkv = read("flash_dkv_roofline", record)
    assert 10.0 < fwd < 20.0 and 20.0 < dq < 35.0 and 15.0 < dkv < 30.0
    # the accepted metric still finds all of them, and lies between
    whole = read("flash_roofline", record)
    assert min(fwd, dq, dkv) < whole < max(fwd, dq, dkv)
    # no such kernel in the trace (PR 25's names): nothing to read
    old = record_from("trace_opt-6.7b.train.json.gz", 1)
    assert read("flash_fwd_roofline", old) is None
    assert read("flash_roofline", old) is not None
