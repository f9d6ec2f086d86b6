"""The readers of the program's own account of its start-up and of its
dispatches' pace (PR 37, ``layer_metrics/startup.py``): each of the
seven on a hand-made list of dispatch records, compile records and
set-up spans gives the number worked out by hand, and None — never an
exception — where the program keeps no such record.  Run by hand:
``pytest benchmark/tests``."""

import pytest

from benchmark import run as R
from benchmark.layer_metrics import scoped

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
         "hbm_bytes": 16e9}
METRICS = ("setup_init_s", "setup_trace_s", "setup_compile_s",
           "setup_step_cache_misses", "setup_dispatch_s",
           "setup_outside_program_pct", "dispatch_late_ms.train")


def dispatch(t0, t1, lower_s=0.0, compile_s=0.0, build_s=0.0,
             program="block_step"):
    return {"program": program, "t0": t0, "t1": t1, "lower_s": lower_s,
            "compile_s": compile_s, "build_s": build_s}


def compiled(program, t0, t1, trace_s, lower_s, compile_s, cache,
             step=True):
    return {"program": program, "step": step, "t0": t0, "t1": t1,
            "trace_s": trace_s, "lower_s": lower_s,
            "compile_s": compile_s, "cache": cache}


def span(name, t0, t1):
    return {"name": name, "t0": t0, "t1": t1, "seconds": t1 - t0}


#: A start as the chip shows one: initialize 2.5 s; the first dispatch
#: (12 -> 46) builds the step (0.25), lowers it inside step.lower (21 +
#: 6 and half a second of cost analysis), loads it from the cache (5.5,
#: beside a helper's 0.5) and runs 1.25 s; a remainder block lowered
#: twice and MISSED; two warm dispatches; four in the window, the third
#: 0.5 s late; afterwards the scope table's compile and a single tick.
RECENT = [
    dispatch(12.0, 46.0, lower_s=27.5, compile_s=6.0, build_s=0.25),
    dispatch(80.0, 86.0, lower_s=1.5, compile_s=2.5),
    dispatch(1.0, 2.0, program="train_step"),
    dispatch(86.0, 88.5),
    dispatch(88.5, 91.0),
    dispatch(100.0, 102.5),
    dispatch(102.5, 105.0),
    dispatch(105.25, 108.0),
    dispatch(108.0, 110.5),
    dispatch(150.0, 151.0, program="train_step"),
]
COMPILES = [
    compiled("_threefry_seed", 5.0, 6.0, 0.25, 0.25, 0.5, None, False),
    compiled("block_step", 13.0, 45.0, 21.0, 6.0, 5.5, "hit"),
    compiled("norms", 44.0, 44.5, 0.0, 0.0, 0.5, "hit", False),
    compiled("block_step", 80.5, 84.5, 1.0, 1.5, 1.5, "miss"),
    compiled("block_step", 120.0, 140.0, 0.0, 7.0, 13.0, "miss"),
]
SPANS = [span("launcher.initialize", 8.0, 10.5),
         span("step.build", 12.25, 12.5),
         span("launcher.initialize", 141.0, 149.0)]
BY_HAND = {
    "setup_init_s": 2.5 + 0.25,
    "setup_trace_s": 21.0 + 6.0 + 1.0 + 1.5,
    "setup_compile_s": 5.5 + 1.5,
    "setup_step_cache_misses": 1,
    # (34 - 6 - 27.5 - 0.25) + (6 - 2.5 - 1.5) + 2.5 + 2.5
    "setup_dispatch_s": 0.25 + 2.0 + 5.0,
    # 100 x (1 - (2.75 + 29.5 + 7 + 7.25) / 93)
    "setup_outside_program_pct": 50.0,
    # periods 2.5, 3.0, 2.5: the longest less the median
    "dispatch_late_ms.train": 500.0,
}


def record(dispatches=4, peaks=PEAKS):
    return {"peaks": peaks, "end_to_end": {"setup_s": 93.0,
                                           "train_rate": 1.0},
            "window": {"dispatches": dispatches, "seconds": 10.5,
                       "items": 4}}


def give_program(monkeypatch, **sources):
    """Stands in for the program's three sources."""
    def program(module, attribute):
        found = sources.get(attribute)
        return None if found is None else (lambda: found)
    monkeypatch.setattr(scoped, "_program", program)


def read(metric, rec):
    return R.find_reader(metric).read(rec, metric)


@pytest.mark.parametrize("metric", METRICS)
def test_each_reader_gives_the_number_worked_out_by_hand(monkeypatch,
                                                         metric):
    give_program(monkeypatch, recent=RECENT, compiles=COMPILES,
                 spans=SPANS)
    assert read(metric, record()) == pytest.approx(BY_HAND[metric])


def test_the_parts_and_the_outside_share_add_to_setup_s(monkeypatch):
    give_program(monkeypatch, recent=RECENT, compiles=COMPILES,
                 spans=SPANS)
    rec = record()
    inside = sum(read(m, rec) for m in METRICS[:3] + METRICS[4:5])
    outside = read("setup_outside_program_pct", rec) / 100.0
    assert inside + outside * 93.0 == pytest.approx(93.0)


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("missing", [
    "a parent from before PR 37: no compile records, no clock",
    "a parent from before PR 26: no dispatch records",
    "a rehearsal: no peaks",
    "a window longer than the records kept"])
def test_each_reader_returns_none_without_the_record(monkeypatch,
                                                     metric, missing):
    rec = record()
    if missing.startswith("a parent from before PR 37"):
        old = [{k: v for k, v in r.items()
                if k in ("program", "serve_s")} for r in RECENT]
        give_program(monkeypatch, recent=old)
    elif missing.startswith("a parent from before PR 26"):
        give_program(monkeypatch)
    elif missing.startswith("a rehearsal"):
        give_program(monkeypatch, recent=RECENT, compiles=COMPILES,
                     spans=SPANS)
        rec = record(peaks=None)
    else:
        give_program(monkeypatch, recent=RECENT, compiles=COMPILES,
                     spans=SPANS)
        rec = record(dispatches=9)
    assert read(metric, rec) is None


def test_the_real_program_hands_the_readers_what_they_read():
    """The field names the readers use are the program's."""
    from veles_tpu.observability import attribution, startup
    attribution.reset()
    with startup.span("launcher.initialize"):
        pass
    with attribution.dispatch(program="block_step", ticks=1) as step:
        with step.enqueue():
            pass
        step.wait(None)
    (row,) = attribution.recent()
    assert set(RECENT[0]) <= set(row)
    (kept,) = startup.spans()
    assert set(SPANS[0]) == set(kept)
    startup._fold("backend", "block_step", 1.0, 2.0, 1.0, "miss", 0.0,
                  None)
    (made,) = startup.compiles()
    assert set(COMPILES[0]) <= set(made)
    attribution.reset()


def test_a_window_of_one_dispatch_has_no_period(monkeypatch):
    give_program(monkeypatch, recent=RECENT[:6], compiles=COMPILES,
                 spans=SPANS)
    rec = record(dispatches=1)
    assert read("dispatch_late_ms.train", rec) is None
    assert read("setup_init_s", rec) == pytest.approx(2.75)
