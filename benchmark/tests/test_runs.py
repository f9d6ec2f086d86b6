"""Whole runs at the rehearsal widths on the CPU: every cell of
``BENCHMARK.json`` end to end with ``--rehearse`` (device metrics
``null``), the refusal to run without a TPU, the control (the reference
in the precision below, in the program's place, reads worse than the
program), and each fault a cell can have planted UNDER the harness, which
must then report ``correct`` false."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import run as R

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
#: Every cell that has a file, in ``BENCHMARK.json`` or not yet (a cell
#: that is built but not proved on the chip is still rehearsed here).
CELLS = sorted(f[:-5] for f in os.listdir(
    os.path.join(ROOT, "benchmark", "workloads")) if f.endswith(".json"))
LISTED = [w["name"] for w in BENCH["workloads"]]
TRAIN = [c for c in CELLS if c.endswith(".train")]


def last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def rehearse(capsys, cell, trace=0, seconds=1.5, seed=3000000019):
    assert R.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace),
                   "--rehearse"]) == 0
    return last_line(capsys)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_end_to_end(capsys, cell, trace):
    result = rehearse(capsys, cell, trace)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["memory_peak_bytes"] is None
    group = "per_layer" if trace else "end_to_end"
    sources = {m["name"]: m["source"] for m in BENCH[group]}
    if cell in LISTED:
        assert result["metrics"], "no metric reported"
    for name, metric in result["metrics"].items():
        # a count made by the program may show; nothing timed or traced
        if sources[name] != "program_counter":
            assert metric["value"] is None, name
    assert list(result)[-1] == "compared"
    assert all(set(c) >= {"name", "value", "limit"}
               for c in result["compared"])


def test_refuses_to_run_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", LISTED[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=600)
    assert done.returncode != 0
    assert '"correct"' not in done.stdout
    assert "not on a TPU" in done.stderr


# -- faults planted under the harness ---------------------------------------

@pytest.mark.parametrize("cell", TRAIN)
def test_state_left_unchanged_is_not_correct(capsys, monkeypatch, cell):
    from veles_tpu.accelerated_units import StepCompiler
    real = StepCompiler.execute_block

    def unchanged(self, blocks, training, key=None, hypers=None):
        import jax
        if not self._compiled:
            self.compile()
        params = {n: jax.numpy.array(v.devmem)
                  for n, v in self._param_vecs.items()}
        states = {n: jax.numpy.array(v.devmem)
                  for n, v in self._state_vecs.items()
                  if "velocity" in n}
        assert states, sorted(self._state_vecs)
        out = real(self, blocks, training, key=key, hypers=hypers)
        for n, v in self._param_vecs.items():
            v.devmem = params[n]
        for n, value in states.items():
            self._state_vecs[n].devmem = value
        return out

    monkeypatch.setattr(StepCompiler, "execute_block", unchanged)
    result = rehearse(capsys, cell)
    assert result["correct"] is False
    gaps = {c["name"]: c for c in result["compared"]}
    # the loss is still right; the state that did not move reads 1
    assert gaps["loss_gap"]["ok"]
    assert gaps["change_gap"]["value"] == pytest.approx(1.0)
    assert gaps["velocity_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("cell", TRAIN)
def test_half_of_the_batch_left_out_is_not_correct(capsys, monkeypatch,
                                                   cell):
    from veles_tpu.loader.base import Loader
    real = Loader.serve_block

    def half(self, max_ticks):
        blocks = real(self, max_ticks)
        mask = blocks[str(id(self.minibatch_mask))]
        mask[:, mask.shape[1] // 2:] = 0.0   # the mean is over the rest
        return blocks

    monkeypatch.setattr(Loader, "serve_block", half)
    result = rehearse(capsys, cell)
    assert result["correct"] is False
    bad = [c["name"] for c in result["compared"] if not c["ok"]]
    assert set(bad) & {"velocity_gap", "change_gap", "loss_gap"}


# -- the control -------------------------------------------------------------

def test_control_comes_out_not_correct_train(capsys):
    """The reference in the program's place, with fp8 operands where
    the configuration states bfloat16, goes through the very checks a
    run goes through and comes out not correct on every seed, by the
    number that is of first order in rounding; the program comes out
    correct on every seed; the planted fault reads ten times the
    program.  At the rehearsal's limits: those in the workload files
    come from the chip, at the cell's own size (PERF.md)."""
    from benchmark import control
    assert control.main(["--workload", TRAIN[0], "--seeds",
                         "21,22,23,24,25,26", "--control-seeds", "6",
                         "--rehearse"]) == 0
    rows = [json.loads(line) for line in
            capsys.readouterr().out.strip().splitlines()]
    rows = [r for r in rows if r.get("phase") == "readings"]
    assert len(rows) == 6
    for r in rows:
        assert r["correct"] == {"program": True, "fp8_e4m3": False,
                                "half_batch": False}, r
        assert r["fp8_e4m3"]["direction_gap"][2] is False
    lower = max(r["program"]["direction_gap"][0] for r in rows)
    upper = min(r["fp8_e4m3"]["direction_gap"][0] for r in rows)
    # (a 64-wide model rounds coarsely: the chip's readings at the
    # cells' widths stand further apart)
    assert upper >= 2 * lower, (upper, lower)
    lower = max(r["program"]["velocity_gap"][0] for r in rows)
    fault = min(r["half_batch"]["velocity_gap"][0] for r in rows)
    assert fault >= 10 * lower, (fault, lower)


def test_step_arguments_keep_one_order(capsys):
    """The loader's Vectors are handed out by ascending address, so the
    fused step's id-keyed arguments sort alike in every process and the
    persistent cache holds one block program, not one of twelve."""
    import argparse
    from benchmark.drivers import train_block
    args = argparse.Namespace(workload=TRAIN[0], seed=5, seconds=1.0,
                              trace=0, rehearse=True)
    ctx, _driver, _device = R.prepare(args)
    _sz, _traffic, trainer = train_block.build(ctx)
    trainer.dispatch()
    trainer.wait()
    compiler = trainer.wf.compiler
    for vectors in (compiler.batch_vectors, compiler.const_vectors):
        keys = [str(id(v)) for v in vectors]
        assert len(keys) >= 2 and keys == sorted(keys)
    trainer.close()
    capsys.readouterr()
