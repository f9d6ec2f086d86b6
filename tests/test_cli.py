"""CLI platform layer tests (reference behavior: veles/__main__.py
Main + cmdline.py flag aggregation — the `velescli` capability)."""

import json
import os
import subprocess
import sys

import numpy
import pytest

from veles_tpu.__main__ import Main, import_workflow_module, \
    apply_config_sources
from veles_tpu.config import root
import veles_tpu.prng as prng

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MNIST = os.path.join(REPO, "veles_tpu", "znicz", "samples", "mnist.py")


def run_main(argv):
    prng.reset()
    return Main(argv).run()


def test_help_flags_aggregate():
    from veles_tpu.cmdline import init_argparser
    parser = init_argparser(prog="veles_tpu")
    text = parser.format_help()
    for flag in ("--result-file", "--snapshot", "--optimize",
                 "--ensemble-train", "--random-seed", "--dry-run"):
        assert flag in text


def test_import_workflow_module_by_path():
    mod = import_workflow_module(MNIST)
    assert hasattr(mod, "run")
    assert hasattr(mod, "MnistWorkflow")


def test_config_overrides_and_files(tmp_path):
    cfg = tmp_path / "cfg.py"
    cfg.write_text("root.cli_test.alpha = 42\n")
    apply_config_sources([str(cfg), "root.cli_test.beta='x'"])
    assert root.cli_test.get("alpha") == 42
    assert root.cli_test.get("beta") == "x"
    root.cli_test.reset()


def test_bad_config_source_raises():
    from veles_tpu.error import Bug
    with pytest.raises(Bug):
        apply_config_sources(["no_such_file.py"])


def test_train_writes_result_file(tmp_path):
    result = tmp_path / "res.json"
    rc = run_main([MNIST, "root.mnist.max_epochs=2",
                   "--result-file", str(result),
                   "--random-seed", "1234", "-v", "warning"])
    assert rc == 0
    data = json.loads(result.read_text())
    assert data["class"] == "MnistWorkflow"
    assert data["results"]["epochs"] == 2
    assert data["results"]["min_validation_err"] < 0.5
    assert "EvaluationFitness" in data["results"]
    root.mnist.reset()


def test_dry_run_init_skips_training(tmp_path):
    result = tmp_path / "res.json"
    graph = tmp_path / "graph.dot"
    rc = run_main([MNIST, "root.mnist.max_epochs=2",
                   "--dry-run", "init", "--result-file", str(result),
                   "--workflow-graph", str(graph), "-v", "warning"])
    assert rc == 0
    assert not result.exists()
    text = graph.read_text()
    assert text.startswith("digraph") and "fc0" in text
    root.mnist.reset()


def test_snapshot_resume_continues(tmp_path):
    """-s resume + --max-epochs raise (reference: __main__.py:532-582)."""
    import pickle

    snap = tmp_path / "wf.pickle"
    m = Main([MNIST, "root.mnist.max_epochs=2", "-v", "warning",
              "--random-seed", "5"])
    m.parse()
    m.seed_random()
    apply_config_sources(m.args.config)
    m.module = import_workflow_module(m.args.workflow)
    m.run_regular()
    with open(snap, "wb") as fout:
        pickle.dump(m.workflow, fout)
    epochs_before = m.workflow.gather_results()["epochs"]
    assert epochs_before == 2
    root.mnist.reset()

    rc = run_main([MNIST, "-s", str(snap), "--max-epochs", "4",
                   "--result-file", str(tmp_path / "res2.json"),
                   "-v", "warning"])
    assert rc == 0
    data = json.loads((tmp_path / "res2.json").read_text())
    assert data["results"]["epochs"] == 4


def test_python_dash_m_entry(tmp_path):
    """`python -m veles_tpu` is a real console entry point."""
    result = tmp_path / "res.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "veles_tpu", MNIST,
         "root.mnist.max_epochs=1", "--result-file", str(result),
         "-v", "warning"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(result.read_text())["results"]["epochs"] == 1


def test_frontend_flag_generates_wizard(tmp_path):
    """`python -m veles_tpu --frontend FILE` emits the wizard and
    exits (reference: velescli --frontend)."""
    out = tmp_path / "wiz.html"
    rc = Main(["--frontend", str(out)]).run()
    assert rc == 0
    page = out.read_text()
    assert "--optimize" in page and "compose()" in page


def test_run_flags_stray_numpy_random(tmp_path):
    """A workflow unit calling global numpy.random during a CLI run
    fails loudly instead of silently breaking reproducibility
    (reference: prng/random_generator.py:49-61)."""
    wf = tmp_path / "stray_random.py"
    wf.write_text('''
import numpy
from veles_tpu.units import Unit, IUnit
from veles_tpu.workflow import Workflow


class StrayRandomUnit(Unit):
    def run(self):
        numpy.random.rand(3)  # the banned global draw


class StrayWorkflow(Workflow):
    def __init__(self, workflow, **kwargs):
        super(StrayWorkflow, self).__init__(workflow, **kwargs)
        self.stray = StrayRandomUnit(self)
        self.stray.link_from(self.start_point)
        self.end_point.link_from(self.stray)


def run(load, main):
    load(StrayWorkflow)
    main()
''')
    rc = run_main([str(wf), "-v", "error"])
    assert rc != 0  # the guard turned the stray draw into a failure
    # The guard must not leak past the run.
    numpy.random.rand(1)
    # Causality: the same workflow passes with the guard disabled.
    rc = run_main([str(wf), "-v", "error",
                   "root.common.engine.poison_numpy_random=False"])
    assert rc == 0
    from veles_tpu.config import root
    root.common.engine.poison_numpy_random = True
