"""Optimization run modes: standalone, coordinator, worker.

Capability parity with the reference optimization workflow (reference:
veles/genetics/optimization_workflow.py — ``GeneticsOptimizer:70``,
``OptimizationWorkflow:290``, subprocess evaluation ``:260``,
master–slave chromosome distribution ``:174-214``; CLI dispatch
``veles/__main__.py:327-338`` ``--optimize size[:generations]``):

* **standalone** — evaluate chromosomes in-process (or via a
  ``python -m veles_tpu`` subprocess), evolve, repeat.
* **coordinator** (``-l``) — an :class:`OptimizationWorkflow` rides the
  existing Server job protocol: jobs are chromosomes, updates are
  fitnesses, dropped workers requeue their chromosomes.
* **worker** (``-m``) — the same workflow object evaluates chromosomes
  locally and reports fitness.

Every evaluation run is seeded identically (``--random-seed`` or 1234),
so chromosomes differ only in their genes — the same fairness guarantee
the reference got by passing the master's seed to each subprocess.
"""

import json
import os
import subprocess
import sys
import tempfile

from ..config import root
from ..error import Bug
from ..harness import FITNESS_KEY, run_workflow_module, seed_to_int
from ..json_encoders import dump_json
from ..launcher import Launcher
from ..logger import Logger
from ..workflow import Workflow
from .core import Population, applied_genes, collect_tunes, _concrete


def evaluate_chromosome(module, tunes, genes, seed,
                        fitness_key=FITNESS_KEY):
    """Runs the model module once with the chromosome's genes written
    into the config tree; returns the fitness scalar.

    The genes apply as a SCOPE (snapshot + restore of the touched
    leaves): the old destructive ``apply_genes`` call leaked one
    chromosome's overrides into every later in-process evaluation —
    a chromosome whose gene happened to match a sibling's stale value
    would read as identical fitness."""
    with applied_genes(root, tunes, genes):
        wf = run_workflow_module(module, seed=seed)
        results = wf.gather_results()
    if fitness_key not in results:
        raise Bug("model results carry no %r — the workflow needs an "
                  "IResultProvider exposing a fitness metric (the "
                  "Decision unit provides it)" % fitness_key)
    return float(results[fitness_key])


def evaluate_chromosome_subprocess(module_path, tunes, genes, seed,
                                   fitness_key=FITNESS_KEY,
                                   extra_argv=()):
    """Same contract via a ``python -m veles_tpu`` child process
    (reference: optimization_workflow.py:260 ``_exec`` — full issue
    isolation at the cost of per-run startup).  The child is a JAX
    process of its own: a parent that has touched JAX holds the chip
    and the child then fails or hangs (one process per chip), so on
    a TPU the parent must stay off JAX, or the in-process and vmapped
    evaluators are the ones to use."""
    overrides = ["root.%s=%r" % (path, _concrete(tune, gene))
                 for (path, tune), gene in zip(tunes, genes)]
    with tempfile.NamedTemporaryFile(
            mode="r", suffix=".json", delete=False) as tmp:
        result_path = tmp.name
    try:
        argv = [sys.executable, "-m", "veles_tpu", module_path] + \
            overrides + ["--result-file", result_path,
                         "--random-seed", str(seed),
                         "-v", "warning"] + list(extra_argv)
        proc = subprocess.run(argv, capture_output=True, text=True)
        if proc.returncode != 0:
            raise Bug("evaluation subprocess failed (rc=%d): %s" %
                      (proc.returncode, proc.stderr[-1000:]))
        with open(result_path) as fin:
            results = json.load(fin)["results"]
        return float(results[fitness_key])
    finally:
        try:
            os.unlink(result_path)
        except OSError:
            pass


class OptimizationWorkflow(Workflow):
    """The GA as a Server-drivable workflow (reference:
    optimization_workflow.py:290): jobs = chromosomes, updates =
    fitnesses.  The same object serves both sides — the coordinator
    holds the live Population; workers only evaluate."""

    def __init__(self, launcher, module, population=None, seed=1234,
                 **kwargs):
        super(OptimizationWorkflow, self).__init__(launcher, **kwargs)
        self.module = module
        self.population = population
        self.eval_seed = seed
        self.negotiates_on_connect = False

    # The Server drives these five hooks -------------------------------

    def should_stop_serving(self):
        return self.population is not None and \
            self.population.complete

    def generate_data_for_slave(self, slave=None):
        got = self.population.acquire(owner=slave)
        if got is None:
            return None
        index, genes = got
        return {"index": index, "genes": genes}

    def generate_initial_data_for_slave(self, slave=None):
        return None

    def apply_data_from_slave(self, data, slave=None):
        self.population.record(data["index"], data["fitness"])

    def drop_slave(self, slave=None):
        self.population.release(slave)

    def do_job(self, data, update, callback):
        """Worker side: evaluate one chromosome in-process."""
        fitness = evaluate_chromosome(
            self.module, self._tunes_cached, data["genes"],
            self.eval_seed)
        callback({"index": data["index"], "fitness": fitness})

    @property
    def _tunes_cached(self):
        # After the first evaluation the Tune leaves were replaced by
        # concrete values, so capture the layout once.
        if not hasattr(self, "_tunes_"):
            self._tunes_ = collect_tunes(root)
        return self._tunes_

    @property
    def checksum(self):
        """Coordinator and workers must optimize the same model
        module, not merely share this file."""
        base = super(OptimizationWorkflow, self).checksum
        mod = self.module
        name = "none" if mod is None else os.path.basename(
            getattr(mod, "__file__", None) or
            getattr(mod, "__name__", "module"))
        return base + "_" + name


class GeneticsOptimizer(Logger):
    """Drives an optimization run in whatever mode the CLI selected
    (reference: __main__.py:710-728 genetics dispatch)."""

    def __init__(self, main, size, generations=None, **kwargs):
        super(GeneticsOptimizer, self).__init__()
        self.main = main
        self.module = main.module
        args = main.args
        self.listen_address = args.listen_address
        self.master_address = args.master_address
        self.result_file = args.result_file
        self.seed = seed_to_int(args.random_seed)
        self.subprocess_mode = kwargs.get("subprocess_mode", bool(
            root.common.genetics.get("subprocess", False)))
        self.tunes = collect_tunes(root)
        self.population = None
        if not self.master_address:
            self.population = Population(
                self.tunes, size, generations,
                seed=self.seed,
                **{k: v for k, v in kwargs.items()
                   if k in ("elite_ratio", "mutation_rate",
                            "blend_alpha", "stagnation")})

    def run(self):
        if self.master_address:
            self._run_worker()
        elif self.listen_address:
            self._run_coordinator()
        else:
            self._run_standalone()
        if self.population is not None:
            return self.population.best
        return None

    # -- modes -------------------------------------------------------------

    def _run_standalone(self):
        pop = self.population
        evaluator = self._make_vmap_evaluator()
        while not pop.complete:
            if evaluator is not None:
                batch = []
                while True:
                    got = pop.acquire()
                    if got is None:
                        break
                    batch.append(got)
                if not batch:
                    raise Bug("population stalled: nothing pending "
                              "yet generation incomplete")
                fitnesses = evaluator.evaluate(
                    [genes for _, genes in batch])
                for (index, _), fitness in zip(batch, fitnesses):
                    self.debug("chromosome %d -> fitness %.6f",
                               index, fitness)
                    pop.record(index, float(fitness))
                continue
            got = pop.acquire()
            if got is None:
                raise Bug("population stalled: nothing pending yet "
                          "generation incomplete")
            index, genes = got
            if self.subprocess_mode:
                fitness = evaluate_chromosome_subprocess(
                    self.module.__file__, self.tunes, genes,
                    self.seed)
            else:
                fitness = evaluate_chromosome(
                    self.module, self.tunes, genes, self.seed)
            self.debug("chromosome %d -> fitness %.6f", index,
                       fitness)
            pop.record(index, fitness)
        self._finish()

    def _make_vmap_evaluator(self):
        """The vmapped generation evaluator when every tune is a GD
        hyperparameter (SURVEY §7 milestone 8); None → per-chromosome
        path."""
        if self.subprocess_mode or not bool(
                root.common.genetics.get("vmap", True)):
            return None
        from .vmap_eval import PopulationEvaluator, hyper_names
        if hyper_names(self.tunes) is None:
            return None
        try:
            return PopulationEvaluator(self.module, self.tunes,
                                       self.seed)
        except Bug as e:
            self.warning("vmapped population evaluation unavailable "
                         "(%s); using per-chromosome runs", e)
            return None

    def _run_coordinator(self):
        from ..server import Server
        launcher = Launcher()
        wf = OptimizationWorkflow(launcher, self.module,
                                  population=self.population,
                                  seed=self.seed)
        server = Server(self.listen_address, wf)
        server.wait()
        self._finish()

    def _run_worker(self):
        from ..client import Client
        launcher = Launcher()
        wf = OptimizationWorkflow(launcher, self.module,
                                  seed=self.seed)
        client = Client(self.master_address, wf)
        client.run()

    def _finish(self):
        best = self.population.best
        if best is None:
            self.warning("optimization produced no evaluated "
                         "chromosome")
            return
        overrides = best.overrides(self.tunes)
        self.info("optimization done after %d generation(s): best "
                  "fitness %.6f with %s",
                  self.population.generation + 1, best.fitness,
                  ", ".join("%s=%s" % kv for kv in overrides.items()))
        if self.result_file:
            dump_json({
                "mode": "genetics",
                "generations": self.population.generation + 1,
                "population": self.population.size,
                "best_fitness": best.fitness,
                "best_config": {"root.%s" % k: v
                                for k, v in overrides.items()},
                "history": self.population.history,
            }, self.result_file)
            self.info("optimization results -> %s", self.result_file)
