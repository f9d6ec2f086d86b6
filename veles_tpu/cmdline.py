"""Command-line argument aggregation.

Capability parity with the reference CLI base (reference:
veles/cmdline.py — ``CommandLineArgumentsRegistry:61``,
``CommandLineBase:86``): any class built with the
:class:`CommandLineArgumentsRegistry` metaclass may declare a static
``init_parser(parser)`` hook; :func:`init_argparser` folds every
registered hook into one argparse tree, so subsystems (launcher,
loaders, genetics, graphics, …) contribute their own flags without the
entry point knowing about them.

TPU-era notes: no Twisted/manhole/daemon flags; backend selection is
cpu/tpu/auto (XLA platforms) instead of OpenCL/CUDA device indices.
"""

import argparse


class CommandLineArgumentsRegistry(type):
    """Metaclass accumulating per-class ``init_parser`` hooks
    (reference: cmdline.py:61)."""

    classes = []

    def __init__(cls, name, bases, clsdict):
        super(CommandLineArgumentsRegistry, cls).__init__(
            name, bases, clsdict)
        init_parser = clsdict.get("init_parser")
        if init_parser is None:
            return
        if not isinstance(init_parser, staticmethod):
            raise TypeError(
                "%s.init_parser must be a staticmethod (it is collected "
                "by CommandLineArgumentsRegistry before instantiation)"
                % name)
        CommandLineArgumentsRegistry.classes.append(cls)


class SortedHelpFormatter(argparse.RawDescriptionHelpFormatter):
    """Alphabetical option listing (reference: cmdline.py:118-122)."""

    def add_arguments(self, actions):
        super(SortedHelpFormatter, self).add_arguments(
            sorted(actions, key=lambda a: a.dest))


class CommandLineBase(object):
    """Holds the base velescli option set (reference: cmdline.py:86).

    Subsystem flags arrive via the registry; these are the core ones
    every run understands.
    """

    DRY_RUN_CHOICES = ("load", "init", "exec", "no")
    LOG_LEVELS = ("debug", "info", "warning", "error")

    @staticmethod
    def init_parser(parser):
        parser.add_argument(
            "workflow", nargs="?", default="",
            help="path to the workflow module (a .py file defining "
                 "run(load, main)) or a dotted module name")
        parser.add_argument(
            "config", nargs="*", default=[],
            help="config file(s) executed with `root` in scope, and/or "
                 "root.path=value override assignments")
        parser.add_argument(
            "-c", "--config-list", nargs="*", default=[], metavar="FILE",
            help="additional config files (explicit form)")
        parser.add_argument(
            "-s", "--snapshot", default="",
            help="resume from a snapshot file (or a _current.lnk "
                 "pointer)")
        parser.add_argument(
            "--chaos", default="", metavar="PLAN",
            help="deterministic fault-injection plan, e.g. "
                 "'net.drop@job:7,worker.kill@job:12,seed:42' — "
                 "replaces --slave-death-probability with a seeded, "
                 "replayable failure schedule (docs/resilience.md)")
        parser.add_argument(
            "-l", "--listen-address", default="", metavar="HOST:PORT",
            help="run as the distributed coordinator (master), "
                 "listening on HOST:PORT")
        parser.add_argument(
            "--blacklist-cooldown", type=float, default=None,
            metavar="SEC",
            help="blacklist parole: a worker machine blacklisted by "
                 "the adaptive job-timeout watchdog is re-admitted "
                 "on PROBATION (one in-flight job until it completes "
                 "clean) after this many seconds instead of being "
                 "ejected for good (default 60; 0 = immediate "
                 "probation)")
        parser.add_argument(
            "-m", "--master-address", default="", metavar="HOST:PORT",
            help="run as a worker (slave) of the coordinator at "
                 "HOST:PORT")
        parser.add_argument(
            "--nodes", default="", metavar="HOST[,HOST...]",
            help="with -l: spawn workers on these hosts over ssh "
                 "('local' spawns subprocesses on this machine); "
                 "dropped workers respawn the same way")
        parser.add_argument(
            "--jax-coordinator", default="", metavar="HOST:PORT",
            help="multi-controller SPMD: jax.distributed coordinator "
                 "address (every process runs the same program over "
                 "the combined device mesh)")
        parser.add_argument(
            "--jax-num-processes", type=int, default=0, metavar="N",
            help="multi-controller SPMD: total process count")
        parser.add_argument(
            "--jax-process-id", type=int, default=0, metavar="I",
            help="multi-controller SPMD: this process's index")
        parser.add_argument(
            "-r", "--random-seed", default="", metavar="SPEC",
            help="seed spec: an integer, or file:count:dtype "
                 "(e.g. /dev/urandom:16:uint32)")
        parser.add_argument(
            "-a", "--backend", default="",
            help="accelerator backend: tpu, cpu or auto")
        parser.add_argument(
            "--result-file", default="", metavar="FILE",
            help="write run metrics JSON here "
                 "(IResultProvider aggregation)")
        parser.add_argument(
            "--dry-run", default="no",
            choices=CommandLineBase.DRY_RUN_CHOICES,
            help="stop after the given stage: load = construct only, "
                 "init = initialize only, exec = run but skip "
                 "result/report output")
        parser.add_argument(
            "-v", "--verbosity", default="info",
            choices=CommandLineBase.LOG_LEVELS, help="log level")
        parser.add_argument(
            "--workflow-graph", default="", metavar="FILE",
            help="write the control-flow graph (Graphviz DOT) here")
        parser.add_argument(
            "--dump-config", action="store_true",
            help="print the effective config tree before running")
        parser.add_argument(
            "--max-epochs", default="", metavar="N",
            help="override the workflow's stop epoch "
                 "(root.common.max_epochs)")
        parser.add_argument(
            "--optimize", default="", metavar="SIZE[:GENERATIONS]",
            help="genetic hyperparameter search over Tune() config "
                 "leaves with the given population size")
        parser.add_argument(
            "--ensemble-train", default="", metavar="N[:RATIO]",
            help="train an ensemble of N instances, each on RATIO of "
                 "the train set (default 1.0)")
        parser.add_argument(
            "--ensemble-test", default="", metavar="FILE",
            help="evaluate the ensemble described by FILE (written by "
                 "--ensemble-train)")
        parser.add_argument(
            "--profile", default="", metavar="DIR",
            help="capture a jax.profiler trace of the run into DIR")
        parser.add_argument(
            "--frontend", nargs="?", const="frontend.html",
            default="", metavar="FILE",
            help="generate the HTML launch wizard (unit registry + "
                 "full flag tree) and exit")
        return parser


#: Modules that contribute flags via a module-level
#: ``init_parser(parser)`` — imported on demand so subsystems stay
#: lazily loadable yet their flags always appear (the reference's
#: per-class aggregation relied on import side effects instead,
#: cmdline.py:61).
CONTRIBUTING_MODULES = (
    "veles_tpu.client",
    "veles_tpu.guardian",
    "veles_tpu.loader.base",
    "veles_tpu.network_common",
    "veles_tpu.observability",
    "veles_tpu.ops.attention",
    "veles_tpu.ops.pipeline",
    "veles_tpu.population",
    "veles_tpu.restful",
    "veles_tpu.snapshotter",
    "veles_tpu.znicz.optimizers",
)


def init_argparser(**kwargs):
    """Builds the aggregated parser: base options + every registered
    class's ``init_parser`` + the contributing modules' hooks
    (reference: cmdline.py's per-class argparse merge)."""
    import importlib
    kwargs.setdefault("formatter_class", SortedHelpFormatter)
    kwargs.setdefault(
        "description",
        "veles_tpu — TPU-native distributed dataflow ML platform")
    parser = argparse.ArgumentParser(**kwargs)
    CommandLineBase.init_parser(parser)
    for name in CONTRIBUTING_MODULES:
        module = importlib.import_module(name)
        hook = getattr(module, "init_parser", None)
        if hook is not None:
            try:
                hook(parser)
            except argparse.ArgumentError:
                pass
    seen = {CommandLineBase}
    for cls in CommandLineArgumentsRegistry.classes:
        if cls in seen:
            continue
        seen.add(cls)
        try:
            cls.init_parser(parser)
        except argparse.ArgumentError:
            # Two subsystems claiming the same flag is a bug, but the
            # CLI should stay usable: first registration wins.
            pass
    return parser
