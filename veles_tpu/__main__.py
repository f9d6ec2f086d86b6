"""velescli — the platform entry point.

Capability parity with the reference entry point (reference:
veles/__main__.py — ``Main:129``, module loading ``_load_model:389``,
config application ``:419,467``, seeding ``_seed_random:476``, snapshot
resume ``_load_workflow:532``, mode dispatch ``_run_core:710``, results
``run:814``): loads a workflow module (a ``.py`` defining
``run(load, main)``), layers config files and ``root.x=y`` overrides,
resumes snapshots, seeds the deterministic PRNGs, dispatches regular /
genetics / ensemble modes, and writes the ``--result-file`` metrics
JSON.

Usage::

    python -m veles_tpu path/to/workflow.py [config.py ...] \
        [root.x=y ...] [options]

TPU-era notes: no Twisted reactor, no daemonization, no web-frontend
wizard process handling here — the launcher owns lifecycle; the
frontend generator lives in ``veles_tpu.scripts.generate_frontend``.
"""

import importlib
import importlib.util
import logging
import os
import sys
import time

from .backends import device_entry, enable_compilation_cache
from .cmdline import CommandLineBase, init_argparser
from .config import root
from .error import Bug
from .json_encoders import dump_json
from .launcher import Launcher
from .logger import Logger
from .snapshotter import SnapshotterToFile
from . import prng


def import_workflow_module(spec):
    """Imports a workflow module from a ``.py`` path or a dotted name
    (reference: __main__.py:389 ``_load_model``).

    Path form: if the file sits inside a package (``__init__.py``
    chain), its real dotted name is imported so relative imports work;
    a bare file is exec'd under a synthetic module name.
    """
    if not spec.endswith(".py"):
        return importlib.import_module(spec)
    path = os.path.abspath(spec)
    if not os.path.isfile(path):
        raise FileNotFoundError("workflow module not found: %s" % spec)
    # Walk up while __init__.py exists to recover the package name.
    parts = [os.path.splitext(os.path.basename(path))[0]]
    parent = os.path.dirname(path)
    while os.path.isfile(os.path.join(parent, "__init__.py")):
        parts.insert(0, os.path.basename(parent))
        parent = os.path.dirname(parent)
    if len(parts) > 1:
        if parent not in sys.path:
            sys.path.insert(0, parent)
        return importlib.import_module(".".join(parts))
    mod_name = "veles_tpu_workflow_" + parts[0]
    spec_obj = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec_obj)
    sys.modules[mod_name] = module
    spec_obj.loader.exec_module(module)
    return module


def apply_config_sources(sources, logger=None):
    """Applies config files and ``root.x=y`` override assignments in
    order (reference: __main__.py:419,467)."""
    for src in sources:
        if "=" in src and src.lstrip().startswith("root."):
            code = src
            origin = "<override>"
        elif os.path.isfile(src):
            with open(src) as fin:
                code = fin.read()
            origin = src
        else:
            raise Bug("config source %r is neither a root.x=y override "
                      "nor an existing file" % src)
        if logger is not None:
            logger.debug("applying config source %s", origin)
        exec(compile(code, origin, "exec"),
             {"root": root, "Tune": _tune_cls()})


def _tune_cls():
    from .config import Tune
    return Tune


class Main(Logger, CommandLineBase):
    """The velescli driver (reference: __main__.py:129)."""

    EXIT_SUCCESS = 0
    EXIT_FAILURE = 1

    def __init__(self, argv=None):
        super(Main, self).__init__()
        self.argv = list(sys.argv[1:] if argv is None else argv)
        self.args = None
        self.launcher = None
        self.workflow = None
        self.module = None
        self._start_time = None
        self._snapshot_loaded = False

    # -- setup -------------------------------------------------------------

    def parse(self):
        parser = init_argparser(prog="veles_tpu")
        # parse_intermixed_args: ``workflow -v error root.x=1`` must
        # work — plain parse_args fills the config positional
        # (nargs="*") with [] at the first optional and then reports
        # trailing root.path=value overrides as unrecognized.
        self.args = parser.parse_intermixed_args(self.argv)
        level = {"debug": logging.DEBUG, "info": logging.INFO,
                 "warning": logging.WARNING,
                 "error": logging.ERROR}[self.args.verbosity]
        logging.getLogger().setLevel(level)
        return self.args

    def seed_random(self):
        """Seeds generator 0 from ``--random-seed`` (reference:
        __main__.py:476-530): an int, or ``file:count:dtype``."""
        spec = self.args.random_seed
        if not spec:
            return
        try:
            seed = int(spec)
        except ValueError:
            seed = spec  # file:count:dtype — RandomGenerator parses it
        prng.get(0).seed(seed)
        self.info("seeded PRNG 0 with %r", spec)

    # -- workflow construction (the load/main closures) --------------------

    def _filtered_worker_argv(self):
        """The velescli argv spawned workers run — RECONSTRUCTED from
        the parsed args rather than filtered from raw argv (raw-string
        filtering misses argparse abbreviations like --listen, which
        would turn workers into recursive coordinators).  Reference
        analogue: launcher.py:75 argv filtering."""
        a = self.args
        out = [a.workflow] + list(a.config)
        for path in a.config_list:
            out += ["-c", path]
        if a.random_seed:
            out += ["--random-seed", a.random_seed]
        if a.verbosity != "info":
            out += ["-v", a.verbosity]
        if a.backend:
            out += ["-a", a.backend]
        if a.max_epochs:
            out += ["--max-epochs", str(a.max_epochs)]
        if a.async_slave:
            out.append("--async-slave")
        if a.slave_death_probability:
            out += ["--slave-death-probability",
                    str(a.slave_death_probability)]
        if a.measure_power:
            out.append("--measure-power")
        if a.reconnect_attempts is not None:
            out += ["--reconnect-attempts", str(a.reconnect_attempts)]
        if a.reconnect_delay is not None:
            out += ["--reconnect-delay", str(a.reconnect_delay)]
        if a.preempt_grace is not None:
            out += ["--preempt-grace", str(a.preempt_grace)]
        if a.chaos:
            # Workers install the SAME plan: each process's rules
            # fire off its own logical counters, so the combined
            # failure schedule stays reproducible.
            out += ["--chaos", a.chaos]
        if a.train_ratio is not None:
            out += ["--train-ratio", str(a.train_ratio)]
        if a.shuffle_limit is not None:
            out += ["--shuffle-limit", str(a.shuffle_limit)]
        # Data-plane knobs travel to spawned workers so the handshake
        # negotiation sees matching preferences on both sides.
        if a.net_codec is not None:
            out += ["--net-codec", a.net_codec]
        if a.net_dtype is not None:
            out += ["--net-dtype", a.net_dtype]
        if a.net_legacy:
            out.append("--net-legacy")
        if a.net_zero is not None:
            out += ["--net-zero", str(a.net_zero)]
        if a.optimizer is not None:
            # Workers must build the same GD units (same slot shapes)
            # as the master or the slot-shard sync cannot decode.
            out += ["--optimizer", a.optimizer]
        return out + ["-m", "{master}"]

    def _launcher_kwargs(self):
        kw = {}
        if self.args.chaos:
            kw["chaos"] = self.args.chaos
        if self.args.listen_address:
            kw["listen_address"] = self.args.listen_address
            if self.args.nodes:
                kw["nodes"] = [n.strip() for n in
                               self.args.nodes.split(",") if n.strip()]
                kw["worker_argv"] = self._filtered_worker_argv()
        if self.args.master_address:
            kw["master_address"] = self.args.master_address
            slave_kwargs = {}
            if self.args.async_slave:
                slave_kwargs["async_mode"] = True
            if self.args.slave_death_probability:
                slave_kwargs["death_probability"] = \
                    self.args.slave_death_probability
                # A CLI worker really dies (its supervisor/respawn
                # hook restarts the process); in-process clients
                # default to abort-and-rejoin instead.
                slave_kwargs["death_exits"] = True
            if self.args.measure_power:
                slave_kwargs["measure_power"] = True
            if self.args.reconnect_attempts is not None:
                slave_kwargs["reconnect_attempts"] = \
                    self.args.reconnect_attempts
            if self.args.reconnect_delay is not None:
                slave_kwargs["reconnect_delay"] = \
                    self.args.reconnect_delay
            if self.args.preempt_grace is not None:
                slave_kwargs["preempt_grace"] = \
                    self.args.preempt_grace
            if self.args.net_legacy:
                slave_kwargs["net_legacy"] = True
            if slave_kwargs:
                kw["slave_kwargs"] = slave_kwargs
        if self.args.jax_coordinator or self.args.jax_num_processes \
                or self.args.jax_process_id:
            if not (self.args.jax_coordinator and
                    self.args.jax_num_processes > 1 and
                    0 <= self.args.jax_process_id <
                    self.args.jax_num_processes):
                # A partially-specified distributed launch silently
                # training N independent standalone copies is the
                # worst failure mode — refuse loudly.
                raise Bug(
                    "--jax-coordinator, --jax-num-processes (>1) and "
                    "a --jax-process-id in [0, N) must be given "
                    "together (got coordinator=%r, num_processes=%r, "
                    "process_id=%r)" % (
                        self.args.jax_coordinator,
                        self.args.jax_num_processes,
                        self.args.jax_process_id))
            # Multi-controller SPMD (launcher.py:120
            # jax.distributed.initialize): every process runs the
            # same program over the combined mesh.
            kw["mode"] = "distributed"
            kw["coordinator_address"] = self.args.jax_coordinator
            kw["num_processes"] = self.args.jax_num_processes
            kw["process_id"] = self.args.jax_process_id
        return kw

    def apply_subsystem_flags(self):
        """Feeds aggregated per-subsystem flags into the config tree
        (the reference's per-class flags were read by each class
        directly; here config is the handoff point)."""
        args = self.args
        if args.train_ratio is not None:
            root.common.loader.train_ratio = args.train_ratio
        if args.shuffle_limit is not None:
            root.common.loader.shuffle_limit = args.shuffle_limit
        if args.snapshot_dir is not None:
            root.common.dirs.snapshots = args.snapshot_dir
        if args.snapshot_compression is not None:
            root.common.snapshotter.compression = \
                args.snapshot_compression
        if args.snapshot_keep is not None:
            root.common.snapshotter.keep = args.snapshot_keep
        if args.no_snapshots:
            root.common.snapshot_disabled = True
        if args.snapshot_artifact:
            root.common.snapshotter.artifact = True
        # Coordinator knobs (server.py reads these back).
        if args.blacklist_cooldown is not None:
            root.common.server.blacklist_cooldown = \
                args.blacklist_cooldown
        # Training health guardian knobs (guardian.init_parser):
        # workflow builders read these back at construction.
        if args.guardian_policy is not None:
            root.common.guardian.policy = args.guardian_policy
        if args.guardian_spike is not None:
            root.common.guardian.spike_factor = args.guardian_spike
        if args.guardian_window is not None:
            root.common.guardian.window = args.guardian_window
        # Serving knobs for the in-workflow RESTfulAPI unit
        # (restful.serving_config_defaults reads these back).
        if args.serve_max_batch is not None:
            root.common.serving.max_batch = args.serve_max_batch
        if args.serve_queue_depth is not None:
            root.common.serving.queue_depth = args.serve_queue_depth
        if args.serve_rate_limit is not None:
            root.common.serving.rate_limit = args.serve_rate_limit
        if args.serve_deadline is not None:
            root.common.serving.deadline = args.serve_deadline
        if args.serve_token is not None:
            root.common.serving.token = args.serve_token
        if args.serve_warmup:
            root.common.serving.warmup = True
        if args.serve_kv_blocks is not None:
            root.common.serving.kv_blocks = args.serve_kv_blocks
        if args.serve_kv_block_size is not None:
            root.common.serving.kv_block_size = \
                args.serve_kv_block_size
        if args.serve_kv_dtype is not None:
            root.common.serving.kv_dtype = args.serve_kv_dtype
        if args.serve_weight_dtype is not None:
            root.common.serving.weight_dtype = \
                args.serve_weight_dtype
        if args.serve_no_paged:
            root.common.serving.paged = False
        if args.serve_spec:
            root.common.serving.spec = True
        if args.serve_spec_draft is not None:
            root.common.serving.spec_draft = args.serve_spec_draft
        if args.serve_spec_max_k is not None:
            root.common.serving.spec_max_k = args.serve_spec_max_k
        if args.serve_spec_draft_blocks is not None:
            root.common.serving.spec_draft_blocks = \
                args.serve_spec_draft_blocks
        if args.serve_drain_timeout is not None:
            root.common.serving.drain_timeout = \
                args.serve_drain_timeout
        if args.serve_reload_watch is not None:
            root.common.serving.reload_watch = \
                args.serve_reload_watch
        if args.serve_reload_poll is not None:
            root.common.serving.reload_poll = args.serve_reload_poll
        if args.serve_fabric_replicas is not None:
            root.common.serving.fabric_replicas = \
                args.serve_fabric_replicas
        if args.serve_fabric_disagg:
            root.common.serving.fabric_disagg = True
        if args.serve_tenant:
            root.common.serving.tenant = list(args.serve_tenant)
        # Attention fast-path knobs (ops/attention.init_parser;
        # docs/attention.md) — read back at unit construction
        # (fused_qkv freezes the parameter layout) and inside the
        # attention formulations (dtype/kernel dispatch).
        if args.attn_fused_qkv is not None:
            root.common.engine.fused_qkv = \
                args.attn_fused_qkv == "on"
        if args.attn_dtype is not None:
            root.common.engine.attention_dtype = args.attn_dtype
        if args.attn_kernel is not None:
            root.common.engine.attention_kernel = args.attn_kernel
        if args.sp_ring_kernel is not None:
            root.common.engine.sp_ring_kernel = args.sp_ring_kernel
        if args.attn_decode_kernel is not None:
            root.common.engine.decode_kernel = \
                args.attn_decode_kernel
        # Pipeline-schedule knobs (ops/pipeline.py init_parser;
        # docs/pipeline.md) — read back at unit construction.
        if args.pp_schedule is not None:
            root.common.engine.pp_schedule = args.pp_schedule
        if args.pp_chunks is not None:
            root.common.engine.pp_chunks = args.pp_chunks
        # Distributed data-plane knobs (network_common.init_parser;
        # docs/distributed.md) — read back by the handshake
        # negotiation and the channels.
        if args.net_codec is not None:
            from .network_common import parse_codec_spec
            name, level, threshold = parse_codec_spec(args.net_codec)
            root.common.net.codec = name
            if level is not None:
                root.common.net.codec_level = level
            if threshold is not None:
                root.common.net.codec_threshold = threshold
        if args.net_dtype is not None:
            root.common.net.dtype = args.net_dtype
        if args.job_ticks is not None:
            if args.job_ticks < 1:
                raise Bug("--job-ticks must be >= 1 (got %d)"
                          % args.job_ticks)
            root.common.net.job_ticks = args.job_ticks
        if args.net_zero is not None:
            if args.net_zero < 0:
                raise Bug("--net-zero must be >= 0 (got %d)"
                          % args.net_zero)
            root.common.net.zero = args.net_zero
        if args.net_legacy:
            root.common.net.mode = "legacy"
        if args.net_require:
            root.common.net.require = True
        # Optimizer family + ZeRO sharding (znicz.optimizers
        # init_parser; docs/optimizers.md): the optimizer default is
        # read back at GD-unit construction (and checked against
        # resumed slots at initialize), --zero by the distributed
        # launcher after the dp mesh is applied.
        if args.optimizer is not None:
            root.common.engine.optimizer = args.optimizer
        if args.zero is not None:
            root.common.engine.zero = args.zero
        # Observability knobs (observability.init_parser;
        # docs/observability.md): --trace-out arms span tracing (the
        # launcher exports at run end; workers enable via handshake),
        # --xprof arms the jax.profiler capture window around the
        # next N fused dispatches.
        if args.trace_out:
            root.common.observability.trace_out = args.trace_out
            root.common.observability.trace = True
            from .observability import tracing
            tracing.enable(ring=args.trace_ring)
        if args.xprof:
            root.common.observability.xprof = args.xprof
            from .observability import attribution
            attribution.configure_xprof(args.xprof,
                                        args.xprof_steps)
        # Population engine knobs (population.init_parser;
        # docs/population.md) — read back by PopulationMaster and
        # the vmap sub-population backend.
        if args.pbt_interval is not None:
            root.common.population.pbt_interval = args.pbt_interval
        if args.pbt_quantile is not None:
            root.common.population.pbt_quantile = args.pbt_quantile
        if args.pbt_perturb is not None:
            root.common.population.pbt_perturb = args.pbt_perturb
        if args.population_vmap is not None:
            root.common.population.vmap = \
                args.population_vmap == "on"

    def load(self, WorkflowClass, **kwargs):
        """``load`` closure passed to the module's run() hook
        (reference: __main__.py:584 ``_load``): builds the launcher,
        then either resumes a snapshot or constructs the workflow."""
        kwargs.setdefault("result_file", self.args.result_file or None)
        self.launcher = Launcher(**self._launcher_kwargs())
        if self.args.snapshot:
            spec = self.args.snapshot
            if spec.startswith(("odbc://", "sqlite://", "db://")):
                from .snapshotter import SnapshotterToDB
                self.workflow = SnapshotterToDB.import_(spec)
            else:
                self.workflow = SnapshotterToFile.import_(spec)
            self._snapshot_loaded = True
            self.launcher.add_ref(self.workflow)
            self.info("resumed snapshot %s (%s)", self.args.snapshot,
                      type(self.workflow).__name__)
        elif self.args.auto_resume and self.launcher.resume_latest(
                expect_class=WorkflowClass) is not None:
            # Coordinator crash-resume: a restarted master picks up
            # the newest *_current.lnk snapshot; in-flight jobs were
            # requeued at pickle time, so the ledger resumes without
            # losing or double-counting a minibatch.
            self.workflow = self.launcher.workflow
            self._snapshot_loaded = True
        else:
            self.workflow = WorkflowClass(self.launcher, **kwargs)
        if self.args.max_epochs:
            decision = getattr(self.workflow, "decision", None)
            if decision is None:
                raise Bug("--max-epochs given but the workflow has no "
                          "decision unit")
            decision.max_epochs = int(self.args.max_epochs)
        return self.workflow, self._snapshot_loaded

    def main(self, **kwargs):
        """``main`` closure passed to the module's run() hook
        (reference: __main__.py:620 ``_main``): initialize → run →
        results."""
        if self.workflow is None:
            raise Bug("main() called before load()")
        if self.args.dry_run == "load":
            return
        if self.args.backend:
            from .backends import Device
            kwargs.setdefault("device",
                              Device.create(self.args.backend))
        self.launcher.initialize(
            snapshot=self._snapshot_loaded, **kwargs)
        if self.args.workflow_graph:
            self.workflow.generate_graph(self.args.workflow_graph)
            self.info("workflow graph -> %s", self.args.workflow_graph)
        if self.args.dry_run == "init":
            return
        profile_dir = self.args.profile
        if profile_dir:
            import jax
            jax.profiler.start_trace(profile_dir)
        try:
            self.launcher.run()
        finally:
            if profile_dir:
                import jax
                jax.profiler.stop_trace()
                self.info("profiler trace -> %s", profile_dir)
        if self.args.dry_run == "exec":
            return
        self.write_results()

    def write_results(self):
        """Serializes run metrics to ``--result-file`` (reference:
        workflow.py:814-836 + __main__.py ``run``)."""
        path = self.args.result_file
        if not path:
            return
        results = {
            "workflow": self.workflow.name,
            "class": type(self.workflow).__name__,
            "checksum": self.workflow.checksum,
            "mode": self.launcher.mode,
            "seed": repr(prng.get(0).seed_value),
            "runtime": self.launcher.runtime,
            "units": len(self.workflow.units),
            "device": device_entry(),
            "results": self.workflow.gather_results(),
        }
        dump_json(results, path)
        self.info("results -> %s", path)

    # -- mode dispatch ------------------------------------------------------

    def run_regular(self):
        run_hook = getattr(self.module, "run", None)
        if run_hook is None:
            raise Bug("workflow module %s has no run(load, main) hook"
                      % self.module.__name__)
        run_hook(self.load, self.main)

    def run_genetics(self):
        from .genetics.optimizer import GeneticsOptimizer
        size_spec = self.args.optimize
        if ":" in size_spec:
            size, generations = (int(p) for p in size_spec.split(":"))
        else:
            size, generations = int(size_spec), None
        optimizer = GeneticsOptimizer(
            main=self, size=size, generations=generations)
        optimizer.run()

    def run_ensemble_train(self):
        from .ensemble import EnsembleTrainer
        spec = self.args.ensemble_train
        if ":" in spec:
            n, ratio = spec.split(":", 1)
            n, ratio = int(n), float(ratio)
        else:
            n, ratio = int(spec), 1.0
        EnsembleTrainer(main=self, instances=n,
                        train_ratio=ratio).run()

    def run_population(self):
        """--population / --pbt dispatch: fleet-scheduled member
        lineages (docs/population.md)."""
        from .population import PopulationEngine
        spec = self.args.population or "2"
        generations = None
        if ":" in spec:
            size, generations = (int(p) for p in spec.split(":"))
        else:
            size = int(spec)
        engine = PopulationEngine(
            main=self, size=size, generations=generations,
            mode="pbt" if self.args.pbt else None)
        engine.run()

    def run_ensemble_test(self):
        from .ensemble import EnsembleTester
        EnsembleTester(main=self,
                       ensemble_file=self.args.ensemble_test).run()

    # -- top-level ----------------------------------------------------------

    def run(self):
        self._start_time = time.time()
        self.parse()
        if self.args.frontend:
            # The wizard needs no workflow (reference: --frontend,
            # __main__.py:251-325 spawned the web wizard).
            try:
                from .scripts.generate_frontend import generate
                path = generate(self.args.frontend)
            except Exception:
                self.exception("frontend generation failed")
                return self.EXIT_FAILURE
            self.info("frontend wizard -> %s", path)
            print(path)
            return self.EXIT_SUCCESS
        if not self.args.workflow:
            init_argparser(prog="veles_tpu").print_help()
            return self.EXIT_FAILURE
        try:
            enable_compilation_cache()
            self.seed_random()
            apply_config_sources(
                list(self.args.config) + list(self.args.config_list),
                logger=self)
            # After config sources so explicit CLI flags win over
            # config-file assignments (reference precedence:
            # __main__.py:467 applies argv overrides last).
            self.apply_subsystem_flags()
            self.module = import_workflow_module(self.args.workflow)
            if self.args.dump_config:
                root.print_()
            guard = bool(root.common.engine.get(
                "poison_numpy_random", True))
            if guard:
                prng.guard_path(os.path.dirname(os.path.abspath(
                    self.args.workflow)))
                prng.poison_numpy_random()
            try:
                if self.args.population or self.args.pbt:
                    self.run_population()
                elif self.args.optimize:
                    self.run_genetics()
                elif self.args.ensemble_train:
                    self.run_ensemble_train()
                elif self.args.ensemble_test:
                    self.run_ensemble_test()
                else:
                    self.run_regular()
            finally:
                if guard:
                    prng.unpoison_numpy_random()
        except KeyboardInterrupt:
            self.warning("interrupted")
            if self.launcher is not None:
                self.launcher.stop()
            return self.EXIT_FAILURE
        except Exception:
            self.exception("workflow run failed")
            return self.EXIT_FAILURE
        self._report_resources()
        return self.EXIT_SUCCESS

    def _report_resources(self):
        """Peak RSS + device memory at exit (reference:
        __main__.py:785-791)."""
        try:
            import resource
            peak_kb = resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss
            self.info("peak RSS: %.1f MB; wall time: %.1fs",
                      peak_kb / 1024.0,
                      time.time() - self._start_time)
        except Exception as e:
            self.debug("peak-RSS report unavailable: %s", e)
        try:
            import jax
            stats = jax.local_devices()[0].memory_stats()
            if stats and "peak_bytes_in_use" in stats:
                self.info("peak device memory: %.1f MB",
                          stats["peak_bytes_in_use"] / 1e6)
        except Exception as e:
            self.debug("device-memory report unavailable: %s", e)


def main(argv=None):
    return Main(argv).run()


if __name__ == "__main__":
    sys.exit(main())
