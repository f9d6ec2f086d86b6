"""Run-mode orchestration.

Capability parity with the reference launcher (reference:
veles/launcher.py — ``Launcher:100``, mode select from ``-l``/``-m``
launcher.py:333-342, web-status heartbeats launcher.py:853-886, remote
process spawn launcher.py:809-843).

TPU-era redesign: the reference launcher owns a Twisted reactor and a
ZeroMQ master–slave fabric because data parallelism is job-shipping.
Here single-process runs (one host, 1..N local TPU chips) need no
reactor at all — SPMD parallelism is expressed with `jax.sharding` and
executed by XLA over ICI (see parallel/).  Multi-host runs use
`jax.distributed` (one process per host, all running the same program),
so the launcher's surviving jobs are: mode selection, process-group
bring-up, lifecycle (initialize → run → stop), heartbeats, and stats.
"""

import json
import os
import threading
import time

from . import resilience
from .config import root, get as config_get
from .distributable import SniffedLock
from .logger import Logger


class Launcher(Logger):
    """Owns workflow lifecycle for this process (reference:
    launcher.py:100)."""

    def __init__(self, interactive=False, **kwargs):
        super(Launcher, self).__init__()
        self.interactive = interactive
        self.workflow = None
        self._mode = kwargs.get("mode", "standalone")
        # Master–slave control plane (reference -l/-m flags,
        # launcher.py:333-342): ``listen_address`` turns this process
        # into a coordinator; ``master_address`` into a worker.
        self.listen_address = kwargs.get("listen_address")
        self.master_address = kwargs.get("master_address")
        if self.listen_address and self._mode == "standalone":
            self._mode = "master"
        if self.master_address and self._mode == "standalone":
            self._mode = "slave"
        self.slave_kwargs = kwargs.get("slave_kwargs", {})
        # Deterministic chaos (--chaos "net.drop@job:7,seed:42"):
        # installing the plan process-wide reaches every Channel,
        # Server, Client, and Snapshotter without explicit wiring —
        # the same plan + seed reproduces the same failure sequence.
        chaos = kwargs.get("chaos")
        if chaos:
            self.injector = resilience.install(chaos)
            self.info("chaos plan installed: %s", chaos)
        else:
            self.injector = kwargs.get("injector")
        self.server = None
        self.client = None
        self._running = threading.Event()
        self._finished = threading.Event()
        self.device = None
        self.coordinator_address = kwargs.get("coordinator_address")
        self.num_processes = int(kwargs.get("num_processes", 1))
        self.process_id = int(kwargs.get("process_id", 0))
        self._start_time = None
        # Web-status heartbeats (reference: launcher.py:853-886).
        # ``status_address`` (or root.common.web.url) turns them on;
        # queued dashboard commands ride the heartbeat response.
        self.status_address = kwargs.get(
            "status_address", config_get(root.common.web.url, None))
        self.heartbeat_interval = float(kwargs.get(
            "heartbeat_interval",
            config_get(root.common.web.interval, 5.0)))
        self.status_token = kwargs.get(
            "status_token", config_get(root.common.web.token, None))
        self._heartbeat_thread = None
        self._heartbeat_stop = threading.Event()
        self._graph_dot_ = None
        self._beat_count_ = 0
        self._plots_sent_ = None
        self._plots_cache_ = {}
        self.graphics_server = None
        # Remote worker spawn (reference: launcher.py:809-843
        # paramiko/SSH _launch_nodes): ``nodes`` lists worker hosts —
        # "local" spawns a subprocess on this machine, anything else
        # goes through ssh; ``worker_argv`` is the velescli argv the
        # workers run (Main filters its own coordinator flags out,
        # {master} is substituted with our address).
        self.nodes = list(kwargs.get("nodes") or [])
        self.worker_argv = list(kwargs.get("worker_argv") or [])
        # Spawns race: the server's respawn hook fires from per-drop
        # threads while the main thread may be launching/stopping.
        self._procs_lock = SniffedLock(name="Launcher.procs_lock")
        self._worker_procs = []  # guarded-by: _procs_lock

    # -- mode flags (reference API) ----------------------------------------

    @property
    def mode(self):
        return self._mode

    @property
    def is_standalone(self):
        return self._mode == "standalone"

    @property
    def is_master(self):
        """Multi-host process 0 plays the coordinator role."""
        return self._mode == "master" or (
            self._mode == "distributed" and self.process_id == 0)

    @property
    def is_slave(self):
        return self._mode == "slave" or (
            self._mode == "distributed" and self.process_id != 0)

    @property
    def is_running(self):
        return self._running.is_set()

    # -- registration ------------------------------------------------------

    def add_ref(self, workflow):
        self.workflow = workflow
        workflow.workflow = self

    def del_ref(self, workflow):
        if self.workflow is workflow:
            self.workflow = None

    # -- coordinator crash-resume ------------------------------------------

    def resume_latest(self, directory=None, prefix=None,
                      expect_class=None):
        """Coordinator crash-resume: loads the newest snapshot named
        by a ``*_current.lnk`` pointer in the snapshot directory,
        adopts it as this launcher's workflow, and returns it — or
        returns None when there is nothing to resume (fresh start).

        ``expect_class`` guards shared snapshot directories: only a
        snapshot holding an instance of that workflow class is
        adopted (newest first); snapshots of OTHER trainings are
        skipped with a warning instead of silently hijacking the run.
        (Skipping still costs a full unpickle of the foreign
        snapshot — give concurrent trainings distinct directories or
        prefixes when snapshots are large.)

        Because snapshot writes are atomic (temp + ``os.replace``)
        and the workflow's pickled state requeues every in-flight
        job (loader ``__getstate__``), a master restarted through
        this path re-serves exactly the minibatches whose updates
        had not been applied at snapshot time: nothing is lost,
        nothing double-counted.  Workers reconnect on their own —
        the client retry policy keeps dialing while the master is
        down."""
        directory = directory or config_get(
            root.common.dirs.snapshots, "snapshots")
        from .snapshotter import SnapshotterToFile
        for path in resilience.iter_snapshots(directory, prefix):
            try:
                workflow = SnapshotterToFile.import_(path)
            except Exception as e:
                # An unloadable snapshot (older code revision, a
                # half-restored file) must not abort the recovery
                # path — fall through to the next candidate.
                self.warning("crash-resume: cannot load %s (%s) — "
                             "trying the next snapshot", path, e)
                continue
            if expect_class is not None and \
                    not isinstance(workflow, expect_class):
                self.warning(
                    "crash-resume: skipping %s — it holds a %s, "
                    "not the %s this invocation trains", path,
                    type(workflow).__name__, expect_class.__name__)
                continue
            self.add_ref(workflow)
            resilience.stats.incr("master.resume")
            self.info("crash-resume: adopted snapshot %s (%s)", path,
                      type(workflow).__name__)
            return workflow
        return None

    # -- lifecycle ---------------------------------------------------------

    def initialize(self, **kwargs):
        """Brings up the process group (if distributed), selects the
        device, and initializes the workflow
        (reference: launcher.py:431) — all of it inside the
        ``launcher.initialize`` set-up span
        (docs/observability.md, "Start-up")."""
        from .observability import startup
        with startup.span("launcher.initialize"):
            return self._initialize(**kwargs)

    def _initialize(self, **kwargs):
        from . import backends
        if self._mode == "distributed" and self.num_processes > 1:
            import jax
            # Idempotent across launchers in one process (genetics/
            # ensembles build a Launcher per candidate run).
            if not jax.distributed.is_initialized():
                jax.distributed.initialize(
                    coordinator_address=self.coordinator_address,
                    num_processes=self.num_processes,
                    process_id=self.process_id)
        self.device = kwargs.pop("device", None) or \
            backends.Device.create(
                config_get(root.common.engine.backend, "auto"))
        self.workflow.initialize(device=self.device, **kwargs)
        if self._mode == "distributed" and self.num_processes > 1:
            if hasattr(self.workflow, "compiler"):
                # Multi-controller SPMD: annotate the step for data
                # parallelism over the COMBINED mesh (every process
                # runs the same program; XLA's psum rides the
                # cross-process collective backend).
                import jax
                from .parallel import (make_mesh, apply_dp_sharding,
                                       apply_zero_sharding)
                apply_dp_sharding(self.workflow,
                                  make_mesh(jax.devices()))
                zero = int(config_get(root.common.engine.zero, 0)
                           or 0)
                if zero:
                    # --zero: optimizer slots shard 1/dp over the
                    # data axis (level 2 adds the grad reduce-scatter
                    # constraints) — docs/optimizers.md.
                    apply_zero_sharding(self.workflow,
                                        self.workflow.mesh,
                                        level=zero)
                self.info("distributed SPMD: %d processes, %d "
                          "devices%s", self.num_processes,
                          len(jax.devices()),
                          ", ZeRO-%d optimizer sharding" % zero
                          if zero else "")
            else:
                self.warning(
                    "distributed mode requested but %s has no fused-"
                    "step compiler — every process will run the FULL "
                    "workflow redundantly", type(self.workflow).
                    __name__)
        if self.is_master and self.listen_address:
            from .server import Server
            self.server = Server(self.listen_address, self.workflow,
                                 on_stopped=self.on_workflow_finished,
                                 injector=self.injector)
        elif self.is_slave and self.master_address:
            from .client import Client
            slave_kwargs = dict(self.slave_kwargs)
            slave_kwargs.setdefault("injector", self.injector)
            self.client = Client(self.master_address, self.workflow,
                                 **slave_kwargs)
        if config_get(root.common.graphics.enabled, False):
            from .graphics_server import GraphicsServer
            self.graphics_server = GraphicsServer.launch()
        if self.status_address and not self.is_slave:
            self._start_heartbeats()
        if self.nodes and self.server is not None:
            self.launch_remote_workers()
            # Dropped workers respawn through the same spawner
            # (reference: server.py:637-655 SSH respawn).
            self.server.respawn = lambda desc: \
                self._spawn_worker(self._node_of(desc))
        return self

    # -- remote worker spawn (reference: launcher.py:809-843) --------------

    def _master_endpoint(self):
        import socket as socket_mod
        host, _ = self.listen_address.rsplit(":", 1) \
            if ":" in self.listen_address else (self.listen_address,
                                                "")
        if host in ("", "0.0.0.0", "::"):
            host = socket_mod.getfqdn()
        return "%s:%d" % (host, self.server.port)

    def _worker_command(self, master):
        import sys
        argv = [arg.replace("{master}", master)
                for arg in self.worker_argv]
        if "-m" not in argv and "--master-address" not in argv:
            argv += ["-m", master]
        return [sys.executable, "-m", "veles_tpu"] + argv

    def _spawn_worker(self, node):
        """Starts one worker process on ``node``.  A ``local`` worker
        is a second JAX process on THIS machine, started after the
        coordinator initialized its workflow on the device: on a
        machine whose chip this process holds, that child cannot
        have it (one process per chip) — ``--nodes local`` is for
        CPU fleets and for hosts where each process is given its own
        devices."""
        import os as os_mod
        import subprocess
        master = self._master_endpoint()
        cmd = self._worker_command(
            "127.0.0.1:%d" % self.server.port
            if node in ("local", "localhost") else master)
        if node not in ("local", "localhost"):
            # ssh host 'cd <cwd> && exec python -m veles_tpu ...'
            import shlex
            remote = "cd %s && exec %s" % (
                shlex.quote(os_mod.getcwd()),
                " ".join(shlex.quote(a) for a in cmd))
            cmd = ["ssh", "-o", "BatchMode=yes", node, remote]
        self.info("spawning worker on %s: %s", node, " ".join(cmd))
        proc = subprocess.Popen(cmd)
        with self._procs_lock:
            self._worker_procs.append((node, proc))
        return proc

    def launch_remote_workers(self):
        for node in self.nodes:
            self._spawn_worker(node)

    def _node_of(self, desc):
        """Node for a dropped worker's respawn: the one with the
        fewest live worker processes — a died worker's ssh/subprocess
        has exited, so its node shows the capacity gap.  The pick
        itself is the fleet-wide least-loaded policy
        (:meth:`FleetScheduler.least_loaded`), shared with every
        other placement decision."""
        if not self.nodes:
            return "local"
        alive = {node: 0 for node in self.nodes}
        with self._procs_lock:
            procs = list(self._worker_procs)
        for node, proc in procs:
            if proc.poll() is None and node in alive:
                alive[node] += 1
        from .fleet import FleetScheduler
        return FleetScheduler.least_loaded(self.nodes,
                                           lambda n: alive[n])

    def run(self):
        """Runs the workflow to completion (blocking)
        (reference: launcher.py:551).

        Master mode: the Server thread pool drives the workflow via
        the job protocol; this thread just waits.  Slave mode: the
        Client job loop runs here.  Standalone: the graph runs here.
        """
        self._start_time = time.time()
        self._running.set()
        self._finished.clear()
        try:
            if self.server is not None:
                self.server.wait()
                if self.server.crashed:
                    # A crashed coordinator must NOT look like a
                    # clean exit: the CLI would write a results file
                    # from the half-trained workflow and exit 0, and
                    # a restart-on-failure supervisor (the documented
                    # crash-resume recovery path) would never fire.
                    raise resilience.MasterCrash("master.crash")
                if getattr(self.server, "failure", None) is not None:
                    # Same contract for a server stopped by a
                    # master-side error (failed update apply,
                    # exhausted snapshot retries): nonzero exit, no
                    # results file.
                    raise self.server.failure
            elif self.client is not None:
                # Spot-preemption contract (docs/distributed.md,
                # "Elastic operations"): SIGTERM drains the worker —
                # in-flight job finishes, update ships, bye goes out,
                # exit code 0 — instead of killing it mid-recv.  The
                # serving engine has had this since its drain PR; the
                # training worker gets the same treatment here.
                from .client import install_sigterm_drain
                install_sigterm_drain(self.client)
                self.client.run()
            else:
                self.workflow.run()
                self._finished.wait()
        finally:
            self._running.clear()
            self._heartbeat_stop.set()
            if self.server is not None:
                self.server.stop()
                # Per-worker job throughput next to the timing table
                # (the comms half of the exit report; wire totals ride
                # print_stats' resilience-events line as net.*).
                slaves = getattr(self.server, "all_slaves", None)
                if slaves:
                    self.info("Worker throughput: %s", "; ".join(
                        "%s=%d jobs (%.2f/s)" % (
                            sid, desc.jobs_done, desc.jobs_per_second)
                        for sid, desc in sorted(slaves.items())))
            self.workflow.print_stats()
            self._export_trace()

    def _export_trace(self):
        """Writes the collected spans as Chrome trace-event JSON when
        ``--trace-out`` armed tracing (master/standalone only — a
        worker's spans already rode the job protocol home)."""
        from .observability import tracing
        path = config_get(root.common.observability.trace_out, None)
        if not path or self.is_slave or not tracing.enabled():
            return
        try:
            obj = tracing.export_chrome_trace(path)
        except OSError as e:
            self.warning("cannot write trace %s: %s", path, e)
            return
        self.info("trace -> %s (%d events)", path,
                  len(obj["traceEvents"]))

    def on_workflow_finished(self):
        self._finished.set()

    # -- heartbeats (reference: launcher.py:853-886) -----------------------

    def _start_heartbeats(self):
        self._heartbeat_stop.clear()
        self._heartbeat_thread = threading.Thread(
            target=self._heartbeat_loop, daemon=True,
            name="veles-heartbeat")
        self._heartbeat_thread.start()

    def _heartbeat_loop(self):
        import urllib.request
        from .json_encoders import dumps_json
        from .network_common import machine_id
        url = self.status_address
        if not url.startswith("http"):
            url = "http://" + url
        url = url.rstrip("/") + "/update"
        mid = "%s/%d" % (machine_id(), os.getpid())
        while not self._heartbeat_stop.wait(self.heartbeat_interval):
            try:
                headers = {"Content-Type": "application/json"}
                if self.status_token:
                    headers["X-Status-Token"] = self.status_token
                req = urllib.request.Request(
                    url, data=dumps_json(
                        self.status_payload(mid)).encode(),
                    headers=headers)
                with urllib.request.urlopen(req, timeout=10) as resp:
                    reply = json.loads(resp.read())
                for cmd in reply.get("commands", []):
                    self._apply_command(cmd)
            except Exception as e:
                self.debug("heartbeat failed: %s", e)

    def status_payload(self, mid):
        wf = self.workflow
        loader = getattr(wf, "loader", None)
        decision = getattr(wf, "decision", None)
        payload = {
            "id": mid,
            "workflow": type(wf).__name__ if wf else None,
            "mode": self.mode,
            "runtime": self.runtime,
            "epoch": getattr(loader, "epoch_number", None),
            "running": self.is_running,
        }
        if decision is not None:
            metrics = {}
            if getattr(decision, "epoch_metrics", None):
                for cls, name in enumerate(("test", "validation",
                                            "train")):
                    v = decision.epoch_metrics[cls]
                    if v is not None:
                        metrics["%s_err" % name] = float(v)
            payload["metrics"] = metrics
        # Training health (guardian.py): policy, event count, and the
        # last NaN/spike event — operators see a recovered run WAS
        # sick, not just that it survived.
        guardian = getattr(wf, "guardian", None)
        if guardian is not None and \
                hasattr(guardian, "health_status"):
            payload["health"] = guardian.health_status()
        if self.server is not None:
            payload["slaves"] = {
                sid: {"state": desc.state,
                      "jobs_done": desc.jobs_done,
                      "jobs_per_s": round(desc.jobs_per_second, 2),
                      "power": desc.power,
                      "blacklisted": desc.blacklisted}
                for sid, desc in self.server.slaves.items()}
        # One snapshot feeds both rows — two would disagree (counters
        # advance between locked copies) within a single beat.
        events = resilience.stats.snapshot()
        # Comms observability (docs/distributed.md): wire volume and
        # data-plane timing totals so operators see when the wire —
        # not the chip — bounds scale-out.
        net = {k: v for k, v in events.items()
               if k.startswith("net.")}
        if net:
            payload["comms"] = net
        # Resilience events (retries, drops, blacklists, crashes,
        # resumes): operators see degradation, not just survive it.
        # net.* already rides the comms row and device.* rides the
        # perf row — don't ship either twice.
        events = {k: v for k, v in events.items()
                  if not k.startswith(("net.", "device."))}
        if events:
            payload["resilience"] = events
        # Perf row (docs/observability.md): live device-time and MFU
        # attribution of the fused step, measured at the dispatch.
        try:
            from .observability import attribution
            perf = attribution.perf_summary()
        except Exception as e:
            self.debug("perf heartbeat section unavailable: %s", e)
            perf = None
        if perf:
            payload["perf"] = perf
        # Serving row: any live ServingEngine in this process (an
        # in-workflow RESTfulAPI unit) ships its decode tok/s, queue
        # depth, and KV-pool occupancy so the soak's numbers are
        # live operator metrics, not just bench output.
        try:
            from .serving.metrics import live_serving_summary
            serving = live_serving_summary()
        except Exception as e:
            self.debug("serving heartbeat section unavailable: %s",
                       e)
            serving = None
        if serving:
            payload["serving"] = serving
        # Fabric row: replica membership, routed totals, and the
        # cross-replica prefix hit-rate from any live serving fabric
        # router in this process (docs/serving.md "Serving fabric").
        try:
            from .serving.fabric import live_fabric_summary
            fabric = live_fabric_summary()
        except Exception as e:
            self.debug("fabric heartbeat section unavailable: %s", e)
            fabric = None
        if fabric:
            payload["fabric"] = fabric
        # Population row: member fitness, lineage generations, and
        # exploit/requeue counts from any live population master in
        # this process (docs/population.md).
        try:
            from .population.master import live_population_summary
            population = live_population_summary()
        except Exception as e:
            self.debug("population heartbeat section unavailable: "
                       "%s", e)
            population = None
        if population:
            payload["population"] = population
        # Fleet row: membership epoch, live size, and the
        # join/leave/drain tallies from any live fleet scheduler in
        # this process — membership change is a numbered event an
        # operator can see, not something to reconstruct from worker
        # logs (docs/distributed.md, "Elastic operations").
        try:
            from .fleet import live_fleet_summary
            fleet = live_fleet_summary()
        except Exception as e:
            self.debug("fleet heartbeat section unavailable: %s", e)
            fleet = None
        if fleet:
            payload["fleet"] = fleet
        # Dashboard depth (reference: web_status.py:113-243 shows the
        # Graphviz workflow graph and plot links): the DOT text rides
        # the first beat and a ~per-minute refresh (the dashboard
        # merges missing sections from the previous beat), plots ride
        # only when a PNG actually changed.
        if wf is not None and self._graph_dot_ is None:
            try:
                self._graph_dot_ = wf.generate_graph(
                    write_on_disk=False)
            except Exception as e:
                self.debug("workflow graph render failed: %s", e)
                self._graph_dot_ = ""
        self._beat_count_ += 1
        if self._graph_dot_ and (self._beat_count_ == 1 or
                                 self._beat_count_ % 12 == 0):
            payload["graph"] = self._graph_dot_
        plots = self._collect_plots()
        if plots is not None:
            payload["plots"] = plots
        return payload

    #: Per-plot and per-beat byte budgets for heartbeat plot payloads.
    PLOT_BYTES_MAX = 256 * 1024
    PLOTS_PER_BEAT = 4

    def _collect_plots(self):
        """Base64 of the most recent rendered plot PNGs.  Returns None
        when nothing changed since the last beat (the encoding cache
        is keyed by (path, mtime, size) so an hours-long run does not
        re-read and re-encode static PNGs every 5 seconds)."""
        import base64
        import glob
        plot_dir = config_get(root.common.dirs.plots, None)
        if not plot_dir or not os.path.isdir(plot_dir):
            return None
        entries = []
        for path in glob.glob(os.path.join(plot_dir, "*.png")):
            try:
                st = os.stat(path)
            except OSError:
                continue  # deleted between glob and stat
            # Oversized files never ship: exclude them up front so
            # they neither poison the sent-keys comparison nor shrink
            # the dashboard's plot set.
            if st.st_size > self.PLOT_BYTES_MAX:
                continue
            entries.append((st.st_mtime, path, st.st_size))
        entries.sort(reverse=True)
        keys = tuple((p, m, s) for m, p, s in
                     entries[:self.PLOTS_PER_BEAT])
        if keys == self._plots_sent_ or not keys:
            # Unchanged — or nothing eligible: omit the section so
            # the dashboard keeps the previously shown plots rather
            # than receiving an erasing empty dict.
            return None
        out = {}
        cache = self._plots_cache_
        for mtime, path, size in entries[:self.PLOTS_PER_BEAT]:
            name = os.path.splitext(os.path.basename(path))[0]
            cached = cache.get(path)
            if cached is not None and cached[0] == (mtime, size):
                out[name] = cached[1]
                continue
            try:
                with open(path, "rb") as fin:
                    blob = base64.b64encode(fin.read()).decode()
            except OSError:
                continue
            cache[path] = ((mtime, size), blob)
            out[name] = blob
        # Drop cache entries for files that no longer exist.
        live = {p for _, p, _ in entries}
        for path in [p for p in cache if p not in live]:
            del cache[path]
        self._plots_sent_ = keys
        return out

    def _apply_command(self, cmd):
        """Dashboard commands arriving via the heartbeat response
        (reference: web_status.py:197-243 /service)."""
        name = cmd.get("command")
        sid = cmd.get("slave")
        self.info("dashboard command: %s %s", name, sid or "")
        if name == "stop":
            self.stop()
        elif self.server is not None and sid:
            if name == "pause":
                self.server.pause_slave(sid)
            elif name == "resume":
                self.server.resume_slave(sid)

    def stop(self):
        self._heartbeat_stop.set()
        with self._procs_lock:
            procs = list(self._worker_procs)
        for node, proc in procs:
            if proc.poll() is None:
                proc.terminate()
        if self.server is not None:
            self.server.stop()
        if self.client is not None:
            self.client.stop()
        if self.workflow is not None and self.workflow.is_running:
            self.workflow.stop()
        self._finished.set()
        self._running.clear()

    @property
    def runtime(self):
        if self._start_time is None:
            return 0.0
        return time.time() - self._start_time
