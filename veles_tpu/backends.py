"""Device and mesh abstraction.

Capability parity with the reference backend layer (reference:
veles/backends.py — ``Device:184``, ``BackendRegistry:166``,
``OpenCLDevice:426``, ``CUDADevice:745``, ``NumpyDevice:917``,
``AutoDevice:406``): a registry of backends selected by name or
environment, a per-device "computing power" benchmark used for load
balancing (backends.py:539-566, accelerated_units.py:699-817), and
device bring-up.

TPU-era mapping: the backends are **cpu** (host XLA, used by tests with
a forced 8-device topology) and **tpu**; a device owns the *set* of
local ``jax.Device`` chips plus an optional ``jax.sharding.Mesh`` over
all addressable chips.  The reference's OpenCL GEMM autotune database
(backends.py:623-731, devices/device_infos.json) has no equivalent job
here — XLA owns tiling — so its role (persisted per-device perf facts)
is filled by the measured-power cache.
"""

import json
import os
import time

from .config import root, get as config_get
from .error import DeviceNotFoundError
from .logger import Logger


#: The persistent XLA compile cache when the environment names none:
#: ONE fixed directory inside the checkout (.gitignore lists it).  The
#: path is part of every cache key, so it is never built from a temp
#: name, a pid or a time — a directory that moves never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable_compilation_cache():
    """Turns on JAX's persistent compile cache (replaces the
    reference's tar.gz program cache keyed by device,
    accelerated_units.py:599-666) and returns its directory.  Called
    by every entry point that compiles — the CLI, the server,
    ``bench.py``, ``chip_smoke.py`` and ``StepCompiler``.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is set in code, so whoever runs the program places the
    cache; otherwise it lives at :data:`COMPILE_CACHE_DIR`."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    if jax.config.jax_compilation_cache_dir != COMPILE_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir",
                          COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def tpu_available():
    """Whether the default JAX backend is a TPU — the platform half
    of every kernel-dispatch decision in ``ops`` (the geometry half
    is the kernel's own ``supports*``).  A backend that fails to
    initialize raises here: a chip that cannot be reached is not a
    reason to run on another device."""
    import jax
    return jax.default_backend() == "tpu"


def device_entry():
    """``{"platform", "kind", "count"}`` of the devices JAX runs this
    process on — the one entry the CLI's result file, the server's
    ``/stats``, ``bench.py`` and ``chip_smoke.py`` carry so that a
    number can always be traced to the device it came from."""
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


class BackendRegistry(type):
    """Backend name → Device class (reference: backends.py:166)."""

    backends = {}

    def __init__(cls, name, bases, clsdict):
        super(BackendRegistry, cls).__init__(name, bases, clsdict)
        backend = clsdict.get("BACKEND")
        if backend is not None:
            BackendRegistry.backends[backend] = cls


class Device(Logger, metaclass=BackendRegistry):
    """A compute placement: one or more local chips + optional mesh
    (reference: backends.py:184)."""

    BACKEND = None

    def __init__(self, **kwargs):
        super(Device, self).__init__()
        self._jax_devices = None
        self._mesh = None
        self._power = None
        self.sync_run = bool(config_get(root.common.engine.sync_run,
                                        False))

    # -- factory -----------------------------------------------------------

    @staticmethod
    def create(backend="auto", **kwargs):
        """Selects a backend by name, ``VELES_TPU_BACKEND``, or
        auto-detection (reference: backends.py:190-197)."""
        backend = backend or "auto"
        if backend == "auto":
            backend = os.environ.get("VELES_TPU_BACKEND", "auto")
        if backend == "auto":
            import jax
            try:
                platform = jax.default_backend()
            except RuntimeError as e:
                raise DeviceNotFoundError(str(e))
            backend = "tpu" if platform == "tpu" else "cpu"
        cls = BackendRegistry.backends.get(backend)
        if cls is None:
            raise DeviceNotFoundError(
                "unknown backend %r (have: %s)" %
                (backend, sorted(BackendRegistry.backends)))
        return cls(**kwargs)

    # -- chips -------------------------------------------------------------

    @property
    def jax_devices(self):
        if self._jax_devices is None:
            import jax
            # Local (addressable) devices: under multi-controller
            # jax.distributed, jax.devices() is the GLOBAL list whose
            # first entries belong to process 0 — placing unsharded
            # uploads there would crash every other process.  Global
            # meshes are built explicitly (parallel.make_mesh).
            self._jax_devices = jax.local_devices()
        return self._jax_devices

    @property
    def default_device(self):
        return self.jax_devices[0]

    @property
    def num_devices(self):
        return len(self.jax_devices)

    @property
    def backend_name(self):
        return self.BACKEND

    @property
    def is_tpu(self):
        return False

    @property
    def is_attached(self):
        return True

    # -- mesh --------------------------------------------------------------

    def make_mesh(self, axes=None):
        """Builds a ``jax.sharding.Mesh`` over all local chips.

        ``axes`` maps axis name → size; ``-1`` means "all remaining
        chips".  Default: 1-D data-parallel mesh over every chip.
        """
        import jax
        import numpy as np
        from jax.sharding import Mesh
        devices = self.jax_devices
        if axes is None:
            axes = {"data": len(devices)}
        names, sizes = zip(*axes.items()) if axes else ((), ())
        sizes = list(sizes)
        total = len(devices)
        if -1 in sizes:
            known = 1
            for s in sizes:
                if s != -1:
                    known *= s
            sizes[sizes.index(-1)] = total // known
        count = 1
        for s in sizes:
            count *= s
        mesh_devices = np.array(devices[:count]).reshape(sizes)
        self._mesh = Mesh(mesh_devices, names)
        return self._mesh

    @property
    def mesh(self):
        if self._mesh is None:
            self.make_mesh()
        return self._mesh

    def sharding(self, *spec):
        """NamedSharding over this device's mesh."""
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    @property
    def replicated_sharding(self):
        return self.sharding()

    # -- computing power ---------------------------------------------------

    @property
    def compute_power(self):
        """GEMM-throughput scalar used for load balancing (reference:
        accelerated_units.py:836-851 ``DeviceBenchmark``); cached under
        ``root.common.dirs.cache``."""
        if self._power is None:
            self._power = self._load_or_measure_power()
        return self._power

    def _power_cache_path(self):
        cache_dir = config_get(root.common.dirs.cache)
        return os.path.join(cache_dir, "device_power.json") \
            if cache_dir else None

    def _power_key(self):
        dev = self.default_device
        return "%s:%s:%d" % (self.BACKEND,
                             getattr(dev, "device_kind", "unknown"),
                             self.num_devices)

    def _load_or_measure_power(self):
        path = self._power_cache_path()
        key = self._power_key()
        if path and os.path.isfile(path):
            try:
                with open(path) as fin:
                    cache = json.load(fin)
                if key in cache:
                    return cache[key]
            except (ValueError, OSError):
                pass
        power = self.measure_power()
        if path:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            cache = {}
            if os.path.isfile(path):
                try:
                    with open(path) as fin:
                        cache = json.load(fin)
                except (ValueError, OSError):
                    cache = {}
            cache[key] = power
            with open(path, "w") as fout:
                json.dump(cache, fout)
        return power

    def measure_power(self, size=3000, repeats=3):
        """Times a ``size×size`` matmul (the reference used a 3001×3001
        GEMM, accelerated_units.py:699-817) → 1000/dt scalar."""
        import jax
        import jax.numpy as jnp
        key = jax.random.PRNGKey(0)
        a = jax.device_put(
            jax.random.normal(key, (size, size), dtype=jnp.float32),
            self.default_device)
        f = jax.jit(lambda x: x @ x)
        f(a).block_until_ready()  # compile
        t0 = time.time()
        for _ in range(repeats):
            out = f(a)
        out.block_until_ready()
        dt = (time.time() - t0) / repeats
        power = 1000.0 / dt
        self.info("measured compute power: %.1f (%.1f GFLOP/s)",
                  power, 2.0 * size ** 3 / dt / 1e9)
        return power

    def __repr__(self):
        return "<%s %d chips>" % (type(self).__name__, self.num_devices)


class CPUDevice(Device):
    """Host XLA backend — also the test backend with a forced virtual
    multi-chip topology (replaces the reference's NumpyDevice,
    backends.py:917)."""

    BACKEND = "cpu"

    @property
    def jax_devices(self):
        if self._jax_devices is None:
            import jax
            self._jax_devices = [d for d in jax.local_devices()
                                 if d.platform == "cpu"] or \
                jax.local_devices()
        return self._jax_devices


#: Reference-compatible alias.
NumpyDevice = CPUDevice


class TPUDevice(Device):
    """TPU backend (replaces OpenCLDevice/CUDADevice,
    backends.py:426,745)."""

    BACKEND = "tpu"

    @property
    def is_tpu(self):
        return True

    @property
    def jax_devices(self):
        if self._jax_devices is None:
            import jax
            # Local devices only — see Device.jax_devices (multi-host
            # placement must never target another process's chips).
            devices = jax.local_devices()
            if devices[0].platform != "tpu":
                raise DeviceNotFoundError(
                    "no TPU platform available (got %s)" %
                    devices[0].platform)
            self._jax_devices = devices
        return self._jax_devices


class AutoDevice(Device):
    """Explicit ``auto`` registration (reference: backends.py:406)."""

    BACKEND = "auto_marker"

    def __new__(cls, **kwargs):
        return Device.create("auto", **kwargs)
