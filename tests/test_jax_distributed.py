"""Real multi-process jax.distributed test (SURVEY §4: "multi-process
CPU jax.distributed loopback"; exercises launcher.py's
``jax.distributed.initialize`` path, which the in-process 8-device
tests cannot).

Two OS processes (coordinator + worker), 4 virtual CPU devices each,
form one 8-device global mesh and train distributed MNIST through the
REAL CLI (``python -m veles_tpu ... --jax-coordinator``): multi-
controller SPMD where the launcher auto-applies DP sharding over the
combined mesh and XLA's gradient psum rides the cross-process (Gloo)
collective backend."""

import json
import os
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MNIST = os.path.join(REPO, "veles_tpu", "znicz", "samples", "mnist.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_jax_distributed_mnist(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    coordinator = "127.0.0.1:%d" % _free_port()

    procs, outs, logs = [], [], []
    try:
        for pid in range(2):
            out = tmp_path / ("result%d.json" % pid)
            outs.append(out)
            log = open(str(tmp_path / ("stderr%d.log" % pid)),
                       "w+")
            logs.append(log)
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "veles_tpu", MNIST,
                 "root.mnist.max_epochs=3",
                 "root.mnist.learning_rate=0.1",
                 "--random-seed", "1234", "-v", "warning",
                 "--jax-coordinator", coordinator,
                 "--jax-num-processes", "2",
                 "--jax-process-id", str(pid),
                 "--result-file", str(out)],
                env=env, cwd=REPO, stderr=log))
        codes = [p.wait(timeout=600) for p in procs]
    finally:
        # One side dying must not orphan the other (it would block in
        # jax.distributed.initialize for its whole timeout).
        for p in procs:
            if p.poll() is None:
                p.kill()
    stderrs = []
    for log in logs:
        log.seek(0)
        stderrs.append(log.read())
        log.close()
    if any("Multiprocess computations aren't implemented"
           in text for text in stderrs):
        # Capability, not correctness: this jaxlib's CPU backend has
        # no cross-process collective implementation — the launcher
        # bring-up worked (initialize + mesh formation), the psum
        # itself cannot exist here.  Skip so environments WITH the
        # Gloo backend keep the full gate.
        import pytest
        pytest.skip("jaxlib CPU backend lacks multiprocess "
                    "collectives in this environment")
    assert codes == [0, 0], stderrs[0][-2000:] + stderrs[1][-2000:]

    results = [json.loads(o.read_text()) for o in outs]
    # Lockstep SPMD: both controllers computed the identical run
    # (everything but wall-clock runtime).
    assert results[0]["results"] == results[1]["results"]
    assert results[0]["mode"] == "distributed"
    assert results[0]["results"]["epochs"] == 3
    assert results[0]["results"]["min_validation_err"] < 0.15


def test_partial_distributed_flags_rejected():
    """--jax-coordinator without a process count (or vice versa) must
    fail loudly, not silently train N standalone copies."""
    from veles_tpu.__main__ import Main
    from veles_tpu.error import Bug
    import pytest
    m = Main([MNIST, "--jax-coordinator", "127.0.0.1:1"])
    m.parse()
    with pytest.raises(Bug):
        m._launcher_kwargs()
    m = Main([MNIST, "--jax-num-processes", "2"])
    m.parse()
    with pytest.raises(Bug):
        m._launcher_kwargs()
