"""Multi-process CPU pod harness: spawn N coordinator-connected
``jax.distributed`` processes over loopback and run a script body in
each — the test-side stand-in for an N-host pod, with the same
probe-and-skip discipline PR 7 established for
``test_two_process_distributed`` (jaxlibs without cross-process CPU
collectives fail the probe with "Multiprocess computations aren't
implemented on the CPU backend"; those containers SKIP the pod tests
cleanly instead of failing them).

Usage::

    from tests import pod_harness

    def test_something_multi_host(tmp_path):
        pod_harness.require_multiprocess(n=2)   # pytest.skip if absent
        outs = pod_harness.run_pod(BODY, n=2, outdir=str(tmp_path))
        # BODY ran with jax.distributed initialized in every process;
        # sys.argv = [script, process_id, coordinator_port, outdir]

Every worker gets the standard CPU pinning preamble (JAX_PLATFORMS=cpu
and its own forced host device count, set before ``import jax``) before
``jax.distributed.initialize``; the repo root is on ``sys.path`` so
bodies import ``deeplearning4j_tpu`` and ``tests.*`` helpers directly.
"""

from __future__ import annotations

import functools
import os
import socket
import subprocess
import sys
import textwrap

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PREAMBLE = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = \\
        "--xla_force_host_platform_device_count={local_devices}"
    import jax
    pid = int(sys.argv[1]); port = sys.argv[2]; outdir = sys.argv[3]
    sys.path.insert(0, {repo!r})
    jax.distributed.initialize(coordinator_address="127.0.0.1:" + port,
                               num_processes={n}, process_id=pid)
""")

_PROBE_BODY = textwrap.dedent("""
    import numpy as np
    from jax.experimental import multihost_utils
    multihost_utils.broadcast_one_to_all(np.ones(1, np.float32))
    print("PROBE_OK")
""")


def free_port() -> str:
    """Ephemeral coordinator port (a collision would read as
    'multi-process unsupported')."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return str(s.getsockname()[1])


def run_pod(body: str, n: int = 2, local_devices: int = 2,
            outdir: str = ".", timeout: float = 300.0,
            check: bool = True):
    """Run ``_PREAMBLE + body`` in ``n`` loopback-coordinated CPU
    processes. Returns a list of per-process ``(returncode, output)``
    pairs; ``check=True`` additionally asserts every process exited 0
    (embedding its tail of output in the failure)."""
    script = _PREAMBLE.format(repo=REPO_ROOT, n=n,
                              local_devices=local_devices) \
        + textwrap.dedent(body)
    port = free_port()
    procs = [subprocess.Popen(
        [sys.executable, "-c", script, str(i), port, str(outdir)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        env=dict(os.environ, JAX_PLATFORMS="cpu")) for i in range(n)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out.decode())
    except Exception:
        for p in procs:
            p.kill()
        raise
    results = [(p.returncode, o) for p, o in zip(procs, outs)]
    if check:
        for i, (rc, out) in enumerate(results):
            assert rc == 0, \
                f"pod worker {i}/{n} failed:\n{out[-3000:]}"
    return results


@functools.lru_cache(maxsize=None)
def cpu_multiprocess_supported(n: int = 2) -> bool:
    """Capability probe: can THIS jax/jaxlib run ``n``-process
    computations on the CPU backend? Feature-probed with ``n`` real
    loopback processes running the same ``broadcast_one_to_all`` the
    distributed paths need."""
    try:
        results = run_pod(_PROBE_BODY, n=n, local_devices=2,
                          timeout=120, check=False)
    except Exception:
        return False
    # exit code AND marker: a worker that prints PROBE_OK then crashes
    # in distributed shutdown must still read as UNSUPPORTED (skip,
    # not flaky-fail — the discipline the old test_cluster probe had)
    return all(rc == 0 and "PROBE_OK" in o for rc, o in results)


def require_multiprocess(n: int = 2) -> None:
    """``pytest.skip`` unless the container can run ``n``-process CPU
    collectives (the probe-and-skip discipline: pod paths run where CI
    supports them, skip cleanly where it doesn't)."""
    import pytest

    if not cpu_multiprocess_supported(n):
        pytest.skip(f"this jax/jaxlib cannot run {n}-process "
                    f"computations on the CPU backend (loopback "
                    f"collective probe failed)")
