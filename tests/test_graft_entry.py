"""The multi-chip gate must survive a jax-pre-initialized caller.

``dryrun_multichip`` runs its body in a fresh subprocess because jax
backends are process-global: once the calling process has initialized
one (the driver's harness touches ``jax.devices()`` first) no in-process
swap to n virtual CPU devices is possible. These tests pin that
contract and the child's environment.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_multichip_survives_preinitialized_jax():
    # Simulate the driver: initialize jax (conftest pins cpu with 8
    # virtual devices; either way the backend is locked) BEFORE calling
    # the gate. The subprocess must make it pass regardless.
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # child must set its own device count
    proc = subprocess.run(
        [sys.executable, "-c",
         "import jax; jax.devices(); "
         "import __graft_entry__; __graft_entry__.dryrun_multichip(4)"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "dryrun_multichip(4): OK" in proc.stdout


def test_dryrun_child_env_is_cpu_with_its_own_device_count():
    # Whatever the parent's platform and device-count flag, the child
    # gets JAX_PLATFORMS=cpu and exactly one device-count flag: its own.
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "tpu"
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        "--xla_cpu_enable_fast_math=false")
    probe = (
        "import __graft_entry__, subprocess\n"
        "def spy(cmd, **kw):\n"
        "    e = kw['env']\n"
        "    assert e['JAX_PLATFORMS'] == 'cpu', e['JAX_PLATFORMS']\n"
        "    flags = e['XLA_FLAGS'].split()\n"
        "    assert [f for f in flags if 'device_count' in f] == "
        "['--xla_force_host_platform_device_count=2'], flags\n"
        "    assert '--xla_cpu_enable_fast_math=false' in flags\n"
        "    class R: returncode, stdout, stderr = 0, 'dryrun ok', ''\n"
        "    return R()\n"
        "subprocess.run = spy\n"
        "__graft_entry__.dryrun_multichip(2)\n"
        "print('CHILD ENV OK')\n"
    )
    proc = subprocess.run([sys.executable, "-c", probe], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "CHILD ENV OK" in proc.stdout
