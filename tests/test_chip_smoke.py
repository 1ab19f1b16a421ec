"""chip_smoke.py's contract, as far as a machine without a chip can pin
it, and the compile-cache rule it relies on.

- without a TPU the script is a failure before any work: exit code 2,
  nothing on stdout, also in a directory that holds nothing else of the
  repo;
- its control flow runs end to end here under the loudly labelled
  dry-run argument, which cannot print the passing result;
- ``optimize.aot_cache.place_compile_cache``: a directory given through
  ``JAX_COMPILATION_CACHE_DIR`` is left alone and none is set in code;
  unset, the cache is one fixed path in the checkout, the same in every
  process; the AOT path reads and writes it, sub-second executables
  included.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, env=None, cwd=REPO, timeout=900):
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_no_chip_is_a_failure_before_any_work():
    proc = _run([SMOKE])
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout == ""                     # prints no result
    assert "no chip" in proc.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = _run([str(tmp_path / "chip_smoke.py")], env=env,
                cwd=str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout == ""


def _dry_run(devices: int):
    env = dict(os.environ, XLA_FLAGS=(
        f"--xla_force_host_platform_device_count={devices}"))
    proc = _run([SMOKE, "--dry-run-on-cpu-at-tiny-size"], env=env)
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["ok"] is False and result["dry_run"] is True
    assert result["device"] == {"platform": "cpu", "kind": "cpu",
                                "count": devices}
    body = lines[:-1]
    assert all("DRY RUN (not a chip run) platform: cpu" in l for l in body)
    return body


def test_dry_run_covers_every_phase_and_cannot_pass():
    """One device, like the machine the driver checks on: train, serve,
    every kernel, and the four-chip phase saying it does not apply."""
    from deeplearning4j_tpu import kernels

    body = _dry_run(devices=1)
    phases = [l.split("]")[0].lstrip("[") for l in body]
    assert phases[:3] == ["env", "train", "serve"]
    assert "compiles_after_warmup=0" in body[1]
    assert "compiles_after_warmup=0" in body[2]
    assert "token-identical-to-dec.generate" in body[2]
    # every registry kernel, built for the backend jax reports
    kernel_lines = [l for l in body if l.startswith("[kernels]")]
    assert {l.split("kernel=")[1].split()[0] for l in kernel_lines} \
        == set(kernels.REGISTRY.ids())
    assert all("interpret=True" in l for l in kernel_lines)
    four = [l for l in body if l.startswith("[four_chip]")]
    assert len(four) == 1 and "status=not-applicable" in four[0]


@pytest.mark.slow
def test_dry_run_four_chip_phase_runs_both_exchanges():
    """Four virtual devices, the fewest on which the four-chip phase
    runs (two more ResNet-50 compiles: kept out of tier-1's time)."""
    four = [l for l in _dry_run(devices=4) if l.startswith("[four_chip]")]
    assert [l.split("mode=")[1].split()[0] for l in four] \
        == ["data-parallel", "zero"]
    assert "optimizer_share_per_device=0.25" in four[1]


# --------------------------------------------------------------------------
# the compile-cache rule
# --------------------------------------------------------------------------

_PRINT_DIR = ("from deeplearning4j_tpu.optimize import aot_cache; import jax; "
              "print(aot_cache.place_compile_cache()); "
              "print(jax.config.jax_compilation_cache_dir)")


def test_cache_dir_from_the_environment_is_left_alone(tmp_path, monkeypatch):
    import jax

    from deeplearning4j_tpu.optimize import aot_cache

    proc = _run(["-c", _PRINT_DIR],
                env=dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [str(tmp_path)] * 2
    # ... because the code sets NO directory on that branch
    calls = []
    monkeypatch.setenv(aot_cache.COMPILE_CACHE_ENV, str(tmp_path))
    monkeypatch.setattr(aot_cache, "_COMPILE_CACHE_PLACED", False)
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append(name))
    aot_cache.place_compile_cache()
    assert "jax_compilation_cache_dir" not in calls


def test_unset_the_cache_is_one_fixed_path_in_every_process():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    seen = set()
    for _ in range(2):
        proc = _run(["-c", _PRINT_DIR], env=env)
        assert proc.returncode == 0, proc.stderr[-2000:]
        seen.update(proc.stdout.split())
    assert seen == {os.path.join(REPO, ".jax_cache")}


_AOT_PROBE = """
import jax, jax.numpy as jnp
from deeplearning4j_tpu.optimize import aot_cache
hits = []
jax.monitoring.register_event_listener(
    lambda e, **kw: hits.append(e)
    if e == "/jax/compilation_cache/cache_hits" else None)
step = aot_cache.wrap(jax.jit(lambda a, b: a @ b + 1.0), "g", "probe")
x = jnp.ones((8, 8))
assert float(step(x, x)[0, 0]) == 9.0
assert aot_cache.stats()["misses"] == 1     # through lowered.compile()
print(len(hits))
"""


def test_aot_path_reads_and_writes_the_cache(tmp_path):
    """A sub-second executable compiled through ``AotStep`` lands in the
    cache directory and is a hit in the next process."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_ENABLE_COMPILATION_CACHE="true")
    env.pop("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", None)
    first = _run(["-c", _AOT_PROBE], env=env)
    assert first.returncode == 0, first.stderr[-2000:]
    assert os.listdir(tmp_path), "nothing was written to the cache"
    assert int(first.stdout.split()[-1]) == 0
    second = _run(["-c", _AOT_PROBE], env=env)
    assert second.returncode == 0, second.stderr[-2000:]
    assert int(second.stdout.split()[-1]) >= 1


def test_tier1_itself_does_not_fill_the_checkout_cache():
    # conftest turned the persistent cache off for this process and its
    # children, whatever directory the rule above names
    import jax

    assert os.environ["JAX_ENABLE_COMPILATION_CACHE"] == "false"
    assert jax.config.jax_enable_compilation_cache is False
