"""Bring-up contracts that hold without a chip: where the compile cache goes, and
that the two chip-facing scripts (chip_smoke.py, benchmarks/run.py) refuse the CPU platform instead of falling back.

Each check needs a fresh interpreter (the cache directory is decided at import,
the scripts decide at start-up); they are started together, so the whole file
costs a few seconds.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRINT_CACHE_DIR = (
    "import pathway_tpu, jax; print(jax.config.jax_compilation_cache_dir)"
)


def _env(**overrides: str) -> dict:
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env.update(overrides)
    return env


@pytest.fixture(scope="module")
def fresh(tmp_path_factory):
    """Every fresh interpreter this file needs, started together (they are
    independent and each costs a couple of seconds of imports): name ->
    completed process."""
    tmp = tmp_path_factory.mktemp("bringup")
    asked = str(tmp / "asked-for")
    print_dir = [sys.executable, "-c", _PRINT_CACHE_DIR]
    run_py = [
        sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
        "--workload", "serve-dense-2m", "--seed", "1", "--seconds", "3", "--trace", "0",
    ]
    launches = {
        "cache_env": (print_dir, str(tmp), _env(JAX_COMPILATION_CACHE_DIR=asked)),
        "cache_default_elsewhere": (print_dir, str(tmp), _env()),
        "cache_default_in_repo": (print_dir, REPO, _env()),
        "chip_smoke": ([sys.executable, os.path.join(REPO, "chip_smoke.py")], REPO, _env()),
        "benchmark": (run_py, REPO, _env()),
    }
    procs = {
        name: subprocess.Popen(
            cmd, cwd=cwd, env=env, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        for name, (cmd, cwd, env) in launches.items()
    }
    done = {}
    for name, proc in procs.items():
        out, err = proc.communicate(timeout=180)
        done[name] = subprocess.CompletedProcess(proc.args, proc.returncode, out, err)
    done["asked"] = asked
    return done


def _printed_dir(proc) -> str:
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()[-1]


def test_compile_cache_dir_from_env_is_left_to_jax(fresh):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself and the package
    sets nothing: the directory is the one asked for, not the checkout's."""
    assert _printed_dir(fresh["cache_env"]) == fresh["asked"]


def test_compile_cache_dir_default_is_fixed_under_the_checkout(fresh):
    """Unset, the cache goes to one fixed directory inside the checkout, whatever
    the working directory and whichever interpreter asks."""
    first = _printed_dir(fresh["cache_default_elsewhere"])
    second = _printed_dir(fresh["cache_default_in_repo"])
    assert first == second == os.path.join(REPO, ".jax_cache")


def test_no_other_code_sets_a_compile_cache_dir():
    """One function in one place (pathway_tpu/__init__.py) decides it;
    chip_smoke.py, benchmarks/ and everything under the package only inherit."""
    hits = []
    for base, dirs, files in os.walk(REPO):
        dirs[:] = [
            d for d in dirs
            if not d.startswith(".")
            and d not in ("__pycache__", "tests", "chip_scratch", "chiprun_out")
        ]
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(base, name)
            with open(path, "r", encoding="utf-8") as f:
                text = f.read()
            if "jax_compilation_cache_dir" in text and "config.update" in text:
                hits.append(os.path.relpath(path, REPO))
    assert hits == [os.path.join("pathway_tpu", "__init__.py")], hits


def test_chip_smoke_refuses_the_cpu_platform(fresh):
    """No accelerator: non-zero exit before any graph is built, no result line."""
    proc = fresh["chip_smoke"]
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stdout  # its first act: say what JAX found
    assert '"ok"' not in proc.stdout
    assert "encoder layers" not in proc.stdout  # no phase started


def test_benchmark_refuses_to_measure_without_a_chip(fresh):
    """`python benchmarks/run.py` on the CPU platform without `--rehearse` stops
    non-zero before any set-up and says it has no result; it does not fall back."""
    proc = fresh["benchmark"]
    assert proc.returncode != 0
    assert "no result" in proc.stderr
    assert "device: cpu" in proc.stdout + proc.stderr  # its first act: say what JAX found
    assert "retrieve_p50_ms" not in proc.stdout  # no metric line under a device name


def test_cluster_rank_on_an_accelerator_stops_with_the_cause(monkeypatch):
    """A rank of a multi-process cluster (or a replica child) that finds JAX on
    an accelerator, or cannot open it because a sibling holds it, stops with a
    message that names the cause; on the CPU platform it passes."""
    import jax

    from pathway_tpu.parallel.mesh import require_cpu_platform

    require_cpu_platform("rank 0 of 2")  # the test platform is cpu

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="rank 1 of 2 opened the 'tpu'.*JAX_PLATFORMS=cpu"):
        require_cpu_platform("rank 1 of 2")

    def held_by_sibling():
        raise RuntimeError("Unable to initialize backend 'tpu': ABORTED: libtpu lockfile")

    monkeypatch.setattr(jax, "default_backend", held_by_sibling)
    with pytest.raises(RuntimeError, match="another process holds it.*one process at a time"):
        require_cpu_platform("replica 3")
