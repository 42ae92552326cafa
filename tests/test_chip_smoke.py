"""chip_smoke.py's contract, pinned on the CPU at a tiny size: the LAST
line of its stdout is exactly `{"ok", "device": {"platform", "kind",
"count"}}`, truthful about the device (so `ok` is false here and the
exit code non-zero), with one JSON object per phase before it. Plus the
two things the script leans on: a compile cache that sits at one path in
every process, and launcher parents that never start a JAX backend
(a parent that holds the chip starves the children that need it).

The chip_smoke runs share one scratch directory inside the checkout, so
they stay in this one file (one xdist worker runs it, in order).
"""

import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = ["--log2-slots", "16", "--batch", "512", "--steps", "3", "--ids-per-field", "50"]


def _env(**extra):
    """One plain CPU device per child: the conftest's 8-device XLA_FLAGS
    is for the in-process fake cluster."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    for name in ("XFLOW_NUM_CPU_DEVICES", "JAX_COMPILATION_CACHE_DIR"):
        env.pop(name, None)
    env.update(extra)
    return env


def _run_smoke(args, cwd, **env):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py"), *args],
        capture_output=True, text=True, cwd=cwd, env=_env(**env), timeout=900,
    )
    lines = r.stdout.splitlines()
    assert lines, r.stderr[-2000:]
    return r, [json.loads(line) for line in lines]


def _assert_contract_shape(last: dict) -> None:
    assert set(last) == {"ok", "device"}, last
    assert set(last["device"]) == {"platform", "kind", "count"}, last


def test_chip_smoke_last_line_and_phases_on_cpu(tmp_path):
    r, objs = _run_smoke(TINY, tmp_path)
    assert r.returncode != 0
    assert not r.stdout.endswith("\n\n")
    *phases, last = objs
    _assert_contract_shape(last)
    assert last == {
        "ok": False, "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    by_name = {p["phase"]: p for p in phases}
    assert list(by_name) == ["gen_data", "train", "xla_parity", "serve", "warm_start"]
    # it ran everything for real, and says what was NOT the chip's path
    train = by_name["train"]
    assert train["engine"] == "sorted" and train["steps"] == 3
    assert train["parser"] == "native" and train["planner"] == "native"
    assert train["checks"]["platform_tpu"] is False
    assert train["checks"]["pallas_kernels"] is False and train["pallas_calls"] == 0
    assert by_name["xla_parity"]["checks"]["loss_parity"] is True
    assert by_name["xla_parity"]["checks"]["kernel_parity"] is False
    assert by_name["serve"]["ok"] is True
    assert by_name["serve"]["pctr_max_abs_err"] <= 1e-5
    assert not os.path.exists(os.path.join(REPO_ROOT, "chip_smoke_run"))


def test_chip_smoke_chips_option_runs_only_the_mesh_phase(tmp_path):
    r, objs = _run_smoke(["--chips", "4", *TINY], tmp_path, XFLOW_NUM_CPU_DEVICES="4")
    assert r.returncode != 0
    *phases, last = objs
    _assert_contract_shape(last)
    assert last["ok"] is False
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    assert [p["phase"] for p in phases] == ["gen_data", "mesh"]
    mesh = phases[1]
    assert mesh["engine"] == "fullshard"
    for check in ("loss_parity", "pctr_parity", "even_shards", "program_args_sharded"):
        assert mesh["checks"][check] is True, mesh
    assert len(set(mesh["state_bytes_per_device"])) == 1


def test_chip_smoke_alone_fails_with_the_same_last_line(tmp_path):
    """In a directory that holds the script and nothing else of the repo
    there is no program to drive: non-zero exit, no device claimed."""
    script = tmp_path / "chip_smoke.py"
    script.write_text(open(os.path.join(REPO_ROOT, "chip_smoke.py")).read())
    env = _env()
    env.pop("PYTHONPATH")
    r = subprocess.run(
        [sys.executable, str(script), *TINY], capture_output=True, text=True,
        cwd=tmp_path, env=env, timeout=300,
    )
    assert r.returncode != 0
    last = json.loads(r.stdout.splitlines()[-1])
    _assert_contract_shape(last)
    assert last == {"ok": False, "device": {"platform": None, "kind": None, "count": 0}}


def test_compile_cache_placed_from_outside_sets_no_directory_in_code(monkeypatch):
    import jax

    from xflow_tpu.compile_cache import enable_compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/outside")
    assert enable_compile_cache() == "/somewhere/outside"
    # no directory of its own; the metadata goes into the key wherever the
    # cache lies (a program is never read back with another commit's scopes)
    assert updates == [("jax_compilation_cache_include_metadata_in_key", True),
                       ("jax_traceback_in_locations_limit", 1)]


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(tmp_path):
    code = (
        "import jax; from xflow_tpu.compile_cache import enable_compile_cache; "
        "d = enable_compile_cache(); "
        "assert jax.config.jax_compilation_cache_dir == d; print(d)"
    )
    seen = []
    for cwd in (tmp_path, REPO_ROOT):
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            cwd=cwd, env=_env(), timeout=120,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        seen.append(r.stdout.strip())
    assert seen == [os.path.join(REPO_ROOT, ".jax_cache")] * 2


_LAUNCHER_PARENTS = """
import os, signal, sys, threading
import jax._src.xla_bridge as xb
from xflow_tpu.launch.cli import main

common = ["--model", "lr", "--log2-slots", "12", "--set", "model.num_fields=8"]
train = ["--train", "train", "--epochs", "1", "--batch-size", "64", *common]
assert main(["gen-data", "train", "--shards", "2", "--rows", "128",
             "--fields", "8", "--ids-per-field", "20"]) == 0
assert main(["launch-local", "--num-processes", "2", "--run-dir", "run", "--",
             *train, "--checkpoint-dir", "ckpt"]) == 0
assert not xb.backends_are_initialized(), "launch-local parent started a backend"
assert main(["launch-multislice", "--slices", "2", "--run-dir", "run_ms", "--",
             *train, "--set", "sync.mode=sync", "--set", "sync.every_steps=1"]) == 0
assert not xb.backends_are_initialized(), "launch-multislice parent started a backend"


class StopOnReady:
    # serve-fleet prints its ready line once every replica answers:
    # that is the moment to ask for the drained shutdown
    def write(self, text):
        sys.__stdout__.write(text)
        if '"fleet": true' in text:
            threading.Timer(0.2, os.kill, (os.getpid(), signal.SIGTERM)).start()

    def flush(self):
        sys.__stdout__.flush()


sys.stdout = StopOnReady()
rc = main(["serve-fleet", "--checkpoint-dir", "ckpt", "--replicas", "2", "--port", "0",
           "--run-dir", "run_fleet", *common])
sys.stdout = sys.__stdout__
assert rc == 0, rc
assert os.path.getsize("run_fleet/serve_router.jsonl") > 0
assert not xb.backends_are_initialized(), "serve-fleet parent started a backend"
print("PARENTS_STAYED_OFF_JAX")
"""


def test_launcher_parents_never_initialise_a_jax_backend(tmp_path):
    """launch-local, launch-multislice and serve-fleet run to their end
    (real children, router stream written) and the parent process has
    still not started a backend: on the chip it would own the device its
    children need."""
    r = subprocess.run(
        [sys.executable, "-c", _LAUNCHER_PARENTS], capture_output=True,
        text=True, cwd=tmp_path, env=_env(), timeout=600,
    )
    if "Multiprocess computations aren't implemented" in r.stderr:
        pytest.skip("multi-process CPU computations unsupported by this jax build")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "PARENTS_STAYED_OFF_JAX" in r.stdout
    for launcher in ("launch-local", "launch-multislice", "serve-fleet"):
        assert f"{launcher}: 2 " in r.stderr and "JAX_PLATFORMS=cpu" in r.stderr
