"""CPU rehearsal of chip_smoke.py: its serving phase at SMOKE widths,
its no-swallow guarantee, and its refusal to run without a TPU."""

import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    from repro import configs
    return configs.get_smoke("qwen2.5-3b")


def test_serve_phase_completes_and_fails_over(smoke, cfg):
    res = smoke.serve_phase(cfg, workers=2, max_new_tokens=4)
    # requests complete with all their tokens, before and after the crash
    assert [len(t) for _p, t in res["after"]] == [5, 5]
    # the crash failed over to the planned warm backup
    assert res["mode"] == "warm"
    assert res["warm"][0] != res["primary"][0]
    assert res["warm_variant"].name == res["warm"][1]
    assert res["mttr_s"] > 0 and "detect" in res["phases"]
    rungs = {r["variant"]: r for r in res["rungs"]}
    assert rungs[res["warm"][1]]["mem_bytes"] < \
        rungs[res["primary"][1]]["mem_bytes"]
    assert all(r["device_bytes"] > 0 for r in res["rungs"])
    # float32 SMOKE weights: cached decode tracks the uncached forward
    ref = res["reference"]
    assert ref["max_abs_diff"] < 1e-3
    assert ref["argmax_agree"] == ref["positions"]
    # the same rung rebuilt from the same seed gives the same tokens
    replay = smoke.replay_on(jax.devices()[0], res["warm_variant"],
                             *res["engine_shape"], res["after"])
    assert replay == [t for _p, t in res["after"]]


def test_serve_phase_fails_when_a_load_raises(smoke, cfg, monkeypatch):
    from repro.serving import server

    real = server.checkpoint_params

    def broken(variant):
        if variant.width_mult < 1.0 or variant.depth_mult < 1.0:
            raise ValueError(f"injected load failure: {variant.name}")
        return real(variant)

    monkeypatch.setattr(server, "checkpoint_params", broken)
    with pytest.raises(ValueError, match="injected load failure"):
        smoke.serve_phase(cfg, workers=2, max_new_tokens=2)


def test_serve_phase_fails_when_a_load_raises_a_jax_error(smoke, cfg,
                                                          monkeypatch):
    """JAX raises an HBM OOM or a failed compile as a RuntimeError, the
    type a dead server's load raises too: on a live worker it is a
    fault, not a death."""
    from repro.serving import server

    real = server.checkpoint_params

    def oom(variant):
        if variant.width_mult < 1.0 or variant.depth_mult < 1.0:
            raise jax.errors.JaxRuntimeError(
                f"RESOURCE_EXHAUSTED: injected OOM loading {variant.name}")
        return real(variant)

    monkeypatch.setattr(server, "checkpoint_params", oom)
    with pytest.raises(jax.errors.JaxRuntimeError, match="injected OOM"):
        smoke.serve_phase(cfg, workers=2, max_new_tokens=2)


def test_serve_phase_fails_when_a_decode_step_raises(smoke, cfg,
                                                     monkeypatch):
    """A decode step that raises on a live worker reaches the run at
    once, not as a request that never finishes."""
    from repro.serving.engine import InferenceEngine

    def broken(self):
        raise jax.errors.JaxRuntimeError("INTERNAL: injected step failure")

    monkeypatch.setattr(InferenceEngine, "step", broken)
    with pytest.raises(jax.errors.JaxRuntimeError,
                       match="injected step failure"):
        smoke.serve_phase(cfg, workers=2, max_new_tokens=2,
                          reference=False)


def test_compile_cache_dir_from_env_or_repo(monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without
    it the cache goes to the fixed .jax_cache/ at the repo root."""
    from repro.launch.compile_cache import enable_compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert enable_compile_cache() == "/elsewhere" and calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(ROOT, ".jax_cache")
    assert enable_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]


def _env(cwd, **extra):
    return {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
            "HOME": os.environ.get("HOME", "/tmp"),
            "JAX_PLATFORMS": "cpu",
            "JAX_COMPILATION_CACHE_DIR": str(cwd), **extra}


def _run(script, cwd):
    return subprocess.run([sys.executable, script], capture_output=True,
                          text=True, timeout=300, cwd=cwd, env=_env(cwd))


REPLICAS = """
import importlib.util, jax
spec = importlib.util.spec_from_file_location("chip_smoke", {script!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
from repro import configs
devs = jax.devices()
assert len(devs) == 4, devs
res = smoke.replica_phase(configs.get_smoke("qwen2.5-3b"), devs,
                          max_new_tokens=3)
on = {{r["server"]: r["device"] for r in res["rungs"]}}
assert on["s0-0"] == str(devs[0]) and on["s0-1"] == str(devs[1]), on
assert on[res["primary"][0]] != on[res["warm"][0]]
assert [len(t) for _p, t in res["after"]] == [4, 4]
print("REPLICAS-OK")
"""


def test_replica_phase_one_worker_per_device(tmp_path):
    """--chips 4's path on four host devices: worker i on device i, the
    backup on another device than the primary, and its tokens equal to
    the replay on device 0 (replica_phase raises otherwise)."""
    env = _env(tmp_path, PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c",
                          REPLICAS.format(script=SCRIPT)],
                         capture_output=True, text=True, timeout=300,
                         cwd=tmp_path, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "REPLICAS-OK" in out.stdout


def test_chip_smoke_exits_nonzero_without_tpu(tmp_path):
    out = _run(SCRIPT, tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_chip_smoke_alone_fails(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    out = _run(str(alone), tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
