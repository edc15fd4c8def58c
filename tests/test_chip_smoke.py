"""chip_smoke.py on the CPU: its phases at ``reduced()`` widths (which
catches wrong paths and arguments before a chip run), its refusal to
run without a TPU, and where the entry points keep the compile cache."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ok_lines(stdout: str):
    return [l for l in stdout.splitlines() if '"ok": true' in l]


def test_train_phase_reduced(smoke):
    out = smoke.train_phase(reduced=True, seq=64, batch=4, steps=3, docs=50)
    assert len(out["losses"]) == 3
    assert out["losses"][-1] < out["losses"][0]
    assert out["compile_s"] > 0


def test_serve_phase_reduced(smoke):
    out = smoke.serve_phase(reduced=True, slots=2, n_requests=4,
                            prompt_lens=(8, 24), max_new=4)
    for kv in ("fp32", "int8"):
        assert sorted(out[kv]["outputs"]) == [0, 1, 2, 3]
        assert all(len(t) == 4 for t in out[kv]["outputs"].values())


def test_serve_phase_flags_a_wrong_first_token(smoke, monkeypatch):
    """The first-token check is live: an engine whose prefill answers a
    token the reference ranks low must fail the phase."""
    from repro.serve import engine

    real = engine.ContinuousEngine._prefill_one

    def off_by_one(self, params, prompt):
        tok0, cache, n = real(self, params, prompt)
        return (tok0 + 1) % self.model.cfg.vocab_size, cache, n

    monkeypatch.setattr(engine.ContinuousEngine, "_prefill_one", off_by_one)
    with pytest.raises(smoke.SmokeFailure, match="first token"):
        smoke.serve_phase(reduced=True, slots=2, n_requests=2,
                          prompt_lens=(8, 16), max_new=2,
                          kv_dtypes=("fp32",))


def test_plans_phase_reduced_on_four_simulated_devices(subproc_env):
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from repro.launch import simulate_host_devices\n"
        "simulate_host_devices(4)\n"
        "import chip_smoke\n"
        "r = chip_smoke.plans_phase(reduced=True, batch=8, seq=64, "
        "steps=2, docs=100)\n"
        "import json; print(json.dumps(sorted(r['plans'])))\n" % ROOT)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=560, env=subproc_env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    from repro.core.plans import PLANS
    assert json.loads(out.stdout.splitlines()[-1]) == sorted(PLANS)
    assert "addressable param shards" in out.stdout


def test_refuses_without_a_tpu(subproc_env):
    env = dict(subproc_env, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                         text=True, timeout=300, env=env, cwd=ROOT)
    assert out.returncode != 0
    assert not _ok_lines(out.stdout)
    assert "no TPU" in out.stderr


def test_refuses_outside_the_repo(tmp_path):
    """Alone in a directory, without the program, the script fails."""
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"],
                         capture_output=True, text=True, timeout=300,
                         env=dict(env, JAX_PLATFORMS="cpu"), cwd=tmp_path)
    assert out.returncode != 0
    assert not _ok_lines(out.stdout)


_TRAIN = [sys.executable, "-m", "repro.launch.train", "--arch", "gpt2m",
          "--reduced", "--plan", "data", "--steps", "1", "--seq", "16",
          "--batch", "2", "--docs", "20"]


def test_compile_cache_follows_the_env_var(subproc_env, tmp_path):
    cache = tmp_path / "jax-cache"
    env = dict(subproc_env, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    out = subprocess.run(_TRAIN, capture_output=True, text=True,
                         timeout=300, env=env, cwd=tmp_path)
    assert out.returncode == 0, out.stderr[-2000:]
    assert cache.is_dir() and any(cache.iterdir())


def test_compile_cache_defaults_to_the_checkout(subproc_env, tmp_path):
    env = {k: v for k, v in subproc_env.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    code = ("from repro.launch import enable_compile_cache\n"
            "print(enable_compile_cache())\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env, cwd=tmp_path)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-1] == os.path.join(ROOT, ".jax_cache")
