"""A configuration names its model family, and the harness finds all it
knows of that architecture by the name: ``families/<family>.py`` (the
program's config, counts and rehearsal sizes) and
``references/<family>.py`` (the plain mathematics).  So a new
architecture is new files only."""
import glob
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import rehearse  # noqa: E402

ROOT, BENCH = rehearse.ROOT, rehearse.BENCH

from chipbench import spec  # noqa: E402

# the harness, which may know no architecture by its key names
HARNESS = sorted(glob.glob(os.path.join(BENCH, "chipbench", "*.py"))
                 + glob.glob(os.path.join(BENCH, "metrics", "*.py"))
                 + [os.path.join(BENCH, "run.py"),
                    os.path.join(BENCH, "calibrate.py")])
REFERENCES = sorted(glob.glob(os.path.join(BENCH, "references", "*.py")))
GPT2_KEY = re.compile(r'gpt2_ref|references[./]gpt2|'
                      r'"n_(embd|inner|layer|head|positions)"')
IMPORTS_PROGRAM = re.compile(r"^\s*(from|import)\s+repro\b", re.M)


def _bench_copy(tmp_path):
    """A benchmark root at ``tmp_path``: ``BENCHMARK.json`` and the files
    under its paths."""
    spec_json = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in spec_json["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path / "benchmarks" / "chip"


def test_a_family_is_found_by_name(tmp_path):
    """The GPT-2 family copied under another name, the original taken
    away, and a configuration and a cell that name the copy: the CPU
    rehearsal of the training traffic reads ``correct``."""
    bench = _bench_copy(tmp_path)
    for kind in ("families", "references"):
        os.rename(bench / kind / "gpt2.py", bench / kind / "twin.py")
    config = json.load(open(bench / "configs" / "gpt2m.json"))
    (bench / "configs" / "twin.json").write_text(
        json.dumps(dict(config, name="twin", family="twin")))
    # the limits at the rehearsal's tiny size, as SOUND_AT_TINY_SIZE
    (bench / "limits" / "twin-train.json").write_text(json.dumps(
        {"limits": {"loss_gap": 0.05, "grad_gap.median": 0.05,
                    "change_gap": 0.05}}))
    entry = {"name": "twin-train", "config": "twin",
             "traffic": "train_data_8x1024", "chips": 1}

    cell = rehearse.tiny_cell("twin-train", str(tmp_path), entry)
    assert cell.family.name == "twin"
    for module in (cell.family.program, cell.family.math):
        assert module.__file__.startswith(str(bench))
    line = rehearse.rehearse("twin-train", root=str(tmp_path), entry=entry)
    assert line["correct"] is True, line["checks"]
    assert set(line["checks"]) == {"loss_gap", "grad_gap.median",
                                   "change_gap"}


def _run_cmd(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "gpt2m-train-1chip", "--seed", "3000000001", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


@pytest.mark.parametrize("family,missing", [
    (None, "configs/gpt2m.json"),
    ("nosuch", "families/nosuch.py"),
    ("halfway", "references/halfway.py"),
])
def test_a_configuration_without_its_family_stops_the_run(tmp_path, family,
                                                          missing):
    bench = _bench_copy(tmp_path)
    path = bench / "configs" / "gpt2m.json"
    config = json.load(open(path))
    del config["family"]
    if family:
        config["family"] = family
    path.write_text(json.dumps(config))
    shutil.copy(bench / "families" / "gpt2.py",
                bench / "families" / "halfway.py")
    res = _run_cmd(tmp_path)
    assert res.returncode == 2, res.stderr
    assert os.path.join(str(tmp_path), "benchmarks", "chip",
                        missing) in res.stderr
    assert not [l for l in res.stdout.splitlines() if l.startswith("{")]


@pytest.mark.parametrize("name,expected", [
    ("gpt2m", dict(name="gpt2m", n_layers=24, d_model=1024, n_heads=16,
                   n_kv_heads=16, d_ff=4096, max_seq_len=1024,
                   source="https://huggingface.co/openai-community/"
                          "gpt2-medium")),
    ("gpt2L", dict(name="gpt2L", n_layers=30, d_model=1280, n_heads=20,
                   n_kv_heads=20, d_ff=5120, max_seq_len=1024,
                   source="https://arxiv.org/abs/2602.02632")),
])
def test_gpt2_program_config_is_the_model_config_of_before(name, expected):
    from repro.configs.base import ModelConfig
    cfg = json.load(open(os.path.join(BENCH, "configs", f"{name}.json")))
    family = spec.load_family(cfg["family"])
    got = family.program.program_config(cfg)
    want = ModelConfig(
        family="dense", vocab_size=50257, rope_theta=0.0, norm="layernorm",
        norm_eps=1e-5, activation="gelu", tie_embeddings=True,
        dtype="bfloat16", **expected)
    assert got == want
    assert got.head_dim == 64


def test_gpt2_program_config_refuses_another_architecture():
    cfg = json.load(open(os.path.join(BENCH, "configs", "gpt2m.json")))
    program = spec.load_family("gpt2").program
    with pytest.raises(ValueError):
        program.program_config(dict(cfg, position_embedding="rope"))
    with pytest.raises(ValueError):
        program.program_config(dict(cfg, activation_function="silu"))


@pytest.mark.parametrize("path", HARNESS,
                         ids=lambda p: os.path.relpath(p, BENCH))
def test_the_harness_names_no_gpt2_key(path):
    text = open(path).read()
    assert not GPT2_KEY.search(text), GPT2_KEY.search(text).group(0)


@pytest.mark.parametrize("path", REFERENCES,
                         ids=lambda p: os.path.relpath(p, BENCH))
def test_a_reference_imports_nothing_of_the_program(path):
    assert not IMPORTS_PROGRAM.search(open(path).read())


def test_calibrate_names_the_faults_of_the_reference(capsys):
    sys.path.insert(0, BENCH)
    import calibrate
    from chipbench import reference
    with pytest.raises(SystemExit):
        calibrate.main(["--help"])
    out = capsys.readouterr().out
    assert all(fault in out for fault in reference.FAULTS[1:]), out


def test_a_family_name_is_a_name():
    with pytest.raises(spec.SpecError):
        spec.load_family("../chipbench/spec")
