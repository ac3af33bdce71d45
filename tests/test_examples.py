"""The examples/ scripts stay runnable (reference analog: tests/book
end-to-end scripts-as-tests)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.slow


def _run(name, timeout=420):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"   # examples are CPU demos
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", name)],
        capture_output=True, text=True, env=env, timeout=timeout, cwd=REPO)


@pytest.mark.parametrize("script", ["train_lenet.py",
                                    "pretrain_llama_mesh.py",
                                    "generate_text.py",
                                    "recommender_host_embedding.py"])
def test_example_runs(script):
    proc = _run(script)
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-1500:])


def test_serve_capi_compiles(tmp_path):
    subprocess.run(["make", "-C", os.path.join(REPO, "csrc"), "capi"],
                   check=True)
    out = str(tmp_path / "serve")
    proc = subprocess.run(
        ["gcc", os.path.join(REPO, "examples", "serve_capi.c"), "-o", out,
         f"-I{REPO}/csrc", f"-L{REPO}/csrc", "-lpaddle_tpu_capi",
         f"-Wl,-rpath,{REPO}/csrc"], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
