"""chip_smoke.py never passes on a CPU; the smoke's phases run at
the rehearsal size; the compile cache goes where the one helper says."""

import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke
from quiver_tpu.utils import enable_compile_cache


@pytest.mark.parametrize(
    "argv", [["chip_smoke.py", "--small"], ["chip_smoke.py"]],
    ids=["smoke-small", "smoke"],
)
def test_no_silent_cpu_pass(argv):
    """No TPU -> a traceback naming the platform, a non-zero exit, and no
    result line on standard output."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, *argv], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120,
    )
    assert out.returncode != 0, out.stdout
    assert "platform 'cpu'" in out.stderr, out.stderr[-2000:]
    assert '"ok"' not in out.stdout, out.stdout


def test_phases_run_at_rehearsal_size():
    """The control flow of every one-chip phase on the CPU, through `run` —
    what `main` calls once it has seen a TPU. In a process of its own: the
    phases load some 350 programs (13,000 memory mappings), and this suite's
    one process already sits near the kernel's per-process limit."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c",
         "import chip_smoke, json; print(json.dumps(chip_smoke.run(['--small'])))"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(l) for l in out.stdout.splitlines() if l.startswith("{")]
    assert lines[-1]["platform"] == "cpu"      # run()'s return value
    assert "NOT the products shape" in lines[1]["note"]
    trained = {l["pipeline"] for l in lines if l.get("phase") == "train"}
    assert trained == {"fused", "dedup", "mixed"}
    served = [l for l in lines if "rows_bit_equal_to_replay" in l][0]
    assert served["rows_bit_equal_to_replay"] == served["requests"] == 512
    assert served["compiled_after_warmup"] == 0
    assert lines[-2]["phase"] == "done"
    assert not any("ok" in l for l in lines)  # only main() prints the result


def test_exception_in_a_phase_is_not_swallowed(monkeypatch):
    def boom(*a, **k):
        raise RuntimeError("injected")

    monkeypatch.setattr(chip_smoke, "train_phase", boom)
    with pytest.raises(RuntimeError, match="injected"):
        chip_smoke.run(["--small"])


def test_main_refuses_cpu(capsys):
    with pytest.raises(RuntimeError, match="platform 'cpu'"):
        chip_smoke.main()
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("env_dir", ["/some/dir", None], ids=["env-set", "env-unset"])
def test_compile_cache_placed_from_outside(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR set -> no directory is set in code; unset
    -> the fixed <checkout>/.jax_cache."""
    updates = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.append((k, v)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(ROOT, ".jax_cache")
        assert enable_compile_cache() == want
        assert updates == [("jax_compilation_cache_dir", want)]
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert enable_compile_cache() == env_dir
        assert updates == []


def test_edge_oracle_tells_edges_from_non_edges():
    import numpy as np

    indptr = np.array([0, 2, 3, 3])           # 0 -> {2, 1}, 1 -> {0}, 2 -> {}
    oracle = chip_smoke.EdgeOracle(indptr, np.array([2, 1, 0]))
    got = oracle.has_edges(np.array([0, 0, 1, 1, 2, 0]), np.array([1, 2, 0, 2, 0, 0]))
    assert got.tolist() == [True, True, True, False, False, False]
