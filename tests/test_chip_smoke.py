"""``chip_smoke.py`` without a chip.

The script refuses to run without a TPU, or away from its checkout, and
prints no result line then.  Its phases run here on the CPU at sizes a test
can afford, which checks their control flow and their own checks; the full
sizes run only on the chip.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load()


@pytest.fixture(scope="module")
def trained(smoke):
    return smoke.phase_train(nodes=16, rounds=4, items_per_node=32)


def _run_script(script: Path, argv: list[str], cwd: Path) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, str(script), *argv],
        capture_output=True, text=True, env=env, cwd=cwd, timeout=300,
    )


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_refuses_without_tpu(argv):
    out = _run_script(SCRIPT, argv, ROOT)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_refuses_outside_checkout(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    out = _run_script(lone, [], tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_phase_train_tiny(trained):
    assert trained["failed"] == []
    assert len(trained["train_loss"]) == 4


def test_phase_reference_tiny(smoke, trained):
    """On the CPU both sides of the comparison are the CPU: bit-equal."""
    out = smoke.phase_reference(16, 32, trained["train_loss"])
    assert out["failed"] == []
    assert out["rel_diff_highest"] == [0.0] * smoke.REF_ROUNDS


def test_phase_headline_tiny(smoke):
    out = smoke.phase_headline(n_nodes=8, per_node=32, rounds=40)
    assert out["failed"] == []
    assert out["corrected_test_loss"][-1] < smoke.LN10 - smoke.CORRECTED_MARGIN


def test_phase_serve_tiny(smoke):
    out = smoke.phase_serve(["--nodes", "8", "--horizon", "3", "--per-node", "32",
                             "--test-size", "64"])
    assert out["failed"] == [] and out["served"] > 0


@pytest.mark.parametrize("rendering", ["onehot", "gather"])
def test_phase_batch_select_tiny(smoke, monkeypatch, rendering):
    """Phase (e) with the rule forced to each rendering on the CPU (where
    it picks the gather): no bit differs from the plain gather."""
    monkeypatch.setattr(smoke.batching, "select_rendering", lambda *a: rendering)
    out = smoke.phase_batch_select(nodes=4, items_per_node=32, rounds=2)
    assert out["failed"] == []
    assert out["rendering"] == rendering
    assert out["values_compared"] == 2 * 4 * 128 * 784


def test_phase_four_chips_on_four_host_devices():
    """The --four-chips phase over 4 forced host devices: the sharded and
    one-device executors agree bitwise on the CPU, the state sits on all
    four devices, and the compiled halo matches the plan's accounting."""
    script = textwrap.dedent(
        f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import importlib.util, json
        spec = importlib.util.spec_from_file_location("chip_smoke", {str(SCRIPT)!r})
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out = mod.phase_four_chips(nodes=16, rounds=3, items_per_node=32, backend="sparse")
        print("RESULT", json.dumps(out))
        """
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, env=env, timeout=420
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(l for l in proc.stdout.splitlines() if l.startswith("RESULT "))
    out = json.loads(line[len("RESULT "):])
    assert out["failed"] == [], out
    assert out["params_bitwise"], out
    assert out["devices_holding_params"] == 4
    assert out["all_to_all_per_mix"] == out["all_to_all_planned"] > 0
