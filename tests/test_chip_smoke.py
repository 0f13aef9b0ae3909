"""chip_smoke.py's phases on CPU.

The phase functions run at the smollm-360m smoke config and small sizes
with the Pallas kernels in interpret mode; the ``--chips 4`` phase runs
in a subprocess on 4 forced host devices (the device count is fixed per
process). ``main`` itself must refuse a machine without a TPU, and a
directory holding the script alone.
"""
import dataclasses
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.config import get_smoke_arch  # noqa: E402
from repro.config.base import CascadeConfig, ProxyConfig  # noqa: E402

SIZES = chip_smoke.Sizes(docs=512, doc_len=32, ingest_batch=16,
                         paper_docs=3000, paper_dim=64, chunk=1024,
                         judge_batch=16)
FOUR_SIZES = chip_smoke.Sizes(docs=64, doc_len=16, ingest_batch=8,
                              paper_docs=2001, paper_dim=64, chunk=700)
ARCH = get_smoke_arch(chip_smoke.ARCH)


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    return env


def _json_lines(stdout: str):
    out = []
    for line in stdout.splitlines():
        try:
            out.append(json.loads(line))
        except ValueError:
            pass
    return out


def test_one_chip_phases_at_smoke_size(tmp_path, capsys):
    """Phases (b)-(f) in order, every check on, kernels interpreted."""
    pcfg = ProxyConfig(hidden_dim=64, latent_dim=32, proj_dim=16,
                       phase1_steps=30, phase2_steps=30, batch_size=64,
                       contrastive_impl="interpret")
    chip_smoke.run_one_chip(ARCH, SIZES, tmp_path, proxy_cfg=pcfg,
                            ccfg=CascadeConfig(accuracy_target=0.9),
                            interpret=True)
    out = capsys.readouterr().out
    for phase in "bcdef":
        assert f"[{phase}] check" in out, out
    assert "paths=('fused',)" in out


def test_failed_check_raises():
    with pytest.raises(chip_smoke.SmokeFailure, match="boom"):
        chip_smoke.check(False, "boom")


_FOUR = textwrap.dedent("""
    import sys, tempfile
    from pathlib import Path
    sys.path.insert(0, {root!r})
    import chip_smoke
    from repro.config import get_smoke_arch
    from repro.engine import ScoringExecutor
    from repro.launch.mesh import make_scoring_mesh

    try:
        ScoringExecutor(use_kernel=True, mesh=make_scoring_mesh(4))
    except ValueError as e:
        print("REFUSED", e)
    sizes = chip_smoke.Sizes(**{sizes!r})
    with tempfile.TemporaryDirectory() as d:
        chip_smoke.phase_four_chips(get_smoke_arch("smollm-360m"), sizes,
                                    Path(d))
    print("FOUR-OK")
""")


@pytest.fixture(scope="module")
def four_devices():
    script = _FOUR.format(root=str(ROOT),
                          sizes=dataclasses.asdict(FOUR_SIZES))
    env = _env(XLA_FLAGS=os.environ.get("XLA_FLAGS", "")
               + " --xla_force_host_platform_device_count=4")
    return subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, env=env,
                          timeout=600)


def test_four_chip_phase_on_4_host_devices(four_devices):
    assert four_devices.returncode == 0, four_devices.stderr
    assert "FOUR-OK" in four_devices.stdout
    assert "paths=('shard',)" in four_devices.stdout


def test_executor_refuses_kernel_with_multi_device_mesh(four_devices):
    assert four_devices.returncode == 0, four_devices.stderr
    assert "REFUSED use_kernel=True scores on one device" \
        in four_devices.stdout


def test_main_refuses_without_tpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], capture_output=True,
        text=True, cwd=ROOT, timeout=300,
        env=_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not _json_lines(proc.stdout)


def test_main_refuses_outside_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, "chip_smoke.py"],
                          capture_output=True, text=True, cwd=tmp_path,
                          env=env, timeout=300)
    assert proc.returncode != 0
    assert not _json_lines(proc.stdout)
