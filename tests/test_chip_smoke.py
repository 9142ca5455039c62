"""CPU rehearsal of ``chip_smoke.py``: its phase functions at reduced
size, so the script cannot rot between chip runs.  The sizes are steered
here; the script itself has no size option.  Off-TPU the kernel phase
runs the Pallas kernel in interpret mode and the engine the XLA twin;
the device check and the ``tpu_custom_call`` assertions stay in
``chip_smoke.main()`` and run only on the chip.
"""
import importlib.util
import os
import pathlib
import subprocess
import sys

import pytest

pytestmark = pytest.mark.tier1

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_device_check_refuses_cpu():
    cs = _chip_smoke()
    with pytest.raises(SystemExit, match="platform 'cpu'"):
        cs.check_device()


def test_kernel_phase_reduced():
    cs = _chip_smoke()
    # served GQA ratio (32/8) at narrow heads; 16 pages per row so the
    # 8-page tile and the whole-row tile are both candidates
    err, tiles = cs.kernel_phase(batch=8, n_q_heads=8, n_kv_heads=2,
                                 head_dim=32, page_size=16, max_len=256)
    assert 0.0 <= err <= cs.KERNEL_TOL
    assert tiles == [1, 2, 4, 8, 16]


def test_serve_phase_reduced():
    from repro.configs import reduced_config

    cs = _chip_smoke()
    cfg = reduced_config("granite-3-2b")
    engine, out = cs.serve_phase(cfg, n_requests=5, prompt_lens=(9, 40),
                                 new_tokens=6, n_slots=2, max_len=64)
    assert len(out) == 5
    assert all(len(t) == 6 for t in out.values())
    assert engine.stats.summary()["generated_tokens"] == 30
    # off-TPU the engine resolves the paged kernel to its XLA twin
    assert engine.paged_meta["impl"] == "xla"
    assert engine.paged_meta["key"].endswith("/cpu")


_FOUR = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import importlib.util
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
from repro.configs import reduced_config
# heads divisible by the 2-way model axis, so the KV pool shards on it
cfg = reduced_config("granite-3-2b", n_heads=8, n_kv_heads=2)
cs.four_chip_phase(cfg, n_requests=6, prompt_lens=(9, 30), new_tokens=5,
                   n_slots=4, max_len=64)
print("FOUR_OK")
"""


def test_four_chip_phase_on_four_cpu_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _FOUR,
                          str(ROOT / "chip_smoke.py")], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "FOUR_OK" in out.stdout, out.stdout[-2000:] + out.stderr[-4000:]
    assert "match token for token" in out.stdout
