"""chip_smoke.py between chip runs: the script itself refuses the CPU,
and its phase functions — importable, which is the only way past that
refusal — run here at a toy width so they cannot rot unnoticed."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402

TOY = chip_smoke.Widths(vocab=96, seq=32, dim=32, heads=2, ffn=64,
                        layers=1, batch=2, steps_per_epoch=3, epochs=2,
                        slots=2, prompts=(3, 5, 3), new_tokens=4,
                        anchors=1500)


def test_script_refuses_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300, cwd=_REPO)
    assert out.returncode != 0
    assert "'cpu'" in out.stderr and "needs a TPU" in out.stderr
    assert out.stdout.strip() == ""        # no result line


def test_result_line_has_the_contract_keys_only():
    line = json.loads(json.dumps(chip_smoke.result(jax.devices())))
    assert line == {"ok": True,
                    "device": {"platform": "cpu", "kind": "cpu",
                               "count": jax.device_count()}}


@pytest.mark.skipif(jax.device_count() < 2, reason="needs a 2-device mesh")
def test_phases_at_toy_width():
    """flash, train (over a data mesh, as --devices does), serve and nms
    end to end, with interpret-mode kernels."""
    flash = chip_smoke.check_flash(TOY)
    assert flash["mosaic_calls"] == 0      # interpreted here
    state, trained = chip_smoke.train(TOY, n_devices=2)
    assert trained["global_batch"] == 2 * TOY.batch
    assert trained["last_loss"] < trained["first_loss"]
    # the script serves what a one-chip run trained; here the mesh's
    # replicated params come back to one device first
    params = {k: np.asarray(v) for k, v in state[0].items()}
    served = chip_smoke.serve(TOY, params)
    assert served["tokens_served"] == 3 * TOY.new_tokens
    assert served["decode_programs"] == 1
    nms = chip_smoke.check_nms(TOY)
    assert 0 < nms["kept"] < TOY.anchors
