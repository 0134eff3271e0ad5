"""``chip_smoke.py`` off the chip.

Two things can be checked without a TPU: that the script refuses to run
(non-zero exit, the platform they found named, no result printed), and that
``chip_smoke.py``'s phases — the same code the chip runs — go through end to
end at a tiny size on the CPU (on-chip-measurement guide §2, rehearsals 1
and 2).  The rehearsal steps around the script's TPU check and stubs what
only a chip reports; it proves control flow and the checks' plumbing, never
a device number.
"""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke as cs  # noqa: E402
from __graft_entry__ import _flagship_cfg  # noqa: E402


@pytest.mark.parametrize("script", ["chip_smoke.py"])
def test_refuses_to_run_without_a_tpu(script):
    proc = subprocess.run(
        [sys.executable, str(REPO / script)], capture_output=True, text=True,
        timeout=300, cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "Nothing was run" in proc.stderr
    for line in proc.stdout.splitlines():     # no result of any kind
        with pytest.raises(ValueError):
            json.loads(line)


@pytest.fixture
def tiny(monkeypatch, cpu_peak_row):
    """The flagship config cut to a size the CPU runs in seconds, with the
    script's sizes cut to match and the chip-only readings stubbed."""
    monkeypatch.setattr(cs, "SEQ", 64)
    monkeypatch.setattr(cs, "TRAIN_BATCH", 8)
    monkeypatch.setattr(cs, "FIRST_LOSS_BAND", (6.0, 6.7))   # ln 512 = 6.24
    monkeypatch.setattr(cs, "PROMPT_LENS", (3, 5, 9, 12, 17, 20, 25, 30))
    monkeypatch.setattr(cs, "NEW_TOKENS", (4, 8, 6, 4, 8, 6, 4, 8))
    monkeypatch.setattr(cs, "memory",
                        lambda devs, key="bytes_in_use": [1 for _ in devs])
    return dataclasses.replace(
        _flagship_cfg(), vocab_size=512, d_model=64, n_heads=4, n_layers=2,
        d_ff=128, max_len=64)


def test_train_phase_rehearsal(tiny):
    cs.phase_train(dataclasses.replace(tiny, causal=False), jax.devices())


def test_serve_phase_rehearsal(tiny):
    cs.phase_serve(dataclasses.replace(tiny, causal=True), jax.devices())


def test_chips4_phase_rehearsal(tiny):
    assert len(jax.devices()) >= 4
    cs.phase_chips4(dataclasses.replace(tiny, causal=False), jax.devices())


def test_serve_check_catches_a_wrong_token(tiny):
    """The tie band must not be so wide that a wrong token passes: flip one
    generated token of a served answer and the plain forward disowns it."""
    import numpy as np

    from deeplearning4j_tpu.models.transformer import TransformerLM
    from deeplearning4j_tpu.serving import ServingConfig

    cfg = dataclasses.replace(tiny, causal=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(cs.SEED))
    requests = cs.make_requests(cfg)
    seqs = cs.serve_mode("dense", ServingConfig(), model, params, requests)
    p_len = [len(p) for p, _ in requests]
    seqs[3][p_len[3] + 1] = (seqs[3][p_len[3] + 1] + 7) % cfg.vocab_size
    m = np.asarray(cs.plain_margins(cfg)(params, cs.pad_sequences(cfg, seqs)))
    rows = [m[r, n - 1:] for r, n in enumerate(p_len)]
    with pytest.raises(cs.SmokeFailure):
        cs.judge("dense", requests, rows, cs.EPS_FLOAT)
