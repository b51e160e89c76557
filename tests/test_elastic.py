"""Elastic re-mesh: lose half the devices mid-run, reshard, continue.

Runs in a subprocess so ``--xla_force_host_platform_device_count=8`` can be
set before jax initializes (the main test process must keep 1 device).
Both subprocess tests carry the ``slow`` marker (registered in
pyproject.toml): deselect with ``pytest -m "not slow"``.
"""

import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import tempfile
import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_smoke
from repro.data import MarkovLMConfig, MarkovLMDataset, ShardedLoader
from repro.models.registry import build_model
from repro.optim import AdamW
from repro.parallel.sharding import default_rules
from repro.runtime import TrainConfig, Trainer

assert len(jax.devices()) == 8, jax.devices()


def make_mesh(n):
    # (data, model) over n devices, TP degree 2
    return jax.make_mesh((n // 2, 2), ("data", "model"),
                         devices=jax.devices()[:n],
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def session(ckpt_dir, n_devices, steps):
    cfg = get_smoke("stablelm-3b")
    model = build_model(cfg)
    mesh = make_mesh(n_devices)
    tr = Trainer(model, AdamW(learning_rate=1e-3), mesh,
                 TrainConfig(log_every=1),
                 ckpt=CheckpointManager(ckpt_dir, save_interval=5))
    loader = ShardedLoader(MarkovLMDataset(MarkovLMConfig(
        vocab_size=cfg.vocab_size, seq_len=16, batch_size=4)))
    _, hist = tr.fit(loader, steps)
    return tr, hist


with tempfile.TemporaryDirectory() as d:
    # phase 1: 8 devices (4x2 mesh)
    tr1, h1 = session(d, 8, 10)
    assert tr1.step == 10
    # "failure": only 4 devices survive -> 2x2 mesh, restore + reshard
    tr2, h2 = session(d, 4, 5)
    assert tr2.step == 15, tr2.step     # resumed from step-10 checkpoint
    losses = [h["loss"] for h in h1 + h2]
    assert all(np.isfinite(l) for l in losses)
    # training continued sensibly (loss in phase 2 not exploding)
    assert h2[-1]["loss"] < h1[0]["loss"] + 1.0
    print("ELASTIC_OK", tr2.step, f"{h1[0]['loss']:.3f}->{h2[-1]['loss']:.3f}")
"""

_DECODE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import tempfile
import jax
import numpy as np

from repro.configs import get_smoke
from repro.models.registry import build_model
from repro.runtime import greedy_decode_reference

assert len(jax.devices()) == 8, jax.devices()

MAX_NEW = 8
PROMPT = 13


def session(n_devices):
    # a fresh "process" after the re-mesh: new model object, params
    # rebuilt from the same seed, a cold compile cache, weights placed
    # on the surviving device set
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dev = jax.devices()[:n_devices][0]
    return model, jax.device_put(params, dev)


toks = (np.arange(PROMPT, dtype=np.int64) % 512).astype(np.int32)

# uninterrupted oracle, all 8 devices
model, params = session(8)
want = greedy_decode_reference(model, params, toks, MAX_NEW, b_kv=4)

# phase 1: decode 3 of 8 tokens on the full mesh, checkpoint the decode
# state (plain numpy arrays -> np.savez round-trip, like any checkpoint)
first, state = greedy_decode_reference(model, params, toks, 3, b_kv=4,
                                       reserve_tokens=MAX_NEW,
                                       return_state=True)
with tempfile.TemporaryDirectory() as d:
    path = os.path.join(d, "decode_state.npz")
    np.savez(path, **state)
    loaded = dict(np.load(path))

# phase 2: half the devices survive; a rebuilt session resumes the
# decode from the restored state and must land on the oracle's tokens
model2, params2 = session(4)
rest = greedy_decode_reference(model2, params2, toks, MAX_NEW - 3,
                               b_kv=4, state=loaded)
got = np.concatenate([first, rest])
assert np.array_equal(got, want), (got, want)
print("DECODE_RESUME_OK", got.tolist())
"""


def _run_subprocess(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))


@pytest.mark.slow
def test_elastic_remesh_subprocess():
    out = _run_subprocess(_SCRIPT)
    assert "ELASTIC_OK" in out.stdout, (out.stdout[-2000:],
                                        out.stderr[-2000:])


@pytest.mark.slow
def test_decode_state_resumes_after_remesh_subprocess():
    """Decode-state checkpointing across an elastic re-mesh: a decode
    split by a device loss — state serialized, session rebuilt on the
    surviving devices, decode resumed — must produce bitwise the tokens
    of the uninterrupted run (DESIGN.md §12)."""
    out = _run_subprocess(_DECODE_SCRIPT)
    assert "DECODE_RESUME_OK" in out.stdout, (out.stdout[-2000:],
                                              out.stderr[-2000:])
